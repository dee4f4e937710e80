#!/usr/bin/env python3
"""The expert layer's combine in four forms, timed alone at a cell's shape,
and the counters of a few steps of the cell (PR 36).

    python3 perfbench/study/combine_forms.py --seed 3600000001 \\
        --out chiprun_out/p36/forms.jsonl
    python3 perfbench/study/combine_forms.py --workload laguna_pretrain_8k \\
        --steps 12 --seed 3600000002 --out chiprun_out/p36/counters.jsonl

The first: ``t`` tokens (16384) of width ``h`` (2048), 8 choices of 256
experts a token drawn uniformly without replacement, experts 0-31 held,
the sorted buffer (36,864 rows of bfloat16) filled with random rows; ms a
call of

  (i)   ``decoder_ops._gather_sum``: one gather of ``[t, h]`` a slot;
  (ii)  ``decoder_ops._held_sum``: the tokens ordered by their number of
        held choices, rank ``j`` gathered for a static prefix, one gather
        back into token order (``order``: its integer work, once a route);
  (iii) rank 0 for every token in token order, ranks >= 1 summed over
        their prefixes and added back with one row scatter-add;
  (iv)  one scatter-add of the weighted sorted rows by ``route["tok"]``;

each checked against (i) ((ii) by its bytes, (iii) and (iv), which add in
another order, by the largest difference), and a whole expert layer's
forward and backward through each arm of the program's one branch point
(``layer``: the fast buffer with the prefix form, with the k-slot form,
and the chunked path, on the same uniform routing; the last two by
overriding ``_plan``'s choice HERE, the program has no such option).

The second builds the cell as the runner does and reads
``model.moe_stats()`` after every step: ``combine_rows_share`` and
``fast_path`` a layer beside what ``moe_counters.py`` reads (the training
runner has no counter channel: PERF.md section 7). One record is appended
to ``--out``. ``--rehearsal`` runs either at a toy size on the CPU, for
the code path only; its times are never a result.
"""

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def timed(fn, args, calls):
    """ms a call of a jitted ``fn`` (compiled and run once before)."""
    import jax
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / calls, out


def forms(args, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import decoder_ops as dops
    t, h, f, k, groups, experts, tm = (
        (256, 64, 32, 4, 4, 32, 8) if args.rehearsal
        else (args.tokens, 2048, 512, 8, 32, 256, 128))
    dt = jnp.float32 if args.rehearsal else jnp.bfloat16
    tiles, chunks, sizes = dops._plan(t, k, groups, experts, tm)
    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    ks = jax.random.split(key, 8)
    idx = jax.vmap(lambda kk: jax.random.permutation(kk, experts)[:k])(
        jax.random.split(ks[0], t)).astype(jnp.int32)
    valid = idx < groups
    weight = (jax.random.uniform(ks[1], (t, k)) + 0.5).astype(dt)
    route = dict(jax.jit(lambda: dops._route(idx, valid, groups, tm,
                                             tiles))(), valid=valid)
    rows = jax.random.normal(ks[2], (tiles * tm, h)).astype(dt)
    order_fn = jax.jit(lambda v, p: dops._held_order(v, p, sizes))
    ms_order, order = timed(order_fn, (valid, route["pos"]), args.calls)
    ms_route, _ = timed(jax.jit(
        lambda i, v: dops._route(i, v, groups, tm, tiles)), (idx, valid),
        args.calls)
    held = np.asarray(jnp.sum(valid, axis=1))

    # route and order go in as arguments, as the program's are computed
    # values: closed over, XLA folds the masks and the forms' sums round
    # differently on the CPU
    def slots(rows, weight, route, order):
        return dops._gather_sum(rows, route["pos"], route["valid"],
                                weight).astype(dt)

    def prefix(rows, weight, route, order):
        return dops._held_sum(rows, order, sizes, weight, dt)

    def rank0_scatter(rows, weight, route, order):
        w = jnp.select([order["sel"][:, :, s] for s in range(k)],
                       [weight[:, s, None] for s in range(k)], 0) \
            .astype(jnp.float32)
        first0 = order["sel"][:, 0, :]
        first = jnp.take(rows, jnp.sum(jnp.where(first0, route["pos"], 0),
                                       axis=1), axis=0).astype(jnp.float32)
        first = jnp.where(jnp.any(first0, axis=1)[:, None],
                          first * w[:, 0, None], 0.0)
        n1 = sizes[1]
        rest = jnp.zeros((n1, rows.shape[1]), jnp.float32)
        for j in range(1, k):
            n = sizes[j]
            if n:
                r = jnp.take(rows, order["pos"][:n, j], axis=0) \
                    .astype(jnp.float32) \
                    * jnp.take(w[:, j], order["perm"][:n])[:, None]
                rest = rest + jnp.pad(
                    jnp.where(j < order["held"][:n, None], r, 0.0),
                    ((0, n1 - n), (0, 0)))
        return first.at[order["perm"][:n1]].add(
            rest, unique_indices=True).astype(dt)

    def sorted_scatter(rows, weight, route, order):
        w = jnp.where(route["live"], weight.reshape(-1)[route["pair"]]
                      .astype(jnp.float32), 0.0)
        return jnp.zeros((t + 1, rows.shape[1]), jnp.float32) \
            .at[route["tok"]].add(rows.astype(jnp.float32) * w[:, None])[:t] \
            .astype(dt)

    rec = {"tag": "combine_forms", "tokens": t, "hidden": h, "top_k": k,
           "held": groups, "experts": experts, "buffer_rows": tiles * tm,
           "chunks": chunks, "prefix_sizes": list(sizes),
           "rows_gathered_over_t": {"i": k, "ii": (sum(sizes) + t) / t},
           "held_pairs": int(held.sum()),
           "tokens_with_more_than_j_held": [int((held > j).sum())
                                            for j in range(k)],
           "prefixes_fit": bool(order["fits"]),
           "ms": {"route": ms_route, "order": ms_order}}
    want = None
    for name, fn in (("i_k_slots", slots), ("ii_prefix", prefix),
                     ("iii_rank0_scatter", rank0_scatter),
                     ("iv_sorted_scatter", sorted_scatter)):
        ms, out = timed(jax.jit(fn), (rows, weight, route, order),
                        args.calls)
        rec["ms"][name] = ms
        out = np.asarray(out.astype(jnp.float32))
        if want is None:
            want = out
        rec.setdefault("against_i", {})[name] = {
            "bytes_equal": out.tobytes() == want.tobytes(),
            "max_abs_diff": float(np.abs(out - want).max())}
    rec["ms"]["ii_prefix_dx"], _ = timed(jax.jit(
        lambda rows, order: dops._held_sum(rows, order, sizes, None, dt)),
        (rows, order), args.calls)
    rec["ms"]["i_k_slots_dx"], _ = timed(jax.jit(
        lambda rows, route: dops._gather_sum(
            rows, route["pos"], route["valid"]).astype(dt)),
        (rows, route), args.calls)

    # a whole layer, forward and backward, through each arm
    x = jax.random.normal(ks[3], (t, h)).astype(dt)
    w13 = (0.02 * jax.random.normal(ks[4], (groups, h, 2 * f))).astype(dt)
    w2 = (0.02 * jax.random.normal(ks[5], (groups, f, h))).astype(dt)
    ct = jax.random.normal(ks[6], (t, h)).astype(dt)
    plan = dops._plan
    arms = {"prefix": plan,
            "k_slots": lambda *a: plan(*a)[:2] + ((),),
            # a fast buffer of one tile an expert: nothing fits it
            "chunks": lambda *a: (groups + 1, plan(*a)[1], ())}
    rec["layer"] = {}
    for name, choice in arms.items():
        dops._plan = choice
        jax.clear_caches()      # the layer's own jits know the shapes only
        try:
            def layer(x, weight, w13, w2):
                def loss(x, weight, w13, w2):
                    out, stats = dops.moe_experts(x, weight, idx, w13, w2,
                                                  0, experts, tm)
                    return jnp.sum(out.astype(jnp.float32)
                                   * ct.astype(jnp.float32)), (out, stats)
                (_, (out, stats)), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3), has_aux=True)(
                        x, weight, w13, w2)
                return out, stats, grads
            ms, (out, stats, grads) = timed(
                jax.jit(layer), (x, weight, w13, w2), args.calls)
        finally:
            dops._plan = plan
            jax.clear_caches()
        got = [np.asarray(a.astype(jnp.float32))
               for a in (out, *grads)]
        if name == "prefix":
            base = got
        rec["layer"][name] = {
            "ms_fwd_bwd": ms, "stats": [float(v) for v in stats],
            "bytes_equal_to_prefix": [a.tobytes() == b.tobytes()
                                      for a, b in zip(got, base)],
            "max_abs_diff_to_prefix": [float(np.abs(a - b).max())
                                       for a, b in zip(got, base)]}
    return rec


def counters(args, dev):
    from paddle_tpu import monitor
    from perfbench import families, run as harness, train
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(ROOT, entry["file"])
    folder = "traffic"
    if args.rehearsal:
        cfg = harness.load_json(ROOT, "perfbench", "rehearsal",
                                families.name_of(cfg) + "-tiny.json")
        folder = "rehearsal"
    job = harness.load_json(ROOT, "perfbench", folder,
                            cell["traffic"] + ".json")
    model, make_step, mesh = train.build(cfg, job, args.seed)
    step = make_step()
    make, _ = train.batch_maker(args.seed, int(job["batch_per_chip"]),
                                int(job["seq"]), int(cfg["vocab_size"]), mesh)
    rows, losses, walls = [], [], []
    for i in range(args.steps):
        t = time.perf_counter()
        losses.append(train.fetch(step(*make(i))))
        walls.append(time.perf_counter() - t)
        rows.append(model.moe_stats())
    layers = sorted(rows[0])

    def over(key, fn):
        return {str(layer): fn([r[layer][key] for r in rows])
                for layer in layers}
    return {"tag": "combine_counters", "workload": args.workload,
            "steps": args.steps, "loss_first": losses[0],
            "loss_last": losses[-1],
            "step_ms_median_after_2": 1e3 * statistics.median(walls[2:]),
            "combine_rows_share_min": over("combine_rows_share", min),
            "combine_rows_share_max": over("combine_rows_share", max),
            "assignments_min": over("assignments", min),
            "assignments_max": over("assignments", max),
            "max_load_over_mean_max": over("max_load_over_mean", max),
            "dropped_pairs_total": sum(r[layer]["dropped_pairs"]
                                       for r in rows for layer in layers),
            "layer_steps_off_the_fast_path": sum(
                not r[layer]["fast_path"] for r in rows for layer in layers),
            "monitor": monitor.stats_with_prefix(
                "STAT_moe_combine_rows_permille")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="",
                    help="a training cell: read its counters instead")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=3600000001)
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--call", default="", help="kept in the record")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="a toy size on the CPU; never a result")
    args = ap.parse_args(argv)
    import jax
    from paddle_tpu.utils import chip
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        raise SystemExit("combine_forms needs the TPU (or --rehearsal)")
    chip.enable_compile_cache()
    rec = (counters if args.workload else forms)(args, dev)
    rec.update(seed=args.seed, call=args.call, rehearsal=args.rehearsal,
               tool="combine_forms.py",
               device={"platform": dev.platform, "kind": dev.device_kind})
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)
    return 0 if rec.get("dropped_pairs_total", 0) == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
