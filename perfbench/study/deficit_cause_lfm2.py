#!/usr/bin/env python3
"""Where ``lfm2_agents_3k``'s largest ``max_logit_deficit`` comes from: the
cell's own run, then the harness's measure over MANY of its completed
requests (``serve.check`` samples 8), and the requests that read worst
replayed layer by layer beside the reference.

    python3 perfbench/study/deficit_cause_lfm2.py --seed 4200000301 \
        --out chiprun_out/p42r1/cause.jsonl

The sound program's reading spreads 60x from run to run (0.0005-0.0279 at a
final gain of 0.005: ``runs_pr42.jsonl``) where bfloat16 rounding alone
would give every run about the same. The hypothesis this script tests: a
**router flip**. The router keeps 4 of 64 by ``s + b``; where the 4th and
5th lie closer than the stream's bfloat16 error moves them, the program
and the float32 reference keep different experts, a quarter of that
layer's expert output is another expert's, and the error no longer has the
size of a rounding. It is carried to the later routers, which flip more
easily in turn.

Three records (a line each in ``--out``):

- ``requests``: for ``--requests`` completed requests of the window, the
  largest deficit over the answer's positions, at which offset, and how
  many positions read over 0 (the emitted token is not the reference's
  best there); the 8 the harness's own sample would have taken are marked.
- ``replay`` (one a replayed request, the ``--worst`` largest whose rows
  fit the longest bucket): the request through ``compare_jamba.Runner``
  (prefill, then its own answer fed back a token a step, every layer's
  output carried out) against the reference on the same tokens: per (answer
  position, layer) the increment's error over the reference's increment,
  and per (position, expert layer) the reference's margin between the 4th
  and the 5th biased score. A **flip** is an expert layer whose increment
  errs by more than ``--flip`` (default 0.05: rounding reads 0.005-0.006 in
  every layer, ``compare_lfm2.py``). Given: the flips' margins beside all
  positions' margins; the logits' error on the reference's 16 best tokens
  by the number of layers flipped at the position; the five positions of
  the largest deficit with their flipped layers.
- ``summary``.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

BLOCK = 256


def reference_fn(cfg, p, steps):
    """Jitted ``(params, ids [1, pad]) -> (logits of positions p - 1 .. p +
    steps - 2 [steps, vocab], the embedded input and every layer's output
    at positions p .. p + steps - 1 [steps, layers + 1, h], the margin
    between the 4th and 5th biased score of every expert layer there
    [steps, expert layers])``: ``families/lfm2.hidden`` itself, its
    ``_experts`` watched."""
    import jax
    import jax.numpy as jnp
    from perfbench.families import lfm2

    @jax.jit
    def run(params, ids):
        states, margins, real = [], [], lfm2._experts

        def watched(u, router, bias, w13, w2, top_k, scale):
            s = jax.nn.sigmoid(u @ lfm2._f32(router)) \
                + lfm2._f32(bias)[None, :]
            top = jax.lax.top_k(s, top_k + 1)[0]
            margins.append(top[:, top_k - 1] - top[:, top_k])
            return real(u, router, bias, w13, w2, top_k, scale)
        lfm2._experts = watched
        try:
            h = lfm2.hidden(params, ids, cfg, collect=states)[0]
        finally:
            lfm2._experts = real
        with jax.default_matmul_precision("highest"):
            logits = jax.lax.dynamic_slice_in_dim(h, p - 1, steps, 0) \
                @ lfm2.head(params, cfg)
        emb = jnp.asarray(params["model.embed.weight"][ids[0]], jnp.float32)
        layers = jnp.stack([emb] + [s[0] for s in states], axis=1)
        return (logits, jax.lax.dynamic_slice_in_dim(layers, p, steps, 0),
                jax.lax.dynamic_slice_in_dim(
                    jnp.stack(margins, axis=1), p, steps, 0))
    return run


def replay(runner, family_cfg, params, seq, p, n, bucket, watch, flip):
    """One request's answer through the program again, beside the
    reference -> the record's numbers."""
    import jax.numpy as jnp
    import numpy as np
    from perfbench.families import lfm2
    steps = n           # the last step's logits score a token never fed
    got = runner.run([seq], [p], bucket, steps, watch, greedy=False)[0]
    pad = -(-len(seq) // BLOCK) * BLOCK
    ids = np.zeros((1, pad), np.int32)
    ids[0, :len(seq)] = seq
    lg, layers, margins = (np.asarray(a) for a in reference_fn(
        family_cfg, p, steps)(params, jnp.asarray(ids)))
    emitted = np.asarray(seq[p:p + steps])
    rows = np.arange(steps)
    deficit = lg.max(-1) - lg[rows, emitted]
    again = np.argmax(got["logits"], axis=-1)
    inc_p = got["layers"][:, 1:] - got["layers"][:, :-1]
    inc_r = layers[:, 1:] - layers[:, :-1]
    # decode step k is fed position p + k and its logits score it: the
    # increments of step k stand beside deficit k + 1
    ratio = np.linalg.norm(inc_p - inc_r, axis=-1) \
        / np.linalg.norm(inc_r, axis=-1)               # [steps, layers]
    sparse = [i for i, (_, dense) in enumerate(lfm2.layer_plan(family_cfg))
              if not dense]
    flips = ratio[:, sparse] > flip                    # [steps, experts]
    best = np.argsort(lg, axis=-1)[:, -16:]
    top_err = np.abs(np.take_along_axis(got["logits"], best, -1)
                     - np.take_along_axis(lg, best, -1)).max(-1)
    # position k + 1's logits come from decode step k
    flipped = np.concatenate([[0], flips.sum(-1)[:-1]])
    by_flips = {}
    for c in sorted(set(flipped.tolist())):
        at = flipped == c
        by_flips[str(c)] = {
            "positions": int(at.sum()),
            "top16_logit_err_median": float(np.median(top_err[at])),
            "top16_logit_err_max": float(top_err[at].max()),
            "deficit_max": float(deficit[at].max())}
    worst = []
    for k in np.argsort(deficit)[::-1][:5]:
        k = int(k)
        step = k - 1
        worst.append({
            "offset": k, "deficit": float(deficit[k]),
            "top16_logit_err": float(top_err[k]),
            "replay_emits_the_engines_token": bool(again[k] == emitted[k]),
            "layers_flipped": [] if step < 0 else
            [sparse[j] for j in np.flatnonzero(flips[step])],
            "their_margins": [] if step < 0 else
            [float(margins[step, j]) for j in np.flatnonzero(flips[step])],
            "inc_err_by_layer": [] if step < 0 else
            [round(float(r), 4) for r in ratio[step]]})
    first = np.array([np.flatnonzero(f)[0] if f.any() else -1
                      for f in flips])
    return {
        "steps": steps, "deficit_max": float(deficit.max()),
        "replay_agrees_with_engine_tokens": int((again == emitted).sum()),
        "inc_err_median_by_layer":
            [round(float(r), 4) for r in np.median(ratio, axis=0)],
        "flips": int(flips.sum()),
        "positions_with_a_flip": int(flips.any(-1).sum()),
        "flips_by_expert_layer": flips.sum(0).tolist(),
        # after a first flip the later routers read a stream that is off
        "flips_a_position_given_one": float(
            flips.sum(-1)[flips.any(-1)].mean()) if flips.any() else 0.0,
        "first_flip_layer_counts": np.bincount(
            first[first >= 0], minlength=len(sparse)).tolist(),
        "margin_median_all": float(np.median(margins)),
        "margin_median_of_flips": float(np.median(margins[flips]))
        if flips.any() else None,
        "margin_p90_of_flips": float(np.quantile(margins[flips], 0.9))
        if flips.any() else None,
        "share_of_margins_under_flips_p90": float(np.mean(
            margins < np.quantile(margins[flips], 0.9)))
        if flips.any() else None,
        "by_layers_flipped": by_flips, "worst_positions": worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="lfm2_agents_3k")
    ap.add_argument("--seed", type=int, default=4200000301)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--gain", type=float, default=None,
                    help="the final norm's gain's std (the file's if not "
                    "given)")
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--worst", type=int, default=2)
    ap.add_argument("--flip", type=float, default=0.05)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal on the toy twin; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, serve, traffic as T
    from compare_lfm2 import runner_of
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("deficit_cause_lfm2 needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    folder = "traffic"
    if args.allow_cpu:
        folder = "rehearsal"
        cfg = harness.load_json(ROOT, "perfbench", "rehearsal",
                                families.name_of(cfg) + "-tiny.json")
    traffic = harness.load_json(ROOT, "perfbench", folder,
                                cell["traffic"] + ".json")
    if args.gain is not None:
        cfg = dict(cfg, final_norm_init=[0.0, args.gain])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def note(rec):
        rec = dict(rec, tool="deficit_cause_lfm2.py", workload=args.workload,
                   config=cfg["name"], seed=args.seed,
                   final_norm_init=cfg.get("final_norm_init"),
                   flip_over=args.flip, device=dev.device_kind)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    t = time.time()
    model, engine = serve.build_engine(cfg, args.seed)
    vocab = int(cfg["vocab_size"])
    serve.warm(engine, traffic, vocab)
    tracing = serve.Tracing(False, harness.OUT, args.seconds,
                            counters=lambda: serve.program_counters(engine))
    _, _, counts, _, fol, (lo, hi) = serve.run_closed(
        engine, traffic, args.seed, args.seconds, vocab, tracing)
    done = [s for s in fol.done if s.req.state == "done"
            and s.finished is not None and lo <= s.finished <= hi]
    # the 8 the harness's check would have read
    rng = np.random.default_rng([int(args.seed), 9])
    picks = set(int(j) for j in rng.choice(
        len(done), size=min(serve.SAMPLE_REQUESTS, len(done)),
        replace=False))
    order = sorted(picks) + [j for j in range(len(done)) if j not in picks]
    order = order[:max(args.requests, len(picks))]
    family = families.load(cfg)
    deficits = serve.deficits_fn(family, cfg)
    params = {n: p.value for n, p in model.named_parameters()}
    pad = int(cfg["engine"]["max_len"])
    rows = []
    for j in order:
        s = done[j]
        seq = list(s.arrival.prompt) + list(s.req.tokens)
        p, n = len(s.arrival.prompt), len(s.req.tokens)
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        nxt = np.zeros(pad, np.int32)
        nxt[:len(seq) - 1] = seq[1:]
        d = np.asarray(deficits(params, jnp.asarray(ids),
                                jnp.asarray(nxt)))[p - 1:p + n - 1]
        rows.append({"j": j, "prompt": p, "answer": n,
                     "in_the_checks_sample": j in picks,
                     "deficit_max": float(d.max()),
                     "at_offset": int(d.argmax()),
                     "positions_over_0": int((d > 0).sum()),
                     "positions_over_a_fifth_of_max":
                         int((d > 0.2 * d.max()).sum()) if d.max() > 0
                         else 0})
    per = np.array([r["deficit_max"] for r in rows])
    note({"record": "requests", "completed_in_window": len(done),
          "read": len(rows),
          "the_checks_own_reading": max(
              r["deficit_max"] for r in rows if r["in_the_checks_sample"]),
          "deficit_max_quantiles_over_requests": {
              q: float(np.quantile(per, float(q)))
              for q in ("0.5", "0.9", "0.99", "1.0")},
          "requests": rows, "seconds": round(time.time() - t, 1)})
    # the engine's pools and programs go before the replay's are built
    buckets = cfg["engine"]["buckets"]
    fits = [r for r in rows if r["prompt"] + r["answer"] <= max(buckets)]
    chosen = sorted(fits, key=lambda r: -r["deficit_max"])[:args.worst]
    kept = [(r, list(done[r["j"]].arrival.prompt)
             + list(done[r["j"]].req.tokens)) for r in chosen]
    for s in list(fol.live):
        engine.cancel(s.req.id)
    del engine, fol, done, tracing, deficits
    gc.collect()
    runner = runner_of(model, cfg)
    watch = model.cfg.layers_of("conv")[0]
    replays = []
    for r, seq in kept:
        t = time.time()
        rec = replay(runner, cfg, params, seq, r["prompt"], r["answer"],
                     T.bucket_for(r["prompt"], buckets), watch, args.flip)
        replays.append(rec)
        note({"record": "replay", "prompt": r["prompt"],
              "answer": r["answer"], "engine_deficit_max": r["deficit_max"],
              "engine_at_offset": r["at_offset"], **rec,
              "seconds": round(time.time() - t, 1)})
    note({"record": "summary",
          "the_checks_own_reading": max(
              r["deficit_max"] for r in rows if r["in_the_checks_sample"]),
          "largest_over_all_read": float(per.max()),
          "replayed": len(replays),
          "worst_positions_with_a_flip": sum(
              bool(w["layers_flipped"]) for rec in replays
              for w in rec["worst_positions"][:1]),
          "completed_in_window": counts.get("completed_in_window")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
