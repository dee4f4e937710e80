#!/usr/bin/env python3
"""The dots.vlm1 program against its plain reference at the published
widths and the cell's lengths, and the planted faults the comparison must
see.

    python3 perfbench/study/compare_dotsvlm.py --seed 4900000301 \
        --out chiprun_out/p49c2/compare.jsonl

Prefill then decode through the serving cache (``compare_mellum.Runner``:
``BlockKVCache.for_model`` from the model's seam, here the latent kind's
ONE array a layer; the model's serving forward jitted as the engine's
entries jit it, every layer's output carried out) against the reference's
full forward (``families/dotsvlm.forward``: float32 at ``highest``, the
materialised form only) on the same tokens: the prompts (``--prompts``, in
the 6144- and 8192-row buckets), then ``--answer`` tokens decoded greedily
by all rows together through the absorbed read. The clean run decodes
greedily and every faulted run replays its tokens, so all are read against
one reference. Read:

- ``deficit_max``: the harness's own measure (``serve.check``), limit 0.05;
- ``logit_diff_max`` / ``logit_diff_p50``: |program - reference| logits
  over the answer's positions (reported, no limit);
- ``inc_median_prefill`` / ``inc_median_decode``: per layer and row,
  |program's increment to the stream - reference's| / |reference's|, the
  median over rows, the largest over layers, over the prompts' rows and
  the decoded rows apart. A median: a row whose router chose another
  expert than the float32 reference's at a near tie (``inc_large``: the
  share of rows whose increment errs by over 0.1, by layer) does not move
  it, a fault of every row does;
- ``inc_large_share`` / ``inc_p90``: of that same error a row, the share
  of rows over 0.1 and the 90th centile, the largest over layers: what a
  fault of SOME rows moves (an expert left out: the rows that chose it);
- ``pool_latent_median``: what layer 0's latent pool holds when the
  requests are done, read back through the block table, against the
  reference's ``(RMSNorm(c), rot(k_r))`` of those positions: relative
  error a row, the median.

``--embed-scales`` reads the clean program at several scales of the
embedding (the rows multiplied in place; the layers' weights as drawn):
what an expert's flip at a near tie costs in logits follows the layers'
share of the stream (PERF.md section 6, PR 49).

Faults, those of ``tests/test_dotsvlm_faults.py``, planted one at a time.
**What the harness's own number has to see** (``HARNESS_SEES``: each must
read ``deficit_max`` over the harness's 0.05, the limit that decides the
cell's ``correct``): the latent attention's faults ``rotated_key_dropped``,
``latent_norm_skipped``, ``no_mscale``; ``shared_expert_left_out``; and the
control of precision, the nearest below the configuration's bfloat16
(``compare_lfm2.fp8_mantissa``: float8_e4m3's 3 bits of mantissa):
``fp8_latent`` (the cache's rows rounded before they are written and read:
an fp8 latent cache, which this configuration does not state). **What a
maximum over positions cannot see at any scale** (``ROUTED``:
``bias_in_the_weights``, ``plain_top_k``, ``bias_dropped``,
``an_expert_left_out``): each moves SOME rows by one or two held experts'
terms, which is what a router flip at a near tie moves the sound program's
rows by (the configuration's ``assumed.embed_init_std`` has the
arithmetic), so they are read and reported, and held by the CPU tests at
float32 and by this script's readings at the scales where an expert layer
was most of a layer's increment (calls 4, 7 and 8 in ``runs_pr49.jsonl``).
``bfloat16_scores`` (both reads' scores rounded before the softmax) is of
the program's own precision: reported, ``UNSEEN``. Exit code 0 when the
clean program passes every limit and every fault of ``HARNESS_SEES`` that
was run reads ``deficit_max`` over its limit.
"""

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

#: limits the CLEAN program is held to at the configuration's scales, and
#: ``deficit_max`` the one every fault of ``HARNESS_SEES`` must pass:
#:
#: - ``deficit_max`` 0.05, the harness's own limit (``serve.LOGIT_TOLERANCE``):
#:   at the configuration's scales (my chip run, PR 49, call 10, seed
#:   4900001007, 500 positions) the clean program 0.0075, float8 rows of
#:   the cache 0.111, the rotated key dropped 1.75; the cell's own check,
#:   4400 positions a run, 0.0140-0.0214 over six runs.
#: - ``inc_median_prefill`` 0.02 / ``inc_median_decode`` 0.03: bfloat16
#:   rounding of every matmul's input reads 0.0146 / 0.0157 of a layer's
#:   increment now that a peaked softmax's read is most of it (0.0044 /
#:   0.0042 while an MLP was); float8 rows 0.115 / 0.120, the key dropped
#:   0.99 / 0.98.
#: - ``pool_latent_median`` 0.01: a bfloat16 pool reads 0.0023; float8's
#:   mantissa 0.027, the key dropped or the norm skipped 0.5-0.6.
#:
#: ``inc_large_share`` / ``inc_p90`` are reported without a limit: they
#: told an expert left out (0.095-0.17 of rows over ``LARGE``), a plain top
#: k (0.097) and the bias dropped (0.28) from the clean program (0.0008-
#: 0.0014) while an expert layer was most of a layer's increment
#: (``embed_init_std`` 8 and 16 with W_o as drawn: calls 4, 7, 8); with
#: the latent attention a third or more of the stream, as the
#: configuration now has it so that ``correct`` sees ITS faults, a held
#: expert's term is a few percent of a layer's increment and they see
#: nothing.
TOLERANCE = {"deficit_max": 0.05,
             "inc_median_prefill": 0.02, "inc_median_decode": 0.03,
             "pool_latent_median": 0.01}
HARNESS_SEES = ("rotated_key_dropped", "latent_norm_skipped", "no_mscale",
                "shared_expert_left_out", "fp8_latent")
ROUTED = ("bias_in_the_weights", "plain_top_k", "bias_dropped",
          "an_expert_left_out")
FAULTS = HARNESS_SEES + ROUTED
#: read and reported, of the program's own precision: both reads' scores
#: rounded to bfloat16 before the softmax (the matmul inputs are bfloat16
#: already and a softmax over thousands of keys averages the rounding
#: out); ``tests/test_dotsvlm_faults.py`` holds it at float32
UNSEEN = ("bfloat16_scores",)
LARGE = 0.1


def router_with(bias_in_weights=False, plain=False, no_bias=False):
    """The router with one mechanism wrong (``tests/
    test_dotsvlm_faults.py``'s)."""
    import jax
    import jax.numpy as jnp
    from perfbench.families import dotsvlm as family

    def router(ctx, ins, attrs):
        x, w, b = ins["X"][0], ins["W"][0], ins["Bias"][0]
        s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                      w.astype(jnp.float32),
                                      precision=jax.lax.Precision.HIGHEST))
        k = int(attrs["top_k"])
        chosen_by = jnp.zeros_like(b) if no_bias else b
        if plain:
            idx = jax.lax.top_k(s + chosen_by, k)[1]
        else:
            idx, _ = family.grouped_choice(
                s, chosen_by, int(attrs["n_group"]),
                int(attrs["topk_group"]), k)
        top = jnp.take_along_axis(s + b if bias_in_weights else s, idx,
                                  axis=-1)
        weight = float(attrs["scale"]) * top \
            / jnp.sum(top, axis=-1, keepdims=True)
        return {"TopkIdx": [idx.astype(jnp.int32)], "TopkWeight": [weight]}
    return router


def _rounded_scores(q_n, q_r, k_n, k_r, scale):
    import jax.numpy as jnp
    lg = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r,
                       preferred_element_type=jnp.float32)) * scale
    return lg.astype(jnp.bfloat16).astype(jnp.float32)


def prompt_with_bfloat16_scores(q_n, q_r, k_n, k_r, v, *, scale, live=None):
    """The materialised read in plain XLA, a block of queries at a time,
    its scores rounded to bfloat16 before the softmax."""
    import jax
    import jax.numpy as jnp
    s = q_n.shape[2]
    block = 128 if s % 128 == 0 else s
    col = jnp.arange(s)[None, :]

    def one(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_n, lo, block, axis=2)
        qr = jax.lax.dynamic_slice_in_dim(q_r, lo, block, axis=2)
        lg = _rounded_scores(qn, qr, k_n, k_r, scale)
        seen = col <= lo + jnp.arange(block)[:, None]
        p = jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(v.dtype)
    out = jax.lax.map(one, jnp.arange(0, s, block))     # [n, b, h, block, d]
    return out.transpose(1, 2, 0, 3, 4).reshape(q_n.shape[:3] + v.shape[3:])


def decode_with_bfloat16_scores(q_lat, q_rope, pool, tables, pos, *, scale):
    import jax
    import jax.numpy as jnp
    r = q_lat.shape[-1]
    g = pool[tables]                                        # [b, T, w, bs]
    b, T, w, bs = g.shape
    g = g.transpose(0, 1, 3, 2).reshape(b, T * bs, w)
    dt = pool.dtype
    lg = _rounded_scores(q_lat.astype(dt)[:, :, None],
                         q_rope.astype(dt)[:, :, None],
                         g[:, None, :, :r], g[..., r:], scale)[:, :, 0]
    seen = jnp.arange(T * bs)[None, None] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), -1)
    return jnp.einsum("bhk,bkr->bhr", p.astype(dt), g[..., :r],
                      preferred_element_type=jnp.float32)


def inject(model, fault):
    """Break the program in one place -> a function that repairs it."""
    import jax.numpy as jnp
    from compare_lfm2 import fp8_mantissa
    from paddle_tpu.models import dotsvlm as P, laguna as L
    attention = P.LatentAttention
    saved = dict(rotate=attention._rotate, rms=P._rms, router=L._moe_router,
                 prompt=P.mla_prompt_attention, paged=P.mla_paged_attention,
                 latents=attention.latents_of)
    sparse = model.model.layers[model.cfg.first_k_dense_replace]
    held = {}

    def repair():
        attention._rotate, P._rms = saved["rotate"], saved["rms"]
        L._moe_router = saved["router"]
        P.mla_prompt_attention = saved["prompt"]
        P.mla_paged_attention = saved["paged"]
        attention.latents_of = saved["latents"]
        for blk in model.model.layers:
            blk.attn.scale = held.get("scale", blk.attn.scale)
        for param, value in held.get("weights", ()):
            param.value = value
    if fault == "rotated_key_dropped":
        attention._rotate = lambda self, x, rows: jnp.zeros_like(x) \
            if x.ndim == 3 else saved["rotate"](self, x, rows)
    elif fault == "latent_norm_skipped":
        rank = model.cfg.kv_lora_rank
        P._rms = lambda x, norm: x if norm.weight.value.shape[0] == rank \
            else saved["rms"](x, norm)
    elif fault == "no_mscale":
        held["scale"] = sparse.attn.scale
        for blk in model.model.layers:
            blk.attn.scale = 1.0 / math.sqrt(model.cfg.head_dim)
    elif fault == "bias_in_the_weights":
        L._moe_router = router_with(bias_in_weights=True)
    elif fault == "plain_top_k":
        L._moe_router = router_with(plain=True)
    elif fault == "bias_dropped":
        L._moe_router = router_with(no_bias=True)
    elif fault == "an_expert_left_out":
        # in every expert layer, the held expert its bias favours most
        held["weights"] = []
        for blk in model.model.layers:
            if blk.sparse:
                w = blk.moe.experts_down
                e = int(jnp.argmax(blk.moe.expert_bias.value[:w.value.shape[0]]))
                held["weights"].append((w, w.value))
                w.value = w.value.at[e].set(0.0)
    elif fault == "shared_expert_left_out":
        held["weights"] = []
        for blk in model.model.layers:
            if blk.sparse:
                w = blk.moe.shared.down.weight
                held["weights"].append((w, w.value))
                w.value = jnp.zeros_like(w.value)
    elif fault == "bfloat16_scores":
        P.mla_prompt_attention = prompt_with_bfloat16_scores
        P.mla_paged_attention = decode_with_bfloat16_scores
    elif fault == "fp8_latent":
        def latents_of(self, u, rows):
            c_q, kept = saved["latents"](self, u, rows)
            return c_q, fp8_mantissa(kept)
        attention.latents_of = latents_of
    else:
        raise KeyError(fault)
    return repair


def compare(got, want, n_prompt):
    """One run's requests against the reference's -> the statistics."""
    import numpy as np
    deficits, diffs, pool_err, large, p90, grown = [], [], [], [], [], []
    med = {"prefill": [], "decode": []}
    for g, w, p in zip(got, want, n_prompt):
        n = len(g["logits"])
        ref = w["logits"][:n]
        emitted = np.argmax(g["logits"], axis=-1)
        deficits.append(float(np.max(
            ref.max(-1) - ref[np.arange(n), emitted])))
        diffs.append(np.abs(g["logits"] - ref).max(-1))
        gh = g["hidden"].astype(np.float64)
        wh = w["hidden"][:, :gh.shape[1]].astype(np.float64)
        emb = w["embedded"][:gh.shape[1]].astype(np.float64)

        def increments(h):
            return np.diff(np.concatenate([emb[None], h]), axis=0)
        dg, dw = increments(gh), increments(wh)
        grown.append(float(np.median(np.linalg.norm(wh[-1], axis=-1)
                                     / np.linalg.norm(emb, axis=-1))))
        e = np.linalg.norm(dg - dw, axis=-1) / np.linalg.norm(dw, axis=-1)
        med["prefill"].append(np.median(e[:, :p], axis=1))
        med["decode"].append(np.median(e[:, p:], axis=1))
        p90.append(np.percentile(e, 90, axis=1))
        large.append(np.mean(e > LARGE, axis=1))
        first, kept = g["pool_k"][0]
        ref_kept = w["kept"][first:first + len(kept)]
        pool_err.append(float(np.median(
            np.linalg.norm(kept - ref_kept, axis=-1)
            / np.linalg.norm(ref_kept, axis=-1))))
    out = {"deficit_max": max(deficits),
           "logit_diff_p50": float(np.median(np.concatenate(diffs))),
           "logit_diff_max": float(np.max(np.concatenate(diffs))),
           "inc_median_prefill": float(np.max(np.mean(med["prefill"], 0))),
           "inc_median_decode": float(np.max(np.mean(med["decode"], 0))),
           "inc_median_by_layer_decode":
               [float(x) for x in np.mean(med["decode"], 0)],
           "inc_p90": float(np.max(np.mean(p90, 0))),
           "inc_large": [float(x) for x in np.mean(large, axis=0)],
           "inc_large_share": float(np.max(np.mean(large, axis=0))),
           "pool_latent_median": max(pool_err),
           # the reference's stream after the last layer over the
           # embedding, a row's norms, the median: the layers' share
           "stream_over_embedding": float(np.mean(grown))}
    out["failed_limits"] = sorted(
        k for k, lim in TOLERANCE.items()
        if not np.isfinite(out[k]) or out[k] > lim)
    out["pass"] = not out["failed_limits"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="dots-vlm1-share32-d6")
    ap.add_argument("--prompts", default="4500,7400")
    ap.add_argument("--answer", type=int, default=120)
    ap.add_argument("--seed", type=int, default=4900000301)
    ap.add_argument("--faults", default=",".join(FAULTS + UNSEEN))
    ap.add_argument("--embed-scales", default="",
                    help="comma-separated multiples of the embedding's rows "
                         "at which the CLEAN program is read first")
    ap.add_argument("--scale-faults", default="",
                    help="faults (of weights only: no new trace) read at "
                         "each of --embed-scales too")
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from compare_mellum import Runner
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, weights
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("compare_dotsvlm needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family = families.load(cfg)
    with weights.recording() as specs:
        model = family.serving_model(cfg)
    weights.fill(model, specs, args.seed)
    model.eval()
    runner = Runner(model, cfg)
    n_prompt = [int(p) for p in args.prompts.split(",")]
    rng = np.random.default_rng([args.seed, 5])
    prompts = [rng.integers(1, cfg["vocab_size"], size=p).tolist()
               for p in n_prompt]
    block = 256 if max(n_prompt) > 256 else 8
    r = cfg["kv_lora_rank"]

    @jax.jit
    def reference(params, ids, first):
        # the logits of the answer's positions only
        states = []
        hid = family.hidden(params, ids, cfg, collect=states)
        emb = jnp.asarray(params["model.embed.weight"][ids[0]], jnp.float32)
        pre = "model.layers.0."
        rope = family._rope(cfg)
        all_dim = family._mscale(rope["factor"], rope["mscale_all_dim"])
        inv, _ = family._inv_freq(dict(rope, attention_factor=1.0),
                                  cfg["qk_rope_head_dim"])
        with jax.default_matmul_precision("highest"):
            logits = jax.lax.dynamic_slice_in_dim(
                hid[0], first, args.answer, axis=0) @ family.head(params, cfg)
            u = family._rms(emb, params[pre + "attn_norm.weight"],
                            cfg["rms_norm_eps"])
            kv = u @ jnp.asarray(params[pre + "attn.kv_a_proj.weight"],
                                 jnp.float32)
            kept = jnp.concatenate([
                family._rms(kv[:, :r], params[pre + "attn.kv_a_norm.weight"],
                            cfg["rms_norm_eps"]),
                family._rotate(kv[:, r:], inv, family._mscale(
                    rope["factor"], rope["mscale"]) / all_dim)], axis=-1)
        return logits, jnp.stack([s[0] for s in states]), emb, kept

    def reference_of(seqs):
        params = {n: p.value for n, p in model.named_parameters()}
        want = []
        for s, p in zip(seqs, n_prompt):
            pad = -(-len(s) // block) * block
            ids = np.zeros((1, pad), np.int32)
            ids[0, :len(s)] = s
            lg, hid, emb, kept = reference(params, jnp.asarray(ids),
                                           jnp.int32(p - 1))
            want.append({"logits": np.asarray(lg),
                         "hidden": np.asarray(hid[:, :len(s)]),
                         "embedded": np.asarray(emb[:len(s)]),
                         "kept": np.asarray(kept[:len(s)])})
        return want

    def clean_run():
        t = time.time()
        clean = runner.run([p + [0] * args.answer for p in prompts],
                           n_prompt, greedy=True)
        seqs = [g["tokens"] for g in clean]
        want = reference_of(seqs)
        print(f"clean run and reference: {time.time() - t:.1f} s",
              flush=True)
        return clean, seqs, want

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def record(rec, run, **more):
        rec.update(run=run, config=cfg["name"], seed=args.seed,
                   prompts=n_prompt, answer=args.answer,
                   tool="compare_dotsvlm", device=dev.device_kind,
                   tolerance=TOLERANCE, **more)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    embed = model.model.embed.weight
    drawn = embed.value
    for scale in [float(x) for x in args.embed_scales.split(",") if x]:
        embed.value = (drawn.astype(jnp.float32) * scale).astype(drawn.dtype)
        clean, seqs, want = clean_run()
        record(compare(clean, want, n_prompt), "clean", embed_scale=scale)
        for fault in [f for f in args.scale_faults.split(",") if f]:
            repair = inject(model, fault)
            try:
                got = runner.run(seqs, n_prompt, greedy=False)
            finally:
                repair()
            record(compare(got, want, n_prompt), fault, embed_scale=scale)
            del got
        del clean, want
        gc.collect()
    embed.value = drawn
    clean, seqs, want = clean_run()
    verdicts = {}
    for fault in ["clean"] + [f for f in args.faults.split(",") if f]:
        t = time.time()
        repair = (lambda: None) if fault == "clean" else inject(model, fault)
        try:
            if fault == "clean":
                got = clean
            else:
                runner._fns.clear()
                got = runner.run(seqs, n_prompt, greedy=False)
            rec = compare(got, want, n_prompt)
        finally:
            repair()
            runner._fns.clear()
        verdicts[fault] = (rec["pass"],
                           "deficit_max" not in rec["failed_limits"])
        record(rec, fault, seconds=round(time.time() - t, 1))
        # a run's hidden states are 0.7 GB a request on the host
        del got
        gc.collect()
    reported = {f: verdicts.pop(f) for f in UNSEEN + ROUTED
                if f in verdicts}
    ok = verdicts.pop("clean")[0] and not any(
        under for _, under in verdicts.values())
    print("compare_dotsvlm:", "the clean program passes and the harness's "
          "own number fails every fault it has to see" if ok else
          f"NOT as it should be: (passes every limit, deficit_max under "
          f"its limit) {verdicts}",
          f"(reported only: {reported})", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
