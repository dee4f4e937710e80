#!/usr/bin/env python3
"""The Keye program against its plain reference at the published widths
and the cell's lengths, and the planted faults the comparison must see.

    python3 perfbench/study/compare_keye.py --seed 4600000301 \
        --out chiprun_out/p46c6/compare.jsonl

Prefill then decode through the serving cache (``compare_mellum.Runner``:
``BlockKVCache.for_model`` from the model's seam, here with the third
per-token array, the model's serving forward jitted as the engine's
entries jit it, every layer's output carried out) against the reference's
full forward (``families/keye.forward``, float32 at ``highest``) on the
same tokens: two prompts (``--prompts``, both past ``topk`` 2048 and in the
8192-row bucket), then ``--answer`` tokens decoded greedily by both rows
together. The clean run decodes greedily and every faulted run replays its
tokens, so all are read against one reference. Read:

- ``deficit_max``: the harness's own measure (``serve.check``), limit 0.05;
- ``logit_diff_max`` / ``logit_diff_p50``: |program - reference| logits
  over the answer's positions (reported, no limit: an expert's flip at a
  near tie moves it);
- ``inc_median_prefill`` / ``inc_median_decode``: per layer and row,
  |program's increment to the stream - reference's| / |reference's|, the
  median over rows, the largest over layers, over the prompts' rows and the
  decoded rows apart;
- ``set_mismatch``: THE SELECTED SETS. For every layer and every row past
  ``topk``, the program's set (the program's own indexer and
  ``ops.attention_ops.topk_mask`` on the hidden states the served run
  carried out, bfloat16 as served) against the reference's (float32): one
  less the mean Jaccard overlap, the largest over layers. Not 0 when
  clean: scores of thousands of keys lie dense at the cut and bfloat16
  moves some across it;
- ``set_size_error``: the largest ``| |S_t| - min(t + 1, topk) |`` over
  rows and layers: 0 for an exact selection;
- ``pool_k_median`` / ``pool_index_median``: what layer 0's K pool and
  indexer-key pool hold when the requests are done, read back through the
  block table, against the reference's rotated K and indexer key of those
  positions: relative error a row, the median.

Faults, those of ``tests/test_keye_faults.py``, planted one at a time:
``selection_dropped`` (all keys read), ``one_key_fewer`` (``topk`` - 1),
``no_causal_mask`` (a prompt's query picks among later rows too),
``one_set_a_chunk`` (a chunk's last query chooses for all 256),
``dead_entries_eligible`` (a decode row picks among its table's rows past
its own), ``index_key_a_row_late``, ``no_weights``, ``no_relu``,
``keys_unrotated``. And two controls of precision, the nearest below the
configuration's bfloat16 (``compare_lfm2.fp8_mantissa``: float8_e4m3's 3
bits of mantissa, rounded in bit arithmetic): ``fp8_index_keys`` (the
indexer's keys rounded before they are scored and written to their pool:
the storage the published indexer has and this configuration does not
state) and ``fp8_experts`` (every expert matrix rounded in place, a second
copy of 7.2 GB not fitting the chip: planted last and not repaired). Exit
code 0 when the clean program passes every limit and every fault and
control fails at least one.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

#: limits of one comparison; a reading above any of them fails it. Each
#: stands between what the clean program read and what the faults it is
#: there to see read (my chip runs, PR 46: call 6, seed 4600000301, and
#: call r1, seed 4600000302 with every fault, both seeds with the two
#: controls of precision; prompts 4500 and 7400, 120 tokens each;
#: ``perfbench/study/runs_pr46.jsonl`` has every line, PERF.md section 6
#: the table; the readings below are call 6's, the second seed's beside
#: them in brackets where they differ):
#:
#: - ``deficit_max`` 0.05, the harness's own limit: clean 0.0016 [0.0].
#:   Over these 240 positions it sees the scores without their causal mask
#:   (0.77 [1.48]), dead table entries (0.33 [0.30]), the weights left out
#:   (0.090 [0.084]) and unrotated keys (0.079 [0.062]); on one seed of the
#:   two the selection dropped (0.037 [0.055]) and the key a row late
#:   (0.036 [0.072]); NOT the ReLU left out (0.048 [0.023]), one set a
#:   chunk (0.0046 [0.015]), a key fewer (0.0051 [0.0074]) or either
#:   control of precision (0.0-0.015): with random weights attention is
#:   diffuse and the stream is the embedding's, so the others exist.
#: - ``inc_median_prefill`` 0.02: clean 0.0093 [0.0092] (bfloat16 rounding
#:   of every matmul's input, growing 0.0080 -> 0.0093 over the six
#:   layers); the faults of the prompt path 0.094 (no ReLU) to 1.9 (no
#:   causal mask); the controls 0.023 (float8 index keys) and 0.046
#:   (float8 experts), both seeds alike.
#: - ``inc_median_decode`` 0.03: clean 0.0174 [0.0179] (call 6 ran with
#:   0.02 here: widened AFTER that reading for room above the clean
#:   program, every verdict of that run standing under either value; the
#:   second seed and the controls were read under 0.03 as it stands); the
#:   two decode-only faults 0.42 (dead entries) and 0.32 (the key a row
#:   late), the others 0.038-1.2; the controls 0.044 and 0.051.
#: - ``set_mismatch`` 0.05: clean 0.0038 (0.0029 in layer 0 to 0.0038 in
#:   layer 5: of a row's 2048 keys about four differ from the float32
#:   reference's, the near ties at the cut that bfloat16 moves; the second
#:   seed reads the same 0.0038); a wrong set 0.29 (no ReLU) to 0.66 (one
#:   set a chunk); float8 index keys 0.022 (some thirty keys of a row's
#:   2048: under this limit, seen by the increments and the pool).
#: - ``set_size_error`` 0.5: clean 0 (every row past ``topk`` keeps exactly
#:   2048 keys); a key fewer 1, the only limit that sees it (one key of
#:   2048 moves a layer's output by less than bfloat16 does).
#: - ``pool_k_median`` 0.01: clean 0.0032 (a bfloat16 pool); no fault here
#:   touches K. ``pool_index_median`` 0.01: clean 0.0022 [0.0023]; the key
#:   a row late 1.4, unrotated 1.0, rounded to float8 0.0265.
TOLERANCE = {"deficit_max": 0.05,
             "inc_median_prefill": 0.02, "inc_median_decode": 0.03,
             "set_mismatch": 0.05, "set_size_error": 0.5,
             "pool_k_median": 0.01, "pool_index_median": 0.01}
FAULTS = ("selection_dropped", "one_key_fewer", "no_causal_mask",
          "one_set_a_chunk", "dead_entries_eligible",
          "index_key_a_row_late", "no_weights", "no_relu", "keys_unrotated",
          "fp8_index_keys", "fp8_experts")
BLOCK = 256


def inject(model, fault):
    """Break the program in one place -> a function that repairs it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import laguna as L
    from paddle_tpu.ops import attention_ops as A
    mask, decode, write = A.topk_mask, A.sparse_decode_attention, \
        A.index_pool_write
    rotate, scores, paged = L._rotate_half, A.index_scores, \
        A.index_scores_paged
    sa = model.cfg.sa_config

    def put(**names):
        def repair():
            A.topk_mask, A.index_scores = mask, scores
            L.sparse_decode_attention, L.index_pool_write = decode, write
            L.index_scores_paged, L._rotate_half = paged, rotate
            model.cfg.sa_config = sa
        for name, value in names.items():
            where, _, attr = name.partition("__")
            setattr({"A": A, "L": L}[where], attr, value)
        return repair
    if fault in ("selection_dropped", "one_key_fewer"):
        model.cfg.sa_config = dict(
            sa, topk=2 ** 20 if fault == "selection_dropped"
            else sa["topk"] - 1)
        return put()
    if fault == "no_causal_mask":
        return put(A__topk_mask=lambda s, valid, k: mask(
            s, jnp.ones_like(valid), k))
    if fault == "one_set_a_chunk":
        return put(A__topk_mask=lambda s, valid, k: jnp.logical_and(
            mask(s, valid, k)[..., -1:, :], valid))
    if fault == "dead_entries_eligible":
        return put(L__sparse_decode_attention=lambda q, kp, vp, tables, pos,
                   sc, topk: decode(q, kp, vp, tables, jnp.full_like(
                       pos, sc.shape[1] - 1), sc, topk))
    if fault == "index_key_a_row_late":
        return put(L__index_pool_write=lambda pool, new, pos, tables: write(
            pool, new, jnp.asarray(pos, jnp.int32) + 1, tables))
    if fault in ("no_weights", "no_relu"):
        relu = jax.nn.relu if fault == "no_weights" else (lambda x: x)
        weigh = (lambda w: 1.0) if fault == "no_weights" \
            else (lambda w: w.astype(jnp.float32))

        def index_scores(q_idx, w, k_idx):
            dots = jnp.einsum("...tjd,...sd->...tjs", q_idx, k_idx,
                              preferred_element_type=jnp.float32)
            return jnp.sum(relu(dots) * weigh(w[..., None]), axis=-2)

        def index_scores_paged(q_idx, w, pool, tables):
            kg = pool[jnp.asarray(tables, jnp.int32)]
            b, T, _, bs = kg.shape
            dots = jnp.einsum("bjd,btdk->bjtk", q_idx.astype(kg.dtype), kg,
                              preferred_element_type=jnp.float32)
            return jnp.sum(relu(dots) * weigh(w[:, :, None, None]),
                           axis=1).reshape(b, T * bs)
        return put(A__index_scores=index_scores,
                   L__index_scores_paged=index_scores_paged)
    if fault == "fp8_index_keys":
        from compare_lfm2 import fp8_mantissa
        index = L.LagunaAttention._index

        def rounded(self, u, rows):
            q, w, k = index(self, u, rows)
            return q, w, fp8_mantissa(k)
        L.LagunaAttention._index = rounded
        return lambda: setattr(L.LagunaAttention, "_index", index)
    if fault == "fp8_experts":
        from compare_lfm2 import fp8_mantissa
        round_in_place = jax.jit(fp8_mantissa, donate_argnums=0)
        for blk in model.model.layers:
            for p in (blk.moe.experts_gate_up, blk.moe.experts_down):
                p.value = round_in_place(p.value)
        return lambda: None
    if fault == "keys_unrotated":
        # [b, s, di] is the indexer's key; its queries are [b, s, hi, di]
        return put(L___rotate_half=lambda x, rows, theta:
                   x if x.ndim == 3 else rotate(x, rows, theta))
    raise ValueError(fault)


def make_runner(model, cfg):
    import numpy as np
    import jax.numpy as jnp
    from compare_mellum import Runner

    class KeyeRunner(Runner):
        """``compare_mellum.Runner`` that also reads layer 0's pool of
        indexer keys back through the table before a row is released."""

        def run(self, seqs, n_prompt, greedy):
            c, kept = self.cache, []
            real = c.release_row

            def release(row):
                held = np.flatnonzero(c.tables[row] != c.TRASH)
                ki = np.asarray(c.arrays()[0][2][c.tables[row][held]]
                                .astype(jnp.float32))       # [n, di, bs]
                kept.append(np.swapaxes(ki, 1, 2).reshape(
                    -1, ki.shape[1])[:int(c.lengths[row])])
                real(row)
            c.release_row = release
            try:
                out = super().run(seqs, n_prompt, greedy)
            finally:
                del c.release_row
            for o, ki in zip(out, kept):
                o["pool_index"] = ki
            return out
    return KeyeRunner(model, cfg)


def program_sets(model, layer, x, rows_padded):
    """The sets the program's own indexer and selection give on the rows
    ``x`` float32 [n, h] (the stream entering ``layer``, as the served run
    carried it out) -> bool [rows_padded, rows_padded]."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.dygraph.tape import no_grad
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.ops import attention_ops as A
    blk = model.model.layers[layer]
    topk = model.cfg.indexer[2]
    x = jnp.pad(jnp.asarray(x), ((0, rows_padded - x.shape[0]), (0, 0)))

    @jax.jit
    def sets(x):
        with no_grad():
            u = blk.attn_norm(Tensor(x[None], stop_gradient=True))
            rows = jnp.arange(rows_padded, dtype=jnp.int32)[None]
            qi, w, ki = blk.attn._index(u.value.astype(model.cfg.dtype),
                                        rows)
        col = jnp.arange(rows_padded, dtype=jnp.int32)[None, :]

        def one(args):
            lo, q, ww = args
            causal = col <= lo + jnp.arange(BLOCK, dtype=jnp.int32)[:, None]
            return A.topk_mask(A.index_scores(q, ww, ki[0]), causal, topk)
        n = rows_padded // BLOCK
        return jax.lax.map(one, (
            jnp.arange(n, dtype=jnp.int32) * BLOCK,
            qi[0].reshape(n, BLOCK, *qi.shape[2:]),
            w[0].reshape(n, BLOCK, -1))).reshape(rows_padded, rows_padded)
    return sets(x)


def compare(got, want, n_prompt, model):
    """One run's requests against the reference's -> the statistics."""
    import numpy as np
    topk = want[0]["topk"]
    deficits, diffs, mismatch, size_err = [], [], [], 0.0
    med = {"prefill": [], "decode": []}
    pool_k, pool_i = [], []
    for g, w, p in zip(got, want, n_prompt):
        n = len(g["logits"])
        ref = w["logits"][:n]       # the answer's positions, from p - 1
        emitted = np.argmax(g["logits"], axis=-1)
        deficits.append(float(np.max(
            ref.max(-1) - ref[np.arange(n), emitted])))
        diffs.append(np.abs(g["logits"] - ref).max(-1))
        gh = g["hidden"].astype(np.float64)
        rows = gh.shape[1]
        wh = w["hidden"][:, :rows].astype(np.float64)
        emb = w["embedded"][:rows].astype(np.float64)

        def increments(h):
            return np.diff(np.concatenate([emb[None], h]), axis=0)
        dg, dw = increments(gh), increments(wh)
        e = np.linalg.norm(dg - dw, axis=-1) / np.linalg.norm(dw, axis=-1)
        med["prefill"].append(np.median(e[:, :p], axis=1))
        med["decode"].append(np.median(e[:, p:], axis=1))
        padded = w["sets"][0].shape[0]
        size = np.minimum(np.arange(rows) + 1, topk)
        per_layer = []
        for layer in range(gh.shape[0]):
            x = emb if layer == 0 else g["hidden"][layer - 1]
            mine = np.asarray(program_sets(
                model, layer, x.astype(np.float32), padded))[:rows, :rows]
            theirs = w["sets"][layer][:rows, :rows]
            both = np.logical_and(mine, theirs).sum(-1)
            either = np.logical_or(mine, theirs).sum(-1)
            per_layer.append(1.0 - float(np.mean(
                (both / either)[topk:])))
            size_err = max(size_err,
                           float(np.abs(mine.sum(-1) - size).max()))
        mismatch.append(per_layer)
        first, k = g["pool_k"][0]
        ref_k = w["k"][first:first + len(k)]

        def row_error(a, b):
            a, b = a.reshape(len(a), -1), b.reshape(len(b), -1)
            return float(np.median(np.linalg.norm(a - b, axis=-1)
                                   / np.linalg.norm(b, axis=-1)))
        pool_k.append(row_error(k, ref_k))
        pool_i.append(row_error(g["pool_index"],
                                w["ki"][:len(g["pool_index"])]))
    mismatch = np.max(mismatch, axis=0)
    out = {"deficit_max": max(deficits),
           "logit_diff_p50": float(np.median(np.concatenate(diffs))),
           "logit_diff_max": float(np.max(np.concatenate(diffs))),
           "inc_median_prefill": float(np.max(np.mean(med["prefill"], 0))),
           "inc_median_decode": float(np.max(np.mean(med["decode"], 0))),
           "inc_median_by_layer_prefill":
               [float(x) for x in np.mean(med["prefill"], 0)],
           "set_mismatch_by_layer": [float(x) for x in mismatch],
           "set_mismatch": float(np.max(mismatch)),
           "set_size_error": size_err,
           "pool_k_median": max(pool_k), "pool_index_median": max(pool_i)}
    out["failed_limits"] = sorted(
        k for k, lim in TOLERANCE.items()
        if not np.isfinite(out[k]) or out[k] > lim)
    out["pass"] = not out["failed_limits"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="keye-vl2-30b-a3b-stage0")
    ap.add_argument("--prompts", default="4500,7400")
    ap.add_argument("--answer", type=int, default=120)
    ap.add_argument("--seed", type=int, default=4600000301)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, weights
    from perfbench.families import keye as K
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("compare_keye needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family = families.load(cfg)
    with weights.recording() as specs:
        model = family.serving_model(cfg)
    weights.fill(model, specs, args.seed)
    model.eval()
    runner = make_runner(model, cfg)
    n_prompt = [int(p) for p in args.prompts.split(",")]
    rng = np.random.default_rng([args.seed, 5])
    prompts = [rng.integers(1, cfg["vocab_size"], size=p).tolist()
               for p in n_prompt]
    t = time.time()
    clean = runner.run([p + [0] * args.answer for p in prompts], n_prompt,
                       greedy=True)
    seqs = [g["tokens"] for g in clean]
    print(f"clean run: {time.time() - t:.1f} s", flush=True)
    params = {n: p.value for n, p in model.named_parameters()}
    global BLOCK
    BLOCK = 256 if max(len(s) for s in seqs) > 256 else 8
    sa = cfg["sa_config"]

    @jax.jit
    def reference(params, ids, first):
        # the logits of the answer's positions only: a request's whole
        # [rows, 151936] float32 does not fit beside the served copy
        states, sets = [], []
        hid = family.hidden(params, ids, cfg, collect=states, sets=sets)
        with jax.default_matmul_precision("highest"):
            logits = jax.lax.dynamic_slice_in_dim(
                hid[0], first, args.answer, axis=0) @ family.head(params, cfg)
        emb = jnp.asarray(params["model.embed.weight"][ids[0]], jnp.float32)
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        rope = {"rope_theta": cfg["rope_theta"]}
        pre = "model.layers.0."
        with jax.default_matmul_precision("highest"):
            u = K._rms(emb, params[pre + "attn_norm.weight"],
                       cfg["rms_norm_eps"])
            k = (u @ jnp.asarray(params[pre + "attn.qkv_proj.weight"][
                :, hq * d:(hq + kv) * d], jnp.float32)
                 ).reshape(-1, kv, d).transpose(1, 0, 2)
            k = K._rotate(K._rms(k, params[pre + "attn.k_norm.weight"],
                                 cfg["rms_norm_eps"]), rope)
            ki = K._rotate(K._layer_norm(
                u @ jnp.asarray(params[pre + "attn.index_k.weight"],
                                jnp.float32),
                params[pre + "attn.index_k_norm_weight"],
                params[pre + "attn.index_k_norm_bias"],
                cfg["rms_norm_eps"])[None], rope)[0]
        return (logits, jnp.stack([s[0] for s in states]), emb,
                k.transpose(1, 0, 2), ki, jnp.stack([s[0] for s in sets]))
    want = []
    t = time.time()
    for s, p in zip(seqs, n_prompt):
        pad = -(-len(s) // BLOCK) * BLOCK
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(s)] = s
        lg, hid, emb, k, ki, sets = reference(params, jnp.asarray(ids),
                                              jnp.int32(p - 1))
        want.append({"logits": np.asarray(lg),
                     "hidden": np.asarray(hid[:, :len(s)]),
                     "embedded": np.asarray(emb[:len(s)]),
                     "k": np.asarray(k[:len(s)]),
                     "ki": np.asarray(ki[:len(s)]),
                     "sets": np.asarray(sets), "topk": int(sa["topk"])})
    print(f"reference: {time.time() - t:.1f} s", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    verdicts = {}
    faults = [f for f in args.faults.split(",") if f]
    if "fp8_experts" in faults[:-1]:
        raise SystemExit("fp8_experts rounds the weights in place and is "
                         "not repaired: name it last")
    for fault in ["clean"] + faults:
        t = time.time()
        repair = (lambda: None) if fault == "clean" else inject(model, fault)
        try:
            if fault == "clean":
                got = clean
            else:
                runner._fns.clear()
                got = runner.run(seqs, n_prompt, greedy=False)
            rec = compare(got, want, n_prompt, model)
        finally:
            repair()
            runner._fns.clear()
        rec.update(run=fault, config=cfg["name"], seed=args.seed,
                   prompts=n_prompt, answer=args.answer, tool="compare_keye",
                   device=dev.device_kind, tolerance=TOLERANCE,
                   seconds=round(time.time() - t, 1))
        verdicts[fault] = rec["pass"]
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    ok = verdicts.pop("clean") and not any(verdicts.values())
    print("compare_keye:", "the clean program passes and every fault "
          "fails" if ok else f"NOT as it should be: clean must pass, "
          f"faults must fail: {verdicts}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
