#!/usr/bin/env python3
"""The two exact top-k selections of the learned sparse attention, each
timed in the other's place at ``keye_longdoc_24k``'s shapes (PR 46).

    python3 perfbench/study/select_forms_keye.py --seed 4600000501 \\
        --out chiprun_out/p46r1/select.jsonl

``ops/attention_ops.py`` selects twice, and both selections are exact with
ties to the lower index: a prompt's chunk of 256 queries wants its sets as
a MASK over the call's keys (the selected read multiplies under it and
gathers nothing) and takes it from ``topk_mask`` (a bisection on the
scores' bits: 32 counts over the row, no sort); a decode row wants its set
as INDICES (it gathers the chosen rows of K and V through the block table)
and takes them from ``lax.top_k`` (a full sort of the row). This times, ms
a call, one layer's selection alone:

  prompt, scores ``[1, 256, keys]`` float32 under the causal mask of a
  chunk's rows (the chunk that ends the bucket):
    ``bisect``     ``topk_mask``: the program's
    ``sort``       ``lax.top_k``, whose last value is the cut, then the
                   mask of the keys above it and of its ties in index
                   order (``families/keye.chosen_keys``: the reference's)
  decode, scores ``[8, table x 256]`` float32, positions over the cell's
  contexts:
    ``sort``       ``lax.top_k``'s indices: the program's
    ``bisect``     ``topk_mask``, then the set's indices by a cumulative
                   count and a scatter (a mask gathers nothing)

and checks that both forms of a shape choose the same sets. One record a
shape is appended to ``--out``. ``--rehearsal`` runs toy shapes on the CPU
for the code path only; its times are never a result.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: name -> (kind, rows, keys, topk): a prompt's last chunk of a bucket, a
#: decode step over the cell's table of 100 blocks of 256
SHAPES = {
    "prompt_chunk_8192": ("prompt", 256, 8192, 2048),
    "prompt_chunk_24576": ("prompt", 256, 24576, 2048),
    "decode_8x25600": ("decode", 8, 25600, 2048),
}
TOY = {
    "prompt_chunk_64": ("prompt", 16, 64, 8),
    "decode_4x128": ("decode", 4, 128, 8),
}


def forms(kind, topk):
    """-> {form: fn(scores, valid) -> bool mask [.., keys]}: each form's
    whole work (a decode form makes the indices the gather takes; the mask
    is rebuilt from them for the comparison, outside the timed call)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import topk_mask
    from perfbench.families.keye import chosen_keys

    def prompt_sort(scores, valid):
        return chosen_keys(scores[0], valid[0], topk)[None]

    def decode_sort(scores, valid):
        # -0.0 as +0.0, as ``sparse_decode_attention`` has it: the chip's
        # sort puts -0.0 under +0.0, and the two are one score (the first
        # run of this script, without it, chose other sets at a cut of 0)
        scores = jnp.where(scores == 0, 0.0, scores)
        _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), topk)
        return idx.astype(jnp.int32)

    def decode_bisect(scores, valid):
        chosen = topk_mask(scores, valid, topk)
        slot = jnp.cumsum(chosen, axis=-1, dtype=jnp.int32) - 1
        keys = jnp.broadcast_to(
            jnp.arange(scores.shape[-1], dtype=jnp.int32), scores.shape)
        rows = jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
        return jnp.zeros((scores.shape[0], topk), jnp.int32).at[
            rows, jnp.where(chosen, slot, topk)].set(keys, mode="drop")
    if kind == "prompt":
        return {"bisect": lambda s, v: topk_mask(s, v, topk),
                "sort": prompt_sort}
    return {"sort": decode_sort, "bisect": decode_bisect}


def timed(fn, args, calls):
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / calls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4600000501)
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy shapes on the CPU; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        raise SystemExit("select_forms_keye needs the TPU (or --rehearsal)")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rng = np.random.default_rng([args.seed, 46])
    for name, (kind, rows, keys, topk) in (
            TOY if args.rehearsal else SHAPES).items():
        if kind == "prompt":
            shape = (1, rows, keys)
            last = keys - rows + np.arange(rows)        # the bucket's end
            valid = np.arange(keys)[None, None, :] <= last[None, :, None]
        else:
            shape = (rows, keys)
            pos = rng.integers(keys // 6, keys - 1, size=rows)
            valid = np.arange(keys)[None, :] <= pos[:, None]
        # ReLU'd weighted sums: half the scores are zeros of either sign,
        # so the shortest contexts' cut falls among ties
        scores = jnp.asarray(np.maximum(rng.normal(size=shape), 0.0)
                             * rng.normal(size=shape), jnp.float32)
        valid = jnp.asarray(valid)
        rec = {"tool": "select_forms_keye", "shape": name, "kind": kind,
               "rows": rows, "keys": keys, "topk": topk, "seed": args.seed,
               "device": dev.device_kind, "ms": {}, "compile_s": {}}
        sets = {}
        for form, fn in forms(kind, topk).items():
            fn = jax.jit(fn)
            t = time.perf_counter()
            got = np.asarray(fn(scores, valid))
            rec["compile_s"][form] = round(time.perf_counter() - t, 2)
            rec["ms"][form] = timed(fn, (scores, valid), args.calls)
            if kind == "decode":
                mask = np.zeros(shape, bool)
                np.put_along_axis(mask, got, True, axis=-1)
                got = mask & np.asarray(valid)
            sets[form] = got
        rec["same_sets"] = bool((sets["bisect"] == sets["sort"]).all())
        rec["set_size"] = int(sets["sort"].sum(-1).min())
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if not rec["same_sets"]:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
