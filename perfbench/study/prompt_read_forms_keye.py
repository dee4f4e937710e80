#!/usr/bin/env python3
"""Three forms of a Keye prompt's selected read, timed at the published
widths and at ``keye_longdoc_24k``'s buckets and lengths (PR 47).

    python3 perfbench/study/prompt_read_forms_keye.py --seed 4700000101 \\
        --out chiprun_out/p47c1/forms.jsonl

One layer's ``sparse_prompt_attention`` alone (32 query / 4 KV heads of
128, 16 indexer heads of 64 over one key head, ``topk`` 2048, bfloat16
operands), ms a call, for a prompt of ``live`` rows in its bucket (the
multiset's shortest, median and longest: 4,277 / 8,555 / 15,689 in 8192 /
12288 / 16384):

  ``rectangle``   the read as PR 46 had it, kept HERE as the oracle and
                  nowhere else: a chunk of 256 queries scores, selects over
                  and multiplies ALL the bucket's keys under the mask, a KV
                  head at a time, every chunk of the bucket
  ``spans``       static key spans: the chunk loop split where the frontier
                  passes a multiple of 4096, each span a loop of the
                  rectangle's body over keys sliced statically to the
                  span's end, its trip count stopping at the last live
                  chunk (two to four copies of the body)
  ``tiles<T>x<S>`` ``ops.attention_ops.sparse_prompt_attention``: a loop
                  over key tiles of ``T`` with a trip count from the
                  chunk's frontier and a running softmax, the selection over
                  the first of ``S`` static slices that holds the frontier
                  (the module's ``SPARSE_KEY_TILE`` / ``SPARSE_SELECT_SPANS``
                  set here for the reading that chooses them)

and each form's outputs of the LIVE rows are compared with the rectangle's
(bfloat16 outputs of unit-scale values: a different order of float32 sums
and an unnormalised bfloat16 weight move them by a bfloat16 step or two).
``pairs_share`` is the (query, key) pairs a form multiplies over the
rectangle's. One record a (bucket, form) is appended to ``--out``.
``--rehearsal`` runs toy shapes on the CPU for the code path only; its
times are never a result.
"""

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: (bucket, live rows): the multiset's shortest, median and longest prompt
CASES = ((8192, 4277), (12288, 8555), (16384, 15689))
WIDTHS = dict(hq=32, hkv=4, d=128, hi=16, di=64, topk=2048, chunk=256,
              span=4096)
TOY_CASES = ((64, 37), (64, 64))
TOY_WIDTHS = dict(hq=4, hkv=2, d=16, hi=2, di=8, topk=8, chunk=16, span=32)
#: (key tile, selection spans) of the tiled form's readings
TILED = ((512, 4), (1024, 4), (2048, 4), (512, 1), (512, 8), (1024, 8))
TOY_TILED = ((16, 4), (32, 2), (16, 1))


def rectangle_chunk(A, k, v, k_idx, topk, c):
    """-> fn(lo, qc, qic, wc) -> [b, hq, c, d]: PR 46's body of one chunk
    over ALL the keys it is given."""
    import jax
    import jax.numpy as jnp
    b, hkv, s, d = k.shape
    scale = 1.0 / math.sqrt(d)
    col = jnp.arange(s, dtype=jnp.int32)

    def one(lo, qc, qic, wc):
        hq = qc.shape[1]
        g = hq // hkv
        row = lo + jnp.arange(c, dtype=jnp.int32)
        causal = col[None, :] <= row[:, None]
        chosen = A.topk_mask(A.index_scores(qic, wc, k_idx),
                             jnp.broadcast_to(causal, (b, c, s)), topk)

        def head(args):
            qh, kh, vh = args
            logits = jnp.einsum("bgqd,bkd->bgqk", qh, kh,
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(chosen[:, None], logits,
                               jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(logits, axis=-1).astype(vh.dtype)
            return jnp.einsum("bgqk,bkd->bgqd", probs, vh,
                              preferred_element_type=jnp.float32)
        out = jax.lax.map(head, (
            qc.reshape(b, hkv, g, c, d).transpose(1, 0, 2, 3, 4),
            k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)))
        return out.transpose(1, 0, 2, 3, 4).reshape(b, hq, c, d).astype(
            v.dtype)
    return one


def chunked(q, q_idx, w, c):
    b, hq, s, d = q.shape
    n = s // c
    return (q.reshape(b, hq, n, c, d).transpose(2, 0, 1, 3, 4),
            q_idx.reshape(b, n, c, *q_idx.shape[2:]).transpose(1, 0, 2, 3, 4),
            w.reshape(b, n, c, -1).transpose(1, 0, 2, 3))


def forms(widths, tiled):
    """-> {form: (fn(q, k, v, q_idx, w, k_idx, live) -> [b, hq, s, d],
    pairs(s, live) -> the (query, key) pairs it multiplies)}."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    topk, c, span = widths["topk"], widths["chunk"], widths["span"]

    def rectangle(q, k, v, q_idx, w, k_idx, live):
        b, hq, s, d = q.shape
        one = rectangle_chunk(A, k, v, k_idx, topk, c)
        xs = chunked(q, q_idx, w, c)
        out = jax.lax.map(lambda a: one(*a), (
            jnp.arange(s // c, dtype=jnp.int32) * c, *xs))
        return out.transpose(1, 2, 0, 3, 4).reshape(b, hq, s, d)

    def spans(q, k, v, q_idx, w, k_idx, live):
        b, hq, s, d = q.shape
        xs = chunked(q, q_idx, w, c)
        last = (live + c - 1) // c
        out = jnp.zeros((s // c, b, hq, c, d), v.dtype)
        for upto in range(span, s + 1, span):
            one = rectangle_chunk(A, k[:, :, :upto], v[:, :, :upto],
                                  k_idx[:, :upto], topk, c)
            out = jax.lax.fori_loop(
                (upto - span) // c, jnp.minimum(upto // c, last),
                lambda i, out, one=one: out.at[i].set(
                    one(i * c, *(x[i] for x in xs))), out)
        return out.transpose(1, 2, 0, 3, 4).reshape(b, hq, s, d)

    def span_pairs(s, live):
        return sum(c * (-(-(i + 1) * c // span) * span)
                   for i in range(-(-live // c)))

    def tiles(kt, n):
        def sized():
            # the module's sizes are read when the read is traced
            A.SPARSE_QUERY_CHUNK, A.SPARSE_KEY_TILE = c, kt
            A.SPARSE_SELECT_SPANS = n
            return A

        def run(q, k, v, q_idx, w, k_idx, live):
            return sized().sparse_prompt_attention(
                q, k, v, q_idx, w, k_idx, topk, live=live)[0]
        return run, lambda s, live: sized().sparse_prompt_pairs(1, s, live)[0]
    out = {"rectangle": (rectangle, lambda s, live: s * s),
           "spans": (spans, span_pairs)}
    out.update({f"tiles{kt}x{n}": tiles(kt, n) for kt, n in tiled})
    return out


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
    ap.add_argument("--seed", type=int, default=4700000101)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--forms", default="",
                    help="comma-separated names (default: all)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy shapes on the CPU; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        raise SystemExit("prompt_read_forms_keye needs the TPU "
                         "(or --rehearsal)")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    W = TOY_WIDTHS if args.rehearsal else WIDTHS
    dt = jnp.float32 if args.rehearsal else jnp.bfloat16
    table = forms(W, TOY_TILED if args.rehearsal else TILED)
    names = [n for n in args.forms.split(",") if n] or list(table)
    worst = 0.0
    for s, live in (TOY_CASES if args.rehearsal else CASES):
        rng = np.random.default_rng([args.seed, s])

        def normal(*shape, to=dt):
            return jnp.asarray(rng.normal(size=shape), to)
        # unit-scale operands: logits of unit variance after 1 / sqrt(d),
        # index scores whose cut falls among distinct values and, at the
        # ReLU's zeros, among ties
        operands = (normal(1, W["hq"], s, W["d"]),
                    normal(1, W["hkv"], s, W["d"]),
                    normal(1, W["hkv"], s, W["d"]),
                    normal(1, s, W["hi"], W["di"]),
                    normal(1, s, W["hi"], to=jnp.float32),
                    normal(1, s, W["di"]))
        length = jnp.asarray(live, jnp.int32)
        oracle = None
        for name in ["rectangle"] + [n for n in names if n != "rectangle"]:
            fn, pairs = table[name]
            fn = jax.jit(fn)
            t = time.perf_counter()
            got = np.asarray(fn(*operands, length).astype(jnp.float32))
            rec = {"tool": "prompt_read_forms_keye", "form": name,
                   "bucket": s, "live": live, "seed": args.seed,
                   "device": dev.device_kind, "widths": W,
                   "first_call_s": round(time.perf_counter() - t, 2),
                   "ms": timed(fn, (*operands, length), args.calls),
                   "pairs_share": pairs(s, live) / (s * s)}
            if oracle is None:
                oracle = got
            diff = np.abs(got[:, :, :live] - oracle[:, :, :live])
            rec["live_rows_diff_max"] = float(diff.max())
            rec["live_rows_diff_mean"] = float(diff.mean())
            rec["oracle_abs_mean"] = float(np.abs(oracle[:, :, :live]).mean())
            if name != "rectangle":
                rec["rows_past_live_are_zero"] = bool(
                    (got[:, :, -(-live // W["chunk"]) * W["chunk"]:]
                     == 0).all())
                worst = max(worst, rec["live_rows_diff_max"])
            print(json.dumps(rec), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    # a bfloat16 output of magnitude under 4 steps by 2^-6; float32 at toy
    # size differs by the order of its sums alone
    return int(worst > (1e-4 if args.rehearsal else 0.0625))


if __name__ == "__main__":
    sys.exit(main())
