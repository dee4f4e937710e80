#!/usr/bin/env python3
"""A latent-attention prompt's materialised read, two ways, timed on the
chip at the cell's shapes: the reading ISSUE 49 asks the choice to rest on.

    python3 perfbench/study/prompt_read_forms_dotsvlm.py \
        --out chiprun_out/p49c2/prompt_forms.jsonl

One pass of the model's read (``models/dotsvlm.PROMPT_HEADS`` = 32 heads of
a 128 + 64-wide key and a 128-wide value, bfloat16) over a prompt of
``--rows`` rows, all live, and over a bucket whose last quarter is padding
(``live`` = 3/4 of the rows):

- ``kernel``: ``ops/pallas/mla_attention.mla_prompt_attention``, the flash
  forward that takes the head's own 128-wide key part beside the ONE
  64-wide rotated key, K and V streamed by tile, tiles above the diagonal
  and query blocks past the live rows not run;
- ``xla_tiles``: chunked XLA in the manner of Keye's prompt path
  (``ops.attention_ops.sparse_prompt_attention``): a chunk of 512 queries
  at a time (``lax.map``), over the key tiles of 512 up to its own frontier
  (a ``fori_loop`` with a data-dependent bound) under a running softmax;
- ``xla_rect``: chunked XLA over every key (a chunk of 256 queries times
  the whole row of keys, masked): what needs no loop bound.

``--tiles`` sets the kernel's (query, key) rows a tile (the program's:
``mla_attention.PROMPT_BLOCK_Q`` / ``_K``). A third form of the kernel, its
keys a lane so that no score product has a transposed operand, was read
once (PR 49, call 4: 1-4% SLOWER than the keys a row at every tiling) and
is gone. Each form is checked against ``xla_rect`` at 2048 rows first (bfloat16
inputs: the largest difference is printed), then timed: the median of
``--reps`` calls after one that compiles. A record a (form, rows, live).
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

HEADS, DN, DR, DV = 32, 128, 64, 128
SCALE = 1.8739 / 192 ** 0.5


def forms():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.mla_attention import mla_prompt_attention

    def logits(qn, qr, kn, kr):
        return (jnp.einsum("bhqd,bhkd->bhqk", qn, kn,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bhqd,bkd->bhqk", qr, kr,
                             preferred_element_type=jnp.float32)) * SCALE

    def kernel(qn, qr, kn, kr, v, live):
        # as the model calls it: the scale folded into the queries
        return mla_prompt_attention(
            (qn * SCALE).astype(qn.dtype), (qr * SCALE).astype(qr.dtype),
            kn, kr, v, scale=1.0, live=live)

    def xla_rect(qn, qr, kn, kr, v, live, chunk=256):
        s = qn.shape[2]
        col = jnp.arange(s)[None, :]

        def one(lo):
            q1 = jax.lax.dynamic_slice_in_dim(qn, lo, chunk, axis=2)
            q2 = jax.lax.dynamic_slice_in_dim(qr, lo, chunk, axis=2)
            lg = logits(q1, q2, kn, kr)
            seen = col <= lo + jnp.arange(chunk)[:, None]
            p = jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), -1)
            return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32
                              ).astype(v.dtype)
        out = jax.lax.map(one, jnp.arange(0, s, chunk))
        return out.transpose(1, 2, 0, 3, 4).reshape(v.shape)

    def xla_tiles(qn, qr, kn, kr, v, live, chunk=512, tile=512):
        b, h, s, _ = qn.shape

        def one(lo):
            q1 = jax.lax.dynamic_slice_in_dim(qn, lo, chunk, axis=2)
            q2 = jax.lax.dynamic_slice_in_dim(qr, lo, chunk, axis=2)
            row = lo + jnp.arange(chunk)[:, None]

            def step(t, carry):
                m, l, acc = carry
                k1 = jax.lax.dynamic_slice_in_dim(kn, t * tile, tile, axis=2)
                k2 = jax.lax.dynamic_slice_in_dim(kr, t * tile, tile, axis=1)
                vt = jax.lax.dynamic_slice_in_dim(v, t * tile, tile, axis=2)
                lg = logits(q1, q2, k1, k2)
                seen = t * tile + jnp.arange(tile)[None, :] <= row
                lg = jnp.where(seen, lg, -1e30)
                m_new = jnp.maximum(m, jnp.max(lg, -1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(lg - m_new)
                return (m_new, alpha * l + jnp.sum(p, -1, keepdims=True),
                        alpha * acc + jnp.einsum(
                            "bhqk,bhkd->bhqd", p.astype(vt.dtype), vt,
                            preferred_element_type=jnp.float32))
            # a chunk past the live rows runs no tile
            tiles = jnp.where(lo < live[0], (lo + chunk + tile - 1) // tile,
                              0)
            m, l, acc = jax.lax.fori_loop(0, tiles, step, (
                jnp.full((b, h, chunk, 1), -1e30, jnp.float32),
                jnp.zeros((b, h, chunk, 1), jnp.float32),
                jnp.zeros((b, h, chunk, DV), jnp.float32)))
            return (acc / jnp.where(l > 0, l, 1.0)).astype(v.dtype)
        out = jax.lax.map(one, jnp.arange(0, s, chunk))
        return out.transpose(1, 2, 0, 3, 4).reshape(v.shape)
    return {"kernel": kernel, "xla_tiles": xla_tiles, "xla_rect": xla_rect}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="6144,16384")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiles", default="",
                    help="the kernel's (query, key) rows a tile, e.g. "
                         "512x512 (default: the program's)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("prompt_read_forms_dotsvlm needs the TPU")
    if args.tiles:
        from paddle_tpu.ops.pallas import mla_attention as M
        M.PROMPT_BLOCK_Q, M.PROMPT_BLOCK_K = (
            int(x) for x in args.tiles.split("x"))
    fns = {k: jax.jit(f) for k, f in forms().items()}

    def inputs(s, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        shapes = ((1, HEADS, s, DN), (1, HEADS, s, DR), (1, HEADS, s, DN),
                  (1, s, DR), (1, HEADS, s, DV))
        return [jax.random.normal(k, sh, jnp.bfloat16)
                for k, sh in zip(ks, shapes)]
    small = inputs(2048)
    live = jnp.asarray([2048], jnp.int32)
    want = np.asarray(fns["xla_rect"](*small, live).astype(jnp.float32))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for name in ("kernel", "xla_tiles"):
        got = np.asarray(fns[name](*small, live).astype(jnp.float32))
        print(f"{name} against xla_rect at 2048 rows: largest difference "
              f"{np.abs(got - want).max():.4f} of values to "
              f"{np.abs(want).max():.2f}", flush=True)
    for s in [int(x) for x in args.rows.split(",")]:
        x = inputs(s, seed=s)
        for share in (1.0, 0.75):
            live = jnp.asarray([int(s * share)], jnp.int32)
            pairs = live[0] * (live[0] + 1) / 2
            for name, fn in fns.items():
                if name == "xla_rect" and (share < 1.0 or args.tiles):
                    continue                # it has no bound to follow
                jax.block_until_ready(fn(*x, live))
                times = []
                for _ in range(args.reps):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(*x, live))
                    times.append(time.perf_counter() - t)
                ms = 1e3 * statistics.median(times)
                rec = {"tool": "prompt_read_forms_dotsvlm", "form": name,
                       "tiles": args.tiles or "program's",
                       "rows": s, "live": int(live[0]), "heads": HEADS,
                       "ms_a_pass": ms, "reps": args.reps,
                       "tflops_live_triangle": float(
                           2.0 * HEADS * (DN + DR + DV) * pairs / ms / 1e9),
                       "device": dev.device_kind}
                print(json.dumps(rec), flush=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
