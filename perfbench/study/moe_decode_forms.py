#!/usr/bin/env python3
"""The expert layer at a few rows: what each form of a decode step's
16 rows x 8 choices over 64 experts costs on the chip, one layer, at the
published widths (hidden 2304, experts of 896, bfloat16).

    python3 perfbench/study/moe_decode_forms.py --out chiprun_out/p31/forms.jsonl

Forms (all give the same sum; each is checked against ``dense``):

- ``sorted16``: the program's (``ops.decoder_ops.moe_experts_decode``): the
  sorted buffer in tiles of 16 rows, a tile only for an expert some row
  chose, the grouped kernels ``moe_up_dec`` / ``moe_down_dec``;
- ``sorted128``: the training path's layout (``moe_experts``: tiles of 128
  rows, every expert a tile);
- ``dense``: every expert on every row, two batched products, the rows'
  weights zero where an expert was not chosen;
- ``walk``: a ``fori_loop`` over the experts, each under a ``cond`` on
  "some row chose it", its weights sliced out of the stack.

The floor to compare with: the touched experts' weights once, ~56 of 64 at
16 uniform rows, 12.4 MB each: 0.69 GB, 0.85 ms at 819 GB/s.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=2304)
    ap.add_argument("--width", type=int, default=896)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--seed", type=int, default=2147484311)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import decoder_ops as D
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("moe_decode_forms needs the TPU (or --allow-cpu)")
    t, h, f, e, k = (args.rows, args.hidden, args.width, args.experts,
                     args.top_k)
    dt = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    key = jax.random.PRNGKey(args.seed & 0x7FFFFFFF)
    ks = jax.random.split(key, 4)
    w13 = (0.02 * jax.random.normal(ks[0], (e, h, 2 * f))).astype(dt)
    w2 = (0.02 * jax.random.normal(ks[1], (e, f, h))).astype(dt)
    x = jax.random.normal(ks[2], (t, h)).astype(dt)
    scores = jax.random.normal(ks[3], (t, e))
    top, idx = jax.lax.top_k(jax.nn.softmax(scores, -1), k)
    weight = (top / top.sum(-1, keepdims=True)).astype(dt)
    idx = idx.astype(jnp.int32)

    def dense(x, weight, idx, w13, w2):
        full = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], idx].set(weight.astype(jnp.float32))
        gu = jnp.einsum("th,ehn->etn", x, w13,
                        preferred_element_type=jnp.float32)
        a = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(x.dtype)
        y = jnp.einsum("etf,efh->eth", a, w2,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("eth,te->th", y, full)

    def sorted16(x, weight, idx, w13, w2):
        return D.moe_experts_decode(x, weight, idx, w13, w2)[0]

    def sorted128(x, weight, idx, w13, w2):
        return D.moe_experts(x, weight, idx, w13, w2, 0, e,
                             128 if dev.platform == "tpu" else 8)[0] \
            .astype(jnp.float32)

    def walk(x, weight, idx, w13, w2):
        full = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], idx].set(weight.astype(jnp.float32))

        def one(i, acc):
            def run(acc):
                gu = jnp.dot(x, w13[i], preferred_element_type=jnp.float32)
                a = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
                y = jnp.dot(a, w2[i], preferred_element_type=jnp.float32)
                return acc + y * full[:, i][:, None]
            return jax.lax.cond(jnp.any(full[:, i] > 0), run,
                                lambda acc: acc, acc)
        return jax.lax.fori_loop(0, e, one, jnp.zeros((t, h), jnp.float32))

    want = None
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    touched = int(jnp.unique(idx).shape[0])
    for name, fn in (("dense", dense), ("sorted16", sorted16),
                     ("sorted128", sorted128), ("walk", walk)):
        jf = jax.jit(fn)
        got = jax.block_until_ready(jf(x, weight, idx, w13, w2))
        want = got if want is None else want
        err = float(jnp.max(jnp.abs(got - want)))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            got = jf(x, weight, idx, w13, w2)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - t0) / args.iters * 1e3
        rec = {"form": name, "ms_a_layer": ms, "max_abs_diff_to_dense": err,
               "rows": t, "experts_touched": touched,
               "device": dev.device_kind}
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
