#!/usr/bin/env python3
"""The two ways to the expert layer's grouped products, timed on the chip
at the cell's shapes (32 held experts of width 512 under hidden 2048,
16,384 pairs drawn evenly over the experts, bfloat16): the repo's own
kernels (``paddle_tpu/ops/pallas/grouped_matmul.py``: groups padded to
tiles of 128 rows, a tile belongs to one expert) and JAX's TPU ``megablox``
``gmm`` / ``tgmm`` (unpadded groups, a tile visited once for every group it
holds rows of), at its default tiling and at one matched to the shapes.

    python3 perfbench/study/gmm_paths.py --out chiprun_out/p27/gmm_paths.jsonl

Each case runs ten calls inside one compiled program (so a launch does not
count) on operands ``x + i`` (so no call is folded into another), the median
of five such programs, less a program that only writes those operands out; a
dense matrix product of the same FLOPs is the yardstick (its operand's add
is fused into the product, so nothing is taken off it: PR 27's first run
did, and read the dense product above the chip's peak). What decided: PERF.md section 6, PR 27.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import importlib
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("gmm_paths needs the TPU")
    g, h, f, pairs = 32, 2048, 512, 16384
    r = np.random.RandomState(args.seed)
    sizes = jnp.asarray(r.multinomial(pairs, np.ones(g) / g), jnp.int32)
    rows = 2 * pairs + g * gm.TILE_M                 # the cell's fast buffer
    tg, na, _ = gm.tile_layout(sizes, gm.TILE_M, rows // gm.TILE_M)

    def arr(*shape):
        return jnp.asarray(r.randn(*shape) * 0.05, jnp.bfloat16)
    xs, hid, act = arr(rows, h), arr(rows, 2 * f), arr(rows, f)
    w13, w2 = arr(g, h, 2 * f), arr(g, f, h)
    # megablox takes the rows unpadded: exactly sum(sizes) of them
    xs_u, hid_u = xs[:pairs], hid[:pairs]
    records = []

    def bench(name, fn, *a, flops, fused=False):
        ten = jax.jit(lambda *a: [fn(*[x + i if j == 0 else x
                                       for j, x in enumerate(a)])
                                  for i in range(10)])
        add = jax.jit(lambda x: [x + i for i in range(10)])
        jax.block_until_ready(ten(*a))
        jax.block_until_ready(add(a[0]))

        def timed(f_, *b):
            out = []
            for _ in range(5):
                t = time.perf_counter()
                jax.block_until_ready(f_(*b))
                out.append(time.perf_counter() - t)
            return statistics.median(out)
        # a kernel's operand ``x + i`` is written out first, which the
        # ``add`` program times alone; XLA fuses it into a dense product's
        # operand read (``fused``), where subtracting it would over-subtract
        ms = (timed(ten, *a) - (0.0 if fused else timed(add, a[0]))) \
            / 10 * 1e3
        rec = {"tag": "gmm_paths", "case": name, "ms_a_call": round(ms, 4),
               "tflop_s": round(flops / ms / 1e9, 1),
               "device": dev.device_kind, "seed": args.seed}
        records.append(rec)
        print(json.dumps(rec), flush=True)
    up = 2.0 * pairs * h * 2 * f
    bench("dense [16384,2048]x[2048,1024]", lambda a, w: a @ w,
          xs_u, w13[0], flops=up, fused=True)
    bench("own gmm up", lambda a, w: gm.gmm(a, w, tg, na, name="moe_up"),
          xs, w13, flops=up)
    bench("own gmm up_dx", lambda a, w: gm.gmm(
        a, w, tg, na, name="moe_up_dx", transpose_rhs=True), hid, w13,
        flops=up)
    bench("own tgmm up_dw", lambda a, b: gm.tgmm(
        a, b, tg, na, g, name="moe_up_dw"), xs, hid, flops=up)
    for tiling in ((128, 128, 128), (512, 1024, 1024)):
        t = "x".join(map(str, tiling))
        bench(f"megablox gmm up {t}", lambda a, w: mb.gmm(
            a, w, sizes, jnp.bfloat16, tiling), xs_u, w13, flops=up)
        bench(f"megablox gmm up_dx {t}", lambda a, w: mb.gmm(
            a, w, sizes, jnp.bfloat16, tiling, transpose_rhs=True),
            hid_u, w13, flops=up)
        bench(f"megablox tgmm up_dw {t}", lambda a, b: mb.tgmm(
            a.T, b, sizes, jnp.bfloat16, tiling), xs_u, hid_u, flops=up)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as fh:
        for rec in records:
            fh.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
