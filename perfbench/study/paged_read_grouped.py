#!/usr/bin/env python3
"""The grouped decode read, one layer at a time: the matrix-unit form of
``paged_decode_attn`` against ``block_attention_gqa``'s table-sized gather,
on the chip, at the rows, lengths and pools of the three cells whose full
attention layers call it (PR 44).

    python3 perfbench/study/paged_read_grouped.py --shape jamba \\
        --out chiprun_out/paged_pr44.jsonl

``--shape mellum`` is ``mellum_code_16k``'s full layers (16 rows, 32 query
heads on 4 KV heads, a table of 64), ``--shape jamba``
``jamba_reasoning_6k``'s (64 rows, 20 query heads on 1 KV head, a table of
32), ``--shape lfm2`` ``lfm2_agents_3k``'s packed read (128 rows, 32 wide
query rows on 4 pool heads, a table of 16); all over 256-row bfloat16
blocks. Each row stands at a prompt of its cell's multiset's range plus a
uniform share of an answer, over a pool of the cell's size. The lines are
``paged_read.py``'s (whose ``--shape mellum`` draws GPT's lengths stretched
to 16k instead): ``us_layer`` the wall time of ``--layers`` dependent calls
in one program over their count, ``live_gb_s`` the K and V bytes of the
rows' live blocks over it, ``max_abs_diff`` against the composed read.
"""

import argparse
import importlib
import json
import math
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from paged_read import time_form  # noqa: E402

D, BS = 128, 256
#: query heads, KV heads (rows of the pool), table entries, pool blocks, the
#: rows of the cell's decode step, its prompts' and its answers' ranges
#: (log-uniform: the traffic files' knots)
SHAPES = {
    "mellum": (32, 4, 64, 801, 16, (2048, 12288), (256, 768)),
    "jamba": (20, 1, 32, 1537, 64, (256, 4096), (512, 2048)),
    "lfm2": (32, 4, 16, 1537, 128, (128, 2048), (256, 1024)),
}


def cell_lengths(rows, rng, cap, prompts, answers):
    """Committed lengths of one step's rows under a table of ``cap``
    positions."""
    def log_uniform(lo, hi):
        return [int(math.exp(rng.uniform(math.log(lo), math.log(hi))))
                for _ in range(rows)]
    return [min(p + int(rng.uniform(0, a)), cap - 1)
            for p, a in zip(log_uniform(*prompts), log_uniform(*answers))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--seed", type=int, default=44)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rows", type=int, default=0,
                    help="default: the rows of the cell's decode step")
    ap.add_argument("--blocks", default="4,8,16")
    ap.add_argument("--shape", required=True, choices=tuple(SHAPES))
    ap.add_argument("--tiny", action="store_true",
                    help="a CPU rehearsal's sizes: nothing it prints is a "
                         "measurement")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import attention_ops
    # the package re-exports the function under the module's name
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    hq, h, t, pool_blocks, rows, prompts, answers = SHAPES[args.shape]
    d, bs, full = D, BS, t * BS
    rows = args.rows or rows
    if args.tiny:
        kv = min(h, 2)
        hq, h, d, bs, t, pool_blocks = hq // h * kv, kv, 32, 16, 8, 0
        args.layers, args.calls = 2, 1
    device = jax.devices()[0]
    seed = args.seed % 2 ** 32          # numpy's and jax's keys hold 32 bits
    lens = cell_lengths(rows, random.Random(args.seed), full, prompts,
                        answers)
    if args.tiny:
        lens = [n * (t * bs) // full for n in lens]
    live = [-(-(n + 1) // bs) for n in lens]
    nb = max(sum(live) + 1, pool_blocks)
    tables = np.zeros((rows, t), np.int32)
    perm = np.random.RandomState(seed).permutation(np.arange(1, nb))
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = perm[at:at + n]
        at += n
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (rows, hq, 1, d), jnp.float32)
    k_pool = jax.random.normal(kk, (nb, h, bs, d), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (nb, h, bs, d), jnp.bfloat16)
    ops = (q, k_pool, v_pool, jnp.asarray(tables),
           jnp.asarray(lens, jnp.int32))
    live_bytes = 2 * sum(live) * h * bs * d * k_pool.dtype.itemsize

    def composed(q, k, v, tables, pos):
        return attention_ops.block_attention_gqa(q, k, v, tables,
                                                 pos).astype(q.dtype)

    def kernel_at(n):
        def fn(q, k, v, tables, pos):
            pa.BLOCKS_A_STEP = n          # read when the call is traced
            return pa._paged_local(q, k, v, tables, pos, None, None,
                                   1.0 / math.sqrt(d), pa._interpret())
        return fn

    ref = np.asarray(composed(*ops), np.float32)
    forms = [("block_attention", composed)]
    forms += [(f"kernel_n{n}", kernel_at(int(n)))
              for n in args.blocks.split(",")]
    out_lines = []
    for name, fn in forms:
        rec = {"traffic": "cell", "rows": rows, "lengths": lens,
               "live_blocks": sum(live), "table_blocks": rows * t,
               "live_bytes": live_bytes, "form": name}
        try:
            diff = float(np.max(np.abs(
                np.asarray(fn(*ops), np.float32) - ref)))
            us = time_form(fn, ops, args.layers, args.calls)
            rec.update(us_layer=us, live_gb_s=live_bytes / us / 1e3,
                       max_abs_diff=diff)
        except Exception as e:   # a form the compiler refuses
            rec.update(error=str(e)[-400:])
        rec.update(device=f"{device.platform}:{device.device_kind}",
                   shape=args.shape, h_q=hq, h_kv=h, d=d, block_size=bs,
                   table=t, pool_dtype="bfloat16", layers=args.layers,
                   calls=args.calls, call="paged_read_grouped.py "
                   + " ".join(argv or sys.argv[1:]))
        line = json.dumps(rec)
        out_lines.append(line)
        print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(out_lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
