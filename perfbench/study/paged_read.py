#!/usr/bin/env python3
"""The decode step's KV read, one layer at a time: the paged kernel
(``ops/pallas/paged_attention.py``, PR 39) against the composed read it
replaced (``ops/attention_ops.block_attention``: the gathered table and two
reductions over it), on the chip, at ``cgpt-1p3b``'s pool.

    python3 perfbench/study/paged_read.py --out chiprun_out/paged_pr39.jsonl

One line a (traffic, rows, form): lengths drawn as ``decode_heavy``,
``chat_steady`` and ``docs_offline`` hold them in a step (below), 8 and 32
rows, the kernel at 4 / 8 / 16 blocks a compute step, ``block_attention``,
and once the grid PR 8's kernel had (batch x heads x table slots, one
``[16, 128]`` block a grid step: kept here, since the program no longer has
it). ``us_layer`` is the wall time of ``--layers`` dependent calls in one
program over their count; ``live_gb_s`` the K and V bytes of the rows' live
blocks over it (the least a read can move); ``max_abs_diff`` against the
composed read on the same inputs. ``--shape mellum`` times the grouped
shape S2 (b) waits for (32 query heads on 4 KV heads, 256-row bfloat16
blocks) against ``block_attention_gqa``.

What a step holds, by traffic (rows of 8; at 32 the same four times):
``decode_heavy`` prompts 16-64 log-spaced + a uniform share of answers
512-840; ``chat_steady`` one or two live rows (occupancy 16%) at prompts
16-768 + up to 256, the others dead (length 0); ``docs_offline`` prompts
512-960 + up to 64.
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

H, D, BS, T = 16, 128, 16, 64


def lengths(traffic, rows, rng):
    """Committed lengths of one step's rows (0 = a dead slot)."""
    def log_uniform(lo, hi, n):
        return [int(math.exp(rng.uniform(math.log(lo), math.log(hi))))
                for _ in range(n)]
    if traffic == "decode_heavy":
        return [p + int(rng.uniform(0, a)) for p, a in
                zip(log_uniform(16, 64, rows), log_uniform(512, 840, rows))]
    if traffic == "chat_steady":
        live = [p + int(rng.uniform(0, a)) for p, a in
                zip(log_uniform(16, 768, rows), log_uniform(16, 256, rows))]
        return [min(n, T * BS - 1) if i % 8 < (1 + (i // 8) % 2) else 0
                for i, n in enumerate(live)]
    if traffic == "docs_offline":
        return [min(p + int(rng.uniform(0, a)), T * BS - 1) for p, a in
                zip(log_uniform(512, 960, rows), log_uniform(16, 64, rows))]
    raise ValueError(traffic)


def old_grid_kernel(q, k_pool, v_pool, tables, pos):
    """PR 8's walk: grid (batch, heads, table slots), one pool block of
    one head a grid step through the BlockSpec's table lookup."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, dp = q.shape
    bs = k_pool.shape[2]
    n_t = tables.shape[1]
    scale = 1.0 / math.sqrt(dp)

    def kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
               acc_ref):
        bb, t = pl.program_id(0), pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        pos_b = pos_ref[bb]

        @pl.when(t * bs <= pos_b + (s - 1))
        def _step():
            qq = q_ref[0, 0].astype(jnp.float32) * scale
            k = k_ref[0, 0].astype(jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32)
            logits = jax.lax.dot_general(
                qq, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            key_pos = t * bs + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1)
            q_pos = pos_b + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 0)
            logits = jnp.where(key_pos <= q_pos, logits, -jnp.inf)
            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1)[:, None])
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new[:, :1])
            m_ref[...] = m_new
            l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
            acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(t == pl.num_programs(2) - 1)
        def _fin():
            o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

    def at_table(bb, hh, t, tbl, pos):
        return (tbl[bb * n_t + t], hh, 0, 0)

    def at_row(bb, hh, t, tbl, pos):
        return (bb, hh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, h, n_t),
        in_specs=[pl.BlockSpec((1, 1, s, dp), at_row),
                  pl.BlockSpec((1, 1, bs, dp), at_table),
                  pl.BlockSpec((1, 1, bs, dp), at_table)],
        out_specs=pl.BlockSpec((1, 1, s, dp), at_row),
        scratch_shapes=[pltpu.VMEM((s, 128), jnp.float32),
                        pltpu.VMEM((s, 128), jnp.float32),
                        pltpu.VMEM((s, dp), jnp.float32)])
    return pl.pallas_call(
        kernel, name="paged_decode_attn_grid", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dp), q.dtype),
        interpret=jax.default_backend() == "cpu",
    )(tables.reshape(-1), pos, q, k_pool, v_pool)


def time_form(fn, args, layers, calls):
    """us a layer: ``layers`` dependent applications of ``fn`` in one
    program (each one's q is the one before's result plus q), ``calls``
    runs of it after two that warm."""
    import jax

    @jax.jit
    def many(q, *rest):
        def body(_, x):
            return q + 1e-3 * fn(x, *rest)
        return jax.lax.fori_loop(0, layers, body, q)

    for _ in range(2):
        many(*args).block_until_ready()
    t = time.perf_counter()
    for _ in range(calls):
        out = many(*args)
    out.block_until_ready()
    return (time.perf_counter() - t) / (calls * layers) * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--seed", type=int, default=39)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rows", default="8,32")
    ap.add_argument("--traffic",
                    default="decode_heavy,chat_steady,docs_offline")
    ap.add_argument("--blocks", default="4,8,16")
    ap.add_argument("--shape", default="gpt", choices=("gpt", "mellum"))
    ap.add_argument("--tiny", action="store_true",
                    help="a CPU rehearsal's sizes: nothing it prints is a "
                         "measurement")
    args = ap.parse_args(argv)
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np
    import importlib
    from paddle_tpu.ops import attention_ops
    # the package re-exports the function under the module's name
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    global H, D, BS, T
    hq, dtype = H, jnp.float32
    if args.shape == "mellum":
        hq, H, BS, dtype = 32, 4, 256, jnp.bfloat16
    if args.tiny:
        hq, H, D, BS, T = hq // H * 2, 2, 32, 8 if BS == 16 else 16, 8
        args.layers, args.calls = 2, 1
    device = jax.devices()[0]
    rng = random.Random(args.seed)
    out_lines = []

    def emit(rec):
        rec.update(device=f"{device.platform}:{device.device_kind}",
                   shape=args.shape, h_q=hq, h_kv=H, d=D, block_size=BS,
                   table=T, pool_dtype=jnp.dtype(dtype).name,
                   layers=args.layers, calls=args.calls,
                   call="paged_read.py " + " ".join(argv or sys.argv[1:]))
        line = json.dumps(rec)
        out_lines.append(line)
        print(line, flush=True)

    def composed(q, k, v, tables, pos):
        if hq == H:
            return attention_ops.block_attention(q, k, v, tables, pos)
        return attention_ops.block_attention_gqa(q, k, v, tables,
                                                 pos).astype(q.dtype)

    def kernel_at(n):
        def fn(q, k, v, tables, pos):
            pa.BLOCKS_A_STEP = n          # read when the call is traced
            return pa._paged_local(q, k, v, tables, pos, None, None,
                                   1.0 / math.sqrt(D), pa._interpret())
        return fn

    for rows in (int(r) for r in args.rows.split(",")):
        for traffic in args.traffic.split(","):
            lens = lengths(traffic, rows, rng)
            if args.shape == "mellum":
                lens = [n * 16 for n in lens]        # contexts to 16k
            if args.tiny:
                lens = [n * (T * BS) // (1024 * (16 if args.shape == "mellum"
                                                 else 1)) for n in lens]
            live = [-(-(n + 1) // BS) for n in lens]
            nb = sum(live) + 1
            nb = max(nb, 400 if rows == 8 and not args.tiny else nb)
            tables = np.zeros((rows, T), np.int32)
            perm = np.random.RandomState(args.seed).permutation(
                np.arange(1, nb))
            at = 0
            for i, n in enumerate(live):
                if lens[i]:
                    tables[i, :n] = perm[at:at + n]
                    at += n
            key = jax.random.PRNGKey(args.seed)
            kq, kk, kv = jax.random.split(key, 3)
            q = jax.random.normal(kq, (rows, hq, 1, D), jnp.float32)
            k_pool = jax.random.normal(kk, (nb, H, BS, D), dtype)
            v_pool = jax.random.normal(kv, (nb, H, BS, D), dtype)
            ops = (q, k_pool, v_pool, jnp.asarray(tables),
                   jnp.asarray(lens, jnp.int32))
            live_bytes = 2 * sum(live) * H * BS * D * k_pool.dtype.itemsize
            base = {"traffic": traffic, "rows": rows, "lengths": lens,
                    "live_blocks": sum(live), "table_blocks": rows * T,
                    "live_bytes": live_bytes}
            ref = np.asarray(composed(*ops), np.float32)
            forms = [("block_attention", composed)]
            forms += [(f"kernel_n{n}", kernel_at(int(n)))
                      for n in args.blocks.split(",")]
            if args.shape == "gpt" and rows == 8 and traffic == "decode_heavy":
                forms.append(("old_grid", old_grid_kernel))
            for name, fn in forms:
                try:
                    diff = float(np.max(np.abs(
                        np.asarray(fn(*ops), np.float32) - ref)))
                    us = time_form(fn, ops, args.layers, args.calls)
                    emit(dict(base, form=name, us_layer=us,
                              live_gb_s=live_bytes / us / 1e3,
                              max_abs_diff=diff))
                except Exception as e:   # a form the compiler refuses
                    emit(dict(base, form=name, error=str(e)[-400:]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(out_lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
