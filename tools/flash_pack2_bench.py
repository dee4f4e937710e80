#!/usr/bin/env python
"""Head-packing experiment for the d=64 flash-attention MXU ceiling.

PERF.md's decomposition: with head_dim 64, both attention matmuls
contract/emit over 64 of the MXU's 128 lanes — a structural ~50%
ceiling on the matmul portion (GPT-2 geometry). Hypothesis: pack TWO
heads per kernel instance — q rides as [bq, 128] (head pair
concatenated along d) and k/v blocks expand to BLOCK-DIAGONAL
[2*bk, 128] so that

    s2  = q  @ K_bd^T -> [bq, 2*bk]   (both heads' logits, one pass)
    acc = p2 @ V_bd   -> [bq, 128]    (both heads' outputs, one pass)

every MXU pass contracts and emits the full 128 lanes. Half the MACs
multiply zeros, so the FLOP count doubles — the bet is that a
64-contraction pass already costs a full pass, making the packed form
2x on paper. The online softmax segments per head ([bq, 2, bk] view).

Forward-only: this is a measurement probe (round-4 item 7); if
it wins, the packed layout graduates into ops/pallas/flash_attention
with a backward. Run on the real chip:

    python tools/flash_pack2_bench.py          # prints one JSON line

Amortizes with an in-graph lax.scan chain, so per-dispatch host time
does not enter the kernel's figure.
"""

import functools
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from paddle_tpu.ops.pallas.flash_attention import _flash_fwd  # noqa: E402
from paddle_tpu.ops.pallas.utils import interpret_mode  # noqa: E402

NEG_INF = float("-inf")


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal,
                       block_k, seq_k):
    block_q, d2 = q_ref.shape[1], q_ref.shape[2]      # d2 = 128
    d = d2 // 2
    jq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    hi = jnp.minimum((jq + 1) * block_q + block_k - 1, seq_k) // block_k \
        if causal else pl.cdiv(seq_k, block_k)

    def body(kb, carry):
        # m/l carried per head half as [bq, 1] (Mosaic-friendly: no
        # repeat/reshape layout casts)
        m1, m2, l1, l2, acc = carry
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(
            jnp.float32)
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(
            jnp.float32)
        z = jnp.zeros((block_k, d), jnp.float32)
        # block-diagonal packing: rows 0..bk are head-1, bk.. head-2
        k_bd = jnp.concatenate(
            [jnp.concatenate([kblk[:, :d], z], 1),
             jnp.concatenate([z, kblk[:, d:]], 1)], 0)   # [2bk, 128]
        v_bd = jnp.concatenate(
            [jnp.concatenate([vblk[:, :d], z], 1),
             jnp.concatenate([z, vblk[:, d:]], 1)], 0)
        s2 = jax.lax.dot_general(q, k_bd, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if causal:
            row = jq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 2 * block_k), 0)
            col = kb * block_k + jnp.mod(jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 2 * block_k), 1), block_k)
            s2 = jnp.where(row >= col, s2, NEG_INF)
        s_a = s2[:, :block_k]
        s_b = s2[:, block_k:]
        m1n = jnp.maximum(m1, jnp.max(s_a, axis=1, keepdims=True))
        m2n = jnp.maximum(m2, jnp.max(s_b, axis=1, keepdims=True))
        a1 = jnp.exp(m1 - m1n)
        a2 = jnp.exp(m2 - m2n)
        p_a = jnp.exp(s_a - m1n)
        p_b = jnp.exp(s_b - m2n)
        l1n = a1 * l1 + jnp.sum(p_a, axis=1, keepdims=True)
        l2n = a2 * l2 + jnp.sum(p_b, axis=1, keepdims=True)
        scaled = jnp.concatenate([acc[:, :d] * a1, acc[:, d:] * a2], 1)
        acc = scaled + jax.lax.dot_general(
            jnp.concatenate([p_a, p_b], 1), v_bd,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m1n, m2n, l1n, l2n, acc

    neg = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    zero = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d2), jnp.float32)
    m1, m2, l1, l2, acc = jax.lax.fori_loop(
        0, hi, body, (neg, neg, zero, zero, acc0))
    o_ref[0] = jnp.concatenate([acc[:, :d] / l1, acc[:, d:] / l2],
                               1).astype(o_ref.dtype)


def packed_flash_fwd(q, k, v, causal, scale, block_q, block_k):
    """q/k/v [bh2, s, 128] (head pairs concatenated along d)."""
    bh2, seq_q, d2 = q.shape
    seq_k = k.shape[1]
    kernel = functools.partial(_packed_fwd_kernel, scale=scale,
                               causal=causal, block_k=block_k,
                               seq_k=seq_k)
    return pl.pallas_call(
        kernel,
        grid=(bh2, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d2), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq_k, d2), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq_k, d2), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d2), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret_mode(),
    )(q, k, v)


def pack_pairs(x):
    """[b, h, s, d] -> [b*h/2, s, 2d] (adjacent head pairs)."""
    b, h, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h // 2, 2, s, d), 2, 3).reshape(
        b * h // 2, s, 2 * d)


def _time_scan(fn, args, iters=50):
    """In-graph scan chain, synchronized by a scalar fetch."""

    def chained(a):
        def step(carry, _):
            out = fn(*[x + carry * 0 for x in a])
            return jnp.sum(out) * 1e-12, None
        s, _ = jax.lax.scan(step, jnp.float32(0), None, length=iters)
        return s

    f = jax.jit(chained)
    float(f(args))                      # compile + warm
    t0 = time.perf_counter()
    float(f(args))
    dt = time.perf_counter() - t0
    return dt / iters


def main():
    b, h, s, d = 8, 16, 1024, 64
    bq = bk = 512
    scale = 1.0 / math.sqrt(d)
    rng = np.random.RandomState(0)
    qkv = [jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
           for _ in range(3)]
    q3 = [x.reshape(b * h, s, d) for x in qkv]
    qp = [pack_pairs(x) for x in qkv]

    # numerical check (fp32, interpreter-safe shapes)
    o_ref, _ = _flash_fwd(*[x.astype(jnp.float32) for x in q3], True,
                          scale, bq, bk)
    o_pk = packed_flash_fwd(*[x.astype(jnp.float32) for x in qp], True,
                            scale, bq, bk)
    o_pk_un = jnp.swapaxes(
        o_pk.reshape(b, h // 2, s, 2, d), 2, 3).reshape(b * h, s, d)
    err = float(jnp.max(jnp.abs(o_ref - o_pk_un)))
    assert err < 2e-3, f"packed kernel numerics off: {err}"

    t_base = _time_scan(
        lambda q, k, v: _flash_fwd(q, k, v, True, scale, bq, bk)[0], q3)
    t_pack = _time_scan(
        lambda q, k, v: packed_flash_fwd(q, k, v, True, scale, bq, bk),
        qp)
    print(json.dumps({
        "metric": "flash_fwd_pack2_speedup",
        "value": round(t_base / t_pack, 3), "unit": "x",
        "base_ms": round(t_base * 1e3, 3),
        "packed_ms": round(t_pack * 1e3, 3),
        "shape": [b, h, s, d], "blocks": [bq, bk],
        "max_abs_err": err,
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }))


if __name__ == "__main__":
    main()
