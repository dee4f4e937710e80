"""Flash attention as Pallas TPU kernels (forward + backward).

The reference's only fused attention is the inference-only CUDA
``multihead_matmul`` (paddle/fluid/operators/fused/multihead_matmul_op.cc:118);
its training attention materializes the full [b, h, s, s] probability
tensor (python/paddle/nn/layer/transformer.py:68). This module is the
TPU-native replacement: O(s) memory attention with online softmax in the
forward and a recomputing two-kernel backward (dq-kernel gridded over q
blocks; dk/dv-kernel gridded over k blocks), so nothing quadratic ever
touches HBM. Inputs may be bf16; all accumulation is fp32 on the MXU.

Layout: the kernels take q/k/v as [batch*heads, seq, head_dim]; the
public entry accepts [b, h, s, d] and collapses the leading axes into the
grid's first dim (per chip, when the program spans several).
The only saved residuals are (o, lse) — the backward recomputes the
probabilities blockwise, the standard flash-attention trade.

Causal masking is block-skipped: a q block only loops over k blocks at or
below its diagonal, halving causal FLOPs rather than masking dead work.

On a CPU backend (tests, virtual meshes) the kernels run in Pallas
interpreter mode, so the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .utils import (interpret_mode as _interpret, pad_lane_dim, pick_block,
                    shard_parallel)

NEG_INF = float("-inf")


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, causal, block_k, seq_k):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    jq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    n_kb = pl.cdiv(seq_k, block_k)
    hi = jnp.minimum((jq + 1) * block_q + block_k - 1, seq_k) // block_k \
        if causal else n_kb

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            row = jq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    grid = (bh, seq_q // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_k=seq_k)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq_k, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq_k, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # lse rides as [bh, 1, seq]: Mosaic requires the last two
            # block dims to be (div 8, div 128) or full — (1, block_q)
            # on a 2-D array satisfies neither, (1, 1, block_q) does.
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_k, seq_k):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    jq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    hi = jnp.minimum((jq + 1) * block_q + block_k - 1, seq_k) // block_k \
        if causal else pl.cdiv(seq_k, block_k)

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            row = jq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, seq_q):
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    jk = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lo = (jk * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32) \
            * scale
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            row = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, seq_q // block_q, body, (z, z))
    # q was pre-scaled, so dk already carries the scale factor
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, o, lse = res
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=seq_k),
        name="flash_bwd_dq",
        grid=(bh, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq_k, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, seq_k, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_q=seq_q),
        name="flash_bwd_dkv",
        grid=(bh, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((1, seq_q, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq_q, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# The [b, h, s, d] level is where a program that spans several chips
# meets the kernels: each (batch, head) pair is independent, so under
# utils.kernel_sharding these two wrappers run the 3-D kernels above on
# each chip's own shard of batch and heads (see utils.shard_parallel).

def _fwd_4d(q, k, v, causal, scale, block_q, block_k):
    b, h, seq_q, d = q.shape
    o, lse = _flash_fwd(*(a.reshape(b * h, a.shape[2], d)
                          for a in (q, k, v)),
                        causal, scale, block_q, block_k)
    return o.reshape(q.shape), lse.reshape(b, h, 1, seq_q)


def _bwd_4d(q, k, v, o, lse, do, causal, scale, block_q, block_k):
    b, h, seq_q, d = q.shape
    res = tuple(a.reshape(b * h, a.shape[2], d) for a in (q, k, v, o)) \
        + (lse.reshape(b * h, 1, seq_q),)
    dq, dk, dv = _flash_bwd(causal, scale, block_q, block_k, res,
                            do.reshape(b * h, seq_q, d))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_fwd_call = shard_parallel(_fwd_4d, ("bh--",) * 3, ("bh--", "bh--"))
_bwd_call = shard_parallel(_bwd_4d, ("bh--",) * 6, ("bh--",) * 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    return _fwd_call(q, k, v, causal, scale, block_q, block_k)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, g):
    return _bwd_call(*res, g, causal, scale, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=512, block_k=512):
    """Flash attention on [b, h, s, d] (or [bh, s, d]) inputs.

    Returns attention output with the input's shape/dtype. Raises
    ValueError for shapes the kernel cannot tile (caller decides the
    fallback); self-attention (seq_q == seq_k) plus cross shapes whose
    sequences are divisible by a power-of-two block are supported.

    Sequence-length limit: every grid step keeps the WHOLE K and V of
    one head in VMEM (the ``(1, seq_k, d)`` blocks above; q, do, lse and
    delta likewise in the dk/dv kernel). At d=128 bf16 the v5e compiler
    accepts forward and backward through s=8192 and refuses s=16384
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem") —
    at compile time under jit, so no ValueError fallback sees it. Longer
    sequences need K/V streamed by block first (ROADMAP S5 / R7a).

    Inside a program that spans several chips (utils.kernel_sharding)
    the kernels run on each chip's own (batch, head) shard; sequence and
    head_dim are whole per chip.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[:, None], k[:, None], v[:, None]
    b, h, seq_q, d = q.shape
    seq_k = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = pick_block(seq_q, block_q, minimum=16)
    bk = pick_block(seq_k, block_k, minimum=16)
    if not bq or not bk:
        raise ValueError(
            f"flash_attention: cannot tile seq_q={seq_q}, seq_k={seq_k}")
    if causal and seq_q != seq_k:
        raise ValueError("causal flash_attention requires seq_q == seq_k")
    # head_dim rides the lane axis whole; an unaligned width is padded
    # with zero columns (k's zero columns contribute nothing to the
    # logits, v's produce zero output columns sliced off below) rather
    # than rejected — pick_block's divisor rule never applies to d.
    dp = pad_lane_dim(d)
    if dp != d:
        pad = [(0, 0), (0, 0), (0, 0), (0, dp - d)]
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    out = _flash(q, k, v, causal, float(scale), bq, bk)
    if dp != d:
        out = out[..., :d]
    return out[:, 0] if squeeze else out
