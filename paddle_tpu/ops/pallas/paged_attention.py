"""Fused paged decode attention for the block-paged serving KV cache.

One kernel replaces the serving decode hot path's XLA chain
(``block_gather`` -> QK^T -> masked softmax -> V): the grid runs over
``(batch, heads, table_slots)`` with the block table scalar-prefetched,
so each step streams ONE physical KV block straight from the pool into
VMEM via the table lookup in the BlockSpec index_map — the gathered
[b, h, T*block_size, d] cache view is never materialized. Softmax is the
standard online form (running max ``m``, normalizer ``l`` and output
accumulator carried in VMEM scratch across the sequential innermost grid
axis, flash-attention style) so memory stays O(block) per step.

Masking mirrors the clamping contract in
:func:`~paddle_tpu.ops.attention_ops.block_gather` /
``decode_attention_mask``: key position ``j`` (logical, ``t*block_size +
lane``) is valid for query row ``i`` iff ``j <= pos[b] + i``. Table
entries past a request's reservation point at the trash block, and every
logical position backed by them sits at/beyond the reservation — hence
beyond ``pos + s`` — so the position mask also masks trash rows exactly;
whole blocks past ``pos + s - 1`` are skipped with ``pl.when`` without
reading them. Block 0 of the walk always holds key 0 (valid for every
query row), so the normalizer is strictly positive.

int8 KV pools ride the same kernel: per-block-per-head absmax scales are
prefetched alongside each code block and applied as ``codes * scale /
127`` — bit-identical to the XLA oracle's
:func:`~paddle_tpu.ops.attention_ops.block_gather_dequant` math, which
is what makes kernel-vs-reference equality testable at int8.

Runs under the Pallas interpreter on CPU backends (same
``interpret_mode`` policy as ``flash_attention``), compiled via Mosaic
on TPU. Awkward head dims are zero-padded to :func:`pad_lane_dim` width
and sliced back (q is padded per call — cheap; pools only when actually
misaligned, which the standard 32/64/128 head dims never are).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import (LANE, interpret_mode as _interpret, pad_lane_dim,
                    shard_parallel)

NEG_INF = float("-inf")

#: int8 symmetric grid max — must match ops.quant_ops.KV_QMAX
_KV_QMAX = 127.0


def _kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
            block_size: int, q_len: int, scale: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, t = pl.program_id(0), pl.program_id(2)
    num_t = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos_b = pos_ref[b]

    # skip blocks that start past the last valid key (pos + q_len - 1);
    # every lane in them would be masked anyway — including trash-backed
    # table padding, whose logical positions sit beyond the reservation
    @pl.when(t * block_size <= pos_b + (q_len - 1))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [s, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bs, d]
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            k = k * (ks_ref[0, 0, 0, 0] / _KV_QMAX)
            v = v * (vs_ref[0, 0, 0, 0] / _KV_QMAX)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [s, bs]
        key_pos = t * block_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        q_pos = pos_b + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 0)
        logits = jnp.where(key_pos <= q_pos, logits, NEG_INF)

        m_prev = m_ref[...]                                  # [s, LANE]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_new)                      # [s, LANE]
        p = jnp.exp(logits - m_new[:, :1])                   # [s, bs]
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == num_t - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, tables, pos, *,
                    k_scale=None, v_scale=None, scale=None,
                    interpret=None):
    """Fused paged decode/verify attention over the block pool.

    Args:
      q: [batch, heads, q_len, head_dim] queries (decode q_len=1,
        speculative verify q_len=K+1).
      k_pool / v_pool: [num_blocks, heads, block_size, head_dim] KV
        pools (f32/bf16, or int8 codes when scales are given).
      tables: [batch, T] int32 block tables (host-side values; padding
        entries point at the trash block).
      pos: [batch] int32 committed lengths; query row i sits at
        absolute position ``pos[b] + i``.
      k_scale / v_scale: optional [num_blocks, heads] f32 absmax scales
        — both present selects the int8 dequantizing path.
      scale: logit scale, default ``1/sqrt(head_dim)`` (the original,
        pre-padding head_dim).
      interpret: force the Pallas interpreter; default follows
        ``interpret_mode()`` (on for CPU backends).

    Returns [batch, heads, q_len, head_dim] in q's dtype, equal to
    :func:`~paddle_tpu.ops.attention_ops.paged_attention_reference`.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    b, h, s, d = q.shape
    nb, hp, bs, dpool = k_pool.shape
    if (hp, dpool) != (h, d) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool shape {k_pool.shape}/{v_pool.shape} does not match "
            f"q {q.shape}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret()

    dp = pad_lane_dim(d)
    if dp != d:
        pad = [(0, 0), (0, 0), (0, 0), (0, dp - d)]
        q = jnp.pad(q, pad)
        k_pool = jnp.pad(k_pool, pad)
        v_pool = jnp.pad(v_pool, pad)

    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if k_scale is None:
        out = _paged_call(q, k_pool, v_pool, tables, pos,
                          float(scale), bool(interpret))
    else:
        out = _paged_quant_call(
            q, k_pool, v_pool, tables, pos,
            jnp.asarray(k_scale, jnp.float32),
            jnp.asarray(v_scale, jnp.float32),
            float(scale), bool(interpret))
    return out[..., :d] if dp != d else out


def _paged_local(q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                 scale, interpret):
    """The kernel call on what one chip holds: q [b, h, s, dp] (dp
    lane-aligned), pools [nb, h, bs, dp], tables [b, T], pos [b],
    optional scales [nb, h]."""
    quant = k_scale is not None
    b, h, s, dp = q.shape
    nb, _, bs, _ = k_pool.shape
    T = tables.shape[1]
    tables_flat = tables.reshape(-1)

    qkv_specs = [
        pl.BlockSpec((1, 1, s, dp), lambda b, h, t, tbl, pos: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, dp),
                     lambda b, h, t, tbl, pos: (tbl[b * T + t], h, 0, 0)),
        pl.BlockSpec((1, 1, bs, dp),
                     lambda b, h, t, tbl, pos: (tbl[b * T + t], h, 0, 0)),
    ]
    operands = [tables_flat, pos, q, k_pool, v_pool]
    if quant:
        qkv_specs += [
            pl.BlockSpec((1, 1, 1, 1),
                         lambda b, h, t, tbl, pos: (tbl[b * T + t], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1),
                         lambda b, h, t, tbl, pos: (tbl[b * T + t], h, 0, 0)),
        ]
        operands += [k_scale.reshape(nb, h, 1, 1),
                     v_scale.reshape(nb, h, 1, 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, T),
        in_specs=qkv_specs,
        out_specs=pl.BlockSpec(
            (1, 1, s, dp), lambda b, h, t, tbl, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s, LANE), jnp.float32),   # running max m
            pltpu.VMEM((s, LANE), jnp.float32),   # normalizer l
            pltpu.VMEM((s, dp), jnp.float32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_size=bs, q_len=s,
                          scale=scale, quant=quant),
        name="paged_decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dp), q.dtype),
        interpret=interpret,
    )(*operands)


def _paged_float(q, k_pool, v_pool, tables, pos, scale, interpret):
    return _paged_local(q, k_pool, v_pool, tables, pos, None, None,
                        scale, interpret)


# Every (batch row, head) pair is independent, so a tensor-parallel
# engine (heads on the mesh's model axis) runs the kernel on each chip's
# own heads of q and of the pools; the pool's block axis, the block rows
# and head_dim stay whole per chip (see utils.shard_parallel).
_paged_call = shard_parallel(
    _paged_float, ("bh--", "-h--", "-h--", "b-", "b"), ("bh--",))
_paged_quant_call = shard_parallel(
    _paged_local,
    ("bh--", "-h--", "-h--", "b-", "b", "-h", "-h"), ("bh--",))
