"""Multi-head latent attention's two reads, as Pallas TPU kernels.

A token of a latent-attention layer keeps ONE vector for all its heads:
the normed latent ``c`` (``r`` values, from which every head's key and
value are up-projections) and the rotated key ``k_r`` (``dr`` values, the
same for every head). The serving pool holds it as ``[blocks, r + dr,
block_size]``: no head axis, a token a lane (``seam.CacheKind`` with no
pair), so a width that is no multiple of 128 pads nothing.

``mla_decode_attn`` (:func:`mla_paged_attention`) is the decode step's
read in the **absorbed** form: the key's up-projection is folded into the
query outside (``q_l[h] = q_n[h] W_uk[h]^T``), the value's is applied to
the result outside, and the kernel reads the pool as it is held. The grid
walks the requests; a request's live blocks go from the pool (HBM) to VMEM
through its block table, ``n`` a compute step, the next step's copies (the
request's next blocks, or the next request's first) in flight meanwhile,
as ``paged_attention.py`` walks K and V. A block is one ``[r + dr, bs]``
tile and both products go through the matrix unit with the heads as the
matrix's rows: ``S = Q_l C + Q_r K_r`` (``[H, r] x [r, bs]``) and, under an
online softmax in float32, ``O_l += P C^T`` (``[H, bs] x [bs, r]``). K and V
of the heads are never materialised. At 128 heads over 576 + 512 values
that is 278.5 kFLOP against 1152 B a cached token in bfloat16, 242 FLOP a
byte against the v5e's 240: the first decode read of this repo that is not
plainly bound by memory.

``mla_prompt_attn`` (:func:`mla_prompt_attention`) is a prompt's read in
the **materialised** form: a flash forward whose key is 192 wide (every
head's own ``k_n`` of 128 beside the ONE rotated ``k_r`` of 64, which is
passed once and never broadcast over the heads) and whose value is 128
wide, with K and V streamed by block through the grid (a 16384-row prompt
keeps no whole head in VMEM), causal blocks above the diagonal and query
blocks past a prompt's ``live`` rows skipped (their index maps repeat the
block before, so nothing is copied for them either; skipped query blocks
come out zero).

Both run under the Pallas interpreter on CPU backends.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode as _interpret, pick_block

#: masked logits and the running max's start: finite (see
#: ``paged_attention._MASKED``)
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)

#: pool blocks a compute step of the decode read, at most, and the VMEM
#: their double buffer may take
BLOCKS_A_STEP = 4
_BUFFER_BYTES = 4 << 20

#: query and key rows of one tile of the prompt read: at 16384 rows of 32
#: heads a pass takes 27.2 ms at 1024 x 1024 and 45.5 at 1024 x 512 (a
#: tile's rescaling of the accumulator and its grid step are paid half as
#: often), against 30.3 for chunked XLA over tiles to the frontier
#: (``perfbench/study/prompt_read_forms_dotsvlm.py``; PERF.md, PR 49)
PROMPT_BLOCK_Q = 1024
PROMPT_BLOCK_K = 1024


def _exact(dtype):
    """float32 operands are multiplied as float32 whatever the process's
    default; a bfloat16 pool's in one pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT


# ------------------------------------------------------------------ decode

def _decode_kernel(tbl_ref, pos_ref, ql_ref, qr_ref, pool_hbm, o_ref, buf,
                   sem, slot_ref, m_ref, l_ref, acc_ref, *, n: int, T: int,
                   rank: int):
    b, B = pl.program_id(0), pl.num_programs(0)
    bs = buf.shape[3]
    f32 = jnp.float32
    exact = _exact(buf.dtype)

    def live_blocks(r):
        return jnp.minimum((pos_ref[r] + bs) // bs, T)

    def copies(r, c, slot, go):
        """Start (or wait for) the copies of request ``r``'s chunk ``c``."""
        def one(i, carry):
            blk = tbl_ref[r * T + c * n + i]
            go(pltpu.make_async_copy(pool_hbm.at[blk], buf.at[slot, i],
                                     sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n, live_blocks(r) - c * n), one, 0)

    def start(r, c, slot):
        copies(r, c, slot, lambda cp: cp.start())

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    nl = live_blocks(b)
    chunks = (nl + (n - 1)) // n
    pos_b = pos_ref[b]
    ql, qr = ql_ref[0], qr_ref[0]                       # [H, r], [H, dr]
    heads = ql.shape[0]
    m_ref[...] = jnp.full(m_ref.shape, _MASKED, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def block(t, slot, i):
        lat = buf[slot, i, pl.ds(0, rank), :]           # [r, bs]
        rope = buf[slot, i, pl.ds(rank, buf.shape[2] - rank), :]
        lg = jax.lax.dot_general(ql, lat, (((1,), (0,)), ((), ())),
                                 precision=exact,
                                 preferred_element_type=f32) \
            + jax.lax.dot_general(qr, rope, (((1,), (0,)), ((), ())),
                                  precision=exact,
                                  preferred_element_type=f32)
        kpos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (heads, bs), 1)
        lg = jnp.where(kpos <= pos_b, lg, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(lg - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(lat.dtype), lat, (((1,), (1,)), ((), ())),
            precision=exact, preferred_element_type=f32)

    def chunk(c, slot):
        # the copies after these: the request's next chunk, or the next
        # request's first
        last = c + 1 == chunks
        b_next = jnp.where(last, b + 1, b)

        @pl.when(b_next < B)
        def _():
            start(b_next, jnp.where(last, 0, c + 1), 1 - slot)

        copies(b, c, slot, lambda cp: cp.wait())

        def one(i, carry):
            block(c * n + i, slot, i)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n, nl - c * n), one, 0)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, chunks, chunk, slot_ref[0])
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "n"))
def _decode_call(q_lat, q_rope, pool, tables, pos, *, interpret, n):
    b, heads, rank = q_lat.shape
    dr = q_rope.shape[2]
    _, width, bs = pool.shape
    T = tables.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, rank), lambda i, tbl, pos: (i, 0, 0)),
            pl.BlockSpec((1, heads, dr), lambda i, tbl, pos: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, rank),
                               lambda i, tbl, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n, width, bs), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),            # the buffer's slot
            pltpu.VMEM((heads, 1), jnp.float32),    # running max m
            pltpu.VMEM((heads, 1), jnp.float32),    # normalizer l
            pltpu.VMEM((heads, rank), jnp.float32),  # output accumulator
        ])
    return pl.pallas_call(
        functools.partial(_decode_kernel, n=n, T=T, rank=rank),
        name="mla_decode_attn", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), q_lat.dtype),
        # the requests in order: a request's last step starts the next
        # one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1), pos, q_lat, q_rope, pool)


def mla_paged_attention(q_lat, q_rope, pool, tables, pos, *, scale: float,
                        interpret=None):
    """The absorbed decode read of one row a request over the latent pool.

    Args:
      q_lat: [batch, heads, r] each head's query folded through the key's
        up-projection (``q_n W_uk^T``), unscaled.
      q_rope: [batch, heads, dr] each head's rotated query part.
      pool: [num_blocks, r + dr, block_size] the latent pool: a token's
        normed latent then its rotated key, a token a lane.
      tables: [batch, T] int32 block tables; pos: [batch] int32 committed
        lengths (the row reads positions ``0 .. pos``; its own row is
        written before the call).
      scale: the logits' scale.

    Returns [batch, heads, r] in ``q_lat``'s dtype: each head's weighted
    sum of the latents, to be passed through the value's up-projection.
    ``tests/test_paged_attention.py`` holds it to a gather of the tables'
    blocks.
    """
    b, heads, rank = q_lat.shape
    if pool.ndim != 3 or pool.shape[1] != rank + q_rope.shape[2] or \
            q_rope.shape[:2] != (b, heads):
        raise ValueError(f"latent pool {pool.shape} does not match q "
                         f"{q_lat.shape} / {q_rope.shape}")
    if interpret is None:
        interpret = _interpret()
    # scaled in float32, multiplied in the pool's type
    ql = (q_lat.astype(jnp.float32) * scale).astype(pool.dtype)
    qr = (q_rope.astype(jnp.float32) * scale).astype(pool.dtype)
    block_bytes = pool.shape[1] * pool.shape[2] * pool.dtype.itemsize
    n = max(1, min(BLOCKS_A_STEP, tables.shape[1],
                   _BUFFER_BYTES // (2 * block_bytes)))
    return _decode_call(ql, qr, pool, jnp.asarray(tables, jnp.int32),
                        jnp.asarray(pos, jnp.int32),
                        interpret=bool(interpret), n=n).astype(q_lat.dtype)


# ------------------------------------------------------------------ prompt

def prompt_tiling(s: int):
    """(query rows, key rows) of one tile of a prompt of ``s`` rows."""
    bq = pick_block(s, PROMPT_BLOCK_Q, minimum=8)
    bk = pick_block(s, PROMPT_BLOCK_K, minimum=8)
    if not bq or not bk:
        raise ValueError(f"mla_prompt_attention: cannot tile {s} rows")
    return bq, bk


def prompt_pairs(b: int, s: int, live) -> int:
    """The live (query, key) pairs of a head's prompt read of ``b`` x ``s``
    rows whose prompts have ``live`` rows: the causal triangle, ``live
    (live + 1) / 2`` a prompt, whatever the tiles (the kernel multiplies
    whole tiles at or under the diagonal: the masked half of a diagonal
    tile and the last query block's padding are its own cost, not work
    the read asks for, so a coarser tile cannot raise a share counted
    from this)."""
    n = min(int(live), s)
    return b * (n * (n + 1) // 2)


def _prompt_kernel(live_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, heads: int):
    i, jq, jk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk = qn_ref.shape[1], kn_ref.shape[1]
    f32 = jnp.float32
    exact = _exact(qn_ref.dtype)
    runs = jq * bq < live_ref[i // heads]
    # the last key tile this query block reaches
    last = ((jq + 1) * bq - 1) // bk

    @pl.when(jk == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def tile(diagonal: bool):
        nt = (((1,), (1,)), ((), ()))
        lg = jax.lax.dot_general(qn_ref[0], kn_ref[0], nt, precision=exact,
                                 preferred_element_type=f32) \
            + jax.lax.dot_general(qr_ref[0], kr_ref[0], nt, precision=exact,
                                  preferred_element_type=f32)
        if scale != 1.0:
            lg = lg * scale
        if diagonal:
            row = jq * bq + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 0)
            col = jk * bk + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
            lg = jnp.where(row >= col, lg, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(lg - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            precision=exact, preferred_element_type=f32)

    # a tile wholly under the diagonal needs no mask: most of a long
    # prompt's tiles, and the mask is a third of a tile's vector work
    below = (jk + 1) * bk - 1 <= jq * bq

    @pl.when(jnp.logical_and(runs, below))
    def _():
        tile(False)

    @pl.when(jnp.logical_and(runs, jnp.logical_and(jk <= last,
                                                   jnp.logical_not(below))))
    def _():
        tile(True)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        # a skipped query block's normalizer is 0: it comes out zero
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0),
                             0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _prompt_call(q_n, q_r, k_n, k_r, v, live, *, scale, interpret):
    b, heads, s, dn = q_n.shape
    dr, dv = q_r.shape[3], v.shape[3]
    bq, bk = prompt_tiling(s)

    def kblock(jq, jk, live, i):
        # tiles above the diagonal, and every tile of a query block past
        # the live rows, repeat the tile before: nothing is copied for them
        last_q = jnp.maximum(live[i] - 1, 0) // bq
        reach = ((jnp.minimum(jq, last_q) + 1) * bq - 1) // bk
        return jnp.where(jq > last_q, reach, jnp.minimum(jk, reach))

    def at_q(i, jq, jk, live):
        return (i, jq, 0)

    def at_k(i, jq, jk, live):
        return (i, kblock(jq, jk, live, i // heads), 0)

    def at_kr(i, jq, jk, live):
        return (i // heads, kblock(jq, jk, live, i // heads), 0)

    flat = lambda a: a.reshape((b * heads,) + a.shape[2:])
    out = pl.pallas_call(
        functools.partial(_prompt_kernel, scale=scale, heads=heads),
        name="mla_prompt_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * heads, s // bq, s // bk),
            in_specs=[pl.BlockSpec((1, bq, dn), at_q),
                      pl.BlockSpec((1, bq, dr), at_q),
                      pl.BlockSpec((1, bk, dn), at_k),
                      pl.BlockSpec((1, bk, dr), at_kr),
                      pl.BlockSpec((1, bk, dv), at_k)],
            out_specs=pl.BlockSpec((1, bq, dv), at_q),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b * heads, s, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(live, flat(q_n), flat(q_r), flat(k_n), k_r, flat(v))
    return out.reshape(b, heads, s, dv)


def mla_prompt_attention(q_n, q_r, k_n, k_r, v, *, scale: float, live=None,
                         interpret=None):
    """The materialised causal read of a prompt's rows over themselves.

    Args:
      q_n / k_n: [batch, heads, s, dn] each head's query and key parts
        without position; q_r: [batch, heads, s, dr] its rotated query
        part; k_r: [batch, s, dr] the ONE rotated key of all heads; v:
        [batch, heads, s, dv].
      scale: the logits' scale (1.0 where the caller folded it into the
        queries: one multiplication a logit less).
      live: optional [batch] int32, each prompt's rows (the rest is a
        bucket's padding): query blocks wholly past them are not run and
        come out zero.

    Returns [batch, heads, s, dv] in ``v``'s dtype:
    ``softmax_causal((q_n k_n^T + q_r k_r^T) scale) v``.
    """
    b, heads, s, _ = q_n.shape
    if interpret is None:
        interpret = _interpret()
    live = jnp.full((b,), s, jnp.int32) if live is None \
        else jnp.broadcast_to(jnp.asarray(live, jnp.int32), (b,))
    return _prompt_call(q_n, q_r, k_n, k_r, v, live, scale=float(scale),
                        interpret=bool(interpret))
