"""Fused paged decode attention for the block-paged serving KV cache.

One kernel is the serving decode step's KV read: each request's live
blocks go from the pool to VMEM through its block table, and nothing
table-sized is gathered. The pools stay in HBM (``memory_space=ANY``);
the kernel walks the batch rows in one program and, for a row at
position ``pos`` with ``q_len`` query rows, copies only its
``ceil((pos + q_len) / block_size)`` live blocks, ``n`` of them a
compute step. A pool block ``[h_kv, block_size, d]`` holds every KV head
and is one contiguous copy (128 KB of float32 at h16 d128 and 16-row
blocks); the copies of the next step (the row's next ``n`` blocks, or
the next row's first) are in flight while this one is computed, so a
dead slot (``pos`` 0) costs one block and a 380-token row 24 of its 64
table entries. Table entries past a request's reservation point at the
trash block and are never read: every logical position they back sits
beyond ``pos + q_len``.

Grouped KV heads: ``q`` is ``[b, h_q, q_len, d]`` over pools of ``h_kv``
heads, ``h_q`` a multiple of ``h_kv`` (query head ``j`` reads KV head
``j // (h_q / h_kv)``), so the ``g * q_len`` query rows of one KV head
share each block that is read.

Softmax is the online form with float32 state. Two arithmetic forms, one
walk; the shape picks (:func:`_rows_form`):

- few query rows over short blocks (GPT's decode and verify: one to a
  handful of rows a head, 16-row blocks) multiply and reduce on the
  vector unit, ``sub`` keys at a time on the sublanes, with one running
  ``(m, l, acc)`` a *sublane* — the logits leave the K tile in the
  layout the V tile wants them in, nothing is transposed, and the
  sublanes are merged once a row at the end;
- many rows over long blocks (grouped heads over 256-row blocks) take
  the two products a (block, KV head) through the matrix unit in the
  pool's type, accumulating in float32.

K and V are read in the pool's type; a float32 pool is never narrowed.
int8 pools ride the same walk: the per-block-per-head absmax scales are
gathered through the table outside (``scale[tables]``, table-sized in
*scalars*) and applied to the block's logits and probabilities, which is
``codes * scale / 127`` of
:func:`~paddle_tpu.ops.attention_ops.block_gather_dequant` moved past
the product.

Masking mirrors :func:`~paddle_tpu.ops.attention_ops.decode_attention_mask`:
key position ``j`` is valid for query row ``i`` iff ``j <= pos[b] + i``.
Key 0 is valid for every row, so the normalizer is strictly positive.
With ``keep`` (a selected read: the keys a request's row may read, a
value a table position) a key also has to be kept; the walk is the same
and a block's dropped keys are masked like the ones past ``pos``. Until a
row has met its first kept key its state is the masked logits' (``m`` at
``_MASKED``, weights ``exp(0)``); the first kept key's ``alpha`` is
``exp(_MASKED - m) = 0`` and wipes it, so a row needs ONE kept key among
its live positions, not key 0.

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

from .utils import interpret_mode as _interpret, pad_lane_dim, shard_parallel

#: masked logits and the running max's start: finite, so that a sublane
#: that has seen no valid key yet computes exp(0), not exp(nan); its
#: weight at the merge is exp(_MASKED - m) = 0
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)

#: int8 symmetric grid max — must match ops.quant_ops.KV_QMAX
_KV_QMAX = 127.0

#: pool blocks a compute step, at most; and the VMEM their two double
#: buffers (K and V) may take, which cuts it for big blocks
BLOCKS_A_STEP = 8
_BUFFER_BYTES = 8 << 20


def _rows_form(rows: int, bs: int, sub: int) -> bool:
    """True: the vector-unit form. Its work grows with the query rows a
    KV head times the key slices a block; the matrix unit's with
    neither, but it pays a weight load a (block, head) product, which
    only a long block amortises."""
    return rows * (bs // sub) <= 64


def _kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest, n: int, T: int,
            q_len: int, sub: int, vector: bool, quant: bool,
            select: bool):
    if quant:
        ksc_ref, vsc_ref, *rest = rest
    if select:
        keep_ref, *rest = rest
    o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = rest
    B, h_kv, rows, dp = q_ref.shape
    bs = k_buf.shape[3]
    f32 = jnp.float32

    def live_blocks(b):
        return jnp.minimum((pos_ref[b] + (q_len + bs - 1)) // bs, T)

    def copies(b, c, slot, go):
        """Start (or wait for) the copies of row ``b``'s chunk ``c``."""
        def one(i, carry):
            blk = tbl_ref[b * T + c * n + i]
            for pool, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                go(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, i], sem.at[s, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n, live_blocks(b) - c * n), one, 0)

    def start(b, c, slot):
        copies(b, c, slot, lambda cp: cp.start())

    def wait(b, c, slot):
        copies(b, c, slot, lambda cp: cp.wait())

    def head_scales(ref, b, t):
        """The block's int8 scales, one a KV head, as [h_kv, 1, 1]."""
        return jnp.concatenate(
            [jnp.full((1, 1, 1), ref[(b * T + t) * h_kv + h]
                      * (1.0 / _KV_QMAX), f32) for h in range(h_kv)])

    def block_vector(b, t, slot, i):
        """One block, every head at once, on the vector unit: ``sub``
        keys a slice on the sublanes, state a (row, head, sublane)."""
        pos_b = pos_ref[b]
        kpos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (1, sub, 1), 1)
        slices = range(bs // sub)
        ks = [k_buf[slot, i, :, pl.ds(u * sub, sub), :].astype(f32)
              for u in slices]                                # [h_kv, sub, dp]
        vs = [v_buf[slot, i, :, pl.ds(u * sub, sub), :].astype(f32)
              for u in slices]
        if quant:
            ksc, vsc = head_scales(ksc_ref, b, t), head_scales(vsc_ref, b, t)
        for j in range(rows):
            qv = q_ref[b, :, pl.ds(j, 1), :]                  # [h_kv, 1, dp]
            qpos = pos_b + (j % q_len)
            lgs = []
            for u in slices:
                lg = jnp.sum(ks[u] * qv, axis=-1, keepdims=True)
                if quant:
                    lg = lg * ksc
                lgs.append(jnp.where(kpos + u * sub <= qpos, lg, _MASKED))
            m_prev = m_ref[j]                                 # [h_kv, sub, 1]
            m_new = functools.reduce(jnp.maximum, lgs, m_prev)
            alpha = jnp.exp(m_prev - m_new)
            ps = [jnp.exp(lg - m_new) for lg in lgs]
            m_ref[j] = m_new
            l_ref[j] = alpha * l_ref[j] + sum(ps)
            if quant:
                ps = [p * vsc for p in ps]
            acc_ref[j] = alpha * acc_ref[j] + sum(
                p * v for p, v in zip(ps, vs))

    def block_matrix(b, t, slot, i):
        """One block, every KV head, on the matrix unit."""
        pos_b = pos_ref[b]
        kpos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        qpos = pos_b + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bs), 0) % q_len
        seen = kpos <= qpos
        if select:
            seen = jnp.logical_and(seen, keep_ref[b, pl.ds(t, 1), :] != 0)
        for h in range(h_kv):
            k, v = k_buf[slot, i, h], v_buf[slot, i, h]       # [bs, dp]
            if quant:
                k, v = k.astype(f32), v.astype(f32)
            q = q_ref[b, h].astype(k.dtype)                   # [rows, dp]
            # float32 operands are multiplied as float32, whatever the
            # process's default; a bfloat16 pool's in one pass
            exact = (jax.lax.Precision.HIGHEST if k.dtype == f32
                     else jax.lax.Precision.DEFAULT)
            lg = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     precision=exact,
                                     preferred_element_type=f32)
            if quant:
                lg = lg * (ksc_ref[(b * T + t) * h_kv + h]
                           * (1.0 / _KV_QMAX))
            lg = jnp.where(seen, lg, _MASKED)
            m_prev = m_ref[h]                                 # [rows, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(lg, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(lg - m_new)
            m_ref[h] = m_new
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * (vsc_ref[(b * T + t) * h_kv + h]
                         * (1.0 / _KV_QMAX))
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=exact, preferred_element_type=f32)

    def finish(b):
        if not vector:
            o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
            return
        # merge the sublanes' softmaxes: weights exp(m - max m)
        m = m_ref[...]                                  # [rows, h_kv, sub, 1]
        w = jnp.exp(m - jnp.max(m, axis=2, keepdims=True))
        l = jnp.sum(l_ref[...] * w, axis=2, keepdims=True)
        out = (jnp.sum(acc_ref[...] * w, axis=2, keepdims=True)
               / l).astype(o_ref.dtype)                 # [rows, h_kv, 1, dp]
        for j in range(rows):
            for h in range(h_kv):
                o_ref[b, h, pl.ds(j, 1), :] = out[j, h]

    block = block_vector if vector else block_matrix

    def row(b, slot):
        nl = live_blocks(b)
        chunks = (nl + (n - 1)) // n
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        def chunk(c, slot):
            # the copies after these: the row's next chunk, or the next
            # row's first
            last = c + 1 == chunks
            b_next = jnp.where(last, b + 1, b)

            @pl.when(b_next < B)
            def _():
                start(b_next, jnp.where(last, 0, c + 1), 1 - slot)

            wait(b, c, slot)

            def one(i, carry):
                block(b, c * n + i, slot, i)
                return carry

            jax.lax.fori_loop(0, jnp.minimum(n, nl - c * n), one, 0)
            return 1 - slot

        slot = jax.lax.fori_loop(0, chunks, chunk, slot)
        finish(b)
        return slot

    start(0, 0, 0)
    jax.lax.fori_loop(0, B, row, 0)


def paged_attention(q, k_pool, v_pool, tables, pos, *,
                    k_scale=None, v_scale=None, keep=None, scale=None,
                    interpret=None):
    """Fused paged decode/verify attention over the block pool.

    Args:
      q: [batch, h_q, q_len, head_dim] queries (decode q_len=1,
        speculative verify q_len=K+1).
      k_pool / v_pool: [num_blocks, h_kv, block_size, head_dim] KV
        pools (f32/bf16, or int8 codes when scales are given); ``h_q`` a
        multiple of ``h_kv``.
      tables: [batch, T] int32 block tables (host-side values; padding
        entries point at the trash block).
      pos: [batch] int32 committed lengths; query row i sits at
        absolute position ``pos[b] + i``.
      k_scale / v_scale: optional [num_blocks, h_kv] f32 absmax scales
        — both present selects the int8 dequantizing path.
      keep: optional [batch, T, block_size] (any integer or boolean
        type): nonzero where the request's rows may read the key at that
        table position. A key is read iff it is kept AND at or before
        the row's position; every request keeps at least one such key.
        The same set for every query row of a request (a decode step
        has one). Float pools only.
      scale: logit scale, default ``1/sqrt(head_dim)`` (the original,
        pre-padding head_dim).
      interpret: force the Pallas interpreter; default follows
        ``interpret_mode()`` (on for CPU backends).

    Returns [batch, h_q, q_len, head_dim] in q's dtype, equal to
    :func:`~paddle_tpu.ops.attention_ops.paged_attention_reference`.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    b, h, s, d = q.shape
    nb, hp, bs, dpool = k_pool.shape
    if dpool != d or h % hp or v_pool.shape != k_pool.shape:
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
    if keep is not None:
        if k_scale is not None:
            raise ValueError("keep is for float pools: an int8 pool's "
                             "read takes no selection")
        if keep.shape != (b, tables.shape[1], bs):
            raise ValueError(f"keep {keep.shape} is not [batch, T, "
                             f"block_size] of tables {tables.shape} over "
                             f"blocks of {bs} rows")
        out = _paged_keep_call(q, k_pool, v_pool, tables, pos,
                               keep.astype(jnp.int32),
                               float(scale), bool(interpret))
    elif k_scale is None:
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
                 scale, interpret, keep=None):
    """The kernel call on what one chip holds: q [b, h_q, s, dp] (dp
    lane-aligned), pools [nb, h_kv, bs, dp], tables [b, T], pos [b],
    optional scales [nb, h_kv], optional keep int32 [b, T, bs]."""
    _, h_kv, bs, dp = k_pool.shape
    block_bytes = h_kv * bs * dp * k_pool.dtype.itemsize
    n = max(1, min(BLOCKS_A_STEP, tables.shape[1],
                   _BUFFER_BYTES // (4 * block_bytes)))
    return _paged_walk(q, k_pool, v_pool, tables, pos, k_scale, v_scale,
                       keep, scale=scale, interpret=interpret, n=n)


# One traced program a shape: a step's layers call the kernel on operands
# of one shape, and (under the interpreter, where the kernel is plain HLO)
# lowering it once a program instead of once a layer is most of what a
# CPU trace of a step costs.
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "n"))
def _paged_walk(q, k_pool, v_pool, tables, pos, k_scale, v_scale, keep,
                *, scale, interpret, n):
    quant, select = k_scale is not None, keep is not None
    b, hq, s, dp = q.shape
    _, h_kv, bs, _ = k_pool.shape
    T = tables.shape[1]
    rows = hq // h_kv * s
    item = k_pool.dtype.itemsize
    # the keys a slice: one native tile of the pool's type on the sublanes
    sub = 32 // item if bs % (32 // item) == 0 else bs
    # a selected read takes the matrix form whatever its shape: one
    # form reads ``keep``, and the served one (grouped heads over long
    # blocks) is this
    vector = _rows_form(rows, bs, sub) and not select

    # the query rows of one KV head together, scaled once
    qf = (q.astype(jnp.float32) * scale).reshape(b, h_kv, rows, dp)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [tables.reshape(-1), pos, qf, k_pool, v_pool]
    in_specs = [smem, smem, vmem, hbm, hbm]
    if quant:
        operands += [k_scale[tables].reshape(-1),
                     v_scale[tables].reshape(-1)]
        in_specs += [smem, smem]
    if select:
        operands.append(keep)
        in_specs.append(vmem)
    state = (rows, h_kv, sub) if vector else (h_kv, rows)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, T=T, q_len=s, sub=sub,
                          vector=vector, quant=quant, select=select),
        name="paged_decode_attn",
        in_specs=in_specs,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((b, h_kv, rows, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, n, h_kv, bs, dp), k_pool.dtype),
            pltpu.VMEM((2, n, h_kv, bs, dp), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM(state + (1,), jnp.float32),    # running max m
            pltpu.VMEM(state + (1,), jnp.float32),    # normalizer l
            pltpu.VMEM(state + (dp,), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(*operands)
    return out.reshape(b, hq, s, dp)


def _paged_float(q, k_pool, v_pool, tables, pos, scale, interpret):
    return _paged_local(q, k_pool, v_pool, tables, pos, None, None,
                        scale, interpret)


def _paged_keep(q, k_pool, v_pool, tables, pos, keep, scale, interpret):
    return _paged_local(q, k_pool, v_pool, tables, pos, None, None,
                        scale, interpret, keep)


# Every (batch row, head) pair is independent, so a tensor-parallel
# engine (heads on the mesh's model axis) runs the kernel on each chip's
# own heads of q and of the pools; the pool's block axis, the block rows
# and head_dim stay whole per chip (see utils.shard_parallel).
_paged_call = shard_parallel(
    _paged_float, ("bh--", "-h--", "-h--", "b-", "b"), ("bh--",))
_paged_keep_call = shard_parallel(
    _paged_keep, ("bh--", "-h--", "-h--", "b-", "b", "b--"), ("bh--",))
_paged_quant_call = shard_parallel(
    _paged_local,
    ("bh--", "-h--", "-h--", "b-", "b", "-h", "-h"), ("bh--",))
