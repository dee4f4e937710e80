"""Fused attention ops.

The reference's only fused attention is the inference-only
multihead_matmul (paddle/fluid/operators/fused/multihead_matmul_op.cc:118);
training attention is composed in python (nn/layer/transformer.py:68).
Here fused attention is first-class and differentiable: one op the
executor can lower either to an XLA-composed softmax(qk)v (fused well by
XLA) or to the pallas flash-attention kernel (ops/pallas/) for long
sequences. Dropout inside attention is intentionally NOT part of this op
(masks wouldn't replay under the vjp-derived grad); callers compose a
dropout op on the probabilities when needed.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register
from .. import flags

_logger = logging.getLogger(__name__)
#: (q shape, k shape) of every fused_attention_qkv trace that wanted the
#: flash kernel and took the XLA-composed form because the kernel could
#: not tile it — empty on a healthy run; chip_smoke.py fails on any
flash_fallback_shapes = set()


def decode_attention_mask(pos, q_len: int, capacity: int,
                          dtype=jnp.float32):
    """Additive attention mask for the fixed-capacity KV-cache decode
    path: query i (absolute position ``pos[b] + i``) may attend cache
    entry j iff ``j <= pos[b] + i``. Entries past the valid length —
    prefill padding, stale rows from a retired slot, a speculative
    verify's rejected tail — get ``finfo.min``, which the softmax turns
    into an exact 0 probability, so a [max_slots, heads, max_len, d]
    cache behaves like each slot's true-length cache. Returns
    [b, 1, q_len, capacity].

    With ``q_len > 1`` this is also the verify-step mask for
    speculative decoding: the K+1 query rows (last committed token +
    K draft tokens, freshly scatter-written at ``pos..pos+K`` by
    :func:`cache_scatter_write`) each see exactly the causal prefix
    ``j <= pos + i``, so row i's logits equal what a sequential decode
    at that position would produce — the acceptance test compares
    argmaxes directly against the draft.
    """
    pos = jnp.asarray(pos, jnp.int32)
    qpos = pos[:, None] + jnp.arange(q_len, dtype=jnp.int32)  # [b, q]
    valid = jnp.arange(capacity, dtype=jnp.int32)[None, None, :] \
        <= qpos[:, :, None]                                   # [b, q, C]
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    return jnp.where(valid, jnp.zeros((), dtype), neg)[:, None]


def cache_scatter_write(buf, new, pos):
    """Write ``new`` [b, h, s, d] rows into the fixed-capacity cache
    ``buf`` [b, h, capacity, d] at each batch row's own offset
    ``pos[b]`` (one in-place dynamic_update_slice per row, vmapped so
    the batched decode/verify step stays a single fused XLA op).

    Contract: ``pos[b] + s <= capacity`` for every live row. XLA
    *clamps* out-of-range start indices instead of failing, which
    would silently shift the write window back onto valid rows and
    corrupt the slot's committed prefix — callers reserve headroom up
    front (ServingEngine.submit keeps ``prompt + max_new_tokens +
    spec_tokens`` within the slot capacity for exactly this reason).
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (buf.shape[0],))

    def _write(b, n, p):
        # all start indices must share a dtype (x64 mode makes a bare
        # python 0 an int64)
        z = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(b, n, (z, p, z))

    return jax.vmap(_write)(buf, new, pos)


#: most rows (batch x rows per request) a KV write lands as unrolled
#: in-place row updates: one ``dynamic_update_slice`` a row, ~0.9 us each
#: on the v5e at float32, but a program of its own a row (160 rows a pool
#: in 48 pools took 47 s to compile: PERF.md, PR 25). Above it the write
#: is one kernel over the chunks of blocks it touches.
INPLACE_WRITE_MAX_ROWS = 64


def _chunk_rows(s: int, bs: int, dtype) -> int:
    """Rows of the aligned pieces of a block a many-row write updates:
    the whole block where the rows span blocks, else the fewest whole
    sublane tiles of the pool's dtype (8 rows of 32 bits) that hold
    ``s`` rows and divide the block."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    c = -(-s // tile) * tile
    return c if c < bs and bs % c == 0 else bs


def _physical_blocks(tables, at, bs: int, overflow_block):
    """``at`` [b, n] logical positions -> their physical blocks through
    ``tables`` [b, T]; positions past the table go to ``overflow_block``."""
    T = tables.shape[1]
    logical = at // bs
    phys = jnp.take_along_axis(tables, jnp.minimum(logical, T - 1), axis=1)
    return jnp.where(logical < T, phys, jnp.int32(overflow_block))


def block_scatter_write(pool, new, pos, tables, overflow_block=0):
    """Write ``new`` [b, h, s, d] rows into the block-paged KV pool
    ``pool`` [num_blocks, h, block_size, d], routing each batch row's
    logical positions ``pos[b]..pos[b]+s-1`` through its block table
    row ``tables[b]`` [b, T] to physical (block, offset) pairs — the
    paged generalization of :func:`cache_scatter_write`, at one fixed
    signature for the compiled decode/verify/prefill steps.

    The form follows the static shape, and both forms keep the pool in
    the layout it arrived in: with the pool donated to the step nothing
    pool-sized is copied. A few rows (decode: one per request; verify:
    K+1) are written as one unrolled in-place ``dynamic_update_slice``
    of ``[1, h, 1, d]`` each. Many rows (the prefill buckets, a decode
    step of more than :data:`INPLACE_WRITE_MAX_ROWS` requests) are
    written by (request, touched chunk): a chunk is an aligned
    ``[h, c, d]`` piece of one block (:func:`_chunk_rows`: the whole
    block for a prompt), ``(s + c - 2) // c + 1`` chunks a request
    whatever ``pos``; the request's rows are laid out chunk by chunk
    where they land (one gather of what ``new`` holds) and one kernel
    with the pool aliased in and out merges each chunk with them
    (``ops/pallas/pool_write.py``). No live block is two work items', so
    no chunk is read after it was written; only a chunk of the trash
    block may be. (One fused scatter, the form to PR 42, made XLA's TPU
    layout assignment move the WHOLE pool to ``[block, row, head, d]``
    and back, whatever the rows written: PERF.md, PR 43.)

    Positions whose logical block falls outside the table (bucketed
    prefill's suffix padding rows, beyond a short request's
    reservation) are routed to ``overflow_block`` — physical block 0,
    BlockKVCache's permanently-allocated *trash block* — instead of
    letting XLA's index clamping silently redirect them onto a live
    block's committed rows (so every start index is in range and the
    clamping never engages). Duplicate (trash, offset) targets are
    fine: one row's value lands, and nothing ever reads the trash block
    through a position mask.
    """
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    b, h, s, d = new.shape
    bs = pool.shape[2]
    new = new.astype(pool.dtype)

    def physical(at):
        return _physical_blocks(tables, at, bs, overflow_block)

    if b * s <= INPLACE_WRITE_MAX_ROWS:
        rowpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        phys, offset = physical(rowpos), rowpos % bs
        # all start indices must share a dtype (x64 mode makes a bare
        # python 0 an int64)
        z = jnp.zeros((), jnp.int32)
        for i in range(b):
            for j in range(s):
                pool = jax.lax.dynamic_update_slice(
                    pool, new[i:i + 1, :, j:j + 1],
                    (phys[i, j], z, offset[i, j], z))
        return pool
    c = _chunk_rows(s, bs, pool.dtype)
    n = (s + c - 2) // c + 1
    # the first position of each chunk a request may touch, and the row
    # of ``new`` that lands there (negative: the chunk starts before pos)
    start = pos[:, None] // c * c + c * jnp.arange(n, dtype=jnp.int32)[None]
    first = start - pos[:, None]
    padded = jnp.pad(new, ((0, 0), (0, 0), (c, c), (0, 0)))
    # [b * n, h, c, d]: each chunk's rows of its request, where they land
    mine = jax.vmap(lambda rows, at: jax.vmap(
        lambda f: jax.lax.dynamic_slice_in_dim(rows, f + c, c, axis=1))(at)
    )(padded, first).reshape(b * n, h, c, d)
    from .pallas.pool_write import pool_chunk_write
    return pool_chunk_write(pool, mine, physical(start).reshape(-1),
                            (start % bs // c).reshape(-1),
                            first.reshape(-1), s)


def block_scatter_write_quant(pool, scales, new, pos, tables,
                              overflow_block=0):
    """Quantizing variant of :func:`block_scatter_write` for the int8
    KV pool: ``pool`` [num_blocks, h, block_size, d] int8 codes with
    per-block-per-head absmax ``scales`` [num_blocks, h] f32. Returns
    ``(pool, scales, max_abs_err)`` where the error scalar is the max
    abs dequantization error over the rows just written (live rows
    only — overflow rows routed to the trash block are excluded).

    Only the statically-bounded window of blocks a write can touch
    (``(s-1)//block_size + 2`` per request) is gathered, dequantized,
    updated, and requantized; untouched neighbour blocks keep their
    exact codes AND scales so repeated decode steps never drift them.
    Scales grow monotonically (``max(old, new content absmax)``): at an
    unchanged scale the dequantize->requantize round trip of existing
    rows is exactly idempotent, so a block's committed rows only ever
    re-encode when a louder row actually lands in that block.
    """
    pos = jnp.asarray(pos, jnp.int32)
    b, h, s, d = new.shape
    bs = pool.shape[2]
    T = tables.shape[1]
    new = jnp.asarray(new, jnp.float32)

    from .quant_ops import quantize_int8, dequantize_int8

    lo = pos // bs                                       # [b] first block
    n_aff = (s - 1) // bs + 2                            # static bound
    jblocks = lo[:, None] + jnp.arange(n_aff, dtype=jnp.int32)[None]
    phys = jnp.take_along_axis(
        jnp.asarray(tables, jnp.int32),
        jnp.minimum(jblocks, T - 1), axis=1)             # [b, n_aff]
    phys = jnp.where(jblocks < T, phys, jnp.int32(overflow_block))

    codes = pool[phys]                                   # [b,n_aff,h,bs,d]
    sc = scales[phys]                                    # [b,n_aff,h]
    vals = dequantize_int8(codes, sc[..., None, None])   # f32

    # insert the new rows at their in-window offsets (window-local
    # position = global position - lo*bs, always within n_aff*bs)
    win = jnp.swapaxes(vals, 2, 3).reshape(b, n_aff * bs, h, d)
    local = (pos % bs)[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    newrows = jnp.swapaxes(new, 1, 2)                    # [b, s, h, d]
    win = win.at[jnp.arange(b)[:, None], local].set(newrows)
    win = jnp.swapaxes(win.reshape(b, n_aff, bs, h, d), 2, 3)

    # which window blocks actually received a row this call
    wrote = jnp.arange(n_aff, dtype=jnp.int32)[None] \
        <= ((pos % bs) + s - 1)[:, None] // bs           # [b, n_aff]

    amax = jnp.max(jnp.abs(win), axis=(3, 4))            # [b, n_aff, h]
    new_sc = jnp.where(wrote[..., None], jnp.maximum(sc, amax), sc)
    new_codes = jnp.where(wrote[..., None, None, None],
                          quantize_int8(win, new_sc[..., None, None]),
                          codes)

    pool = pool.at[phys.reshape(-1)].set(
        new_codes.reshape(b * n_aff, h, bs, d))
    scales = scales.at[phys.reshape(-1)].set(
        new_sc.reshape(b * n_aff, h))

    # max abs dequant error over the live rows just written
    recon = jnp.swapaxes(
        dequantize_int8(new_codes, new_sc[..., None, None]), 2, 3)
    recon = recon.reshape(b, n_aff * bs, h, d)
    recon_rows = recon[jnp.arange(b)[:, None], local]    # [b, s, h, d]
    rowpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    live = (rowpos // bs < T)[..., None, None]
    err = jnp.max(jnp.where(live, jnp.abs(recon_rows - newrows), 0.0))
    return pool, scales, err


def block_gather(pool, tables):
    """Materialize each request's logical KV row from the paged pool:
    ``pool`` [num_blocks, h, block_size, d] gathered through ``tables``
    [b, T] -> [b, h, T*block_size, d], the layout
    :func:`decode_attention_mask` and fused attention already expect
    (capacity = T*block_size; table entries past a request's
    reservation point at the trash block, whose rows sit beyond the
    valid length and are masked to exact zero probability).
    """
    g = pool[jnp.asarray(tables, jnp.int32)]        # [b, T, h, bs, d]
    b, T, h, bs, d = g.shape
    return jnp.swapaxes(g, 1, 2).reshape(b, h, T * bs, d)


def block_attention(q, k_pool, v_pool, tables, pos):
    """Masked softmax attention of ``q`` [b, h, s, d] over each
    request's paged float KV, composed in XLA on the blocks as they are
    gathered: ``pool[tables]`` is [b, T, h, bs, d], and both
    contractions take it in that order instead of through
    :func:`block_gather`'s [b, h, T*bs, d] view. With the pool kept in
    its own layout (:func:`block_scatter_write`) that view costs a
    transposing copy of the whole gathered V of every layer on the TPU;
    this form has none. Same mask, same values as
    :func:`paged_attention_reference` -> [b, h, s, d].
    """
    tables = jnp.asarray(tables, jnp.int32)
    kg, vg = k_pool[tables], v_pool[tables]         # [b, T, h, bs, d]
    b, T, h, bs, d = kg.shape
    s = q.shape[2]
    logits = jnp.einsum("bhqd,bthkd->bhqtk", q, kg).reshape(
        b, h, s, T * bs) * (1.0 / math.sqrt(d))
    logits = logits + decode_attention_mask(pos, s, T * bs, logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).reshape(b, h, s, T, bs)
    return jnp.einsum("bhqtk,bthkd->bhqd", probs, vg)


def block_attention_gqa(q, k_pool, v_pool, tables, pos, window=0):
    """One decode row a request over its paged KV with grouped KV heads:
    ``q`` [b, hq, 1, d] (already rotated), pools [blocks, hkv, bs, d]
    (``hq`` a multiple of ``hkv``: query head ``j`` reads KV head
    ``j // (hq / hkv)``), ``tables`` [b, T], ``pos`` [b] the row's position
    (its own K and V are in the pool already) -> [b, hq, 1, d] float32.

    ``window`` > 0: key ``t`` is visible iff ``pos - window < t <= pos``, and
    only the ``ceil(window / bs) + 1`` table entries from the first block
    the window reaches are gathered, so a window layer reads its window
    whatever the context's length (the entries behind it are the trash
    block's anyway: ``BlockKVCache`` frees them). Products take the pools'
    dtype in and accumulate in float32; the softmax is float32.

    The served steps call it for their window layers only (the paged
    kernel's walk starts at a request's first block). Without a window it
    gathers the whole table, ``[b, T, hkv, bs, d]`` of K and of V whatever
    the requests' lengths: no served step takes that arm since PR 44 (a
    full layer reads through ``ops.pallas.paged_attention``), and it
    stays as the composed read the kernel is held against
    (``tests/test_paged_attention.py``, ``perfbench/study/paged_read*.py``).
    """
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    b, hq, s, d = q.shape
    if s != 1:
        raise ValueError(f"block_attention_gqa reads one row a request, "
                         f"got {s}")
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    T = tables.shape[1]
    if window:
        slots = min(T, -(-window // bs) + 1)
        first = jnp.maximum(pos - window + 1, 0) // bs
    else:
        slots, first = T, jnp.zeros_like(pos)
    entry = first[:, None] + jnp.arange(slots, dtype=jnp.int32)[None]
    phys = jnp.take_along_axis(tables, jnp.minimum(entry, T - 1), axis=1)
    phys = jnp.where(entry < T, phys, 0)
    kg, vg = k_pool[phys], v_pool[phys]           # [b, slots, hkv, bs, d]
    qg = q.reshape(b, hkv, hq // hkv, d).astype(kg.dtype)
    logits = jnp.einsum("bhgd,bthkd->bhgtk", qg, kg,
                        preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(d))
    key = entry[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
    seen = key <= pos[:, None, None]
    if window:
        seen = jnp.logical_and(seen, key > (pos - window)[:, None, None])
    logits = jnp.where(seen[:, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.reshape(b, hkv, hq // hkv, slots * bs),
                           axis=-1).reshape(logits.shape)
    out = jnp.einsum("bhgtk,bthkd->bhgd", probs.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, 1, d)


def block_gather_dequant(pool, scales, tables):
    """:func:`block_gather` for the int8 pool: gather code blocks and
    their per-block-per-head scales through ``tables`` and dequantize to
    f32 -> [b, h, T*block_size, d]. This is the XLA half of the int8
    read contract; the Pallas paged kernel applies the identical
    ``codes * scale / 127`` math per streamed block."""
    from .quant_ops import dequantize_int8
    tables = jnp.asarray(tables, jnp.int32)
    g = dequantize_int8(pool[tables],
                        scales[tables][..., None, None])  # [b,T,h,bs,d]
    b, T, h, bs, d = g.shape
    return jnp.swapaxes(g, 1, 2).reshape(b, h, T * bs, d)


def paged_attention_reference(q, k_pool, v_pool, tables, pos, *,
                              k_scale=None, v_scale=None, scale=None):
    """XLA-composed paged decode/verify attention — the correctness
    oracle for :func:`~paddle_tpu.ops.pallas.paged_attention.paged_attention`:
    gather (+ dequantize when int8 scales are given) each request's
    logical KV rows through its block table, mask everything past
    ``pos[b] + row`` (which covers trash-block padding: positions backed
    by the trash block sit at/beyond the reservation, hence beyond
    ``pos + s``), softmax, V-accumulate. q: [b, h, s, d] -> [b, h, s, d].
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if k_scale is not None:
        k = block_gather_dequant(k_pool, k_scale, tables)
        v = block_gather_dequant(v_pool, v_scale, tables)
    else:
        k = block_gather(k_pool, tables)
        v = block_gather(v_pool, tables)
    b, h, s, d = q.shape
    mask = decode_attention_mask(pos, s, k.shape[2], q.dtype)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _composed_attention(q, k, v, mask, causal=False,
                               scale=float(scale))


def _composed_attention(q, k, v, mask, causal, scale, window=0):
    if k.shape[1] != q.shape[1]:
        # grouped KV heads: query head j reads KV head j // group
        group = q.shape[1] // k.shape[1]
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((s_q, s_k), bool), s_k - s_q)
        if window:
            # query i sees keys i-window+1 .. i
            causal_mask &= ~jnp.tril(jnp.ones((s_q, s_k), bool),
                                     s_k - s_q - window)
        logits = jnp.where(causal_mask, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@register("fused_attention_qkv", no_grad_slots=("Mask",))
def _fused_attention_qkv(ctx, ins, attrs):
    """q/k/v: [batch, heads, seq, head_dim]. Mask broadcastable to
    [batch, heads, q_seq, k_seq] (additive, -inf for masked). K and V may
    have fewer heads than Q (grouped KV: a divisor of Q's count; query head
    j reads KV head ``j // group``). Attr ``window`` > 0 with ``causal``
    limits query i to keys ``i-window+1 .. i``; attr ``kernel_tag`` names
    the flash kernels of this call (``flash_fwd_<tag>`` ...)."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = ins["Mask"][0] if ins.get("Mask") else None
    causal = bool(attrs.get("causal", False))
    window = int(attrs.get("window") or 0)
    if window and not causal:
        raise ValueError("fused_attention_qkv: window needs causal=True")
    scale = attrs.get("scale") or (1.0 / math.sqrt(q.shape[-1]))

    # sequence/context parallelism: with attr seq_axis set and the axis
    # bound (shard_map over a seq-sharded mesh), q/k/v arrive as local
    # sequence chunks and attention runs as a ppermute ring
    seq_axis = attrs.get("seq_axis")
    if seq_axis:
        try:
            jax.lax.axis_index(seq_axis)
            bound = True
        except NameError:
            bound = False
        if bound and mask is not None:
            # silently attending only within local chunks would be wrong
            raise NotImplementedError(
                "fused_attention_qkv: explicit Mask + seq_axis (ring "
                "attention) is not supported; use causal=True or drop "
                "sequence parallelism for masked attention")
        if bound:
            from ..distributed.ring_attention import ring_attention
            return {"Out": [ring_attention(q, k, v, seq_axis,
                                           causal=causal, scale=scale)]}

    use_pallas = (attrs.get("use_pallas", "auto") != "never"
                  and flags.get_flag("use_pallas_attention")
                  and q.shape[-2] >= flags.get_flag("pallas_min_seq")
                  and q.shape[-2] == k.shape[-2]
                  and mask is None)
    if use_pallas:
        from .pallas.flash_attention import flash_attention
        try:
            return {"Out": [flash_attention(
                q, k, v, causal=causal, scale=scale,
                block_q=flags.get_flag("pallas_flash_block_q"),
                block_k=flags.get_flag("pallas_flash_block_k"),
                window=window, tag=attrs.get("kernel_tag") or "")]}
        except ValueError as e:
            # a shape the kernel cannot tile (flash_attention raises
            # before building anything): take the XLA-composed form and
            # say so once per distinct shape. A refusal by the TPU
            # compiler itself comes later, under jit, and is never
            # caught here.
            shape = (tuple(q.shape), tuple(k.shape))
            if shape not in flash_fallback_shapes:
                flash_fallback_shapes.add(shape)
                _logger.warning(
                    "fused_attention_qkv: pallas flash attention cannot "
                    "tile q%s k%s (%s); using XLA-composed attention "
                    "(O(s^2) memory)", *shape, e)
    return {"Out": [_composed_attention(q, k, v, mask, causal, scale,
                                        window)]}


# ------------------------------------------------- learned sparse attention
#
# A layer with an indexer keeps, beside K and V, one small key a token (the
# indexer's, ``d`` values, no head axis) and every query reads K and V only
# at the ``topk`` keys whose index score is largest. The pool of indexer
# keys is ``[blocks, d, block_size]``: a token a LANE, so that a ``d`` under
# 128 pads nothing on the chip (``[blocks, block_size, 64]`` is laid out in
# rows of 128 lanes, twice the bytes) and a block is the ``[d, keys]``
# operand the scores' product wants. Everything here is plain XLA but a
# decode row's read, which is ``paged_decode_attn``'s walk under the set's
# mask.
#
# Both paths want their sets as a mask, and take it from two exact
# selections, each where it is the faster by its seconds on a v5e
# (``perfbench/study/select_forms_keye.py``, PR 46): a prompt takes
# :func:`topk_mask`'s bisection (256 queries over 24576 keys: 0.58 ms,
# against 7.1 by ``lax.top_k``'s sort and a mask of the cut's ties); a
# decode row takes ``lax.top_k`` and masks by its cut (8 rows of 25600:
# 0.21 ms; the bisection's 32 dependent counts over so few rows read 1.1
# with a scatter behind them).

#: queries of one chunk of a prompt's selected read: a prompt's bucket is a
#: multiple of it. A chunk holds its scores and its chosen set over the keys
#: up to its frontier (``[chunk, heads, keys]`` float32 a span, ``[chunk,
#: keys]`` a set) and, a key tile at a time, the logits of all its heads
#: ``[hq, chunk, tile]`` float32 (read when the read is traced)
SPARSE_QUERY_CHUNK = 256
#: keys of one tile of that read: a chunk multiplies the tiles up to its
#: frontier and no others, under a running softmax (read when traced)
SPARSE_KEY_TILE = 512
#: static slices of the keys a chunk's selection is taken over (the first
#: 1..n of n equal spans, whichever first holds the chunk's frontier: the
#: bisection's 32 dependent counts want a row of static length)
SPARSE_SELECT_SPANS = 4


def _rows_by_block(new, pos, bs: int):
    """The rows ``new`` [b, s, d] of positions ``pos[b]..pos[b]+s-1`` laid
    out by the blocks of ``bs`` rows they touch, ``n = (s + bs - 2) // bs +
    1`` a request whatever ``pos`` -> (``mine`` [b, n, bs, d]: each block's
    rows where they land, ``start`` [b, n]: a block's first position,
    ``first`` [b, n]: the row of ``new`` at a block's first row, negative
    where the block starts before ``pos``)."""
    s = new.shape[1]
    n = (s + bs - 2) // bs + 1
    start = pos[:, None] // bs * bs + bs * jnp.arange(n, dtype=jnp.int32)[None]
    first = start - pos[:, None]
    padded = jnp.pad(new, ((0, 0), (bs, bs), (0, 0)))
    mine = jax.vmap(lambda rows, at: jax.vmap(
        lambda f: jax.lax.dynamic_slice_in_dim(rows, f + bs, bs, axis=0))(at)
    )(padded, first)
    return mine, start, first


def index_pool_write(pool, new, pos, tables, overflow_block=0):
    """Write ``new`` [b, s, d], the indexer's keys of rows
    ``pos[b]..pos[b]+s-1``, into ``pool`` [blocks, d, block_size] through
    ``tables`` [b, T]: :func:`block_scatter_write` for a pool with no head
    axis and its tokens along the last one (that function's pieces are
    ``[1, h, 1, d]`` rows and, in ``pool_chunk_write``'s kernel, ``[h, c,
    d]`` chunks with ``d`` on 128 lanes; here a token is a lane and ``d``
    is under 128, so only the route through the table,
    :func:`_physical_blocks`, is shared). A few rows are in-place updates
    of one ``[1, d, 1]`` column each; many rows go a whole block at a
    time, the rows of a touched block that are not the call's merged from
    what the pool holds. Rows past the table go to ``overflow_block``."""
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    b, s, d = new.shape
    bs = pool.shape[2]
    new = new.astype(pool.dtype)

    def physical(at):
        return _physical_blocks(tables, at, bs, overflow_block)

    if b * s <= INPLACE_WRITE_MAX_ROWS:
        rowpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        phys, offset = physical(rowpos), rowpos % bs
        z = jnp.zeros((), jnp.int32)
        for i in range(b):
            for j in range(s):
                pool = jax.lax.dynamic_update_slice(
                    pool, new[i, j][None, :, None],
                    (phys[i, j], z, offset[i, j]))
        return pool
    mine, start, first = _rows_by_block(new, pos, bs)   # [b, n, bs, d]
    n = start.shape[1]
    row = first[:, :, None] + jnp.arange(bs, dtype=jnp.int32)   # [b, n, bs]
    written = jnp.logical_and(row >= 0, row < s)
    phys = physical(start).reshape(-1)
    merged = jnp.where(written.reshape(b * n, 1, bs),
                       mine.reshape(b * n, bs, d).transpose(0, 2, 1),
                       pool[phys])
    return pool.at[phys].set(merged)


def latent_pool_write(pool, new, pos, tables, overflow_block=0):
    """Write ``new`` [b, s, d], a latent-attention layer's rows (a token's
    normed latent then its rotated key), into ``pool`` [blocks, d,
    block_size], a layer's ONLY array (``seam.CacheKind`` with no pair),
    through ``tables`` [b, T]. A few rows are :func:`index_pool_write`'s
    in-place columns. Many rows go by (request, touched block) through
    ``pool_chunk_write``'s kernel with the pool aliased in and out, its
    tokens along the lanes: a block is read, the request's columns laid
    over it, and written back, so nothing but the touched blocks moves
    (a prompt of 16384 rows touches 65 of 2177)."""
    b, s, d = new.shape
    if b * s <= INPLACE_WRITE_MAX_ROWS:
        return index_pool_write(pool, new, pos, tables, overflow_block)
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    bs = pool.shape[2]
    mine, start, first = _rows_by_block(new.astype(pool.dtype), pos, bs)
    n = start.shape[1]
    from .pallas.pool_write import pool_chunk_write
    phys = _physical_blocks(tables, start, bs, overflow_block).reshape(-1)
    return pool_chunk_write(
        pool[:, None], mine.reshape(b * n, 1, bs, d).transpose(0, 1, 3, 2),
        phys, jnp.zeros_like(phys), first.reshape(-1), s, lanes=True)[:, 0]


def index_scores(q_idx, w, k_idx):
    """The indexer's score of every key for every query:
    ``I[.., t, s] = sum_j w[.., t, j] relu(q_idx[.., t, j] . k_idx[.., s])``
    with ``q_idx`` [.., t, heads, d], ``w`` [.., t, heads] float32 and
    ``k_idx`` [.., s, d] (one key head for all the indexer's heads). The
    products accumulate in float32; ReLU, weights and the sum are
    float32."""
    dots = jnp.einsum("...tjd,...sd->...tjs", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[..., None].astype(jnp.float32),
                   axis=-2)


def index_scores_paged(q_idx, w, pool, tables):
    """:func:`index_scores` of one decode row a request over its paged
    indexer keys: ``q_idx`` [b, heads, d], ``w`` [b, heads], ``pool``
    [blocks, d, block_size], ``tables`` [b, T] -> float32 [b, T x
    block_size] (entries past a request's rows score garbage: the caller
    masks by position)."""
    kg = pool[jnp.asarray(tables, jnp.int32)]           # [b, T, d, bs]
    b, T, _, bs = kg.shape
    dots = jnp.einsum("bjd,btdk->bjtk", q_idx.astype(kg.dtype), kg,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots)
                   * w[:, :, None, None].astype(jnp.float32),
                   axis=1).reshape(b, T * bs)


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order (-0.0
    as +0.0: the two are one score, a tie)."""
    x = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def topk_mask(scores, valid, k: int):
    """The EXACT set of the ``k`` largest ``scores`` [.., n] among the
    ``valid`` [.., n] keys of each row, as a boolean mask (all the valid
    keys where they are ``k`` or fewer); ties go to the lower index, as
    ``lax.top_k`` breaks them. No sort: the ``k``-th largest value is found
    by bisection on the scores' bits (32 counts over the row), and the keys
    equal to it are admitted in index order up to ``k``."""
    n = scores.shape[-1]
    if k >= n:
        return valid
    key = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)
    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    tie = key == kth[..., None]
    first = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) <= room[..., None]
    return jnp.logical_and(
        valid, jnp.logical_or(above, jnp.logical_and(tie, first)))


def sparse_prompt_tiling(s: int):
    """-> (queries a chunk, keys a tile, the selection's static spans) of a
    prompt's selected read over ``s`` rows: the module's sizes where ``s``
    is a multiple of them, else one chunk / one tile / one span."""
    c, kt, spans = SPARSE_QUERY_CHUNK, SPARSE_KEY_TILE, SPARSE_SELECT_SPANS
    c = c if s > c and s % c == 0 else s
    kt = kt if s > kt and s % kt == 0 else s
    return c, kt, spans if s % (spans * kt) == 0 else 1


def sparse_prompt_tiles(s: int, live=None):
    """THE bounds of :func:`sparse_prompt_attention`'s loops, for the
    program and for whoever counts what it reads: int32 [chunks], the key
    tiles chunk ``i`` of queries multiplies. Chunk ``[lo, lo + c)`` reads
    the keys ``[0, f)``, ``f`` = ``min(lo + c, live)`` rounded up to the
    tile (no row of it can choose a key past its own last live row), and a
    chunk with no row under ``live`` reads none and is not run (the chunks
    that run are a prefix). ``live``: the call's own rows, an int or a
    traced scalar (None: all ``s``, the causal bound alone); numpy out for
    an int, a traced array for a traced length."""
    c, kt, _ = sparse_prompt_tiling(s)
    xp = jnp if isinstance(live, jax.Array) else np
    end = xp.arange(1, s // c + 1, dtype=xp.int32) * c
    live = s if live is None else live
    return xp.where(end - c < live,
                    (xp.minimum(end, live) + kt - 1) // kt, 0).astype(xp.int32)


def sparse_prompt_pairs(b: int, s: int, live=None):
    """-> (the (query, key) pairs :func:`sparse_prompt_attention` multiplies
    for ``b`` prompts of ``s`` rows whose longest has ``live``, the pairs of
    the whole ``[s, s]`` rectangle), host integers from the loop's own
    bounds (:func:`sparse_prompt_tiles`)."""
    c, kt, _ = sparse_prompt_tiling(s)
    return b * c * kt * int(sparse_prompt_tiles(s, live).sum()), b * s * s


def sparse_prompt_attention(q, k, v, q_idx, w, k_idx, topk: int, live=None):
    """A prompt's rows over themselves where every query reads only the
    keys its indexer picks: ``q`` [b, hq, s, d], ``k`` / ``v`` [b, hkv, s,
    d] (grouped: query head ``j`` reads KV head ``j // (hq / hkv)``),
    ``q_idx`` [b, s, heads, di], ``w`` [b, s, heads], ``k_idx`` [b, s, di]
    -> ([b, hq, s, d] in ``v``'s dtype, the key tiles the loop multiplied,
    int32). Query ``t`` scores keys ``s <= t`` (:func:`index_scores`),
    keeps its own ``topk`` largest (:func:`topk_mask`: a set for EVERY row,
    shared by its heads) and takes the softmax over those.

    The read follows the prompt's live causal triangle
    (:func:`sparse_prompt_tiles`): :data:`SPARSE_QUERY_CHUNK` queries at a
    time, and a chunk scores, selects over, multiplies and normalises over
    the keys up to its own last live row and no others. ``live`` (the
    call's own rows, the longest of a batch; a traced scalar, so a bucket
    has one program whatever its prompts' lengths) ends the chunks: one
    with no live row is not run and rows past ``live`` come out as zeros,
    which nothing reads (no live row sees them, and the head is taken on
    the last live row). None: every row is live and the causal bound alone
    holds. A chunk's selection is taken over the first of
    :data:`SPARSE_SELECT_SPANS` static slices of the keys that holds its
    frontier; its products go a tile of :data:`SPARSE_KEY_TILE` keys at a
    time, all heads at once, under the chosen set's mask and a running
    softmax (the row's maximum, sum and accumulator carried from tile to
    tile in float32; a tile with none of a row's keys adds nothing to it):
    neither the ``[s, s]`` scores nor a head's logits exist whole. (The
    chosen keys are not gathered: a gather a query would move ``topk`` rows
    of K and V for every row of the prompt, so the selection is wanted as
    a mask, which :func:`topk_mask` gives without a sort.)"""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    c, kt, spans = sparse_prompt_tiling(s)
    n, span = s // c, s // spans
    scale = 1.0 / math.sqrt(d)
    tiles = jnp.asarray(sparse_prompt_tiles(s, live))
    live = s if live is None else live
    col = jnp.arange(s, dtype=jnp.int32)
    low = jnp.finfo(jnp.float32).min

    def select(upto):
        def over(row, qic, wc):
            valid = jnp.broadcast_to(col[None, :upto] <= row[:, None],
                                     (b, c, upto))
            chosen = topk_mask(index_scores(qic, wc, k_idx[:, :upto]), valid,
                               topk)
            return jnp.pad(chosen, ((0, 0), (0, 0), (0, s - upto)))
        return over
    selects = [select((i + 1) * span) for i in range(spans)]
    chunks = (q.reshape(b, hkv, g, n, c, d).transpose(3, 0, 1, 2, 4, 5),
              q_idx.reshape(b, n, c, *q_idx.shape[2:]).transpose(
                  1, 0, 2, 3, 4),
              w.reshape(b, n, c, -1).transpose(1, 0, 2, 3))

    def chunk(i, carry):
        out, ran = carry
        qc, qic, wc = (x[i] for x in chunks)    # [b, hkv, g, c, d] ..
        row = i * c + jnp.arange(c, dtype=jnp.int32)
        chosen = jax.lax.switch((tiles[i] * kt - 1) // span, selects,
                                row, qic, wc)               # [b, c, s]

        def tile(j, state):
            top, total, acc, ran = state
            kj, vj, keep = (jax.lax.dynamic_slice_in_dim(x, j * kt, kt, 2)
                            for x in (k, v, chosen))
            keep = keep[:, None, None]                      # [b, 1, 1, c, kt]
            logits = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kj,
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(keep, logits, low)
            new = jnp.maximum(top, jnp.max(logits, axis=-1))
            # (a row with no chosen key in this tile, or none yet: its
            # masked logits equal its maximum, and are no weights)
            probs = jnp.where(keep, jnp.exp(logits - new[..., None]), 0.0)
            shrink = jnp.exp(top - new)
            return (new, total * shrink + jnp.sum(probs, axis=-1),
                    acc * shrink[..., None] + jnp.einsum(
                        "bhgqk,bhkd->bhgqd", probs.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32), ran + 1)
        _, total, acc, ran = jax.lax.fori_loop(0, tiles[i], tile, (
            jnp.full((b, hkv, g, c), low, jnp.float32),
            jnp.zeros((b, hkv, g, c), jnp.float32),
            jnp.zeros((b, hkv, g, c, d), jnp.float32), ran))
        # (a row past ``live`` in the last live chunk may have chosen no
        # key under the frontier: no 0 / 0 on its way to zero)
        read = acc / jnp.where(total > 0, total, 1.0)[..., None]
        read = jnp.where((row < live)[:, None], read, 0.0)
        return out.at[i].set(read.reshape(b, hq, c, d).astype(v.dtype)), ran

    out, ran = jax.lax.fori_loop(
        0, jnp.sum(tiles > 0, dtype=jnp.int32), chunk,
        (jnp.zeros((n, b, hq, c, d), v.dtype), jnp.zeros((), jnp.int32)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, hq, s, d), ran


def sparse_decode_attention(q, k_pool, v_pool, tables, pos, scores,
                            topk: int):
    """One decode row a request over the keys its indexer picks: ``q``
    [b, hq, 1, d] (rotated; its own K and V are in the pools already),
    pools [blocks, hkv, block_size, d], ``tables`` [b, T], ``pos`` [b] the
    row's position, ``scores`` float32 [b, T x block_size] its index score
    of every table position (:func:`index_scores_paged`) -> (float32
    [b, hq, 1, d], what the read counted a row, int32 [b, 2]: the keys
    that were eligible and the keys the read kept). The ``topk`` largest
    scores among positions ``<= pos`` are the chosen keys (``lax.top_k``:
    exact, ties to the lower position; its last value is the cut and the
    highest position it chose at the cut ends the ties, so the set comes
    out as a mask with no scatter). ``paged_decode_attn`` walks the row's
    live blocks under that mask (``keep``): the softmax is over the chosen
    keys and no others. A context of ``topk`` rows or fewer keeps all of
    them.

    Why a walk of EVERY live block and not a gather of the chosen rows: on
    a v5e an XLA gather of 2 x ``hkv`` x ``topk`` single rows of ``d`` a
    request costs 9 ns a row whatever the bytes, 1.52 ms a call at 8
    requests of 4k-16k rows where this walk reads 0.60 with five times the
    bytes, and 1.57 against 0.74 at 4k-24k
    (``perfbench/study/decode_read_forms_keye.py`` keeps the gather as its
    oracle). The fewer bytes win only at contexts past what one chip's
    pools hold; a walk that skips the blocks with no chosen key is ROADMAP
    R10's."""
    from .pallas.paged_attention import paged_attention
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    b, hq, _, d = q.shape
    bs = k_pool.shape[2]
    n = scores.shape[1]
    at = jnp.arange(n, dtype=jnp.int32)[None]
    eligible = at <= pos[:, None]
    # (-0.0 as +0.0: one score, a tie, whatever the sort's order of them)
    masked = jnp.where(eligible, jnp.where(scores == 0, 0.0, scores),
                       -jnp.inf)
    vals, idx = jax.lax.top_k(masked, min(int(topk), n))    # [b, k]
    cut = vals[:, -1:]
    last_tie = jnp.max(jnp.where(vals == cut, idx.astype(jnp.int32), -1),
                       axis=1, keepdims=True)
    chosen = jnp.logical_and(eligible, jnp.logical_or(
        masked > cut, jnp.logical_and(masked == cut, at <= last_tie)))
    out = paged_attention(q, k_pool, v_pool, tables, pos,
                          keep=chosen.reshape(b, n // bs, bs),
                          scale=1.0 / math.sqrt(d))
    return out.astype(jnp.float32), jnp.stack(
        [jnp.sum(eligible, axis=1, dtype=jnp.int32),
         jnp.sum(chosen, axis=1, dtype=jnp.int32)], axis=1)
