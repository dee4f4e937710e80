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
probabilities blockwise, the standard flash-attention trade. The vjp
names the pair (``utils.FLASH_RESIDUAL_NAMES``); a recompute segment
(fleet.utils.recompute) keeps them beside its input, so its backward
rebuilds q, k and v from that input but does not run the forward kernel
again. Outside a checkpoint the names are the identity.

Causal masking is block-skipped: a q block only loops over k blocks at or
below its diagonal, halving causal FLOPs rather than masking dead work.

A sliding window (``window``: query i sees keys ``i-window+1 .. i``) is
block-skipped the same way from below: the key loop of a q block starts at
the first block the window reaches, the query loop of a k block ends at
the last one. Grouped KV heads (fewer k/v heads than q heads) are read
through the index map (query head ``j`` reads KV head ``j // group``); the
dk/dv kernel writes one partial per query head and the group is summed
outside it. Both are static arguments: with ``window`` off and one KV head
a query head the traced kernels are what they were without them. ``tag``
names the three kernels (``flash_fwd_<tag>`` ...), so that a program whose
layers differ in head count or key range has one shape under each name.

On a CPU backend (tests, virtual meshes) the kernels run in Pallas
interpreter mode, so the same code path is exercised everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import (FLASH_RESIDUAL_NAMES, interpret_mode as _interpret,
                    pad_lane_dim, pick_block, shard_parallel)

NEG_INF = float("-inf")
#: the compiler's default scoped VMEM limit: a forward whose blocks and
#: tiles (:func:`fwd_vmem_bytes`) fit it is compiled as it always was; past
#: it the call asks for a limit of its own, while one head's K and V stay
#: under :data:`_FWD_KV_MOST` (the chip has 128 MiB)
_FWD_VMEM_DEFAULT = 16 << 20
_FWD_KV_MOST = 64 << 20


# ---------------------------------------------------------------- forward

def _window_lo(jq, block_q, block_k, window):
    """First k block a q block's window reaches (0 with no window)."""
    if not window:
        return 0
    return jnp.maximum(jq * block_q - (window - 1), 0) // block_k


def _mask(s, row0, col0, window):
    """Causal mask of one ``[block_q, block_k]`` tile of logits whose
    first row and column are ``row0`` / ``col0``; with ``window`` also the
    keys more than ``window - 1`` behind the query."""
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = row >= col
    if window:
        keep = jnp.logical_and(keep, col > row - window)
    return jnp.where(keep, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, causal, block_k, seq_k, window=0):
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
            s = _mask(s, jq * block_q, kb * block_k, window)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # the first block a window reaches can lie wholly behind the
        # window of the q block's last rows: their maximum is still -inf
        m_ref = jnp.where(m_new == NEG_INF, 0.0, m_new) if window else m_new
        alpha = jnp.exp(m_prev - m_ref)
        p = jnp.exp(s - m_ref)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        _window_lo(jq, block_q, block_k, window), hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def fwd_kv_bytes(seq_k: int, d: int, dtype) -> int:
    """VMEM the forward's whole K and V of one head take: two arrays,
    double-buffered."""
    return 2 * 2 * seq_k * d * jnp.dtype(dtype).itemsize


def fwd_vmem_bytes(seq_k: int, d: int, dtype, block_q: int,
                   block_k: int) -> int:
    """VMEM a forward grid step takes, about: one head's K and V, the q and
    o blocks (double-buffered), and the loop's float32 tiles (q and the
    accumulator, a K and a V block, the logits and the probabilities)."""
    item = jnp.dtype(dtype).itemsize
    return fwd_kv_bytes(seq_k, d, dtype) + 2 * 2 * block_q * d * item \
        + 4 * (2 * block_q * d + 2 * block_k * d + 2 * block_q * block_k)


def _kv_map(group):
    """Index map of a k/v operand whose head serves ``group`` query heads."""
    if group == 1:
        return lambda i, j: (i, 0, 0)
    return lambda i, j: (i // group, 0, 0)


def _name(stem, tag):
    return f"{stem}_{tag}" if tag else stem


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=0, tag=""):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    kv = _kv_map(bh // k.shape[0])
    grid = (bh, seq_q // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_k=seq_k, window=window)
    need = fwd_vmem_bytes(seq_k, d, k.dtype, block_q, block_k)
    params = {} if need <= _FWD_VMEM_DEFAULT else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=need + (16 << 20))}
    o, lse = pl.pallas_call(
        kernel,
        name=_name("flash_fwd", tag),
        grid=grid,
        **params,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq_k, d), kv),
            pl.BlockSpec((1, seq_k, d), kv),
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
                   *, scale, causal, block_k, seq_k, window=0):
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
            s = _mask(s, jq * block_q, kb * block_k, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(_window_lo(jq, block_q, block_k, window), hi,
                           body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, seq_q,
                    window=0):
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    jk = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lo = (jk * block_k) // block_q if causal else 0
    hi = seq_q // block_q
    if window:
        # the last query that sees this block's last key
        hi = jnp.minimum(
            ((jk + 1) * block_k + window - 2) // block_q + 1, hi)

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
            s = _mask(s, qb * block_q, jk * block_k, window)
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
    dk, dv = jax.lax.fori_loop(lo, hi, body, (z, z))
    # q was pre-scaled, so dk already carries the scale factor
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_k, res, g, window=0, tag=""):
    q, k, v, o, lse = res
    bh, seq_q, d = q.shape
    bh_kv, seq_k = k.shape[0], k.shape[1]
    group = bh // bh_kv
    kv = _kv_map(group)
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=seq_k, window=window),
        name=_name("flash_bwd_dq", tag),
        grid=(bh, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, seq_k, d), kv),
            pl.BlockSpec((1, seq_k, d), kv),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # one (dk, dv) partial per QUERY head; a KV head's group is summed below
    kv_block = (lambda i, j: (i, j, 0)) if group == 1 \
        else (lambda i, j: (i // group, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_q=seq_q, window=window),
        name=_name("flash_bwd_dkv", tag),
        grid=(bh, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((1, seq_q, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, seq_q, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    if group > 1:
        dk, dv = (a.reshape(bh_kv, group, seq_k, d).astype(jnp.float32)
                  .sum(axis=1).astype(a.dtype) for a in (dk, dv))
    return dq, dk, dv


# The [b, h, s, d] level is where a program that spans several chips
# meets the kernels: each (batch, head) pair is independent, so under
# utils.kernel_sharding these two wrappers run the 3-D kernels above on
# each chip's own shard of batch and heads (see utils.shard_parallel).

def _fwd_4d(q, k, v, causal, scale, block_q, block_k, window, tag):
    b, h, seq_q, d = q.shape
    o, lse = _flash_fwd(*(a.reshape(-1, a.shape[2], d)
                          for a in (q, k, v)),
                        causal, scale, block_q, block_k, window, tag)
    return o.reshape(q.shape), lse.reshape(b, h, 1, seq_q)


def _bwd_4d(q, k, v, o, lse, do, causal, scale, block_q, block_k, window,
            tag):
    b, h, seq_q, d = q.shape
    res = tuple(a.reshape(-1, a.shape[2], d) for a in (q, k, v, o)) \
        + (lse.reshape(b * h, 1, seq_q),)
    dq, dk, dv = _flash_bwd(causal, scale, block_q, block_k, res,
                            do.reshape(b * h, seq_q, d), window, tag)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_fwd_call = shard_parallel(_fwd_4d, ("bh--",) * 3, ("bh--", "bh--"))
_bwd_call = shard_parallel(_bwd_4d, ("bh--",) * 6, ("bh--",) * 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, window, tag):
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, window,
                     tag)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, window, tag):
    # JAX's jvp wraps the first scope it meets ("jvp(flash_fwd)") and a
    # compiled program names a kernel by its last: one scope around the
    # call takes the wrapper, and the kernel keeps the primal's name
    with jax.named_scope("flash_attention"):
        o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, window,
                           tag)
    o, lse = map(checkpoint_name, (o, lse), FLASH_RESIDUAL_NAMES)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, window, tag, res, g):
    return _bwd_call(*res, g, causal, scale, block_q, block_k, window, tag)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=512, block_k=512, window=0, tag=""):
    """Flash attention on [b, h, s, d] (or [bh, s, d]) inputs.

    Returns attention output with the input's shape/dtype. Raises
    ValueError for shapes the kernel cannot tile (caller decides the
    fallback); self-attention (seq_q == seq_k) plus cross shapes whose
    sequences are divisible by a power-of-two block are supported.

    ``k`` / ``v`` may have fewer heads than ``q`` (a divisor of its count):
    query head ``j`` reads KV head ``j // (h_q // h_kv)``. ``window`` > 0
    (causal only) limits query ``i`` to keys ``i-window+1 .. i``. ``tag``
    is appended to the three kernels' names.

    Sequence-length limit: every grid step keeps the WHOLE K and V of
    one head in VMEM (the ``(1, seq_k, d)`` blocks above; q, do, lse and
    delta likewise in the dk/dv kernel), double-buffered: ``8 s d`` bytes
    in bfloat16 (:func:`fwd_kv_bytes`), beside the q / o blocks and the
    loop's float32 tiles (:func:`fwd_vmem_bytes`: 3.5 MiB at d=128, 5 MiB
    at d=256 with blocks of 512). Under the compiler's default scoped limit
    (16 MiB) the v5e compiler accepts, forward-only, d=128 through s=12288
    and d=256 through s=4096, and refuses d=128 at s=16384 and d=256 at
    s=6144 and s=8192 ("RESOURCE_EXHAUSTED: Ran out of memory in memory
    space vmem", at compile time under jit: described-chip compiles, PR
    58). A forward past the default asks for a scoped limit of its own
    (what it needs plus 16 MiB) and is then accepted at d=256 through
    s=32768 and at d=128 through s=65536 (``tests/test_chip_compile.py``
    holds the served shapes); a forward that fits the default is compiled
    exactly as before. Past :data:`_FWD_KV_MOST` (64 MiB of K and V) the
    call is refused HERE, a ``ValueError`` before anything is built, which
    ``fused_attention_qkv`` catches and answers with the composed form.
    The backward kernels keep the default limit (forward and backward are
    accepted through s=8192 at d=128): training cells stay there. Longer
    sequences still want K and V streamed by block (ROADMAP S5 / R8a).

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
    if window and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if h % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"flash_attention: {h} query heads over {k.shape[1]} KV heads")
    resident = fwd_kv_bytes(seq_k, pad_lane_dim(d), k.dtype)
    if resident > _FWD_KV_MOST:
        raise ValueError(
            f"flash_attention: one head's K and V at seq_k={seq_k}, d={d} "
            f"({k.dtype}) take {resident >> 20} MiB of VMEM double-buffered, "
            f"over the {_FWD_KV_MOST >> 20} MiB the forward may ask for: K "
            f"and V are not streamed by block yet")
    # head_dim rides the lane axis whole; an unaligned width is padded
    # with zero columns (k's zero columns contribute nothing to the
    # logits, v's produce zero output columns sliced off below) rather
    # than rejected — pick_block's divisor rule never applies to d.
    dp = pad_lane_dim(d)
    if dp != d:
        pad = [(0, 0), (0, 0), (0, 0), (0, dp - d)]
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    out = _flash(q, k, v, causal, float(scale), bq, bk, int(window or 0),
                 str(tag))
    if dp != d:
        out = out[..., :d]
    return out[:, 0] if squeeze else out
