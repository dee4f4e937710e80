"""The gated delta rule of a served linear-attention layer, as two Pallas
kernels.

``ops/gated_delta_ops.py`` states the recurrence: a value head carries a
MATRIX ``S [d_k, d_v]`` (float32) whose step first decays it, then
subtracts what it already predicts for the key before writing::

    S'_t = exp(g_t) S_{t-1};   S_t = S'_t + k_t (x) (beta_t (v_t - S'_t^T k_t))
    o_t  = S_t^T q_t

The step is not elementwise in ``S`` (the key reads the whole state), so it
is no associative scan; a prompt takes the **chunked** form and a decode
row a read-modify-write of its 64 KB a head.

``gdn_prefill``: the grid is ``(rows, value heads, chunks of time)``, time
innermost and sequential, the state carried between a head's chunks in a
VMEM scratch; the ``[T, d_k, d_v]`` states never exist. Inside a chunk of
``C`` rows, with ``G_t`` the running sum of ``g`` from the chunk's start and
``D[t, j] = exp(G_t - G_j)`` for ``j <= t`` (never a quotient of
exponentials: every exponent is <= 0), the writes ``u_t = beta_t (v_t -
S'_t^T k_t)`` solve the unit-lower-triangular system::

    (I + A) U = beta * (V - exp(G) * (K S_0)),   A[t, j] = beta_t D[t, j] (k_t . k_j), j < t

whose inverse is a product of ``log2 C`` factors (``A`` is nilpotent:
``(I + A)^-1 = (I + P)(I + P^2)(I + P^4) ...`` with ``P = -A``), and then::

    O   = exp(G) * (Q S_0) + (D * (Q K^T)) U
    S_C = exp(G_C) S_0 + (exp(G_C - G) * K)^T U

Every product is float32 at ``HIGHEST`` (the state is what thousands of
tokens multiply). Right padding is the caller's: ``g = 0`` and ``beta = 0``
past a row's ``last`` neither decay nor write, so the state after the last
chunk IS the state at ``last``; ``last`` rides as a scalar-prefetch operand
so that a chunk wholly behind it is skipped (its ``o`` written as zeros).

``gdn_decode``: one token against the carried state for every row of a
decode step. The state array ``[slots, heads, d_k, d_v]`` is aliased in
and out (``input_output_aliases``) and blocked ``(1, HEADS_A_STEP, d_k,
d_v)`` through the step's row table (scalar prefetch), so a step reads and
rewrites each request's state where it lies and nothing state-sized is
copied. A row that is none (its table entry out of range) is given the
block of a neighbouring row that is a request's, visited as a revisit of
the resident block, and its body is skipped: nothing of it is written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode as _interpret

PREFILL = "gdn_prefill"
DECODE = "gdn_decode"
#: rows of time of one grid step of the prompt's kernel
CHUNK = 64
#: value heads of one grid step of the decode kernel, at most: at 32 heads
#: of 128 x 128 a request's whole layer, 2 MB in and 2 MB out, each
#: double-buffered (the scoped limit is raised for them)
HEADS_A_STEP = 32
_DECODE_VMEM = 64 << 20

_HI = jax.lax.Precision.HIGHEST


def whole_lanes(d_k: int, d_v: int) -> bool:
    """Whether a head ``d_k`` by ``d_v`` fills whole lanes (what both
    kernels take)."""
    return d_k % 128 == 0 and d_v % 128 == 0


def tiles(t: int, d_k: int, d_v: int) -> bool:
    """Whether the prompt's kernel takes ``t`` rows of heads ``d_k`` by
    ``d_v``: whole lanes, and rows in chunks of :data:`CHUNK`."""
    return whole_lanes(d_k, d_v) and t % CHUNK == 0


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _prefill_kernel(last_ref, q_ref, k_ref, v_ref, gb_ref, o_ref, s_ref,
                    carry, *, chunk):
    row, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    live = step * chunk <= last_ref[row]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]       # [C, d]
        g_row, beta_row = gb_ref[0:1, :], gb_ref[1:2, :]    # [1, C]
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        eye = i == j
        # G_t = sum_{j <= t} g_j as a column and as a row; beta as a column
        g_col = jnp.sum(jnp.where(j <= i, g_row, 0.0), axis=1, keepdims=True)
        g_run = jnp.sum(jnp.where(eye, g_col, 0.0), axis=0, keepdims=True)
        beta = jnp.sum(jnp.where(eye, beta_row, 0.0), axis=1, keepdims=True)
        decay = jnp.exp(jnp.where(j <= i, g_col - g_run, -jnp.inf))  # D
        kk = _dot(k, k, (((1,), (1,)), ((), ())))
        qk = _dot(q, k, (((1,), (1,)), ((), ())))
        # (I + A)^-1 = (I + P)(I + P^2)(I + P^4)..., P = -A nilpotent
        p = jnp.where(j < i, -(beta * decay * kk), 0.0)
        inv = jnp.where(eye, 1.0, p)
        n = 2
        while n < chunk:
            p = _dot(p, p)
            inv = inv + _dot(inv, p)
            n *= 2
        s0 = carry[...]
        gamma = jnp.exp(g_col)                                  # [C, 1]
        u = _dot(inv, beta * (v - gamma * _dot(k, s0)))         # [C, d_v]
        o_ref[...] = gamma * _dot(q, s0) + _dot(decay * qk, u)
        g_end = g_col[chunk - 1:chunk, :]                       # [1, 1]
        carry[...] = jnp.exp(g_end) * s0 + _dot(
            jnp.exp(g_end - g_col) * k, u, (((0,), (0,)), ((), ())))

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        s_ref[...] = carry[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_prefill(q, k, v, g, beta, last, interpret=None):
    """``q``, ``k`` [b, T, h_k, d_k] (normalised, ``q`` scaled), ``v``
    [b, T, h_v, d_v], ``g``, ``beta`` [b, T, h_v] (already 0 past ``last``),
    ``last`` [b] int32 -> (o float32 [b, T, h_v, d_v], the state after the
    bucket's last row float32 [b, h_v, d_k, d_v])."""
    b, t, h_k, d_k = q.shape
    h_v, d_v = v.shape[2], v.shape[3]
    if not tiles(t, d_k, d_v) or h_v % h_k:
        raise ValueError(f"gdn_prefill cannot tile {t} rows of {h_v} heads "
                         f"{d_k} x {d_v} over {h_k} key heads")
    group, f32 = h_v // h_k, jnp.float32
    chunk = CHUNK
    qh, kh, vh = (a.astype(f32).transpose(0, 2, 1, 3) for a in (q, k, v))
    # g and beta of a chunk as two rows of lanes: [b, h_v, chunks, 2, C]
    gb = jnp.stack([g.astype(f32), beta.astype(f32)], axis=-1).transpose(
        0, 2, 1, 3).reshape(b, h_v, t // chunk, chunk, 2).swapaxes(3, 4)

    def keyed(r, h, c, last):
        return (r, h // group, c, 0)
    o, s = pl.pallas_call(
        functools.partial(_prefill_kernel, chunk=chunk),
        name=PREFILL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h_v, t // chunk),
            in_specs=[
                pl.BlockSpec((None, None, chunk, d_k), keyed),
                pl.BlockSpec((None, None, chunk, d_k), keyed),
                pl.BlockSpec((None, None, chunk, d_v),
                             lambda r, h, c, last: (r, h, c, 0)),
                pl.BlockSpec((None, None, None, 2, chunk),
                             lambda r, h, c, last: (r, h, c, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, chunk, d_v),
                             lambda r, h, c, last: (r, h, c, 0)),
                pl.BlockSpec((None, None, d_k, d_v),
                             lambda r, h, c, last: (r, h, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((d_k, d_v), f32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, h_v, t, d_v), f32),
            jax.ShapeDtypeStruct((b, h_v, d_k, d_v), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(last, jnp.int32), qh, kh, vh, gb)
    return o.transpose(0, 2, 1, 3), s


def _decode_kernel(rows_ref, qt_ref, kt_ref, v_ref, decay_ref, beta_ref,
                   s_in, o_ref, s_out, *, heads, group, slots):
    hg, i = pl.program_id(0), pl.program_id(1)
    none = rows_ref[i] >= slots

    @pl.when(jnp.logical_not(none))
    def _():
        for h in range(heads):
            head = hg * heads + h
            a, beta = decay_ref[i, head], beta_ref[i, head]
            k = kt_ref[:, h // group:h // group + 1]        # [d_k, 1]
            q = qt_ref[:, h // group:h // group + 1]
            s = a * s_in[h]                                 # [d_k, d_v]
            told = jnp.sum(s * k, axis=0, keepdims=True)    # S'^T k
            s = s + k * (beta * (v_ref[h:h + 1, :] - told))
            s_out[h] = s
            o_ref[h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)

    # a row that is none shares a neighbour's block and writes nothing of
    # the state; where it is the first to visit a block (no request's row
    # need follow: a dispatch with no request at all), the block goes back
    # as it came
    @pl.when(none)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_and(none, i == 0))
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode(q, k, v, g, beta, state, rows, interpret=None):
    """One token a row: ``q``, ``k`` [b, h_k, d_k] (normalised, ``q``
    scaled), ``v`` [b, h_v, d_v], ``g``, ``beta`` [b, h_v], ``state``
    float32 [slots, h_v, d_k, d_v] (aliased in and out: with the caller's
    buffer donated it is rewritten where it lies), ``rows`` [b] int32 the
    state's row of each row of the step (``slots`` or more: none, nothing
    of it is read or written) -> (o float32 [b, h_v, d_v], the state)."""
    b, h_k, d_k = q.shape
    h_v, d_v = v.shape[1], v.shape[2]
    slots = state.shape[0]
    group, f32 = h_v // h_k, jnp.float32
    heads = max(n for n in range(group, HEADS_A_STEP + 1, group)
                if h_v % n == 0)
    keys = heads // group
    rows = jnp.asarray(rows, jnp.int32)
    valid = rows < slots
    # a none row takes the block of the nearest request's row before it
    # (of the first one, where none is before): a revisit, never a write
    idx = jnp.arange(b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(valid, idx, -1))
    partner = jnp.where(before >= 0, before, jnp.argmax(valid))
    block = jnp.where(jnp.any(valid), rows[partner], 0)
    table = jnp.concatenate([rows, block.astype(jnp.int32)])

    def keyed(x):
        """[b, h_k, d_k] -> [b, head groups, d_k, keys]: a key a lane."""
        return x.astype(f32).reshape(b, h_k // keys, keys, d_k).swapaxes(2, 3)
    key_spec = pl.BlockSpec((None, None, d_k, keys),
                            lambda hg, i, t: (i, hg, 0, 0))
    row_spec = pl.BlockSpec((None, heads, d_v), lambda hg, i, t: (i, hg, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    state_spec = pl.BlockSpec((None, heads, d_k, d_v),
                              lambda hg, i, t: (t[b + i], hg, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, group=group,
                          slots=slots),
        name=DECODE,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h_v // heads, b),
            in_specs=[key_spec, key_spec, row_spec, smem, smem, state_spec],
            out_specs=[row_spec, state_spec]),
        out_shape=[
            jax.ShapeDtypeStruct((b, h_v, d_v), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # operands count the scalar-prefetch table: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_DECODE_VMEM),
        interpret=_interpret() if interpret is None else interpret,
    )(table, keyed(q), keyed(k), v.astype(f32), jnp.exp(g.astype(f32)),
      beta.astype(f32), state.astype(f32))
    return o, state
