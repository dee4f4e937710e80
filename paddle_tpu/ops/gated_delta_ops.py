"""The arithmetic of a Gated DeltaNet mixer (Qwen3-Next's linear-attention
layers), on arrays, beside ``ssm_ops.py`` whose causal convolution and tail
it shares.

A value head carries a MATRIX from token to token, per request: ``S``
``[d_k, d_v]`` float32 (the recurrence multiplies it once a token), whose
step subtracts what the state already predicts for the key before it
writes::

    S'_t = exp(g_t) * S_{t-1}
    S_t  = S'_t + k_t (x) (beta_t * (v_t - S'_t^T k_t))
    o_t  = S_t^T q_t

``q`` and ``k`` arrive L2-normalised (:func:`l2norm`), ``q`` scaled by
``d_k ** -0.5``; ``g <= 0`` and ``beta`` in (0, 1) are one value a value
head; key head ``j`` serves the ``h_v / h_k`` consecutive value heads ``j
* group ..``. Everything exists in the two forms serving needs: a prompt's
``T`` rows from a zero state, handing over the state **at each row's own
last token** (``last`` [b]: ``g`` and ``beta`` are zeroed past it, which
neither decays nor writes, so a bucket's right padding advances nothing),
and one token against the carried state.

:func:`gated_delta_rule` is the prompt's recurrence in three forms that
agree: :func:`gated_delta_sequential` (a ``lax.scan`` over time: the plain
form the others are tested against), :func:`gated_delta_chunked` (the
chunked rule in ``jax.numpy``: a unit-lower-triangular solve inside a chunk,
the state carried between chunks: what the CPU runs) and the Pallas kernel
``gdn_prefill`` of ``ops/pallas/gated_delta.py`` (what the chip runs; the
``[T, d_k, d_v]`` states never reach HBM there). :func:`gated_delta_step`
is the decode row's: ``gdn_decode`` on the chip, plain arithmetic here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas.utils import interpret_mode

#: rows of time of one chunk of the chunked form
CHUNK = 64


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def round_to(x, dtype):
    """float32 ``x`` rounded to ``dtype``'s precision and kept float32, by
    ``lax.reduce_precision``: a cast there and back is compiled away on
    the chip (XLA allows excess precision), this is not."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def mask_past_last(g, beta, last):
    """``g``, ``beta`` [b, T, h] with both zeroed past row ``last`` [b]: a
    padded position then neither decays nor writes."""
    t = g.shape[1]
    keep = (jnp.arange(t, dtype=jnp.int32)[None, :]
            <= jnp.asarray(last, jnp.int32)[:, None])[..., None]
    return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)


def _by_value_head(x, h_v: int):
    """``x`` [.., h_k, d] -> [.., h_v, d]: a key head a group of value
    heads."""
    return jnp.repeat(x, h_v // x.shape[-2], axis=-2)


def gated_delta_sequential(q, k, v, g, beta, last, state_dtype=jnp.float32):
    """The recurrence as a ``lax.scan`` over time. ``q``, ``k`` [b, T, h_k,
    d_k], ``v`` [b, T, h_v, d_v], ``g``, ``beta`` [b, T, h_v], ``last`` [b]
    -> (o float32 [b, T, h_v, d_v], the state after row ``last`` [b, h_v,
    d_k, d_v]). ``state_dtype``: what the carried state is rounded to after
    every step (the studies' planted fault; float32 as served)."""
    h_v = v.shape[2]
    f32 = jnp.float32
    g, beta = mask_past_last(g.astype(f32), beta.astype(f32), last)
    q, k = (_by_value_head(a.astype(f32), h_v) for a in (q, k))

    def step(s, inp):
        qt, kt, vt, gt, bt = inp                        # [b, h, ..]
        s = jnp.exp(gt)[..., None, None] * s
        told = jnp.einsum("bhkv,bhk->bhv", s, kt)
        s = s + kt[..., :, None] * (bt[..., None] * (vt - told))[..., None, :]
        s = round_to(s, state_dtype)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    zero = jnp.zeros((v.shape[0], h_v, q.shape[-1], v.shape[-1]), f32)
    s, o = jax.lax.scan(step, zero, tuple(
        a.swapaxes(0, 1) for a in (q, k, v.astype(f32), g, beta)))
    return o.swapaxes(0, 1), s


def gated_delta_chunked(q, k, v, g, beta, last, chunk: int = CHUNK):
    """The same in chunks of ``chunk`` rows of time (the kernel's algebra,
    ``ops/pallas/gated_delta.py``): inside a chunk the writes solve a
    unit-lower-triangular system, between chunks the carried state.
    ``[chunk, chunk]`` a head is what it materialises."""
    b, t, h_v, d_v = v.shape
    f32 = jnp.float32
    if t % chunk:
        chunk = t
    g, beta = mask_past_last(g.astype(f32), beta.astype(f32), last)
    q, k = (_by_value_head(a.astype(f32), h_v) for a in (q, k))
    i = jnp.arange(chunk)[:, None]
    j = jnp.arange(chunk)[None, :]

    def one(s0, inp):
        qc, kc, vc, gc, bc = inp            # [b, C, h, ..]; g, beta [b, C, h]
        run = jnp.cumsum(gc, axis=1).transpose(0, 2, 1)        # [b, h, C]
        bc = bc.transpose(0, 2, 1)
        decay = jnp.exp(jnp.where(
            j <= i, run[..., :, None] - run[..., None, :], -jnp.inf))
        kk = jnp.einsum("bthk,bjhk->bhtj", kc, kc)
        qk = jnp.einsum("bthk,bjhk->bhtj", qc, kc)
        a = jnp.where(j < i, bc[..., :, None] * decay * kk, 0.0)
        gamma = jnp.exp(run)[..., None]                        # [b, h, C, 1]
        rhs = bc[..., None] * (
            vc.transpose(0, 2, 1, 3)
            - gamma * jnp.einsum("bthk,bhkv->bhtv", kc, s0))
        u = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(chunk, dtype=f32), rhs, lower=True,
            unit_diagonal=True)
        o = gamma * jnp.einsum("bthk,bhkv->bhtv", qc, s0) \
            + jnp.einsum("bhtj,bhjv->bhtv", decay * qk, u)
        end = run[..., -1:]
        s = jnp.exp(end)[..., None] * s0 + jnp.einsum(
            "bthk,bhtv->bhkv",
            jnp.exp(end - run).transpose(0, 2, 1)[..., None] * kc, u)
        return s, o.transpose(0, 2, 1, 3)

    def chunks(x):
        return x.reshape((b, t // chunk, chunk) + x.shape[2:]).swapaxes(0, 1)
    zero = jnp.zeros((b, h_v, q.shape[-1], d_v), f32)
    s, o = jax.lax.scan(one, zero, tuple(
        chunks(a) for a in (q, k, v.astype(f32), g, beta)))
    return o.swapaxes(0, 1).reshape(b, t, h_v, d_v), s


def gated_delta_rule(q, k, v, g, beta, last):
    """A prompt's rule: the Pallas kernel on the chip, the chunked form
    where kernels would run in the interpreter (the CPU) or where the
    kernel cannot tile the heads."""
    from .pallas import gated_delta as kernel
    if interpret_mode() or not kernel.tiles(q.shape[1], q.shape[-1],
                                            v.shape[-1]):
        return gated_delta_chunked(q, k, v, g, beta, last)
    g, beta = mask_past_last(g.astype(jnp.float32),
                             beta.astype(jnp.float32), last)
    return kernel.gdn_prefill(q, k, v, g, beta, last)


def gated_delta_step_plain(q, k, v, g, beta, state):
    """One token against the carried state, in ``jax.numpy``: ``q``, ``k``
    [b, h_k, d_k], ``v`` [b, h_v, d_v], ``g``, ``beta`` [b, h_v], ``state``
    [b, h_v, d_k, d_v] -> (o [b, h_v, d_v], the new state). Every row is
    updated, whatever it holds."""
    f32 = jnp.float32
    h_v = v.shape[1]
    q, k = (_by_value_head(a.astype(f32), h_v) for a in (q, k))
    s = jnp.exp(g.astype(f32))[..., None, None] * state
    told = jnp.einsum("bhkv,bhk->bhv", s, k)
    s = s + k[..., :, None] * (beta.astype(f32)[..., None]
                               * (v.astype(f32) - told))[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q), s


def gated_delta_step(q, k, v, g, beta, state, rows):
    """A decode step's rows against the state array ``[slots, h_v, d_k,
    d_v]``, row ``i`` of the step on the state's row ``rows[i]`` (out of
    range: none, its write dropped): ``gdn_decode`` on the chip, which
    rewrites the rows where they lie; here a gather, the plain step and a
    scatter."""
    from .pallas import gated_delta as kernel
    if not interpret_mode() and kernel.whole_lanes(q.shape[-1], v.shape[-1]):
        return kernel.gdn_decode(q, k, v, g, beta, state, rows)
    rows = jnp.asarray(rows, jnp.int32)
    mine = state[jnp.minimum(rows, state.shape[0] - 1)]
    o, new = gated_delta_step_plain(q, k, v, g, beta, mine)
    return o, state.at[rows].set(new, mode="drop")
