"""The arithmetic of a selective state-space (Mamba) mixer, on arrays; its
causal convolution and the tail it carries (:func:`causal_conv`,
:func:`conv_tail`) are also the whole recurrence of a gated
short-convolution mixer (``models/lfm2.py``: K = 3, no bias).

A mixer carries two things from token to token, per request: the scan
state ``s`` ``[d_state, d_inner]`` (float32: the recurrence multiplies it
once a token) and the causal convolution's tail, the last ``d_conv - 1``
rows of its input ``[d_conv - 1, d_inner]``. Everything here exists in the
two forms serving needs: a prompt's ``T`` rows from a zero state, handing
over the state **at each row's own last token** (``last`` [b]: a bucket's
right padding must not advance ``s``, and the tail is the rows before
``last``, not before the bucket's end), and one token against the carried
state.

``selective_scan`` is the prompt's recurrence::

    s_t = exp(dt_t[None, :] * A) * s_{t-1} + (dt_t * x_t)[None, :] * B_t[:, None]
    y_t = (sum_n s_t[n] * C_t[n] + D * x_t) * silu(z_t)

with ``A`` ``[d_state, d_inner]`` (negative), in three forms that agree:
:func:`selective_scan_sequential` (a ``lax.scan`` over time: the plain
form the others are tested against), :func:`selective_scan_chunked` (an
associative scan inside chunks of time, the carry between them: what the
CPU runs) and the Pallas kernel of ``ops/pallas/selective_scan.py`` (what
the chip runs; ``[T, d_inner, d_state]`` never reaches HBM there).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas.utils import interpret_mode, pick_block

#: rows of time of one chunk of the chunked form
CHUNK = 64


def causal_conv(xp, weight, bias=None, tail=None):
    """Depthwise causal convolution: ``xp`` [b, T, d] (any float dtype),
    ``weight`` [d, K], ``bias`` [d] (None: a convolution with none),
    ``tail`` [b, K - 1, d] the rows before row 0 (None: zeros) -> float32
    [b, T, d], ``bias + sum_j weight[:, j] * xp[t - (K - 1) + j]``."""
    b, t, d = xp.shape
    k = weight.shape[1]
    if tail is None:
        tail = jnp.zeros((b, k - 1, d), xp.dtype)
    rows = jnp.concatenate([tail.astype(xp.dtype), xp], axis=1)
    rows = rows.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    out = 0.0 if bias is None else bias.astype(jnp.float32)[None, None, :]
    for j in range(k):
        out = out + rows[:, j:j + t] * w[None, None, :, j]
    return out


def conv_tail(xp, last, k: int):
    """The convolution's tail a prompt leaves: rows ``last - (k - 2) ..
    last`` of ``xp`` [b, T, d] (zeros before row 0) -> [b, k - 1, d]."""
    b, _, d = xp.shape
    rows = jnp.concatenate([jnp.zeros((b, k - 1, d), xp.dtype), xp], axis=1)
    # padded row j is xp's row j - (k - 1): the tail starts at last + 1
    return jax.vmap(lambda r, at: jax.lax.dynamic_slice_in_dim(
        r, at, k - 1, axis=0))(rows, jnp.asarray(last, jnp.int32) + 1)


def _gate(y, x, d_skip, z):
    return (y + d_skip[None, None, :] * x) * jax.nn.silu(z)


def selective_scan_sequential(x, dt, a, b, c, d_skip, z, last):
    """The recurrence as a ``lax.scan`` over time. ``x``, ``dt``, ``z``
    [rows, T, d] float32, ``a`` [n, d], ``b``, ``c`` [rows, T, n],
    ``d_skip`` [d], ``last`` [rows] -> (gated y [rows, T, d], the state
    after row ``last`` [rows, n, d])."""
    rows, t, d = x.shape
    n = a.shape[0]
    last = jnp.asarray(last, jnp.int32)

    def step(carry, inp):
        s, kept = carry
        i, xt, dtt, bt, ct = inp
        s = jnp.exp(dtt[:, None, :] * a[None]) * s \
            + (dtt * xt)[:, None, :] * bt[:, :, None]
        kept = jnp.where((i == last)[:, None, None], s, kept)
        return (s, kept), jnp.sum(s * ct[:, :, None], axis=1)

    zero = jnp.zeros((rows, n, d), jnp.float32)
    (_, kept), ys = jax.lax.scan(
        step, (zero, zero),
        (jnp.arange(t, dtype=jnp.int32), x.swapaxes(0, 1),
         dt.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1)))
    return _gate(ys.swapaxes(0, 1), x, d_skip, z), kept


def selective_scan_chunked(x, dt, a, b, c, d_skip, z, last, chunk=CHUNK):
    """The same in chunks of ``chunk`` rows of time: inside a chunk an
    associative scan over ``(decay, input)`` pairs (stable whatever the
    decay: no quotient of products), between chunks the carried state.
    ``[chunk, n, d]`` a row is what it materialises."""
    rows, t, d = x.shape
    n = a.shape[0]
    chunk = pick_block(t, chunk, minimum=1) or t
    last = jnp.asarray(last, jnp.int32)

    def combine(left, right):
        return right[0] * left[0], right[0] * left[1] + right[1]

    def one(carry, inp):
        s, kept = carry                              # [rows, n, d]
        t0, xc, dtc, bc, cc = inp                    # [rows, chunk, ...]
        decay = jnp.exp(dtc[:, :, None, :] * a[None, None])
        fed = (dtc * xc)[:, :, None, :] * bc[:, :, :, None]
        decay_to, fed_to = jax.lax.associative_scan(combine, (decay, fed),
                                                    axis=1)
        states = decay_to * s[:, None] + fed_to      # [rows, chunk, n, d]
        at = jnp.clip(last - t0, 0, chunk - 1)
        mine = jnp.take_along_axis(states, at[:, None, None, None],
                                   axis=1)[:, 0]
        here = jnp.logical_and(last >= t0, last < t0 + chunk)
        kept = jnp.where(here[:, None, None], mine, kept)
        y = jnp.sum(states * cc[:, :, :, None], axis=2)
        return (states[:, -1], kept), y

    def chunks(v):
        return v.reshape(rows, t // chunk, chunk, v.shape[-1]).swapaxes(0, 1)
    zero = jnp.zeros((rows, n, d), jnp.float32)
    (_, kept), ys = jax.lax.scan(
        one, (zero, zero),
        (jnp.arange(0, t, chunk, dtype=jnp.int32), chunks(x), chunks(dt),
         chunks(b), chunks(c)))
    y = ys.swapaxes(0, 1).reshape(rows, t, d)
    return _gate(y, x, d_skip, z), kept


def selective_scan(x, dt, a, b, c, d_skip, z, last):
    """A prompt's scan: the Pallas kernel on the chip, the chunked form
    where kernels would run in the interpreter (the CPU) or where the
    kernel cannot tile the channels."""
    from .pallas import selective_scan as kernel
    if interpret_mode() or not kernel.tiles(x.shape[1], x.shape[2]):
        return selective_scan_chunked(x, dt, a, b, c, d_skip, z, last)
    return kernel.selective_scan(x, dt, a, b, c, d_skip, z, last)


def selective_step(x, dt, a, b, c, d_skip, z, state):
    """One token against the carried state: ``x``, ``dt``, ``z`` [rows, d],
    ``b``, ``c`` [rows, n], ``state`` [rows, n, d] -> (gated y [rows, d],
    the new state). Every row is updated, whatever it holds."""
    state = jnp.exp(dt[:, None, :] * a[None]) * state \
        + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(state * c[:, :, None], axis=1) + d_skip[None, :] * x
    return y * jax.nn.silu(z), state
