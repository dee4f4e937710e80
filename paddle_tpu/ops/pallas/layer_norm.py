"""Fused LayerNorm as Pallas TPU kernels (forward + backward).

Capability analog of the reference's fused CUDA layer_norm
(paddle/fluid/operators/layer_norm_op.cu) — one VMEM pass computes
mean/rstd and the normalized output per row block; the backward fuses
dx with the dgamma/dbeta row-reductions by accumulating into a single
revisited output block across sequential grid steps (the canonical TPU
reduction pattern). fp32 statistics regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .utils import interpret_mode as _interpret, pick_block


def _pick_rows(n: int, preferred: int = 256) -> int:
    # full-array fallback (one grid step) when n has no aligned divisor
    return pick_block(n, preferred) or n


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                dx_ref, dg_ref, db_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    dyg = dy * g
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=1, keepdims=True)
    dx = rstd * (dyg - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    dg_part = jnp.sum(dy * xhat, axis=0)
    db_part = jnp.sum(dy, axis=0)

    @pl.when(i == 0)
    def _():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += dg_part.astype(dg_ref.dtype)
    db_ref[...] += db_part.astype(db_ref.dtype)


def _ln_fwd(x, gamma, beta, eps, block_n):
    n, h = x.shape
    grid = (n // block_n,)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="layer_norm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, h), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, gamma, beta)
    return y, mean, rstd


def _ln_bwd(eps, block_n, res, dy):
    x, gamma, mean, rstd = res
    n, h = x.shape
    dx, dg, db = pl.pallas_call(
        _bwd_kernel,
        name="layer_norm_bwd",
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((h,), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, gamma, mean, rstd, dy)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x, gamma, beta, eps, block_n):
    return _ln_fwd(x, gamma, beta, eps, block_n)


def _ln_vjp_fwd(x, gamma, beta, eps, block_n):
    y, mean, rstd = _ln_fwd(x, gamma, beta, eps, block_n)
    return (y, mean, rstd), (x, gamma, mean, rstd)


def _ln_vjp_bwd(eps, block_n, res, cots):
    # mean/rstd are non-differentiable observables (the reference's
    # layer_norm_grad likewise ignores Mean/Variance cotangents)
    dy, _, _ = cots
    return _ln_bwd(eps, block_n, res, dy)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def fused_layer_norm_with_stats(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last axis returning (y, mean, variance) with
    mean/variance shaped like the flattened row count — the stats come
    from the kernel itself, not a recompute."""
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    block_n = _pick_rows(x2.shape[0])
    y, mean, rstd = _ln(x2, gamma, beta, float(eps), block_n)
    var = 1.0 / (rstd * rstd) - eps
    return y.reshape(shape), mean[:, 0], var[:, 0]


def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last axis; leading axes are flattened to rows."""
    y, _, _ = fused_layer_norm_with_stats(x, gamma, beta, eps)
    return y
