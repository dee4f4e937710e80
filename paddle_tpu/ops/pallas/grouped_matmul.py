"""Grouped matrix products as Pallas TPU kernels: the expert layer's three
products, whose work follows the group sizes.

Rows arrive sorted by group (expert) in a buffer of static size, each
group starting on a row tile of ``tm`` rows and owning at least one tile
(:func:`tile_layout` makes that layout from the group sizes). A row tile
then belongs to exactly one group, which the kernels read from a
scalar-prefetched table, so the weight block of a tile is chosen by the
index map and the routing of a step changes only the table's contents:
shapes are static and nothing recompiles. Tiles at or past ``n_active``
hold no row of any group and are skipped: no product, no weight read, and
their rows of the result are left unwritten (callers mask them).

- :func:`gmm`: ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (forward, and
  with ``transpose_rhs`` the gradient of the left operand);
- :func:`tgmm`: ``out[g] = lhs[rows of g]^T @ rhs[rows of g]`` (the
  gradient of the weights), float32 accumulation across a group's tiles.

The grids put the row tiles innermost, so a group's weight block stays in
VMEM while its tiles pass. On a CPU backend the kernels run in the Pallas
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode as _interpret

#: rows of one tile: the MXU's height on the v5e, and the granule a group
#: is padded to (a group of n rows computes ceil(n / TILE_M) tiles)
TILE_M = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def tile_layout(group_sizes, tm: int, num_tiles: int, min_tiles: int = 1):
    """The padded layout of groups of ``group_sizes`` [g] rows in a buffer
    of ``num_tiles`` row tiles -> (``tile_group`` [num_tiles] int32: the
    group of each tile, the last active tile's group past the end;
    ``n_active`` [1] int32; ``row_start`` [g] int32: the first row of each
    group). Every group owns at least ``min_tiles`` tiles: one, so that its
    block of a ``tgmm`` result is written (zeros for an empty group); none
    where only :func:`gmm` runs (a forward pass of a few rows: an expert no
    row chose then costs no tile and its weights are not read). At least
    one tile is active whatever the sizes."""
    sizes = jnp.asarray(group_sizes, jnp.int32)
    tiles = jnp.maximum((sizes + tm - 1) // tm, min_tiles)
    ends = jnp.cumsum(tiles)
    n_active = ends[-1]
    if not min_tiles:
        n_active = jnp.maximum(n_active, 1)
    m = jnp.minimum(jnp.arange(num_tiles, dtype=jnp.int32), n_active - 1)
    tile_group = jnp.searchsorted(ends, m, side="right").astype(jnp.int32)
    if not min_tiles:
        # with no row at all the one active tile would name a group past
        # the end
        tile_group = jnp.minimum(tile_group, sizes.shape[0] - 1)
    return (tile_group, n_active[None].astype(jnp.int32),
            ((ends - tiles) * tm).astype(jnp.int32))


def _precision(dtype):
    """bfloat16 operands multiply as they are whatever the process's default
    matmul precision says (Mosaic refuses a bf16 product at float32
    precision); float32 operands follow the default."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _gmm_kernel(tg_ref, na_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            precision=_precision(lhs_ref.dtype),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _column_tile(n: int, tn: int) -> int:
    """The widest tile of at most ``tn`` columns that divides ``n`` in
    whole lanes (``tn`` itself where it divides: 1024 of 1024 or 2048; 896
    of 1792, 768 of 2304), or ``n`` when it is narrower or has no such
    divisor."""
    if n <= tn or n % tn == 0:
        return min(tn, n)
    return next((t for t in range(tn - tn % 128, 0, -128) if n % t == 0), n)


def gmm(lhs, rhs, tile_group, n_active, *, name: str, tm: int = TILE_M,
        tn: int = 1024, transpose_rhs: bool = False):
    """``lhs`` [m, k] x ``rhs`` [g, k, n] (``[g, n, k]`` with
    ``transpose_rhs``) -> [m, n] in ``lhs``'s dtype: row tile ``i`` is
    multiplied by the block of group ``tile_group[i]``."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_tile(n, tn)
    if m % tm or n % tn:
        raise ValueError(f"gmm: cannot tile m={m} by {tm}, n={n} by {tn}")

    def row(j, i, tg, na):
        return jnp.minimum(i, na[0] - 1)

    rhs_spec = pl.BlockSpec(
        (1, tn, k), lambda j, i, tg, na: (tg[row(j, i, tg, na)], j, 0)) \
        if transpose_rhs else pl.BlockSpec(
        (1, k, tn), lambda j, i, tg, na: (tg[row(j, i, tg, na)], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, i, tg, na: (row(j, i, tg, na), 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, tg, na: (row(j, i, tg, na), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=_params(("parallel", "arbitrary")),
        interpret=_interpret(),
    )(tile_group, n_active, lhs, rhs)


def _tgmm_kernel(tg_ref, na_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
    i = pl.program_id(2)
    last_tile = na_ref[0] - 1
    num = pl.num_programs(2)
    here = tg_ref[jnp.minimum(i, last_tile)]
    first = jnp.logical_or(i == 0, tg_ref[jnp.maximum(i - 1, 0)] != here)
    last = jnp.logical_or(i == last_tile,
                          tg_ref[jnp.minimum(i + 1, num - 1)] != here)
    active = i <= last_tile

    @pl.when(jnp.logical_and(active, first))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            precision=_precision(lhs_ref.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(active, last))
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, rhs, tile_group, n_active, num_groups: int, *, name: str,
         tm: int = TILE_M, tk: int = 1024, tn: int = 1024):
    """``lhs`` [m, k], ``rhs`` [m, n] -> [num_groups, k, n] in ``lhs``'s
    dtype: group ``g``'s block is the sum over its row tiles of
    ``lhs_tile^T @ rhs_tile``. Every group must own a tile
    (:func:`tile_layout`), or its block is left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tk, tn = min(tk, k), min(tn, n)
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tgmm: cannot tile m={m}/{tm} k={k}/{tk} "
                         f"n={n}/{tn}")

    def row(i, na):
        return jnp.minimum(i, na[0] - 1)

    return pl.pallas_call(
        _tgmm_kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, m // tm),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda a, b, i, tg, na: (row(i, na), a)),
                pl.BlockSpec((tm, tn),
                             lambda a, b, i, tg, na: (row(i, na), b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn),
                lambda a, b, i, tg, na: (tg[row(i, na)], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), lhs.dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(tile_group, n_active, lhs, rhs)
