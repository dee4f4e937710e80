"""A many-row KV write into the block pool, in place: one kernel whose
grid walks the chunks of blocks the write touches.

``ops.attention_ops.block_scatter_write`` enumerates the work by
(request, touched chunk): a chunk is an aligned ``[h, c, d]`` piece of one
block, and the rows of the request that land in it are laid out beside it
by the caller (``mine``, ``[n, h, c, d]``, one gather in XLA, where an
unaligned shift of rows costs nothing special). The kernel reads a chunk
of the pool, keeps its rows where none of the request's lands
(``first[k] + r`` outside ``[0, s)``), takes the request's elsewhere and
writes the chunk back; the pool is aliased in and out, so nothing but the
touched chunks moves, and the pool keeps the layout it arrived in. The
physical block and the chunk's index in it are scalar-prefetched: the
pipeline fetches chunk ``k + 1`` while chunk ``k`` is merged.

A chunk that several work items name is only ever a piece of the trash
block (a live block is one request's, and a request's chunks are
distinct): the pipeline may fetch it for one item before another's merge
is written back, so any one item's rows land there or the old ones stay,
which is the trash block's contract. No live chunk is read after it was
written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode as _interpret, shard_parallel

#: most bytes of one operand's block in the kernel's window (three
#: operands, double-buffered): the heads are split over the grid above it
_BLOCK_BYTES = 1 << 20


def _merge(phys_ref, chunk_ref, first_ref, mine_ref, pool_ref, out_ref, *,
           s, lanes=False):
    del phys_ref, chunk_ref
    c, d = out_ref.shape[-2:]
    # a pool with its tokens along the last axis (``lanes``) takes the
    # request's by column instead of by row
    t = first_ref[pl.program_id(0)] \
        + jax.lax.broadcasted_iota(jnp.int32, (c, d), 1 if lanes else 0)
    lands = jnp.logical_and(t >= 0, t < s)
    out_ref[...] = jnp.where(lands[None], mine_ref[...], pool_ref[...])


@functools.partial(jax.jit, static_argnames=("s", "interpret", "lanes"))
def _chunk_write(pool, mine, phys, chunk, first, *, s, interpret,
                 lanes=False):
    n, h, c, d = mine.shape
    hb = h
    while hb > 1 and (hb * c * d * pool.dtype.itemsize > _BLOCK_BYTES
                      or h % hb):
        hb -= 1
    at_mine = pl.BlockSpec((None, hb, c, d),
                           lambda k, j, phys, chunk, first: (k, j, 0, 0))
    at_pool = pl.BlockSpec(
        (None, hb, c, d),
        lambda k, j, phys, chunk, first: (phys[k], j, chunk[k], 0))
    return pl.pallas_call(
        functools.partial(_merge, s=s, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n, h // hb),
            in_specs=[at_mine, at_pool], out_specs=at_pool),
        # the result, and with it the aliased operand, is held to HBM: left
        # free, XLA's memory space assignment moves a whole 52 MB pool into
        # the chip's alternate memory ahead of the kernel and back out
        # after it (a sliced prefetch and a ``copy-start`` of the pool's
        # shape, for two of four pools of GPT's 8 x 64 prefill program),
        # for a kernel that touches a few chunks. (A program that donates
        # the pool and returns NOTHING but this result is refused by XLA's
        # verifier, "Different aliasing shapes": every step returns more.)
        out_shape=pltpu.HBM(pool.shape, pool.dtype),
        # operands: phys, chunk, first, mine, pool -> the pool is the result
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="pool_chunk_write",
    )(phys, chunk, first, mine, pool)


def pool_chunk_write(pool, mine, phys, chunk, first, s: int,
                     lanes: bool = False):
    """``pool`` [blocks, h, bs, d] with, for each work item ``k``, rows
    ``r`` of its chunk (``c`` rows from row ``chunk[k] * c`` of block
    ``phys[k]``) replaced by ``mine[k, :, r]`` where ``0 <= first[k] + r <
    s`` -> the pool, updated in place when the caller donates it. With
    ``lanes`` the pool is ``[blocks, h, d, bs]``, a token a lane, and a
    chunk is a whole block: column ``r`` is replaced by ``mine[k, :, :,
    r]``. Under :func:`~.utils.kernel_sharding` each chip writes its own
    heads."""
    def local(pool, mine, phys, chunk, first):
        return _chunk_write(pool, mine, phys, chunk, first, s=int(s),
                            interpret=bool(_interpret()), lanes=bool(lanes))
    return shard_parallel(local, ("-h--", "-h--", "-", "-", "-"),
                          ("-h--",))(pool, mine, phys, chunk, first)
