"""The chunked selective scan of a served prompt, as one Pallas kernel.

``ops/ssm_ops.py`` states the recurrence. Written out in XLA it either
materialises ``exp(dt A)`` and the states as ``[T, d_inner, d_state]``
float32 (1.3 GB a layer at 4096 rows of 5120 channels by 16) or runs as
``T`` sequential steps. Here the state stays in registers and VMEM:

- the grid is ``(rows, channel tiles, chunks of time)``, time innermost and
  sequential; a tile is ``SUBLANE x LANE`` = 1024 channels, so every value
  the loop touches is one dense vector register ``[8, 128]``, and the state
  of a tile is ``d_state`` of them, carried through the chunk's loop in
  registers and between chunks in a VMEM scratch;
- the channels ride as ``[groups, 128]``: ``x``, ``dt``, ``z`` and ``y`` are
  ``[rows, T, groups, 128]`` float32 (a reshape of ``[rows, T, d]``), so row
  ``t`` of a tile is one aligned ``[8, 128]`` load;
- ``B_t[n]`` and ``C_t[n]`` are scalars to the channels. A chunk's ``B``
  ``[chunk, n]`` is spread over the lanes once a chunk by the matrix unit
  (products of its three bfloat16 pieces with a 0/1 matrix, exact):
  ``[chunk, n x 128]`` in VMEM, of which eight rows of time are loaded as
  one aligned ``[8, 128]`` tile a state and row ``i`` of it broadcast over
  the sublanes at step ``i``;
- ``last`` [rows] is a scalar-prefetch operand: the state is copied out
  after row ``last`` of each prompt, so the bucket's padding behind it
  advances nothing that is kept.

The work is the vector unit's (an exponential and six multiply-adds a
channel and state a row; no matrix product but the spreading of ``B`` and
``C``), which no other kernel of this repo is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import LANE, SUBLANE, interpret_mode as _interpret, pick_block

NAME = "selective_scan"
#: rows of time of one grid step
CHUNK = 128


def tiles(t: int, d: int) -> bool:
    """Whether the kernel takes ``t`` rows of ``d`` channels: the channels
    in whole lanes, in tiles of 8 groups or as one tile of fewer, and the
    rows in chunks of at least a sublane tile."""
    groups, rest = divmod(d, LANE)
    return rest == 0 and (groups % SUBLANE == 0 or groups < SUBLANE) \
        and pick_block(t, CHUNK) >= SUBLANE


def _kernel(last_ref, x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
            y_ref, s_ref, carry, b_wide, c_wide, *, chunk, n):
    row, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    # column j * LANE + l of `spread` picks state j: B[t, j] on 128 lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, n * LANE), 1)
    state = jax.lax.broadcasted_iota(jnp.int32, (n, n * LANE), 0)
    spread = (lane // LANE == state).astype(jnp.bfloat16)
    for src, dst in ((b_ref, b_wide), (c_ref, c_wide)):
        # three bfloat16 pieces that sum to the float32 value exactly
        # (8 + 8 + 8 bits of mantissa), each spread by a bfloat16 product
        # with a 0/1 matrix, which the matrix unit computes exactly: no
        # reliance on how a float32 product's precision is lowered (a
        # single bfloat16 pass rounds B and C to 3 digits, and the state
        # with them)
        rest, wide = src[...], jnp.zeros(dst.shape, jnp.float32)
        for _ in range(3):
            piece = rest.astype(jnp.bfloat16)
            rest = rest - piece.astype(jnp.float32)
            # DEFAULT, whatever jax_default_matmul_precision says: one
            # bfloat16 pass is exact here, and Mosaic takes no other for
            # bfloat16 operands
            wide = wide + jnp.dot(piece, spread,
                                  precision=jax.lax.Precision.DEFAULT,
                                  preferred_element_type=jnp.float32)
        dst[...] = wide

    last = last_ref[row] - step * chunk
    d_skip = d_ref[...]

    def body(t8, s):
        # eight rows of time a pass: one aligned [8, 128] load of the
        # spread B and C a state, a static row of it a step
        t0 = pl.multiple_of(t8 * SUBLANE, SUBLANE)
        b_rows = [b_wide[pl.ds(t0, SUBLANE), j * LANE:(j + 1) * LANE]
                  for j in range(n)]
        c_rows = [c_wide[pl.ds(t0, SUBLANE), j * LANE:(j + 1) * LANE]
                  for j in range(n)]
        s = list(s)
        for i in range(SUBLANE):
            t = t0 + i
            xt, dtt = x_ref[t], dt_ref[t]
            fed = dtt * xt
            y = d_skip * xt
            for j in range(n):
                s[j] = jnp.exp(dtt * a_ref[j]) * s[j] \
                    + fed * b_rows[j][i:i + 1]
                y = y + s[j] * c_rows[j][i:i + 1]
            zt = z_ref[t]
            y_ref[t] = y * (zt * jax.nn.sigmoid(zt))

            @pl.when(t == last)
            def _(s=tuple(s)):
                for j in range(n):
                    s_ref[j] = s[j]
        return tuple(s)

    s = jax.lax.fori_loop(0, chunk // SUBLANE, body,
                          tuple(carry[j] for j in range(n)))
    for j in range(n):
        carry[j] = s[j]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(x, dt, a, b, c, d_skip, z, last, interpret=None):
    """``ssm_ops.selective_scan_sequential``'s operands and results:
    ``x``, ``dt``, ``z`` [rows, T, d], ``a`` [n, d], ``b``, ``c``
    [rows, T, n], ``d_skip`` [d], ``last`` [rows] int32 -> (gated y
    [rows, T, d] float32, the state after row ``last`` [rows, n, d])."""
    rows, t, d = x.shape
    n = a.shape[0]
    if not tiles(t, d):
        raise ValueError(f"selective_scan cannot tile {t} rows of {d} "
                         f"channels")
    groups = d // LANE
    tile = min(SUBLANE, groups)
    chunk = pick_block(t, CHUNK)
    f32 = jnp.float32

    def wide(v):
        return v.astype(f32).reshape(rows, t, groups, LANE)
    rowwise = pl.BlockSpec((None, chunk, tile, LANE),
                           lambda r, g, k, last: (r, k, g, 0))
    coeff = pl.BlockSpec((None, chunk, n), lambda r, g, k, last: (r, k, 0))
    y, s = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n=n),
        name=NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, groups // tile, t // chunk),
            in_specs=[
                rowwise, rowwise, rowwise, coeff, coeff,
                pl.BlockSpec((n, tile, LANE),
                             lambda r, g, k, last: (0, g, 0)),
                pl.BlockSpec((tile, LANE), lambda r, g, k, last: (g, 0)),
            ],
            out_specs=[
                rowwise,
                pl.BlockSpec((None, n, tile, LANE),
                             lambda r, g, k, last: (r, 0, g, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n, tile, LANE), f32),
                pltpu.VMEM((chunk, n * LANE), f32),
                pltpu.VMEM((chunk, n * LANE), f32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((rows, t, groups, LANE), f32),
            jax.ShapeDtypeStruct((rows, n, groups, LANE), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(last, jnp.int32), wide(x), wide(dt), wide(z),
      b.astype(f32), c.astype(f32),
      a.astype(f32).reshape(n, groups, LANE),
      d_skip.astype(f32).reshape(groups, LANE))
    return y.reshape(rows, t, d), s.reshape(rows, n, d)
