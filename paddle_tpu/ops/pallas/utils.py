"""Shared policy helpers for the Pallas kernels (single source of truth
for backend detection and block-size selection, so the kernels cannot
drift apart)."""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P


def interpret_mode() -> bool:
    """Run kernels in the Pallas interpreter on CPU backends (tests,
    virtual meshes); compile via Mosaic on TPU."""
    return jax.default_backend() == "cpu"


#: TPU vector-register geometry: the last (lane) axis tiles in units of
#: LANE, the second-to-last (sublane) axis in units of SUBLANE (f32; bf16
#: and int8 need 16/32 sublanes, which LANE-padding also satisfies since
#: the kernels keep head_dim on the lane axis).
LANE = 128
SUBLANE = 8

#: ``jax.ad_checkpoint.checkpoint_name`` names of the flash forward's two
#: outputs (o, lse), whatever its tag, window or grouping. The kernel's
#: vjp places them; fleet.utils.recompute's checkpoint policy keeps them.
FLASH_RESIDUAL_NAMES = ("flash_o", "flash_lse")


def pick_block(n: int, preferred: int, minimum: int = 8) -> int:
    """Largest power-of-two divisor of ``n`` in [minimum, preferred]
    (Mosaic sublane alignment); 0 when none exists.

    This selects *sequence*-axis tiles only. The head_dim (lane) axis is
    never tiled by the kernels — it rides whole — so it must NOT be fed
    through ``pick_block``: a head_dim like 20 has no power-of-two
    divisor >= 8 and would return 0 (an untileable-shape ValueError in
    the callers) even though the kernel can run it fine by padding.
    Use :func:`pad_lane_dim` for that axis instead.
    """
    b = preferred
    while b >= minimum:
        if n % b == 0:
            return b
        b //= 2
    return 0


def pad_lane_dim(d: int) -> int:
    """Aligned width for a head_dim riding the lane (last) axis of a
    kernel block: the kernels zero-pad ``d`` up to this and slice the
    output back, instead of failing on awkward widths.

    Mosaic accepts a full-extent last block dim, but relayouts and MXU
    feeds want alignment: below one full LANE register we round up to
    the SUBLANE granule (d=20 -> 24, cheap); at or above a full lane we
    round to whole LANE multiples (d=150 -> 256) so the block tiles
    registers exactly. Common head dims (32/64/128) are already aligned
    and pass through unchanged — padding costs nothing in the standard
    configs.
    """
    d = int(d)
    if d <= 0:
        raise ValueError(f"head_dim must be positive, got {d}")
    if d < LANE:
        return -(-d // SUBLANE) * SUBLANE
    return -(-d // LANE) * LANE


# ---------------------------------------------------------------------
# Kernels inside a program that spans several chips
# ---------------------------------------------------------------------
# A Mosaic kernel is one opaque custom call. Inside a jitted program laid
# out over a mesh the compiler cannot split it, and JAX refuses to lower
# it ("Mosaic kernels cannot be automatically partitioned. Please wrap
# the call in a shard_map."). Under the Pallas interpreter on CPU devices
# the same kernel is plain HLO that GSPMD partitions like anything else,
# so only a chip shows the refusal. The attention kernels compute every
# (batch, head) pair independently: each chip can run the kernel on its
# own shard of those two axes. The step builders that own a mesh
# (jit.to_static / zero_train_step, the serving step entries) say which
# mesh axis each of the two is sharded over; the kernels read it here at
# trace time and wrap themselves in a shard_map.

_sharding = threading.local()


@contextlib.contextmanager
def kernel_sharding(mesh, batch=None, heads=None):
    """While tracing inside: kernels built by :func:`shard_parallel` run
    per chip of ``mesh``, with their batch axis split over mesh axis
    ``batch`` and their heads axis over mesh axis ``heads`` (None =
    whole on every chip). A None or one-device ``mesh`` changes
    nothing."""
    prev = getattr(_sharding, "ctx", None)
    _sharding.ctx = ((mesh, batch, heads)
                     if mesh is not None and mesh.size > 1 else None)
    try:
        yield
    finally:
        _sharding.ctx = prev


def shard_parallel(fn, in_dims, out_dims):
    """Wrap ``fn(*arrays, *static)`` so that under
    :func:`kernel_sharding` it runs once per chip on the local shard.

    ``in_dims`` / ``out_dims`` name, per array operand / result, what
    each axis is: ``"b"`` batch, ``"h"`` heads, ``"-"`` an axis that must
    be whole on every chip — e.g. ``"bh--"`` for q ``[b, h, s, d]``,
    ``"-h--"`` for a KV pool ``[blocks, h, rows, d]``. Outside the
    context (one device, or a caller that laid out nothing) it is plain
    ``fn``.
    """
    def call(*args):
        ctx = getattr(_sharding, "ctx", None)
        if ctx is None:
            return fn(*args)
        mesh, batch, heads = ctx
        arrays, static = args[:len(in_dims)], args[len(in_dims):]
        axis = {"b": batch, "h": heads, "-": None}

        def specs(dims):
            return tuple(P(*(axis[d] for d in dd)) for dd in dims)

        out_specs = specs(out_dims)
        return jax.shard_map(
            lambda *local: fn(*local, *static), mesh=mesh,
            in_specs=specs(in_dims),
            out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
            check_vma=False)(*arrays)
    return call
