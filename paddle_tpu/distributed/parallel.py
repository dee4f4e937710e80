"""Process/mesh initialization for distributed training.

Analog of python/paddle/distributed/parallel.py (init_parallel_env:32,
ParallelEnv) — but TPU-native: instead of one OS process per GPU with NCCL
rank bootstrap (reference imperative/nccl_context.cc TCP ncclUniqueId
exchange), a single python process drives all local chips SPMD through a
jax.sharding.Mesh, and multi-host scaling uses jax.distributed (ICI/DCN
handled by the runtime). "ranks" are mesh positions.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np


class ParallelEnv:
    """Analog of fluid/dygraph/parallel.py ParallelEnv:62 — env-derived
    topology (PADDLE_TRAINER_ID etc. honored for launcher parity)."""

    def __init__(self):
        self._rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
        self._endpoints = os.getenv("PADDLE_TRAINER_ENDPOINTS", "").split(",")
        self._current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def trainer_endpoints(self):
        return self._endpoints

    @property
    def current_endpoint(self):
        return self._current_endpoint

    # legacy names
    local_rank = rank
    nranks = world_size


def _maybe_init_multiprocess():
    """Join the multi-process world described by the launcher env plane.

    The launcher (``paddle_tpu.distributed.launch --nproc_per_node N``)
    exports ``PADDLE_COORDINATOR`` + ``PADDLE_TRAINER_ID`` +
    ``PADDLE_TRAINERS_NUM`` — the analog of the reference's
    gen_nccl_id rank bootstrap (imperative/nccl_context.cc, launch_utils
    PADDLE_* plane), realized as ``jax.distributed.initialize``: after it
    returns, ``jax.devices()`` is the GLOBAL device list and GSPMD
    computations over a global mesh insert cross-process collectives.

    Testability plane: ``PADDLE_DIST_PLATFORM=cpu`` +
    ``PADDLE_DIST_DEVICES_PER_PROC=K`` provision K virtual CPU devices
    per process with the gloo cross-process collectives implementation —
    the TestDistBase-style CI path (no TPU pod required).
    """
    _apply_platform_env()
    coordinator = os.getenv("PADDLE_COORDINATOR")
    if not coordinator:
        return False
    import jax

    if jax.distributed.is_initialized():
        return True  # already initialized (idempotent re-entry)
    rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
    world = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=world, process_id=rank)
    return True


def _apply_platform_env():
    """Apply the launcher's platform plane (PADDLE_DIST_PLATFORM /
    PADDLE_DIST_DEVICES_PER_PROC) — must run before the jax backend is
    touched. It goes through ``jax.config.update`` and not the
    JAX_PLATFORMS variable: by the time a training script calls
    ``init_parallel_env`` it has imported jax, which read the
    environment once at import."""
    import jax

    platform = os.getenv("PADDLE_DIST_PLATFORM")
    ndev = os.getenv("PADDLE_DIST_DEVICES_PER_PROC")
    if not platform and not ndev:
        return
    try:
        if platform:
            jax.config.update("jax_platforms", platform)
        if ndev:
            jax.config.update("jax_num_cpu_devices", int(ndev))
        if (platform or "").startswith("cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception as e:
        raise RuntimeError(
            "multi-process init needs jax platform config before the "
            "backend is touched; call init_parallel_env() before any "
            f"device computation (config error: {e})")


def init_parallel_env(data_axis: str = "dp",
                      mesh_shape: Optional[dict] = None):
    """Create the device mesh and register ring 0 -> data axis.

    Single host: mesh over all local devices. Multi-process/multi-host:
    when the launcher's ``PADDLE_COORDINATOR`` env plane is present this
    first joins the global world via ``jax.distributed.initialize`` (so
    the mesh spans every process's devices); otherwise call
    jax.distributed.initialize yourself before this.
    Returns the ParallelEnv.
    """
    import jax
    from jax.sharding import Mesh
    from . import env as dist_env

    _maybe_init_multiprocess()

    from .env import build_mesh
    if mesh_shape:
        mesh = build_mesh(tuple(mesh_shape.keys()),
                          tuple(mesh_shape.values()))
    else:
        mesh = build_mesh((data_axis,))
    dist_env.set_mesh(mesh)
    dist_env.set_data_axis(data_axis if data_axis in mesh.axis_names else None)
    dist_env.register_ring(0, data_axis)
    return ParallelEnv()


def get_rank() -> int:
    return ParallelEnv().rank


def get_world_size() -> int:
    import jax
    ws = ParallelEnv().world_size
    if ws > 1:
        return ws
    from . import env as dist_env
    mesh = dist_env.current_mesh()
    if mesh is not None:
        return int(np.prod(list(mesh.shape.values())))
    return len(jax.devices())
