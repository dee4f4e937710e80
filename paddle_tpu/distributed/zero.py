"""ZeRO-sharded optimizer plane (Rajbhandari et al., "ZeRO: Memory
Optimizations Toward Training Trillion Parameter Models").

The reference framework has *no* sharding/ZeRO optimizer at all
(distributed_strategy.proto:94-130 — the field does not exist); this
module closes that gap the TPU-native way: **pure pjit/GSPMD, no
explicit collectives**. Annotating the optimizer moments (stage 1) and
the gradients (stage 2) with data-axis ``NamedSharding``s is enough —
XLA inserts the reduce-scatter (grads onto moment shards), runs the
sharded update, and all-gathers the updated params where the next
forward demands them. No ``jax.shard_map``, no rewritten programs.

:func:`zero_train_step` mirrors ``jit.to_static``'s train-step contract
(same ``layers``/``optimizers`` state threading, same donate/retrace
semantics) with ZeRO layouts substituted, so a stage can be flipped by
``FLAGS_zero_stage`` without touching the step function.

The other half of the train→serve loop lives here too:
:func:`save_train_state` / :func:`load_train_state` checkpoint the
(sharded) optimizer state through ``CheckpointSaver`` — gather-on-save,
host numpy on disk — and :func:`weights_from_checkpoint` extracts the
param dict a running ``ServingEngine.swap_weights`` accepts.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import flags as _flags
from ..dygraph.tensor import Tensor
from ..jit import _StateSpec, _lower_step, batch_axis, to_static
from ..ops.pallas.utils import kernel_sharding
from .sharding import (ShardingRules, _param_names_by_id, map_opt_state,
                       opt_state_shardings, state_shardings,
                       zero_grad_specs)

__all__ = [
    "zero_train_step", "resolve_stage", "byte_report", "device_bytes",
    "save_train_state", "load_train_state", "weights_from_checkpoint",
]


def resolve_stage(stage: Optional[int] = None) -> int:
    """``stage`` argument if given, else ``FLAGS_zero_stage``; must be
    0, 1 or 2."""
    if stage is None:
        stage = _flags.get_flag("zero_stage")
    stage = int(stage)
    if stage not in (0, 1, 2):
        raise ValueError(
            f"zero_stage must be 0 (off), 1 (optimizer state) or 2 "
            f"(+ gradients), got {stage}")
    return stage


def _resolve_axis(mesh, axis: Optional[str]) -> str:
    """Data axis to shard over: explicit ``axis``, else ``"dp"`` /
    ``"data"`` when the mesh has one, else the first mesh axis."""
    names = tuple(mesh.axis_names)
    if axis is not None:
        if axis not in names:
            raise ValueError(
                f"zero axis {axis!r} not on mesh axes {names}")
        return axis
    for cand in ("dp", "data"):
        if cand in names:
            return cand
    return names[0]


def _constrain_zero(spec, snapshot, mesh, rules: ShardingRules,
                    axis: str, stage: int):
    """ZeRO-aware ``constrain_snapshot``: params/buffers pinned like the
    plain path, but optimizer moments (and stage-2 grads) pinned to
    their data-sharded ZeRO spec instead of inheriting the param layout
    — this in-graph pin is what makes GSPMD keep the update sharded
    rather than all-gathering the moments back."""
    from .sharding import constrain_snapshot

    out = constrain_snapshot(spec, snapshot, mesh, rules)
    if stage <= 0:
        return out
    # moments (and stage-2 grads) share one spec per parameter
    zspecs = zero_grad_specs(spec, mesh, rules, axis=axis)

    def c(v, s):
        if v is None:
            return None
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, s))

    out["opt"] = map_opt_state(spec, snapshot["opt"],
                               lambda i, v: c(v, zspecs[i]),
                               lambda v: c(v, P()))
    if stage >= 2 and "grads" in snapshot:
        out["grads"] = [c(v, s)
                        for v, s in zip(snapshot["grads"], zspecs)]
    return out


def zero_train_step(function=None, *, layers, optimizers, mesh,
                    param_rules=None, arg_specs=None, stage=None,
                    axis=None, donate_state: bool = True,
                    retain_grads: bool = True):
    """``jit.to_static`` for a train step with ZeRO optimizer-state
    partitioning over the mesh's data axis.

    Same contract as ``@to_static(layers=..., optimizers=..., mesh=...,
    param_rules=..., arg_specs=...)`` — the decorated function calls
    ``backward()`` and ``opt.step()``, state threads through one pjit'd
    computation — with the optimizer moments laid out per
    ``opt_state_shardings`` (stage >= 1) and the gradients
    reduce-scattered onto the same shards (stage 2). ``stage=None``
    reads ``FLAGS_zero_stage``; stage 0 delegates to plain
    ``to_static`` (replicated optimizer state). Tensor-parallel
    ``param_rules`` compose: ZeRO shards the first dim the rules leave
    free (see ``zero_partition_spec``).

    The returned wrapper exposes ``.byte_report()`` — the live
    per-device parameter/optimizer byte accounting (also published as
    ``zero_*_bytes_per_device`` gauges on every call).
    """
    stage_v = resolve_stage(stage)

    def deco(fn):
        if stage_v == 0:
            wrapper = to_static(fn, layers=layers, optimizers=optimizers,
                                donate_state=donate_state, mesh=mesh,
                                param_rules=param_rules,
                                arg_specs=arg_specs,
                                retain_grads=retain_grads)
            wrapper.byte_report = lambda: byte_report(
                layers, optimizers, stage=0)
            return wrapper
        if mesh is None:
            raise ValueError("zero_train_step stage >= 1 requires a mesh")
        axis_v = _resolve_axis(mesh, axis)
        rules = param_rules or ShardingRules([])
        spec_holder = {}

        def get_spec():
            if "spec" not in spec_holder:
                spec_holder["spec"] = _StateSpec(layers or [],
                                                 optimizers or [])
            return spec_holder["spec"]

        compiled_holder = {}

        def make_compiled(grads_present):
            def traced(state, args):
                spec = get_spec()
                spec.load(state)
                targs = jax.tree_util.tree_map(
                    lambda a: Tensor(a, stop_gradient=True), args)
                with kernel_sharding(mesh, batch=batch_axis(arg_specs)):
                    out = fn(*targs)
                out_arrays = jax.tree_util.tree_map(
                    lambda t: t.value if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
                new_state = spec.snapshot()
                if not retain_grads:
                    new_state["grads"] = [None] * len(new_state["grads"])
                new_state = _constrain_zero(spec, new_state, mesh, rules,
                                            axis_v, stage_v)
                return out_arrays, new_state

            from ..observability import compile_tracker as _ct
            spec = get_spec()
            st_sh = state_shardings(spec, mesh, rules)
            st_sh["opt"] = opt_state_shardings(spec, mesh, rules,
                                               axis=axis_v, stage=stage_v)
            if stage_v >= 2:
                g_sh = [NamedSharding(mesh, s)
                        for s in zero_grad_specs(spec, mesh, rules,
                                                 axis=axis_v)]
            else:
                g_sh = st_sh["params"]
            st_sh["grads"] = [sh if present else None
                              for sh, present in zip(g_sh, grads_present)]
            arg_sh = (tuple(NamedSharding(mesh, s) for s in arg_specs)
                      if arg_specs is not None else None)
            donate = (0,) if donate_state else ()
            return _ct.tracked_jit(
                "zero_train_step", traced,
                labels={"py_fn": getattr(fn, "__name__", "?"),
                        "stage": str(stage_v)},
                donate_argnums=donate, in_shardings=(st_sh, arg_sh))

        def prepare(args):
            state = get_spec().snapshot()
            grads_present = tuple(g is not None for g in state["grads"])
            key = (grads_present, _flags.version())
            if key not in compiled_holder:
                compiled_holder[key] = make_compiled(grads_present)
            arr_args = jax.tree_util.tree_map(
                lambda a: a.value if isinstance(a, Tensor)
                else jnp.asarray(a), tuple(args),
                is_leaf=lambda t: isinstance(t, Tensor))
            return compiled_holder[key], state, arr_args

        @functools.wraps(fn)
        def wrapper(*args):
            spec = get_spec()
            compiled, state, arr_args = prepare(args)
            try:
                out_arrays, new_state = compiled(state, arr_args)
            except Exception:
                # tracing assigns tracers into the eager Parameters; on a
                # mid-trace raise restore concrete state (to_static's
                # contract)
                spec.load(state)
                raise
            spec.load(new_state)
            byte_report(layers, optimizers, stage=stage_v)
            return jax.tree_util.tree_map(
                lambda a: Tensor(a, stop_gradient=True)
                if isinstance(a, jax.Array) else a, out_arrays)

        wrapper.__wrapped__ = fn
        wrapper.lower = functools.partial(_lower_step, prepare, get_spec)
        wrapper.byte_report = lambda: byte_report(layers, optimizers,
                                                  stage=stage_v,
                                                  publish=False)
        return wrapper

    if function is not None:
        return deco(function)
    return deco


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------


def device_bytes(arrays) -> tuple:
    """``(total_bytes, max_per_device_bytes)`` over concrete arrays.

    Sharded jax arrays count their local shard per device
    (``addressable_shards``); replicated arrays count fully on every
    device — so ``max_per_device`` is the real HBM high-water mark, the
    number the ZeRO memory win is measured by."""
    per: Dict = {}
    total = 0
    for a in arrays:
        if a is None:
            continue
        shards = getattr(a, "addressable_shards", None)
        if shards:
            total += int(a.nbytes)
            for s in shards:
                d = s.device
                per[d] = per.get(d, 0) + int(s.data.nbytes)
        else:
            nb = int(np.asarray(a).nbytes)
            total += nb
            per[None] = per.get(None, 0) + nb
    return total, (max(per.values()) if per else 0)


def byte_report(layers, optimizers, *, stage: int = 0,
                publish: bool = True) -> Dict[str, int]:
    """Live per-device parameter/optimizer byte accounting for a train
    state; published as ``zero_param_bytes_per_device`` /
    ``zero_opt_bytes_per_device`` gauges (labeled by stage) unless
    ``publish=False``."""
    spec = _StateSpec(layers or [], optimizers or [])
    p_total, p_dev = device_bytes([p.value for p in spec.params])
    o_total, o_dev = device_bytes(
        [v for o in spec.optimizers for v in o._eager_state.values()])
    rep = {"stage": int(stage),
           "param_bytes": p_total, "param_bytes_per_device": p_dev,
           "opt_bytes": o_total, "opt_bytes_per_device": o_dev}
    if publish:
        from .. import observability as _obs
        _obs.gauge("zero_param_bytes_per_device",
                   "max over devices of resident parameter bytes for "
                   "the last zero_train_step state").labels(
            stage=str(stage)).set(p_dev)
        _obs.gauge("zero_opt_bytes_per_device",
                   "max over devices of resident optimizer-state bytes "
                   "(ZeRO memory win shows up here: ~1/dp of the total "
                   "moment bytes at stage >= 1)").labels(
            stage=str(stage)).set(o_dev)
    return rep


# ---------------------------------------------------------------------------
# checkpoint: gather-on-save train state -> CheckpointSaver -> swap_weights
# ---------------------------------------------------------------------------

_PARAM_PREFIX = "param/"
_OPT_PREFIX = "opt{i}/"


def save_train_state(saver, layers, optimizers, number: int,
                     meta: Optional[dict] = None) -> str:
    """Checkpoint params + optimizer state through ``CheckpointSaver``.

    Gather-on-save: every (possibly ZeRO-sharded) array is pulled to
    host numpy (``np.asarray`` gathers the shards), so the file is
    layout-free — loadable into any stage/mesh, and directly consumable
    by ``ServingEngine.swap_weights`` via
    :func:`weights_from_checkpoint`. Keys: ``param/<dotted name>`` and
    ``opt<i>/<state_dict key>`` per optimizer."""
    spec = _StateSpec(layers or [], optimizers or [])
    names = _param_names_by_id(spec.layers)
    state: Dict[str, np.ndarray] = {}
    for p in spec.params:
        state[_PARAM_PREFIX + names.get(id(p), p.name)] = np.asarray(p.value)
    for i, o in enumerate(spec.optimizers):
        pre = _OPT_PREFIX.format(i=i)
        for k, v in o.state_dict().items():
            state[pre + k] = np.asarray(v)
    m = dict(meta or {})
    m.setdefault("zero_stage", _flags.get_flag("zero_stage"))
    return saver.save(state, number, meta=m)


def load_train_state(saver, layers, optimizers,
                     number: Optional[int] = None):
    """Restore a :func:`save_train_state` checkpoint into live
    layers/optimizers. Returns the checkpoint ``meta`` dict, or ``None``
    when the saver has no loadable checkpoint. Unknown params in the
    file are ignored (same forgiving contract as
    ``Optimizer.set_state_dict``)."""
    state, meta = saver.load(number)
    if state is None:
        return None
    by_name = {}
    for layer in (layers or []):
        for name, p in layer.named_parameters():
            by_name.setdefault(name, p)
    for key, v in state.items():
        if not key.startswith(_PARAM_PREFIX):
            continue
        p = by_name.get(key[len(_PARAM_PREFIX):])
        if p is not None:
            p.value = jnp.asarray(v, p.value.dtype)
    for i, o in enumerate(optimizers or []):
        pre = _OPT_PREFIX.format(i=i)
        sub = {k[len(pre):]: v for k, v in state.items()
               if k.startswith(pre)}
        if sub:
            o.set_state_dict(sub)
    return dict(meta or {})


def weights_from_checkpoint(state: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The ``{dotted param name: array}`` dict inside a
    :func:`save_train_state` checkpoint — the exact shape
    ``ServingEngine.swap_weights`` accepts."""
    return {k[len(_PARAM_PREFIX):]: v for k, v in state.items()
            if k.startswith(_PARAM_PREFIX)}
