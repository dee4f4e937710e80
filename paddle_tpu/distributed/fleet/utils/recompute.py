"""Activation recomputation for dygraph — fleet.utils.recompute parity.

Analog of the reference's `paddle.distributed.fleet.utils.recompute`
(python/paddle/distributed/fleet/utils/recompute.py: RecomputeFunction
saves only the inputs and re-runs the forward inside backward). The TPU
redesign: the wrapped segment executes under ``jax.checkpoint`` inside a
single tape op (``recompute_segment``). Where a gradient is wanted the
op's forward takes ``jax.vjp`` of the checkpoint and its grad op
(``recompute_segment_grad``) calls that vjp, so the backward reads what
the forward left and the forward is traced once. XLA materializes no
segment activations but the two that a flash attention kernel's backward
reads and cannot rebuild without running the kernel again: its output
and log-sum-exp (``_KEEP_FLASH``: one ``[b, h, s, d]`` array and one
``[b, h, 1, s]`` float32 row a segment, beside the segment's input).
Everything else (norms, projections, q, k and v, the MLP or expert pass)
is recomputed in the backward from the segment's input, trading FLOPs
for HBM. That is exactly what makes larger batches fit (see PERF.md:
batch 16 on the 345M flagship OOMs without this).

Static-graph programs have their own recompute path
(framework/backward.py checkpoint segments); this module is the dygraph/
to_static twin.

Parameters touched by the segment are discovered with a zero-FLOP
``jax.eval_shape`` probe (abstract tracing executes the python, so the
tape sees every Parameter the segment reads), then passed to the
checkpointed function explicitly so their gradients flow.
"""

from __future__ import annotations

import threading
from typing import Callable, List

import jax

from ....ops.pallas.utils import FLASH_RESIDUAL_NAMES

_probe_state = threading.local()

# ONE policy object for every segment of every model: jax.checkpoint's
# policy is a static argument of the checkpointed call, and one made anew
# a call is a new static argument a block.
_KEEP_FLASH = jax.checkpoint_policies.save_only_these_names(
    *FLASH_RESIDUAL_NAMES)


def _probe_hook(ins):
    """Called by Tracer.trace_op for every op while probing."""
    bag = getattr(_probe_state, "params", None)
    if bag is None:
        return
    from ....dygraph.tensor import Parameter
    for ts in ins.values():
        for t in ts:
            if isinstance(t, Parameter) and not t.stop_gradient \
                    and id(t) not in bag:
                bag[id(t)] = t


def _discover_params(function, arg_tensors) -> List:
    """Abstract-trace the segment to find the Parameters it reads."""
    from ....dygraph import tape as _tape
    from ....dygraph.tensor import Tensor

    prev_bag = getattr(_probe_state, "params", None)
    prev_hook = getattr(_tape._probe_tls, "hook", None)
    _probe_state.params = {}
    _tape._probe_tls.hook = _probe_hook
    try:
        def probe(arrs):
            outs = function(*[Tensor(a, stop_gradient=True)
                              for a in arrs])
            outs = outs if isinstance(outs, (list, tuple)) else [outs]
            return [t.value for t in outs]

        jax.eval_shape(probe, [t.value for t in arg_tensors])
        found = list(_probe_state.params.values())
    finally:
        _tape._probe_tls.hook = prev_hook
        _probe_state.params = prev_bag
    # nested probe: report our params upward too
    if prev_bag is not None:
        for p in found:
            prev_bag.setdefault(id(p), p)
    return found


def recompute(function: Callable, *args, preserve_rng_state: bool = True):
    """Run ``function(*args)`` storing no intermediate activations but a
    flash attention call's output and log-sum-exp; the backward pass
    re-executes the rest (fleet.utils.recompute parity).

    ``function`` must be jnp-traceable dygraph code (Layers / tensor
    ops). Returns the function's output Tensor(s) with gradients flowing
    to both ``args`` and every Parameter the segment touches.
    """
    from ....dygraph import tape as _tape
    from ....dygraph.tensor import Tensor
    from ....ops import registry as _reg

    arg_ts = [a if isinstance(a, Tensor) else Tensor(a) for a in args]
    params = _discover_params(function, arg_ts)

    # seed snapshot: stateful rng draws (dropout masks) start from the
    # call's seed whenever the segment is traced
    seed0 = _reg._EAGER_SEED

    def pure(param_arrays, arg_arrays):
        old_vals = [p.value for p in params]
        old_seed = _reg._EAGER_SEED
        _reg._EAGER_SEED = seed0
        try:
            for p, v in zip(params, param_arrays):
                p.value = v
            with _tape.no_grad():
                outs = function(*[Tensor(a, stop_gradient=True)
                                  for a in arg_arrays])
        finally:
            for p, v in zip(params, old_vals):
                p.value = v
            if preserve_rng_state:
                _reg._EAGER_SEED = old_seed
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        return [t.value for t in outs]

    # Execute as ONE tape op: its forward runs the checkpointed segment
    # and keeps the vjp, its grad op IS the rematerializing backward. The
    # segment rides in attrs (python object — dygraph only; program
    # recording filters it).
    differentiate = _tape.default_tracer().enabled and any(
        not t.stop_gradient for t in params + arg_ts)
    outs = _tape.run_op(
        "recompute_segment",
        {"Params": params, "X": arg_ts},
        {"__segment__": _Segment(jax.checkpoint(pure, policy=_KEEP_FLASH),
                                 differentiate)})
    out_list = outs["Out"]
    return out_list[0] if len(out_list) == 1 else tuple(out_list)


class _Segment:
    """One call of :func:`recompute` between its forward and its
    backward: the checkpointed function and, once the forward has run
    with a gradient wanted, its outputs and the vjp that holds what the
    backward reads (the inputs and what ``_KEEP_FLASH`` names)."""

    __slots__ = ("ckpt", "differentiate", "outs", "vjp")

    def __init__(self, ckpt, differentiate):
        self.ckpt, self.differentiate = ckpt, differentiate


def _register_lowerings():
    import jax.numpy as jnp
    import numpy as np

    from ....ops.registry import GRAD_SLOT_SUFFIX, register

    @register("recompute_segment")
    def _recompute_segment(ctx, ins, attrs):
        seg = attrs["__segment__"]
        args = list(ins.get("Params", [])), list(ins["X"])
        if not seg.differentiate:
            return {"Out": list(seg.ckpt(*args))}
        seg.outs, seg.vjp = jax.vjp(seg.ckpt, *args)
        return {"Out": list(seg.outs)}

    @register("recompute_segment_grad")
    def _recompute_segment_grad(ctx, ins, attrs):
        seg = attrs["__segment__"]
        present = attrs.get("__out_grad_present__", {}).get(
            "Out", [True] * len(seg.outs))
        given = iter(ins.get("Out" + GRAD_SLOT_SUFFIX, []))
        cot = []
        for out, has in zip(seg.outs, present):
            g = next(given) if has else None
            if not jnp.issubdtype(out.dtype, jnp.inexact):
                cot.append(np.zeros(out.shape, jax.dtypes.float0))
            else:
                cot.append(jnp.zeros_like(out) if g is None
                           else jnp.asarray(g, out.dtype))
        wanted = attrs.get("__in_grad_wanted__", {})
        return {slot + GRAD_SLOT_SUFFIX:
                [g for g, w in zip(grads, wanted.get(slot,
                                                     [True] * len(grads)))
                 if w]
                for slot, grads in zip(("Params", "X"), seg.vjp(cot))}


_register_lowerings()
