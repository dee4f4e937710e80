"""jit — dygraph-to-static compilation.

Analog of python/paddle/fluid/dygraph/jit.py + dygraph_to_static/ (the
ProgramTranslator AST transpiler, program_translator.py:667). The TPU-native
design is radically simpler: every dygraph op is already a jnp call, so an
entire eager train step can be traced by jax.jit. ``to_static`` wraps a
function, threading all mutable framework state (parameter values, grads,
optimizer accumulators, PRNG) through the traced function as inputs/outputs
— so param mutation by ``optimizer.step()`` and ``.grad`` accumulation by
``backward()`` happen ON TRACERS inside the compiled computation and are
written back to the eager objects after each call.

This is the dygraph performance path on TPU: one XLA computation per step
instead of per-op dispatch.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Callable, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .dygraph.layers import Layer
from .dygraph.tensor import Parameter, Tensor
from .ops.pallas.utils import kernel_sharding


class _StateSpec:
    """Collects the mutable state a traced step touches."""

    def __init__(self, layers: Sequence[Layer], optimizers: Sequence):
        self.layers = list(layers)
        self.params: List[Parameter] = []
        self.buffers: List[Tensor] = []
        seen = set()
        for layer in layers:
            for p in layer.parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    self.params.append(p)
            for sub in layer.sublayers(include_self=True):
                for b in sub._buffers.values():
                    if id(b) not in seen:
                        seen.add(id(b))
                        self.buffers.append(b)
        self.optimizers = list(optimizers)
        self._index = {id(p): i for i, p in enumerate(self.params)}

    def opt_key(self, key):
        """An optimizer-state key as the traced step sees it: the
        optimizer's ``(id(param), slot)`` becomes ``(index of the param
        in this spec, slot)``. An id differs from process to process, and
        a pytree's keys end up in the lowered program (argument order,
        ``jax.result_info``): with ids in them the same step would never
        find itself in the persistent compile cache."""
        if isinstance(key, tuple) and key[0] in self._index:
            return (self._index[key[0]],) + key[1:]
        return key

    def _eager_key(self, key):
        """Inverse of :meth:`opt_key`."""
        if isinstance(key, tuple) and isinstance(key[0], int) \
                and key[0] < len(self.params):
            return (id(self.params[key[0]]),) + key[1:]
        return key

    def snapshot(self):
        """-> pytree of current state arrays."""
        # OrderedDict keeps the order the optimizer made its accumulators
        # in (a plain dict is flattened sorted by key)
        opt_states = [OrderedDict((self.opt_key(k), v)
                                  for k, v in opt._eager_state.items())
                      for opt in self.optimizers]
        return {
            "params": [p.value for p in self.params],
            "grads": [None if p.grad is None else p.grad.value
                      for p in self.params],
            "buffers": [b.value for b in self.buffers],
            "opt": opt_states,
        }

    def load(self, state):
        for p, v in zip(self.params, state["params"]):
            p.value = v
        for p, g in zip(self.params, state["grads"]):
            p.grad = None if g is None else Tensor(g, stop_gradient=True)
        for b, v in zip(self.buffers, state["buffers"]):
            b.value = v
        for opt, os in zip(self.optimizers, state["opt"]):
            opt._eager_state = {self._eager_key(k): v
                                for k, v in os.items()}


def to_static(function: Optional[Callable] = None, *, layers=None,
              optimizers=None, donate_state: bool = True, mesh=None,
              param_rules=None, arg_specs=None, ast_convert: bool = False,
              retain_grads: bool = True):
    """Compile a dygraph function into one XLA computation.

    - forward-only: ``fast = to_static(model)`` or
      ``@to_static(layers=[model])`` — params thread automatically.
    - train step: ``@to_static(layers=[model], optimizers=[opt])`` around a
      function that calls backward() and opt.step(); param/accumulator
      updates happen inside the compiled computation.
    - SPMD: pass ``mesh`` (jax.sharding.Mesh) + ``param_rules``
      (distributed.sharding.ShardingRules) + ``arg_specs`` (PartitionSpec
      per step argument) and the whole train step compiles GSPMD-sharded:
      params/grads/optimizer state laid out per the rules, XLA inserting
      the collectives. This subsumes the reference's ParallelExecutor +
      allreduce-insertion machinery for the dygraph path.

    Inputs may be Tensors or arrays; outputs mirror the function's returns
    with Tensors for traced arrays. Retraces on new input shapes/dtypes.

    ``ast_convert=True`` first runs the dygraph_to_static source
    converter over the function (the reference's ProgramTranslator AST
    mode): supported data-dependent ``if`` statements become traceable
    where-merges instead of tripping the traced-``__bool__`` guard.

    ``retain_grads=False`` (capacity lever for billion-param training):
    when the optimizer update runs INSIDE the step, gradients never
    need to leave the computation — dropping them from the output state
    lets XLA free each grad as soon as its parameter update consumes
    it, instead of materializing all of them as step outputs. After the
    call every ``p.grad`` is None (the reference's
    clear_grad(set_to_none=True) semantics). Measured: peak HBM at 1B
    scale drops by the full fp32-grads footprint (PERF.md ≥1B capacity
    analysis).
    """
    if function is not None and isinstance(function, Layer) and layers is None:
        layer = function
        if ast_convert:
            # AST mode targets the layer's forward (the lambda below has
            # no convertible source); hooks still run via __call__
            from .dygraph.dygraph_to_static import convert_function
            layer.forward = convert_function(layer.forward)
        return to_static(lambda *a, **kw: layer(*a, **kw), layers=[layer],
                         optimizers=optimizers, donate_state=donate_state,
                         mesh=mesh, param_rules=param_rules,
                         arg_specs=arg_specs)

    def deco(fn):
        if ast_convert:
            from .dygraph.dygraph_to_static import convert_function
            fn = convert_function(fn)
        spec_holder = {}

        def get_spec():
            if "spec" not in spec_holder:
                if mesh is None:
                    _make_state_before_the_first_step(optimizers or [])
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
                    # grads stay internal: XLA frees each one at its
                    # consuming param update (set_to_none contract)
                    new_state["grads"] = [None] * len(new_state["grads"])
                if mesh is not None:
                    # pin fed-back state layouts in-graph (lazy opt
                    # accumulators make out_shardings unusable)
                    from .distributed.sharding import (ShardingRules,
                                                       constrain_snapshot)
                    new_state = constrain_snapshot(
                        spec, new_state, mesh,
                        param_rules or ShardingRules([]))
                return out_arrays, new_state
            donate = (0,) if donate_state else ()
            from .observability import compile_tracker as _ct
            _labels = {"py_fn": getattr(fn, "__name__", "?")}
            if mesh is None:
                return _ct.tracked_jit("to_static", traced,
                                       labels=_labels,
                                       donate_argnums=donate)
            from jax.sharding import NamedSharding
            from .distributed.sharding import ShardingRules, state_shardings
            rules = param_rules or ShardingRules([])
            st_sh = state_shardings(get_spec(), mesh, rules)
            st_sh["grads"] = [sh if present else None
                              for sh, present in zip(st_sh["params"],
                                                     grads_present)]
            arg_sh = (tuple(NamedSharding(mesh, s) for s in arg_specs)
                      if arg_specs is not None else None)
            return _ct.tracked_jit("to_static", traced, labels=_labels,
                                   donate_argnums=donate,
                                   in_shardings=(st_sh, arg_sh))

        def prepare(args):
            """-> (jitted step for the current state structure, state,
            array args)."""
            state = get_spec().snapshot()
            grads_present = tuple(g is not None for g in state["grads"])
            # flags version: a set_flags() between calls must retrace so
            # flag-gated lowerings (pallas attention/LN) take effect
            from . import flags as _flags
            key = (grads_present, _flags.version())
            if key not in compiled_holder:
                compiled_holder[key] = make_compiled(grads_present)
            arr_args = jax.tree_util.tree_map(
                lambda a: a.value if isinstance(a, Tensor) else jnp.asarray(a),
                tuple(args),
                is_leaf=lambda t: isinstance(t, Tensor))
            return compiled_holder[key], state, arr_args

        @functools.wraps(fn)
        def wrapper(*args):
            spec = get_spec()
            compiled, state, arr_args = prepare(args)
            try:
                out_arrays, new_state = compiled(state, arr_args)
            except Exception:
                # tracing assigns tracers into the eager Parameters; if the
                # user fn raised mid-trace, restore concrete state so the
                # model isn't left holding dead tracers
                spec.load(state)
                raise
            spec.load(new_state)
            return jax.tree_util.tree_map(
                lambda a: Tensor(a, stop_gradient=True) if isinstance(
                    a, jax.Array) else a, out_arrays)
        wrapper.__wrapped__ = fn
        wrapper.lower = functools.partial(_lower_step, prepare, get_spec)
        return wrapper

    if function is not None:
        return deco(function)
    return deco


def _make_state_before_the_first_step(optimizers):
    """On one device a step's optimizers get their accumulators before the
    step is first traced (``Optimizer.init_state``: the values the first
    ``step()`` would give them). The state then has one structure from
    the first call on and the step is ONE program: made inside the first
    call, the accumulators were outputs of a program of their own that
    ran once, and a second program was traced, lowered and compiled for
    the state as it then was (at ``gpt2-1p1b`` a minute of set-up cold,
    20 s warm, and a second 65 MB entry in the persistent cache: PERF.md
    section 6, PR 60). Across a mesh they stay lazy: made inside the
    first step they are born with the layout the rules give them, and a
    copy of every moment on one device is what ZeRO and tensor
    parallelism are there to avoid. An optimizer with no eager step path,
    or with no parameter list, is left as it is."""
    for opt in optimizers:
        if getattr(opt, "_eager_op", None) is not None \
                and getattr(opt, "_parameter_list", None) is not None:
            opt.init_state()


def batch_axis(arg_specs):
    """The mesh axis a sharded step splits its batch over: the leading
    entry the step arguments' PartitionSpecs agree on, else None. Read
    by the Pallas kernels (``kernel_sharding``), which run per chip on
    their own rows of the batch."""
    firsts = {s[0] if len(s) else None for s in (arg_specs or ())}
    return firsts.pop() if len(firsts) == 1 else None


def _lower_step(prepare, get_spec, *args, place=None):
    """``step.lower(*args)``: the ``jax.stages.Lowered`` of the program
    the next ``step(*args)`` would run, for reading what the compiler
    made of it (``.compile().as_text()``, ``.memory_analysis()``).
    Runs nothing and leaves the eager state as it was. ``place`` maps
    every state/argument array to what is handed to ``jit.lower`` — a
    ``jax.ShapeDtypeStruct`` on a described device compiles the step
    for a chip that is not attached."""
    compiled, state, arr_args = prepare(args)
    placed = (state, arr_args)
    if place is not None:
        placed = jax.tree_util.tree_map(place, placed)
    try:
        return compiled.lower(*placed)
    finally:
        # a trace leaves tracers in the eager Parameters
        get_spec().load(state)


def to_static_multi_step(fn, *, layers, optimizers=None,
                         donate_state: bool = True, mesh=None,
                         param_rules=None, arg_specs=None,
                         retain_grads: bool = True):
    """Compile K chained train steps into ONE XLA execution (lax.scan).

    The analog of the reference's ``train_from_dataset`` trainer loop
    (framework/trainer.h:41, multi_trainer.cc:120): keep the host out of
    the per-step path entirely. Each wrapper argument carries a leading
    step dimension [K, ...]; the returned outputs are stacked [K, ...].

    The state pytree must be structurally stable across steps — run ONE
    ordinary ``to_static`` step first so grads and optimizer accumulators
    exist, then hand the same layers/optimizers here.
    """
    spec = _StateSpec(layers or [], optimizers or [])
    compiled_holder = {}

    def make_compiled():
        def body(state, x):
            spec.load(state)
            targs = jax.tree_util.tree_map(
                lambda a: Tensor(a, stop_gradient=True), x)
            out = fn(*targs)
            out_arrays = jax.tree_util.tree_map(
                lambda t: t.value if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))
            snap = spec.snapshot()
            if not retain_grads:
                # keep the scan carry grad-free: XLA frees each grad at
                # its consuming update (same lever as to_static)
                snap["grads"] = [None] * len(snap["grads"])
            return snap, out_arrays

        def traced(state, args):
            new_state, outs = jax.lax.scan(body, state, args)
            return outs, new_state

        donate = (0,) if donate_state else ()
        from .observability import compile_tracker as _ct
        _labels = {"py_fn": getattr(fn, "__name__", "?")}
        if mesh is None:
            return _ct.tracked_jit("to_static_multi_step", traced,
                                   labels=_labels, donate_argnums=donate)
        from jax.sharding import NamedSharding
        from .distributed.sharding import ShardingRules, state_shardings
        rules = param_rules or ShardingRules([])
        st_sh = state_shardings(spec, mesh, rules)
        st_sh["grads"] = [sh if p.grad is not None else None
                          for sh, p in zip(st_sh["params"], spec.params)]
        arg_sh = (tuple(NamedSharding(mesh, s) for s in arg_specs)
                  if arg_specs is not None else None)
        return _ct.tracked_jit("to_static_multi_step", traced,
                               labels=_labels, donate_argnums=donate,
                               in_shardings=(st_sh, arg_sh))

    def wrapper(*args):
        state = spec.snapshot()
        if "c" not in compiled_holder:
            compiled_holder["c"] = make_compiled()
        arr_args = jax.tree_util.tree_map(
            lambda a: a.value if isinstance(a, Tensor) else jnp.asarray(a),
            tuple(args), is_leaf=lambda t: isinstance(t, Tensor))
        try:
            outs, new_state = compiled_holder["c"](state, arr_args)
        except Exception:
            spec.load(state)
            raise
        spec.load(new_state)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=True)
            if isinstance(a, jax.Array) else a, outs)

    wrapper.__wrapped__ = fn
    return wrapper


class InputSpec:
    """Shape/dtype spec for jit.save tracing — the ONE InputSpec class,
    re-exported as paddle.static.InputSpec (they are the same class in
    the reference too). None dims normalize to -1."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = [-1 if d is None else int(d) for d in shape]
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return (f"InputSpec(shape={self.shape}, dtype={self.dtype!r}, "
                f"name={self.name!r})")


class TranslatedLayer:
    """A loaded jit.save artifact: Program + params, callable like the
    original Layer (hapi/jit TranslatedLayer parity). Runs through the
    trace-once Executor, so the first call compiles and the rest are
    cached."""

    def __init__(self, program, feed_names, fetch_names, state):
        import jax.numpy as _jnp
        from .framework import Executor, Scope
        self.program = program
        self._feed_names = feed_names
        self._fetch_names = fetch_names
        self._scope = Scope()
        for k, v in state.items():
            self._scope.set_var(k, _jnp.asarray(v))
        self._exe = Executor()

    def __call__(self, *args):
        import numpy as _np
        feed = {n: (a.value if isinstance(a, Tensor) else a)
                for n, a in zip(self._feed_names, args)}
        outs = self._exe.run(self.program, feed=feed,
                             fetch_list=self._fetch_names,
                             scope=self._scope)
        outs = [Tensor(jnp.asarray(o), stop_gradient=True) for o in outs]
        return outs[0] if len(outs) == 1 else tuple(outs)

    def state_dict(self):
        return {n: self._scope.find_var(n)
                for n in self._scope.all_var_names()}


def save(layer, path: str, input_spec=None):
    """jit.save: trace the layer's forward into a Program (the
    ProgramDescTracer analog — imperative/jit/program_desc_tracer.cc /
    dygraph/jit.py TracedLayer) and persist Program JSON (.pdmodel) +
    parameters (.pdparams). Inference semantics: the layer is traced in
    eval() mode."""
    import os

    import numpy as np

    from .dygraph.tape import record_program
    from .framework.program import Program

    if input_spec is None:
        raise ValueError("jit.save requires input_spec (shapes/dtypes or "
                         "example Tensors) to trace the forward")
    inputs = []
    for s in input_spec:
        if isinstance(s, Tensor):
            inputs.append(s)
        elif isinstance(s, InputSpec):
            shape = tuple(1 if d in (-1, None) else d for d in s.shape)
            inputs.append(Tensor(jnp.zeros(shape, s.dtype),
                                 stop_gradient=True))
        else:
            inputs.append(Tensor(jnp.asarray(s), stop_gradient=True))

    was_training = getattr(layer, "training", False)
    layer.eval()
    try:
        prog = Program()
        with record_program(prog):
            out = layer(*inputs)
    finally:
        if was_training:
            layer.train()
    outs = list(out) if isinstance(out, (list, tuple)) else [out]
    blk = prog.global_block()
    feed_names = []
    for t in inputs:
        if t.name in blk.vars:
            blk.vars[t.name].is_data = True
        feed_names.append(t.name)
    meta = {
        "program": prog.to_dict(),
        "feed_names": feed_names,
        "fetch_names": [t.name for t in outs],
    }
    import json
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + ".pdmodel", "w") as f:
        json.dump(meta, f)
    # persist under the traced VAR names (the program references t.name;
    # state_dict's structured names are a different namespace). Params
    # and buffers (batch-norm running stats) both appear in the recorded
    # program as non-feed inputs.
    var_state = {}
    for v in layer.state_dict().values():
        if hasattr(v, "name") and v.name in blk.vars:
            var_state[v.name] = np.asarray(v.value)
    np.savez(path + ".pdiparams", **var_state)
    return prog


def load(path: str) -> TranslatedLayer:
    """jit.load: restore the traced Program + params as a callable."""
    import json

    import numpy as np

    from .framework.program import Program

    with open(path + ".pdmodel") as f:
        meta = json.load(f)
    prog = Program.from_dict(meta["program"])
    # same structural cleanup the inference Predictor applies on load
    # (ir_pass_manager.cc analog): saved programs are is_test traces, so
    # dropout deletion / BN folding are always valid here
    from .inference import apply_inference_passes
    prog = apply_inference_passes(prog)
    data = np.load(path + ".pdiparams.npz")
    state = {k: data[k] for k in data.files}
    return TranslatedLayer(prog, meta["feed_names"], meta["fetch_names"],
                           state)


# AST-mode entry points (ProgramTranslator parity) — re-exported so user
# code can write `from paddle_tpu.jit import declarative`
from .dygraph.dygraph_to_static import (ProgramTranslator,  # noqa: E402
                                        convert_function, declarative)
