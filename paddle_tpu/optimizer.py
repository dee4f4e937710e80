"""Optimizers over the Program IR.

Analog of python/paddle/fluid/optimizer.py:56-3100: ``minimize(loss)`` runs
append_backward then appends per-parameter update ops (+ accumulator vars
initialized by the startup program). Regularization and gradient clipping
are program rewrites, matching the reference's capability so downstream
passes (DGC, gradient merge, AMP) can see them.

The same classes also drive dygraph parameters (see dygraph/ engine):
``apply_gradients`` works on eager tensors through the op lowerings.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from .framework import unique_name
from .framework.backward import append_backward
from . import optimizer_lr as lr  # paddle.optimizer.lr namespace
from .framework.program import (Variable, default_main_program,
                                default_startup_program)
from .layers.tensor import create_global_var


class GradClipBase:
    def _clip_static(self, params_grads, block):
        raise NotImplementedError


class GradientClipByValue(GradClipBase):
    def __init__(self, max, min=None):  # noqa: A002
        self.max = float(max)
        self.min = float(min) if min is not None else -float(max)

    def _clip_eager(self, params):
        import jax.numpy as jnp
        from .dygraph.tensor import Tensor
        for p in params:
            if p.grad is not None:
                p.grad = Tensor(jnp.clip(p.grad.value, self.min, self.max),
                                stop_gradient=True)

    def _clip_static(self, params_grads, block):
        out = []
        for p, g in params_grads:
            clipped = block.create_var(unique_name.generate(g.name + "@CLIP"),
                                       stop_gradient=True)
            block.append_op("clip", {"X": g}, {"Out": clipped},
                            {"min": self.min, "max": self.max,
                             "op_role": "optimize"})
            out.append((p, clipped))
        return out


class GradientClipByNorm(GradClipBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def _clip_eager(self, params):
        import jax.numpy as jnp
        from .dygraph.tensor import Tensor
        for p in params:
            if p.grad is None:
                continue
            g = p.grad.value
            norm = jnp.sqrt(jnp.sum(jnp.square(g)))
            scale = jnp.where(norm > self.clip_norm,
                              self.clip_norm / jnp.maximum(norm, 1e-12), 1.0)
            p.grad = Tensor(g * scale, stop_gradient=True)

    def _clip_static(self, params_grads, block):
        out = []
        for p, g in params_grads:
            clipped = block.create_var(unique_name.generate(g.name + "@CLIP"),
                                       stop_gradient=True)
            block.append_op("clip_by_norm", {"X": g}, {"Out": clipped},
                            {"max_norm": self.clip_norm,
                             "op_role": "optimize"})
            out.append((p, clipped))
        return out


class GradientClipByGlobalNorm(GradClipBase):
    """sqrt(sum ||g||^2) <= clip_norm — the transformer staple."""

    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def _clip_eager(self, params):
        import jax.numpy as jnp
        from .dygraph.tensor import Tensor
        gs = [p.grad.value for p in params if p.grad is not None]
        if not gs:
            return
        total = sum(jnp.sum(jnp.square(g)) for g in gs)
        norm = jnp.sqrt(total)
        scale = self.clip_norm / jnp.maximum(norm, self.clip_norm)
        for p in params:
            if p.grad is not None:
                p.grad = Tensor(p.grad.value * scale, stop_gradient=True)

    def _clip_static(self, params_grads, block):
        sq_names = []
        for _, g in params_grads:
            sq = block.create_var(unique_name.generate("gsq"),
                                  stop_gradient=True)
            block.append_op("squared_l2_norm", {"X": g}, {"Out": sq},
                            {"op_role": "optimize"})
            sq_names.append(sq.name)
        total = block.create_var(unique_name.generate("global_norm_sq"),
                                 stop_gradient=True)
        block.append_op("sum", {"X": sq_names}, {"Out": total},
                        {"op_role": "optimize"})
        norm = block.create_var(unique_name.generate("global_norm"),
                                stop_gradient=True)
        block.append_op("sqrt", {"X": total}, {"Out": norm},
                        {"op_role": "optimize"})
        # scale = clip / max(norm, clip)
        maxed = block.create_var(unique_name.generate("norm_max"),
                                 stop_gradient=True)
        clip_v = block.create_var(unique_name.generate("clip_const"),
                                  stop_gradient=True)
        block.append_op("fill_constant_like", {"X": norm}, {"Out": clip_v},
                        {"value": self.clip_norm, "op_role": "optimize"})
        block.append_op("elementwise_max", {"X": norm, "Y": clip_v},
                        {"Out": maxed}, {"op_role": "optimize"})
        scale_var = block.create_var(unique_name.generate("clip_scale"),
                                     stop_gradient=True)
        block.append_op("elementwise_div", {"X": clip_v, "Y": maxed},
                        {"Out": scale_var}, {"op_role": "optimize"})
        out = []
        for p, g in params_grads:
            clipped = block.create_var(unique_name.generate(g.name + "@CLIP"),
                                       stop_gradient=True)
            block.append_op("elementwise_mul", {"X": g, "Y": scale_var},
                            {"Out": clipped},
                            {"axis": -1, "op_role": "optimize"})
            out.append((p, clipped))
        return out


# Declarative spec for the eager (dygraph) step path: per op type, the
# accumulator slots (slot name, accum key, init, shape override) and the
# output->state writeback map. Drives Optimizer.step() through the same
# op lowerings the static executor uses.
_EAGER_SPECS = {
    "sgd": dict(accums=[], outs={"ParamOut": "param"}),
    "momentum": dict(accums=[("Velocity", "velocity", 0.0, None)],
                     outs={"ParamOut": "param", "VelocityOut": "velocity"}),
    "lars_momentum": dict(accums=[("Velocity", "velocity", 0.0, None)],
                          outs={"ParamOut": "param",
                                "VelocityOut": "velocity"}),
    "adagrad": dict(accums=[("Moment", "moment", 0.0, None)],
                    outs={"ParamOut": "param", "MomentOut": "moment"}),
    "adam": dict(accums=[("Moment1", "m1", 0.0, None),
                         ("Moment2", "m2", 0.0, None),
                         ("Beta1Pow", "b1p", 1.0, (1,)),
                         ("Beta2Pow", "b2p", 1.0, (1,))],
                 outs={"ParamOut": "param", "Moment1Out": "m1",
                       "Moment2Out": "m2", "Beta1PowOut": "b1p",
                       "Beta2PowOut": "b2p"}),
    "rmsprop": dict(accums=[("MeanSquare", "ms", 0.0, None),
                            ("Moment", "mom", 0.0, None)],
                    outs={"ParamOut": "param", "MeanSquareOut": "ms",
                          "MomentOut": "mom"}),
    "ftrl": dict(accums=[("SquaredAccumulator", "sq", 0.0, None),
                         ("LinearAccumulator", "lin", 0.0, None)],
                 outs={"ParamOut": "param", "SquaredAccumOut": "sq",
                       "LinearAccumOut": "lin"}),
}
_EAGER_SPECS["adamw"] = _EAGER_SPECS["adam"]
_EAGER_SPECS["lamb"] = _EAGER_SPECS["adam"]


class Optimizer:
    """Base (analog of fluid/optimizer.py:56).

    Serves both modes: ``minimize(loss)`` rewrites a static Program;
    ``step()`` applies updates eagerly to dygraph Parameters passed via
    ``parameters=``/``parameter_list`` (2.0 paddle.optimizer surface).
    """

    _accum_specs: Sequence[Tuple[str, float]] = ()  # (name, init value)
    _eager_op: Optional[str] = None  # op type for the eager step path

    def __init__(self, learning_rate=0.001, parameter_list=None,
                 parameters=None, regularization=None, weight_decay=None,
                 grad_clip: Optional[GradClipBase] = None,
                 name: Optional[str] = None):
        self._learning_rate = learning_rate
        self._parameter_list = (list(parameters) if parameters is not None
                                else (list(parameter_list)
                                      if parameter_list is not None else None))
        if regularization is None and weight_decay is not None and \
                not isinstance(weight_decay, float):
            regularization = weight_decay
        elif regularization is None and isinstance(weight_decay, float):
            regularization = L2Decay(weight_decay)
        self.regularization = regularization
        self._grad_clip = grad_clip
        self._name = name or type(self).__name__
        self._lr_var: Optional[Variable] = None
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self._eager_state: Dict[tuple, object] = {}
        self.helper = None

    # -- learning rate -----------------------------------------------------
    def _create_lr_var(self):
        if self._lr_var is not None:
            return self._lr_var
        lr = self._learning_rate
        if isinstance(lr, Variable):
            self._lr_var = lr
        else:
            from .optimizer_lr import LRScheduler
            if isinstance(lr, LRScheduler):
                self._lr_scheduler = lr
                lr = lr()
            self._lr_var = create_global_var(
                shape=[1], value=float(lr), dtype="float32",
                persistable=True,
                name=unique_name.generate("learning_rate"))
        return self._lr_var

    def get_lr_var(self):
        return self._lr_var

    def sync_lr(self, scope):
        """Push the scheduler's current lr into the scope's lr var (static
        mode). Call after scheduler.step()."""
        sched = getattr(self, "_lr_scheduler", None)
        if sched is not None and self._lr_var is not None:
            import jax.numpy as jnp
            scope.set_var(self._lr_var.name,
                          jnp.asarray([sched()], jnp.float32))

    def set_lr(self, value: float, scope=None):
        from .framework.scope import global_scope
        import jax.numpy as jnp
        self._learning_rate = float(value)
        if self._lr_var is not None:
            (scope or global_scope()).set_var(
                self._lr_var.name, jnp.asarray([float(value)], jnp.float32))

    # -- accumulators ------------------------------------------------------
    def _add_accumulator(self, name: str, param: Variable, init_value=0.0,
                         shape=None, dtype=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        shape = list(shape if shape is not None else param.shape)
        v = create_global_var(
            shape=shape, value=float(init_value), dtype=dtype or param.dtype,
            persistable=True, name=unique_name.generate(f"{param.name}_{name}"))
        self._accumulators.setdefault(name, {})[param.name] = v
        return v

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- program rewrites --------------------------------------------------
    def _append_regularization(self, params_grads, block):
        out = []
        for p, g in params_grads:
            reg = p.regularizer or self.regularization
            if reg is None:
                out.append((p, g))
                continue
            kind, coeff = (reg if isinstance(reg, tuple)
                           else (reg.kind, reg.coeff))
            if kind == "l2":
                scaled = block.create_var(
                    unique_name.generate(g.name + "@REG"), stop_gradient=True)
                block.append_op("scale", {"X": p}, {"Out": scaled},
                                {"scale": float(coeff),
                                 "op_role": "optimize"})
                merged = block.create_var(
                    unique_name.generate(g.name + "@REGSUM"),
                    stop_gradient=True)
                block.append_op("sum", {"X": [g.name, scaled.name]},
                                {"Out": merged}, {"op_role": "optimize"})
                out.append((p, merged))
            elif kind == "l1":
                sign = block.create_var(
                    unique_name.generate(g.name + "@SIGN"), stop_gradient=True)
                block.append_op("sign", {"X": p}, {"Out": sign},
                                {"op_role": "optimize"})
                scaled = block.create_var(
                    unique_name.generate(g.name + "@REG"), stop_gradient=True)
                block.append_op("scale", {"X": sign}, {"Out": scaled},
                                {"scale": float(coeff),
                                 "op_role": "optimize"})
                merged = block.create_var(
                    unique_name.generate(g.name + "@REGSUM"),
                    stop_gradient=True)
                block.append_op("sum", {"X": [g.name, scaled.name]},
                                {"Out": merged}, {"op_role": "optimize"})
                out.append((p, merged))
            else:
                raise ValueError(f"unknown regularizer kind {kind!r}")
        return out

    # -- per-optimizer op --------------------------------------------------
    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _create_accumulators(self, block, parameters):
        pass

    def _finish_update(self, block, params_grads):
        pass

    # -- public ------------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, checkpoints=None):
        plist = parameter_list or self._parameter_list
        return append_backward(loss, parameter_list=plist,
                               no_grad_set=no_grad_set,
                               checkpoints=checkpoints)

    def apply_gradients(self, params_grads, startup_program=None):
        # Operate on the program that owns the parameters — minimize() may
        # be called outside the program_guard the model was built under.
        # Accumulator/LR init ops must land in the startup program the user
        # will run: the one passed in, or the one the main program was built
        # against (recorded by program_guard).
        from .framework.program import program_guard
        program = params_grads[0][0].block.program if params_grads \
            else default_main_program()
        startup = startup_program or getattr(program, "_startup_ref", None)
        with program_guard(program, startup):
            block = program.global_block()
            if self._grad_clip is not None:
                params_grads = self._grad_clip._clip_static(params_grads,
                                                            block)
            params_grads = self._append_regularization(params_grads, block)
            self._create_lr_var()
            self._create_accumulators(block, [p for p, _ in params_grads])
            ops = []
            for p_g in params_grads:
                ops.append(self._append_optimize_op(block, p_g))
            self._finish_update(block, params_grads)
        return ops

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads, startup_program)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        opt_ops = self.apply_gradients(params_grads, startup_program)
        return opt_ops, params_grads

    # -- dygraph (2.0) eager path -----------------------------------------
    def _eager_attrs(self) -> dict:
        return {}

    def _current_lr(self) -> float:
        lr = self._learning_rate
        from .optimizer_lr import LRScheduler
        if isinstance(lr, LRScheduler):
            return float(lr())
        return float(lr)

    def get_lr(self) -> float:
        return self._current_lr()

    @property
    def _parameters_or_raise(self):
        if self._parameter_list is None:
            raise ValueError(
                "eager step() requires parameters= at construction "
                "(2.0 dygraph mode)")
        return self._parameter_list

    def step(self):
        """Apply one eager update to all dygraph parameters with grads."""
        import jax.numpy as jnp
        from .ops import registry as _reg
        op_type = self._eager_op
        if op_type is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no eager step path")
        spec = _EAGER_SPECS[op_type]
        ctx = _reg.LoweringContext(eager=True)
        if self._grad_clip is not None:
            self._grad_clip._clip_eager(self._parameters_or_raise)
        lr = self._current_lr()
        for p in self._parameters_or_raise:
            if p.grad is None or not getattr(p, "trainable", True):
                continue
            g = p.grad.value
            # per-param regularization (L2/L1 decay into the gradient)
            reg = getattr(p, "regularizer", None) or self.regularization
            if reg is not None and op_type != "adamw":
                kind, coeff = (reg if isinstance(reg, tuple)
                               else (reg.kind, reg.coeff))
                if kind == "l2":
                    g = g + coeff * p.value
                elif kind == "l1":
                    g = g + coeff * jnp.sign(p.value)
            lr_arr = jnp.asarray([lr * getattr(p, "lr_scale", 1.0)],
                                 jnp.float32)
            ins = {"Param": [p.value], "Grad": [g], "LearningRate": [lr_arr]}
            for slot, key, init, shape in spec["accums"]:
                ins[slot] = [self._accumulator(p, key, init, shape)]
            outs = _reg.execute(ctx, op_type, ins, self._eager_attrs())
            for oslot, target in spec["outs"].items():
                val = outs[oslot][0]
                if target == "param":
                    p.value = val
                else:
                    prev = self._eager_state.get((id(p), target))
                    if prev is not None and val.dtype != prev.dtype:
                        val = val.astype(prev.dtype)  # keep bf16 storage
                    self._eager_state[(id(p), target)] = val

    def _accumulator(self, p, key, init, shape):
        """The eager accumulator ``key`` of parameter ``p``, made at its
        initial value the first time it is asked for."""
        import jax.numpy as jnp
        skey = (id(p), key)
        if skey not in self._eager_state:
            dt = p.value.dtype
            moment_dtype = getattr(self, "_moment_dtype", None)
            if moment_dtype is not None and shape is None \
                    and key in ("m1", "m2", "moment", "mom"):
                dt = jnp.dtype(moment_dtype)
            self._eager_state[skey] = jnp.full(
                shape or p.value.shape, init, dt)
        return self._eager_state[skey]

    def init_state(self):
        """Make every trainable parameter's accumulators now, at their
        initial values, instead of in the first ``step()``. A step compiled
        by ``jit.to_static`` then has the same state before and after its
        first call, so it compiles once and not twice; where there is no
        mesh ``to_static`` calls this itself before its first trace."""
        if self._eager_op is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no eager step path")
        for p in self._parameters_or_raise:
            if getattr(p, "trainable", True):
                for _, key, init, shape in \
                        _EAGER_SPECS[self._eager_op]["accums"]:
                    self._accumulator(p, key, init, shape)

    def clear_grad(self):
        for p in self._parameters_or_raise:
            p.clear_grad()

    clear_gradients = clear_grad

    def state_dict(self) -> dict:
        """Accumulator state keyed by PARAMETER NAME (stable across
        processes when models are built in the same order)."""
        by_id = {id(p): p.name for p in (self._parameter_list or [])}
        out = {"_lr": self._current_lr()}
        for (pid, key), v in self._eager_state.items():
            pname = by_id.get(pid, str(pid))
            out[f"{pname}:{key}"] = v
        return out

    def set_state_dict(self, state: dict):
        import jax.numpy as jnp
        by_name = {p.name: p for p in (self._parameter_list or [])}
        for k, v in state.items():
            if k == "_lr":
                from .optimizer_lr import LRScheduler
                if not isinstance(self._learning_rate, LRScheduler):
                    self._learning_rate = float(v)
                continue
            pname, _, key = k.rpartition(":")
            p = by_name.get(pname)
            if p is not None:
                self._eager_state[(id(p), key)] = jnp.asarray(v)

    load_state_dict = set_state_dict

    def _lr_input(self, param) -> Variable:
        """Per-param lr (honors ParamAttr.learning_rate scale)."""
        lr = self._create_lr_var()
        scale = getattr(param, "lr_scale", 1.0)
        if scale == 1.0:
            return lr
        block = default_main_program().global_block()
        scaled = block.create_var(
            unique_name.generate(f"{param.name}_lr"), stop_gradient=True,
            persistable=False)
        block.append_op("scale", {"X": lr}, {"Out": scaled},
                        {"scale": float(scale), "op_role": "optimize"})
        return scaled


class SGDOptimizer(Optimizer):
    _eager_op = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "sgd", {"Param": p, "Grad": g,
                    "LearningRate": self._lr_input(p)},
            {"ParamOut": p}, {"op_role": "optimize"})


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum=0.9, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._use_nesterov = use_nesterov
        self._eager_op = "momentum"

    def _eager_attrs(self):
        return {"mu": self._momentum, "use_nesterov": self._use_nesterov}

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            {"Param": p, "Grad": g, "Velocity": v,
             "LearningRate": self._lr_input(p)},
            {"ParamOut": p, "VelocityOut": v},
            {"mu": self._momentum, "use_nesterov": self._use_nesterov,
             "op_role": "optimize"})


class LarsMomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay
        self._eager_op = "lars_momentum"

    def _eager_attrs(self):
        return {"mu": self._momentum, "lars_coeff": self._lars_coeff,
                "lars_weight_decay": self._lars_weight_decay}

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "lars_momentum",
            {"Param": p, "Grad": g, "Velocity": v,
             "LearningRate": self._lr_input(p)},
            {"ParamOut": p, "VelocityOut": v},
            {"mu": self._momentum, "lars_coeff": self._lars_coeff,
             "lars_weight_decay": self._lars_weight_decay,
             "op_role": "optimize"})


class AdagradOptimizer(Optimizer):
    _eager_op = "adagrad"

    def __init__(self, learning_rate, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon = epsilon

    def _eager_attrs(self):
        return {"epsilon": self._epsilon}

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "adagrad",
            {"Param": p, "Grad": g, "Moment": m,
             "LearningRate": self._lr_input(p)},
            {"ParamOut": p, "MomentOut": m},
            {"epsilon": self._epsilon, "op_role": "optimize"})


class AdamOptimizer(Optimizer):
    _op_type = "adam"
    _eager_op = "adam"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, moment_dtype=None, **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        # moment_dtype="bfloat16" stores m/v in bf16 — halves optimizer
        # state HBM (the factored/low-precision-moment trade; update math
        # still runs in the promoted dtype, storage rounds back)
        self._moment_dtype = moment_dtype

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow", p, init_value=1.0, shape=[1])
            self._add_accumulator("beta2_pow", p, init_value=1.0, shape=[1])

    def _extra_attrs(self):
        return {}

    def _eager_attrs(self):
        attrs = {"beta1": self._beta1, "beta2": self._beta2,
                 "epsilon": self._epsilon}
        attrs.update(self._extra_attrs())
        return attrs

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        attrs = {"beta1": self._beta1, "beta2": self._beta2,
                 "epsilon": self._epsilon, "op_role": "optimize"}
        attrs.update(self._extra_attrs())
        return block.append_op(
            self._op_type,
            {"Param": p, "Grad": g,
             "Moment1": self._get_accumulator("moment1", p),
             "Moment2": self._get_accumulator("moment2", p),
             "Beta1Pow": self._get_accumulator("beta1_pow", p),
             "Beta2Pow": self._get_accumulator("beta2_pow", p),
             "LearningRate": self._lr_input(p)},
            {"ParamOut": p,
             "Moment1Out": self._get_accumulator("moment1", p),
             "Moment2Out": self._get_accumulator("moment2", p),
             "Beta1PowOut": self._get_accumulator("beta1_pow", p),
             "Beta2PowOut": self._get_accumulator("beta2_pow", p)},
            attrs)


class AdamWOptimizer(AdamOptimizer):
    _op_type = "adamw"
    _eager_op = "adamw"

    def __init__(self, learning_rate=0.001, weight_decay=0.01, **kw):
        super().__init__(learning_rate, **kw)
        self._coeff = weight_decay

    def _extra_attrs(self):
        return {"coeff": self._coeff, "with_decay": True}


class LambOptimizer(AdamOptimizer):
    _op_type = "lamb"
    _eager_op = "lamb"

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, **kw):
        super().__init__(learning_rate, beta1=beta1, beta2=beta2,
                         epsilon=epsilon, **kw)
        self._weight_decay = lamb_weight_decay

    def _extra_attrs(self):
        return {"weight_decay": self._weight_decay}


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered
        if not centered:
            self._eager_op = "rmsprop"

    def _eager_attrs(self):
        return {"decay": self._rho, "epsilon": self._epsilon,
                "momentum": self._momentum, "centered": self._centered}

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("moment", p)
            if self._centered:
                self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        ins = {"Param": p, "Grad": g,
               "MeanSquare": self._get_accumulator("mean_square", p),
               "Moment": self._get_accumulator("moment", p),
               "LearningRate": self._lr_input(p)}
        outs = {"ParamOut": p,
                "MeanSquareOut": self._get_accumulator("mean_square", p),
                "MomentOut": self._get_accumulator("moment", p)}
        if self._centered:
            ins["MeanGrad"] = self._get_accumulator("mean_grad", p)
            outs["MeanGradOut"] = self._get_accumulator("mean_grad", p)
        return block.append_op(
            "rmsprop", ins, outs,
            {"decay": self._rho, "epsilon": self._epsilon,
             "momentum": self._momentum, "centered": self._centered,
             "op_role": "optimize"})


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power
        self._eager_op = "ftrl"

    def _eager_attrs(self):
        return {"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power}

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "ftrl",
            {"Param": p, "Grad": g,
             "SquaredAccumulator": self._get_accumulator("squared", p),
             "LinearAccumulator": self._get_accumulator("linear", p),
             "LearningRate": self._lr_input(p)},
            {"ParamOut": p,
             "SquaredAccumOut": self._get_accumulator("squared", p),
             "LinearAccumOut": self._get_accumulator("linear", p)},
            {"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power,
             "op_role": "optimize"})


class RecomputeOptimizer(Optimizer):
    """Activation-recompute wrapper (fluid/optimizer.py:4518 parity):

        opt = RecomputeOptimizer(SGDOptimizer(0.1))
        opt._set_checkpoints([h1, h2])
        opt.minimize(loss)

    backward() runs the checkpointed rewrite (backward.py
    ``checkpoints=``): forward segments are re-emitted behind
    optimization_barriers inside the backward, so only checkpoint
    activations survive the forward pass — FLOPs traded for HBM, the
    canonical TPU memory lever.
    """

    def __init__(self, inner_optimizer: Optimizer):
        self._inner = inner_optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = list(checkpoints)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, checkpoints=None):
        return self._inner.backward(
            loss, startup_program, parameter_list, no_grad_set,
            checkpoints=checkpoints or self._checkpoints)

    def apply_gradients(self, params_grads, startup_program=None):
        return self._inner.apply_gradients(params_grads, startup_program)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        if not self._checkpoints:
            raise ValueError(
                "RecomputeOptimizer: call _set_checkpoints() first")
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        opt_ops = self.apply_gradients(params_grads, startup_program)
        return opt_ops, params_grads


class PipelineOptimizer:
    """Pipeline-parallel wrapper (fluid/optimizer.py:3666 parity):

        with device_guard("tpu:0"): ...first half...
        with device_guard("tpu:1"): ...second half + loss...
        opt = PipelineOptimizer(SGDOptimizer(0.1), num_microbatches=4)
        opt.minimize(loss)
        runner = opt.runner()           # GPipe schedule
        runner.run(exe, scope, microbatch_feeds, fetch_list=[loss.name])

    minimize() builds the ordinary joint program (backward + optimizer
    ops inherit their forward op's op_device), then splits it into
    per-stage forward/backward/optimize phase programs with microbatch
    gradient accumulation (distributed/fleet/pipeline.py).
    """

    def __init__(self, optimizer, num_microbatches: int = 1):
        self._inner = optimizer
        self._num_microbatches = int(num_microbatches)
        self._stages = None

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        opt_ops, params_grads = self._inner.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        from .distributed.fleet.pipeline import split_pipeline_program
        program = loss.block.program
        self._stages = split_pipeline_program(program,
                                              self._num_microbatches)
        program._pipeline_stages = self._stages
        program._pipeline_num_microbatches = self._num_microbatches
        return opt_ops, params_grads

    def runner(self, devices=None, schedule: str = "gpipe"):
        """Build the microbatch runner. ``devices`` (list of jax.Device)
        places each stage's compiled programs on its own chip for real
        pipeline parallelism; ``schedule`` is "gpipe" or "1f1b"."""
        from .distributed.fleet.pipeline import PipelineRunner
        if self._stages is None:
            raise ValueError("call minimize() before runner()")
        return PipelineRunner(self._stages, self._num_microbatches,
                              devices=devices, schedule=schedule)


# fluid-style aliases
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adagrad = AdagradOptimizer
Lamb = LambOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
LarsMomentum = LarsMomentumOptimizer


class L1Decay:
    kind = "l1"

    def __init__(self, regularization_coeff=0.0):
        self.coeff = regularization_coeff


class L2Decay:
    kind = "l2"

    def __init__(self, regularization_coeff=0.0):
        self.coeff = regularization_coeff


# ---------------------------------------------------------------------------
# Weight-averaging / slow-weight wrappers (optimizer.py:3107 ModelAverage,
# :3416 ExponentialMovingAverage, :4828 LookaheadOptimizer)
# ---------------------------------------------------------------------------


def _trainable_params(program):
    return [v for v in program.global_block().vars.values()
            if getattr(v, "is_parameter", False)
            and not getattr(v, "stop_gradient", False)]


def _append_shadow_init(startup, param_name, shadow_name):
    """startup: shadow <- param (runs after the param's own init op)."""
    sblk = startup.global_block()
    sblk.create_var(shadow_name, persistable=True, stop_gradient=True)
    sblk.append_op("assign", {"X": [param_name]}, {"Out": [shadow_name]},
                   {})


def _int_counter(program, startup, name: str):
    """Persistable int64 step counter initialized to 0 (int64 so the
    count never saturates the way a float32 would at 2^24)."""
    blk = program.global_block()
    cname = unique_name.generate(name)
    blk.create_var(cname, persistable=True, stop_gradient=True)
    sblk = startup.global_block()
    sblk.create_var(cname, persistable=True, stop_gradient=True)
    sblk.append_op("fill_constant", {}, {"Out": [cname]},
                   {"shape": [1], "value": 0, "dtype": "int64"})
    return cname


class _ScopeSwapMixin:
    """Shared apply/restore scaffolding: swap params for derived values
    in a scope, restore on exit (the EMA/ModelAverage protocol)."""

    _pairs: list  # (param name, aux var name)
    _backup: dict

    def _swap_value(self, scope, param_name, aux_name):
        raise NotImplementedError

    def apply(self, scope=None, need_restore: bool = True):
        import contextlib

        from .framework.scope import global_scope
        scope = scope or global_scope()

        @contextlib.contextmanager
        def _ctx():
            self._backup = {p: scope.find_var(p) for p, _ in self._pairs}
            for p, a in self._pairs:
                scope.set_var(p, self._swap_value(scope, p, a))
            try:
                yield self
            finally:
                if need_restore:
                    self.restore(scope)
        return _ctx()

    def restore(self, scope=None):
        from .framework.scope import global_scope
        scope = scope or global_scope()
        for p, v in self._backup.items():
            scope.set_var(p, v)
        self._backup = {}


class ExponentialMovingAverage(_ScopeSwapMixin):
    """EMA shadow weights, updated in-graph
    (optimizer.py:3416 ExponentialMovingAverage).

    >>> ema = ExponentialMovingAverage(0.999)
    >>> opt.minimize(loss); ema.update()        # build once
    >>> with ema.apply(scope):                  # eval with EMA weights
    ...     exe.run(test_program, ...)
    """

    def __init__(self, decay: float = 0.999, name: Optional[str] = None):
        self._decay = float(decay)
        self._name = name or "ema"
        self._pairs = []          # (param name, ema var name)
        self._backup = {}

    def update(self):
        """Append ema = decay*ema + (1-decay)*param for every trainable
        param of the current main program; shadow init rides the
        startup program (run startup after calling this)."""
        program = default_main_program()
        startup = default_startup_program()
        blk = program.global_block()
        for p in _trainable_params(program):
            ema_name = unique_name.generate(f"{p.name}.{self._name}")
            blk.create_var(ema_name, persistable=True,
                           stop_gradient=True)
            _append_shadow_init(startup, p.name, ema_name)
            scaled_e = unique_name.generate(f"{ema_name}.sc")
            blk.create_var(scaled_e, stop_gradient=True)
            blk.append_op("scale", {"X": [ema_name]}, {"Out": [scaled_e]},
                          {"scale": self._decay, "op_role": "optimize"})
            scaled_p = unique_name.generate(f"{p.name}.sc")
            blk.create_var(scaled_p, stop_gradient=True)
            blk.append_op("scale", {"X": [p.name]}, {"Out": [scaled_p]},
                          {"scale": 1.0 - self._decay,
                           "op_role": "optimize"})
            blk.append_op("sum", {"X": [scaled_e, scaled_p]},
                          {"Out": [ema_name]}, {"op_role": "optimize"})
            self._pairs.append((p.name, ema_name))
        return self

    def _swap_value(self, scope, param_name, aux_name):
        return scope.find_var(aux_name)


class ModelAverage(_ScopeSwapMixin):
    """Windowed parameter average, accumulated in-graph
    (optimizer.py:3107 ModelAverage). The reference rotates three
    partial sums; here the window restarts whenever the accumulated
    count reaches ``max_average_window`` — same estimator family
    (average over the most recent training tail), branch-free IR.
    ``average_window_rate``/``min_average_window`` are accepted for
    signature parity; the restart policy is driven by
    ``max_average_window`` alone."""

    def __init__(self, average_window_rate: float = 0.15,
                 min_average_window: int = 10000,
                 max_average_window: int = 10000):
        self._max_window = int(max_average_window)
        self._pairs = []          # (param, sum var)
        self._num_name = None
        self._backup = {}

    def update(self):
        program = default_main_program()
        startup = default_startup_program()
        blk = program.global_block()

        def ap(type_, ins, outs, attrs=None):
            blk.append_op(type_, ins, outs,
                          dict(attrs or {}, op_role="optimize"))

        def tmp(base, **kw):
            name = unique_name.generate(base)
            blk.create_var(name, stop_gradient=True, **kw)
            return name

        self._num_name = _int_counter(program, startup,
                                      "model_average.num")
        ap("increment", {"X": [self._num_name]},
           {"Out": [self._num_name]}, {"step": 1})
        # reset mask: 1.0 when the window is full (num == max_window)
        maxc = tmp("ma.max")
        ap("fill_constant_like", {"X": [self._num_name]}, {"Out": [maxc]},
           {"value": float(self._max_window)})
        eq = tmp("ma.eq")
        ap("equal", {"X": [self._num_name], "Y": [maxc]}, {"Out": [eq]},
           {})
        maskf = tmp("ma.maskf")
        ap("cast", {"X": [eq]}, {"Out": [maskf]},
           {"in_dtype": "bool", "out_dtype": "float32"})
        inv = tmp("ma.inv")
        ap("scale", {"X": [maskf]}, {"Out": [inv]},
           {"scale": -1.0, "bias": 1.0})
        # num <- num*(1-mask) + mask  (restart counts the current step)
        maski = tmp("ma.maski")
        ap("cast", {"X": [eq]}, {"Out": [maski]},
           {"in_dtype": "bool", "out_dtype": "int64"})
        invi = tmp("ma.invi")
        ap("scale", {"X": [maski]}, {"Out": [invi]},
           {"scale": -1, "bias": 1})
        kept = tmp("ma.kept")
        ap("elementwise_mul", {"X": [self._num_name], "Y": [invi]},
           {"Out": [kept]}, {"axis": -1})
        ap("sum", {"X": [kept, maski]}, {"Out": [self._num_name]}, {})
        for p in _trainable_params(program):
            sum_name = unique_name.generate(f"{p.name}.avg_sum")
            blk.create_var(sum_name, persistable=True,
                           stop_gradient=True)
            sblk = startup.global_block()
            sblk.create_var(sum_name, persistable=True,
                            stop_gradient=True)
            sblk.append_op("scale", {"X": [p.name]}, {"Out": [sum_name]},
                           {"scale": 0.0})
            acc = tmp(f"{p.name}.avg_acc")
            ap("sum", {"X": [sum_name, p.name]}, {"Out": [acc]}, {})
            # sum <- acc*(1-mask) + p*mask  (window restart)
            keep = tmp(f"{p.name}.avg_keep")
            ap("elementwise_mul", {"X": [acc], "Y": [inv]},
               {"Out": [keep]}, {"axis": -1})
            fresh = tmp(f"{p.name}.avg_fresh")
            ap("elementwise_mul", {"X": [p.name], "Y": [maskf]},
               {"Out": [fresh]}, {"axis": -1})
            ap("sum", {"X": [keep, fresh]}, {"Out": [sum_name]}, {})
            self._pairs.append((p.name, sum_name))
        return self

    def _swap_value(self, scope, param_name, aux_name):
        import numpy as _np
        n = float(_np.asarray(scope.find_var(self._num_name))
                  .reshape(-1)[0])
        return _np.asarray(scope.find_var(aux_name)) / max(n, 1.0)


class LookaheadOptimizer:
    """Lookahead slow/fast weights (optimizer.py:4828): every k steps
    slow += alpha * (fast - slow); fast <- slow. Branch-free IR (the
    k-step condition rides the shared every-k gate, XLA-friendly — no
    cond). Slow weights exist only for the params the inner optimizer
    actually updates (parameter_list respected)."""

    def __init__(self, inner_optimizer, alpha: float = 0.5, k: int = 5):
        self.inner_optimizer = inner_optimizer
        self.alpha = float(alpha)
        self.k = int(k)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        result = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        _, params_grads = result
        program = loss.block.program
        startup = startup_program or getattr(program, "_startup_ref",
                                             None) or \
            default_startup_program()
        from .distributed.fleet.fleet_base import _emit_every_k_gate
        from .framework.program import program_guard
        with program_guard(program, startup):
            blk = program.global_block()
            step = _int_counter(program, startup, "lookahead.step")
            gate_b = _emit_every_k_gate(blk, step, self.k, "optimize")
            mask = unique_name.generate("lookahead.mask")
            blk.create_var(mask, stop_gradient=True)
            blk.append_op("cast", {"X": [gate_b]}, {"Out": [mask]},
                          {"in_dtype": "bool", "out_dtype": "float32",
                           "op_role": "optimize"})
            for p, _g in params_grads:
                slow = unique_name.generate(f"{p.name}.slow")
                blk.create_var(slow, persistable=True,
                               stop_gradient=True)
                _append_shadow_init(startup, p.name, slow)

                def tmp(base):
                    name = unique_name.generate(base)
                    blk.create_var(name, stop_gradient=True)
                    return name
                diff = tmp(f"{p.name}.la_diff")
                blk.append_op("elementwise_sub",
                              {"X": [p.name], "Y": [slow]},
                              {"Out": [diff]}, {"op_role": "optimize"})
                stepv = tmp(f"{p.name}.la_step")
                blk.append_op("scale", {"X": [diff]}, {"Out": [stepv]},
                              {"scale": self.alpha,
                               "op_role": "optimize"})
                masked = tmp(f"{p.name}.la_masked")
                blk.append_op("elementwise_mul",
                              {"X": [stepv], "Y": [mask]},
                              {"Out": [masked]},
                              {"axis": -1, "op_role": "optimize"})
                blk.append_op("sum", {"X": [slow, masked]},
                              {"Out": [slow]}, {"op_role": "optimize"})
                # fast <- mask*slow + (1-mask)*fast
                ps = tmp(f"{p.name}.la_ps")
                blk.append_op("elementwise_sub",
                              {"X": [slow], "Y": [p.name]},
                              {"Out": [ps]}, {"op_role": "optimize"})
                psm = tmp(f"{p.name}.la_psm")
                blk.append_op("elementwise_mul",
                              {"X": [ps], "Y": [mask]},
                              {"Out": [psm]},
                              {"axis": -1, "op_role": "optimize"})
                blk.append_op("sum", {"X": [p.name, psm]},
                              {"Out": [p.name]},
                              {"op_role": "optimize"})
        return result


EMA = ExponentialMovingAverage
Lookahead = LookaheadOptimizer
