"""GSPMD sharding rules: parameter-path -> PartitionSpec.

The TPU-native replacement for the reference's per-grad NCCL plumbing
(transpiler/collective.py GradAllReduce) and the north-star "sharding"
strategy absent from the reference (distributed_strategy.proto:94-130):
instead of rewriting programs to insert collectives, we annotate the
*state pytree* with `jax.sharding.NamedSharding`s and let XLA GSPMD insert
all_gather/reduce_scatter/psum where the dataflow demands. Rules are
regex-over-dotted-parameter-path (the `named_parameters()` naming), the
way T5X/Flax partition rules work — that is the idiomatic JAX surface.

Used by `paddle_tpu.jit.to_static(mesh=..., param_rules=...)` to compile a
whole dygraph train step SPMD across a mesh.
"""

from __future__ import annotations

import dataclasses
import re
from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec


class ShardingRules:
    """Ordered (regex, PartitionSpec) table; first match wins.

    A rule's spec is validated against the parameter shape: axes whose
    mesh-dim size does not divide the parameter dim fall back to
    replicated on that axis (so one rule set serves many model sizes).
    """

    def __init__(self, rules: Sequence[Tuple[str, PartitionSpec]],
                 default: PartitionSpec = P()):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default = default

    def spec_for(self, name: str, shape: Sequence[int],
                 mesh: Mesh) -> PartitionSpec:
        for pat, spec in self._rules:
            if pat.search(name):
                return _fit_spec(spec, shape, mesh, name=name)
        return _fit_spec(self.default, shape, mesh, name=name)

    def merge(self, other: "ShardingRules",
              default: PartitionSpec = None) -> "ShardingRules":
        """Compose rule tables: self's rules take precedence, then
        other's; default comes from `default` or other. The ZeRO+TP
        composition (TP rules first, fully-sharded fallback) is the
        canonical use."""
        out = ShardingRules([], default=default if default is not None
                            else other.default)
        out._rules = list(self._rules) + list(other._rules)
        return out


def _fit_spec(spec: PartitionSpec, shape: Sequence[int],
              mesh: Mesh, name: Optional[str] = None) -> PartitionSpec:
    if spec is None:
        return P()
    dims = list(spec)
    if len(dims) > len(shape):
        return P()
    out = []
    for i, ax in enumerate(dims):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if shape[i] % size == 0:
            out.append(ax)
        else:
            # the downgrade keeps one rule set serving many model sizes,
            # but a silently-replicated tensor is exactly how a big run
            # quietly eats HBM — count it and put it on the run log
            # (tools/lint_sharding.py reports the same thing statically)
            _note_replicated_fallback(name, i, ax, size, shape[i])
            out.append(None)
    return P(*out)


def _note_replicated_fallback(name: Optional[str], dim: int, ax,
                              axis_size: int, dim_size: int):
    from .. import monitor
    monitor.stat_add("STAT_sharding_replicated_fallback")
    try:
        from ..observability import runlog
        runlog.log_event("sharding_fallback",
                         param=name or "<unnamed>", dim=dim,
                         axis=str(ax), axis_size=axis_size,
                         dim_size=dim_size)
    except Exception:
        pass  # observability must never break a sharding decision


# Megatron-style tensor parallelism for the GPT family over an "mp" axis:
# column-parallel qkv/fc1 (shard the output features), row-parallel
# out_proj/fc2 (shard the input features -> GSPMD inserts the psum),
# vocab-parallel embeddings.
GPT_TENSOR_PARALLEL_RULES = ShardingRules([
    (r"qkv_proj\.weight$", P(None, "mp")),
    (r"qkv_proj\.bias$", P("mp")),
    (r"fc1\.weight$", P(None, "mp")),
    (r"fc1\.bias$", P("mp")),
    (r"out_proj\.weight$", P("mp", None)),
    (r"fc2\.weight$", P("mp", None)),
    (r"wte\.weight$", P("mp", None)),
])

# Encoder families (ERNIE/BERT, nn.MultiHeadAttention /
# TransformerEncoderLayer names). Kept as a separate table: fusing it
# into the GPT rules left 4 dead rules (encoder names absent from GPT)
# and 2 shadowed ones (unanchored `v_proj.weight$` also matches
# `qkv_proj.weight` but always lost to the GPT rule above).
ENCODER_TENSOR_PARALLEL_RULES = ShardingRules([
    (r"q_proj\.weight$|k_proj\.weight$|v_proj\.weight$", P(None, "mp")),
    (r"q_proj\.bias$|k_proj\.bias$|v_proj\.bias$", P("mp")),
    (r"linear1\.weight$", P(None, "mp")),
    (r"linear1\.bias$", P("mp")),
    (r"linear2\.weight$", P("mp", None)),
    # vocab-parallel word embedding
    (r"word_embeddings\.weight$", P("mp", None)),
])

ERNIE_TENSOR_PARALLEL_RULES = ENCODER_TENSOR_PARALLEL_RULES

# Serving-engine tensor parallelism: the GPT table re-expressed on the
# ("data", "model") serving mesh axis names — attention heads / MLP
# hidden column-parallel on "model", out_proj/fc2 row-parallel (GSPMD
# inserts the psum), vocab-parallel embedding. Used by ServingEngine to
# place params and the paged KV pool when FLAGS_serving_mesh is set.
SERVING_TP_RULES = ShardingRules([
    (r"qkv_proj\.weight$", P(None, "model")),
    (r"qkv_proj\.bias$", P("model")),
    (r"fc1\.weight$", P(None, "model")),
    (r"fc1\.bias$", P("model")),
    (r"out_proj\.weight$", P("model", None)),
    (r"fc2\.weight$", P("model", None)),
    (r"wte\.weight$", P("model", None)),
])

# ZeRO-style optimizer/param sharding over the data axis (sharding
# stage-3 analog): shard the largest dim of every tensor over "dp".
FULLY_SHARDED_RULES = ShardingRules([
    (r"\.weight$", P("dp")),
], default=P())


def parse_serving_mesh(spec: str) -> Optional[Tuple[int, int]]:
    """``FLAGS_serving_mesh`` syntax: ``'DATAxMODEL'`` -> ``(data,
    model)``; empty/whitespace -> ``None`` (single-device engine)."""
    spec = (spec or "").strip()
    if not spec:
        return None
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(
            f"serving_mesh must look like '1x2' (data x model), "
            f"got {spec!r}")
    data, model = (int(p) for p in parts)
    if data < 1 or model < 1:
        raise ValueError(f"serving_mesh axes must be >= 1, got {spec!r}")
    return data, model


def serving_mesh(data: int = 1, model: int = 1) -> Mesh:
    """The ``("data", "model")`` serving mesh over the first
    ``data * model`` local devices (SNIPPETS [2] layout: replicas on
    ``data``, tensor parallelism on ``model``)."""
    import jax
    import numpy as np
    n = int(data) * int(model)
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"serving mesh {data}x{model} needs {n} devices, "
            f"only {len(devs)} available")
    return Mesh(np.asarray(devs[:n]).reshape(int(data), int(model)),
                ("data", "model"))


def mesh_cache_key(mesh: Optional[Mesh]):
    """Hashable compile-cache key component for a mesh: ``None`` for the
    single-device path, else (axis names, mesh shape, device ids) — so a
    *recreated* Mesh over the same devices reuses the cache entry while
    a different geometry gets its own compile."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def kv_pool_pspec(shape: Sequence[int]) -> PartitionSpec:
    """PartitionSpec for one paged-KV pool array: block pools
    ``(num_blocks, heads, block, head_dim)`` and int8 scale planes
    ``(num_blocks, heads)`` both shard the heads axis on ``"model"``
    (block tables index only the leading, unsharded blocks dim, so host
    remapping never moves bytes across devices)."""
    if len(shape) == 4:
        return P(None, "model", None, None)
    return P(None, "model")


def kv_pool_shardings(mesh: Mesh, layers) -> List[tuple]:
    """NamedSharding per array of each pool layer tuple (2-tuple f32/bf16
    pools or 4-tuple int8 pools + scales), divisibility-fitted so a
    heads count the mesh can't divide falls back to replicated instead
    of failing placement."""
    out = []
    for layer in layers:
        out.append(tuple(
            NamedSharding(mesh, _fit_spec(kv_pool_pspec(a.shape), a.shape,
                                          mesh, name="kv_pool"))
            for a in layer))
    return out


def state_shardings(spec, mesh: Mesh, rules: ShardingRules):
    """Build the sharding pytree matching jit._StateSpec.snapshot().

    Parameters (and their grads) shard per the rules; optimizer
    accumulators inherit their parameter's spec when shapes match
    (moments), else replicate (beta_pow scalars); buffers replicate.
    """
    p_specs = param_partition_specs(spec, mesh, rules)
    p_sh = [NamedSharding(mesh, s) for s in p_specs]
    repl = NamedSharding(mesh, P())
    # "grads" is filled in by the caller (presence depends on whether the
    # step has run before); grads shard like their params.
    return {
        "params": p_sh,
        "buffers": [repl for _ in spec.buffers],
        "opt": map_opt_state(spec, spec.snapshot()["opt"],
                             lambda i, v: p_sh[i], lambda v: repl),
    }


def map_opt_state(spec, opt_states, moment, scalar):
    """Map the ``"opt"`` part of a ``jit._StateSpec.snapshot()`` (one
    ordered dict per optimizer, keyed ``(param index, slot)``) to a
    pytree of the same structure: an entry with its parameter's shape is
    a moment of parameter ``i`` and becomes ``moment(i, v)``; anything
    else (the ``(1,)`` beta_pow scalars) becomes ``scalar(v)``."""
    shapes = [tuple(p.value.shape) for p in spec.params]

    def one(key, v):
        i = key[0] if isinstance(key, tuple) else None
        if isinstance(i, int) and i < len(shapes) \
                and tuple(v.shape) == shapes[i]:
            return moment(i, v)
        return scalar(v)

    return [OrderedDict((k, one(k, v)) for k, v in od.items())
            for od in opt_states]


def _param_names_by_id(layers) -> Dict[int, str]:
    """Dotted ``named_parameters()`` path per parameter identity — the
    name the rule regexes match against (first registration wins, the
    way `named_parameters` deduplicates tied weights)."""
    names: Dict[int, str] = {}
    for layer in layers:
        for name, p in layer.named_parameters():
            names.setdefault(id(p), name)
    return names


def param_partition_specs(spec, mesh: Mesh,
                          rules: ShardingRules) -> List[PartitionSpec]:
    """PartitionSpec per spec.params entry (rule lookup by dotted name)."""
    names = _param_names_by_id(spec.layers)
    return [rules.spec_for(names.get(id(p), p.name), p.value.shape, mesh)
            for p in spec.params]


def constrain_snapshot(spec, snapshot, mesh: Mesh, rules: ShardingRules):
    """Pin a post-step state snapshot's layouts INSIDE the traced
    computation via with_sharding_constraint: params/grads per the rules,
    optimizer accumulators like their parameter (moments) or replicated
    (scalars), buffers replicated.

    This — rather than jit's out_shardings — is how the fed-back state
    stays layout-stable across compiles: optimizer accumulators are
    created lazily during the first step, so the output pytree structure
    isn't known before tracing.
    """
    import jax

    p_specs = param_partition_specs(spec, mesh, rules)

    def c(v, s):
        if v is None:
            return None
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, s))

    out = dict(snapshot)
    out["params"] = [c(v, s) for v, s in zip(snapshot["params"], p_specs)]
    if "grads" in snapshot:
        out["grads"] = [c(v, s)
                        for v, s in zip(snapshot["grads"], p_specs)]
    out["buffers"] = [c(v, P()) for v in snapshot["buffers"]]
    out["opt"] = map_opt_state(spec, snapshot["opt"],
                               lambda i, v: c(v, p_specs[i]),
                               lambda v: c(v, P()))
    return out


def data_parallel_shardings(mesh: Mesh, n_args: int,
                            axis: str = "dp") -> tuple:
    """Shard the leading (batch) dim of every step argument over `axis`."""
    sh = NamedSharding(mesh, P(axis))
    return tuple(sh for _ in range(n_args))


# ---------------------------------------------------------------------------
# ZeRO optimizer-state partitioning (distributed/zero.py front end)
# ---------------------------------------------------------------------------


def zero_partition_spec(shape: Sequence[int], mesh, axis: str = "dp",
                        base: PartitionSpec = P(),
                        name: Optional[str] = None) -> PartitionSpec:
    """ZeRO layout for one optimizer accumulator (or stage-2 gradient):
    keep the tensor's base (tensor-parallel) spec and additionally shard
    the first dimension the data ``axis`` size divides that the base
    spec leaves unsharded — ZeRO composed with TP, not instead of it.

    No divisible free dim -> the base spec unchanged, with the same
    replicated-fallback accounting ``_fit_spec`` uses: a
    silently-unsharded moment is exactly how a ZeRO run quietly loses
    its memory win.
    """
    mesh = _as_mesh(mesh)
    size = mesh.shape[axis]
    dims = list(base or ())
    dims = dims + [None] * (len(shape) - len(dims))
    if size > 1 and len(shape) > 0:
        for i, d in enumerate(shape):
            if dims[i] is None and d >= size and d % size == 0:
                dims[i] = axis
                return P(*dims)
        _note_replicated_fallback(name, 0, axis, size,
                                  shape[0] if len(shape) else 0)
    return P(*dims) if any(d is not None for d in dims) else P()


def zero_grad_specs(spec, mesh: Mesh, rules: ShardingRules, *,
                    axis: str = "dp") -> List[PartitionSpec]:
    """Stage-2 gradient PartitionSpec per ``spec.params`` entry: the
    param's rule spec with the data axis added (``zero_partition_spec``)
    — grads enter and leave the compiled step reduce-scattered onto the
    same shards the optimizer moments live on."""
    p_specs = param_partition_specs(spec, mesh, rules)
    names = _param_names_by_id(spec.layers)
    return [zero_partition_spec(tuple(p.value.shape), mesh, axis=axis,
                                base=ps, name=names.get(id(p), p.name))
            for p, ps in zip(spec.params, p_specs)]


def opt_state_shardings(spec, mesh: Mesh, rules: ShardingRules, *,
                        axis: str = "dp", stage: int = 1) -> List[Dict]:
    """The ``"opt"`` entries of :func:`state_shardings` under ZeRO-
    ``stage``: moment accumulators (shape == their param's) shard over
    the data ``axis`` on top of their tensor-parallel spec, scalar
    accumulators (beta_pow ``(1,)``) replicate. ``stage <= 0`` returns
    the plain param-inherited layouts."""
    if stage <= 0:
        return state_shardings(spec, mesh, rules)["opt"]
    zsh = [NamedSharding(mesh, s)
           for s in zero_grad_specs(spec, mesh, rules, axis=axis)]
    repl = NamedSharding(mesh, P())
    return map_opt_state(spec, spec.snapshot()["opt"],
                         lambda i, v: zsh[i], lambda v: repl)


def estimate_zero_opt_bytes(named_params, mesh, rules: ShardingRules, *,
                            axis: str = "dp", stage: int = 1,
                            dtype_bytes: int = 4,
                            accums_per_param: int = 2,
                            scalar_accums: int = 2) -> Dict[str, int]:
    """Static optimizer-state byte estimate under ZeRO — the
    ``lint_sharding`` companion to ``distributed.zero.byte_report``,
    needing only names+shapes (no devices). Defaults model the adam
    family's eager state: two moment tensors per param plus two ``(1,)``
    scalars. Returns ``{"opt_bytes", "opt_bytes_per_device"}``."""
    mesh = _as_mesh(mesh)
    total = per_device = 0
    for name, shape in _normalize_named_params(named_params):
        n = dtype_bytes
        for d in shape:
            n *= int(d)
        base = rules.spec_for(name, shape, mesh)
        zspec = base if stage <= 0 else zero_partition_spec(
            shape, mesh, axis=axis, base=base, name=name)
        shards = 1
        for ax in zspec:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is not None:
                    shards *= mesh.shape[a]
        moment = accums_per_param * n
        total += moment
        per_device += moment // shards
        scalars = scalar_accums * dtype_bytes
        total += scalars
        per_device += scalars
    return {"opt_bytes": total, "opt_bytes_per_device": per_device}


# ---------------------------------------------------------------------------
# static rule linting (tools/lint_sharding.py front end)
# ---------------------------------------------------------------------------


class _MeshShapeView:
    """Shape-only mesh stand-in: rule fitting reads nothing but
    ``mesh.shape[axis]``, so the linter can check a 2×2 ``dp``/``mp``
    layout on a machine with one device (or none)."""

    def __init__(self, shape: Dict[str, int]):
        self.shape = dict(shape)

    def __repr__(self):
        return f"_MeshShapeView({self.shape})"


def _as_mesh(mesh) -> Any:
    return _MeshShapeView(mesh) if isinstance(mesh, dict) else mesh


@dataclasses.dataclass
class RuleReport:
    """Match accounting for one rule (or the default, pattern=None)."""

    pattern: Optional[str]
    spec: PartitionSpec
    matches: int = 0          # params whose name the regex matches at all
    wins: int = 0             # params where this rule decided the spec


@dataclasses.dataclass
class ShardingLintResult:
    diagnostics: List[Any]            # framework.analysis.Diagnostic
    rules: List[RuleReport]
    params: List[Tuple[str, Tuple[int, ...], PartitionSpec]]
    total_bytes: int
    per_device_bytes: int
    replicated_bytes: int

    @property
    def errors(self):
        return [d for d in self.diagnostics if d.severity == "error"]

    @property
    def warnings(self):
        return [d for d in self.diagnostics if d.severity == "warning"]

    def ok(self) -> bool:
        return not self.errors


def _normalize_named_params(named_params) -> List[Tuple[str, Tuple[int, ...]]]:
    if hasattr(named_params, "named_parameters"):
        named_params = list(named_params.named_parameters())
    out = []
    for name, p in named_params:
        if isinstance(p, (tuple, list)):
            shape = tuple(int(d) for d in p)
        elif hasattr(p, "value") and hasattr(p.value, "shape"):
            shape = tuple(int(d) for d in p.value.shape)
        else:
            shape = tuple(int(d) for d in p.shape)
        out.append((name, shape))
    return out


def lint_sharding_rules(rules: ShardingRules, named_params, mesh, *,
                        dtype_bytes: int = 4,
                        replicated_warn_mb: float = 64.0
                        ) -> ShardingLintResult:
    """Statically check a rule table against a model's parameters and a
    mesh — the pre-flight for ``to_static(mesh=..., param_rules=...)``.

    ``named_params``: a Layer (its ``named_parameters()`` is used) or an
    iterable of ``(dotted_name, shape)`` pairs. ``mesh``: a real
    ``jax.sharding.Mesh`` or a plain ``{axis: size}`` dict (no devices
    needed). Findings, as verifier ``Diagnostic`` records:

    - ``sharding.unknown-axis`` (ERROR): a spec names a mesh axis that
      does not exist — at run time this is a ``KeyError`` deep inside
      spec fitting;
    - ``sharding.dead-rule`` (WARNING): regex matches no parameter;
    - ``sharding.shadowed-rule`` (WARNING): regex matches parameters
      but an earlier rule always wins them;
    - ``sharding.replicated-fallback`` (WARNING): a matched axis is
      dropped because the mesh-axis size does not divide the dim;
    - ``sharding.large-replicated`` (WARNING): a fully-replicated
      parameter bigger than ``replicated_warn_mb``.

    Plus the per-device memory estimate (``per_device_bytes``) under
    the final fitted specs.
    """
    from ..framework.analysis import ERROR, WARNING, Diagnostic

    mesh = _as_mesh(mesh)
    params = _normalize_named_params(named_params)
    reports = [RuleReport(pat.pattern, spec)
               for pat, spec in rules._rules]
    default_report = RuleReport(None, rules.default)
    # shadowed-rule attribution: rule idx -> {winner idx}
    lost_to: Dict[int, set] = {}
    seen_unknown_axis: set = set()
    diags: List[Diagnostic] = []
    final: List[Tuple[str, Tuple[int, ...], PartitionSpec]] = []
    total = per_device = replicated = 0

    def screen_axes(spec, rule_label) -> bool:
        """ERROR once per (rule, axis) for axes missing from the mesh;
        True when every axis exists."""
        all_ok = True
        for ax in spec or ():
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is None:
                    continue
                if a not in mesh.shape:
                    all_ok = False
                    key = (rule_label, a)
                    if key not in seen_unknown_axis:
                        seen_unknown_axis.add(key)
                        diags.append(Diagnostic(
                            ERROR, "sharding.unknown-axis",
                            f"rule {rule_label} names mesh axis {a!r}, "
                            f"but the mesh only has "
                            f"{sorted(mesh.shape)} — spec fitting "
                            f"KeyErrors at run time", var=str(rule_label)))
        return all_ok

    for name, shape in params:
        matched = [i for i, (pat, _) in enumerate(rules._rules)
                   if pat.search(name)]
        for i in matched:
            reports[i].matches += 1
        if matched:
            winner = matched[0]
            reports[winner].wins += 1
            for i in matched[1:]:
                lost_to.setdefault(i, set()).add(winner)
            spec = rules._rules[winner][1]
            label = f"#{winner} {reports[winner].pattern!r}"
        else:
            default_report.matches += 1
            default_report.wins += 1
            spec = rules.default
            label = "<default>"

        nbytes = dtype_bytes
        for d in shape:
            nbytes *= int(d)
        total += nbytes

        if not screen_axes(spec, label):
            fitted = P()
        else:
            dims = list(spec or ())
            if len(dims) > len(shape):
                fitted = P()
            else:
                out_dims = []
                for i, ax in enumerate(dims):
                    if ax is None:
                        out_dims.append(None)
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    size = 1
                    for a in axes:
                        size *= mesh.shape[a]
                    if shape[i] % size == 0:
                        out_dims.append(ax)
                    else:
                        diags.append(Diagnostic(
                            WARNING, "sharding.replicated-fallback",
                            f"{name!r} dim {i} (size {shape[i]}) is not "
                            f"divisible by axis {ax!r} (size {size}); "
                            f"rule {label} silently replicates this dim",
                            var=name))
                        out_dims.append(None)
                fitted = P(*out_dims)

        shards = 1
        for ax in fitted:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is not None:
                    shards *= mesh.shape[a]
        per_device += nbytes // shards
        if shards == 1:
            replicated += nbytes
            if nbytes > replicated_warn_mb * 1024 * 1024:
                diags.append(Diagnostic(
                    WARNING, "sharding.large-replicated",
                    f"{name!r} ({nbytes / 2**20:.1f} MiB, shape "
                    f"{list(shape)}) is fully replicated on every "
                    f"device (rule {label})", var=name))
        final.append((name, shape, fitted))

    for i, rep in enumerate(reports):
        if rep.matches == 0:
            diags.append(Diagnostic(
                WARNING, "sharding.dead-rule",
                f"rule #{i} {rep.pattern!r} matches no parameter",
                var=rep.pattern))
        elif rep.wins == 0:
            winners = ", ".join(
                f"#{w} {reports[w].pattern!r}"
                for w in sorted(lost_to.get(i, ())))
            diags.append(Diagnostic(
                WARNING, "sharding.shadowed-rule",
                f"rule #{i} {rep.pattern!r} matches {rep.matches} "
                f"parameter(s) but never wins — shadowed by earlier "
                f"rule(s) {winners}", var=rep.pattern))

    return ShardingLintResult(
        diagnostics=diags, rules=reports + [default_report],
        params=final, total_bytes=total, per_device_bytes=per_device,
        replicated_bytes=replicated)
