"""XLA compile tracker — every ``jax.jit`` entry point in the codebase
goes through ``tracked_jit`` so recompilation (the dominant TPU latency
hazard) is a first-class, attributable metric instead of a silent bench
regression.

``tracked_jit(name, fn, labels=..., **jit_kwargs)`` returns a callable
that behaves exactly like ``jax.jit(fn, **jit_kwargs)`` plus:

- a per-instance ``.traces`` dict (``{"count": n}``) incremented each
  time XLA retraces — the contract the serving tests already pin on
  ``decode_step(model)["traces"]["count"]``;
- a process-wide record per (name, labels) aggregating compile count,
  the wall of the calls that traced, and the abstract shape/dtype
  signature that triggered each compile (``compiles()`` exposes it);
- **the account by stage** on the same record: ``trace_ms`` (the Python
  tracing of the site's function, stamped inside the wrapper that runs
  only when JAX retraces; exclusive, so a tracked site traced inside
  another is left out of the outer's), and from JAX's own
  ``jax.monitoring`` events ``lower_ms`` (jaxpr to MLIR module),
  ``compile_ms`` (``compile_or_get_cached``: the backend's compilation
  or the persistent cache's retrieval), ``programs`` (executables built
  or retrieved), ``cache_hits``, ``cache_misses`` (a program compiled
  and written: one too small or too quick to be kept counts as
  neither) and ``cache_retrieval_ms`` (inside ``compile_ms``). An event
  belongs to the site that is tracing or building on the calling
  thread; one with no such site (eager single-op programs, a bare
  ``jax.jit``) goes to the record named ``(untracked)``, whose tracing
  is not stamped, so :func:`compile_totals` is the process's;
- counters in the metrics registry: ``xla_compiles{fn=...}``,
  ``xla_trace_ms``, ``xla_lower_ms``, ``xla_backend_compile_ms``,
  ``xla_cache_hits`` and ``xla_cache_misses`` under the same labels;
- with the profiler on, one parentless ``program.build`` span a call
  that built a program (``args``: ``site``, ``trace_ms``, ``lower_ms``,
  ``compile_ms``, ``cache_hit``), and every trace runs under a
  ``program.trace`` ``RecordEvent`` (a ``TraceAnnotation`` too, so a
  retrace inside a device trace names its idle gap);
- when ``FLAGS_warn_recompiles=N`` (N>0) and a tracked function
  compiles more than N times, a structured ``RecompileWarning`` naming
  the offending signature (and the previous one) is raised via
  ``warnings.warn`` and mirrored into the run log.

The signature is only computed on calls that actually retraced, and the
stamps, the thread-local and the listeners run only while a program is
traced or built, so the steady-state (cache-hit) overhead is one integer
compare. ``total_ms`` less ``trace_ms``, ``lower_ms`` and ``compile_ms``
is the program's first run.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax

from .. import flags as _flags
from .. import profiler as _profiler
from . import metrics as _metrics
from . import runlog as _runlog


class RecompileWarning(UserWarning):
    """A tracked function compiled more often than FLAGS_warn_recompiles."""


def _qualname(name: str, labels: Dict[str, str]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


#: what a record keeps by stage, beside ``count`` and ``total_ms``
STAGES = ("trace_ms", "lower_ms", "compile_ms", "programs", "cache_hits",
          "cache_misses", "cache_retrieval_ms")
UNTRACKED = "(untracked)"
#: the registry counter that follows a stage (``programs`` and the
#: retrieval's time have none)
_STAGE_COUNTERS = {
    "trace_ms": ("xla_trace_ms",
                 "ms of Python tracing per tracked function, exclusive"),
    "lower_ms": ("xla_lower_ms", "ms of lowering to an MLIR module"),
    "compile_ms": ("xla_backend_compile_ms",
                   "ms of backend compilation or cache retrieval"),
    "cache_hits": ("xla_cache_hits",
                   "programs the persistent compile cache served"),
    "cache_misses": ("xla_cache_misses",
                     "programs compiled and written to the cache"),
}


class _CompileRecord:
    """Aggregate compile stats for one (name, labels) site."""

    __slots__ = ("name", "labels", "count", "total_ms",
                 "signatures", "last_signature") + STAGES

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = dict(labels)
        self.count = 0
        self.total_ms = 0.0
        for stage in STAGES:
            setattr(self, stage, 0)
        # keep the last few (signature, ms) pairs — enough to attribute
        # a recompile loop without unbounded growth
        self.signatures: collections.deque = collections.deque(maxlen=8)
        self.last_signature: Optional[str] = None


_lock = threading.Lock()
_records: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], _CompileRecord] = {}


def _record_for(name: str, labels: Dict[str, str]) -> _CompileRecord:
    key = (name, tuple(sorted(labels.items())))
    with _lock:
        rec = _records.get(key)
        if rec is None:
            rec = _records[key] = _CompileRecord(name, labels)
        return rec


def _site_labels(rec: _CompileRecord) -> Dict[str, str]:
    # site labels may not shadow the fn= label carrying the site name
    lbls = {k: v for k, v in rec.labels.items() if k != "fn"}
    lbls["fn"] = rec.name
    return lbls


class _Building(threading.local):
    """What this thread is tracing or building now."""
    site: Optional[_CompileRecord] = None   # whose JAX's events are
    frame: Optional[list] = None    # innermost open trace: [ns not its own]
    build: Optional[dict] = None    # the program being built: stage -> sum


_now = _Building()


def _add(rec: Optional[_CompileRecord], stage: str, amount):
    """``amount`` more of ``stage`` for ``rec`` (None: the untracked
    record), in the registry and in the program being built too."""
    if rec is None:
        rec = _record_for(UNTRACKED, {})
    with _lock:
        setattr(rec, stage, getattr(rec, stage) + amount)
    build = _now.build
    if build is not None:
        build[stage] = build.get(stage, 0) + amount
    if stage in _STAGE_COUNTERS:
        _metrics.DEFAULT.counter(*_STAGE_COUNTERS[stage]).labels(
            **_site_labels(rec)).add(amount)


_DURATIONS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ms",
    "/jax/core/compile/backend_compile_duration": "compile_ms",
    # inside backend_compile_duration, which bounds compile_or_get_cached
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_ms",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _on_duration(event: str, secs: float, **_):
    stage = _DURATIONS.get(event)
    if stage is None:
        return
    _add(_now.site, stage, secs * 1e3)
    if stage == "cache_retrieval_ms":
        return
    if stage == "compile_ms":
        _add(_now.site, "programs", 1)
    frame = _now.frame
    if frame is not None:
        # an eager program built while a site traces: not its tracing
        frame[0] += int(secs * 1e9)


def _on_event(event: str, **_):
    stage = _EVENTS.get(event)
    if stage is not None:
        _add(_now.site, stage, 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def _describe_leaf(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        dims = ",".join(str(d) for d in shape)
        return f"{getattr(dtype, 'name', dtype)}[{dims}]"
    return type(x).__name__


_SIG_MAX_CHARS = 512


def abstract_signature(args: tuple, kwargs: dict) -> str:
    """Abstract shape/dtype signature of a call, e.g.
    ``f64[4,32],i64[4],int`` — what XLA keys its trace cache on (up to
    static argnums / weak types, which is plenty for attribution)."""
    try:
        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:
        leaves = list(args) + list(kwargs.values())
    sig = ",".join(_describe_leaf(x) for x in leaves)
    if len(sig) > _SIG_MAX_CHARS:
        sig = sig[:_SIG_MAX_CHARS] + f"...({len(leaves)} leaves)"
    return sig


def tracked_jit(name: str, fn, *, labels: Optional[Dict[str, str]] = None,
                **jit_kwargs):
    """``jax.jit`` with compile accounting; see module docstring.

    Extra attributes on the returned wrapper:
      ``.traces``   — per-instance ``{"count": n}`` retrace counter
      ``.record``   — the process-wide :class:`_CompileRecord`
      ``.jitted``   — the underlying ``jax.jit`` object
    """
    labels = dict(labels or {})
    rec = _record_for(name, labels)
    traces = {"count": 0}
    qual = _qualname(name, labels)

    def _traced(*args, **kwargs):
        traces["count"] += 1
        outer_site, outer_frame = _now.site, _now.frame
        if outer_frame is None:
            _now.build = {}
        frame = _now.frame = [0]
        _now.site = rec
        t0 = time.perf_counter_ns()
        try:
            with _profiler.RecordEvent("program.trace", {"site": qual}):
                out = fn(*args, **kwargs)
        except BaseException:
            _now.site = outer_site      # nothing will be built
            raise
        finally:
            took = time.perf_counter_ns() - t0
            _now.frame = outer_frame
            _add(rec, "trace_ms", (took - frame[0]) / 1e6)
        if outer_frame is not None:
            # traced inside another site: lowered into its module, and
            # what JAX reports from here on is the outer site's again
            outer_frame[0] += took
            _now.site = outer_site
        return out

    jitted = jax.jit(functools.wraps(fn)(_traced), **jit_kwargs)
    seen = [0]
    seen_lock = threading.Lock()

    @functools.wraps(fn)
    def call(*args, **kwargs):
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        if traces["count"] != seen[0]:
            wall = time.perf_counter() - t0
            if _now.frame is None:      # a program of its own was built
                _built(qual, t0, wall)
            _note_compiles(rec, traces, seen, seen_lock, args, kwargs,
                           wall * 1e3)
        return out

    def lower(*args, **kwargs):
        try:
            return jitted.lower(*args, **kwargs)
        finally:        # no call follows that would end the build
            _now.site = _now.build = None

    call.traces = traces
    call.record = rec
    call.jitted = jitted
    call.lower = lower
    return call


def _built(site: str, t0: float, wall: float):
    """The end of a call that built a program: JAX's events are no
    site's again, and the build is one ``program.build`` span."""
    build, _now.site, _now.build = _now.build or {}, None, None
    _profiler.record_span("program.build", t0, wall, {
        "site": site,
        "trace_ms": round(build.get("trace_ms", 0.0), 3),
        "lower_ms": round(build.get("lower_ms", 0.0), 3),
        "compile_ms": round(build.get("compile_ms", 0.0), 3),
        "cache_hit": int(build.get("cache_hits", 0) > 0)})


def _note_compiles(rec: _CompileRecord, traces, seen, seen_lock,
                   args, kwargs, wall_ms: float):
    with seen_lock:
        delta = traces["count"] - seen[0]
        if delta <= 0:  # concurrent caller already accounted for it
            return
        seen[0] = traces["count"]
    sig = abstract_signature(args, kwargs)
    with _lock:
        prev_sig = rec.last_signature
        rec.count += delta
        rec.total_ms += wall_ms
        rec.signatures.append({"signature": sig, "ms": round(wall_ms, 3)})
        rec.last_signature = sig
        count_now = rec.count
    _metrics.DEFAULT.counter(
        "xla_compiles", "XLA compiles per tracked function"
    ).labels(**_site_labels(rec)).add(delta)
    limit = int(_flags.get_flag("warn_recompiles") or 0)
    if limit > 0 and count_now > limit:
        qual = _qualname(rec.name, rec.labels)
        msg = (f"XLA recompile: {qual} compiled {count_now} times "
               f"(FLAGS_warn_recompiles={limit}); offending signature "
               f"{sig!r}" +
               (f"; previous signature {prev_sig!r}"
                if prev_sig and prev_sig != sig else ""))
        warnings.warn(RecompileWarning(msg), stacklevel=4)
        _runlog.log_event("recompile_warning", fn=rec.name,
                          labels=rec.labels, count=count_now,
                          limit=limit, signature=sig,
                          previous_signature=prev_sig)


def compiles() -> Dict[str, Dict[str, Any]]:
    """Snapshot of all tracked compile sites, keyed by qualified name
    (``decode_step``, ``serving_prefill{bucket=8}``, ...), with
    ``(untracked)`` once a program was built outside every site."""
    with _lock:
        out: Dict[str, Dict[str, Any]] = {}
        for rec in _records.values():
            out[_qualname(rec.name, rec.labels)] = {
                "name": rec.name,
                "labels": dict(rec.labels),
                "count": rec.count,
                "total_ms": round(rec.total_ms, 3),
                **{stage: round(getattr(rec, stage), 3)
                   for stage in STAGES},
                "last_signature": rec.last_signature,
                "signatures": [dict(s) for s in rec.signatures],
            }
        return out


def compile_totals() -> Dict[str, float]:
    """The stages summed over every site and ``(untracked)``: what the
    process spent building programs so far. Each only grows."""
    with _lock:
        return {stage: sum(getattr(rec, stage) for rec in _records.values())
                for stage in STAGES}


def reset_compiles():
    """Drop all compile records (tests)."""
    with _lock:
        _records.clear()
