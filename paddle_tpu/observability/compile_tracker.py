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
  tracing wall time, and the abstract shape/dtype signature that
  triggered each compile (``compiles()`` exposes it);
- counters in the metrics registry: ``xla_compiles{fn=...}`` and the
  ``xla_compile_ms`` histogram;
- when ``FLAGS_warn_recompiles=N`` (N>0) and a tracked function
  compiles more than N times, a structured ``RecompileWarning`` naming
  the offending signature (and the previous one) is raised via
  ``warnings.warn`` and mirrored into the run log.

The signature is only computed on calls that actually retraced, so the
steady-state (cache-hit) overhead is one integer compare.
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
from . import metrics as _metrics
from . import runlog as _runlog


class RecompileWarning(UserWarning):
    """A tracked function compiled more often than FLAGS_warn_recompiles."""


def _qualname(name: str, labels: Dict[str, str]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


class _CompileRecord:
    """Aggregate compile stats for one (name, labels) site."""

    __slots__ = ("name", "labels", "count", "total_ms",
                 "signatures", "last_signature")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = dict(labels)
        self.count = 0
        self.total_ms = 0.0
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

    def _traced(*args, **kwargs):
        traces["count"] += 1
        return fn(*args, **kwargs)

    jitted = jax.jit(functools.wraps(fn)(_traced), **jit_kwargs)
    seen = [0]
    seen_lock = threading.Lock()

    @functools.wraps(fn)
    def call(*args, **kwargs):
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        if traces["count"] != seen[0]:
            _note_compiles(rec, traces, seen, seen_lock, args, kwargs,
                           (time.perf_counter() - t0) * 1e3)
        return out

    call.traces = traces
    call.record = rec
    call.jitted = jitted
    call.lower = getattr(jitted, "lower", None)
    return call


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
    reg = _metrics.DEFAULT
    # site labels may not shadow the fn= label carrying the site name
    lbls = {k: v for k, v in rec.labels.items() if k != "fn"}
    lbls["fn"] = rec.name
    reg.counter("xla_compiles",
                "XLA compiles per tracked function").labels(**lbls).add(delta)
    reg.histogram("xla_compile_ms",
                  "wall ms of calls that triggered an XLA compile"
                  ).observe(wall_ms)
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
    (``decode_step``, ``serving_prefill{bucket=8}``, ...)."""
    with _lock:
        out: Dict[str, Dict[str, Any]] = {}
        for rec in _records.values():
            out[_qualname(rec.name, rec.labels)] = {
                "name": rec.name,
                "labels": dict(rec.labels),
                "count": rec.count,
                "total_ms": round(rec.total_ms, 3),
                "last_signature": rec.last_signature,
                "signatures": [dict(s) for s in rec.signatures],
            }
        return out


def reset_compiles():
    """Drop all compile records (tests)."""
    with _lock:
        _records.clear()
