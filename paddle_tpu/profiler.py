"""Profiler: scoped host events + chrome-trace output + XLA (xplane)
device tracing.

Capability analog of the reference's profiler plane: RAII RecordEvent
markers (platform/profiler.h:126), EnableProfiler/DisableProfiler
(:208-211), CUPTI DeviceTracer (device_tracer.h:41), the
fluid/profiler.py python surface (:131-255) and tools/timeline.py's
chrome://tracing converter. TPU translation: host events are recorded
in-process AND forwarded to jax.profiler.TraceAnnotation so they appear
inside the XLA xplane timeline; device-side tracing is jax.profiler
start/stop_trace (TensorBoard-loadable), replacing CUPTI.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except ImportError:          # host-only use without jax: in-process events only
    _TraceAnnotation = None

_lock = threading.Lock()
_enabled = False
_events: List[dict] = []
_trace_dir: Optional[str] = None
_ids = itertools.count(1)       # span ids, unique in the process
_local = threading.local()      # .stack: ids of the spans open on this thread


def _open_spans() -> List[int]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class RecordEvent:
    """Scoped annotation (platform/profiler.h:126 RAII analog); usable
    as a context manager or decorator. No-op unless the profiler is on,
    except the jax TraceAnnotation which is cheap and always useful.

    While the profiler is on each event keeps an ``id`` unique in the
    process, the ``id`` of its ``parent`` (the RecordEvent open on the
    same thread when it was entered, None at the root) and the optional
    ``args`` (a small dict of str/int/float, also handed to the
    TraceAnnotation as keyword arguments: they become stats of the
    trace event, its name stays ``name``).

    Profiler on or off, the two clock readings an event takes anyway stay
    on it: ``t0`` at entry and ``t1`` at exit, ``time.perf_counter_ns``
    (0 until taken). Code that accounts for the interval beside the span
    (the serving engine's step account) reads them and not the clock, so
    the account and the span are the same readings."""

    _ann = _id = _parent = None
    t0 = t1 = 0

    def __init__(self, name: str, args: Optional[dict] = None):
        self.name = name
        self.args = args

    def __enter__(self):
        if _TraceAnnotation is not None:
            self._ann = (_TraceAnnotation(self.name, **self.args)
                         if self.args else _TraceAnnotation(self.name))
            self._ann.__enter__()
        if _enabled:
            stack = _open_spans()
            self._id = next(_ids)
            self._parent = stack[-1] if stack else None
            stack.append(self._id)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = self.t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._id is None:        # entered with the profiler off
            return False
        stack = _open_spans()
        if self._id in stack:       # not when exited on another thread
            stack.remove(self._id)
        _record(self.name, self.t0 / 1e3, (t1 - self.t0) / 1e3,
                self._id, self._parent, self.args)
        self._id = None
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with RecordEvent(self.name, self.args):
                return fn(*a, **kw)
        return wrapper


def _record(name, ts_us, dur_us, id_, parent, args):
    event = {"name": name, "ts": ts_us, "dur": dur_us,   # chrome trace: us
             "tid": threading.get_ident() % 100000,
             "id": id_, "parent": parent}
    if args:
        event["args"] = args
    with _lock:
        # _enabled is mutated by start/stop_profiler under _lock;
        # read it there too so a concurrent stop can't interleave
        if _enabled:
            _events.append(event)


def record_span(name: str, start_s: float, dur_s: float,
                args: Optional[dict] = None):
    """Record an interval measured elsewhere (seconds on the
    ``time.perf_counter`` clock the RecordEvents use) as a parentless
    event; nothing happens with the profiler off. For intervals that no
    ``with`` block spans, e.g. the gap between two of a request's tokens
    committed in different engine steps."""
    if _enabled:
        _record(name, start_s * 1e6, dur_s * 1e6, next(_ids), None, args)


def start_profiler(state: str = "All", trace_dir: Optional[str] = None):
    """fluid/profiler.py start_profiler parity. With ``trace_dir`` a
    jax/XLA device trace (xplane, TensorBoard-loadable) records too."""
    global _enabled, _trace_dir
    with _lock:
        _events.clear()
        _enabled = True
    if trace_dir:
        import jax.profiler
        jax.profiler.start_trace(trace_dir)
        _trace_dir = trace_dir


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile"):
    """Stop, write a chrome://tracing JSON to ``profile_path`` and print
    the summary table (fluid/profiler.py stop_profiler +
    tools/timeline.py collapsed into one step)."""
    global _enabled, _trace_dir
    with _lock:
        _enabled = False
        events = list(_events)
        _events.clear()
    if _trace_dir is not None:
        import jax.profiler
        jax.profiler.stop_trace()
        _trace_dir = None
    trace = {"traceEvents": [
        {**e, "ph": "X", "pid": 0, "cat": "host"} for e in events]}
    d = os.path.dirname(profile_path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(profile_path, "w") as f:
        json.dump(trace, f)
    summary = summarize(events, sorted_key)
    if summary:
        name_w = max(len(s["name"]) for s in summary)
        print(f"{'Event':{name_w}s}  {'Calls':>6s}  {'Total(ms)':>10s}  "
              f"{'Avg(ms)':>10s}  {'Self(ms)':>10s}")
        for s in summary:
            print(f"{s['name']:{name_w}s}  {s['calls']:6d}  "
                  f"{s['total_ms']:10.3f}  {s['avg_ms']:10.3f}  "
                  f"{s['self_ms']:10.3f}")
    _print_metrics_summary()
    return summary


def _print_metrics_summary():
    """Counter/histogram totals from the observability plane, appended
    to the host-event table so one report covers both."""
    from . import observability
    snap = observability.snapshot()
    counters = {**snap["counters"], **snap["gauges"]}
    hists = snap["histograms"]
    if counters:
        print("Counters:")
        name_w = max(len(n) for n in counters)
        for name in sorted(counters):
            print(f"  {name:{name_w}s}  {counters[name]}")
    if hists:
        print(f"{'Histogram':28s}  {'Count':>7s}  {'Sum':>12s}  "
              f"{'p50':>10s}  {'p95':>10s}  {'p99':>10s}")
        for name in sorted(hists):
            h = hists[name]
            if not h["count"]:
                continue
            row = [f"{h[k]:10.4g}" if h[k] is not None else f"{'-':>10s}"
                   for k in ("p50", "p95", "p99")]
            print(f"{name:28s}  {h['count']:7d}  {h['sum']:12.4g}  "
                  + "  ".join(row))
    comp = snap.get("compiles") or {}
    if comp:
        print("XLA compiles:")
        for qual in sorted(comp):
            c = comp[qual]
            print(f"  {qual}: {c['count']} traces, {c['programs']} programs "
                  f"({c['trace_ms']:.1f} ms tracing, "
                  f"{c['lower_ms']:.1f} lowering, "
                  f"{c['compile_ms']:.1f} compiling, "
                  f"{c['cache_hits']} cache hits; "
                  f"{c['total_ms']:.1f} ms in the calls that traced)")


def summarize(events: List[dict], sorted_key: Optional[str] = None):
    """Per-name calls, total, average and self time. ``self_ms`` is the
    events' durations minus what their children (the events naming them
    as ``parent``) cover; children of one parent sit on one thread and
    do not overlap, so their durations add."""
    covered: Dict[int, float] = {}
    for e in events:
        if e.get("parent") is not None:
            covered[e["parent"]] = covered.get(e["parent"], 0.0) + e["dur"]
    agg: Dict[str, dict] = {}
    for e in events:
        a = agg.setdefault(e["name"], {"name": e["name"], "calls": 0,
                                       "total_ms": 0.0, "self_ms": 0.0})
        a["calls"] += 1
        a["total_ms"] += e["dur"] / 1e3
        a["self_ms"] += max(0.0, e["dur"] - covered.get(e.get("id"), 0.0)) / 1e3
    out = list(agg.values())
    for a in out:
        a["avg_ms"] = a["total_ms"] / a["calls"]
    key = {"total": "total_ms", "ave": "avg_ms", "calls": "calls",
           None: "total_ms"}.get(sorted_key, "total_ms")
    out.sort(key=lambda a: -a[key])
    return out


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: str = "/tmp/profile",
             trace_dir: Optional[str] = None):
    """``with profiler.profiler(): ...`` context (fluid/profiler.py:255)."""
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
