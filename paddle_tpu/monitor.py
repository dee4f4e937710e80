"""Runtime counters — StatRegistry analog (platform/monitor.h:76,129).

``STAT_ADD("STAT_total_feasign_num_in_mem", n)`` style counters used by
the dataset/PS tiers for observability; thread-safe, exported as a dict.
``stat_time(name)`` adds a minimal latency facility on the same
registry: phase timings (any ``with`` block; serving prefill/decode
through ``stat_observe``, from readings the engine holds already) land in
``stats()`` as ``<name>_calls`` / ``<name>_ms`` without a separate
metrics stack.

Since the observability plane landed, this module is a *shim*: every
``stat_add`` counter is a Counter and every ``stat_time`` /
``stat_observe`` site a Histogram in
``paddle_tpu.observability.metrics.DEFAULT``, so the same values surface
in ``GET /metrics`` / ``observability.snapshot()``. The dict-shaped API
(exact key names, int/float types, dotted fault-site names) is unchanged
— the whole chaos suite pins it.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict

from .observability import metrics as _metrics

_lock = threading.Lock()
# names this shim has created in the shared registry, by flavor — needed
# so stats()/reset() cover exactly the STAT plane and leave native
# instruments (serving histograms, compile counters) alone
_counter_names: set = set()
_timer_names: set = set()


def _registry() -> _metrics.MetricsRegistry:
    return _metrics.DEFAULT


def stat_add(name: str, value: int = 1):
    with _lock:
        _counter_names.add(name)
    _registry().counter(name).add(int(value))


def stat_set(name: str, value: int):
    with _lock:
        _counter_names.add(name)
    _registry().counter(name).set(int(value))


def stat_get(name: str) -> int:
    reg = _registry()
    with _lock:
        if name in _counter_names:
            inst = reg.get(name)
            return inst.value if inst is not None else 0
        # derived stat_time keys kept readable through stat_get, as the
        # flat-dict store allowed
        for suffix in ("_calls", "_ms"):
            if name.endswith(suffix) and name[:-len(suffix)] in _timer_names:
                inst = reg.get(name[:-len(suffix)])
                if inst is None:
                    return 0
                return inst.count if suffix == "_calls" else inst.sum
    return 0


@contextlib.contextmanager
def stat_time(name: str):
    """``with stat_time("STAT_phase"): ...`` — records one
    call and its wall-clock milliseconds as ``<name>_calls`` (int) and
    ``<name>_ms`` (float total) alongside the ordinary counters, so
    ``stats()["STAT_phase_ms"] / stats()["STAT_phase_calls"]``
    is the mean latency."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        stat_observe(name, (time.perf_counter() - t0) * 1e3)


def stat_observe(name: str, ms: float):
    """One call of ``ms`` milliseconds under ``name``, as :func:`stat_time`
    records it, for a phase whose ends no ``with`` block spans and whose
    clock readings the caller holds already (the serving engine's
    ``STAT_serving_prefill`` / ``_decode`` / ``_verify``: one observation a
    dispatch, from its dispatch to its tokens fetched)."""
    with _lock:
        _timer_names.add(name)
    _registry().histogram(name).observe(ms)


def stats() -> Dict[str, float]:
    reg = _registry()
    out: Dict[str, float] = {}
    with _lock:
        counters = list(_counter_names)
        timers = list(_timer_names)
    for name in counters:
        inst = reg.get(name)
        if inst is not None:
            out[name] = inst.value
    for name in timers:
        inst = reg.get(name)
        if inst is not None:
            out[name + "_calls"] = inst.count
            out[name + "_ms"] = inst.sum
    return out


def stats_with_prefix(prefix: str) -> Dict[str, int]:
    """Counters under one namespace, e.g. ``stats_with_prefix
    ("STAT_fault_")`` — how the chaos suite asserts every injection and
    every recovery was actually observed, not just survived."""
    return {k: v for k, v in stats().items() if k.startswith(prefix)}


def reset():
    reg = _registry()
    with _lock:
        names = _counter_names | _timer_names
        _counter_names.clear()
        _timer_names.clear()
    for name in names:
        reg.unregister(name)


# C++-style aliases
STAT_ADD = stat_add
STAT_RESET = reset
