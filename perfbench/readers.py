"""Per-layer metrics as data: ``metrics/<name>.json`` says where a number
comes from and how it is reduced, and :func:`read` does it.

A run gathers *observations*, a dict of five groups:

- ``spans``: name -> list of durations in seconds (the program's
  ``serving.prefill`` / ``serving.decode`` ``RecordEvent`` spans and the
  harness's own ``bench.*`` spans, over the measured window);
- ``span_args``: ``<span>.<arg>`` -> the values that argument took over the
  window, for every recorded span that carries ``args``
  (``serving.prefill_step.rows``, ``serving.engine_step.active``);
- ``samples``: name -> list of numbers the harness took (one per step, per
  request or per release);
- ``counters``: name -> one number (counts and readings: compiles, peak
  bytes, tokens; and the program's own, ``engine.<key>`` of
  ``engine.stats()`` and ``STAT_serving_*``: the window's difference under
  the name, the closing value under ``<name>.close``);
- ``trace``: name -> one number from the device trace (:mod:`xplane`;
  ``kernel_s.<kernel>``, ``kernel_calls.<kernel>`` and, where the family has
  counts for the kernel, ``kernel_floor_s.<kernel>`` among them).

A metric file's ``reader`` names a group (``from``), a ``name`` in it, a
reduction, and optionally a ``scale`` to multiply by and an ``over`` — a
second name whose value divides the first (``"over": "window_s"``, a
counter, or ``"<group>.<name>"``: ``"trace.busy_s"``; the name may itself
hold dots, ``"counters.engine.pool_dispatches"``). A reader that finds
nothing to read returns ``None`` and the harness leaves the metric out of
the line.

The reductions are a fixed set: ``sum mean max min count value p<q>``.
A new metric over an existing span or counter is a new file, no code.
"""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
GROUPS = ("spans", "span_args", "samples", "counters", "trace")


def spec(name: str) -> dict:
    with open(os.path.join(_HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    s = sorted(values)
    if len(s) == 1:
        return float(s[0])
    pos = q / 100.0 * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def _reduce(values, how: str) -> Optional[float]:
    if isinstance(values, (int, float)):
        values = [values]
    values = [float(v) for v in values]
    if how == "count":
        return float(len(values))
    if not values:
        return None
    if how in ("sum", "mean", "max", "min"):
        return float({"sum": sum, "mean": statistics.fmean,
                      "max": max, "min": min}[how](values))
    if how == "value":
        return values[-1]
    if how.startswith("p") and how[1:].replace(".", "", 1).isdigit():
        return percentile(values, float(how[1:]))
    raise ValueError(f"unknown reduction {how!r}")


def _lookup(obs: dict, group: str, name: str):
    if group not in GROUPS:
        raise ValueError(f"unknown group {group!r}")
    return obs.get(group, {}).get(name)


def read(name: str, obs: dict) -> Optional[float]:
    """The value of per-layer metric ``name`` from ``obs``, or None."""
    r = spec(name)["reader"]
    raw = _lookup(obs, r["from"], r["name"])
    if raw is None:
        return None
    value = _reduce(raw, r.get("reduce", "value"))
    if value is None:
        return None
    if "over" in r:
        group, _, key = r["over"].partition(".")
        if group not in GROUPS:
            group, key = "counters", r["over"]
        denom = _lookup(obs, group, key)
        if not denom:
            return None
        value /= float(denom)
    return value * float(r.get("scale", 1.0))
