"""From the profiler's trace to numbers: device busy and idle, time per
operation, idle gaps named by what the host was doing, Mosaic kernel time
(all of it, and each named kernel's seconds and calls), collective time that
no compute hides.

Two steps, so that the arithmetic can be checked without a chip:

- :func:`load` reads an ``.xplane.pb`` with nothing but JAX into a plain
  dict ``{"planes": [{"name", "lines": [{"name", "events": [[name,
  start_ns, dur_ns], ...]}]}]}`` (:func:`dump` / :func:`load_json` keep
  such a dict as JSON: ``tests/data/`` holds one recorded on the v5e);
- :func:`reduce` is pure arithmetic over that dict.

What a v5e trace looks like (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per program
execution), ``XLA Ops`` (one per HLO instruction executed, named by the
instruction's text: ``%fusion.3 = f32[8,2048]{..} fusion(...), kind=kLoop``)
and ``Async XLA Ops`` (one per asynchronous pair, from ``-start`` to
``-done``). The host is the plane ``/host:CPU``, one line per thread;
``jax.profiler.TraceAnnotation`` spans (the program's ``serving.decode``,
the harness's ``bench.step``) are events on the main thread's line, on the
same clock as the device planes.
"""

from __future__ import annotations

import bisect
import json
import re
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"     # the harness wraps the traced window in it
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "ragged-all-to-all")
MOSAIC_TARGET = "tpu_custom_call"
SHORT_GAP_NS = 20_000            # gaps under 20 us are launch latency
Interval = Tuple[float, float]

_INSTR = re.compile(r"^%(?P<name>\S+) = (?P<shape>.*?) (?P<op>[\w\-]+)\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


# ------------------------------------------------------------------ loading

def load(path: str) -> dict:
    """An ``.xplane.pb`` as a plain dict (needs jax, runs anywhere)."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]}
            for line in plane.lines]}
        for plane in data.planes]}


def dump(trace: dict, path: str):
    with open(path, "w") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------- intervals

def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -------------------------------------------------------------------- names

def opcode(instr: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event name ('' if it is not an
    instruction's text)."""
    m = _INSTR.match(instr)
    return m.group("op") if m else ""


def is_collective(instr: str) -> bool:
    op = opcode(instr)
    return any(op == c or op == c + "-start" or op == c + "-done"
               for c in COLLECTIVES)


def is_mosaic(instr: str) -> bool:
    return MOSAIC_TARGET in instr


def short_name(instr: str) -> str:
    """``%copy.5 = f32[400,16,16,128]{..} copy(...)`` -> ``copy_f32_400_16_16_128``
    (the instruction's name without its number, the result's type and
    extents; a fusion keeps its kind): stable across runs, short enough for
    a ledger line."""
    m = _INSTR.match(instr)
    if not m:
        return re.sub(r"[^\w.\-]+", "_", instr)[:60]
    name = re.sub(r"[.\d]+$", "", m.group("name"))
    shape = _SHAPE.search(m.group("shape"))
    label = name
    if shape:
        dims = shape.group(2).replace(",", "_")
        label += f"_{shape.group(1)}" + (f"_{dims}" if dims else "")
    kind = re.search(r"kind=(k\w+)", instr)
    if kind:
        label += "_" + kind.group(1)
    if is_mosaic(instr):
        label += "_mosaic"
    return label


def kernel_stem(instr: str) -> str:
    """The name the program gave a kernel (``pallas_call(name=...)``), read
    off the instruction XLA made of it: ``%flash_fwd.40 = (bf16[128,1024,
    128]{..}, ..) custom-call(..)`` -> ``flash_fwd``. XLA numbers its
    instructions after a dot, so a kernel's name has none."""
    m = _INSTR.match(instr)
    return m.group("name").split(".")[0] if m else ""


def _module_of(modules: List[Tuple[float, float, str]], starts: List[float],
               t: float) -> str:
    """The program execution (``XLA Modules`` event) that holds time ``t``;
    ``modules`` sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1]:
        return modules[i][2]
    return "?"


# ------------------------------------------------------------------- reduce

def _lines(plane: dict) -> Dict[str, list]:
    return {ln["name"]: ln["events"] for ln in plane["lines"]}


def _host_spans(trace: dict):
    """(window interval, spans of the thread that holds it) or (None, [])."""
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    return (start, start + dur), line["events"]
    return None, []


def _blame_all(spans, times: Sequence[float]) -> List[str]:
    """What the host's main thread was in at each of ``times`` (ascending):
    the outermost harness span and the innermost span of any kind,
    ``outer/inner``. Python-tracer frames (``$file:line fn``) are skipped.
    One sweep: the spans open at a time are few (their nesting depth)."""
    todo = sorted((s, s + d, n) for n, s, d in spans
                  if n != WINDOW_SPAN and not n.startswith("$"))
    out, open_, i = [], [], 0
    for t in times:
        while i < len(todo) and todo[i][0] <= t:
            open_.append(todo[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] > t]
        if not open_:
            out.append("host_outside_any_span")
            continue
        outer = next((n for _, _, n in open_ if n.startswith("bench.")), None)
        inner = min(open_, key=lambda sp: sp[1] - sp[0])[2]
        if outer is None or outer == inner:
            out.append(outer or inner)
        else:
            out.append(outer + "/" + re.sub(r"[^\w.:\-]+", "_", inner))
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """Everything the per-layer metrics and ``breakdown`` read from a trace.

    Seconds are averaged over the device planes (the chips used). Every
    Mosaic kernel in the window, not the ``top`` largest, is there by the
    name the program gave it: ``kernel_s.<name>``, ``kernel_calls.<name>``.
    Raises if the trace has no window span or no device plane: a traced run
    in which no operation ran on the device is not a result."""
    window, spans = _host_spans(trace)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    devices = [p for p in trace["planes"]
               if p["name"].startswith("/device:TPU:")
               and OPS_LINE in _lines(p)]
    if not devices:
        raise ValueError("no /device:TPU:<n> plane with an 'XLA Ops' line")
    busy = mosaic = coll_total = coll_exposed = 0.0
    per_op: Dict[str, float] = {}
    kernels: Dict[str, List[float]] = {}      # stem -> [ns, calls]
    gaps: Dict[str, float] = {}
    for plane in devices:
        lines = _lines(plane)
        modules = sorted((s, s + d, re.sub(r"\(\d+\)$", "", n))
                         for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        ops = [(n, s, s + d) for n, s, d in lines[OPS_LINE]
               if s + d > lo and s < hi]
        ops_u = clip(union([(a, b) for _, a, b in ops]), lo, hi)
        busy += length(ops_u)
        compute_u = clip(union([(a, b) for n, a, b in ops
                                if not is_collective(n)]), lo, hi)
        coll = [(a, b) for n, a, b in ops if is_collective(n)]
        coll += [(s, s + d) for n, s, d in lines.get(ASYNC_LINE, [])
                 if is_collective(n)]
        coll_u = clip(union(coll), lo, hi)
        coll_total += length(coll_u)
        coll_exposed += length(subtract(coll_u, compute_u))
        for n, a, b in ops:
            dur = min(b, hi) - max(a, lo)
            if is_mosaic(n):
                mosaic += dur
                # a call the window cuts counts as the part of it inside
                k = kernels.setdefault(kernel_stem(n), [0.0, 0.0])
                k[0] += dur
                k[1] += dur / (b - a) if b > a else 1.0
            key = f"{_module_of(modules, starts, a)}:{short_name(n)}"
            per_op[key] = per_op.get(key, 0.0) + dur
        idle = subtract([(lo, hi)], ops_u)
        long_ = [g for g in idle if g[1] - g[0] >= SHORT_GAP_NS]
        short = length(idle) - length(long_)
        if short:
            gaps["_gaps_under_20_us_"] = gaps.get("_gaps_under_20_us_",
                                                  0.0) + short
        names = _blame_all(spans, [(a + b) / 2 for a, b in long_])
        for (a, b), key in zip(long_, names):
            gaps[key] = gaps.get(key, 0.0) + (b - a)
    n = len(devices)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    named = {}
    for stem, (ns, calls) in kernels.items():
        named["kernel_s." + stem] = ns / n / 1e9
        named["kernel_calls." + stem] = calls / n
    return {**named,
            "devices": n,
            "window_s": (hi - lo) / 1e9,
            "busy_s": busy / n / 1e9,
            "mosaic_s": mosaic / n / 1e9,
            "collective_s": coll_total / n / 1e9,
            "collective_exposed_s": coll_exposed / n / 1e9,
            "device_ops": ranked(per_op),
            "idle_gaps": ranked(gaps)}


def host_span_seconds(trace: dict, name: str) -> List[float]:
    """Durations (s) of every host span called ``name`` that lies inside
    the traced window, on the thread that holds the window."""
    window, spans = _host_spans(trace)
    if window is None:
        return []
    lo, hi = window
    return [d / 1e9 for n, s, d in spans
            if n == name and s >= lo and s + d <= hi]
