#!/usr/bin/env python
"""Summarize a profiler chrome-trace JSON or an observability JSONL
run log as a top-N table.

    python tools/trace_summary.py /tmp/profile            # chrome trace
    python tools/trace_summary.py /tmp/runlog/runlog-1.jsonl
    python tools/trace_summary.py TRACE --top 20 --sort calls
    python tools/trace_summary.py /tmp/serving_trace.json --blame

Chrome traces (written by paddle_tpu.profiler.stop_profiler or
paddle_tpu.observability.tracing.export_chrome_trace) aggregate per
event name: calls, total ms, average ms. Run logs (written by
paddle_tpu.observability.log_event under FLAGS_runlog_dir) aggregate
per event kind: count, wall-clock span, and means of any numeric
fields (loss, step_time_ms, ttft_ms, ...) seen on that kind.

``--blame`` reads per-request serving spans instead — either a
tracing chrome trace (X events grouped by ``args.request``) or a
spans JSONL (``tracing.export_spans_jsonl``: one
``{"trace", "span", "t0", "t1", "dur_ms", ...}`` line per span) — and
prints the latency-component blame table: per-component total ms,
share of summed E2E, p95 ms, and which component dominates the E2E
p95 tail (see paddle_tpu/observability/tracing.py for the accounting
identity behind the numbers).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List


def load_events(path: str):
    """Returns ("chrome", events) or ("runlog", events). A chrome trace
    is one JSON document ({"traceEvents": [...]} or a bare event
    array); anything that only parses line by line is a JSONL run
    log."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        if "traceEvents" not in doc:
            raise SystemExit(
                f"{path}: JSON object without traceEvents — neither a "
                "chrome trace nor a JSONL run log")
        return "chrome", doc["traceEvents"]
    if isinstance(doc, list):
        return "chrome", doc
    events = []
    for ln, line in enumerate(text.splitlines(), start=1):
        line = line.strip()
        if not line:
            continue
        try:
            events.append(json.loads(line))
        except json.JSONDecodeError as e:
            raise SystemExit(f"{path}:{ln}: bad JSONL line: {e}")
    return "runlog", events


def summarize_chrome(events: List[dict]) -> List[dict]:
    agg: Dict[str, dict] = {}
    for e in events:
        if e.get("ph") not in (None, "X"):
            continue
        a = agg.setdefault(e.get("name", "?"),
                           {"name": e.get("name", "?"), "calls": 0,
                            "total_ms": 0.0})
        a["calls"] += 1
        a["total_ms"] += float(e.get("dur", 0.0)) / 1e3  # us -> ms
    for a in agg.values():
        a["avg_ms"] = a["total_ms"] / a["calls"]
    return list(agg.values())


def summarize_runlog(events: List[dict]) -> List[dict]:
    agg: Dict[str, dict] = {}
    for e in events:
        kind = e.get("kind", "?")
        a = agg.setdefault(kind, {"name": kind, "calls": 0,
                                  "mono_min": None, "mono_max": None,
                                  "fields": {}})
        a["calls"] += 1
        mono = e.get("mono")
        if isinstance(mono, (int, float)):
            a["mono_min"] = mono if a["mono_min"] is None else \
                min(a["mono_min"], mono)
            a["mono_max"] = mono if a["mono_max"] is None else \
                max(a["mono_max"], mono)
        for k, v in e.items():
            if k in ("seq", "ts", "mono", "kind"):
                continue
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                s = a["fields"].setdefault(k, [0, 0.0])
                s[0] += 1
                s[1] += v
    out = []
    for a in agg.values():
        span = (a["mono_max"] - a["mono_min"]
                if a["mono_min"] is not None else 0.0)
        means = {k: s[1] / s[0] for k, s in sorted(a["fields"].items())}
        out.append({"name": a["name"], "calls": a["calls"],
                    "total_ms": span * 1e3,
                    "avg_ms": span * 1e3 / a["calls"], "means": means})
    return out


def _pctl(vals: List[float], q: float) -> float:
    import math
    s = sorted(vals)
    idx = min(len(s) - 1,
              max(0, int(math.ceil(q / 100.0 * len(s))) - 1))
    return s[idx]


def collect_blame(fmt: str, events: List[dict]) -> Dict[int, dict]:
    """Group serving spans by request: chrome X events carry the
    request index in ``args.request`` (the tracing exporter), spans
    JSONL carries it as ``trace``. Returns
    {request: {"components": {name: ms}, "e2e_ms": float}}."""
    per: Dict[int, dict] = {}
    for e in events:
        if fmt == "chrome":
            if e.get("ph") != "X" or \
                    not isinstance(e.get("args"), dict) or \
                    "request" not in e["args"]:
                continue
            rid = e["args"]["request"]
            name = e.get("name", "?")
            dur = float(e.get("dur", 0.0)) / 1e3        # us -> ms
        else:
            if "span" not in e or "trace" not in e:
                continue
            rid = e["trace"]
            name = e["span"]
            dur = float(e.get("dur_ms",
                              (e.get("t1", 0.0) - e.get("t0", 0.0))
                              * 1e3))
        r = per.setdefault(rid, {"components": {}, "e2e_ms": 0.0})
        r["components"][name] = r["components"].get(name, 0.0) + dur
        r["e2e_ms"] += dur
    return per


def print_blame(per: Dict[int, dict], path: str) -> int:
    if not per:
        print(f"{path}: no per-request serving spans "
              "(need tracing chrome-trace X events with args.request, "
              "or export_spans_jsonl lines)")
        return 1
    rows = list(per.values())
    e2es = [r["e2e_ms"] for r in rows]
    p95 = _pctl(e2es, 95)
    tail = [r for r in rows if r["e2e_ms"] >= p95]
    names = sorted({n for r in rows for n in r["components"]})
    total_e2e = sum(e2es)
    name_w = max([12] + [len(n) for n in names])
    print(f"{len(rows)} requests, E2E p95 {p95:.3f} ms")
    print(f"{'Component':{name_w}s}  {'Total(ms)':>12s}  {'Share':>7s}  "
          f"{'p95(ms)':>10s}  {'TailMean(ms)':>12s}")
    tail_means = {}
    for name in names:
        vals = [r["components"].get(name, 0.0) for r in rows]
        tot = sum(vals)
        tmean = sum(r["components"].get(name, 0.0)
                    for r in tail) / len(tail)
        tail_means[name] = tmean
        share = tot / total_e2e if total_e2e else 0.0
        print(f"{name:{name_w}s}  {tot:12.3f}  {share:7.1%}  "
              f"{_pctl(vals, 95):10.3f}  {tmean:12.3f}")
    dominant = max(names, key=lambda n: tail_means[n])
    print(f"tail blame: {dominant} dominates the E2E p95 tail")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="top-N summary of a chrome trace or JSONL run log")
    ap.add_argument("path", help="chrome-trace JSON or runlog .jsonl")
    ap.add_argument("--top", type=int, default=15,
                    help="rows to print (default 15)")
    ap.add_argument("--sort", choices=("total", "calls", "ave"),
                    default="total", help="sort key (default total ms)")
    ap.add_argument("--blame", action="store_true",
                    help="per-request latency-component blame table "
                         "(serving tracing exports only)")
    args = ap.parse_args(argv)

    fmt, events = load_events(args.path)
    if args.blame:
        return print_blame(collect_blame(fmt, events), args.path)
    rows = (summarize_chrome(events) if fmt == "chrome"
            else summarize_runlog(events))
    if not rows:
        print(f"{args.path}: no events")
        return 0
    key = {"total": "total_ms", "ave": "avg_ms", "calls": "calls"}[args.sort]
    rows.sort(key=lambda a: -a[key])
    rows = rows[:args.top]

    name_w = max(len(r["name"]) for r in rows)
    span_h = "Span(ms)" if fmt == "runlog" else "Total(ms)"
    print(f"{'Event':{name_w}s}  {'Calls':>7s}  {span_h:>10s}  "
          f"{'Avg(ms)':>10s}")
    for r in rows:
        line = (f"{r['name']:{name_w}s}  {r['calls']:7d}  "
                f"{r['total_ms']:10.3f}  {r['avg_ms']:10.3f}")
        means = r.get("means")
        if means:
            extras = ", ".join(f"{k}={v:.4g}" for k, v in means.items())
            line += f"  [{extras}]"
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
