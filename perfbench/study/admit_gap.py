#!/usr/bin/env python3
"""What an admission costs the device, from one traced run (PR 48).

    python3 perfbench/run.py --workload lfm2_agents_3k --seed 1 --trace 1
    python3 perfbench/study/admit_gap.py [--tree <checkout>] [--tag t] \
        [--out chiprun_out/gaps.jsonl]

Reads what the traced run of ``<checkout>/perfbench/run.py`` left under
``<checkout>/perfbench/.out`` and prints one JSON line:

- from the device trace (the traced part of the window): for every
  execution of a prompt program (``jit__prefill``) the device's idle time
  from its end to the start of the program behind it (``gap_after_ms``:
  the gap an admission; a decode step built by the host once the prefill
  was fetched and committed starts a fetch, two commits, a round's
  bookkeeping and a launch later, a step dispatched behind the prefill
  starts at once) and from the end of the program before it to its start
  (``gap_before_ms``), with their counts, means, medians and sums, and the
  same for the decode step's executions for comparison;
- from the program's spans (the whole window): for every prefill flight
  its parts on the host's clock (``serving.prefill`` the dispatch,
  ``serving.prefill.fetch`` the wait, ``serving.prefill.commit``,
  ``serving.prefill_step`` the whole), and how long after the prefill's
  first tokens were fetched the next decode step's inputs were begun
  (``next_step_after_fetch_ms``: negative where the step was dispatched
  before the fetch).

The run is over and its files are read (jax is needed to read the trace
file, not the chip). Works on a tree before PR 48 too (the spans have the
same names; ``serving.prefill_step`` is then the ``with`` block around the
group).
"""

import argparse
import glob
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def summary(values) -> dict:
    if not values:
        return {"n": 0}
    return {"n": len(values), "mean": statistics.fmean(values),
            "p50": statistics.median(values), "sum": sum(values),
            "max": max(values)}


def device_gaps(xplane, trace: dict) -> dict:
    """Idle ms before and after each program execution on the first device
    plane inside the harness's window span, by program."""
    window, _ = xplane._host_spans(trace)
    plane = next((p for p in trace["planes"]
                  if p["name"].startswith("/device:TPU:")
                  and xplane.MODULES_LINE in xplane._lines(p)), None)
    if window is None or plane is None:
        return {}
    lo, hi = window
    mods = sorted((start, start + dur, name.split("(")[0])
                  for name, start, dur
                  in xplane._lines(plane)[xplane.MODULES_LINE]
                  if start >= lo and start + dur <= hi)
    out = {}
    for i, (start, end, name) in enumerate(mods):
        rec = out.setdefault(name, {"ms": [], "before": [], "after": [],
                                    "then": {}})
        rec["ms"].append((end - start) / 1e6)
        if i:
            rec["before"].append(max(0.0, start - mods[i - 1][1]) / 1e6)
        if i + 1 < len(mods):
            rec["after"].append(max(0.0, mods[i + 1][0] - end) / 1e6)
            nxt = mods[i + 1][2]
            rec["then"][nxt] = rec["then"].get(nxt, 0) + 1
    return {name: {"run_ms": summary(rec["ms"]),
                   "gap_before_ms": summary(rec["before"]),
                   "gap_after_ms": summary(rec["after"]),
                   "followed_by": rec["then"]}
            for name, rec in out.items()}


def host_parts(events) -> dict:
    """The prefill flights' parts from the program's spans."""
    by = {}
    for e in events:
        flight = (e.get("args") or {}).get("flight")
        if flight is not None:
            by.setdefault(e["name"], {}).setdefault(flight, e)
    inputs = sorted((f, e["ts"]) for f, e
                    in by.get("serving.decode.inputs", {}).items())
    parts = {name: [] for name in ("serving.prefill",
                                   "serving.prefill.fetch",
                                   "serving.prefill.commit",
                                   "serving.prefill_step")}
    after = []
    for flight, fetch in by.get("serving.prefill.fetch", {}).items():
        for name in parts:
            e = by.get(name, {}).get(flight)
            if e is not None:
                parts[name].append(e["dur"] / 1e3)
        nxt = next((ts for f, ts in inputs if f > flight), None)
        if nxt is not None:
            after.append((nxt - fetch["ts"] - fetch["dur"]) / 1e3)
    out = {name + "_ms": summary(v) for name, v in parts.items()}
    out["next_step_after_fetch_ms"] = summary(after)
    out["next_step_dispatched_before_fetch"] = sum(a < 0 for a in after)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose traced run is read")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", help="append the line to this file too")
    args = ap.parse_args(argv)
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    from perfbench import xplane
    out_dir = os.path.join(tree, "perfbench", ".out")
    rec = {"tool": "admit_gap", "tag": args.tag, "tree": args.tree}
    paths = glob.glob(os.path.join(out_dir, "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if paths:
        rec["device"] = device_gaps(xplane, xplane.load(paths[0]))
    spans = os.path.join(out_dir, "host_spans.json")
    if os.path.exists(spans):
        with open(spans) as f:
            rec["host"] = host_parts(json.load(f)["traceEvents"])
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
