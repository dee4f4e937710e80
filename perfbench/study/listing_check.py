#!/usr/bin/env python3
"""Hold ``measure.py``'s traced records to the listing, and a pair to itself.

    python3 perfbench/study/listing_check.py --pr 57 --call p57c1 \
        --parent _chip_scratch/parent chiprun_out/p57c1/runs.jsonl \
        >> perfbench/study/runs_pr57.jsonl

Needs no chip. Every record is printed again with its ``call`` and, where it
is traced, with ``listed`` and ``read`` (how many per-layer names the cell's
entries list in the record's own tree and how many the line carries),
``not_read`` and ``not_listed`` (the names on one side only: both have to be
empty) and ``busy_shares`` (every counted kernel's seconds over the device's
busy seconds, from ``notes.kernels`` and ``device.busy_s``: what a retired
``_busy_pct`` entry read, still one division away). A record whose tag holds
``_P_`` is the parent's: the change's record of the same cell and seed then
also gets ``only_parent`` (the names the parent's line has more) and
``kept_vs_parent`` (for every name on both lines, change over parent).
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@functools.lru_cache(maxsize=None)
def listed(root: str, cell: str) -> frozenset:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return frozenset(m["name"] for m in bench["per_layer"]
                     if cell in m["workloads"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("runs", help="measure.py's --out file")
    ap.add_argument("--pr", type=int, required=True)
    ap.add_argument("--call", required=True)
    ap.add_argument("--parent", default=None,
                    help="the parent's tree, for records tagged _P_")
    args = ap.parse_args(argv)
    with open(args.runs) as f:
        records = [json.loads(line) for line in f if line.strip()]
    parents = {}
    for rec in records:
        rec = {"pr": args.pr, "call": args.call, **rec}
        line, of_parent = rec["line"], "_P_" in rec["tag"]
        if rec["trace"] and line:
            names = set(line["metrics"])
            want = listed(args.parent if of_parent else ROOT, rec["workload"])
            rec.update(listed=len(want), read=len(names),
                       not_read=sorted(want - names),
                       not_listed=sorted(names - want))
            busy = line["device"]["busy_s"]
            rec["busy_shares"] = {
                k: 100.0 * v["seconds"] / busy
                for k, v in line.get("notes", {}).get("kernels", {}).items()}
            key = (rec["workload"], rec["seed"])
            if of_parent:
                parents[key] = line["metrics"]
            elif key in parents:
                mine, theirs = line["metrics"], parents[key]
                rec["only_parent"] = sorted(set(theirs) - set(mine))
                rec["kept_vs_parent"] = {
                    n: (mine[n]["value"] / theirs[n]["value"]
                        if theirs[n]["value"] else mine[n]["value"])
                    for n in sorted(set(mine) & set(theirs))}
        print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
