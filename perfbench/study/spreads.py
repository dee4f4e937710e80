#!/usr/bin/env python3
"""Spreads from the kept runs: for each cell, tag (set) and metric the
median and the distance between the quartiles as a share of the median.

    python3 perfbench/study/spreads.py perfbench/study/runs.jsonl [--notes]

``--notes`` adds the numbers a run keeps under ``notes`` (other views of
the same gaps, losses, counts)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from measure import spread  # noqa: E402


def main(argv) -> int:
    path = argv[1]
    with_notes = "--notes" in argv
    groups = {}
    for raw in open(path):
        rec = json.loads(raw)
        line = rec.get("line")
        if not line or rec["rc"] != 0:
            print("skipped:", rec["workload"], rec["tag"], rec["seed"],
                  "rc", rec["rc"])
            continue
        key = (rec["workload"], rec["tag"], rec["seconds"], rec["trace"])
        g = groups.setdefault(key, {})
        for k, v in line["metrics"].items():
            g.setdefault(k, []).append(v["value"])
        if with_notes:
            for k, v in (line.get("notes") or {}).items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    g.setdefault("notes." + k, []).append(v)
    for key, g in groups.items():
        print("%s  set=%s  seconds=%s trace=%s" % key)
        for k, v in g.items():
            if len(v) < 2:
                print(f"   {k}: {v}")
                continue
            med, sp = spread(v)
            print(f"   {k}: n={len(v)} median {med:.6g} spread {100 * sp:.3f}% "
                  f"range {min(v):.6g}..{max(v):.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
