#!/usr/bin/env python3
"""Run one cell several times, one process each, and keep every run's line.

    python3 perfbench/study/measure.py --workload chat_steady \
        --seeds 11,12,13 --seconds 45 --tag setA --out chiprun_out/runs.jsonl

This parent never imports jax (a chip belongs to one process at a time);
each run is ``perfbench/run.py`` as a child, as the driver starts it. One
JSON record per run is appended to ``--out``: tag, workload, seed, seconds,
trace, exit code, wall seconds and the run's last line. At the end the
spread of each metric over the runs of this call is printed: the distance
between the first and third quartile (``statistics.quantiles(v, n=4)``) as
a share of the median, which is what a bound is set from.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def spread(values):
    """(median, IQR / median) of a list with at least two values."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1500.0)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    values = {}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cmd = [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        t = time.time()
        try:
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True, timeout=args.timeout)
            rc, out, err = proc.returncode, proc.stdout, proc.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, e.stdout or "", e.stderr or ""
            out = out if isinstance(out, str) else out.decode()
            err = err if isinstance(err, str) else err.decode()
        wall = time.time() - t
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        rec = {"tag": args.tag, "workload": args.workload, "seed": seed,
               "run": n, "seconds": args.seconds, "trace": args.trace,
               "rc": rc, "wall_s": round(wall, 1), "line": line}
        if rc != 0 or line is None:
            rec["stderr_tail"] = err[-3000:]
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if line is None:
            print(f"[{args.workload} seed {seed}] rc={rc} no result\n"
                  + err[-3000:], flush=True)
            return 1            # a cell that fails once fails again
        shown = {k: round(v["value"], 4) for k, v in line["metrics"].items()}
        print(f"[{args.workload} seed {seed} trace {args.trace}] rc={rc} "
              f"wall {wall:.0f}s correct={line['correct']} "
              f"attempted={line['attempted']} failed={line['failed']} "
              f"{shown} notes={json.dumps(line.get('notes'))[:600]}",
              flush=True)
        for k, v in line["metrics"].items():
            values.setdefault(k, []).append(v["value"])
    for k, v in values.items():
        if len(v) >= 2:
            med, sp = spread(v)
            print(f"  {args.workload} {k}: n={len(v)} median {med:.6g} "
                  f"spread(IQR/median) {100 * sp:.3f}%  min {min(v):.6g} "
                  f"max {max(v):.6g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
