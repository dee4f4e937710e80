#!/usr/bin/env python3
"""One untraced run of a serving cell, printing what the result line does
not carry: the window's share of decode dispatches that were issued ahead
of the fetch of the step before them, and their rows (PR 34).

    python3 perfbench/study/ahead_counters.py --workload decode_heavy \
        --seed 3400000031 --out chiprun_out/ahead_pr34.jsonl

The run is ``perfbench/run.py``'s (``serve.run`` with the cell's own
configuration and traffic); the counters are the window's differences of
``engine.stats()`` (``perfbench/serve.py`` ``program_counters``). On a tree
whose engine has no such counters the shares are null.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from paddle_tpu.utils import chip
    from perfbench import run as R, serve
    chip.enable_compile_cache()
    bench = R.load_json(ROOT, "BENCHMARK.json")
    cell = R.find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = R.load_json(ROOT, entry["file"])
    traffic = R.load_json(R.HERE, "traffic", cell["traffic"] + ".json")
    os.makedirs(R.OUT, exist_ok=True)
    e2e, obs, counts = serve.run(cell, cfg, traffic, args.seed, args.seconds,
                                 False, R.OUT, R.T_START)
    c = obs["counters"]
    dispatches = c.get("engine.sampler_dispatches")
    ahead = c.get("engine.ahead_dispatches")
    completed = c.get("engine.completed")
    dropped = c.get("engine.ahead_rows_dropped")
    rec = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "end_to_end": e2e,
        "correct": bool(counts.get("correct")),
        "decode_dispatches": dispatches, "ahead_dispatches": ahead,
        "ahead_share_pct": (100.0 * ahead / dispatches
                            if ahead is not None and dispatches else None),
        "ahead_rows_committed": c.get("engine.ahead_rows_committed"),
        "ahead_rows_dropped": dropped, "completed": completed,
        "dropped_rows_a_completion": (dropped / completed
                                      if dropped is not None and completed
                                      else None),
        "inputs_resident": c.get("engine.inputs_resident"),
        "compiles_in_window": c.get("compiles_in_window"),
    }
    line = json.dumps(rec)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
