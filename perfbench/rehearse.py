#!/usr/bin/env python3
"""Rehearse the harness on the CPU at toy size: the control flow, the
lookup of files by name, the shape of the last line. Not a measurement.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py --workload chat_steady

Each cell of BENCHMARK.json runs as its twin: the same cell name (so the
same lists of metrics), the toy configuration of the cell's family
(``rehearsal/<family>-tiny.json``) and the traffic file
``rehearsal/<traffic>.json``. A four-chip cell gets four
virtual CPU devices. The script prints the device it ran on, and on any
device that is not a TPU every value of the last line is ``null`` — a
number from a CPU run is never written under the name of a device metric.
What a CPU run can say (counts, ``correct``) stays. Exit code 0 means the
harness ran to its end, nothing more.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COUNTS_AND_CORRECTNESS = (
    "failures", "token_gaps", "completed_in_window", "tokens_counted",
    "max_logit_deficit", "checked_requests", "leaked_kv_blocks",
    "loss_first", "loss_last", "check_loss_program", "check_loss_reference",
    "check_loss_diff", "steps_in_window", "wrong_length_requests")


def run_twin(bench: dict, cell: dict, args) -> dict:
    """One run of ``cell``'s twin -> the result line, values and all: the
    same cell name, the family's toy configuration, the toy traffic."""
    from perfbench import families, run as harness
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    family = families.name_of(harness.load_json(ROOT, entry["file"]))
    bench["configs"] = [{"name": cell["config"],
                         "file": f"perfbench/rehearsal/{family}-tiny.json"}]
    return harness.run_cell(bench, args, rehearsal=True,
                            traffic_dir="rehearsal")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import run as harness
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    if int(cell["chips"]) > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    line = run_twin(bench, cell, args)
    dev = line["device"]
    print(f"rehearsal of {args.workload} ran on {dev['count']} x "
          f"{dev['platform']} ({dev['kind']})", flush=True)
    if dev["platform"] != "tpu":
        # computed, so the code path ran; not shown, so it cannot be quoted
        for m in line["metrics"].values():
            m["value"] = None
        for key in ("busy_s", "window_s", "memory_peak_bytes"):
            if key in dev:
                dev[key] = None
        line.pop("breakdown", None)
        # of the notes, only counts and correctness: no time from a CPU
        line["notes"] = {k: v for k, v in line["notes"].items()
                         if k in COUNTS_AND_CORRECTNESS}
        line["rehearsal"] = ("values withheld: not a TPU run; metric names "
                             "and units are the cell's own")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
