#!/usr/bin/env python3
"""Where ANY cell's set-up goes, from inside: one run of the cell through
the harness's own ``run.run_cell`` at a short ``--seconds``, then the
program's compile account (``observability.compiles()``: tracing, lowering
and backend compilation by ``tracked_jit`` site, programs built, what the
persistent cache did, ``(untracked)`` for what no site owns), its totals,
and the line's ``setup_s`` beside them.

    python3 perfbench/study/program_account.py --workload pretrain_1chip \
        --seed 2147484611 --seconds 10 --tag warm \
        --out chiprun_out/p52/runs.jsonl

The eight serving cells print the totals in their traced line
(``programs_*.setup``); the three training cells have no counter channel,
so this script is how their set-up is read. Run it twice in one call: the
first run of a fresh cache compiles, the second shows what the cache
serves. The account is read where the harness counts its compiles: when
the window opens and when it closes (``at_close`` is what the ``.close``
metrics read), and at the end of the run (the check's program with it).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import run as harness    # noqa: E402  set-up counts from here

import argparse                         # noqa: E402
import json                             # noqa: E402

TIMES = ("total_ms", "trace_ms", "lower_ms", "compile_ms",
         "cache_retrieval_ms")


def account() -> dict:
    from paddle_tpu import observability
    sites = observability.compiles()
    return {"totals": observability.compile_totals(),
            "sites": {name: {k: v for k, v in rec.items()
                             if k in ("count", "total_ms",
                                      *observability.compile_tracker.STAGES)}
                      for name, rec in sites.items()}}


def withhold(*records: dict):
    """A rehearsal's account: the counts stay, no time from a CPU."""
    for rec in records:
        for key in TIMES:
            if key in rec:
                rec[key] = None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147484611)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--tag", default="", help="e.g. first / warm")
    ap.add_argument("--out", default=os.path.join(HERE, "runs_pr52.jsonl"))
    ap.add_argument("--rehearsal", action="store_true",
                    help="the cell's toy twin on the CPU; values withheld")
    args = ap.parse_args(argv)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    if args.rehearsal and int(cell["chips"]) > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    from perfbench import serve
    # the harness counts its compiles as the window opens and closes:
    # the account is read at the same two places
    counted, count = [], serve.compile_count

    def counting():
        counted.append(account())
        return count()
    serve.compile_count = counting
    if args.rehearsal:
        from perfbench import rehearse
        line = rehearse.run_twin(bench, cell, args)
    else:
        line = harness.run_cell(bench, args)
    at_end = account()
    at_open, at_close = (counted + [at_end, at_end])[:2]
    setup = (line["metrics"].get("setup_s") or {}).get("value")
    rec = {"tag": "program_account", "run": args.tag,
           "workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "trace": args.trace,
           "device": line["device"], "correct": line["correct"],
           "setup_s": setup,
           "metrics": {k: v["value"] for k, v in line["metrics"].items()},
           "cache_dir_from_env": bool(
               os.environ.get("JAX_COMPILATION_CACHE_DIR")),
           "at_open": at_open["totals"], "at_close": at_close,
           "at_end": at_end["totals"]}
    if line["device"]["platform"] != "tpu":
        rec["rehearsal"] = "times withheld: not a TPU run"
        rec["setup_s"] = None
        rec["metrics"] = dict.fromkeys(rec["metrics"])
        withhold(rec["at_open"], rec["at_end"], rec["at_close"]["totals"],
                 *rec["at_close"]["sites"].values())
    tot = rec["at_close"]["totals"]
    print(f"{args.workload} [{args.tag}] setup_s {rec['setup_s']}: "
          f"{tot['programs']} programs, {tot['cache_hits']} hits, "
          f"{tot['cache_misses']} misses; trace/lower/compile ms "
          f"{tot['trace_ms']} / {tot['lower_ms']} / {tot['compile_ms']}",
          flush=True)
    for name, site in sorted(rec["at_close"]["sites"].items()):
        print(f"  {name}: {site}", flush=True)
    print(json.dumps(rec), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
