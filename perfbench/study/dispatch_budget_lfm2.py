#!/usr/bin/env python3
"""The sweep that fixes ``tokens_a_dispatch`` of a served configuration: the
cell's own run (``serve.run``: build, warm, pre-roll, window, check) with the
configuration's budget replaced by each value in turn, one process a value.

    python3 perfbench/study/dispatch_budget_lfm2.py --workload lfm2_agents_3k \
        --budgets 512,1024,2048 --seconds 30 --seed 4200000011 \
        --out chiprun_out/p42c1/budget.jsonl

A prefill dispatch of a bucket computes ``budget // bucket`` rows (at least
one), whatever it admitted: a large budget repays the read of the weights
when several prompts wait, and computes padding when a closed loop frees
one slot at a time. A line a value: ``serve_tok_s``, ``setup_s``, the
check's notes, the rows a dispatch computed and the share of them that
were prompts'. This parent never imports jax (a chip belongs to one process
at a time).
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def child(args) -> int:
    import time
    t_start = time.perf_counter()
    sys.path.insert(0, ROOT)
    import jax
    from paddle_tpu.utils import chip
    from perfbench import run as harness, serve
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        raise SystemExit("the sweep is a measurement: it needs the TPU")
    chip.enable_compile_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    folder = "rehearsal" if args.allow_cpu else "traffic"
    cfg = harness.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    if args.allow_cpu:
        from perfbench import families
        cfg = harness.load_json(ROOT, "perfbench", "rehearsal",
                                families.name_of(cfg) + "-tiny.json")
    traffic = harness.load_json(ROOT, "perfbench", folder,
                                cell["traffic"] + ".json")
    cfg = dict(cfg, tokens_a_dispatch=int(args.child))
    os.makedirs(harness.OUT, exist_ok=True)
    e2e, obs, counts = serve.run(cell, cfg, traffic, args.seed, args.seconds,
                                 False, harness.OUT, t_start)
    c = obs["counters"]
    rec = {"tool": "dispatch_budget_lfm2.py", "workload": args.workload,
           "tokens_a_dispatch": int(args.child), "seed": args.seed,
           "seconds": args.seconds, **e2e,
           "prefill_rows_computed": c.get("engine.prefill_rows_computed"),
           "prefill_rows_live": c.get("engine.prefill_rows_live"),
           "prefill_tokens_computed":
               c.get("engine.prefill_tokens_computed"),
           "prefill_tokens_live": c.get("engine.prefill_tokens_live"),
           "sampler_dispatches": c.get("engine.sampler_dispatches"),
           "device": jax.devices()[0].device_kind, **counts}
    print(json.dumps(rec), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--budgets", default="512,1024,2048")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=4200000011)
    ap.add_argument("--out")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal on the toy twin; never a result")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return child(args)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for budget in args.budgets.split(","):
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               args.workload, "--seconds", str(args.seconds), "--seed",
               str(args.seed), "--child", budget] \
            + (["--allow-cpu"] if args.allow_cpu else [])
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        lines = proc.stdout.strip().splitlines()
        line = lines[-1] if proc.returncode == 0 and lines else json.dumps(
            {"tool": "dispatch_budget_lfm2.py", "tokens_a_dispatch":
             int(budget), "rc": proc.returncode,
             "stderr": proc.stderr[-1500:]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
