#!/usr/bin/env python3
"""The sweep that fixes ``rate_per_s`` of an open-loop traffic file: one
engine, built once, offered the file's traffic at each rate in turn
(pre-roll and window as in a run), and for each rate the share of requests
that found every slot busy when they were due.

    python3 perfbench/study/sweep_rate.py --workload chat_steady \
        --rates 0.5,0.6,0.7,0.8,0.9,1.0,1.2 --seconds 45 --seed 5 \
        --out chiprun_out/rate_sweep.jsonl

The rate to write into the file is the highest swept rate at which that
share is at most one in twenty. Needs the chip; one process.
"""

import argparse
import json
import os
import statistics
import sys
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    from perfbench import readers, run as harness, serve
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the sweep is a measurement: it needs the TPU")
    from paddle_tpu.utils import chip
    chip.enable_compile_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = harness.load_json(ROOT, "perfbench", "traffic",
                                cell["traffic"] + ".json")
    model, engine = serve.build_engine(cfg, args.seed)
    serve.warm(engine, traffic, int(cfg["vocab_size"]))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for rate in (float(r) for r in args.rates.split(",")):
        e2e, samples, counts, _, fol, _ = serve.run_open(
            engine, traffic, args.seed, args.seconds, int(cfg["vocab_size"]),
            serve.Tracing(False, harness.OUT, args.seconds),
            rate_override=rate)
        for s in list(fol.live):          # empty the engine for the next rate
            engine.cancel(s.req.id)
        engine.run_until_idle()
        engine.cache.flush_prefix_cache()
        ttft = samples["ttft_s"]
        rec = {"workload": args.workload, "seed": args.seed,
               "seconds": args.seconds, "rate_per_s": rate,
               "found_all_slots_busy_share":
                   counts["found_all_slots_busy_share"],
               "attempted": counts["attempted"], "failed": counts["failed"],
               "itl_mean_ms": e2e["itl_mean_ms"],
               "itl_worst5pct_mean_ms": counts["itl_worst5pct_mean_ms"],
               "occupancy_pct": 100 * statistics.fmean(
                   samples["live_slot_share"]),
               "ttft_p50_ms": 1e3 * readers.percentile(ttft, 50),
               "ttft_p90_ms": 1e3 * readers.percentile(ttft, 90),
               "gen_lag_p99_ms": 1e3 * readers.percentile(
                   samples["gen_lag_s"], 99),
               "completed_in_window": counts["completed_in_window"],
               "device": jax.devices()[0].device_kind}
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
