#!/usr/bin/env python3
"""The expert layer's counters over some steps of a training cell, read by
hand: the training runner has no counter channel yet (PERF.md, Open
questions), so no cell metric reads ``model.moe_stats()`` / ``STAT_moe_*``.

    python3 perfbench/study/moe_counters.py --workload laguna_pretrain_8k \
        --seed 2147484301 --steps 20 --out chiprun_out/p27/counters.jsonl

Builds the cell as the runner does (``train.build``, ``train.batch_maker``),
runs ``--steps`` steps on new seeded batches and reads the counters after
every one: per sparse layer the (token, held expert) pairs, the largest
expert load over the mean, the dropped pairs (must be 0) and whether the
fast buffer held the step; and a ``trajectory`` of a dozen steps (the
pairs a layer, the step's wall time, the loss), because the load moves as
the router learns. One record is appended to ``--out``.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="laguna_pretrain_8k")
    ap.add_argument("--seed", type=int, default=2147484301)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the toy twin on the CPU; never a result")
    args = ap.parse_args(argv)
    import jax
    from paddle_tpu import monitor
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, train
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        raise SystemExit("moe_counters needs the TPU (or --rehearsal)")
    chip.enable_compile_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = harness.load_json(ROOT, entry["file"])
    folder = "traffic"
    if args.rehearsal:
        cfg = harness.load_json(
            ROOT, "perfbench", "rehearsal",
            families.name_of(cfg) + "-tiny.json")
        folder = "rehearsal"
    job = harness.load_json(ROOT, "perfbench", folder,
                            cell["traffic"] + ".json")
    model, make_step, mesh = train.build(cfg, job, args.seed)
    step = make_step()
    make, _ = train.batch_maker(args.seed, int(job["batch_per_chip"]),
                                int(job["seq"]), int(cfg["vocab_size"]), mesh)
    rows, losses, walls = [], [], []
    for i in range(args.steps):
        t = time.perf_counter()
        losses.append(train.fetch(step(*make(i))))
        walls.append(time.perf_counter() - t)
        rows.append(model.moe_stats())
    layers = sorted(rows[0])
    every = max(1, args.steps // 12)
    trajectory = [{"step": i, "step_ms": round(1e3 * walls[i], 1),
                   "loss": losses[i],
                   "assignments": [rows[i][layer]["assignments"]
                                   for layer in layers]}
                  for i in range(0, args.steps, every)]

    def over(key, fn):
        return {str(layer): fn([r[layer][key] for r in rows])
                for layer in layers}
    rec = {"tag": "moe_counters", "workload": args.workload,
           "seed": args.seed, "steps": args.steps,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "loss_first": losses[0], "loss_last": losses[-1],
           "assignments_min": over("assignments", min),
           "assignments_max": over("assignments", max),
           "max_load_over_mean_max": over("max_load_over_mean", max),
           "dropped_pairs_total": sum(r[layer]["dropped_pairs"]
                                      for r in rows for layer in layers),
           "steps_off_the_fast_path": sum(not r[layer]["fast_path"]
                                          for r in rows for layer in layers),
           "trajectory": trajectory,
           "monitor": monitor.stats_with_prefix("STAT_moe_")}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)
    return 0 if rec["dropped_pairs_total"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
