#!/usr/bin/env python3
"""What ``correct`` reads when the KV pool is not the cell's: the serving
check of ``serve.check`` (same reference, same statistic, same control) on
one engine per ``--kv`` value over the same weights and the same prompts.

    python3 perfbench/study/pool_probe.py --workload chat_steady \
        --kv f32,bf16 --seed 7 --out chiprun_out/pool_probe.jsonl

Not a cell and not a timing: it answers whether ``LOGIT_TOLERANCE`` tells a
bf16 pool (ROADMAP S3) from the float32 one. Needs the chip; one process.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kv", required=True, help="comma-separated kv_dtype")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prompts", default="100:96,120:96,200:96,250:96",
                    help="prompt:answer lengths (default: buckets 128, 256)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="off the TPU, at the rehearsal's toy size")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from perfbench import run as harness, serve, traffic as T
    if jax.devices()[0].platform != "tpu" and not args.rehearsal:
        raise SystemExit("the probe is a measurement: it needs the TPU")
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.utils import chip
    chip.enable_compile_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_json(ROOT, "perfbench/rehearsal/gpt2-tiny.json"
                            if args.rehearsal else next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    model, engine = serve.build_engine(cfg, args.seed)
    del engine
    e = cfg["engine"]
    vocab = int(cfg["vocab_size"])
    rng = np.random.default_rng([args.seed, 5])
    prompts = [(tuple(int(t) for t in rng.integers(1, vocab, size=p)), a)
               for p, a in (map(int, pa.split(":"))
                            for pa in args.prompts.split(","))]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for kv in args.kv.split(","):
        engine = ServingEngine(
            model, max_slots=e["max_slots"], max_len=e["max_len"],
            buckets=e["buckets"], block_size=e["block_size"],
            num_blocks=e["num_blocks"], prefix_cache=e["prefix_cache"],
            max_queue=e["max_queue"], eos_token_id=None, kv_dtype=kv)
        fol, failures = serve.Follower(), []
        for i, (prompt, answer) in enumerate(prompts):
            serve._submit(engine, fol, T.Arrival(i, 0.0, prompt, answer),
                          True, failures)
        while fol.live:
            engine.step()
            fol.after_step(time.perf_counter())
        notes = serve.check(model, engine, cfg, fol, args.seed,
                            float("-inf"), float("inf"))
        rec = {"workload": args.workload, "seed": args.seed, "kv_dtype": kv,
               "requests": len(prompts), "failures": failures,
               "device": jax.devices()[0].device_kind, **notes}
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
