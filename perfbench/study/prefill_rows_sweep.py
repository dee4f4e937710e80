#!/usr/bin/env python3
"""What a prefill dispatch costs by its rows and its bucket (PR 32): the
sweep that chose GPT's ``tokens_a_dispatch``.

    python3 perfbench/study/prefill_rows_sweep.py --seed 2147485001 \
        --out chiprun_out/p32/sweep.jsonl

One build of ``docs_offline``'s engine (``serve.build_engine``: the cell's
weights, pool, buckets). For every bucket and every count of rows the
engine's own prefill entry is compiled at ``[rows, bucket]`` (the seam's
budget set to ``rows x bucket``, nothing else touched) and timed from
building its inputs to its first tokens committed, which is what the span
``serving.prefill_step`` covers: prompts of the bucket's length through
``engine.submit`` / ``engine.step``, ``reps`` dispatches after one that
compiles, the median kept. A dispatch costs what its shape costs whatever
share of its rows is live (padding rows run the same products and write
block 0), so ``ms_a_dispatch`` at ``rows`` is what ONE live prompt pays
where the entry has that many rows, and ``ms_a_row`` (over the rows) what
each pays when all are live.

The lines of PR 32's call 1 in ``runs_pr32.jsonl`` carry ``head``: they were
taken on a tree in which GPT's call also took ``last=`` (the head on each
prompt's last row, ``ServedModel.head_on_last_row``), ``last`` with it and
``full`` without; the step bought 0.4-0.6 ms of a one-row dispatch and was
left out (PERF.md section 6, PR 32), so this tree gives the ``full`` lines.
"""

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="docs_offline")
    ap.add_argument("--seed", type=int, default=2147485001)
    ap.add_argument("--buckets", default="64,128,256,512,768,1024")
    ap.add_argument("--rows", default="1,2,4,8")
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the toy twin on the CPU; never a result")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import run as harness, serve
    chip.enable_compile_cache()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    if args.rehearsal:
        cfg = harness.load_json(ROOT, "perfbench", "rehearsal",
                                "gpt2-tiny.json")
    else:
        conf = next(c for c in bench["configs"]
                    if c["name"] == cell["config"])
        cfg = harness.load_json(ROOT, conf["file"])
    _, engine = serve.build_engine(cfg, args.seed)
    spec = engine.spec
    rng = np.random.default_rng(args.seed)
    vocab = int(cfg["vocab_size"])
    times = []
    inner = engine._prefill_group

    def timed(bucket, group):
        t = time.perf_counter()
        n = inner(bucket, group)
        times.append((bucket, len(group), (time.perf_counter() - t) * 1e3))
        return n

    engine._prefill_group = timed
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    buckets = [int(b) for b in args.buckets.split(",")]
    for bucket in buckets:
        if bucket not in engine.buckets:
            raise SystemExit(f"{bucket} is not a bucket of the engine "
                             f"({engine.buckets})")
        below = max([b for b in engine.buckets if b < bucket], default=0)
        plen = min(bucket, engine.max_len - 1)
        assert plen > below
        per_prompt = -(-(plen + 1) // engine.cache.block_size)
        for rows in (int(r) for r in args.rows.split(",")):
            if rows > engine.max_slots:
                continue
            spec.tokens_a_dispatch = rows * bucket
            assert spec.prefill_rows(bucket, engine.max_slots) == rows
            # another count of rows is another entry of the same bucket
            engine._prefill_fns.pop(bucket, None)
            live = max(1, min(rows, engine.cache.blocks_free // per_prompt))
            del times[:]
            for _ in range(args.reps + 1):
                for _ in range(live):
                    engine.submit(rng.integers(1, vocab, size=plen).tolist(),
                                  max_new_tokens=1)
                engine.run_until_idle()
                engine.cache.flush_prefix_cache()
            ms = [t for b, _, t in times if b == bucket][1:]
            got = {n for _, n, _ in times}
            rec = {"tag": "prefill_rows_sweep",
                   "head": "last" if spec.head_on_last_row else "full",
                   "seed": args.seed, "bucket": bucket, "rows": rows,
                   "live": live, "live_seen": sorted(got),
                   "device": jax.devices()[0].device_kind,
                   "dispatches": len(ms),
                   "ms_a_dispatch": statistics.median(ms),
                   "ms_min": min(ms), "ms_max": max(ms),
                   "ms_a_row": statistics.median(ms) / rows,
                   "tokens": rows * bucket,
                   "us_a_token": 1e3 * statistics.median(ms)
                   / (rows * bucket)}
            print(json.dumps(rec), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
