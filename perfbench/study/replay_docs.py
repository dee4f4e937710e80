#!/usr/bin/env python3
"""Replay the closed loop's scheduling on the CPU and count how it batches.

    JAX_PLATFORMS=cpu python3 perfbench/study/replay_docs.py --size 16 \
        --seeds 1,2,3,4,5,6

With ``eos`` off and FIFO admission the sequence of steps of a closed loop
is a function of the seed alone: no clock enters it. So the real engine at
the real geometry (8 slots, 1024 rows, 400 blocks, the cell's buckets) over
a toy model gives, on the CPU, the real COUNTS per seed: prefill dispatches
per bucket, requests that shared a dispatch, decode steps, mean live slots.
Those counts explain why ``serve_tok_s`` spreads over seeds (PERF.md,
Findings): the requests that happen to free their slots in the same step
share one 8-row prefill, and how often that happens is chance.

Time here is MODELLED from three stated costs (seconds per prefill dispatch
of each bucket and per decode step, defaults read off the chip's runs): the
"modelled tokens/s" ranks seeds as the chip does, and is not a measurement.
"""

import argparse
import dataclasses
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", type=int, default=None,
                    help="override the traffic file's multiset size")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--cost", default="768:0.21,1024:0.29,decode:0.0447")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine
    from perfbench import run as harness, serve, traffic as T
    cost = {k: float(v) for k, v in
            (kv.split(":") for kv in args.cost.split(","))}
    cfg = harness.load_json(ROOT, "perfbench", "configs", "cgpt-1p3b.json")
    e = cfg["engine"]
    tr = harness.load_json(ROOT, "perfbench", "traffic", "docs_offline.json")
    if args.size:
        tr["multiset"]["size"] = args.size
    toy = dataclasses.replace(
        GPT_CONFIGS["gpt2-tiny"], max_position_embeddings=e["max_len"],
        num_layers=1, hidden_size=64, num_heads=2, ffn_hidden_size=128)
    model = GPTForCausalLM(toy)
    model.eval()
    depth = int(tr["queue_depth_slots"]) * e["max_slots"]
    rates = []
    for seed in (int(s) for s in args.seeds.split(",")):
        eng = ServingEngine(
            model, max_slots=e["max_slots"], max_len=e["max_len"],
            buckets=e["buckets"], block_size=e["block_size"],
            num_blocks=e["num_blocks"], prefix_cache=e["prefix_cache"],
            max_queue=e["max_queue"], eos_token_id=None)
        stream = T.closed_loop_stream(tr, seed, toy.vocab_size)
        fol, fails = serve.Follower(), []
        now, t0, credits = 0.0, None, []
        dispatches, shared, steps, live = {}, 0, 0, 0
        while t0 is None or now - t0 < args.seconds:
            for _ in range(max(0, depth - fol.queued())):
                serve._submit(eng, fol, next(stream), t0 is not None, fails)
            eng.step()
            new = [s for s in fol.live if s.seen == 0 and s.req.tokens]
            buckets = {T.bucket_for(len(s.arrival.prompt), eng.buckets)
                       for s in new}
            now += sum(cost[str(b)] for b in buckets) + cost["decode"]
            credit = fol.after_step(now)
            if t0 is None:
                if len(fol.completions) >= int(tr["preroll_completions"]):
                    t0 = now
                continue
            for b in buckets:
                dispatches[b] = dispatches.get(b, 0) + 1
            shared += len(new) - len(buckets)
            steps += 1
            live += eng.cache.num_used
            credits.append((now, credit))
        comps = [c for c in fol.completions if t0 < c <= t0 + args.seconds]
        tokens = sum(n for te, n in credits if comps[0] < te <= comps[-1])
        rates.append(tokens / (comps[-1] - comps[0]))
        print(f"seed {seed}: completions {len(comps)} prefill dispatches "
              f"{dict(sorted(dispatches.items()))} shared {shared} decode "
              f"steps {steps} mean live slots {live / steps:.2f} modelled "
              f"tokens/s {rates[-1]:.1f}", flush=True)
    if len(rates) > 1:
        q1, _, q3 = statistics.quantiles(rates, n=4)
        print(f"multiset size {tr['multiset']['size']}: modelled tokens/s "
              f"median {statistics.median(rates):.1f}, quartile distance "
              f"{100 * (q3 - q1) / statistics.median(rates):.2f}% of it, over "
              f"{len(rates)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
