#!/usr/bin/env python3
"""How far does ``--seed`` alone spread a closed-loop cell's ``serve_tok_s``?

    python3 perfbench/study/closed_loop_sim.py --traffic keye_longdoc_24k \
        --buckets 8192,12288,16384 --prompt-us-a-row 10 \
        --prompt-ns-a-row2 2.9 --step-ms 10 --seeds 600

No chip and no jax: the harness's closed loop on a clock of its own. The
generator is the benchmark's (``traffic.closed_loop_stream``: the seed
orders each repetition of the multiset), the loop is ``serve.run_closed``'s
(the queue kept ``queue_depth_slots x slots`` deep, a step admits into
every free slot, one prompt a dispatch, then decodes every live row; the
window opens at the ``preroll_completions``-th completion; tokens are
credited as ``Follower.after_step`` credits them and counted from the
window's first completion to its last), and time is a model of the engine:
a prompt of bucket ``b`` costs ``a x b + c x b^2`` and a decode step a
constant. Prints one JSON line: the median, the standard deviation over the
seeds, the completions, and the spread of sets of six seeds as the driver's
check reckons it (the middle half by ``statistics.quantiles``, the run
farthest from the median left out where that narrows it) at the median and
the 90th centile with the share of sets under ``--limit``.

PR 46 found with it why ``keye_longdoc_24k`` failed its admission: at 5.7k
tokens/s a window admits one whole repetition of 16 prompts and the first
four or five of the next, which the seed draws. With three parameters
guessed from the trace (10 us a row, 3.5 ns a row squared, 17.6 ms a step)
it gave the twelve measured seeds one by one (correlation 0.93; PERF.md
section 6). ``--measured runs.jsonl`` prints that comparison for the
untraced runs of the cell in a ``measure.py`` record file.
"""

import argparse
import json
import os
import statistics
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import traffic as T                             # noqa: E402


def lengths(traffic: dict, seed: int):
    """The (prompt, answer) lengths ``closed_loop_stream`` yields for
    ``seed``, without drawing token ids."""
    pairs = T.multiset(traffic)
    order = T._rng(seed, T._TAG_ORDER)
    while True:
        for j in order.permutation(len(pairs)):
            yield pairs[j]


def run(traffic, seed, buckets, prompt_s, step_s, seconds=45.0, slots=8):
    """-> (serve_tok_s, completions in the window) of one seed."""
    stream = lengths(traffic, seed)
    depth = int(traffic["queue_depth_slots"]) * slots
    need = int(traffic["preroll_completions"])
    queue, live, done, credits = [], [], [], []
    now, opened = 0.0, None
    while opened is None or now - opened < seconds:
        while len(queue) < depth:
            queue.append(next(stream))
        credit = 0
        while queue and len(live) < slots:
            prompt, answer = queue.pop(0)
            now += prompt_s(T.bucket_for(prompt, buckets))
            credit += prompt + 1            # the prompt and its first token
            live.append(answer - 1)
        if live:
            now += step_s
            credit += len(live)
            live = [left - 1 for left in live]
        done += [now] * sum(left <= 0 for left in live)
        live = [left for left in live if left > 0]
        if opened is None:
            if len(done) >= need:
                opened = now
            continue
        credits.append((now, credit))
    inside = [t for t in done if opened < t <= opened + seconds]
    first, last = inside[0], inside[-1]
    tokens = sum(n for t, n in credits if first < t <= last)
    return tokens / (last - first), len(inside)


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def check_spread(values):
    """A set's spread as the driver's check takes it: the run farthest
    from the median left out where that narrows it."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(spread(values), spread(rest))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True,
                    help="a closed-loop traffic file's name or path")
    ap.add_argument("--buckets", required=True, help="comma-separated")
    ap.add_argument("--prompt-us-a-row", type=float, required=True)
    ap.add_argument("--prompt-ns-a-row2", type=float, required=True)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, default=600)
    ap.add_argument("--limit", type=float, default=0.05,
                    help="half the metric's bound")
    ap.add_argument("--measured", help="a measure.py record file: print "
                    "its untraced runs of the cell beside the model's")
    args = ap.parse_args(argv)
    path = args.traffic if os.path.exists(args.traffic) else os.path.join(
        os.path.dirname(HERE), "traffic", args.traffic + ".json")
    traffic = T.load(path)
    buckets = [int(b) for b in args.buckets.split(",")]
    a, c = args.prompt_us_a_row * 1e-6, args.prompt_ns_a_row2 * 1e-9

    def one(seed):
        return run(traffic, seed, buckets, lambda b: a * b + c * b * b,
                   args.step_ms * 1e-3, args.seconds, args.slots)
    if args.measured:
        cell = os.path.splitext(os.path.basename(path))[0]
        got, model = [], []
        for line in open(args.measured):
            rec = json.loads(line)
            if rec.get("workload") != cell or rec.get("trace") or \
                    rec.get("rc") != 0:
                continue
            value = rec["line"]["metrics"]["serve_tok_s"]["value"]
            sim, n = one(rec["seed"])
            got.append(value)
            model.append(sim)
            print(json.dumps({
                "seed": rec["seed"], "measured": value, "model": sim,
                "completed": rec["line"]["notes"]["completed_in_window"],
                "model_completed": n}))
        print(json.dumps({"runs": len(got), "correlation": float(
            np.corrcoef(got, model)[0, 1]) if len(got) > 2 else None}))
        return 0
    seeds = np.random.default_rng(1).integers(1, 2**31, size=args.seeds)
    runs = [one(int(s)) for s in seeds]
    values = [v for v, _ in runs]
    sets = sorted(check_spread(values[i:i + 6])
                  for i in range(0, len(values) - 5, 6))
    print(json.dumps({
        "tool": "closed_loop_sim", "traffic": os.path.basename(path),
        "buckets": buckets, "prompt_us_a_row": args.prompt_us_a_row,
        "prompt_ns_a_row2": args.prompt_ns_a_row2, "step_ms": args.step_ms,
        "seeds": len(values), "median": statistics.median(values),
        "sd_share": statistics.pstdev(values) / statistics.mean(values),
        "completions": [min(n for _, n in runs), max(n for _, n in runs)],
        "six_seed_spread_p50": sets[len(sets) // 2],
        "six_seed_spread_p90": sets[int(0.9 * len(sets))],
        "sets_under_limit": sum(s < args.limit for s in sets) / len(sets)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
