#!/usr/bin/env python3
"""Where a serving cell's set-up goes: the harness's own ``build_engine``
and ``warm`` with a clock around each part, and JAX's own events beside
them: for every program compiled, whether the persistent compile cache
served it, and the seconds of tracing, lowering and backend compilation.

    python3 perfbench/study/setup_anatomy.py --workload mellum_code_16k \
        --seed 2147484611 --out chiprun_out/p31/setup.jsonl

Run it twice in one call: the second run shows what the cache serves. What
``setup_s`` has beyond these parts is the pre-roll (the window opens at the
traffic's ``preroll_completions``-th completion), the interpreter's and
JAX's start (``imports_s`` here) and the process's own.
"""

import argparse
import json
import logging
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mellum_code_16k")
    ap.add_argument("--seed", type=int, default=2147484611)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the cell's toy twin on the CPU; never a result")
    args = ap.parse_args(argv)
    import jax
    from jax import monitoring
    from paddle_tpu.utils import chip
    from perfbench import run as harness, serve
    imports_s = time.time() - T0
    events, durations = {}, {}
    monitoring.register_event_listener(
        lambda name, **kw: events.__setitem__(name, events.get(name, 0) + 1))
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: durations.__setitem__(
            name, durations.get(name, 0.0) + secs))
    # which program a hit or a miss was: the compiler's own log lines
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            low = msg.lower()
            if "cache miss" in low or "cache hit" in low:
                lines.append(("miss " if "miss" in low else "hit ")
                             + msg.split("'")[1])
            elif msg.startswith("Finished"):
                secs = msg.rsplit(" in ", 1)[-1].split(" ")[0]
                try:
                    if float(secs) >= 1.0:
                        lines.append(msg[:120])
                except ValueError:
                    pass
    for name in ("jax._src.compiler", "jax._src.compilation_cache",
                 "jax._src.dispatch", "jax._src.interpreters.pxla"):
        log = logging.getLogger(name)
        log.setLevel(logging.DEBUG)
        log.addHandler(Keep())
        log.propagate = False
    cache = chip.enable_compile_cache()
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    if args.rehearsal:
        cfg = harness.load_json(ROOT, "perfbench", "rehearsal",
                                "mellum-tiny.json")
        traffic = harness.load_json(ROOT, "perfbench", "rehearsal",
                                    cell["traffic"] + ".json")
    else:
        conf = next(c for c in bench["configs"]
                    if c["name"] == cell["config"])
        cfg = json.load(open(os.path.join(ROOT, conf["file"])))
        traffic = harness.load_json(ROOT, "perfbench", "traffic",
                                    cell["traffic"] + ".json")
    parts = {"imports_s": imports_s}
    before = chip.cache_entries(cache)

    def clock(name, fn):
        t = time.time()
        out = fn()
        parts[name] = time.time() - t
        return out

    model, engine = clock("build_engine_s",
                          lambda: serve.build_engine(cfg, args.seed))
    jax.block_until_ready([p.value for _, p in model.named_parameters()])
    parts["build_engine_s"] = time.time() - T0 - imports_s
    # serve.warm, a bucket at a time
    import numpy as np
    from perfbench import traffic as T
    rng = np.random.default_rng(0)
    longest = {}
    for plen, _ in T.multiset(traffic):
        b = T.bucket_for(plen, engine.buckets)
        longest[b] = max(longest.get(b, 0), plen)
    for bucket in sorted(longest):
        def one(bucket=bucket):
            for _ in range(2):
                engine.submit(rng.integers(
                    1, int(cfg["vocab_size"]),
                    size=longest[bucket]).tolist(), max_new_tokens=3)
            engine.run_until_idle()
        clock(f"warm_bucket_{bucket}_s", one)
    rec = {"tag": "setup_anatomy", "workload": args.workload,
           "seed": args.seed, "device": jax.devices()[0].device_kind,
           "cache_dir": cache,
           "cache_dir_from_env": bool(
               os.environ.get("JAX_COMPILATION_CACHE_DIR")),
           "cache_entries_before": before,
           "cache_entries_after": chip.cache_entries(cache),
           "parts": parts, "total_s": time.time() - T0,
           "events": {k: v for k, v in events.items() if "cache" in k
                      or "compile" in k},
           "durations_s": {k: round(v, 3) for k, v in durations.items()},
           "cache_lines": lines[:200]}
    print(json.dumps(rec), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
