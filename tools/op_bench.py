#!/usr/bin/env python
"""Per-op microbenchmark CLI — the op_tester harness.

Analog of paddle/fluid/operators/benchmark/op_tester.cc (config-driven
single-op benchmark). Usage:

    python tools/op_bench.py --op matmul_v2 \
        --input 'X:4096x4096:float32' --input 'Y:4096x4096:float32' \
        --attr transpose_y=false --repeat 50

Runs the registered lowering under jit on the default backend,
synchronizing by fetch, and prints one JSON line
with mean/min step time and achieved GFLOP/s when --flops is given.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _parse_input(spec: str):
    name, shape_s, dtype = (spec.split(":") + ["float32"])[:3]
    shape = tuple(int(d) for d in shape_s.split("x"))
    return name, shape, dtype


def _parse_attr(spec: str):
    k, _, v = spec.partition("=")
    try:
        return k, json.loads(v)  # numbers, bools, lists, dicts
    except (json.JSONDecodeError, ValueError):
        return k, v


def main(argv=None):
    p = argparse.ArgumentParser("op_bench")
    p.add_argument("--op", required=True)
    p.add_argument("--input", action="append", default=[],
                   help="slot:shape:dtype, e.g. X:128x1024:float32 "
                        "(slot[i] for list slots: X0,X1 -> slot X)")
    p.add_argument("--attr", action="append", default=[])
    p.add_argument("--repeat", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--flops", type=float, default=0.0,
                   help="analytic FLOPs per call (for GFLOP/s)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import registry as reg

    rng = np.random.RandomState(0)
    ins = {}
    for spec in args.input:
        name, shape, dtype = _parse_input(spec)
        slot = name.rstrip("0123456789") or name
        arr = (rng.randint(0, 1000, shape).astype(dtype)
               if np.issubdtype(np.dtype(dtype), np.integer)
               else rng.randn(*shape).astype(dtype))
        ins.setdefault(slot, []).append(jnp.asarray(arr))
    attrs = dict(_parse_attr(a) for a in args.attr)

    def run(arrs):
        ctx = reg.LoweringContext(rng=jax.random.PRNGKey(0))
        outs = reg.execute(ctx, args.op, arrs, attrs)
        return [v for vals in outs.values() for v in vals
                if hasattr(v, "dtype")]

    fn = jax.jit(run)
    for _ in range(args.warmup):
        out = fn(ins)
        np.asarray(out[0])  # fetch-sync
    times = []
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        out = fn(ins)
        np.asarray(out[0])
        times.append(time.perf_counter() - t0)
    mean_s, min_s = float(np.mean(times)), float(np.min(times))
    result = {
        "op": args.op,
        "mean_ms": round(mean_s * 1e3, 4),
        "min_ms": round(min_s * 1e3, 4),
        "repeat": args.repeat,
        "backend": jax.default_backend(),
    }
    if args.flops:
        result["gflops"] = round(args.flops / min_s / 1e9, 6)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
