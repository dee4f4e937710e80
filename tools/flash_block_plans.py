#!/usr/bin/env python
"""The three flash kernels alone, at the cells' shapes, under candidate
block plans (PR 59's study; the plan landed at PR 60).

``ops/pallas/flash_attention.block_plan`` decides from a call's shape how
its logits are cut into grid steps and tiles. This script is how its rules
were chosen: for each shape it runs ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` by themselves under each candidate plan (``plan=`` of
the kernels' own entry), chained inside one jitted ``lax.scan`` so that
dispatch does not enter, and reads each kernel's seconds from the DEVICE
trace of the scans (``perfbench/xplane.py``'s reader: the kernel's own
events by its name, so the scan's glue is not in the number). Each line
carries the plan, its ``visited_share`` (live logits over logits computed:
a number of the shape alone), us a call, and us a 512 x 512 tile of live
logits.

    python tools/flash_block_plans.py --out chiprun_out/p59c1/plans.jsonl
    python tools/flash_block_plans.py --shapes gpt --plans auto,512.256.2.2

A plan is ``tile.dq_sub[.dq_tiles[.tiles]]``, the fields of ``BlockPlan``.
The lines of ``perfbench/study/runs_pr59.jsonl`` were written while a plan
could also give the forward and the dk/dv kernel sub-tiles and more steps
of their own (``tile.sub[.tiles]``, each one number or ``fwd-dq-dkv``):
those forms lost there and are gone from the kernels.

``--parent FILE`` also times the kernels of another commit's
``flash_attention.py`` (``git show <commit>:paddle_tpu/ops/pallas/
flash_attention.py > FILE``), and ``--jax-reference`` JAX's own
``pallas.ops.tpu.flash_attention`` forward at GPT's shape, a yardstick.
On the CPU it runs the interpreter at the shapes it is given (use
``--tiny``) and prints no device time: a rehearsal, not a measurement.
"""

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.ops.pallas import flash_attention as _  # noqa: E402,F401
from perfbench import xplane  # noqa: E402

fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]

#: name -> (query heads, KV heads, seq, d, window, backward too): what one
#: chip's call holds in the cell (batch times heads)
SHAPES = {
    "gpt": (128, 128, 1024, 128, 0, True),          # pretrain_1chip, b8 h16
    "laguna_win": (16, 2, 8192, 128, 512, True),    # laguna_pretrain_8k
    "laguna_full": (12, 2, 8192, 128, 0, True),
    "mellum_win": (32, 4, 4096, 128, 1024, False),  # a served prompt
    "qwen3next": (16, 2, 8192, 256, 0, False),
}
TINY = {"gpt": (4, 4, 256, 32, 0, True),
        "laguna_win": (4, 1, 256, 32, 64, True)}


def parse_plan(text):
    """``tile.dq_sub[.dq_tiles[.tiles]]``, or ``auto`` for the shape's
    own."""
    if text == "auto":
        return None
    tile, *rest = map(int, text.split("."))
    return fa.BlockPlan(tile, tile, *rest)


def inputs(shape, seed):
    hq, hkv, s, d, _, _ = shape
    r = np.random.RandomState(seed)

    def a(h):
        return jnp.asarray(r.randn(h, s, d), jnp.bfloat16)
    q, k, v, do = a(hq), a(hkv), a(hkv), a(hq)
    lse = jnp.asarray(r.rand(hq, 1, s) + 5.0, jnp.float32)
    delta = jnp.asarray(r.randn(hq, 1, s), jnp.float32)
    return q, k, v, do, lse, delta


def chained(fn, iters):
    """``fn(q, ...)`` ``iters`` times inside one jitted scan, each call
    reading the one before it through q."""
    def run(q, *rest):
        def step(carry, _):
            out = fn(q + carry.astype(q.dtype), *rest)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            return sum(o.ravel()[0].astype(jnp.float32)
                       for o in outs) * 0, None
        return jax.lax.scan(step, jnp.float32(0), None, length=iters)[0]
    return jax.jit(run)


def kernels_of(module, shape, plan, tag):
    """Kernel stem -> function of the six inputs that runs it alone."""
    _, _, s, d, window, backward = shape
    scale = d ** -0.5
    kw = {} if plan is ... else {"plan": plan}
    tile = plan.block_q if plan is not ... else min(s, 512)

    def fwd(q, k, v, do, lse, delta):
        return module._flash_fwd(q, k, v, True, scale, tile, tile, window,
                                 tag, **kw)[0]
    out = {"flash_fwd": fwd}
    if backward and hasattr(module, "_flash_bwd_dq"):
        for stem in ("flash_bwd_dq", "flash_bwd_dkv"):
            out[stem] = (lambda q, k, v, do, lse, delta, f=getattr(
                module, "_" + stem): f(q, k, v, do, lse, delta, True, scale,
                                       tile, tile, window, tag, **kw))
    elif backward:
        # a commit whose backward is one function: both kernels in one run
        def bwd(q, k, v, do, lse, delta):
            o = (q.astype(jnp.float32) * 0).astype(q.dtype)
            return module._flash_bwd(True, scale, tile, tile,
                                     (q, k, v, o, lse), do, window, tag)
        out["flash_bwd"] = bwd
    return out


def device_kernel_seconds(trace_dir):
    """kernel name -> (seconds, calls) over the trace's device planes."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return {}
    trace = xplane.load(sorted(paths)[-1])
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        for line in plane["lines"]:
            if line["name"] != xplane.OPS_LINE:
                continue
            for name, _, dur in line["events"]:
                if xplane.is_mosaic(name):
                    k = out.setdefault(xplane.kernel_stem(name), [0.0, 0])
                    k[0] += dur / 1e9
                    k[1] += 1
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--plans", default="auto,512.512,512.256,512.128,"
                    "512.256.2,512.256.4")
    ap.add_argument("--parent", default="")
    ap.add_argument("--jax-reference", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=5900000001)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    on_chip = jax.default_backend() == "tpu"
    shapes = TINY if args.tiny else SHAPES
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu.ops.pallas._flash_parent", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    runs = []        # (line, {stem: jitted scan}, inputs, tag)
    for sname in args.shapes.split(","):
        if sname not in shapes:
            continue
        shape = shapes[sname]
        hq, hkv, s, d, window, _ = shape
        data = inputs(shape, args.seed % (2 ** 31))
        cands = [("parent", parent, ...)] if parent else []
        cands += [(p, fa, parse_plan(p)) for p in args.plans.split(",")]
        for n, (label, module, plan) in enumerate(cands):
            if plan is None:
                plan = fa.block_plan(s, s, d, True, window)
            if plan is not ... and any((s // plan.block_q) % t
                                       for t in (plan.dq_tiles, plan.tiles)):
                continue
            tag = f"{sname}_{n}"
            line = {"shape": sname, "plan": label, "q_heads": hq,
                    "kv_heads": hkv, "seq": s, "d": d, "window": window}
            whole = min(s, 512)    # the tile of a commit with no plan
            share_of = plan if plan is not ... else fa.BlockPlan(
                whole, whole, whole)
            line["visited_share"] = [round(share_of.visited_share(
                s, s, True, window, kernel), 4) for kernel in range(3)]
            if plan is not ...:
                line["plan_is"] = [plan.block_q, plan.dq_sub,
                                   plan.dq_tiles, plan.tiles]
            jitted = {}
            for stem, fn in kernels_of(module, shape, plan, tag).items():
                jitted[stem] = chained(fn, args.iters)
            runs.append((line, jitted, data, tag))

    # compile and warm everything, then trace one pass over all of it
    for line, jitted, data, tag in runs:
        for stem, f in list(jitted.items()):
            t0 = time.perf_counter()
            try:
                float(f(*data))
            except Exception as e:  # a plan the compiler refuses is a line
                line[stem + "_error"] = str(e).splitlines()[0][:200]
                del jitted[stem]
                continue
            line[stem + "_compile_s"] = round(time.perf_counter() - t0, 2)
    reference = None
    if args.jax_reference and not args.tiny:
        from jax.experimental.pallas.ops.tpu import flash_attention as ref
        r = np.random.RandomState(1)
        q4 = [jnp.asarray(r.randn(8, 16, 1024, 128), jnp.bfloat16)
              for _ in range(3)]
        reference = chained(lambda q, k, v: ref.flash_attention(
            q, k, v, causal=True, sm_scale=128 ** -0.5), args.iters)
        float(reference(*q4))

    trace_dir = tempfile.mkdtemp(prefix="flash_plans_")
    if on_chip:
        jax.profiler.start_trace(trace_dir)
    for line, jitted, data, tag in runs:
        for stem, f in jitted.items():
            t0 = time.perf_counter()
            float(f(*data))
            line[stem + "_wall_us"] = round(
                (time.perf_counter() - t0) / args.iters * 1e6, 1)
    if reference is not None:
        t0 = time.perf_counter()
        float(reference(*q4))
        ref_wall = (time.perf_counter() - t0) / args.iters * 1e6
    seconds = {}
    if on_chip:
        jax.profiler.stop_trace()
        seconds = device_kernel_seconds(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)

    device = getattr(jax.devices()[0], "device_kind", "cpu")
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        out = open(args.out, "a")
    for line, jitted, data, tag in runs:
        live = fa.live_products(line["seq"], line["seq"], True,
                                line["window"]) * line["q_heads"]
        for stem in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            got = seconds.get(f"{stem}_{tag}")
            if got:
                us = got[0] / got[1] * 1e6
                line[stem + "_us"] = round(us, 1)
                line[stem + "_us_per_live_tile"] = round(
                    us / (live / 512 ** 2), 3)
        line.update(device=device, command=" ".join(sys.argv))
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
    if reference is not None:
        names = {k: v for k, v in seconds.items()
                 if not k.startswith("flash_")}
        line = {"shape": "gpt", "plan": "jax.pallas.ops.tpu.flash_attention",
                "wall_us": round(ref_wall, 1), "device": device,
                "kernels_us": {k: round(v[0] / v[1] * 1e6, 1)
                               for k, v in names.items()},
                "command": " ".join(sys.argv)}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
