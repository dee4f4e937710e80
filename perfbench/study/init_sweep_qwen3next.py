#!/usr/bin/env python3
"""What the harness's own check (``serve.check``: the reference's best
logit minus the reference's logit of the emitted token, largest over the
answers' positions of 8 completed requests, limit 0.05) reads in the
Qwen3-Next configuration as its random weights' scales move: for the sound
program and for the faults the configuration's ``assumed`` names.

    python3 perfbench/study/init_sweep_qwen3next.py --seed 2147484658 \
        --out chiprun_out/p58/init_sweep.jsonl

A point is ``embed_init_std : recurrence``: ``slow`` is the
configuration's own ``A_log ~ N(-0.5, 1.0)``, ``dt_bias ~ N(-4.6, 1.3)``
(``g`` about -0.006 a token: the state remembers over hundreds of tokens),
``published`` is ``A_log ~ N(2.0, 0.6)`` with the same ``dt_bias`` (about
the published initialisers' spread: ``g`` about -0.07, a dozen tokens),
``plain`` ``N(0, 0.02)`` for both (``g`` about -0.7: three tokens). At each
point and run the REAL engine is built as the harness builds it
(``serve.build_engine``; ``max_len`` cut to ``--max-len`` so that the
check's reference, which pads to it, stays cheap), serves ``--requests``
prompts of ``--prompt`` + ``--spread`` x i rows (one bucket, padding of a
few hundred rows behind each) with ``--answer`` tokens each, and
``serve.check`` itself reads the result. The faults, planted in code and
repaired after each run (a new model and new programs a run):

- ``zeroed``: the state a prompt leaves is zero (the first decode step
  starts from nothing; the convolution's tail is sound);
- ``pads``: ``g`` and ``beta`` are not zeroed past a prompt's own last
  token, so the bucket's padding decays and writes;
- ``bf16_state``: ``S`` is rounded to bfloat16 at the hand-over and after
  every decode step (the nearest precision below the stated float32; by
  ``lax.reduce_precision``: in call 2 the fault was a cast there and back,
  which the chip's compiler removed, and its readings were the sound
  program's to the last digit);
- ``bf16_router``: the router scores inputs rounded to bfloat16 (the
  weights are bfloat16 already);
- ``no_gate``: the shared expert's sigmoid gate is dropped.

A line a (point, run): ``correct``, ``max_logit_deficit`` and the rest of
the check's notes.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

RECURRENCE = {"slow": None,         # the configuration file's own
              "published": {"a_log_init": [2.0, 0.6]},
              "plain": {"a_log_init": [0.0, 0.02],
                        "dt_bias_init": [0.0, 0.02]}}
RUNS = ("sound", "zeroed", "pads", "bf16_state", "bf16_router", "no_gate")


def plant(run):
    """Break the served path in code; -> a function that repairs it."""
    import jax.numpy as jnp
    from paddle_tpu.models import laguna, qwen3next
    from paddle_tpu.ops import gated_delta_ops as gdn
    real = {"rule": gdn.gated_delta_rule, "step": gdn.gated_delta_step,
            "mask": gdn.mask_past_last, "router": laguna._moe_router,
            "shared": qwen3next.Qwen3NextMoE._shared}

    def repair():
        gdn.gated_delta_rule, gdn.gated_delta_step = real["rule"], real["step"]
        gdn.mask_past_last, laguna._moe_router = real["mask"], real["router"]
        qwen3next.Qwen3NextMoE._shared = real["shared"]

    def bf16(x):
        # not a cast there and back: the chip's compiler removes that pair
        return gdn.round_to(x.astype(jnp.float32), jnp.bfloat16)
    if run == "sound":
        pass
    elif run == "zeroed":
        def rule(*a):
            o, s = real["rule"](*a)
            return o, jnp.zeros_like(s)
        gdn.gated_delta_rule = rule
    elif run == "pads":
        gdn.mask_past_last = lambda g, beta, last: (g, beta)
    elif run == "bf16_state":
        def rule(*a):
            o, s = real["rule"](*a)
            return o, bf16(s)

        def step(*a):
            o, s = real["step"](*a)
            return o, bf16(s)
        gdn.gated_delta_rule, gdn.gated_delta_step = rule, step
    elif run == "bf16_router":
        def router(ctx, ins, attrs):
            return real["router"](ctx, dict(
                ins, X=[bf16(ins["X"][0])],
                W=[bf16(w) for w in ins["W"]]), attrs)
        laguna._moe_router = router
    elif run == "no_gate":
        qwen3next.Qwen3NextMoE._shared = laguna.LagunaMoE._shared
    else:
        raise ValueError(run)
    return repair


def serve_and_check(cfg, seed, prompts, answer):
    """The harness's engine over ``prompts`` with ``answer`` tokens each ->
    ``serve.check``'s notes."""
    from perfbench import serve, traffic as T
    model, engine = serve.build_engine(cfg, seed)
    fol = serve.Follower()
    for p in prompts:
        req = engine.submit(list(p), max_new_tokens=answer)
        fol.live.append(serve.Stream(req, T.Arrival(0, 0.0, p, answer), True))
    while fol.live:
        engine.step()
        fol.after_step(time.perf_counter())
    return serve.check(model, engine, cfg, fol, seed, 0.0, float("inf"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen3-next-80b-a3b-ep4-d8")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=2200)
    ap.add_argument("--spread", type=int, default=97)
    ap.add_argument("--answer", type=int, default=300)
    ap.add_argument("--max-len", type=int, default=3584)
    ap.add_argument("--points", default="1:slow",
                    help="embed std : recurrence, comma-separated")
    ap.add_argument("--runs", default=",".join(RUNS))
    ap.add_argument("--seed", type=int, default=2147484658)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import gc
    import jax
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import run as harness, traffic as T
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("init_sweep_qwen3next needs the TPU (or "
                         "--allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    base = harness.load_json(ROOT, "perfbench", folder,
                             args.config + ".json")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rng = np.random.default_rng([args.seed, 5])
    lengths = [args.prompt + args.spread * i for i in range(args.requests)]
    prompts = [tuple(int(t) for t in rng.integers(1, base["vocab_size"], n))
               for n in lengths]
    bucket = T.bucket_for(max(lengths), base["engine"]["buckets"])
    blocks = -(-args.max_len // base["engine"]["block_size"])
    for point in args.points.split(","):
        std, name = point.split(":")
        cfg = dict(base, embed_init_std=float(std),
                   **(RECURRENCE[name] or {}))
        cfg["engine"] = dict(base["engine"], max_len=args.max_len,
                             buckets=[bucket], max_slots=args.requests,
                             num_blocks=args.requests * blocks + 1)
        cfg["max_position_embeddings"] = args.max_len
        for run in args.runs.split(","):
            t = time.time()
            repair = plant(run)
            try:
                notes = serve_and_check(cfg, args.seed, prompts, args.answer)
            finally:
                repair()
            rec = dict(notes, run=run, embed_std=float(std), recurrence=name,
                       a_log_init=cfg.get("a_log_init"),
                       dt_bias_init=cfg.get("dt_bias_init"),
                       config=cfg["name"], seed=args.seed, prompts=lengths,
                       bucket=bucket, answer=args.answer,
                       max_len=args.max_len, device=dev.device_kind,
                       seconds=round(time.time() - t, 1))
            print(json.dumps(rec), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
