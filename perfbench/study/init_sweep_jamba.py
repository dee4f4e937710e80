#!/usr/bin/env python3
"""What the harness's own check (``serve.check``: the reference's best
logit minus the reference's logit of the emitted token, largest over the
answers' positions of 8 completed requests, limit 0.05) reads in the Jamba
configuration as the scale of its random weights moves: for the sound
program, for a state zeroed at the hand-over, and for a state the bucket's
padding advanced.

    python3 perfbench/study/init_sweep_jamba.py --seed 2147484601 \
        --out chiprun_out/p33/init_sweep.jsonl

A point is (``embed_init_std``, the std of the final norm's zero-mean gain
or ``const`` for the constant 1, the recurrence's initialisers): ``slow`` is
the configuration's own ``A_log ~ N(1.5, 0.8)``, ``b_dt ~ N(-4.6, 1.3)``
(the state remembers over tens to hundreds of tokens), ``plain`` is
``N(0, 0.02)`` for both (``dt A`` about -0.7: the state forgets within
three tokens). At each point and run the REAL engine is built as the
harness builds it (``serve.build_engine``; ``max_len`` cut to ``--max-len``
so that the check's reference, which pads to it, stays cheap), serves
``--requests`` prompts of ``--prompt`` + ``--spread`` x i rows (one bucket, padding of
a few hundred rows behind each) with ``--answer`` tokens each, and
``serve.check`` itself reads the result. The faults are planted where the
prefill hands the state over (``ops/ssm_ops.selective_scan``, a new model
and new programs a run):

- ``zeroed``: the state a prompt leaves is zero (the first decode step
  starts from nothing; the convolution's tail is sound);
- ``pads``: the state is taken at the bucket's end, not at the prompt's
  own last token.

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
              "plain": {"a_log_init": [0.0, 0.02],
                        "dt_bias_init": [0.0, 0.02]}}
RUNS = ("sound", "zeroed", "pads")


def plant(run):
    """Break the hand-over of the state; -> a function that repairs it."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    real = ssm_ops.selective_scan
    if run == "sound":
        return lambda: None
    if run == "zeroed":
        def scan(*a):
            y, s = real(*a)
            return y, jnp.zeros_like(s)
    elif run == "pads":
        def scan(x, dt, a, b, c, d, z, last):
            return real(x, dt, a, b, c, d, z,
                        jnp.full_like(last, x.shape[1] - 1))
    else:
        raise ValueError(run)
    ssm_ops.selective_scan = scan
    return lambda: setattr(ssm_ops, "selective_scan", real)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="jamba2-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=560)
    ap.add_argument("--spread", type=int, default=37)
    ap.add_argument("--answer", type=int, default=300)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--points", default="1:0.1:slow,1:0.3:slow,"
                    "0.5:0.1:slow,1:0.1:plain,1:const:slow",
                    help="embed std : final gain std (const: the "
                    "constant 1) : recurrence, comma-separated")
    ap.add_argument("--runs", default=",".join(RUNS))
    ap.add_argument("--seed", type=int, default=2147484601)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import run as harness, serve, traffic as T
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("init_sweep_jamba needs the TPU (or --allow-cpu)")
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
    for point in args.points.split(","):
        std, gain, name = point.split(":")
        std = float(std)
        cfg = dict(base, embed_init_std=std, **(RECURRENCE[name] or {}))
        cfg.pop("final_norm_init", None)
        if gain != "const":
            cfg["final_norm_init"] = [0.0, float(gain)]
        cfg["engine"] = dict(base["engine"], max_len=args.max_len,
                             buckets=[bucket],
                             max_slots=args.requests)
        cfg["max_position_embeddings"] = args.max_len
        for run in args.runs.split(","):
            t = time.time()
            repair = plant(run)
            try:
                model, engine = serve.build_engine(cfg, args.seed)
                fol = serve.Follower()
                for p in prompts:
                    arrival = T.Arrival(0, 0.0, p, args.answer)
                    req = engine.submit(list(p),
                                        max_new_tokens=args.answer)
                    fol.live.append(serve.Stream(req, arrival, True))
                while fol.live:
                    engine.step()
                    fol.after_step(time.perf_counter())
                notes = serve.check(model, engine, cfg, fol, args.seed,
                                    0.0, float("inf"))
            finally:
                repair()
            rec = dict(notes, run=run, embed_std=std, recurrence=name,
                       final_norm_init=cfg.get("final_norm_init"),
                       a_log_init=cfg.get("a_log_init"),
                       dt_bias_init=cfg.get("dt_bias_init"),
                       config=cfg["name"], seed=args.seed,
                       prompts=lengths, bucket=bucket,
                       answer=args.answer, max_len=args.max_len,
                       device=dev.device_kind,
                       seconds=round(time.time() - t, 1))
            print(json.dumps(rec), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            del model, engine, fol
    return 0


if __name__ == "__main__":
    sys.exit(main())
