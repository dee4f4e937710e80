#!/usr/bin/env python3
"""How close the served Qwen3-Next program comes to its plain reference at
the published widths: LOGITS, not tokens, of the decode steps of a few
requests through the harness's own engine, against the reference's full
forward on each final sequence (float32 at ``highest``, the delta rule a
scan over time), and what a state of the nearest precision below the stated
one moves them by.

    python3 perfbench/study/compare_qwen3next.py --seed 2147484658 \
        --out chiprun_out/p58/compare.jsonl

The engine is built as the harness builds it (``serve.build_engine``;
``max_len`` cut to ``--max-len`` and one bucket, so that the reference's
whole logits ``[rows, vocabulary]`` stay small) and serves ``--requests``
prompts of ``--prompt`` + ``--spread`` x i rows with ``--answer`` tokens
each; the logits the engine's own compiled decode entry returns are tapped
by request and position. A line a run (``init_sweep_qwen3next.plant``'s
names; ``sound`` is the program as it is):

- ``logit_err_max`` / ``logit_err_rms``: the program's decode logits less
  the reference's, over every tapped position and vocabulary row;
- ``deficit_max``: the harness's own number on the same rows (the
  reference's best logit less its logit of the emitted token);
- ``ref_bf16_state_shift_max`` (the sound run only): the REFERENCE with its
  state rounded to bfloat16 after every step, less the reference: what the
  nearest precision below the stated float32 state moves the logits by.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def tap(engine):
    """Record the logits the engine's decode entry returns, by request."""
    import numpy as np
    rows, real = {}, engine.spec.decode_entry

    def decode_entry(*a, **kw):
        ent = real(*a, **kw)

        def fn(*args):
            out = ent["fn"](*args)
            lengths = np.asarray(args[1])
            for slot, req in engine._active.items():
                made = int(lengths[slot]) - len(req.prompt) + 1
                if made < req.max_new_tokens:
                    rows.setdefault(req.id, []).append(
                        np.asarray(out[1][slot]))
            return out
        return dict(ent, fn=fn)
    engine.spec.decode_entry = decode_entry
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen3-next-80b-a3b-ep4-d8")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=2200)
    ap.add_argument("--spread", type=int, default=97)
    ap.add_argument("--answer", type=int, default=48)
    ap.add_argument("--max-len", type=int, default=3072)
    ap.add_argument("--runs", default="sound,bf16_state")
    ap.add_argument("--seed", type=int, default=2147484658)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    from init_sweep_qwen3next import plant
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, serve, traffic as T
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("compare_qwen3next needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family = families.load(cfg)
    rng = np.random.default_rng([args.seed, 5])
    lengths = [args.prompt + args.spread * i for i in range(args.requests)]
    prompts = [tuple(int(t) for t in rng.integers(1, cfg["vocab_size"], n))
               for n in lengths]
    bucket = T.bucket_for(max(lengths), cfg["engine"]["buckets"])
    blocks = -(-args.max_len // cfg["engine"]["block_size"])
    cfg["engine"] = dict(cfg["engine"], max_len=args.max_len,
                         buckets=[bucket], max_slots=args.requests,
                         num_blocks=args.requests * blocks + 1)
    cfg["max_position_embeddings"] = args.max_len
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    forward = jax.jit(lambda p, ids: family.forward(p, ids, cfg)[0])
    rounded = jax.jit(lambda p, ids: family.forward(
        p, ids, cfg, state_dtype=jnp.bfloat16)[0])
    for run in args.runs.split(","):
        t = time.time()
        repair = plant(run)
        try:
            model, engine = serve.build_engine(cfg, args.seed)
            rows = tap(engine)
            reqs = [engine.submit(list(p), max_new_tokens=args.answer)
                    for p in prompts]
            engine.run_until_idle()
        finally:
            repair()
        params = {n: p.value for n, p in model.named_parameters()}
        err_max = sq = count = deficit = shift = 0.0
        for r in reqs:
            p, n = len(r.prompt), len(r.tokens)
            # right-padded to max_len (the reference's attention takes whole
            # blocks of rows; a row's logits do not depend on those behind)
            seq = np.zeros(args.max_len, np.int32)
            seq[:p + n] = list(r.prompt) + list(r.tokens)
            ref = np.asarray(forward(params, jnp.asarray(seq[None])))
            got = np.stack(rows[r.id])
            diff = got - ref[p:p + n - 1]
            err_max = max(err_max, float(np.abs(diff).max()))
            sq, count = sq + float((diff ** 2).sum()), count + diff.size
            at = np.arange(p - 1, p + n - 1)
            deficit = max(deficit, float(
                (ref[at].max(-1) - ref[at, seq[p:p + n]]).max()))
            if run == "sound":
                low = np.asarray(rounded(params, jnp.asarray(seq[None])))
                shift = max(shift, float(np.abs(low[at] - ref[at]).max()))
        rec = {"tool": "compare_qwen3next.py", "run": run,
               "logit_err_max": err_max,
               "logit_err_rms": (sq / count) ** 0.5, "deficit_max": deficit,
               "config": cfg["name"], "seed": args.seed, "prompts": lengths,
               "answer": args.answer, "bucket": bucket,
               "device": dev.device_kind,
               "seconds": round(time.time() - t, 1)}
        if run == "sound":
            rec["ref_bf16_state_shift_max"] = shift
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        del model, engine, params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
