#!/usr/bin/env python3
"""What the harness's own check (``serve.check``: the reference's best
logit minus the reference's logit of the emitted token, largest over the
answers' positions of 8 completed requests, limit 0.05) reads in the LFM2
configuration as the scale of its random weights moves: for the sound
program and for planted faults; and the load the router's bias gives.

    python3 perfbench/study/init_sweep_lfm2.py --seed 2147484642 \
        --out chiprun_out/p42c2/init_sweep.jsonl

A point is (the std of the final norm's zero-mean gain or ``const`` for the
constant 1, the std of ``expert_bias``). At each point and run the REAL
engine is built as the harness builds it (``serve.build_engine``;
``max_len`` cut to ``--max-len`` so that the check's reference, which pads
to it, stays cheap), serves ``--requests`` prompts of ``--prompt`` +
``--spread`` x i rows (one bucket, padding behind each) with ``--answer``
tokens each, and ``serve.check`` itself reads the result. The faults are
planted in the program alone (a new model and new programs a run):

- ``tail_late``: the convolution's tail handed over a row late;
- ``tail_pads``: the tail taken at the bucket's end, not at the prompt's
  own last token;
- ``no_bias``: the bias left out of the router's choice;
- ``bias_weights``: the bias added to the routing weights too;
- ``expert_out``: the expert its bias favours most left out of one layer;
- ``no_qk_norm``: the per-head norm on q and k dropped.

A line a (point, run): ``correct``, ``max_logit_deficit`` and the rest of
the check's notes; for the sound run also ``load_over_mean``: per expert
layer, the largest expert's share of one 2048-row prompt's choices (uniform
ids) over the mean share, which is what ``expert_bias_init_std`` is chosen
by.

``--cell lfm2_agents_3k`` plants each of ``--runs`` in **the cell's own
run** instead (``perfbench/run.py``'s ``run_cell``: the committed files,
the cell's traffic, 45 s, the harness's check of 8 of its completions) and
keeps the result line: the control that the harness's ``correct`` is held
to. Only the faults that need no built model (``CELL_RUNS``).
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

RUNS = ("sound", "tail_late", "tail_pads", "no_bias", "bias_weights",
        "expert_out", "no_qk_norm")
#: the faults that ``--cell`` can plant: swapped in before ``run_cell``
#: builds the model
CELL_RUNS = ("sound", "tail_late", "tail_pads", "no_bias", "bias_weights")


def plant(run):
    """Break the program in one place, before the model is built; -> (a
    function to call on the built model, a function that repairs it)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import laguna
    from paddle_tpu.ops import ssm_ops
    nothing = lambda *a: None                             # noqa: E731

    def swap(module, name, new):
        real = getattr(module, name)
        setattr(module, name, new(real))
        return nothing, lambda: setattr(module, name, real)
    if run == "sound":
        return nothing, nothing
    if run == "tail_late":
        return swap(ssm_ops, "conv_tail",
                    lambda real: lambda v, last, k: real(v, last - 1, k))
    if run == "tail_pads":
        return swap(ssm_ops, "conv_tail", lambda real: lambda v, last, k:
                    real(v, jnp.full_like(last, v.shape[1] - 1), k))
    if run == "no_bias":
        return swap(laguna, "_moe_router", lambda real: lambda c, ins, a:
                    real(c, {k: v for k, v in ins.items() if k != "Bias"},
                         a))
    if run == "bias_weights":
        def biased(real):
            def router(ctx, ins, attrs):
                out = real(ctx, ins, attrs)
                s = jax.nn.sigmoid(jnp.matmul(
                    ins["X"][0].astype(jnp.float32),
                    ins["W"][0].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)) + ins["Bias"][0]
                top = jnp.take_along_axis(s, out["TopkIdx"][0], axis=-1)
                return dict(out, TopkWeight=[
                    top / jnp.sum(top, -1, keepdims=True)])
            return router
        return swap(laguna, "_moe_router", biased)
    if run == "expert_out":
        def drop(model):
            moe = next(b.moe for b in model.model.layers if b.sparse)
            moe.experts_down.value = moe.experts_down.value.at[
                int(jnp.argmax(moe.expert_bias.value))].set(0)
        return drop, nothing
    if run == "no_qk_norm":
        def drop(model):
            model.cfg.qk_norm = False   # every layer reads this one object
        return drop, nothing
    raise ValueError(run)


def load_over_mean(model, vocab: int, rows: int, seed: int):
    """Per expert layer: the largest expert's share of the choices of one
    ``rows``-row prompt of uniform ids, over the mean share."""
    import numpy as np
    from paddle_tpu.models import laguna
    seen, real = [], laguna._moe_router

    def spy(ctx, ins, attrs):
        out = real(ctx, ins, attrs)
        seen.append(np.asarray(out["TopkIdx"][0]))
        return out
    laguna._moe_router = spy
    try:
        model(np.random.default_rng([seed, 7]).integers(
            1, vocab, (1, rows)).astype(np.int32))
    finally:
        laguna._moe_router = real
    e = model.cfg.num_experts
    return [round(float(np.bincount(i.ravel(), minlength=e).max()
                        / (i.size / e)), 3) for i in seen]


def through_the_cell(args) -> int:
    """Each of ``--runs`` planted in the cell's own run; a line a run."""
    import jax
    from perfbench import rehearse, run as harness
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for run in args.runs.split(","):
        if run not in CELL_RUNS:
            raise SystemExit(f"--cell plants {CELL_RUNS}; got {run}")
        t = time.time()
        bench = harness.load_json(ROOT, "BENCHMARK.json")
        job = argparse.Namespace(
            workload=args.cell, seed=args.seed, trace=0,
            seconds=3.0 if args.allow_cpu else float(bench["run_seconds"]))
        _, repair = plant(run)
        try:
            line = rehearse.run_twin(
                bench, harness.find_cell(bench, args.cell), job) \
                if args.allow_cpu else harness.run_cell(bench, job)
        finally:
            repair()
        rec = {"tool": "init_sweep_lfm2.py", "cell": args.cell, "run": run,
               "seed": args.seed, "correct": line["correct"],
               "max_logit_deficit": line["notes"].get("max_logit_deficit"),
               "line": line, "device": jax.devices()[0].device_kind,
               "seconds": round(time.time() - t, 1)}
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        gc.collect()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="lfm2-24b-a2b-d9")
    ap.add_argument("--cell", default="",
                    help="plant the runs in this cell's own run instead")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=560)
    ap.add_argument("--spread", type=int, default=37)
    ap.add_argument("--answer", type=int, default=200)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--points", default="0.01:0.03",
                    help="final gain std (const: the constant 1) : "
                    "expert_bias std, comma-separated")
    ap.add_argument("--runs", default=",".join(RUNS))
    ap.add_argument("--load-rows", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=2147484642)
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
        raise SystemExit("init_sweep_lfm2 needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    if args.cell:
        return through_the_cell(args)
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
        gain, bias = point.split(":")
        cfg = dict(base, expert_bias_init_std=float(bias))
        cfg.pop("final_norm_init", None)
        if gain != "const":
            cfg["final_norm_init"] = [0.0, float(gain)]
        cfg["engine"] = dict(base["engine"], max_len=args.max_len,
                             buckets=[bucket], max_slots=args.requests,
                             num_blocks=0)
        cfg["max_position_embeddings"] = args.max_len
        for run in args.runs.split(","):
            t = time.time()
            on_model, repair = plant(run)
            try:
                model, engine = serve.build_engine(cfg, args.seed)
                on_model(model)
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
                if run == "sound" and args.load_rows:
                    notes["load_over_mean"] = load_over_mean(
                        model, base["vocab_size"],
                        min(args.load_rows, args.max_len), args.seed)
            finally:
                repair()
            rec = dict(notes, tool="init_sweep_lfm2.py", run=run,
                       final_norm_init=cfg.get("final_norm_init"),
                       expert_bias_init_std=float(bias),
                       config=cfg["name"], seed=args.seed, prompts=lengths,
                       bucket=bucket, answer=args.answer,
                       max_len=args.max_len, device=dev.device_kind,
                       seconds=round(time.time() - t, 1))
            print(json.dumps(rec), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            # the layers refer to one another: without a collection the
            # old model's 10 GB are still held when the next is built
            del model, engine, fol
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
