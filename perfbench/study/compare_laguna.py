#!/usr/bin/env python3
"""What the harness's loss check does not see, looked at beside it: the
program's forward AS TIMED (``amp.auto_cast("O2")``, the flash and grouped
kernels) against the family's plain reference on the same weights, at the
configuration's published widths and the cell's sequence, one row.

    python3 perfbench/study/compare_laguna.py --seed 2147484201 \
        --out chiprun_out/p27/compare.jsonl

Read per layer, from the hidden state after it (``[8192, 2048]``), with
``e_t = |h_program[t] - h_reference[t]| / |h_reference[t]|`` (L2 over the
hidden axis) per token: the MEDIAN of ``e_t`` (a dense fault moves every
token) and the SHARE of tokens with ``e_t`` above ``LARGE`` (a sparse fault
moves a few tokens a lot). Both grow from layer to layer in a clean
program, so each is also read as its largest STEP from one layer to the
next, which is where a fault in one layer shows.

The tolerance, and why it is not tighter: the program rounds every
activation and weight to bfloat16 (8 bits of mantissa), which the median
shows: 0.66% after layer 0 and +0.05 point a layer (my chip runs, PR 27).
And the router is a discontinuous function of its input: the 8th and 9th
largest of 256 scores lie ~0.05 logit apart on average and bfloat16 inputs
move a logit by ~0.005, so in every sparse layer some tokens choose another
8th expert than the float32 reference does; where one of the two is a held
expert, the token's state moves by that expert's whole contribution (up to
24% of the state's norm). In the clean program 1.4 to 2.4% of the tokens
join the large-error set at every sparse layer. That is a property of
routing in bfloat16, not a fault, and it is why the sparse statistic is a
share of tokens and not a maximum, and why the per-token losses are
recorded and not judged (their largest difference, 0.6, is one flipped
expert). ``TOLERANCE`` stands between what the clean program read over the
seeds of ``runs_pr27.jsonl`` and what each injected fault read (PERF.md
section 6 has both).

Three faults, injected here one at a time into the program only (the
reference keeps the true weights and masks), to show the tolerance sees
them: ``window``, the window of one sliding layer widened to the whole
sequence; ``expert``, one held expert's tokens dropped in one layer (its
down-projection zeroed); ``gate``, the per-head output gate omitted in one
layer. Exit code 0 when the clean program passes and every fault fails.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

LARGE = 0.04
#: limits of one comparison; a reading above any of them fails it. Clean
#: readings / the smallest fault's: median 0.0110 / 0.0203 (gate), its step
#: 0.0006 / 0.0099 (gate), the large share's step 0.0244 / 0.0470 (expert)
TOLERANCE = {"median_rel_err": 0.015, "median_step": 0.003,
             "large_step": 0.035}
FAULTS = ("window", "expert", "gate")


def fault_layers(cfg):
    """The layer each fault goes into: the second sliding-window layer, the
    middle sparse layer, the last layer (2, 5 and 8 of layers 0-8)."""
    n = cfg["num_hidden_layers"]
    sliding = [i for i in range(n)
               if cfg["layer_types"][i] == "sliding_attention"]
    sparse = [i for i in range(n) if cfg["mlp_layer_types"][i] == "sparse"]
    return {"window": sliding[1], "expert": sparse[len(sparse) // 2],
            "gate": n - 1}


def build(cfg, seed):
    from perfbench import families, weights
    family = families.load(cfg)
    with weights.recording() as specs:
        from paddle_tpu.models import LagunaForCausalLM
        model = LagunaForCausalLM(family.model_config(cfg))
    weights.fill(model, specs, seed)
    return family, model


def inject(model, fault, where):
    """Break the program in one place; -> a function that repairs it."""
    import jax.numpy as jnp
    layer = model.model.layers[where]
    if fault == "window":
        attn = layer.attn
        was = attn.cfg
        attn.cfg = dataclasses.replace(
            was, sliding_window=was.max_position_embeddings)
        return lambda: setattr(attn, "cfg", was)
    if fault == "expert":
        p = layer.moe.experts_down
        was = p.value
        e = min(5, was.shape[0] - 1)
        p.value = was.at[e].set(jnp.zeros_like(was[e]))
        return lambda: setattr(p, "value", was)
    if fault == "gate":
        layer.attn._gate = lambda h, o: o
        return lambda: delattr(layer.attn, "_gate")
    raise ValueError(fault)


def program_forward(model, ids):
    """-> (logits float32 [s, rows], hidden [layers, s, h] float32)."""
    import jax.numpy as jnp
    from paddle_tpu import amp, jit

    def fwd(ids):
        states = []
        with amp.auto_cast(level="O2"):
            logits = model(ids, collect=states)
        return logits, states
    # a new wrapper every time: a fault changes what is traced
    logits, states = jit.to_static(fwd, layers=[model],
                                   donate_state=False)(ids)
    return (logits.value[0].astype(jnp.float32),
            jnp.stack([s.value[0].astype(jnp.float32) for s in states]))


def compare(got, want, labels, vlo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    (logits, hidden), (ref_logits, ref_hidden) = got, want

    def token_loss(lg):
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, (labels - vlo)[:, None],
                                    axis=-1)[:, 0]
    err = jnp.linalg.norm(hidden - ref_hidden, axis=-1) \
        / jnp.linalg.norm(ref_hidden, axis=-1)              # [layers, s]
    dl = token_loss(logits) - token_loss(ref_logits)
    per_layer = [{"median_rel_err": float(jnp.median(e)),
                  "large_share": float(jnp.mean(e > LARGE)),
                  "max_rel_err": float(jnp.max(e))} for e in err]
    def step(key):
        v = [p[key] for p in per_layer]
        return max(b - a for a, b in zip(v, v[1:]))
    out = {"per_layer": per_layer,
           "median_rel_err": max(p["median_rel_err"] for p in per_layer),
           "median_step": step("median_rel_err"),
           "large_share": max(p["large_share"] for p in per_layer),
           "large_step": max(per_layer[0]["large_share"],
                             step("large_share")),
           "loss_mean_abs_diff": float(jnp.mean(jnp.abs(dl))),
           "loss_max_abs_diff": float(jnp.max(jnp.abs(dl))),
           "loss_program": float(jnp.mean(token_loss(logits))),
           "loss_reference": float(jnp.mean(token_loss(ref_logits)))}
    out["failed_limits"] = sorted(k for k, lim in TOLERANCE.items()
                                  if not np.isfinite(out[k]) or out[k] > lim)
    out["pass"] = not out["failed_limits"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="laguna-xs2-share8")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--seed", type=int, default=2147484201)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.utils import chip
    from perfbench import run as harness, train
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("compare_laguna needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family, model = build(cfg, args.seed)
    pt.set_flags({"pallas_min_seq": min(1024, args.seq)})
    vlo = cfg["held"]["vocab_rows"][0]
    _, tiled = train.batch_maker(args.seed, 1, args.seq,
                                 int(cfg["vocab_size"]), None)
    ids, labels = tiled(0)
    params = {n: p.value for n, p in model.named_parameters()}

    def reference(p, i):
        states = []
        logits = family.forward(p, i, cfg, collect=states)
        return logits[0], jnp.stack([s[0] for s in states])
    t = time.time()
    want = jax.jit(reference)(params, ids)
    jax.block_until_ready(want)
    print(f"reference: {time.time() - t:.1f} s", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    verdicts, where = {}, fault_layers(cfg)
    for fault in ("clean",) + tuple(f for f in args.faults.split(",") if f):
        repair = inject(model, fault, where[fault]) if fault != "clean" \
            else (lambda: None)
        t = time.time()
        try:
            got = program_forward(model, ids)
        finally:
            repair()
        rec = {"tag": "compare", "fault": fault, "config": args.config,
               "seq": args.seq, "seed": args.seed,
               "fault_layer": where.get(fault), "large": LARGE,
               "tolerance": TOLERANCE,
               "device": {"platform": dev.platform, "kind": dev.device_kind},
               **compare(got, want, labels[0], vlo)}
        verdicts[fault] = rec["pass"]
        print(f"{fault}: pass={rec['pass']} failed={rec['failed_limits']} "
              f"median {rec['median_rel_err']:.5f} step "
              f"{rec['median_step']:.5f} large {rec['large_share']:.5f} "
              f"step {rec['large_step']:.5f} loss diff "
              f"{rec['loss_mean_abs_diff']:.5f} ({time.time() - t:.1f} s)",
              flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    ok = verdicts.pop("clean") and not any(verdicts.values())
    print("the tolerance passes the program and sees every fault"
          if ok else "the tolerance does NOT separate them", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
