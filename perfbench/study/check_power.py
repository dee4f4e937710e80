#!/usr/bin/env python3
"""What the two checks behind ``correct`` can see, at the real widths,
under a configuration's initial weights, one fault at a time.

    python3 perfbench/study/check_power.py --part serve --config cgpt-1p3b
    python3 perfbench/study/check_power.py --part train --config cgpt-1p3b-d20

``serve``: a random prompt, then the reference's own greedy answer. For
each fault the faulty model's choice at every answer position (teacher-
forced on the clean stream, as ``serve.check`` forces the engine's) is
scored as the check scores an engine's token: the clean reference's best
logit minus its logit of the chosen token. ``LOGIT_TOLERANCE`` sees a fault
only if some position's deficit is above it.

``train``: the reference's loss on one row of random tokens, whole and
faulty. ``LOSS_TOLERANCE`` sees a fault only if it moves the loss by more.

Logits and losses are arithmetic, not device metrics: this runs on whatever
device JAX has (it says which), in float32 at ``highest`` like the checks.
"""

import argparse
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PROMPT, ANSWER = 64, 96     # the serving stream; the train row is 1024 long
SEQ = 1024


def faults(params, layers, jnp):
    """name -> (params, layers, context manager or None) of each fault."""
    wpe = params["gpt.wpe.weight"]
    bf16 = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
            for k, v in params.items()}
    return {
        "weights_rounded_to_bf16": (bf16, layers, None),
        "last_block_dropped": (params, layers - 1, None),
        "causal_mask_dropped": (params, layers,
                                mock.patch.object(jnp, "tril", jnp.ones_like)),
        "positions_off_by_one": (dict(params, **{
            "gpt.wpe.weight": jnp.roll(wpe, 1, axis=0)}), layers, None),
        "positions_zeroed": (dict(params, **{
            "gpt.wpe.weight": jnp.zeros_like(wpe)}), layers, None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--part", required=True, choices=("serve", "train"))
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models import GPTForCausalLM
    from perfbench import families, reference, run as harness, serve, train, weights
    cfg = harness.load_json(ROOT, "perfbench", "configs",
                            args.config + ".json")
    gcfg = families.load(cfg).model_config(cfg)
    kw = dict(num_heads=cfg["n_head"],
              vocab_size=cfg["program"]["vocab_rows"])
    layers, vocab = int(cfg["n_layer"]), int(cfg["vocab_size"])

    def params_of(seed):
        with weights.recording() as specs:
            model = GPTForCausalLM(gcfg)
        weights.fill(model, specs, seed)
        return {n: p.value for n, p in model.named_parameters()}

    params = params_of(args.seed)
    out = {"part": args.part, "config": args.config, "seed": args.seed,
           "device": jax.devices()[0].device_kind}

    def under(fault, fn):
        p, n, ctx = fault
        with ctx or mock.patch.object(jnp, "tril", jnp.tril):
            return jax.jit(lambda p: fn(p, n))(p)

    if args.part == "serve":
        p0, n = PROMPT, ANSWER
        ids = np.zeros((1, p0 + n), np.int32)
        ids[0, :p0] = np.random.default_rng([args.seed, 5]).integers(
            1, vocab, size=p0)
        fwd = jax.jit(lambda p, i: reference.forward(
            p, i, num_layers=layers, **kw)[0])
        for k in range(p0, p0 + n):         # the clean greedy answer
            ids[0, k] = int(np.asarray(fwd(params, jnp.asarray(ids)))[k - 1]
                            .argmax())
        clean = np.asarray(fwd(params, jnp.asarray(ids)))[p0 - 1:p0 + n - 1]
        top2 = np.sort(clean, axis=-1)[:, -2:]
        out.update(prompt=p0, answer=n, logit_std=float(clean.std()),
                   repeats_previous_token_share=float(np.mean(
                       ids[0, p0:] == ids[0, p0 - 1:-1])),
                   best_minus_second_median=float(
                       np.median(top2[:, 1] - top2[:, 0])),
                   tolerance=serve.LOGIT_TOLERANCE, faults={})
        for name, fault in faults(params, layers, jnp).items():
            logits = np.asarray(under(fault, lambda p, nl: reference.forward(
                p, jnp.asarray(ids), num_layers=nl, **kw)[0]))
            pick = logits[p0 - 1:p0 + n - 1].argmax(-1)
            d = clean.max(-1) - clean[np.arange(n), pick]
            out["faults"][name] = {
                "max_deficit": float(d.max()),
                "positions_over_tolerance": int(
                    (d > serve.LOGIT_TOLERANCE).sum()),
                "positions_choosing_another_token": int(
                    (pick != ids[0, p0:]).sum())}
    else:
        ids = jax.random.randint(weights.seed_key(args.seed + 1),
                                 (1, SEQ), 1, vocab, jnp.int32)
        labels = jnp.roll(ids, -1, axis=1)

        def loss(fault, lab=labels):
            return float(under(fault, lambda p, nl: reference.loss(
                p, ids, lab, num_layers=nl, **kw)))

        whole = loss((params, layers, None))
        out.update(seq=SEQ, whole=whole,
                   tolerance=train.LOSS_TOLERANCE, moved_by={})
        for name, fault in faults(params, layers, jnp).items():
            out["moved_by"][name] = loss(fault) - whole
        out["moved_by"]["labels_not_shifted"] = \
            loss((params, layers, None), ids) - whole
        del params
        out["moved_by"]["weights_of_another_seed"] = \
            loss((params_of(args.seed + 12345), layers, None)) - whole
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
