#!/usr/bin/env python3
"""What the harness's own measure (``serve.check``: the reference's best
logit minus the reference's logit of the emitted token, largest over the
answers' positions, limit 0.05) reads in the Mellum configuration as the
scale of its random weights moves: for the sound program, for one expert
left out, and for int8 weights.

    python3 perfbench/study/init_sweep_mellum.py --seed 2147484601 \
        --out chiprun_out/p31/init_sweep.jsonl

ONE build and ONE set of compiled entries (``compare_mellum.Runner``: the
serving forward over the paged cache by kind). A point of the sweep only
rescales values: the embedding's rows to ``--embed-stds`` (the share of the
residual stream the 8 layers carry goes as ~0.1 / std) and the routers'
weights by ``--router-scales`` (a sharper softmax over the 64 experts: the
chosen eight's weights spread out, so the 8th, which a rounding tie can
swap, carries less). At each point ``--requests`` prompts of ``--prompt``
rows are prefilled and ``--answer`` tokens decoded greedily together (as
many answer tokens as the harness's check reads in the cell: 8 requests,
265-742 tokens each), the reference (float32, ``highest``) is computed on
those sequences, and each fault replays the same tokens:

- ``expert``: one expert of the middle layer left out (its down projection
  zeroed in the program only);
- ``int8_weights``: every matrix but embedding and head rounded to an int8
  grid a column (in place, so it runs last, over every point again).

A line a (point, run): ``deficit_max`` and its 99th percentile over the
answers' positions, the share of positions whose emitted token is not the
reference's best (``flipped``), and, for the point, ``layers_share``: the
median over rows of |h_8 - h_0| / |h_8| in the reference.
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


def deficits(got, ref_rows):
    """Per request: ``got`` the program's logits of the answer's positions,
    ``ref_rows`` the reference's -> one array of deficits, and how many
    positions emitted another token than the reference's best."""
    import numpy as np
    ds, flips = [], 0
    for g, ref in zip(got, ref_rows):
        n = len(g["logits"])
        emitted = np.argmax(g["logits"], axis=-1)
        ds.append(ref[:n].max(-1) - ref[np.arange(n), emitted])
        flips += int(np.sum(emitted != np.argmax(ref[:n], axis=-1)))
    d = np.concatenate(ds)
    return {"deficit_max": float(d.max()),
            "deficit_p99": float(np.quantile(d, 0.99)),
            "flipped": flips / len(d), "positions": int(len(d))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mellum2-12b-d8")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=2600)
    ap.add_argument("--answer", type=int, default=400)
    ap.add_argument("--embed-stds", default="1,0.5,0.25,0.125")
    ap.add_argument("--router-scales", default="1,2,4")
    ap.add_argument("--seed", type=int, default=2147484601)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import compare_mellum as C
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, weights
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("init_sweep_mellum needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family = families.load(cfg)
    with weights.recording() as specs:
        model = family.serving_model(cfg)
    weights.fill(model, specs, args.seed)
    model.eval()
    named = dict(model.named_parameters())
    embed = named["model.embed.weight"]
    routers = [p for n, p in named.items() if n.endswith("router.weight")]
    # unit-scale rows: the file's own std divided out (a power of two in
    # every point, so the bfloat16 values are exact multiples)
    unit = (embed.value.astype(jnp.float32)
            / float(model.cfg.embed_init_std or model.cfg.init_std))
    base_routers = [p.value for p in routers]

    def set_point(std, sharp):
        embed.value = (unit * std).astype(embed.value.dtype)
        for p, b in zip(routers, base_routers):
            p.value = (b.astype(jnp.float32) * sharp).astype(b.dtype)

    runner = C.Runner(model, cfg)
    n_prompt = [args.prompt + 9 * i for i in range(args.requests)]
    rng = np.random.default_rng([args.seed, 5])
    prompts = [rng.integers(1, cfg["vocab_size"], size=p).tolist()
               for p in n_prompt]
    block = 256 if args.prompt + args.answer > 256 else 8

    @jax.jit
    def reference(params, ids, first):
        states = []
        logits = family.forward(params, ids, cfg, collect=states)[0]
        emb = jnp.asarray(params["model.embed.weight"][ids[0]], jnp.float32)
        last = states[-1][0]
        share = jnp.linalg.norm(last - emb, axis=-1) \
            / jnp.linalg.norm(last, axis=-1)
        rows = jax.lax.dynamic_slice_in_dim(logits, first, args.answer, 0)
        return rows, share

    def params_now():
        return {n: p.value for n, p in model.named_parameters()}

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def note(rec):
        rec.update(config=cfg["name"], seed=args.seed, prompt=args.prompt,
                   requests=args.requests, answer=args.answer,
                   device=dev.device_kind)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    points = [(float(s), float(r)) for r in args.router_scales.split(",")
              for s in args.embed_stds.split(",")]
    kept = {}
    n_mid = cfg["num_hidden_layers"] // 2
    down = model.model.layers[n_mid].moe.experts_down
    for std, sharp in points:
        t = time.time()
        set_point(std, sharp)
        clean = runner.run([p + [0] * args.answer for p in prompts],
                           n_prompt, greedy=True)
        seqs = [g["tokens"] for g in clean]
        refs, shares = [], []
        for s, p in zip(seqs, n_prompt):
            pad = -(-len(s) // block) * block
            ids = np.zeros((1, pad), np.int32)
            ids[0, :len(s)] = s
            rows, share = reference(params_now(), jnp.asarray(ids), p - 1)
            refs.append(np.asarray(rows))
            shares.append(np.asarray(share[:len(s)]))
        layers_share = float(np.median(np.concatenate(shares)))
        kept[std, sharp] = (seqs, refs, layers_share)
        rec = deficits(clean, refs)
        rec.update(run="clean", embed_std=std, router_scale=sharp,
                   layers_share=layers_share)
        note(rec)
        was = down.value
        down.value = was.at[5].set(jnp.zeros_like(was[5]))
        try:
            got = runner.run(seqs, n_prompt, greedy=False)
        finally:
            down.value = was
        rec = deficits(got, refs)
        rec.update(run="expert", embed_std=std, router_scale=sharp,
                   layers_share=layers_share)
        note(rec)
        del clean, got
        print(f"point {std} x{sharp}: {time.time() - t:.1f} s", flush=True)
    # int8 weights, in place (a second copy does not fit), over every point
    set_point(1.0, 1.0)
    quantize = jax.jit(lambda v: C.fake_int8(v, axis=-2), donate_argnums=0)
    for name, p in model.named_parameters():
        if p.value.ndim >= 2 and "embed" not in name \
                and "lm_head" not in name:
            p.value = quantize(p.value)
    base_routers = [p.value for p in routers]
    for std, sharp in points:
        set_point(std, sharp)
        seqs, refs, layers_share = kept[std, sharp]
        got = runner.run(seqs, n_prompt, greedy=False)
        rec = deficits(got, refs)
        rec.update(run="int8_weights", embed_std=std, router_scale=sharp,
                   layers_share=layers_share)
        note(rec)
        del got
    return 0


if __name__ == "__main__":
    sys.exit(main())
