#!/usr/bin/env python3
"""Does the harness's own check see a fault in ``mellum_code_16k``? A whole
run of the cell (``perfbench/run.py``'s ``run_cell``: the same build, warm-up,
window and ``serve.check``) with ONE fault planted in the served program
after its weights are drawn, and lifted just before the check computes the
reference, so that the reference reads the sound weights and the tokens it
is given are the faulty program's.

    python3 perfbench/study/check_power_mellum.py --fault expert \
        --seed 2147484701 --out chiprun_out/p31/check_power.jsonl

``expert``: one expert of 64 of the middle layer left out (its down
projection zeroed). ``int8_weights``: every matrix but embedding and head
rounded to an int8 grid a column, in place; the sound values wait on the
host meanwhile (a second copy does not fit the chip). The line is the
cell's own result line with ``fault`` beside it: ``correct`` should be
false and ``notes.max_logit_deficit`` above the harness's 0.05 for a fault
the check can see.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def plant(model, fault):
    """Break the served program in one place -> a function that repairs
    it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from compare_mellum import fake_int8
    if fault == "expert":
        n = model.cfg.num_hidden_layers
        p = model.model.layers[n // 2].moe.experts_down
        was = p.value
        p.value = was.at[5].set(jnp.zeros_like(was[5]))
        return lambda: setattr(p, "value", was)
    if fault == "int8_weights":
        quantize = jax.jit(lambda v: fake_int8(v, axis=-2), donate_argnums=0)
        sound = {}
        for name, p in model.named_parameters():
            if p.value.ndim >= 2 and "embed" not in name \
                    and "lm_head" not in name:
                sound[name] = np.asarray(p.value)
                p.value = quantize(p.value)

        def repair():
            for name, p in model.named_parameters():
                if name in sound:
                    p.value = jnp.asarray(sound.pop(name))
        return repair
    raise ValueError(fault)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", required=True,
                    choices=("expert", "int8_weights"))
    ap.add_argument("--workload", default="mellum_code_16k")
    ap.add_argument("--seed", type=int, default=2147484701)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the cell's toy twin on the CPU; never a result")
    args = ap.parse_args(argv)
    args.trace = 0
    from perfbench import families, run as harness, serve
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    real_build, real_check = serve.build_engine, serve.check
    repair = []

    def build_engine(cfg, seed):
        model, engine = real_build(cfg, seed)
        repair.append(plant(model, args.fault))
        return model, engine

    def check(*a, **kw):
        repair.pop()()      # the reference reads the sound weights
        return real_check(*a, **kw)
    serve.build_engine, serve.check = build_engine, check
    if args.rehearsal:      # the twin, as perfbench/rehearse.py makes it
        cell = harness.find_cell(bench, args.workload)
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        family = families.name_of(harness.load_json(ROOT, entry["file"]))
        bench["configs"] = [{
            "name": cell["config"],
            "file": f"perfbench/rehearsal/{family}-tiny.json"}]
        line = harness.run_cell(bench, args, rehearsal=True,
                                traffic_dir="rehearsal")
    else:
        line = harness.run_cell(bench, args)
    line.update(fault=args.fault, workload=args.workload, seed=args.seed)
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
