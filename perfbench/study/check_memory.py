#!/usr/bin/env python3
"""What the serving check's one program holds (PR 37): the compiler's
``memory_analysis()`` of ``serve.deficits_fn`` for a configuration at its
engine's ``max_len``, in blocks of ``CHECK_ROWS`` rows as the check runs it
and whole (one block as long as ``max_len``: the form the check had to
PR 36, ``max_len x vocabulary`` float32 logits in one array).

    python3 perfbench/study/check_memory.py --config mellum2-12b-d8 \
        [--described] [--out chiprun_out/check_memory.jsonl]

Nothing runs and no weight is made: the program is compiled for the shapes
of the served model's parameters. On the chip it compiles for the chip it
finds; with ``--described`` for a v5e that is described and not attached
(``jax.experimental.topologies``), which needs no chip. One JSON line a
form; bytes as the compiler states them.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def parameter_shapes(family, cfg, sharding):
    """The served model's parameters as shapes and dtypes, by name."""
    import jax
    from perfbench import weights

    def build():
        with weights.recording():
            model = family.serving_model(cfg)
        return {n: p.value for n, p in model.named_parameters()}
    return {n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for n, a in jax.eval_shape(build).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--described", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from perfbench import families, run as harness, serve
    cfg = harness.load_json(ROOT, "perfbench", "configs",
                            args.config + ".json")
    family = families.load(cfg)
    if args.described:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        sharding = SingleDeviceSharding(device)
    else:
        device, sharding = jax.devices()[0], None
    rows = int(cfg["engine"]["max_len"])
    params = parameter_shapes(family, cfg, sharding)
    ids = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=sharding)
    nxt = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=sharding)
    vocabulary = jax.eval_shape(lambda p: family.head(p, cfg),
                                params).shape[1]
    for form, block in (("blocks", serve.CHECK_ROWS), ("whole", rows)):
        rec = {"tool": "check_memory.py", "config": args.config,
               "form": form, "rows": rows, "rows_a_block": min(block, rows),
               "vocabulary": vocabulary,
               "logits_bytes": min(block, rows) * vocabulary * 4,
               "device": device.device_kind, "described": args.described}
        try:
            mem = serve.deficits_fn(family, cfg, block).lower(
                params, ids, nxt).compile().memory_analysis()
            rec.update(temp_bytes=mem.temp_size_in_bytes,
                       argument_bytes=mem.argument_size_in_bytes,
                       output_bytes=mem.output_size_in_bytes)
        except Exception as e:      # the compiler refuses what does not fit
            rec["refused"] = f"{type(e).__name__}: {str(e)[:600]}"
        print(json.dumps(rec), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
