#!/usr/bin/env python3
"""Do two trees lower the same served programs? (PR 46)

    JAX_PLATFORMS=cpu python3 perfbench/study/same_programs.py <tree> \
        [--out runs.jsonl]

For the toy twin of every served family a tree has had since PR 42 (lfm2
at 128 rows a step, mellum, jamba, gpt2) it lowers the decode step and one
prefill bucket as the engine's own entries jit them and prints one JSON
line a program: the family, the program, a hash of the lowered text
(StableHLO, source locations stripped) and the text's length. Run it on a
parent's ``git archive`` and on the change: equal hashes say the change
left those models' programs as they were, whatever a noisy pair of chip
runs reads (one ``lfm2_agents_3k`` change run of PR 46 read 11.5% low
beside an identical program, a stall of seconds: PERF.md section 6;
``runs_pr46.jsonl`` holds both trees' lines). No chip and no weights of
size: a CPU run of seconds.

The step's arguments are taken from the engine as ``tests/test_chip_compile.py``
takes them (``_step_args``, ``_prefill_entry``: no public call hands out a
program without running it), and only through names the parent has too.
"""

import argparse
import hashlib
import json
import os
import re
import sys

#: family -> (its models module's table of configurations, the toy twin,
#: the model class, the engine's sizes)
TWINS = {
    "lfm2": ("LFM2_CONFIGS", "lfm2-tiny", "Lfm2ForCausalLM",
             dict(max_slots=128, max_len=256)),
    "mellum": ("MELLUM_CONFIGS", "mellum-tiny", "MellumForCausalLM",
               dict(max_slots=4, max_len=128)),
    "jamba": ("JAMBA_CONFIGS", "jamba-tiny", "JambaForCausalLM",
              dict(max_slots=4, max_len=128)),
    "gpt2": ("GPT_CONFIGS", "gpt2-tiny", "GPTForCausalLM",
             dict(max_slots=4, max_len=128)),
}


def text_hash(lowered) -> dict:
    text = re.sub(r"loc\(.*?\)|#loc\d*.*", "", lowered.as_text())
    return {"sha1": hashlib.sha1(text.encode()).hexdigest()[:12],
            "chars": len(text)}


def programs(model, **sizes):
    """-> {"decode": ..., "prefill": ...}: the hashes of the model's decode
    step and of its one prefill bucket."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.generation import param_leaves
    from paddle_tpu.serving import ServingEngine
    engine = ServingEngine(model, eos_token_id=None, prefix_cache=False,
                           buckets=[64], block_size=8, num_blocks=0, **sizes)
    params = param_leaves(model)
    with engine._step_lock:
        args = engine._step_args(engine._stamps())
    if engine._counted is not None:
        args += (engine._counted,)
    step = engine.spec.decode_entry(None, engine.kv_dtype, None)["fn"]
    bucket = engine.buckets[0]
    rows = engine.spec.prefill_rows(bucket, engine.max_slots)
    tables = jax.tree_util.tree_map(
        jnp.asarray, engine.cache.table_rows([], rows))
    prompt = (jnp.zeros((rows, bucket), jnp.int32),
              jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32),
              tables, engine.cache.arrays())
    prefill = engine._prefill_entry(bucket)["fn"]
    return {"decode": text_hash(step.raw.lower(params, *args)),
            "prefill": text_hash(prefill.raw.lower(params, *prompt))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tree", help="the root of a checkout or a git archive")
    ap.add_argument("--out", help="append the lines to this file too")
    args = ap.parse_args(argv)
    tree = os.path.abspath(args.tree)
    out = os.path.abspath(args.out) if args.out else None
    sys.path.insert(0, tree)
    os.chdir(tree)
    from paddle_tpu import models
    from paddle_tpu.dygraph import layers
    for family, (table, twin, cls, sizes) in TWINS.items():
        layers.seed(1)
        model = getattr(models, cls)(getattr(models, table)[twin])
        model.eval()
        for program, read in programs(model, **sizes).items():
            line = json.dumps(dict(tool="same_programs", tree=args.tree,
                                   family=family, twin=twin,
                                   program=program, **read))
            print(line, flush=True)
            if out:
                with open(out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
