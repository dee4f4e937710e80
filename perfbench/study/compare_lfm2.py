#!/usr/bin/env python3
"""The LFM2 program against its plain reference at the published widths
and the cell's lengths, and the planted faults the comparison must see.

    python3 perfbench/study/compare_lfm2.py --seed 2147484242 \
        --out chiprun_out/p42c2/compare.jsonl

Prefill then decode through the serving cache (``compare_jamba.Runner``:
``BlockKVCache.for_model`` with the attention layers' blocks and the
convolution layers' tails, the model's serving forward jitted as the
engine's entries jit it, every layer's output carried out) against the
reference's full forward (``families/lfm2.forward``, float32 at
``highest``) on the same tokens:

- ``short``: two prompts of unequal length (``--short``, default 301 and
  498 rows) in ONE dispatch of the 512-row bucket, so each one's tail is
  taken at its own last token, then ``--steps`` tokens decoded greedily by
  both rows together;
- ``long``: one prompt of ``--long`` rows (default 1900) in the 2048-row
  bucket, then ``--steps`` tokens.

A comparison reads, worst over its rows: ``deficit_max`` (the harness's own
measure over the decoded positions), ``logit_max`` (the largest |program -
reference| logit there), ``inc_median`` / ``inc_worst_layer`` (per layer and
decoded row, |program's increment to the stream - reference's| /
|reference's increment|: the median over layers and rows, and the largest
over layers of the median over rows), ``inc_first_row`` (the largest over
layers of that ratio at the FIRST decoded row: a tail handed over wrongly
is wrong for two steps and then gone, which a median over 48 rows hides)
and ``tail_replay`` (the carried tail
of the first convolution layer after the last decoded token against the
tail the PROGRAM's own prefill of the same tokens, prompt and answer as one
prompt, leaves at that row: both round ``v`` to bfloat16 alike, so what is
left is how the tail was carried).

Faults, each replaying the clean run's tokens of the ``short`` case
(``--faults``, ``--fault-cases``): the CPU tests' (``tests/test_lfm2.py``)
``bias_weights``, ``no_bias``, ``tail_late``, ``tail_pads``,
``no_qk_norm``, and ``fp8_experts`` (every expert matrix rounded to
float8_e4m3's 3 bits of mantissa, :func:`fp8_mantissa`: the nearest
precision below the configuration's bfloat16; planted last, in place, and
not repaired). Two of the tests' faults stay with the CPU tests: the dense
layer run as a sparse one needs a second build of the weights; and
``expert_out`` (one expert left out of one layer; ``--faults`` still takes
it) touches the ~12% of one layer's rows that choose the expert, which
under bfloat16 at these widths no reading here separates whatever the
tokens: its ``inc_worst_layer`` read 0.0149 over one decoded sequence and
0.0081 over another, beside the clean 0.0061 (my chip runs, PR 42, calls
3 and 6). Exit code 0 when the clean program passes and every fault
fails.
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

#: limits of one comparison; a reading above any of them fails it. Each
#: stands between what the clean program read and what the fault it is
#: there to see read (my chip runs, PR 42, calls 2 and 3, at the committed
#: gain of the final norm, 0.005, clean ``short`` / ``long`` first; call 6
#: read the logits' two 2.5x lower at a gain of 0.002 and the others alike;
#: PERF.md section 6 has every reading, ``perfbench/study/runs_pr42.jsonl``
#: every line):
#:
#: - ``deficit_max`` 0.05, the harness's own limit: clean 0.0005 / 0.0; a
#:   tail handed over wrongly 0.166-0.183; no other fault passes it over
#:   these 96 positions (the missing bias 0.022; the cell's own run, 8
#:   requests of ~550 positions, reads it 0.063:
#:   ``init_sweep_lfm2.py --cell``).
#: - ``logit_max`` 0.07: clean 0.0493 / 0.0421. Not a rounding: the
#:   largest error is at a position where the program and the reference
#:   kept different experts (a router flip at a near-tie of the 4th and 5th
#:   biased score, ``deficit_cause_lfm2.py``), so the clean reading moves
#:   with the tokens; no bias in the choice 0.098, the tails 0.49-0.52.
#: - ``inc_median`` 0.012: clean 0.0055 / 0.0054; no bias 0.18, the
#:   experts at float8's mantissa 0.0153.
#: - ``inc_worst_layer`` 0.0095: clean 0.0061 / 0.0059; the bias in the
#:   weights 0.033, no q/k norm 0.084, float8 0.042.
#: - ``inc_first_row`` 0.1: clean 0.0064 / 0.0062; the tails 1.3, no q/k
#:   norm 0.19.
#: - ``tail_replay`` 1e-6: clean 0.0 exactly (the same bfloat16 rows,
#:   carried); the tails 1.4-1.5.
TOLERANCE = {"deficit_max": 0.05, "logit_max": 0.07, "inc_median": 0.012,
             "inc_worst_layer": 0.0095, "inc_first_row": 0.1,
             "tail_replay": 1e-6}
FAULTS = ("bias_weights", "no_bias", "tail_late", "tail_pads", "no_qk_norm",
          "fp8_experts")
BLOCK = 256     # the reference's attention takes rows in blocks of it


def fp8_mantissa(v):
    """``v`` rounded (to nearest, ties to even) to float8_e4m3's 3 bits
    of mantissa at its own exponent: what an fp8 weight path with an ideal
    scale would hold. In bit arithmetic: on this chip a cast to float8 and
    back is compiled away (the first chip run read the clean program's
    numbers to the digit)."""
    import jax
    import jax.numpy as jnp
    bits = {2: jnp.uint16, 4: jnp.uint32}[v.dtype.itemsize]
    drop = jnp.finfo(v.dtype).nmant - 3
    u = jax.lax.bitcast_convert_type(v, bits)
    u = (u + ((1 << (drop - 1)) - 1) + ((u >> drop) & 1)) >> drop << drop
    return jax.lax.bitcast_convert_type(u.astype(bits), v.dtype)


def runner_of(model, cfg):
    from compare_jamba import Runner

    class Lfm2Runner(Runner):
        """The watched state is a convolution layer's tail (the kind's one
        array)."""

        def _state_of(self, layer):
            import numpy as np
            return np.asarray(self.cache.arrays()[layer][0], np.float32)
    return Lfm2Runner(model, cfg)


def reference_of(family, params, cfg, seq, p, steps):
    """The reference on one sequence -> (logits of positions p - 1 .. p +
    steps - 2, every layer's output and the embedded input at positions
    p .. p + steps - 1 [steps, layers + 1, h])."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    pad = -(-len(seq) // BLOCK) * BLOCK
    ids = np.zeros((1, pad), np.int32)
    ids[0, :len(seq)] = seq

    @jax.jit
    def run(params, ids):
        states = []
        logits = family.forward(params, ids, cfg, collect=states)[0]
        emb = jnp.asarray(params["model.embed.weight"][ids[0]], jnp.float32)
        layers = jnp.stack([emb] + [s[0] for s in states], axis=1)
        return (jax.lax.dynamic_slice_in_dim(logits, p - 1, steps, 0),
                jax.lax.dynamic_slice_in_dim(layers, p, steps, 0))
    lg, layers = run(params, jnp.asarray(ids))
    return np.asarray(lg), np.asarray(layers)


def compare(got, refs):
    """``got`` a run's requests, ``refs`` the reference's of each -> the
    readings (the worst over the requests)."""
    import numpy as np
    out = {k: 0.0 for k in TOLERANCE}
    ratios = []
    for g, (lg, layers) in zip(got, refs):
        n = len(g["logits"])
        emitted = np.argmax(g["logits"], axis=-1)
        d = lg[:n].max(-1) - lg[np.arange(n), emitted]
        out["deficit_max"] = max(out["deficit_max"], float(d.max()))
        out["logit_max"] = max(out["logit_max"],
                               float(np.abs(g["logits"] - lg[:n]).max()))
        inc_p = g["layers"][:, 1:] - g["layers"][:, :-1]
        inc_r = layers[:, 1:] - layers[:, :-1]
        ratios.append(np.linalg.norm(inc_p - inc_r, axis=-1)
                      / np.linalg.norm(inc_r, axis=-1))    # [steps, layers]
        out["inc_first_row"] = max(out["inc_first_row"],
                                   float(ratios[-1][0].max()))
        out["tail_replay"] = max(out["tail_replay"], float(
            np.linalg.norm(g["state_end"] - g["state_replayed"])
            / np.linalg.norm(g["state_replayed"])))
    ratio = np.concatenate(ratios)
    out["inc_median"] = float(np.median(ratio))
    out["inc_worst_layer"] = float(np.median(ratio, axis=0).max())
    return out


def inject(runner, fault):
    """Break the program in one place; -> a function that repairs it."""
    import jax.numpy as jnp
    from init_sweep_lfm2 import plant
    model = runner.model
    runner._fns.clear()
    if fault == "fp8_experts":
        # in place, stack by stack (a second copy of 9.7 GB of experts does
        # not fit the chip), so this fault is planted last and not repaired
        import jax
        rounded = jax.jit(fp8_mantissa, donate_argnums=0)
        for blk in model.model.layers:
            if blk.sparse:
                for p in (blk.moe.experts_gate_up, blk.moe.experts_down):
                    p.value = rounded(p.value)
        return runner._fns.clear
    if fault == "expert_out":
        moe = next(b.moe for b in model.model.layers if b.sparse)
        was = moe.experts_down.value
    on_model, undo = plant(fault)
    on_model(model)

    def repair():
        undo()
        if fault == "expert_out":
            moe.experts_down.value = was
        if fault == "no_qk_norm":
            model.cfg.qk_norm = True
        runner._fns.clear()
    return repair


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="lfm2-24b-a2b-d9")
    ap.add_argument("--short", default="301,498")
    ap.add_argument("--long", type=int, default=1900)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--fault-cases", default="short",
                    help="the cases a fault replays (short,long)")
    ap.add_argument("--seed", type=int, default=2147484242)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at toy size; never a result")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import families, run as harness, traffic as T, weights
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        raise SystemExit("compare_lfm2 needs the TPU (or --allow-cpu)")
    chip.enable_compile_cache()
    folder = "configs" if os.path.exists(os.path.join(
        ROOT, "perfbench", "configs", args.config + ".json")) else "rehearsal"
    cfg = harness.load_json(ROOT, "perfbench", folder, args.config + ".json")
    family = families.load(cfg)
    with weights.recording() as specs:
        model = family.serving_model(cfg)
    weights.fill(model, specs, args.seed)
    model.eval()
    runner = runner_of(model, cfg)
    watch = model.cfg.layers_of("conv")[0]
    rng = np.random.default_rng([args.seed, 3])
    buckets = cfg["engine"]["buckets"]
    cases = {}
    short = [int(n) for n in args.short.split(",")]
    for name, lengths in (("short", short), ("long", [args.long])):
        seqs = [rng.integers(1, cfg["vocab_size"], n).tolist()
                for n in lengths]
        cases[name] = (seqs, lengths, T.bucket_for(max(lengths), buckets))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def note(rec):
        rec = dict(rec, tool="compare_lfm2.py", config=cfg["name"],
                   seed=args.seed, steps=args.steps, device=dev.device_kind)
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    def params_now():
        return {n: p.value for n, p in model.named_parameters()}
    ok, tokens, refs = True, {}, {}
    for name, (seqs, lengths, bucket) in cases.items():
        t = time.time()
        got = runner.run(seqs, lengths, bucket, args.steps, watch)
        tokens[name] = [g["tokens"] for g in got]
        refs[name] = [reference_of(family, params_now(), cfg, g["tokens"],
                                   p, args.steps)
                      for g, p in zip(got, lengths)]
        read = compare(got, refs[name])
        passed = all(read[k] <= TOLERANCE[k] for k in TOLERANCE)
        ok &= passed
        note({"case": name, "fault": None, "prompts": lengths,
              "bucket": bucket, "passes": passed, **read,
              "seconds": round(time.time() - t, 1)})
    # fp8_experts rewrites the weights in place: whatever the order asked
    faults = sorted((f for f in args.faults.split(",") if f),
                    key=lambda f: f == "fp8_experts")
    for fault in faults:
        repair = inject(runner, fault)
        try:
            for name, (seqs, lengths, bucket) in cases.items():
                if name not in args.fault_cases.split(","):
                    continue
                t = time.time()
                got = runner.run(tokens[name], lengths, bucket, args.steps,
                                 watch, greedy=False)
                read = compare(got, refs[name])
                over = [k for k in TOLERANCE if read[k] > TOLERANCE[k]]
                note({"case": name, "fault": fault, "prompts": lengths,
                      "bucket": bucket, "fails_by": over, **read,
                      "seconds": round(time.time() - t, 1)})
                if name == "short":
                    ok &= bool(over)
        finally:
            repair()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
