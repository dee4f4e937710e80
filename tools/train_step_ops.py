#!/usr/bin/env python
"""Who owns a training cell's device time: every instruction of the
compiled train step with its milliseconds a step in a device trace, its
results, and the ``op_name`` the compiler kept for it (the program's
``jax.named_scope``s and the ops the tape traced, e.g. ``vocab_loss``).

The benchmark's ``breakdown`` names an op by its instruction's stem and
first result (``convert_reduce_fusion_f32_8_1024_kOutput``), which says
what XLA made and not whose it is; this reads the same trace by
instruction and looks each one up in ``Compiled.as_text()`` of the very
step (``step.lower(*batch).compile()``: the same program, from the
compile cache). Builds the cell as ``perfbench/train.py`` does, warms it,
traces ``--steps`` steps. Needs the cell's chips.

    python3 tools/train_step_ops.py --workload pretrain_1chip --seed 7 \
        --out chiprun_out/<call>/ops.json --hlo chiprun_out/<call>/step.hlo.txt

Prints one JSON line: ``step_ms`` (host clock, p50 of the traced steps),
``busy_ms_a_step``, ``temp_bytes`` / ``argument_bytes`` / ``output_bytes`` /
``alias_bytes`` of ``memory_analysis()``, the device's
``peak_bytes_in_use``, ``scopes`` (ms a step under each name of
``--scopes``, matched in ``op_name``), and ``ops``: the ``--top`` largest
instructions. ``--out`` keeps every instruction.
"""

import argparse
import json
import math
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KIND = re.compile(r"kind=(k\w+)")
_CALLS = re.compile(r"calls=%(\S+?)[,\s)]")


def instructions(text: str) -> dict:
    """instruction name -> {results, opcode, kind, op_name, convolutions,
    fused} over every computation of an optimised HLO text;
    ``convolutions`` counts the products inside a fusion's called
    computation, ``fused`` says the instruction is inside one (its result
    is a register's, not memory's)."""
    from perfbench import xplane
    fused_bodies = set(_CALLS.findall(text))
    out, convs, current = {}, {}, None
    for ln in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(", ln)
        if head:
            current = head.group(1)
            convs[current] = 0
            continue
        m = xplane._INSTR.match(re.sub(r"^ROOT ", "", ln.strip()))
        if not m:
            continue
        convs[current] += int(m.group("op") == "convolution")
        kind, meta, calls = (_KIND.search(ln), _OP_NAME.search(ln),
                             _CALLS.search(ln))
        out[m.group("name")] = {
            "results": re.sub(r"\{[^}]*\}", "", m.group("shape")),
            "opcode": m.group("op"), "kind": kind.group(1) if kind else "",
            "op_name": meta.group(1) if meta else "",
            "calls": calls.group(1) if calls else None,
            "fused": current in fused_bodies}
    for info in out.values():
        body = info.pop("calls")
        info["convolutions"] = (convs.get(body, 0) if body
                                else int(info["opcode"] == "convolution"))
    return out


def written_float32(table: dict, values: int, vocab: int) -> list:
    """The float32 arrays of the logits' size (``values`` of them, the
    last extent ``vocab``) that the step WRITES: results of instructions
    outside fused computations."""
    want = re.compile(r"f32\[((?:\d+,)*%d)\]" % vocab)
    return [f"{name}: f32[{dims}]" for name, info in table.items()
            if not info["fused"]
            for dims in want.findall(info["results"])
            if math.prod(int(d) for d in dims.split(",")) == values]


def device_ops(trace: dict, table: dict, steps: int) -> list:
    """Every instruction a device ran in a trace of ``steps`` steps, largest
    first: ms a step (a mean over the chips), calls a step, the breakdown's
    name for it and what ``table`` knows of it."""
    from perfbench import xplane
    lines = [ln["events"] for p in trace["planes"]
             if p["name"].startswith("/device:TPU:")
             for ln in p["lines"] if ln["name"] == xplane.OPS_LINE]
    per = {}
    for events in lines:
        for name, _, dur in events:
            m = xplane._INSTR.match(name)
            got = per.setdefault(m.group("name") if m else name[:60],
                                 [0.0, 0, name])
            got[0] += dur
            got[1] += 1
    share = 1.0 / len(lines) / steps
    return [{"instruction": key, "short": xplane.short_name(name),
             "ms_a_step": 1e-6 * ns * share, "calls_a_step": calls * share,
             **table.get(key, {})}
            for key, (ns, calls, name) in sorted(per.items(),
                                                 key=lambda kv: -kv[1][0])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument("--scopes", default="vocab_loss,flash_attention")
    ap.add_argument("--out", default=None)
    ap.add_argument("--hlo", default=None)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the toy twin on the CPU: no trace, no times")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from paddle_tpu.utils import chip
    from perfbench import families, run as bench_run, train, xplane
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = os.path.join(ROOT, "perfbench")
    cfg, traffic_dir = bench_run.load_json(ROOT, entry["file"]), "traffic"
    if args.rehearsal:
        cfg = bench_run.load_json(
            here, "rehearsal", families.name_of(cfg) + "-tiny.json")
        traffic_dir = "rehearsal"
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("a device trace needs the TPU")
    chip.enable_compile_cache()
    traffic = bench_run.load_json(here, traffic_dir,
                                  cell["traffic"] + ".json")
    chips = int(cell["chips"])
    model, make_step, mesh = train.build(cfg, traffic, args.seed)
    step = make_step()
    batch = int(traffic["batch_per_chip"]) * chips
    make, _ = train.batch_maker(args.seed, batch, int(traffic["seq"]),
                                int(cfg["vocab_size"]), mesh)
    # the logits' last extent: the rows the program's head holds
    vocab = int(cfg.get("program", {}).get("vocab_rows", cfg["vocab_size"]))
    for n in range(int(traffic["warm_steps"])):
        train.fetch(step(*make(n)))
        if n == 0 and traffic.get("rewrap_after_first_step"):
            step = make_step()
    compiled = step.lower(*make(0)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    if args.hlo:
        os.makedirs(os.path.dirname(args.hlo) or ".", exist_ok=True)
        with open(args.hlo, "w") as f:
            f.write(text)
    table = instructions(text)
    line = {"tool": "tools/train_step_ops.py", "workload": args.workload,
            "seed": args.seed, "platform": jax.devices()[0].platform,
            "steps": args.steps,
            "temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "float32_logits_written": written_float32(
                table, batch // chips * int(traffic["seq"]) * vocab, vocab)}
    if not args.rehearsal:
        trace_dir = os.path.join(here, ".out", "step_ops_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        batches = [make(100 + i) for i in range(args.steps)]
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        step_s = []
        for b in batches:
            t0 = time.perf_counter()
            train.fetch(step(*b))
            step_s.append(time.perf_counter() - t0)
        jax.profiler.stop_trace()
        pb = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
              for f in fs if f.endswith(".xplane.pb")][0]
        ops = device_ops(xplane.load(pb), table, args.steps)
        stats = jax.devices()[0].memory_stats() or {}
        line["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        line["step_ms"] = 1e3 * float(np.median(step_s))
        line["busy_ms_a_step"] = sum(o["ms_a_step"] for o in ops)
        line["scopes"] = {
            s: sum(o["ms_a_step"] for o in ops if s in o.get("op_name", ""))
            for s in args.scopes.split(",") if s}
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({**line, "ops": ops}, f)
        line["ops"] = ops[:args.top]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
