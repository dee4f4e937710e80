#!/usr/bin/env python3
"""One run of a serving cell, printing what the result line cannot carry
while ``BENCHMARK.json``'s ``per_layer`` list is full: every metric file of
the cell's family that is not listed, read as ``run.py`` would read it.

    python3 perfbench/study/step_account.py --workload decode_heavy \
        --seed 3500000011 --trace 0 --out chiprun_out/account_pr35.jsonl

The run is ``perfbench/run.py``'s (``serve.run`` with the cell's own
configuration and traffic). The counter files (the step account of
``engine.stats()``: PR 35) read in an untraced run too; the span files need
``--trace 1``, which also gives ``listed`` (the cell's listed per-layer
metrics, for comparison) and ``modules`` (each program's executions and
busy seconds on the device in the traced part of the window). ``estimates``
is what the engine's SLO cost estimates stand at when the window closes.
On a tree without a span or counter its metrics are left out.

``--rehearsal`` runs the cell's toy twin as ``rehearse.py`` does (the
family's tiny configuration, the rehearsal traffic) off the TPU: it shows
which files were read, and withholds every value, since a number from a CPU
run is never written under the name of a device metric.
"""

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def suffix_of(bench: dict, cell: str) -> str:
    """The family suffix of the cell's own metrics (``decode`` for
    ``decode_heavy``): that of the listed metrics which name this cell
    alone."""
    own = [m["name"].rsplit(".", 1)[1] for m in bench["per_layer"]
           if m.get("workloads") == [cell] and "." in m["name"]]
    if not own:
        raise SystemExit(f"no per-layer metric is listed for {cell} alone")
    return max(set(own), key=own.count)


def device_modules(trace_dir: str) -> dict:
    """name -> [executions, seconds] of every program on the first device
    plane, inside the harness's window span."""
    from perfbench import xplane
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return {}
    trace = xplane.load(paths[0])
    window, _ = xplane._host_spans(trace)
    plane = next((p for p in trace["planes"]
                  if p["name"].startswith("/device:TPU:")
                  and xplane.MODULES_LINE in xplane._lines(p)), None)
    if window is None or plane is None:
        return {}
    lo, hi = window
    out = {}
    for name, start, dur in xplane._lines(plane)[xplane.MODULES_LINE]:
        if start >= lo and start + dur <= hi:
            rec = out.setdefault(name.split("(")[0], [0, 0.0])
            rec[0] += 1
            rec[1] += dur / 1e9
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from paddle_tpu.utils import chip
    from perfbench import families, flops, readers, run as R, serve
    on_tpu = jax.devices()[0].platform == "tpu"
    if not (on_tpu or args.rehearsal):
        raise SystemExit("a measurement needs the TPU (--rehearsal runs "
                         "the toy twin anywhere and withholds the values)")
    chip.enable_compile_cache()
    bench = R.load_json(ROOT, "BENCHMARK.json")
    cell = R.find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = R.load_json(ROOT, entry["file"])
    traffic_dir = "traffic"
    if args.rehearsal:
        cfg = R.load_json(R.HERE, "rehearsal",
                          families.name_of(cfg) + "-tiny.json")
        traffic_dir = "rehearsal"
    traffic = R.load_json(R.HERE, traffic_dir, cell["traffic"] + ".json")
    os.makedirs(R.OUT, exist_ok=True)

    built = []
    build = serve.build_engine

    def keep(cfg, seed):       # the engine, for its estimates afterwards
        built.append(build(cfg, seed))
        return built[-1]
    serve.build_engine = keep
    e2e, obs, counts = serve.run(cell, cfg, traffic, args.seed, args.seconds,
                                 bool(args.trace), R.OUT, R.T_START)
    engine = built[-1][1]

    device = R.device_report(int(cell["chips"]))
    if "memory_peak_bytes" in device:
        obs["counters"]["peak_hbm_bytes"] = device["memory_peak_bytes"]
    tr = obs.get("trace") or {}
    if tr:
        family = families.load(cfg)
        tr.update(flops.kernel_floors(
            tr, lambda name: family.kernel_counts(name, cfg, traffic),
            device["kind"]))
    suffix = suffix_of(bench, cell["name"])
    listed = {m["name"] for m in bench["per_layer"]}
    files = sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(R.HERE, "metrics")) if f.endswith(f".{suffix}.json"))

    def read_all(names):
        values = {n: readers.read(n, obs) for n in names}
        return {n: v if on_tpu else None
                for n, v in values.items() if v is not None}

    # the step account's keys (none on a tree before PR 35) and PR 34's
    # two, as the window's differences
    from paddle_tpu.serving import engine as engine_mod
    account = getattr(engine_mod, "_ACCOUNT_KEYS", ()) + (
        "ahead_dispatches", "sampler_dispatches")
    rec = {
        "tag": args.tag, "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "trace": args.trace, "end_to_end": e2e,
        "correct": bool(counts.get("correct")),
        "unlisted": read_all(n for n in files if n not in listed),
        "estimates": {
            "tpot_cost_ms": engine._tpot_cost_ms(),
            "prefill_cost_ms": {str(b): engine._prefill_cost_ms(b)
                                for b in sorted(engine._prefill_ewma)}},
        "account": {k: obs["counters"]["engine." + k] for k in account
                    if "engine." + k in obs["counters"]},
        "compiles_in_window": obs["counters"].get("compiles_in_window"),
        "device": device,
    }
    if args.trace:
        rec["listed"] = read_all(
            m["name"] for m in R.metrics_of(bench, "per_layer", cell["name"]))
        if tr:
            rec["busy_s"], rec["window_s"] = tr["busy_s"], tr["window_s"]
            rec["modules"] = device_modules(os.path.join(R.OUT, "trace"))
    if not on_tpu:
        rec["end_to_end"] = dict.fromkeys(e2e)
        rec["estimates"] = rec["account"] = "withheld: not a TPU run"
    line = json.dumps(rec)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
