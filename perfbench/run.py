#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, its traffic mix and every per-layer metric are
files found by the names in ``BENCHMARK.json`` (``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json``), and the
configuration names its architecture's family (``families/<family>``): a
later PR adds a cell, a configuration, an architecture or a metric over an
existing span, counter or kernel by adding files and an entry, and edits
nothing here.

The LAST line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``);
with ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. The run needs a TPU with the chips the
cell asks for: anything else exits non-zero and prints no result. It reads
and writes inside the checkout only (compile cache in ``.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` says otherwise, traces in ``perfbench/.out``).
"""

import time
T_START = time.perf_counter()       # set-up is counted from here

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, ".out")
RUNNERS = {"open_loop": "serve", "closed_loop": "serve", "train": "train"}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                     f"(known: {[c['name'] for c in bench['workloads']]})")


def metrics_of(bench: dict, group: str, cell: str):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def device_report(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(jax.devices())}
    if all(p is not None for p in peaks):
        out["memory_peak_bytes"] = int(max(peaks))
    return out


def run_cell(bench: dict, args, rehearsal: bool = False,
             traffic_dir: str = "traffic", **extra):
    """One run of the cell -> the result line as a dict. ``rehearsal`` lets
    the cell run off the TPU and ``traffic_dir`` names the directory of its
    traffic file (``rehearse.py`` uses both)."""
    sys.path.insert(0, ROOT)
    import jax
    cell = find_cell(bench, args.workload)
    chips = int(cell["chips"])
    devs = jax.devices()
    if not rehearsal and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"{args.workload} needs {chips} TPU chip(s); JAX reports "
            f"{len(devs)} x {devs[0].platform}. The benchmark never falls "
            f"back to another device.")
    from paddle_tpu.utils import chip
    chip.enable_compile_cache()
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, traffic_dir, cell["traffic"] + ".json")
    os.makedirs(OUT, exist_ok=True)
    from perfbench import families, flops, readers, serve, train
    runner = {"serve": serve, "train": train}[RUNNERS[traffic["kind"]]]
    e2e, obs, counts = runner.run(cell, cfg, traffic, args.seed,
                                  float(args.seconds), bool(args.trace),
                                  OUT, T_START, **extra)
    device = device_report(chips)
    if "memory_peak_bytes" in device:
        obs["counters"]["peak_hbm_bytes"] = device["memory_peak_bytes"]
    metrics = {}
    tr = obs.get("trace") or {}
    if tr:
        family = families.load(cfg)
        tr.update(flops.kernel_floors(
            tr, lambda name: family.kernel_counts(name, cfg, traffic),
            device["kind"]))
    if args.trace:
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = readers.read(m["name"], obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if tr:
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
    else:
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] not in e2e:
                raise SystemExit(f"{cell['name']} did not produce "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line = {"correct": bool(counts.pop("correct")),
            "attempted": int(counts.pop("attempted")),
            "failed": int(counts.pop("failed")),
            "metrics": metrics, "device": device}
    if args.trace and tr:
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["notes"] = counts
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    line = run_cell(bench, args)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
