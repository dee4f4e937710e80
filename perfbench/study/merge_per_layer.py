#!/usr/bin/env python3
"""ISSUE 37's (a), as a program: one ``per_layer`` entry a reader with a
``workloads`` list instead of a copy a cell.

    python3 perfbench/study/merge_per_layer.py --out <directory>

It reads ``BENCHMARK.json`` and ``perfbench/metrics/`` as they stand and
writes ``<directory>/BENCHMARK.json`` and ``<directory>/metrics/*.json``:
the listing a ``benchmark`` PR commits once the tier-1 tests stop pinning
today's names and counts (PERF.md section 7 has the pins). Nothing of the
repo is changed. The rules:

- files with the same ``reader``, ``unit``, ``better``, ``source``,
  ``layer`` and ``moves`` become one file and one entry; its ``workloads``
  are the copies' cells (an unlisted copy's cell is its suffix's), its name
  ``<base>.serve`` where it moves ``serve_tok_s``, ``<base>.train`` for
  ``train_tok_s_chip``, ``<base>.chat`` for ``itl_mean_ms``;
- the first copy's ``what`` stands for all, and what another copy says
  beyond it or instead is kept under ``what_in[<cell>]`` (``readers.py``
  reads ``reader`` only);
- a file no other shares stays as it is, and is listed if it was not;
- the outside halves ``decode_step_p50_ms.*`` and ``prefill_share_pct.*``
  go (their inside halves read the same to the percent: ledger, PR 34).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIELDS = ("unit", "better", "source", "layer", "moves")
SUFFIX_CELLS = {"chat": ["chat_steady"], "docs": ["docs_offline"],
                "decode": ["decode_heavy"], "mellum": ["mellum_code_16k"],
                "jamba": ["jamba_reasoning_6k"],
                "moe": ["laguna_pretrain_8k"],
                "train": ["pretrain_1chip", "pretrain_zero2_dp4"]}
SUFFIX_OF = {"serve_tok_s": "serve", "train_tok_s_chip": "train",
             "itl_mean_ms": "chat"}
RETIRED = ("decode_step_p50_ms", "prefill_share_pct")


def key_of(spec: dict) -> tuple:
    """What two copies of one metric share."""
    return (json.dumps(spec["reader"], sort_keys=True),
            *(spec[f] for f in FIELDS))


def merged(bench: dict, specs: dict):
    """(``per_layer`` entries, name -> metric file) of the merged listing.
    ``specs`` is name -> metric file of every file under ``metrics/``."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    order = [c["name"] for c in bench["workloads"]]
    groups: dict = {}
    # listed files first, in the listing's order, so that order survives
    for name in [*listed, *sorted(set(specs) - set(listed))]:
        base, suffix = name.rsplit(".", 1)
        if base in RETIRED:
            continue
        cells = listed[name]["workloads"] if name in listed \
            else SUFFIX_CELLS[suffix]
        groups.setdefault(key_of(specs[name]), []).append((name, cells))
    entries, files = [], {}
    for copies in groups.values():
        first = specs[copies[0][0]]
        bases = {name.rsplit(".", 1)[0] for name, _ in copies}
        if len(bases) != 1:
            raise SystemExit(f"one reader under two names: {sorted(bases)}")
        name = copies[0][0] if len(copies) == 1 \
            else f"{bases.pop()}.{SUFFIX_OF[first['moves']]}"
        cells = sorted({c for _, cs in copies for c in cs}, key=order.index)
        spec = dict(first)
        whats = {c: specs[n]["what"] for n, cs in copies for c in cs}
        # the oldest copy's sentence stands for all; a copy that says more
        # or something else keeps that under its cell
        said = first["what"]
        own = {c: w[len(said):].lstrip(";:., ") if w.startswith(said) else w
               for c, w in whats.items() if w != said}
        if own:
            spec["what_in"] = {c: own[c] for c in cells if c in own}
        files[name] = spec
        entries.append({"name": name, **{f: spec[f] for f in FIELDS},
                        "workloads": cells})
    return entries, files


def load(root: str):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = os.path.join(root, "perfbench", "metrics")
    specs = {}
    for name in sorted(os.listdir(metrics)):
        with open(os.path.join(metrics, name)) as f:
            specs[name[:-len(".json")]] = json.load(f)
    return bench, specs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench, specs = load(ROOT)
    entries, files = merged(bench, specs)
    os.makedirs(os.path.join(args.out, "metrics"), exist_ok=True)
    for name, spec in files.items():
        with open(os.path.join(args.out, "metrics", name + ".json"),
                  "w") as f:
            json.dump(spec, f, indent=1)
            f.write("\n")
    with open(os.path.join(args.out, "BENCHMARK.json"), "w") as f:
        json.dump({**bench, "per_layer": entries}, f, indent=1)
        f.write("\n")
    by_cell = {c["name"]: sum(c["name"] in m["workloads"] for m in entries)
               for c in bench["workloads"]}
    print(json.dumps({"entries_before": len(bench["per_layer"]),
                      "files_before": len(specs),
                      "entries_after": len(entries),
                      "files_after": len(files), "by_cell": by_cell}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
