"""``BENCHMARK.json``'s listing and the files under ``perfbench/metrics/`` as
they stand when the tests are collected, found by what they read: the tests
that walk them name a cell or a reader, never a metric's name, a suffix or
a count, so the benchmark may merge, rename and list its files as it likes.
"""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import families, run             # noqa: E402

FIELDS = ("unit", "better", "source", "layer", "moves")


def load(*parts):
    return run.load_json(ROOT, "perfbench", *parts)


BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELLS = {c["name"]: c for c in BENCH["workloads"]}
LISTED = {m["name"]: m for m in BENCH["per_layer"]}
#: every file under ``metrics/``, listed or waiting for room
SPECS = {f[:-len(".json")]: load("metrics", f) for f in sorted(
    os.listdir(os.path.join(ROOT, "perfbench", "metrics")))}
#: a case an (entry, cell in its workloads)
PAIRS = [(name, cell) for name, m in LISTED.items() for cell in m["workloads"]]
#: a case a (file, cell that reads it); a file no entry lists waits: None
FILE_PAIRS = [(name, cell) for name in SPECS for cell in
              (LISTED[name]["workloads"] if name in LISTED else [None])]


def reports(cell: str, metric: str) -> bool:
    """Does ``cell`` report the end-to-end metric ``metric``?"""
    return any(m["name"] == metric
               for m in run.metrics_of(BENCH, "end_to_end", cell))


def files_reading(name: str, **rest) -> list:
    """The metric files whose reader's ``name`` is ``name`` and whose other
    items are ``rest``."""
    return [n for n, spec in SPECS.items()
            if spec["reader"]["name"] == name
            and all(spec["reader"].get(k) == v for k, v in rest.items())]


def named(cell: str, reader_name: str) -> str:
    """The name of the one entry ``cell`` reports whose file reads
    ``reader_name``."""
    (name,) = [m["name"] for m in run.metrics_of(BENCH, "per_layer", cell)
               if SPECS[m["name"]]["reader"]["name"] == reader_name]
    return name


def kernel_of(spec: dict):
    """The kernel whose share of its roofline or of the busy time the file
    reads from the device trace, or None."""
    r = spec["reader"]
    for head in ("kernel_floor_s.", "kernel_s."):
        if r["from"] == "trace" and r["name"].startswith(head):
            return r["name"][len(head):]
    return None


@functools.lru_cache(maxsize=None)
def job_of(cell: str) -> tuple:
    """(the family, the configuration, the traffic file) of ``cell``."""
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == CELLS[cell]["config"])
    cfg = run.load_json(ROOT, entry["file"])
    return (families.load(cfg), cfg,
            load("traffic", CELLS[cell]["traffic"] + ".json"))


def counts(kernel: str, cell: str):
    """What the family of ``cell``'s configuration counts for one call of
    ``kernel`` in the cell's own job."""
    family, cfg, job = job_of(cell)
    return family.kernel_counts(kernel, cfg, job)
