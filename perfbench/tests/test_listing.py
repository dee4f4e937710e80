"""``BENCHMARK.json``'s ``per_layer`` against the files it names, a case an
``(entry, cell in its workloads)``, held to what a listing must keep and to
no name, suffix or count of today's (PR 37).

Two listings pass through the same cases: ``committed`` (``BENCHMARK.json``
and ``metrics/`` as they stand) and ``merged`` (what
``study/merge_per_layer.py`` makes of them: one entry a reader). The second
is ISSUE 37's (a), proven here and not committed, because the tier-1 tests
pin today's listing by name and count (PERF.md section 7): once they walk a
listing as these cases do, a ``benchmark`` PR commits the merged one and the
``merged`` column goes.

``data/per_layer_pr36.json`` is the listing at PR 36, a row an (entry,
cell): nothing it reports may be lost but the four outside halves."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(BENCH_DIR, "study"))

import merge_per_layer                              # noqa: E402
from perfbench import families                      # noqa: E402

FIELDS = merge_per_layer.FIELDS
BENCH, SPECS = merge_per_layer.load(ROOT)
LISTINGS = {
    "committed": (BENCH["per_layer"],
                  {m["name"]: SPECS[m["name"]] for m in BENCH["per_layer"]}),
    "merged": merge_per_layer.merged(BENCH, SPECS)}
CELLS = {c["name"]: c for c in BENCH["workloads"]}
with open(os.path.join(HERE, "data", "per_layer_pr36.json")) as f:
    AT_PR36 = json.load(f)
PAIRS = [(listing, m["name"], cell) for listing, (entries, _) in
         LISTINGS.items() for m in entries for cell in m["workloads"]]


def reports(cell: str, metric: str) -> bool:
    """Does ``cell`` report the end-to-end metric ``metric``?"""
    entry = next(m for m in BENCH["end_to_end"] if m["name"] == metric)
    return cell in entry.get("workloads", CELLS)


def key(spec: dict) -> tuple:
    return (json.dumps(spec["reader"], sort_keys=True), spec["moves"])


def load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("listing,name,cell", PAIRS)
def test_an_entry_agrees_with_its_file_in_a_cell_that_can_report_it(
        listing, name, cell):
    entries, files = LISTINGS[listing]
    entry = next(m for m in entries if m["name"] == name)
    spec = files[name]
    assert all(spec[f] == entry[f] for f in FIELDS)
    assert set(entry) == {"name", *FIELDS, "workloads"}
    assert cell in CELLS and reports(cell, entry["moves"])
    assert {"from", "name"} <= set(spec["reader"]) and spec["what"]
    assert set(spec.get("what_in", {})) <= set(entry["workloads"])
    # a kernel's share: the reader names the kernel, and the cell's family
    # counts it for the cell's job
    stem = name.rsplit(".", 1)[0]
    for tail, over in (("_roofline_pct", "trace.kernel_s.{}"),
                       ("_busy_pct", "trace.busy_s")):
        if stem.endswith(tail):
            kernel = stem[:-len(tail)]
            assert spec["reader"]["over"] == over.format(kernel)
            assert spec["reader"]["name"].endswith("." + kernel)
            entry_of = next(c for c in BENCH["configs"]
                            if c["name"] == CELLS[cell]["config"])
            cfg = load(os.path.relpath(os.path.join(ROOT, entry_of["file"]),
                                       BENCH_DIR))
            job = load("traffic", CELLS[cell]["traffic"] + ".json")
            assert families.load(cfg).kernel_counts(kernel, cfg, job)


@pytest.mark.parametrize("listing", sorted(LISTINGS))
def test_nothing_pr36_reported_is_lost_but_the_outside_halves(listing):
    entries, files = LISTINGS[listing]
    retired = [r for r in AT_PR36
               if r["name"].rsplit(".", 1)[0] in merge_per_layer.RETIRED]
    assert len(AT_PR36) == 138 and len(retired) == 4
    for row in AT_PR36:
        if row in retired and listing == "merged":
            continue
        mine = [m for m in entries if row["cell"] in m["workloads"]
                and key(files[m["name"]]) == key(row)]
        assert len(mine) == 1, (row["name"], row["cell"])
        assert all(mine[0][f] == row[f] for f in FIELDS)


def test_the_merged_listing_is_one_entry_a_reader_with_room_to_spare():
    entries, files = LISTINGS["merged"]
    assert len(entries) == len(files) == 101 <= 104
    assert len({key(s) for s in files.values()}) == len(files)
    assert len({m["name"] for m in entries}) == len(entries)
    # every file of today's is read by an entry of the merged listing
    readers_of = {key(s) for s in files.values()}
    unread = [n for n, s in SPECS.items() if key(s) not in readers_of]
    assert sorted(n.rsplit(".", 1)[0] for n in unread) == \
        ["decode_step_p50_ms"] * 2 + ["prefill_share_pct"] * 2
    jamba = {m["name"] for m in entries
             if "jamba_reasoning_6k" in m["workloads"]}
    assert len(jamba) >= 20 and jamba >= {
        "device_idle_pct.serve", "peak_hbm_gib.serve",
        "mosaic_time_pct.serve", "sched_occupancy_pct.serve",
        "decode_device_ms_per_step.serve", "state_gib.jamba"}
    train = next(m for m in entries if m["name"] == "mfu_pct.train")
    assert train["workloads"] == ["pretrain_1chip", "pretrain_zero2_dp4",
                                  "laguna_pretrain_8k"]


def test_the_committed_listing_is_full_and_fifty_files_wait():
    """What PR 37 found and could not change (the tier-1 pins): 128 of 128
    entries, 39 of them copies, 50 files no run reads."""
    entries, _ = LISTINGS["committed"]
    assert len(entries) == 128 and len(SPECS) == 178
    assert len({key(SPECS[m["name"]]) for m in entries}) == 128 - 39
