"""``BENCHMARK.json``'s ``per_layer`` against the files it names, a case an
``(entry, cell in its workloads)``, held to what a listing must keep and to
no name, suffix or count of today's (PR 37), and against its own history, a
case a row of each listing that was recorded.

Since PR 40 the listing is one entry a reader (``<base>.serve`` where the
entry moves ``serve_tok_s``, ``.train`` for ``train_tok_s_chip``, ``.chat``
for ``itl_mean_ms``; a file no other shares kept its name) and every file
under ``metrics/`` is listed. Two listings are recorded under ``data/``, a
row an (entry, cell) with its reader and fields: ``per_layer_pr36.json``
(128 entries, 138 rows, before PR 40's merge) and ``per_layer_pr56.json``
(127 entries, 358 rows, before PR 57's retirement). Nothing either reported
may be lost but what was retired by name: ``data/per_layer_renames.json``
leads from every name that went to the name that reads the same now, or to
``null``, and ``data/per_layer_retired.json`` says of each ``null`` which
listed entries to read instead in the row's cell (PR 40's four outside
halves, PR 57's outside half of the queue wait and seven busy shares of
kernels under 3% of the busy time)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from perfbench import families                      # noqa: E402

FIELDS = ("unit", "better", "source", "layer", "moves")


def load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


BENCH = load(os.pardir, "BENCHMARK.json")
ENTRIES = BENCH["per_layer"]
SPECS = {f[:-len(".json")]: load("metrics", f)
         for f in sorted(os.listdir(os.path.join(BENCH_DIR, "metrics")))}
CELLS = {c["name"]: c for c in BENCH["workloads"]}
#: the recorded listings, oldest first: (the PR it stood at, its rows)
HISTORY = [(pr, load("tests", "data", f"per_layer_pr{pr}.json"))
           for pr in (36, 56)]
RENAMES = load("tests", "data", "per_layer_renames.json")
RETIRED = load("tests", "data", "per_layer_retired.json")
LISTED = {m["name"]: m for m in ENTRIES}
PAIRS = [(m["name"], cell) for m in ENTRIES for cell in m["workloads"]]


def reports(cell: str, metric: str) -> bool:
    """Does ``cell`` report the end-to-end metric ``metric``?"""
    entry = next(m for m in BENCH["end_to_end"] if m["name"] == metric)
    return cell in entry.get("workloads", CELLS)


def key(spec: dict) -> tuple:
    return (json.dumps(spec["reader"], sort_keys=True), spec["moves"])


@pytest.mark.parametrize("name,cell", PAIRS)
def test_an_entry_agrees_with_its_file_in_a_cell_that_can_report_it(name,
                                                                    cell):
    entry, spec = LISTED[name], SPECS[name]
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
            cfg = load(os.pardir, entry_of["file"])
            job = load("traffic", CELLS[cell]["traffic"] + ".json")
            assert families.load(cfg).kernel_counts(kernel, cfg, job)


@pytest.mark.parametrize("row", [
    pytest.param(row, id=f"pr{pr}-{row['name']}-{row['cell']}")
    for pr, rows in HISTORY for row in rows])
def test_nothing_a_listing_reported_is_lost_but_what_was_retired_by_name(row):
    """A row of a recorded listing: one listed entry still has its reader
    and its ``moves`` in its cell, under the name the renames file leads
    to; or its name leads to ``null``, no entry reads it, and every entry
    the retirement names to read instead is listed for the row's cell."""
    now = RENAMES.get(row["name"], row["name"])
    mine = [m for m in ENTRIES if row["cell"] in m["workloads"]
            and key(SPECS[m["name"]]) == key(row)]
    if now is None:
        instead = RETIRED[row["name"]]["read_by"]
        assert not mine and instead and all(
            row["cell"] in LISTED[n]["workloads"] for n in instead)
        return
    assert [m["name"] for m in mine] == [now], (row["name"], row["cell"])
    assert all(mine[0][f] == row[f] for f in FIELDS)


def test_the_recorded_listings_and_the_trail_are_whole():
    assert [(pr, len(rows), len({r["name"] for r in rows}))
            for pr, rows in HISTORY] == [(36, 138, 128), (56, 358, 127)]
    # a name that went leads to a file that is there, or to nothing
    assert {r["name"] for r in HISTORY[0][1]} <= set(RENAMES)
    assert {v for v in RENAMES.values() if v is not None} <= set(SPECS)
    # and every name that leads to nothing says who retired it, on what
    # evidence of which ledger lines, and what reads it now
    assert set(RETIRED) == {k for k, v in RENAMES.items() if v is None}
    assert all(set(r) == {"retired_by", "read_by", "ledger_pr", "evidence"}
               and r["ledger_pr"] < r["retired_by"] and r["evidence"]
               for r in RETIRED.values())
    # PR 40 retired four names; PR 57 eight, each an entry of PR 56's listing
    gone = {pr: {k for k, r in RETIRED.items() if r["retired_by"] == pr}
            for pr in (40, 57)}
    assert (len(gone[40]), len(gone[57])) == (4, 8)
    assert gone[57] <= {r["name"] for r in HISTORY[1][1]}


def test_the_listing_is_one_entry_a_reader_and_every_file_is_read():
    assert set(LISTED) == set(SPECS)
    # the benchmark's contract (the driver's, not this repository's) takes
    # 1 to 128 per-layer metrics; a file past that is refused before a run
    assert len(LISTED) == len(ENTRIES) <= 128
    assert len({key(s) for s in SPECS.values()}) == len(SPECS)
    assert all(m["workloads"] for m in ENTRIES)
