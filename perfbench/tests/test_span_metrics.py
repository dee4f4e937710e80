"""The per-layer metrics that read the program's own spans (PR 24): each
file under ``metrics/`` reads a hand-written ``obs`` to the number worked
out by hand, and finds nothing where the program has no such span (the
parent commit of PR 24, which the driver runs with these files)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import readers  # noqa: E402

#: seconds, as serve.py's ``spans`` group holds them
OBS = {
    "spans": {
        "serving.decode_step": [0.0420, 0.0440, 0.0430, 0.0900],
        "serving.decode": [0.0058, 0.0060, 0.0062],
        "serving.decode.inputs": [0.0050, 0.0046, 0.0048],
        "serving.decode.fetch": [0.0360, 0.0372, 0.0368, 0.0364],
        "serving.decode.commit": [0.0004, 0.0008, 0.0006],
        "serving.prefill_step": [0.25, 0.35, 0.30],
        "serving.schedule": [0.001, 0.002, 0.0005, 0.0005],
        "serving.token_gap": [0.043, 0.045, 0.044, 0.088],
    },
    "samples": {},
    "counters": {"window_s": 45.0},
    "trace": {},
}
#: metric stem -> the value OBS gives it
EXPECTED = {
    "decode_step_inside_p50_ms": 43.5,      # between 43.0 and 44.0
    "decode_inputs_p50_ms": 4.8,
    "decode_fetch_p50_ms": 36.6,            # between 36.4 and 36.8
    "decode_commit_p50_ms": 0.6,
    "prefill_inside_share_pct": 100.0 * 0.90 / 45.0,
    "sched_host_share_pct": 100.0 * 0.004 / 45.0,
    "token_gap_inside_mean_ms": 55.0,       # 0.220 / 4
}
FAMILIES = {stem: (("chat",) if stem == "token_gap_inside_mean_ms"
                   else ("chat", "docs")) for stem in EXPECTED}
NAMES = sorted(f"{stem}.{fam}" for stem, fams in FAMILIES.items()
               for fam in fams)


@pytest.mark.parametrize("name", NAMES)
def test_span_metric_reads_the_hand_written_obs(name):
    assert readers.read(name, OBS) == pytest.approx(
        EXPECTED[name.rsplit(".", 1)[0]])


@pytest.mark.parametrize("name", NAMES)
def test_span_metric_finds_nothing_in_a_program_without_the_span(name):
    parent = {"spans": {"serving.decode": [0.006], "serving.prefill": [0.3],
                        "bench.step": [0.043]},
              "samples": {}, "counters": {"window_s": 45.0}, "trace": {}}
    assert readers.read(name, parent) is None


def test_the_span_metrics_are_in_the_benchmark_as_program_spans():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cell = {"chat": "chat_steady", "docs": "docs_offline"}
    moves = {"chat": "itl_mean_ms", "docs": "serve_tok_s"}
    assert len(NAMES) == 13
    for name in NAMES:
        fam = name.rsplit(".", 1)[1]
        m, spec = entries[name], readers.spec(name)
        assert m["source"] == spec["source"] == "program_span"
        assert m["workloads"] == [cell[fam]]
        assert m["moves"] == spec["moves"] == moves[fam]
        assert spec["reader"]["from"] == "spans"
        assert spec["reader"]["name"].startswith("serving.")
