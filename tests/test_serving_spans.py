"""The serving step seen from inside: the engine's tree of program spans
(``profiler.RecordEvent`` with identity) and the per-token stamps.

The contracts under test, on ``gpt2-tiny`` on the CPU:

- with the profiler on, one engine run gives every ``serving.*`` span
  but the root (``serving.engine_step``) a ``parent`` that encloses it
  in time, the children of one parent do not overlap, and every name's
  ``self_ms`` is non-negative;
- the kept spans did not move: ``serving.decode`` still closes at
  dispatch, so the ``serving.decode.fetch`` of the same
  ``serving.decode_step`` starts at or after its end;
- ``Request.token_at`` holds one ascending stamp per committed token,
  the first one ``first_token_at`` itself, and each gap between two of
  them is one ``serving.token_gap`` event — with the profiler off the
  stamps are still there and no event is;
- the single step (f32, int8 pools, with a LoRA pool) and the
  speculative step speak the same names;
- nothing of this reaches ``observability/tracing.py``: a seeded
  virtual-clock run exports the same bytes with the profiler on and off
  and marks nothing new.
"""

import contextlib
import io
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.models import GPT_CONFIGS, GPTForCausalLM
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from tools.loadgen import LoadGen, VirtualClock

GEOM = dict(max_slots=4, max_len=64, buckets=[16, 32])
PATHS = {
    "paged": dict(block_size=8, num_blocks=40),
    "int8": dict(block_size=8, num_blocks=40, kv_dtype="int8"),
    "lora": dict(block_size=8, num_blocks=40, lora_rank=2),
    "spec2": dict(block_size=8, num_blocks=40, spec_tokens=2),
}
WORK = ((5, 6), (9, 4), (20, 7))      # (prompt tokens, new tokens)
#: every span of the step tree: name -> the parent's name
TREE = {
    "serving.engine_step": None,
    "serving.schedule": "serving.engine_step",
    # a prefill group is a flight (PR 48): dispatched in the round's
    # admission, fetched and committed after the decode step behind it is
    # on the device; ``serving.prefill_step`` is drawn from its stamps,
    # dispatch to commit, and has no parent (as ``serving.flight``)
    "serving.prefill_step": None,
    "serving.prefill": "serving.engine_step",
    "serving.prefill.fetch": "serving.engine_step",
    "serving.prefill.commit": "serving.engine_step",
    "serving.decode_step": "serving.engine_step",
    "serving.decode": "serving.decode_step",
    "serving.decode.inputs": "serving.decode",
    "serving.decode.fetch": "serving.decode_step",
    "serving.decode.commit": "serving.decode_step",
}


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    m = GPTForCausalLM(GPT_CONFIGS["gpt2-tiny"])
    m.eval()
    return m


def _run(model, path, tmp_path, profile=True):
    """Warm the engine's shapes, then serve WORK; returns (requests,
    the window's events, the profiler's summary)."""
    eng = ServingEngine(model, **GEOM, **PATHS[path])
    rng = np.random.default_rng(3)
    vocab = GPT_CONFIGS["gpt2-tiny"].vocab_size
    eng.submit(rng.integers(1, vocab, size=4).tolist(), max_new_tokens=3)
    eng.run_until_idle()
    out = tmp_path / f"{path}.json"
    if profile:
        profiler.start_profiler()
    reqs = [eng.submit(rng.integers(1, vocab, size=n).tolist(),
                       max_new_tokens=m) for n, m in WORK]
    eng.run_until_idle()
    if not profile:
        return reqs, [], []
    with contextlib.redirect_stdout(io.StringIO()):
        summary = profiler.stop_profiler(profile_path=str(out))
    return reqs, json.loads(out.read_text())["traceEvents"], summary


@pytest.fixture(scope="module")
def paged_run(model, tmp_path_factory):
    return _run(model, "paged", tmp_path_factory.mktemp("spans"))


def _tree(events):
    return [e for e in events if e["name"] in TREE]


def test_every_span_has_a_parent_that_encloses_it(paged_run):
    _, events, _ = paged_run
    by_id = {e["id"]: e for e in events}
    assert len(by_id) == len(events)            # ids are unique
    seen = set()
    for e in _tree(events):
        seen.add(e["name"])
        if TREE[e["name"]] is None:
            assert e["parent"] is None
            continue
        parent = by_id[e["parent"]]
        assert parent["name"] == TREE[e["name"]], (e, parent)
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
        assert parent["tid"] == e["tid"]
    assert seen == set(TREE)                    # the whole tree ran


def test_children_of_one_parent_do_not_overlap(paged_run):
    _, events, summary = paged_run
    kids = {}
    for e in _tree(events):
        kids.setdefault(e["parent"], []).append(e)
    for parent, group in kids.items():
        if parent is None:
            continue
        group.sort(key=lambda e: e["ts"])
        for a, b in zip(group, group[1:]):
            assert a["ts"] + a["dur"] <= b["ts"], (a, b)
    assert all(s["self_ms"] >= 0.0 for s in summary)
    by_name = {s["name"]: s for s in summary}
    # a parent's self time is what its children leave of it
    step, total = by_name["serving.decode_step"], 0.0
    for child in ("serving.decode", "serving.decode.fetch",
                  "serving.decode.commit"):
        total += by_name[child]["total_ms"]
    assert step["self_ms"] == pytest.approx(step["total_ms"] - total,
                                            abs=1e-6)
    assert by_name["serving.decode.fetch"]["self_ms"] == \
        pytest.approx(by_name["serving.decode.fetch"]["total_ms"])


def test_kept_decode_span_still_closes_at_dispatch(paged_run):
    _, events, _ = paged_run
    steps = [e for e in events if e["name"] == "serving.decode_step"]
    assert steps
    for step in steps:
        mine = {e["name"]: e for e in events if e["parent"] == step["id"]}
        decode = mine["serving.decode"]
        if "serving.decode.fetch" not in mine:
            # an admission into an idle engine: the round dispatched the
            # step behind its prefill and had none in flight to commit
            assert decode["args"]["launches"] == 1
            continue
        fetch = mine["serving.decode.fetch"]
        assert fetch["ts"] >= decode["ts"] + decode["dur"]
        assert mine["serving.decode.commit"]["ts"] >= \
            fetch["ts"] + fetch["dur"]
        assert step["args"]["n"] == 1 and step["args"]["active"] >= 1
        assert mine["serving.decode.commit"]["args"]["tokens"] == \
            step["args"]["active"]
    numbered = [e["args"]["step"] for e in events
                if e["name"] == "serving.engine_step"]
    assert numbered == list(range(numbered[0],
                                  numbered[0] + len(numbered)))


def test_token_stamps_and_gap_events(paged_run):
    reqs, events, _ = paged_run
    gaps = [e for e in events if e["name"] == "serving.token_gap"]
    tokens = sum(len(r.tokens) for r in reqs)
    assert tokens == sum(m for _, m in WORK)
    assert len(gaps) == tokens - len(reqs)
    assert all(e["parent"] is None and e["dur"] >= 0.0 for e in gaps)
    for r in reqs:
        assert len(r.token_at) == len(r.tokens)
        assert r.token_at[0] == r.first_token_at
        assert r.token_at == sorted(r.token_at)
        assert r.token_at[-1] <= r.finished_at
        mine = sorted((e for e in gaps if e["args"]["request"] == r.id),
                      key=lambda e: e["ts"])
        # the events are the stamps' differences, on the spans' clock
        want = np.diff(r.token_at) * 1e6
        assert [e["dur"] for e in mine] == pytest.approx(list(want),
                                                         abs=1e-3)
        assert mine[0]["ts"] == pytest.approx(r.token_at[0] * 1e6)


@pytest.mark.parametrize("path", ["int8", "lora", "spec2"])
def test_every_path_speaks_the_same_names(model, tmp_path, path):
    reqs, events, summary = _run(model, path, tmp_path)
    # the parentless spans, recorded from stamps (PR 35 added two), and
    # the compile account's (PR 52): a path that is not warmed builds its
    # programs inside the window, and each says so
    names = {e["name"] for e in events} - {
        "serving.token_gap", "serving.flight", "serving.ttft",
        "program.build", "program.trace"}
    want = set(TREE)
    if path == "spec2":     # the kept span of the verify step
        want = (want - {"serving.decode"}) | {"serving.verify"}
    assert names == want
    by_id = {e["id"]: e for e in events}
    for e in events:
        if e["name"] == "serving.decode.inputs":
            assert by_id[e["parent"]]["name"] in ("serving.decode",
                                                  "serving.verify")
    n = 3 if path == "spec2" else 1
    assert {e["args"]["n"] for e in events
            if e["name"] == "serving.decode_step"} == {n}
    gaps = sum(e["name"] == "serving.token_gap" for e in events)
    assert gaps == sum(len(r.tokens) for r in reqs) - len(reqs)
    assert all(s["self_ms"] >= 0.0 for s in summary)
    for r in reqs:
        assert r.token_at[0] == r.first_token_at
        assert r.token_at == sorted(r.token_at)
        assert len(r.token_at) == len(r.tokens)


def test_profiler_off_records_nothing_and_still_stamps(model, tmp_path):
    reqs, _, _ = _run(model, "paged", tmp_path, profile=False)
    for r in reqs:
        assert len(r.token_at) == len(r.tokens)
        assert r.token_at[0] == r.first_token_at
    profiler.start_profiler()
    with contextlib.redirect_stdout(io.StringIO()):
        summary = profiler.stop_profiler(
            profile_path=str(tmp_path / "off.json"))
    assert summary == []
    assert json.loads((tmp_path / "off.json").read_text()) == \
        {"traceEvents": []}


def _burst_exports(model, tmp_path, tag, profile):
    """The seeded virtual-clock burst of test_tracing.py, exported."""
    tracing.reset()
    vc = VirtualClock()
    eng = ServingEngine(model, clock=vc.now, slo_ttft_ms=60.0,
                        slo_prefill_ms=4.0, slo_tpot_ms=1.5,
                        max_slots=2, max_len=32, buckets=[8])
    lg = LoadGen(mode="bursty", rate=30.0, duration=0.5, seed=11,
                 vocab_size=97, prompt_tokens=(3, 7), new_tokens=(2, 4))
    if profile:
        profiler.start_profiler()
    report = lg.run(eng, clock=vc, step_cost_ms=4.0)
    events = []
    if profile:
        with contextlib.redirect_stdout(io.StringIO()):
            profiler.stop_profiler(
                profile_path=str(tmp_path / f"{tag}.host.json"))
        events = json.loads(
            (tmp_path / f"{tag}.host.json").read_text())["traceEvents"]
    assert report["completed"] > 0
    chrome, spans = tmp_path / f"{tag}.json", tmp_path / f"{tag}.jsonl"
    tracing.export_chrome_trace(str(chrome))
    tracing.export_spans_jsonl(str(spans))
    kinds = {kind for tr in tracing.store().finished()
             for kind, _t, _track in tr.marks}
    tracing.reset()
    return chrome.read_bytes(), spans.read_bytes(), kinds, events


def test_request_marks_and_exports_are_untouched(model, tmp_path):
    off = _burst_exports(model, tmp_path, "off", profile=False)
    on = _burst_exports(model, tmp_path, "on", profile=True)
    assert on[0] == off[0] and on[1] == off[1]      # byte for byte
    assert on[2] == off[2]
    # per-token stamps added no kind of mark: the decode of a request is
    # still one stretch between its first_token and its finish
    assert off[2] <= {"submit", "admit", "first_token", "finish",
                      "resume", "cancel"}
    # the gaps are on the engine's (here virtual) clock: whole 4 ms
    # steps, none where a prefill and a decode share a step
    gaps = [e["dur"] for e in on[3] if e["name"] == "serving.token_gap"]
    assert gaps and max(gaps) > 0.0
    assert all(g / 4000.0 == pytest.approx(round(g / 4000.0), abs=1e-6)
               for g in gaps)
