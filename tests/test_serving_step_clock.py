"""The serving step's one clock (PR 35).

Every dispatch the engine gives the device is a flight with an id (one
engine-wide sequence) and the readings of its five edges (``_Stamps``),
dispatch, launched, fetch, fetched, committed. The readings are the ones
the step's ``RecordEvent`` spans take anyway (``RecordEvent.t0`` / ``.t1``, kept with
the profiler on or off), so the spans, the always-on step account in
``engine.stats()`` and the SLO cost estimates are one set of numbers:

- the spans of one dispatch carry ``flight=<id>`` and one parentless
  ``serving.flight`` joins them; ``serving.ttft`` is a request's time to
  its first token from inside, split at the admission;
- the account's sums are the spans' sums, and grow with the profiler off;
- the estimates time a step to its tokens fetched, not its launch: with
  an entry that takes ~0 to dispatch and 20 ms to deliver they read ~20 ms
  (``SlowDevice``; the parent commit of PR 35 closed its clock at the
  launch and read the launch).

The checks that take a ``path`` run over the engines the cells serve
(PR 61): GPT's single and verify steps, and one engine for each other
served family, built as the harness builds it
(``perfbench.serve.build_engine``) from the family's toy twin
(``perfbench/rehearsal/<family>-tiny.json``): a window, recurrent state,
convolution tails, an indexer, a latent row, a matrix state.

CPU, toy models; nothing here is a device metric.
"""

import contextlib
import io
import json
import os
import time

import numpy as np
import jax
import pytest

import paddle_tpu as pt
import perfbench_listing as listing
from paddle_tpu import monitor, profiler
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _ACCOUNT_KEYS
from perfbench import serve

VOCAB = 97
GEOM = dict(max_slots=3, max_len=48, buckets=[8, 16])
#: GPT's two decode steps, by the engine's keywords
GPT_PATHS = {"single": {}, "spec2": {"spec_tokens": 2}}
#: the other served families: one engine each, the toy twin's own geometry
FAMILIES = ("dotsvlm", "jamba", "keye", "lfm2", "mellum", "qwen3next")
PATHS = sorted(GPT_PATHS) + list(FAMILIES)
REHEARSAL = os.path.join(listing.ROOT, "perfbench", "rehearsal")
WORK = ((3, 9), (5, 7), (7, 8), (9, 6))   # (prompt tokens, new tokens)
STAMPS = ("t_dispatch", "t_launched", "t_fetch", "t_fetched", "t_committed")


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_layers=2, num_heads=4, ffn_hidden_size=64))
    m.eval()
    return m


def _prompts(work=WORK, seed=5):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, VOCAB, size=n).tolist(), m) for n, m in work]


def _warmed(eng):
    for p, m in _prompts(seed=11):
        eng.submit(p, max_new_tokens=m)
    eng.run_until_idle()
    eng.reset_cost_estimates()
    return eng


def _engine(model, **kw):
    return ServingEngine(model, **{**GEOM, **kw})


def _warm(model, **kw):
    """An engine whose shapes are compiled and whose estimates are fresh."""
    return _warmed(_engine(model, **kw))


@pytest.fixture(scope="module")
def built(model):
    """-> path -> (an engine of that path, its account as it was built).
    GPT's are new at every call (their programs are the model's); a
    family's is built once and shared, so a check reads its account as
    growth."""
    shared = {}

    def get(path):
        if path in GPT_PATHS:
            eng = _engine(model, **GPT_PATHS[path])
            return eng, _account(eng)
        if path not in shared:
            with open(os.path.join(REHEARSAL, f"{path}-tiny.json")) as f:
                _, eng = serve.build_engine(json.load(f), seed=0)
            shared[path] = eng, _account(eng)
        return shared[path]
    return get


def _stop_profiler(tmp_path):
    out = tmp_path / "spans.json"
    with contextlib.redirect_stdout(io.StringIO()):
        profiler.stop_profiler(profile_path=str(out))
    return json.loads(out.read_text())["traceEvents"]


def _account(eng):
    st = eng.stats()
    return {k: st[k] for k in _ACCOUNT_KEYS}


def _traced_run(eng, tmp_path, last=None):
    """Serve WORK on a warm engine with the profiler on -> (requests, the
    flights in the order they were committed, events, the account's
    growth). ``last``: decode parameters of the last request (a sampled
    one draws its first token on the host: its prefill is fetched at once
    and the step after it is the host's to build)."""
    flights, landed = [], eng._landed

    def keep(fl, *a, **k):
        flights.append(fl)
        return landed(fl, *a, **k)
    eng._landed = keep
    before = _account(eng)
    followed = eng.stats()["admit_ahead_dispatches"]
    profiler.start_profiler()
    work = _prompts()
    reqs = [eng.submit(p, max_new_tokens=m,
                       **(last or {} if i == len(work) - 1 else {}))
            for i, (p, m) in enumerate(work)]
    eng.run_until_idle()
    events = _stop_profiler(tmp_path)
    del eng._landed         # (a family's engine is shared)
    after = _account(eng)
    grew = {k: after[k] - before[k] for k in after}
    grew["admit_ahead_dispatches"] = \
        eng.stats()["admit_ahead_dispatches"] - followed
    return reqs, flights, events, grew


@pytest.fixture(scope="module", params=PATHS)
def traced(request, built, tmp_path_factory):
    return _traced_run(_warmed(built(request.param)[0]),
                       tmp_path_factory.mktemp(request.param))


def _by_flight(events, name):
    out = {}
    for e in events:
        if e["name"] == name:
            out.setdefault(e["args"]["flight"], []).append(e)
    return out


# ------------------------------------------------------------- the readings

def test_a_record_event_keeps_its_two_readings_with_the_profiler_off():
    lo = time.perf_counter_ns()
    with profiler.RecordEvent("clock.test") as ev:
        assert ev.t0 >= lo and ev.t1 == 0
    assert lo <= ev.t0 <= ev.t1 <= time.perf_counter_ns()


def test_a_recorded_event_is_its_own_readings(tmp_path):
    profiler.start_profiler()
    with profiler.RecordEvent("clock.test", {"k": 1}) as ev:
        pass
    (e,) = [e for e in _stop_profiler(tmp_path) if e["name"] == "clock.test"]
    assert e["ts"] == ev.t0 / 1e3 and e["dur"] == (ev.t1 - ev.t0) / 1e3


def test_stat_observe_is_what_stat_time_records():
    monitor.reset()
    monitor.stat_observe("STAT_clock_phase", 2.5)
    monitor.stat_observe("STAT_clock_phase", 1.5)
    with monitor.stat_time("STAT_clock_phase"):
        pass
    assert monitor.stat_get("STAT_clock_phase_calls") == 3
    assert monitor.stat_get("STAT_clock_phase_ms") == pytest.approx(4.0,
                                                                    abs=0.5)


# ------------------------------------------------------ flights and spans

def test_flight_ids_are_one_sequence_and_stamps_are_monotone(traced):
    _, flights, _, grew = traced
    ids = [fl.id for fl in flights]
    assert len(set(ids)) == len(ids) and min(ids) > 0
    for fl in flights:
        stamps = [getattr(fl, s) for s in STAMPS]
        assert stamps == sorted(stamps) and stamps[0] > 0, (fl.id, stamps)
    assert grew["decode_flights"] + grew["prefill_flights"] == len(flights)


def test_a_flights_spans_carry_its_id_and_one_flight_span_joins_them(traced):
    _, flights, events, _ = traced
    joined = _by_flight(events, "serving.flight")
    assert sorted(joined) == sorted(fl.id for fl in flights)
    for fl in flights:
        (span,) = joined[fl.id]
        assert span["parent"] is None
        assert span["ts"] == pytest.approx(fl.t_dispatch / 1e3, abs=1e-3)
        assert span["dur"] == pytest.approx(
            (fl.t_committed - fl.t_dispatch) / 1e3, abs=1e-3)
    prefill = {i for i, (s,) in joined.items() if s["args"]["prefill"]}
    assert prefill and all("bucket" in joined[i][0]["args"] for i in prefill)
    for name in ("serving.prefill", "serving.prefill.fetch",
                 "serving.prefill.commit"):
        assert {i: len(v) for i, v in _by_flight(events, name).items()} == \
            dict.fromkeys(prefill, 1)
    decode = set(joined) - prefill
    for name in ("serving.decode.fetch", "serving.decode.commit"):
        assert {i: len(v) for i, v in _by_flight(events, name).items()} == \
            dict.fromkeys(decode, 1)
    inputs = _by_flight(events, "serving.decode.inputs")
    assert {i: len(v) for i, v in inputs.items()} == \
        dict.fromkeys(decode, 1)
    for fl in flights:
        if fl.id in inputs:
            (e,) = inputs[fl.id]
            assert e["ts"] == fl.t_dispatch / 1e3
            assert e["args"]["ahead"] == joined[fl.id][0]["args"]["ahead"]


def test_a_step_built_by_the_host_and_one_dispatched_ahead(model, tmp_path):
    # (all greedy, every step is dispatched behind a step or a prefill
    # since PR 48: the sampled request's admission leaves one to the host)
    _, flights, events, _ = _traced_run(
        _warm(model), tmp_path, last=dict(seed=3, temperature=0.8, top_k=12))
    joined = _by_flight(events, "serving.flight")
    prefill = {i for i, (s,) in joined.items() if s["args"]["prefill"]}
    ahead = {i for i, (s,) in joined.items() if s["args"]["ahead"]}
    decode = [fl for fl in flights if fl.id not in prefill]
    assert any(fl.id in ahead for fl in decode)
    assert any(fl.id not in ahead for fl in decode)
    launches = [e["args"]["launches"] for e in events
                if e["name"] == "serving.decode"]
    # a round that finds no step in flight launches that step and the one
    # after it; a round that finds one launches at most the next
    assert set(launches) <= {0, 1, 2} and 2 in launches and 1 in launches
    assert sum(launches) >= len(decode)
    by_id = {fl.id: fl for fl in flights}
    fetch = _by_flight(events, "serving.decode.fetch")
    for fl in decode:
        if fl.id in ahead:
            # dispatched before the step before it was fetched
            before = by_id.get(fl.id - 1)
            if before is not None and before.id not in prefill:
                assert fl.t_launched <= before.t_fetch
        (e,) = fetch[fl.id]
        assert e["ts"] == fl.t_fetch / 1e3


def test_the_step_behind_a_prefill_is_dispatched_before_its_fetch(model,
                                                                  tmp_path):
    """A prefill group is a flight: its stamps are ordered like a step's,
    the decode step behind it (the next id) is dispatched between its
    launch and its fetch, and ``serving.prefill_step`` is drawn from its
    stamps, dispatch to commit, around its own three spans."""
    _, flights, events, grew = _traced_run(_warm(model), tmp_path)
    joined = _by_flight(events, "serving.flight")
    by_id = {fl.id: fl for fl in flights}
    prefill = sorted(i for i, (s,) in joined.items() if s["args"]["prefill"])
    assert prefill
    followed = 0
    for i in prefill:
        pre = by_id[i]
        stamps = [getattr(pre, s) for s in STAMPS]
        assert stamps == sorted(stamps) and stamps[0] > 0
        behind = by_id.get(i + 1)
        if behind is None or (i + 1) in prefill:
            continue        # a second group of its round follows it
        assert joined[i + 1][0]["args"]["ahead"] == 1
        assert pre.t_launched <= behind.t_dispatch <= behind.t_launched \
            <= pre.t_fetch
        # and the step is fetched after the prefill it queued behind
        assert pre.t_fetched <= behind.t_fetch
        followed += 1
    assert followed >= 1
    assert grew["admit_ahead_dispatches"] == len(prefill) == \
        grew["prefill_flights"]
    steps = _by_flight(events, "serving.prefill_step")
    assert sorted(steps) == prefill
    for i in prefill:
        (e,), fl = steps[i], by_id[i]
        assert e["parent"] is None and e["args"]["rows"] >= 1
        assert e["ts"] == pytest.approx(fl.t_dispatch / 1e3, abs=1e-3)
        assert e["dur"] == pytest.approx(
            (fl.t_committed - fl.t_dispatch) / 1e3, abs=1e-3)
        for name in ("serving.prefill", "serving.prefill.fetch",
                     "serving.prefill.commit"):
            (inner,) = _by_flight(events, name)[i]
            # (the same readings through two float paths: to a nanosecond)
            assert e["ts"] <= inner["ts"] + 1e-3 and \
                inner["ts"] + inner["dur"] <= e["ts"] + e["dur"] + 1e-3


def test_the_account_is_the_sum_of_the_matching_spans(traced):
    """host + wait of the decode flights, rebuilt from the spans alone:
    inputs entry to the first reading after the launch (the next inputs
    entry under the same ``serving.decode`` / ``.verify``, else that
    span's close), plus fetch entry to commit close. The same readings:
    equal to the microsecond (they differ by float rounding only)."""
    _, flights, events, grew = traced
    by_id = {e["id"]: e for e in events}
    inputs = _by_flight(events, "serving.decode.inputs")
    fetch = _by_flight(events, "serving.decode.fetch")
    commit = _by_flight(events, "serving.decode.commit")
    siblings = {}
    for (e,) in inputs.values():
        siblings.setdefault(e["parent"], []).append(e["ts"])
    total_us = wait_us = 0.0
    for fl in flights:
        if fl.id not in fetch:
            continue        # a prefill group
        (i,), (f,), (c,) = inputs[fl.id], fetch[fl.id], commit[fl.id]
        parent = by_id[i["parent"]]
        assert parent["name"] in ("serving.decode", "serving.verify")
        later = [ts for ts in siblings[i["parent"]] if ts > i["ts"]]
        launched = min(later) if later else parent["ts"] + parent["dur"]
        total_us += (launched - i["ts"]) + (c["ts"] + c["dur"] - f["ts"])
        wait_us += f["dur"]
    assert grew["decode_wait_ms"] == pytest.approx(wait_us / 1e3, abs=1e-3)
    assert grew["decode_host_ms"] + grew["decode_wait_ms"] == \
        pytest.approx(total_us / 1e3, abs=1e-3)
    # the prefill groups: serving.prefill whole, then fetch to commit close
    pre = _by_flight(events, "serving.prefill")
    pfetch = _by_flight(events, "serving.prefill.fetch")
    pcommit = _by_flight(events, "serving.prefill.commit")
    total_us = sum(pre[i][0]["dur"] + pcommit[i][0]["ts"]
                   + pcommit[i][0]["dur"] - pfetch[i][0]["ts"] for i in pre)
    assert grew["prefill_host_ms"] + grew["prefill_wait_ms"] == \
        pytest.approx(total_us / 1e3, abs=1e-3)


def test_the_device_bound_lies_between_the_wait_and_the_round(traced):
    _, _, _, grew = traced
    assert 0 < grew["decode_wait_ms"] <= grew["decode_device_ms"] + 1e-6
    assert grew["decode_device_ms"] <= grew["round_ms"]
    assert grew["rounds"] > 0 and grew["round_ms"] > 0
    assert grew["stalled_ms"] <= grew["round_ms"]
    assert grew["rounds_over_1s"] <= grew["rounds_over_100ms"] <= \
        grew["rounds"]


def test_the_phase_timers_count_one_observation_a_flight(model):
    monitor.reset()
    eng = _warm(model)
    st = eng.stats()
    assert monitor.stat_get("STAT_serving_decode_calls") == \
        st["decode_flights"]
    assert monitor.stat_get("STAT_serving_prefill_calls") == \
        st["prefill_flights"]
    # dispatch to fetched: the phase's latency holds the wait for its tokens
    assert monitor.stat_get("STAT_serving_decode_ms") >= st["decode_wait_ms"]
    assert monitor.stat_get("STAT_serving_prefill_ms") >= \
        st["prefill_wait_ms"]


# ------------------------------------------------------------------- TTFT

def test_one_ttft_span_a_request_split_at_its_admission(traced):
    reqs, _, events, grew = traced
    spans = {e["args"]["request"]: e for e in events
             if e["name"] == "serving.ttft"}
    assert sorted(spans) == sorted(r.id for r in reqs)
    assert sum(e["name"] == "serving.ttft" for e in events) == len(reqs)
    for r in reqs:
        e, a = spans[r.id], spans[r.id]["args"]
        assert e["parent"] is None
        assert e["dur"] == pytest.approx(
            (r.first_token_at - r.submitted_at) * 1e6, abs=1e-3)
        assert a["queue_ms"] + a["prefill_ms"] == pytest.approx(
            e["dur"] / 1e3, abs=1e-6)
        assert a["queue_ms"] == pytest.approx(
            (r.admitted_at - r.submitted_at) * 1e3, abs=1e-9)
        assert a["queue_ms"] >= 0 and a["prefill_ms"] > 0
    assert grew["first_tokens"] == len(reqs)
    assert grew["ttft_ms"] == pytest.approx(
        sum(r.ttft for r in reqs) * 1e3, abs=1e-6)
    assert grew["queue_wait_ms"] == pytest.approx(
        sum(e["args"]["queue_ms"] for e in spans.values()), abs=1e-6)


# ------------------------------------------------- profiler off, monotone

@pytest.mark.parametrize("path", PATHS)
def test_with_the_profiler_off_nothing_is_recorded_and_the_account_grows(
        built, path, tmp_path):
    eng = _warmed(built(path)[0])
    before = _account(eng)
    for p, m in _prompts():
        eng.submit(p, max_new_tokens=m)
    eng.run_until_idle()
    after = _account(eng)
    assert _stop_profiler(tmp_path) == []
    for key in ("decode_flights", "decode_host_ms", "decode_wait_ms",
                "decode_device_ms", "prefill_flights", "prefill_host_ms",
                "prefill_wait_ms", "rounds", "round_ms", "first_tokens",
                "ttft_ms"):
        assert after[key] > before[key], key
    assert after["first_tokens"] - before["first_tokens"] == len(WORK)


@pytest.mark.parametrize("path", PATHS)
def test_the_account_counts_the_table_entries_the_rows_stood_on(built, path):
    """``kv_blocks_live`` / ``kv_blocks_table``: at every committed decode
    flight the live rows' ``ceil(length / block_size)`` against the whole
    table, from the lengths the host holds (PR 39: what the paged kernel
    had to read of what the gathered table held)."""
    eng = _warmed(built(path)[0])
    before = _account(eng)
    bs, table = eng.cache.block_size, eng.cache.tables.size
    for p, m in _prompts():
        eng.submit(p, max_new_tokens=m)
    eng.run_until_idle()
    grew = {k: v - before[k] for k, v in _account(eng).items()}
    assert grew["kv_blocks_table"] == grew["decode_flights"] * table
    assert 0 < grew["kv_blocks_live"] < grew["kv_blocks_table"]
    # no row can stand on more entries than its longest context needs
    longest = max(len(p) + m for p, m in _prompts())
    assert grew["kv_blocks_live"] <= grew["decode_flights"] \
        * eng.max_slots * -(-longest // bs)


@pytest.mark.parametrize("path", PATHS)
def test_every_key_of_the_account_is_monotone(built, path):
    eng, new = built(path)
    assert set(new) == set(_ACCOUNT_KEYS) and not any(new.values())
    last = _account(eng)
    for p, m in _prompts():
        eng.submit(p, max_new_tokens=m)
    while not eng.idle:
        eng.step()
        now = _account(eng)
        for key in _ACCOUNT_KEYS:
            assert now[key] >= last[key], key
        last = now
    assert all(isinstance(last[k], int) for k in _ACCOUNT_KEYS
               if not k.endswith("_ms"))


# ------------------------------------------------------------ the estimates

class DeviceClock:
    """The spans' clock, stepped: every read costs the host 10 us, and
    waiting for the device moves it to when the device delivers. Stands in
    for ``profiler.time``, so a flight's stamps are deterministic."""

    def __init__(self):
        self.ns = 10 ** 9

    def perf_counter_ns(self):
        self.ns += 10_000
        return self.ns

    def __getattr__(self, name):        # whatever else the module uses
        return getattr(time, name)


class _Late:
    """An array the device delivers at ``at`` on ``clock``: whoever reads
    it before then waits, as ``np.asarray`` of a jax array whose program
    still runs does."""

    def __init__(self, real, at, clock):
        self.real, self.at, self.clock = real, at, clock

    def _wait(self):
        self.clock.ns = max(self.clock.ns, self.at)
        return self.real

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._wait())

    def __jax_array__(self):
        return self._wait()


class SlowDevice:
    """Entries that return at once and deliver ``step_ms`` later, one
    after the other as a device runs its queue: the real entry computes
    (on the CPU, in no time of ``clock``'s) and its output ``index`` is
    handed out late."""

    def __init__(self, clock: DeviceClock, step_ms: float):
        self.clock, self.step_ns = clock, int(step_ms * 1e6)
        self.free_at = 0

    def entry(self, fn, index: int):
        def call(*args):
            # (a prefill's ``merge`` operand holds the step in flight's
            # tokens: unwrapped where it is nested too)
            args = jax.tree_util.tree_map(
                lambda a: a.real if isinstance(a, _Late) else a, args,
                is_leaf=lambda a: isinstance(a, _Late))
            out = list(fn(*args))
            self.free_at = max(self.free_at, self.clock.ns) + self.step_ns
            out[index] = _Late(out[index], self.free_at, self.clock)
            return tuple(out)
        return call

    def attach(self, eng):
        decode, prefill = eng.spec.decode_entry, eng._prefill_entry
        eng.spec.decode_entry = lambda *a: {
            "fn": self.entry(decode(*a)["fn"], 0)}       # the next tokens
        eng._prefill_entry = lambda bucket: {
            "fn": self.entry(prefill(bucket)["fn"], 3)}  # the first tokens


@pytest.fixture
def slow_engine(model, monkeypatch):
    """-> a function: a warm engine behind a device of 20 ms a dispatch."""
    clock = DeviceClock()
    monkeypatch.setattr(profiler, "time", clock)

    def make(**kw):
        eng = _warm(model, **kw)
        SlowDevice(clock, 20.0).attach(eng)
        return eng
    return make


@pytest.mark.parametrize("ahead", (True, False),
                         ids=("dispatched_ahead", "built_by_the_host"))
def test_the_estimates_time_a_step_to_its_tokens_not_its_launch(slow_engine,
                                                                ahead):
    """An entry that takes ~0 to dispatch and 20 ms to deliver: the SLO
    gate's costs must read the 20 ms. ``_tpot_cost_ms()`` and
    ``_prefill_cost_ms(bucket)`` read 15-40 ms here (on the stepped clock:
    20 and a few reads), whether a step was dispatched behind the step in
    flight or built by the host after that was committed. The parent
    commit of PR 35 wrapped the dispatch in ``t0 = time.perf_counter()``
    pairs that closed at the launch: on such an entry both estimates read
    under 2 ms there (the launch alone), as they read 1.3 of a 13.1 ms
    step on the chip."""
    eng = slow_engine()
    if not ahead:
        eng._rows_ahead = lambda fl: None
    before = eng.stats()
    work = _prompts(WORK[:3])       # all three admitted in the first round
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in work]
    eng.run_until_idle()
    assert all(r.state == "done" and len(r.tokens) == m
               for r, (_, m) in zip(reqs, work))
    st = {k: v - before[k] for k, v in eng.stats().items()
          if k in _ACCOUNT_KEYS or k == "ahead_dispatches"}
    assert (st["ahead_dispatches"] > 0) == ahead
    assert 15.0 <= eng._tpot_cost_ms() <= 40.0, eng._tpot_cost_ms()
    assert eng._tpot_cost_ms() == pytest.approx(20.0, abs=0.5)
    for bucket in (8, 16):
        assert 15.0 <= eng._prefill_cost_ms(bucket) <= 40.0, \
            (bucket, eng._prefill_cost_ms(bucket))
    assert eng.predict_ttft_ms(prompt_len=5, queue_ahead=0) >= 15.0
    # the account saw the same: the host waited for nearly all of a step
    flights = st["decode_flights"]
    assert st["decode_device_ms"] / flights == pytest.approx(20.0, abs=0.5)
    assert st["decode_wait_ms"] / flights >= 19.0
    assert st["decode_host_ms"] / flights < 1.0
    assert st["prefill_wait_ms"] / st["prefill_flights"] >= 19.0
    assert monitor.stat_get("STAT_serving_decode_ms") >= \
        st["decode_wait_ms"]


@pytest.mark.parametrize("late_kw", ({}, dict(seed=3, temperature=0.8)),
                         ids=("fetched_in_order", "overtaken"))
def test_a_step_a_prefill_overtook_feeds_no_estimate(slow_engine, late_kw):
    """A prefill dispatched behind the step in flight. Its group all
    greedy, that step is fetched first (then the prefill, then the step
    behind it): every step's time on the device is observed and feeds
    the estimate. A group with a sampled row is fetched at once, before
    that step: the step's tokens are there when the host comes, its time
    on the device was not observed (the account adds the bound, nearly
    0), and the estimate does not take the sample. Either way the
    prefill's own sample holds its wait behind that step: what the next
    arrival pays."""
    eng = slow_engine(max_slots=2)
    samples, note = [], eng._note_tpot_ms

    def keep(ms):
        samples.append(ms)
        return note(ms)
    eng._note_tpot_ms = keep
    before = eng.stats()
    first, late = _prompts(((4, 12), (6, 4)))
    eng.submit(first[0], max_new_tokens=first[1])
    for _ in range(4):
        eng.step()                  # a step is in flight when the next comes
    eng.submit(late[0], max_new_tokens=late[1], **late_kw)
    eng.run_until_idle()
    st = eng.stats()
    assert st["prefill_flights"] - before["prefill_flights"] == 2
    assert st["admit_ahead_dispatches"] - before["admit_ahead_dispatches"] \
        == (1 if late_kw else 2)
    # every decode step, but the one the host-drawn group overtook
    assert len(samples) == \
        st["decode_flights"] - before["decode_flights"] - bool(late_kw)
    assert min(samples) >= 15.0 and max(samples) <= 40.0, samples
    # the late prefill waited for the step ahead of it, then ran: ~40 ms
    # where the first took 20, and the EWMA stands between
    assert eng._prefill_cost_ms(8) == pytest.approx(26.0, abs=1.0)


def test_a_prefill_flight_reads_what_the_synchronous_path_read(slow_engine):
    """A late prompt behind a step in flight, its first token fetched
    after the step behind it was dispatched (greedy) or at once, as every
    group was before PR 48 (a sampled row: the host draws from the
    logits). The bucket's SLO sample (dispatch to fetched) and the
    request's TTFT read the same to within the host's time for a step
    (here a few reads of a clock that costs 10 us a read); what the host
    *waited* in the prefill's own fetch is the prompt's time on the
    device alone, the rest of the step ahead of it having been waited for
    in that step's fetch, where the synchronous fetch waited for both."""
    reads = {}
    for at_once in (False, True):
        eng = slow_engine(
            max_slots=2,
            clock=lambda: profiler.time.perf_counter_ns() / 1e9)
        first, late = _prompts(((4, 12), (6, 4)))
        eng.submit(first[0], max_new_tokens=first[1])
        for _ in range(4):
            eng.step()
        before = eng.stats()
        eng.reset_cost_estimates()
        req = eng.submit(late[0], max_new_tokens=late[1],
                         **(dict(seed=3, temperature=0.8) if at_once else {}))
        eng.run_until_idle()
        st = {k: v - before[k] for k, v in eng.stats().items()
              if k in _ACCOUNT_KEYS or k == "admit_ahead_dispatches"}
        assert st["prefill_flights"] == 1
        assert st["admit_ahead_dispatches"] == (not at_once)
        reads[at_once] = (eng._prefill_cost_ms(8), req.ttft * 1e3,
                          st["prefill_wait_ms"])
    (cost, ttft, wait), (cost0, ttft0, wait0) = reads[False], reads[True]
    assert cost == pytest.approx(cost0, abs=1.0) and 39.0 <= cost0 <= 41.0
    assert ttft == pytest.approx(ttft0, abs=1.0) and ttft0 >= 39.0
    assert wait == pytest.approx(20.0, abs=1.0)
    assert wait0 == pytest.approx(40.0, abs=1.0)


# ------------------------------------------- the metric files that read it

#: what a window of a change run leaves in the harness's observations
OBS = {
    "spans": {"serving.flight": [0.026, 0.027, 0.013, 0.040],
              "serving.ttft": [0.040, 0.044, 0.050]},
    "span_args": {"serving.ttft.queue_ms": [1.0, 2.0, 11.0]},
    "samples": {},
    "counters": {"engine.decode_flights": 3000, "engine.decode_host_ms": 5700,
                 "engine.decode_wait_ms": 33000,
                 "engine.decode_device_ms": 39300,
                 "engine.ahead_dispatches": 2964,
                 "engine.sampler_dispatches": 3000,
                 "engine.admit_ahead_dispatches": 540,
                 "engine.prefill_flights": 545,
                 "engine.stalled_ms": 0.0, "window_s": 45.0},
    "trace": {},
}
#: a reader (from, name, reduce) -> (what it is over, the value OBS gives it)
ACCOUNT = {
    ("counters", "engine.decode_host_ms", "value"):
        ("counters.engine.decode_flights", 1.9),
    ("counters", "engine.decode_wait_ms", "value"):
        ("counters.engine.decode_flights", 11.0),
    ("counters", "engine.decode_device_ms", "value"):
        ("counters.engine.decode_flights", 13.1),
    ("counters", "engine.ahead_dispatches", "value"):
        ("counters.engine.sampler_dispatches", 98.8),
    ("counters", "engine.admit_ahead_dispatches", "value"):
        ("counters.engine.prefill_flights", 54000 / 545),
    ("counters", "engine.stalled_ms", "value"): (None, 0.0),
    ("spans", "serving.flight", "p50"): (None, 26.5),
    ("spans", "serving.ttft", "p50"): (None, 44.0),
    ("spans", "serving.ttft", "p90"): (None, 48.8),
    ("span_args", "serving.ttft.queue_ms", "p90"): (None, 9.2),
}


def account_files(reader):
    group, name, how = reader
    return listing.files_reading(name, reduce=how, **{"from": group})


def test_every_reader_of_the_account_has_a_file():
    assert all(map(account_files, ACCOUNT))


@pytest.mark.parametrize("name", [
    n for reader in ACCOUNT for n in account_files(reader)])
def test_a_metric_file_reads_the_account_and_nothing_from_the_parent(name):
    """Data over the counter and span channels, no reader code: whichever
    files read the account (found by their reader, listed or not) read a
    change run's observations to the number worked out by hand, and find
    nothing (and do not raise) in a parent's."""
    from perfbench import readers
    spec = listing.SPECS[name]
    r = spec["reader"]
    over, value = ACCOUNT[r["from"], r["name"], r["reduce"]]
    assert r.get("over") == over
    assert readers.read(name, OBS) == pytest.approx(value)
    parent = {"spans": {"serving.decode.fetch": [0.011]}, "span_args": {},
              "samples": {}, "trace": {},
              "counters": {"engine.sampler_dispatches": 3000,
                           "window_s": 45.0}}
    assert readers.read(name, parent) is None
    assert spec["source"] == ("program_span" if r["from"]
                              in ("spans", "span_args")
                              else "program_counter")
