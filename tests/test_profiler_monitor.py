"""Profiler (RecordEvent, chrome trace, summary) + StatRegistry.

Parity targets: platform/profiler.h:126,208, fluid/profiler.py:131-255,
tools/timeline.py, platform/monitor.h:76.
"""

import json
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor, profiler


def test_record_event_and_chrome_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    profiler.start_profiler()
    with profiler.RecordEvent("matmul_phase"):
        np.dot(np.ones((64, 64)), np.ones((64, 64)))
    with profiler.RecordEvent("matmul_phase"):
        np.dot(np.ones((64, 64)), np.ones((64, 64)))
    with profiler.RecordEvent("io_phase"):
        pass
    summary = profiler.stop_profiler(sorted_key="total",
                                     profile_path=path)
    by_name = {s["name"]: s for s in summary}
    assert by_name["matmul_phase"]["calls"] == 2
    assert by_name["io_phase"]["calls"] == 1
    trace = json.load(open(path))
    assert len(trace["traceEvents"]) == 3
    assert {e["name"] for e in trace["traceEvents"]} == \
        {"matmul_phase", "io_phase"}


def test_profiler_context_and_decorator(tmp_path):
    calls = []

    @profiler.RecordEvent("decorated")
    def work():
        calls.append(1)
        return 7

    with profiler.profiler(profile_path=str(tmp_path / "t.json")):
        assert work() == 7
    assert calls == [1]


def test_events_off_when_disabled(tmp_path):
    with profiler.RecordEvent("ghost"):
        pass
    profiler.start_profiler()
    summary = profiler.stop_profiler(
        profile_path=str(tmp_path / "e.json"))
    assert all(s["name"] != "ghost" for s in summary)


def test_record_event_decorator_preserves_metadata():
    @profiler.RecordEvent("meta")
    def documented(a, b=1):
        """the docstring survives"""
        return a + b

    assert documented.__name__ == "documented"
    assert documented.__doc__ == "the docstring survives"
    assert documented(2, b=3) == 5


def test_chrome_trace_event_schema(tmp_path):
    """Every emitted event carries the chrome://tracing complete-event
    fields tools/timeline.py consumers expect (ph=X, us timestamps)."""
    path = str(tmp_path / "schema.json")
    profiler.start_profiler()
    with profiler.RecordEvent("one"):
        time.sleep(0.001)
    profiler.stop_profiler(profile_path=path)
    trace = json.load(open(path))
    (e,) = trace["traceEvents"]
    # ... plus the span's identity; "args" only when some were given
    assert set(e) == {"name", "ph", "ts", "dur", "pid", "tid", "cat",
                      "id", "parent"}
    assert e["ph"] == "X" and e["cat"] == "host" and e["pid"] == 0
    assert e["dur"] >= 1000  # slept 1ms; dur is in microseconds
    assert isinstance(e["id"], int) and e["parent"] is None


def test_span_identity_parent_args_and_self_time(tmp_path):
    """Each event keeps a process-unique id, the id of the RecordEvent
    open on its thread when it was entered, and its args; summarize()
    gives a name's self time: its durations minus what children cover."""
    path = str(tmp_path / "tree.json")
    profiler.start_profiler()
    with profiler.RecordEvent("outer", {"step": 3}):
        with profiler.RecordEvent("inner"):
            time.sleep(0.002)
        with profiler.RecordEvent("inner"):
            with profiler.RecordEvent("leaf", {"rows": 2, "kind": "x"}):
                time.sleep(0.001)
        time.sleep(0.001)
    # an interval measured elsewhere, on the same clock, parentless
    t = time.perf_counter()
    profiler.record_span("gap", t - 0.5, 0.25, {"request": 7})
    summary = profiler.stop_profiler(profile_path=path)
    events = json.load(open(path))["traceEvents"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (outer,), (leaf,), (gap,) = by_name["outer"], by_name["leaf"], by_name["gap"]
    assert len({e["id"] for e in events}) == len(events) == 5
    assert outer["parent"] is None and outer["args"] == {"step": 3}
    assert [e["parent"] for e in by_name["inner"]] == [outer["id"]] * 2
    assert leaf["parent"] == by_name["inner"][1]["id"]
    assert leaf["args"] == {"rows": 2, "kind": "x"}
    assert "args" not in by_name["inner"][0]
    assert gap["parent"] is None and gap["args"] == {"request": 7}
    assert gap["dur"] == pytest.approx(0.25e6)
    assert gap["ts"] == pytest.approx((t - 0.5) * 1e6)
    rows = {s["name"]: s for s in summary}
    inner_total = sum(e["dur"] for e in by_name["inner"]) / 1e3
    assert rows["outer"]["self_ms"] == pytest.approx(
        outer["dur"] / 1e3 - inner_total)
    assert rows["outer"]["self_ms"] >= 1.0          # its own 1 ms sleep
    assert rows["inner"]["self_ms"] == pytest.approx(
        inner_total - leaf["dur"] / 1e3)
    assert rows["leaf"]["self_ms"] == pytest.approx(rows["leaf"]["total_ms"])
    # the stack is kept only while the profiler is on: a span entered
    # now is nobody's child later
    with profiler.RecordEvent("off"):
        profiler.start_profiler()
        with profiler.RecordEvent("fresh"):
            pass
    profiler.stop_profiler(profile_path=path)
    (fresh,) = json.load(open(path))["traceEvents"]
    assert fresh["name"] == "fresh" and fresh["parent"] is None
    profiler.record_span("ghost", 0.0, 1.0)         # off: nothing
    profiler.start_profiler()
    assert profiler.stop_profiler(profile_path=path) == []


def test_summarize_sort_keys():
    events = [{"name": "big", "dur": 9000.0},
              {"name": "hot", "dur": 1000.0},
              {"name": "hot", "dur": 1000.0},
              {"name": "hot", "dur": 1000.0}]
    assert [s["name"] for s in profiler.summarize(events, "total")] == \
        ["big", "hot"]
    assert [s["name"] for s in profiler.summarize(events, "calls")] == \
        ["hot", "big"]
    assert [s["name"] for s in profiler.summarize(events, "ave")] == \
        ["big", "hot"]


def test_profiler_off_records_nothing(tmp_path):
    with profiler.RecordEvent("off_event"):
        pass
    profiler.start_profiler()
    summary = profiler.stop_profiler(
        profile_path=str(tmp_path / "off.json"))
    assert all(s["name"] != "off_event" for s in summary)
    trace = json.load(open(tmp_path / "off.json"))
    assert trace["traceEvents"] == []


def test_stat_registry():
    monitor.reset()
    monitor.STAT_ADD("feasigns", 10)
    monitor.stat_add("feasigns", 5)
    monitor.stat_set("epoch", 3)
    assert monitor.stat_get("feasigns") == 15
    assert monitor.stats() == {"feasigns": 15, "epoch": 3}
    monitor.reset()
    assert monitor.stats() == {}


def test_stat_time_records_count_and_total_ms():
    monitor.reset()
    for _ in range(3):
        with monitor.stat_time("phase"):
            time.sleep(0.002)
    s = monitor.stats()
    assert s["phase_calls"] == 3
    assert s["phase_ms"] >= 3 * 2.0 * 0.5  # wall clock, generous slack
    # exceptions still record the timing (the finally path)
    with pytest.raises(RuntimeError):
        with monitor.stat_time("phase"):
            raise RuntimeError("boom")
    assert monitor.stats()["phase_calls"] == 4
    monitor.reset()
