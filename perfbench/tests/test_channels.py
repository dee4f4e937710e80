"""The two channels of PR 26 (CPU, no chip): the program's counters over
the window (``serve.Tracing`` with a ``counters`` callable) and the
arguments of its spans (``serve.spans_of``), read by metric files as data.
``data/v5e_host_spans.json`` was recorded on the v5e (``data/README.md``)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import readers, serve  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "v5e_host_spans.json")) as f:
        return serve.spans_of(json.load(f)["traceEvents"])


def test_span_args_of_the_recorded_run(recorded):
    spans, span_args = recorded
    assert span_args["serving.prefill_step.rows"] == [1, 1, 2, 1, 1]
    assert span_args["serving.prefill_step.bucket"] == [768, 1024, 768,
                                                        1024, 768]
    assert len(span_args["serving.engine_step.active"]) == \
        len(spans["serving.engine_step"]) == 28
    assert sum(spans["serving.prefill_step"]) == pytest.approx(1.331093898)
    # a span without args gives durations only
    assert not any(k.startswith("serving.decode.fetch.") for k in span_args)


def test_metric_files_read_the_recorded_run(recorded):
    spans, span_args = recorded
    obs = {"spans": spans, "span_args": span_args, "samples": {},
           "counters": {"window_s": 2.4}, "trace": {}}
    assert readers.read("prefill_rows_mean.docs", obs) == pytest.approx(1.2)
    assert readers.read("prefill_inside_share_pct.docs", obs) == \
        pytest.approx(100.0 * 1.331093898 / 2.4)
    # a program whose spans carry no args: nothing to read, no metric
    obs["span_args"] = {}
    assert readers.read("prefill_rows_mean.docs", obs) is None


def test_window_counters_are_the_difference_and_the_closing_value(tmp_path):
    states = iter([
        {"engine.pool_dispatches": 120, "engine.pool_inplace": 120,
         "engine.kv_blocks_used": 300, "STAT_serving_tokens": 9000},
        {"engine.pool_dispatches": 2302, "engine.pool_inplace": 2302,
         "engine.kv_blocks_used": 280, "STAT_serving_tokens": 21000,
         "engine.ttft_p50_ms": 81.5}])       # None at the open: not numeric
    tracing = serve.Tracing(False, str(tmp_path), 45.0,
                            counters=lambda: next(states))
    tracing.window_open()
    assert tracing.window_close() == {}      # untraced: counters, no spans
    c = tracing.window_counters
    assert c["engine.pool_dispatches"] == 2182
    assert c["STAT_serving_tokens"] == 12000
    assert c["engine.kv_blocks_used.close"] == 280      # a gauge
    assert c["engine.kv_blocks_used"] == -20            # its difference
    assert c["engine.ttft_p50_ms"] == c["engine.ttft_p50_ms.close"] == 81.5


@pytest.mark.parametrize("name", ["pool_inplace_share_pct.chat",
                                  "pool_inplace_share_pct.docs",
                                  "pool_inplace_share_pct.decode"])
def test_pool_inplace_share_reads_dotted_counter_names(name):
    obs = {"counters": {"engine.pool_inplace": 2180.0,
                        "engine.pool_dispatches": 2182.0}}
    assert readers.read(name, obs) == pytest.approx(100.0 * 2180 / 2182)
    assert readers.read(name, {"counters": {"window_s": 45.0}}) is None
    # no dispatch in the window: no share
    assert readers.read(name, {"counters": {
        "engine.pool_inplace": 0, "engine.pool_dispatches": 0}}) is None


def test_over_without_a_group_is_a_counter(monkeypatch):
    spec = {"reader": {"from": "spans", "name": "s", "reduce": "sum",
                       "over": "window_s"}}
    monkeypatch.setattr(readers, "spec", lambda name: spec)
    obs = {"spans": {"s": [1.0, 2.0]}, "counters": {"window_s": 6.0}}
    assert readers.read("any", obs) == pytest.approx(0.5)
    spec["reader"]["over"] = "trace.kernel_s.flash_fwd"
    obs["trace"] = {"kernel_s.flash_fwd": 12.0}
    assert readers.read("any", obs) == pytest.approx(0.25)


def test_program_counters_keeps_numbers_under_the_programs_names():
    from paddle_tpu import monitor

    class Engine:
        def stats(self):
            return {"pool_dispatches": 7, "pool_inplace_share": 1.0,
                    "paged": True, "attn_impl": "xla", "ttft_p50_ms": None,
                    "shed": {"queue_full": 0}}

    monitor.stat_add("STAT_serving_pool_inplace", 7)
    got = serve.program_counters(Engine())
    assert got["engine.pool_dispatches"] == 7
    assert got["engine.pool_inplace_share"] == 1.0
    assert got["STAT_serving_pool_inplace"] >= 7
    assert not {"engine.paged", "engine.attn_impl", "engine.ttft_p50_ms",
                "engine.shed"} & set(got)
