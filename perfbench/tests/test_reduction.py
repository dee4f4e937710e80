"""The arithmetic from traces, spans and counters to metrics (CPU, no chip).

``data/v5e_trace.json`` is a slice of a trace recorded on the v5e by the
harness itself (how it was cut is in ``data/README.md``);
``data/v5e_trace.expected.json`` holds what :func:`xplane.reduce` gave for
it when it was recorded, so a later edit of the reduction shows as a diff.
The hand-made traces below pin the interval arithmetic itself."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import flops, readers, xplane  # noqa: E402

MS = 1e6   # ns


def op(name, opcode, start_ms, dur_ms, extra=""):
    text = f"%{name} = f32[8,128]{{1,0}} {opcode}(f32[8,128]{{1,0}} %p){extra}"
    return [text, start_ms * MS, dur_ms * MS]


def trace(device_lines, host_events, n_devices=1):
    planes = [{"name": f"/device:TPU:{i}",
               "lines": [{"name": k, "events": v}
                         for k, v in device_lines.items()]}
              for i in range(n_devices)]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": host_events}]})
    return {"planes": planes}


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert xplane.length([(0, 3), (5, 6)]) == 4
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert xplane.subtract([(0, 2), (3, 5)], []) == [(0, 2), (3, 5)]
    assert xplane.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_names():
    text = ("%copy.5 = f32[400,16,16,128]{3,2,1,0:T(8,128)} "
            "copy(f32[400,16,16,128]{3,2,1,0:T(8,128)} %param.3)")
    assert xplane.opcode(text) == "copy"
    assert xplane.short_name(text) == "copy_f32_400_16_16_128"
    tup = ("%all-gather-start.2 = (bf16[512,2048]{1,0:T(8,128)(2,1)}, "
           "bf16[2048,2048]{1,0}) all-gather-start(bf16[512,2048]{1,0} %x), "
           "channel_id=3")
    assert xplane.opcode(tup) == "all-gather-start"
    assert xplane.is_collective(tup)
    assert not xplane.is_collective(text)
    kern = ('%custom-call.7 = bf16[128,1024,128]{2,1,0} custom-call(bf16[128,'
            '1024,128]{2,1,0} %q), custom_call_target="tpu_custom_call"')
    assert xplane.is_mosaic(kern) and not xplane.is_mosaic(text)
    assert xplane.short_name(kern).endswith("_mosaic")
    fus = "%fusion.3 = f32[8,2048]{1,0} fusion(f32[8,2048]{1,0} %a), kind=kLoop, calls=%f"
    assert xplane.short_name(fus) == "fusion_f32_8_2048_kLoop"


def test_busy_idle_gaps_and_kernels():
    ops = [op("fusion.1", "fusion", 10, 20, ", kind=kLoop"),
           op("custom-call.2", "custom-call", 30, 10,
              ', custom_call_target="tpu_custom_call"'),
           op("copy.3", "copy", 60, 10),
           op("copy.4", "copy", 120, 10)]      # outside the window
    host = [[xplane.WINDOW_SPAN, 0 * MS, 100 * MS],
            ["bench.step", 5 * MS, 50 * MS],
            ["serving.decode", 8 * MS, 30 * MS],
            ["bench.step", 56 * MS, 40 * MS],
            ["$file.py:1 frame", 0, 100 * MS]]
    mods = [["jit__step(123)", 10 * MS, 30 * MS], ["jit__prefill(9)", 60 * MS, 10 * MS]]
    r = xplane.reduce(trace({"XLA Ops": ops, "XLA Modules": mods}, host))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["mosaic_s"] == pytest.approx(0.010)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    assert dict(r["device_ops"]) == pytest.approx({
        "jit__step:fusion_f32_8_128_kLoop": 0.020,
        "jit__step:custom-call_f32_8_128_mosaic": 0.010,
        "jit__prefill:copy_f32_8_128": 0.010})
    # gaps: 0-10 (midpoint 5 ms: bench.step just open, no inner span),
    # 40-60 (midpoint 50: bench.step), 70-100 (midpoint 85: bench.step)
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.step": 0.060})
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert xplane.host_span_seconds(
        trace({"XLA Ops": ops}, host), "serving.decode") == [pytest.approx(0.03)]


def test_gap_is_named_by_outer_and_innermost_span():
    ops = [op("a.1", "fusion", 0, 10), op("b.2", "fusion", 30, 10)]
    host = [[xplane.WINDOW_SPAN, 0, 40 * MS],
            ["bench.step", 1 * MS, 38 * MS],
            ["serving.decode", 12 * MS, 16 * MS],
            ["np.asarray(jax.Array)", 18 * MS, 4 * MS]]
    r = xplane.reduce(trace({"XLA Ops": ops}, host))
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.step/np.asarray_jax.Array_": 0.020})


def test_collective_time_not_hidden_by_compute():
    # sync all-reduce 10-20 on the op line: fully exposed (10 ms).
    # async all-gather 30-50: start op 30-31, done op 45-50 on the op line,
    # compute 31-45 in between -> exposed 1 + 5 = 6 ms of its 20.
    ops = [op("f.1", "fusion", 0, 10),
           op("all-reduce.1", "all-reduce", 10, 10),
           op("all-gather-start.1", "all-gather-start", 30, 1),
           op("f.2", "fusion", 31, 14),
           op("all-gather-done.1", "all-gather-done", 45, 5)]
    asyn = [op("all-gather-start.1", "all-gather-start", 30, 20)]
    host = [[xplane.WINDOW_SPAN, 0, 50 * MS]]
    r = xplane.reduce(trace({"XLA Ops": ops, "Async XLA Ops": asyn}, host,
                            n_devices=2))
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx(0.030)
    assert r["collective_exposed_s"] == pytest.approx(0.016)
    assert r["busy_s"] == pytest.approx(0.040)


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce(trace({"XLA Ops": []}, []))
    with pytest.raises(ValueError):
        xplane.reduce({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "t", "events": [[xplane.WINDOW_SPAN, 0, 1]]}]}]})


def test_recorded_v5e_trace_reduces_as_recorded():
    path = os.path.join(HERE, "data", "v5e_trace.json")
    got = xplane.reduce(xplane.load_json(path))
    with open(os.path.join(HERE, "data", "v5e_trace.expected.json")) as f:
        want = json.load(f)
    for key in ("devices", "window_s", "busy_s", "mosaic_s", "collective_s",
                "collective_exposed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert [k for k, _ in got["device_ops"]] == [k for k, _ in want["device_ops"]]
    assert [k for k, _ in got["idle_gaps"]] == [k for k, _ in want["idle_gaps"]]
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["mosaic_s"] <= got["busy_s"]
    gaps = sum(v for _, v in xplane.reduce(
        xplane.load_json(path), top=10**6)["idle_gaps"])
    assert gaps + got["busy_s"] == pytest.approx(got["window_s"], rel=1e-6)


# ------------------------------------------------------------ readers, flops

def test_reader_reductions():
    obs = {"spans": {"serving.decode": [0.040, 0.050, 0.045],
                     "serving.prefill": [0.3, 0.2]},
           "samples": {"decode_only_step_s": [0.044, 0.046],
                       "prefill_excess_s": [0.3, 0.2]},
           "counters": {"window_s": 10.0, "compiles_in_window": 0},
           "trace": {"mosaic_s": 1.0, "busy_s": 4.0}}
    assert readers.read("decode_dispatch_p50_ms.chat", obs) == pytest.approx(45.0)
    assert readers.read("decode_step_p50_ms.docs", obs) == pytest.approx(45.0)
    assert readers.read("prefill_share_pct.docs", obs) == pytest.approx(5.0)
    assert readers.read("compiles_in_window.train", obs) == 0.0
    assert readers.read("mosaic_time_pct.train", obs) == pytest.approx(25.0)
    # nothing to read -> nothing
    assert readers.read("gen_lag_p99_ms.chat", obs) is None
    assert readers.read("device_idle_pct.chat", obs) is None
    assert readers.percentile([1, 2, 3, 4], 50) == 2.5
    assert readers.percentile([5], 99) == 5


def test_every_per_layer_metric_of_the_benchmark_has_its_file():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = readers.spec(m["name"])
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        assert spec["moves"] in e2e
        assert spec["reader"]["from"] in readers.GROUPS


def test_causal_flop_count_and_peaks():
    kw = dict(hidden=2048, ffn=8192, layers=20, vocab_rows=50304, seq=1024)
    per_layer = 8 * 2048**2 + 4 * 2048 * 8192 + 2 * 1024 * 2048
    assert flops.train_flops_per_token(**kw) == \
        3.0 * (20 * per_layer + 2 * 2048 * 50304)
    # the masked half that bench.model_flops_per_token counts is ~4% of it
    full = 3.0 * (20 * (per_layer + 2 * 1024 * 2048) + 2 * 2048 * 50304)
    assert 1.03 < full / flops.train_flops_per_token(**kw) < 1.05
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("_source")
