"""Device-cost observatory (observability/devprof.py).

The contracts under test:

- **cost capture is per tracked_jit site**: with FLAGS_serving_devprof
  on, every compile of a tracked serving entry records its lowered
  ``cost_analysis()`` (flops / HBM bytes / output bytes) under its
  qualified name in ``devprof.cost_table()``, mints ``xla_cost``
  gauges, and yields a stable ``cost_digest()`` — while the compile
  counters the predictor audits never move (devprof is a validated
  compile no-op);
- **sampled timing is deterministic on a virtual clock**: the
  Knuth-hash sampler is a pure function of the dispatch counter, and
  the ``block_until_ready`` sync never leaks wall time into the
  engine's SLO cost estimators — two same-seed virtual-clock runs
  with devprof on produce identical reports, and those reports equal
  the devprof-OFF run bit for bit (the regression lock for the
  admission-EMA wall-clock leak);
- **blame stays an accounting identity through the split**: an
  annotated trace replaces ``decode`` with ``decode_device`` +
  ``decode_host`` and still sums exactly to E2E — on the plain
  engine, at megastep N>1, and across a disagg prefill->decode
  handoff;
- **MFU math**: roofline/aggregate MFU and HBM utilization follow
  exactly from injected costs and timings, and the captured
  decode-step flops respect a hand-computed tiny-GPT matmul floor;
- **sampling=0 is bit-identical to devprof-off**: no samples means no
  annotation, so chrome-trace and spans exports are byte-identical;
- **the perf ledger round-trips**: append -> read -> baseline ->
  compare passes on itself, flags an injected regression, honors
  per-metric tolerance/slack, and gates the cost digest.
"""

import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability
from paddle_tpu.analysis import predict_serving_compiles
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import devprof, tracing
from paddle_tpu.serving import DisaggRouter, ServingEngine
from tools import perf_ledger, perf_regress
from tools.loadgen import LoadGen, VirtualClock


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = GPTConfig(vocab_size=97, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _clean():
    """Every test leaves the observatory, traces and flags as it
    found them (test_devprof sorts before test_tracing — leaked state
    would poison the byte-identity tests there)."""
    yield
    pt.set_flags({"serving_devprof": False,
                  "serving_devprof_sample": 0.1})
    devprof.reset()
    tracing.reset()


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 97, size=n).tolist() for n in sizes]


_GEOM = dict(max_slots=3, max_len=32, buckets=[8, 16], max_queue=16,
             block_size=4)

#: hand-computed tiny-GPT matmul floor for ONE decode step at the
#: _GEOM geometry: 2 flops/MAC * (per layer: QKV+proj 4*h^2 + FFN
#: 2*h*ffn, summed over layers, + the h*vocab head) * batch(max_slots)
_DECODE_MATMUL_FLOOR = 2 * (2 * (4 * 32 * 32 + 2 * 32 * 64)
                            + 32 * 97) * 3          # = 116928


def _run_engine(model, **kw):
    eng = ServingEngine(model, **_GEOM, **kw)
    reqs = [eng.submit(p, max_new_tokens=4)
            for p in _prompts((3, 5, 7), seed=1)]
    eng.run_until_idle()
    return eng, reqs


# ------------------------------------------------- static cost capture
def test_cost_capture_per_tracked_site(model):
    pt.set_flags({"serving_devprof": True})
    observability.reset_compiles()
    eng, reqs = _run_engine(model, devprof_sample=1.0)
    assert all(r.state == "done" for r in reqs)
    tbl = devprof.cost_table()
    assert "decode_step_paged" in tbl, sorted(tbl)
    assert any(k.startswith("serving_prefill_paged{bucket=")
               for k in tbl), sorted(tbl)
    for qual, rec in tbl.items():
        assert rec["captures"] >= 1
        assert rec["signature"], qual
    dec = tbl["decode_step_paged"]
    # captured flops can never undercut the hand-counted matmuls
    assert dec["flops"] >= _DECODE_MATMUL_FLOOR, dec
    assert dec["hbm_bytes"] and dec["hbm_bytes"] > 0, dec
    # the digest is a stable 16-hex function of the table
    d1, d2 = devprof.cost_digest(), devprof.cost_digest()
    assert d1 == d2 and len(d1) == 16
    int(d1, 16)
    # gauges minted per site+metric; snapshot carries the same table
    text = observability.prometheus_text()
    assert 'xla_cost{fn="decode_step_paged"' in text
    assert observability.snapshot()["device_costs"] == tbl
    # the capture path added ZERO tracked compiles beyond the engine's
    # own predicted surfaces: re-lowering the raw fn is out-of-band
    wl = [[(p, 4) for p in _prompts((3, 5, 7), seed=1)]]
    want = predict_serving_compiles(wl, buckets=[8, 16], max_len=32,
                                    block_size=4)
    observed = {q: rec["count"]
                for q, rec in observability.compiles().items()}
    assert observed == want


def test_cost_capture_off_without_flag(model):
    assert not devprof.enabled()
    assert devprof.note_compile("x", {}, lambda v: v, {}, (1.0,),
                                {}) is None
    assert devprof.cost_table() == {}
    assert devprof.cost_digest() is None


def test_normalize_cost_shape_variants():
    full = devprof._normalize_cost(
        {"flops": 10, "bytes accessed": 20.5,
         "bytes accessedout{}": 3, "utilization": 9})
    assert full == {"flops": 10.0, "hbm_bytes": 20.5, "out_bytes": 3.0}
    empty = {"flops": None, "hbm_bytes": None, "out_bytes": None}
    assert devprof._normalize_cost(None) == empty
    assert devprof._normalize_cost({"flops": "nan?"})["flops"] is None


def test_predictor_devprof_is_validated_noop():
    wl = [[([1, 2, 3], 4), ([5, 6, 7, 8, 9], 3)]]
    kw = dict(buckets=[8, 16], max_len=32, block_size=4)
    plain = predict_serving_compiles(wl, **kw)
    assert predict_serving_compiles(wl, devprof=True, **kw) == plain
    assert predict_serving_compiles(wl, devprof=0.25, **kw) == plain
    with pytest.raises(ValueError, match="devprof"):
        predict_serving_compiles(wl, devprof=1.5, **kw)


# ------------------------------------------------- sampling machinery
def test_sampler_deterministic_and_proportional():
    p = devprof.DevProfiler(sample=0.25, peak_flops=1.0,
                            peak_bytes_per_s=1.0)
    picks = [p.tick() for _ in range(2000)]
    q = devprof.DevProfiler(sample=0.25, peak_flops=1.0,
                            peak_bytes_per_s=1.0)
    # pure function of the dispatch counter: replays sample the same
    # step indices, no RNG stream consumed
    assert picks == [q.tick() for _ in range(2000)]
    frac = sum(picks) / len(picks)
    assert 0.18 < frac < 0.32, frac
    off = devprof.DevProfiler(sample=0.0, peak_flops=1.0,
                              peak_bytes_per_s=1.0)
    assert not any(off.tick() for _ in range(100))
    assert off.stats()["dispatches"] == 100
    with pytest.raises(ValueError, match="sample"):
        devprof.DevProfiler(sample=1.5)


def _seeded_burst(model, *, devprof_on, sample=1.0, seed=11):
    """One seeded virtual-clock loadgen burst; returns (report,
    engine-stats) with the store holding the run's traces."""
    tracing.reset()
    vc = VirtualClock()
    kw = dict(devprof=True, devprof_sample=sample) if devprof_on else {}
    eng = ServingEngine(model, clock=vc.now, slo_ttft_ms=60.0,
                        slo_prefill_ms=4.0, slo_tpot_ms=1.5,
                        **_GEOM, **kw)
    lg = LoadGen(mode="bursty", rate=30.0, duration=0.5, seed=seed,
                 vocab_size=97, prompt_tokens=(3, 7), new_tokens=(2, 4))
    report = lg.run(eng, clock=vc, step_cost_ms=4.0)
    assert report["completed"] > 0
    return report, eng.stats()


_REPORT_KEYS = ("completed", "shed_total", "ttft_ms_p50", "ttft_ms_p95",
                "goodput_per_s", "slo_attainment")


def test_virtual_clock_determinism_and_no_admission_perturbation(model):
    """Two same-seed virtual-clock runs with devprof sampling EVERY
    dispatch agree exactly — and agree with the devprof-OFF run. The
    second equality is the regression lock for the wall-clock leak:
    the sampler's block_until_ready must close OUTSIDE the admission
    EMA windows, or SLO shed decisions pick up wall noise."""
    base, _ = _seeded_burst(model, devprof_on=False)
    runs = [_seeded_burst(model, devprof_on=True) for _ in range(2)]
    for rep, st in runs:
        for k in _REPORT_KEYS:
            assert rep.get(k) == base.get(k), (k, rep.get(k),
                                               base.get(k))
        dp = st["devprof"]
        assert dp["sample"] == 1.0
        assert dp["dispatches"] > 0
        assert dp["samples"] == dp["dispatches"]
    # the sampler's dispatch/sample counters replay exactly too
    assert runs[0][1]["devprof"]["dispatches"] == \
        runs[1][1]["devprof"]["dispatches"]
    # virtual-clock samples are zero-width: the device fraction stays
    # unannotated rather than inventing a 0/0 split
    assert runs[0][1]["devprof"]["device_frac"] is None


# ------------------------------------------------- blame device split
def _split_identity(info):
    bl = info["blame_ms"]
    assert "decode" not in bl, bl
    assert {"decode_device", "decode_host"} <= set(bl), bl
    assert bl["decode_device"] >= 0.0 and bl["decode_host"] >= 0.0
    assert sum(bl.values()) == pytest.approx(info["e2e_ms"], abs=1e-6)


def test_blame_split_identity_plain_engine(model):
    tracing.reset()
    eng, reqs = _run_engine(model, devprof=True, devprof_sample=1.0)
    frac = eng.stats()["devprof"]["device_frac"]
    assert frac is not None and 0.0 <= frac <= 1.0
    for r in reqs:
        info = tracing.get(r.id)
        assert info is not None and info["outcome"] == "done"
        _split_identity(info)
        # the TTFT prefix survives the split untouched
        assert info["ttft_ms"] == pytest.approx(r.ttft * 1e3, abs=1e-3)


def test_blame_split_identity_megastep(model):
    tracing.reset()
    eng, reqs = _run_engine(model, megastep=4, devprof=True,
                            devprof_sample=1.0)
    dp = eng.stats()["devprof"]
    assert any(e["entry"].startswith("decode_megastep_paged{n=")
               for e in dp["entries"]), dp["entries"]
    for r in reqs:
        info = tracing.get(r.id)
        assert info is not None and info["outcome"] == "done"
        _split_identity(info)


def test_blame_split_identity_disagg_handoff(model):
    """Requests that prefill on one worker and decode on another keep
    the exact identity with BOTH the handoff component and the
    device/host split (the split annotation comes from the decode
    worker that finishes the request)."""
    tracing.reset()
    pt.set_flags({"serving_devprof": True,
                  "serving_devprof_sample": 1.0})
    rt = DisaggRouter(model, n_prefill=1, n_decode=2,
                      prefix_cache=False, **_GEOM)
    reqs = [rt.submit(p, max_new_tokens=6)
            for p in _prompts((3, 7), seed=3)]
    rt.run_until_idle()
    for r in reqs:
        assert r.state == "done"
        info = tracing.get(r.id)
        assert info is not None and info["outcome"] == "done"
        assert "handoff" in info["blame_ms"], info["blame_ms"]
        _split_identity(info)


# ------------------------------------------------- MFU / roofline math
def _inject_cost(entry, flops, hbm_bytes):
    with devprof._lock:
        devprof._COSTS[entry] = {
            "flops": flops, "hbm_bytes": hbm_bytes, "out_bytes": 1.0,
            "signature": "syn", "supported": True, "captures": 1}


def test_mfu_and_roofline_hand_math():
    """Every derived number follows by hand from two injected samples
    against a synthetic cost entry and unit peaks."""
    _inject_cost("syn", flops=2e6, hbm_bytes=4e6)
    p = devprof.DevProfiler(sample=1.0, peak_flops=1e10,
                            peak_bytes_per_s=1e10)
    p.note_step("syn", device_s=0.001, host_s=0.0005)
    roof = p.roofline("syn")
    # per-dispatch 1 ms: mfu = 2e6 / (1e-3 * 1e10) = 0.2, hbm 0.4
    assert roof["mfu"] == pytest.approx(0.2)
    assert roof["hbm_util"] == pytest.approx(0.4)
    assert roof["verdict"] == "hbm-bound"
    assert roof["device_ms_mean"] == pytest.approx(1.0)
    assert p.device_frac() == pytest.approx(0.001 / 0.0015)
    assert p.mfu() == pytest.approx(0.2)
    # a second, host-heavy sample flips the verdict and halves the
    # per-dispatch device time: mfu doubles, host share dominates
    p.note_step("syn", device_s=0.0, host_s=0.004)
    roof2 = p.roofline("syn")
    assert roof2["samples"] == 2
    assert roof2["verdict"] == "host-bound"
    assert roof2["mfu"] == pytest.approx(0.4)
    assert p.mfu() == pytest.approx(roof2["mfu"])
    assert p.host_share() == pytest.approx(0.0045 / 0.0055)
    # the gauges carry the same numbers
    snap = observability.snapshot()["gauges"]
    assert snap["serving_mfu"] == pytest.approx(roof2["mfu"])
    assert snap["serving_host_overhead_share"] == \
        pytest.approx(p.host_share())
    # an entry with no captured cost is honest about it
    q = devprof.DevProfiler(sample=1.0, peak_flops=1e9,
                            peak_bytes_per_s=1e9)
    q.note_step("uncaptured", device_s=0.001, host_s=0.0)
    assert q.roofline("uncaptured")["verdict"] == "unattributed"
    assert q.mfu() is None


def test_real_capture_feeds_live_mfu(model):
    """End-to-end on the real engine (wall clock): sampled decode
    dispatches joined against captured costs mint a live MFU."""
    pt.set_flags({"serving_devprof": True})
    eng, _reqs = _run_engine(model, devprof_sample=1.0)
    dp = eng.stats()["devprof"]
    assert dp["samples"] > 0
    assert dp["mfu"] is not None and dp["mfu"] > 0.0
    by_entry = {e["entry"]: e for e in dp["entries"]}
    dec = by_entry["decode_step_paged"]
    # the reported roofline recomputes from its own published parts
    # (both sides round to 6 decimals, so compare at that granularity)
    want = dec["flops"] / (dec["device_ms_mean"] * 1e-3 *
                           eng._devprof.peak_flops)
    assert dec["mfu"] == pytest.approx(want, abs=5.1e-7)
    text = observability.prometheus_text()
    assert "serving_mfu" in text and "serving_device_step_ms" in text
    observability.validate_prometheus_text(text)


# ------------------------------------------------- sampling=0 identity
def test_sampling_zero_bit_identical_to_off(model, tmp_path):
    """FLAGS on + sample=0.0 must leave every byte-identity surface
    untouched: no samples -> no annotation -> no split -> chrome and
    spans exports equal the devprof-off run's exactly."""
    artifacts = []
    for mode in ("off", "zero"):
        if mode == "zero":
            pt.set_flags({"serving_devprof": True})
        rep, st = _seeded_burst(model, devprof_on=(mode == "zero"),
                                sample=0.0)
        chrome = tmp_path / f"trace_{mode}.json"
        spans = tmp_path / f"spans_{mode}.jsonl"
        tracing.export_chrome_trace(str(chrome))
        tracing.export_spans_jsonl(str(spans))
        artifacts.append((chrome.read_bytes(), spans.read_bytes(),
                          {k: rep.get(k) for k in _REPORT_KEYS}))
        if mode == "zero":
            dp = st["devprof"]
            assert dp["samples"] == 0 and dp["dispatches"] > 0
            assert dp["device_frac"] is None
        else:
            assert "devprof" not in st
    assert artifacts[0][0] == artifacts[1][0]
    assert artifacts[0][1] == artifacts[1][1]
    assert artifacts[0][2] == artifacts[1][2]


# ------------------------------------------------- perf ledger / gate
_REPORT = {
    "goodput_per_s": 52.13, "ttft_ms_p95": 6.4, "tpot_ms_p95": 3.71,
    "slo_attainment": 1.0, "completed": 25, "offered": 25,
    "shed_total": 0, "new_compiles_after_warmup": 0,
    "devprof": {"sample": 1.0, "dispatches": 113, "samples": 113,
                "device_frac": 0.4, "host_overhead_share": 0.6,
                "mfu": 0.12, "cost_digest": "ab" * 8},
}


def test_ledger_append_read_roundtrip(tmp_path):
    path = tmp_path / "ledger.jsonl"
    row = perf_ledger.append_report(str(path), dict(_REPORT),
                                    run="loadgen", label="t")
    assert row["schema"] == perf_ledger.SCHEMA
    assert row["goodput_per_s"] == 52.13 and row["mfu"] == 0.12
    assert row["cost_digest"] == "ab" * 8 and row["run"] == "loadgen"
    perf_ledger.append_report(str(path), dict(_REPORT), run="soak")
    rows = perf_ledger.read_rows(str(path))
    assert len(rows) == 2 and rows[0] == row
    assert perf_ledger.latest(str(path))["run"] == "soak"
    # corrupt trailing line -> loud failure, never a silent skip
    with open(path, "a") as f:
        f.write("not json\n")
    with pytest.raises(ValueError, match=r":3: bad ledger line"):
        perf_ledger.read_rows(str(path))


def test_regress_gate_baseline_and_injection(tmp_path):
    path = tmp_path / "ledger.jsonl"
    base = tmp_path / "baseline.json"
    row = perf_ledger.append_report(str(path), dict(_REPORT),
                                    run="loadgen")
    perf_regress.write_baseline(str(base), row)
    doc = json.loads(base.read_text())
    assert doc["metrics"]["goodput_per_s"] == 52.13
    assert doc["cost_digest"] == "ab" * 8
    # a run compared against its own baseline always passes
    failures, _notes = perf_regress.compare(row, doc, tolerance=0.10)
    assert failures == []
    # injected regression: goodput halves -> the gate trips
    bad = dict(row)
    bad["goodput_per_s"] = row["goodput_per_s"] / 2
    failures, _ = perf_regress.compare(bad, doc, tolerance=0.10)
    assert any("goodput_per_s" in f for f in failures), failures
    # latency-like metrics trip on the OTHER side
    slow = dict(row)
    slow["tpot_ms_p95"] = row["tpot_ms_p95"] * 2
    failures, _ = perf_regress.compare(slow, doc, tolerance=0.10)
    assert any("tpot_ms_p95" in f for f in failures), failures
    # within-tolerance drift passes
    drift = dict(row)
    drift["goodput_per_s"] = row["goodput_per_s"] * 0.95
    assert perf_regress.compare(drift, doc, tolerance=0.10)[0] == []
    # a gated metric missing from the row is itself a failure
    gone = dict(row)
    gone["ttft_ms_p95"] = None
    failures, _ = perf_regress.compare(gone, doc, tolerance=0.10)
    assert any("ttft_ms_p95" in f for f in failures), failures


def test_regress_digest_and_slack_rules(tmp_path):
    row = perf_ledger.make_row(dict(_REPORT), run="loadgen")
    # zero-valued lower-better baselines get an absolute slack so the
    # relative band never collapses to [0, 0]
    zrow = dict(row)
    zrow["ttft_ms_p95"] = 0.0
    doc = {"schema": 1, "cost_digest": row["cost_digest"],
           "metrics": {}}
    perf_regress.write_baseline(str(tmp_path / "b.json"), zrow)
    zdoc = json.loads((tmp_path / "b.json").read_text())
    assert zdoc["metrics"]["ttft_ms_p95"] == {"value": 0.0,
                                              "slack": 1.0}
    probe = dict(zrow)
    probe["ttft_ms_p95"] = 0.9          # inside the slack band
    assert perf_regress.compare(probe, zdoc)[0] == []
    probe["ttft_ms_p95"] = 1.2          # outside it
    assert perf_regress.compare(probe, zdoc)[0] != []
    # digest drift: a note by default, fatal under --strict-digest
    doc["metrics"] = {"goodput_per_s": row["goodput_per_s"]}
    doc["cost_digest"] = "f" * 16
    failures, notes = perf_regress.compare(row, doc)
    assert failures == [] and any("digest" in n for n in notes)
    failures, _ = perf_regress.compare(row, doc, strict_digest=True)
    assert any("digest" in f for f in failures)
    # an empty baseline is a configuration error, not a green gate
    with pytest.raises(SystemExit, match="empty baseline"):
        perf_regress.write_baseline(str(tmp_path / "e.json"), row,
                                    metrics=["no_such_metric"])
