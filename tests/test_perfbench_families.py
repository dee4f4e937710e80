"""The benchmark's family seam, where the driver counts it: the checks of
``perfbench/tests/test_families.py`` as cases over both families, every
kernel's operation counts against a hand count written here, and the new
cell's toy twin rehearsed to its end."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import families, flops          # noqa: E402

CONFIGS = {"cgpt-1p3b": "gpt2", "cgpt-1p3b-d20": "gpt2",
           "laguna-xs2-share8": "laguna"}
JOBS = {"gpt2": "pretrain_1chip", "laguna": "laguna_pretrain_8k"}


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def config(name):
    return load("configs", name + ".json")


def test_an_unknown_family_is_an_error_that_lists_the_known_ones():
    with pytest.raises(SystemExit) as e:
        families.load({"name": "some-model", "family": "no_such_family"})
    assert "no_such_family" in str(e.value)
    assert families.known() == ["gpt2", "laguna"]
    assert all(name in str(e.value) for name in families.known())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_configuration_reaches_its_family_with_every_export(name):
    cfg = config(name)
    assert families.name_of(cfg) == CONFIGS[name]
    family = families.load(cfg)
    assert family.__name__ == "perfbench.families." + CONFIGS[name]
    assert all(callable(getattr(family, x)) for x in families.EXPORTS)


def test_a_family_that_lacks_an_export_is_refused(monkeypatch):
    half = types.ModuleType("perfbench.families.fixture_half")
    half.forward = lambda params, ids, cfg: None
    monkeypatch.setitem(sys.modules, half.__name__, half)
    with pytest.raises(SystemExit) as e:
        families.load({"name": "x", "family": "fixture_half"})
    assert "train_flops_per_token" in str(e.value)


@pytest.mark.parametrize("name,key", [("cgpt-1p3b-d20", "n_head"),
                                      ("laguna-xs2-share8", "vocab_size")])
def test_the_file_is_the_truth_the_program_is_checked_against(name, key):
    cfg = config(name)
    family = families.load(cfg)
    family.model_config(cfg)                       # as written: accepted
    cfg[key] += 1
    with pytest.raises(SystemExit):
        family.model_config(cfg)


def test_the_laguna_file_holds_the_published_widths_uncut():
    cfg = config("laguna-xs2-share8")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2") \
            if os.path.exists(f.name) else None
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "vocab_size",
                       "max_position_embeddings"}
    assert changed <= set(cfg["reduced"])
    mc = families.load(cfg).model_config(cfg)
    assert mc.num_params() == cfg["params_held"] == 975_874_048
    assert (mc.experts, mc.kv_heads, mc.vocab) == \
        ((0, 32), (0, 1), (0, 12544))


# per layer 8*2048^2 + 4*2048*8192 + 2*1024*2048 = 104,857,600; head
# 2*2048*50304 = 206,045,184; x3 for the backward.
# Laguna share, forward a token at s 8192: a window layer's projections
# 2*2048*(8+2)*128 + 2*2048*8 + 2*8*128*2048 = 9,469,952 and scores
# 4*8*128*496.03125 = 2,031,744 (mean keys (512*513/2 + 7680*512) / 8192);
# a full layer's 2*2048*8*128 + 2*2048*6 + 2*6*128*2048 = 7,364,608 and
# 4*6*128*4096.5 = 12,584,448; the dense MLP 6*2048*8192 = 100,663,296; a
# sparse MLP 2*2048*256 + 6*2048*512 (shared) + 8*32/256 * 6*2048*512
# (routed) = 13,631,488; the head 2*2048*12544 = 51,380,224. Layers 0-8 are
# 3 full, 6 window; 1 dense, 8 sparse.
LAGUNA_FORWARD = (3 * (7_364_608 + 12_584_448) + 6 * (9_469_952 + 2_031_744)
                  + 100_663_296 + 8 * 13_631_488 + 51_380_224)


@pytest.mark.parametrize("name,seq,by_hand", [
    ("cgpt-1p3b", 1024, 3 * (24 * 104_857_600 + 206_045_184)),
    ("cgpt-1p3b-d20", 1024, 3 * (20 * 104_857_600 + 206_045_184)),
    ("laguna-xs2-share8", 8192, 3 * LAGUNA_FORWARD)])
def test_train_flops_per_token_is_the_hand_count(name, seq, by_hand):
    cfg = config(name)
    assert families.load(cfg).train_flops_per_token(cfg, seq) == by_hand
    if name == "laguna-xs2-share8":
        assert LAGUNA_FORWARD == 389_952_768


# one call's (FLOPs, bytes), by hand. gpt2 at batch 8 x 16 heads, s 1024,
# d 128: a matmul is 2*128*1024^2*128/2; an array 128*1024*128*2 bytes, a
# float32 row 128*1024*4. Laguna at batch 2, s 8192, d 128, 1 KV head:
# window layers 8 query heads (bh 16, 496.03125 keys), full layers 6 (bh
# 12, 4096.5 keys); an array of the query heads is bh*8192*128*2 bytes, of
# the KV head 2*8192*128*2; matmuls 2 / 3 / 4, query-head arrays 2 / 3 / 2,
# KV arrays 2 / 2 / 4, rows 1 / 2 / 2. Grouped products at the expected load
# under uniform ids, 16384 tokens * 8 choices * 32/256 held = 16384 rows:
# 2*16384*2048*n FLOPs, n 1024 (gate and up) or 512 (down), and the rows on
# both sides of the product plus the held stack [32, 2048, n] at 2 bytes.
MM = 2 * 128 * 1024 * 1024 * 128 // 2
ARR, ROW = 128 * 1024 * 128 * 2, 128 * 1024 * 4
QW, QF, KV = 16 * 8192 * 128 * 2, 12 * 8192 * 128 * 2, 2 * 8192 * 128 * 2
UP = (68_719_476_736, 2 * 16384 * (2048 + 1024) + 2 * 32 * 2048 * 1024)
DOWN = (34_359_738_368, 2 * 16384 * (512 + 2048) + 2 * 32 * 512 * 2048)
KERNELS = {
    ("gpt2", "flash_fwd"): (2 * MM, 4 * ARR + ROW),
    ("gpt2", "flash_bwd_dq"): (3 * MM, 5 * ARR + 2 * ROW),
    ("gpt2", "flash_bwd_dkv"): (4 * MM, 6 * ARR + 2 * ROW),
    ("laguna", "flash_fwd_win"): (33_288_093_696, 2 * QW + 2 * KV
                                  + 16 * 8192 * 4),
    ("laguna", "flash_fwd_full"): (206_183_596_032, 2 * QF + 2 * KV
                                   + 12 * 8192 * 4),
    ("laguna", "flash_bwd_dq_win"): (49_932_140_544, 3 * QW + 2 * KV
                                     + 2 * 16 * 8192 * 4),
    ("laguna", "flash_bwd_dq_full"): (309_275_394_048, 3 * QF + 2 * KV
                                      + 2 * 12 * 8192 * 4),
    ("laguna", "flash_bwd_dkv_win"): (66_576_187_392, 2 * QW + 4 * KV
                                      + 2 * 16 * 8192 * 4),
    ("laguna", "flash_bwd_dkv_full"): (412_367_192_064, 2 * QF + 4 * KV
                                       + 2 * 12 * 8192 * 4),
    ("laguna", "moe_up"): UP,
    ("laguna", "moe_up_dx"): UP,
    ("laguna", "moe_up_dw"): UP,
    ("laguna", "moe_down"): DOWN,
    ("laguna", "moe_down_dx"): DOWN,
    ("laguna", "moe_down_dw"): DOWN,
}


@pytest.mark.parametrize("family,kernel", sorted(KERNELS))
def test_kernel_counts_are_the_hand_count(family, kernel):
    name = {"gpt2": "cgpt-1p3b-d20", "laguna": "laguna-xs2-share8"}[family]
    cfg, job = config(name), load("traffic", JOBS[family] + ".json")
    got = families.load(cfg).kernel_counts(kernel, cfg, job)
    assert got == KERNELS[family, kernel]
    assert 2 * 16 * 8192 * 496.03125 * 128 * 2 == 33_288_093_696
    # and the floor the roofline share divides
    floors = flops.kernel_floors(
        {"kernel_calls." + kernel: 2.0},
        lambda n: families.load(cfg).kernel_counts(n, cfg, job),
        "TPU v5 lite")
    peak = flops.peaks("TPU v5 lite")
    assert floors["kernel_floor_s." + kernel] == pytest.approx(
        2.0 * max(got[0] / peak["bf16_flops"],
                  got[1] / peak["hbm_bytes_per_s"]))


@pytest.mark.parametrize("family", ["gpt2", "laguna"])
def test_a_kernel_the_family_has_no_count_for_is_none(family):
    name = {"gpt2": "cgpt-1p3b-d20", "laguna": "laguna-xs2-share8"}[family]
    cfg, job = config(name), load("traffic", JOBS[family] + ".json")
    counts = families.load(cfg).kernel_counts
    assert counts("paged_decode_attn", cfg, job) is None
    other = {"gpt2": "flash_fwd_win", "laguna": "flash_fwd"}[family]
    assert counts(other, cfg, job) is None        # the other family's name
    assert counts(sorted(k for f, k in KERNELS if f == family)[0], cfg,
                  {"kind": "open_loop"}) is None


def test_every_metric_of_the_new_cell_has_its_file_and_its_kernel():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["laguna_pretrain_8k"]]
    assert len(mine) == 6 + 12 + 6
    kernels = {k for f, k in KERNELS if f == "laguna"}
    for m in mine:
        spec = load("metrics", m["name"] + ".json")
        assert m["name"].endswith(".moe") and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"] == "train_tok_s_chip"
        stem = m["name"][:-len(".moe")]
        if stem.endswith("_roofline_pct"):
            kernel = stem[:-len("_roofline_pct")]
            assert kernel in kernels
            assert spec["reader"]["name"] == "kernel_floor_s." + kernel
            assert spec["reader"]["over"] == "trace.kernel_s." + kernel
        if stem.endswith("_busy_pct"):
            assert stem[:-len("_busy_pct")] in kernels
            assert spec["reader"]["over"] == "trace.busy_s"


def test_the_new_cells_toy_twin_rehearses_to_its_end():
    """``rehearse.py --workload laguna_pretrain_8k`` exits 0: the harness
    found every file by name, built the family's train job, ran the window
    and the check against the family's reference."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "rehearse.py"),
         "--workload", "laguna_pretrain_8k", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    assert line["notes"]["check_loss_diff"] <= 0.05


# ---- PR 28, PR 30: the engine's counters through the counter channel

COUNTER_SHARES = {
    "sampler_skipped_share_pct": ("engine.sampler_skipped",
                                  "engine.sampler_dispatches"),
    "inputs_resident_share_pct": ("engine.inputs_resident",
                                  "engine.inputs_dispatches")}
SHARE_CELLS = {"chat": "chat_steady", "docs": "docs_offline",
               "decode": "decode_heavy"}


@pytest.mark.parametrize("suffix", sorted(SHARE_CELLS))
@pytest.mark.parametrize("metric", sorted(COUNTER_SHARES))
def test_a_counter_share_reads_both_engine_counters(metric, suffix):
    from perfbench import readers
    name = f"{metric}.{suffix}"
    part, whole = COUNTER_SHARES[metric]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = load("metrics", name + ".json")
    twin = load("metrics", f"pool_inplace_share_pct.{suffix}.json")
    assert entry["workloads"] == [SHARE_CELLS[suffix]]
    assert all(spec[k] == entry[k] == twin[k]
               for k in ("layer", "unit", "better", "source", "moves"))
    obs = {"counters": {part: 1500.0, whole: 1600.0}}
    assert readers.read(name, obs) == pytest.approx(93.75)
    # the parent's program has neither counter: nothing to read, no metric
    assert readers.read(name, {"counters": {
        "engine.pool_dispatches": 1600.0}}) is None
    # no decode dispatch in the window: no share
    assert readers.read(name, {"counters": {part: 0, whole: 0}}) is None


REHEARSE_WITH_VALUES = """
import argparse, json, sys
sys.path.insert(0, {root!r})
from perfbench import run as harness
bench = harness.load_json({root!r}, "BENCHMARK.json")
bench["configs"] = [{{"name": "cgpt-1p3b",
                     "file": "perfbench/rehearsal/gpt2-tiny.json"}}]
args = argparse.Namespace(workload="decode_heavy", seed=2147483659,
                          seconds=1.5, trace=1)
print(json.dumps(harness.run_cell(bench, args, rehearsal=True,
                                  traffic_dir="rehearsal")))
"""


def test_a_rehearsal_reports_every_decode_step_as_sampler_skipped():
    """``decode_heavy``'s toy twin, traced, on a real engine (its own
    process, as ``rehearse.py`` runs it, but with the values kept): the
    harness submits no decode parameters, so every decode dispatch of the
    window is all-greedy and the reader finds both counters."""
    out = subprocess.run(
        [sys.executable, "-c", REHEARSE_WITH_VALUES.format(root=ROOT)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert m["sampler_skipped_share_pct.decode"]["value"] == 100.0
    # eight rows turn over about once in thirty steps of the toy twin
    assert 80.0 <= m["inputs_resident_share_pct.decode"]["value"] < 100.0
    assert m["pool_inplace_share_pct.decode"]["value"] == 100.0
    assert m["compiles_in_window.decode"]["value"] == 0
