"""The family seam (CPU, ``gpt2-tiny`` size): a cell's check goes through
the family its configuration names, not through GPT-2's by default; an
unknown family is an error that lists the known ones; the ``gpt2`` family's
operation counts are the hand-worked ones on both configuration files."""

import argparse
import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from perfbench import families, flops  # noqa: E402


def config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_an_unknown_family_is_an_error_that_lists_the_known_ones():
    with pytest.raises(SystemExit) as e:
        families.load({"name": "some-moe", "family": "no_such_family"})
    assert "no_such_family" in str(e.value) and "gpt2" in str(e.value)
    assert "gpt2" in families.known()


def test_a_configuration_without_the_key_is_of_the_gpt2_family():
    for name in ("cgpt-1p3b", "cgpt-1p3b-d20"):
        assert "family" not in config(name)       # their bytes are kept
        assert families.load(config(name)).__name__ == \
            "perfbench.families.gpt2"


def test_a_family_that_lacks_an_export_is_refused(monkeypatch):
    half = types.ModuleType("perfbench.families.fixture_half")
    half.forward = lambda params, ids, cfg: None
    monkeypatch.setitem(sys.modules, half.__name__, half)
    with pytest.raises(SystemExit) as e:
        families.load({"name": "x", "family": "fixture_half"})
    assert "train_flops_per_token" in str(e.value)


@pytest.mark.parametrize("name,layers,by_hand", [
    # per layer 8*2048^2 + 4*2048*8192 + 2*1024*2048 = 104,857,600;
    # head 2*2048*50304 = 206,045,184; x3 for the backward
    ("cgpt-1p3b", 24, 3 * (24 * 104_857_600 + 206_045_184)),
    ("cgpt-1p3b-d20", 20, 3 * (20 * 104_857_600 + 206_045_184))])
def test_gpt2_train_flops_per_token_is_flops_py_on_the_files_numbers(
        name, layers, by_hand):
    cfg = config(name)
    got = families.load(cfg).train_flops_per_token(cfg, 1024)
    assert got == by_hand == flops.train_flops_per_token(
        hidden=2048, ffn=8192, layers=layers, vocab_rows=50304, seq=1024)


# ---- a second family, reached with no edit to run.py / serve.py / readers.py

def fixture_family(monkeypatch, name, **replaced):
    """A family module under ``name`` that builds the program's gpt2-tiny;
    its reference is GPT-2's but for the exports ``replaced``."""
    gpt2 = importlib.import_module("perfbench.families.gpt2")
    mod = types.ModuleType("perfbench.families." + name)
    for export in families.EXPORTS:
        setattr(mod, export, replaced.get(export, getattr(gpt2, export)))
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def rehearse(tmp_path, family, workload="docs_offline"):
    """One traced CPU run of ``workload``'s toy twin whose configuration
    names ``family`` -> the result line."""
    from perfbench import run as harness
    cfg = harness.load_json(BENCH_DIR, "rehearsal", "gpt2-tiny.json")
    cfg["family"] = family
    path = tmp_path / (family + "-tiny.json")
    path.write_text(json.dumps(cfg))
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.find_cell(bench, workload)
    bench["configs"] = [{"name": cell["config"], "file": str(path)}]
    args = argparse.Namespace(workload=workload, seed=5, seconds=1.5, trace=1)
    return harness.run_cell(bench, args, rehearsal=True,
                            traffic_dir="rehearsal")


def hidden_and_one(params, ids, cfg):
    """GPT-2's hidden state with one more feature, a constant 1 ..."""
    import jax.numpy as jnp
    from perfbench.families import gpt2
    x = gpt2.hidden(params, ids, cfg)
    return jnp.concatenate([x, jnp.ones_like(x[..., :1])], axis=-1)


def head_and_odd(params, cfg):
    """... which this head weighs 1 for every odd token: a reference that is
    wrong by one logit unit where the engine emitted an even one."""
    import jax.numpy as jnp
    from perfbench.families import gpt2
    w = gpt2.head(params, cfg)
    odd = (jnp.arange(w.shape[1]) % 2).astype(w.dtype)
    return jnp.concatenate([w, odd[None]], axis=0)


def test_the_check_goes_through_the_family_named_true_reference(
        monkeypatch, tmp_path):
    fixture_family(monkeypatch, "fixture_true")
    line = rehearse(tmp_path, "fixture_true")
    assert line["correct"] is True
    assert line["notes"]["checked_requests"] == 8
    assert line["notes"]["max_logit_deficit"] <= 0.05
    # the two channels, end to end on a real engine: the program's counters
    # (every dispatch is a pool dispatch) and a span's argument
    m = line["metrics"]
    assert 0.0 <= m["pool_inplace_share_pct.docs"]["value"] <= 100.0
    assert m["prefill_rows_mean.docs"]["value"] >= 1.0


def test_the_check_goes_through_the_family_named_wrong_reference(
        monkeypatch, tmp_path):
    fixture_family(monkeypatch, "fixture_off_by_one", hidden=hidden_and_one,
                   head=head_and_odd)
    line = rehearse(tmp_path, "fixture_off_by_one")
    assert line["correct"] is False
    # the best odd token gained a unit over the even token emitted
    assert 0.5 < line["notes"]["max_logit_deficit"] <= 1.0
    assert line["notes"]["leaked_kv_blocks"] == 0
