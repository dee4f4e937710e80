"""chip_smoke.py rehearsed on the CPU at gpt2-tiny size, and the two
things its entry points settle before touching a chip: where the compile
cache lives and which peaks a device is rated by."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--train-model", "gpt2-tiny", "--serve-model", "gpt2-tiny",
        "--train-seq", "128", "--train-batch", "2", "--max-len", "128",
        "--buckets", "16,32"]


def _env(**extra):
    # the children are ordinary user processes: one CPU device, none of
    # the suite's virtual-device XLA_FLAGS
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    return dict(env, JAX_PLATFORMS="cpu", **extra)


def _smoke(args, cache_dir):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + args,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache_dir)),
        capture_output=True, text=True, timeout=600)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


def _phase_results(lines):
    return [json.loads(ln) for ln in lines if ln.startswith('{"phase"')]


def test_chip_smoke_rehearsal_on_cpu_never_reports_success(tmp_path):
    """Every phase runs and passes at tiny size, the cache lands where
    JAX_COMPILATION_CACHE_DIR says and is hit by a second run — and the
    script still exits non-zero with ok false: it is not a chip run."""
    cache = tmp_path / "cache"
    rc, lines, err = _smoke(TINY, cache)
    assert rc != 0, "a CPU rehearsal must not exit 0"
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    phases = _phase_results(lines)
    assert [p["phase"] for p in phases] == ["train", "serve"], err[-2000:]
    assert all(p["ok"] for p in phases)
    assert not [ln for ln in lines if "FAIL" in ln], lines
    for needle in ("[train] pass: loss fell",
                   "[serve] pass: engine vs greedy_search",
                   "pass: leaked_kv_blocks == 0", "pass: exceptions == 0"):
        assert any(needle in ln for ln in lines), (needle, lines)
    # the cache went where the variable says, nowhere in the checkout
    assert any(f.endswith("-cache") for f in os.listdir(cache))
    assert phases[0]["cache_entries_new"] > 0
    # same shapes again: every program is found, none is added
    rc, lines, err = _smoke(TINY + ["--phase", "train"], cache)
    again, = _phase_results(lines)
    assert again["phase"] == "train" and again["ok"], err[-2000:]
    assert again["cache_entries_new"] == 0


def test_compile_cache_dir_is_the_variable_or_one_path_in_the_checkout(
        tmp_path):
    code = ("import os, jax; from paddle_tpu.utils import chip; "
            "p = chip.enable_compile_cache(); "
            "print(p); print(jax.config.jax_compilation_cache_dir)")

    def where(cwd, **env):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, text=True,
            env=dict(_env(**env), PYTHONPATH=REPO),
            capture_output=True, timeout=300, check=True).stdout.split()
        assert out[0] == out[1], "helper and jax.config disagree"
        return out[0]

    placed = str(tmp_path / "placed")
    assert where(REPO, JAX_COMPILATION_CACHE_DIR=placed) == placed
    other = tmp_path / "elsewhere"
    other.mkdir()
    assert where(REPO) == where(str(other)) \
        == os.path.join(REPO, ".jax_cache")


def test_tpu_peaks_come_from_one_table_and_an_unknown_tpu_raises():
    import bench
    from paddle_tpu.utils import chip
    assert chip.tpu_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v9"):
        chip.tpu_peaks("TPU v9")
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench.detect_peak_flops(dev) == 197e12
    dev.device_kind = "TPU v9"
    with pytest.raises(KeyError, match="TPU v9"):
        bench.detect_peak_flops(dev)


def _benchmark_peaks():
    with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
        return {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


@pytest.mark.parametrize("kind", sorted(_benchmark_peaks()))
def test_the_programs_peaks_equal_the_benchmarks(kind):
    """Two tables in two owners (``utils/chip.py`` the program's,
    ``perfbench/peaks.json`` the benchmark's) may not drift."""
    from paddle_tpu.utils import chip
    row = _benchmark_peaks()[kind]
    assert chip.tpu_peaks(kind) == (row["bf16_flops"],
                                    row["hbm_bytes_per_s"])


def test_the_two_peak_tables_list_the_same_kinds():
    from paddle_tpu.utils import chip
    assert sorted(chip.TPU_PEAKS) == sorted(_benchmark_peaks())
