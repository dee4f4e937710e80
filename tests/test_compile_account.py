"""The compile account by stage and by site (PR 52).

``compile_tracker`` splits what a ``tracked_jit`` site costs to build into
tracing (its own stamps, exclusive), lowering and backend compilation
(JAX's ``jax.monitoring`` events), counts the programs built and what the
persistent cache did, keeps what no site owns under ``(untracked)``, and
``engine.stats()`` carries the process's sums: the channel the benchmark's
five ``*.setup`` files read. CPU, toy sizes; nothing here is a device
metric.
"""

import contextlib
import io
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
import perfbench_listing as listing
from paddle_tpu import observability, profiler
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import compile_tracker as ct
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM_KEYS = ("programs_built", "programs_trace_ms", "programs_lower_ms",
                "programs_compile_ms", "programs_cache_hits",
                "programs_cache_misses")
#: every event JAX reports in this process, whoever listens to it
EVENTS = {"n": 0}


def _count(*_, **__):
    EVENTS["n"] += 1


jax.monitoring.register_event_listener(_count)
jax.monitoring.register_event_duration_secs_listener(_count)


def _site(name, labels=None):
    return observability.compiles()[ct._qualname(name, labels or {})]


def _stages(rec):
    return {s: rec[s] for s in ct.STAGES}


def _stop_profiler(tmp_path):
    out = tmp_path / "spans.json"
    with contextlib.redirect_stdout(io.StringIO()) as said:
        profiler.stop_profiler(profile_path=str(out))
    return json.loads(out.read_text())["traceEvents"], said.getvalue()


# ------------------------------------------------------------ one site

def test_a_site_traced_once_reads_every_stage():
    fn = ct.tracked_jit("acct_once", lambda x: jnp.tanh(x) * 2)
    t0 = time.perf_counter()
    fn(jnp.ones((4, 4)))
    wall = (time.perf_counter() - t0) * 1e3
    rec = _site("acct_once")
    assert rec["count"] == 1 and rec["programs"] == 1
    assert rec["trace_ms"] > 0 and rec["lower_ms"] > 0
    assert rec["compile_ms"] > 0
    # the stages are parts of the call that traced, the rest its first run
    assert rec["trace_ms"] + rec["lower_ms"] + rec["compile_ms"] \
        <= rec["total_ms"] <= wall


class _Counting:
    """Stands in for a module attribute and counts every use of it."""

    def __init__(self, real):
        object.__setattr__(self, "_real", real)
        object.__setattr__(self, "uses", 0)

    def __getattr__(self, name):
        object.__setattr__(self, "uses", self.uses + 1)
        return getattr(self._real, name)

    def __setattr__(self, name, value):
        object.__setattr__(self, "uses", self.uses + 1)
        setattr(self._real, name, value)


def test_a_call_that_does_not_retrace_changes_nothing_and_reads_nothing(
        monkeypatch):
    """The steady state: no event of JAX's (so no listener runs), no
    ``perf_counter_ns`` stamp, no use of the thread-local; the one
    ``perf_counter`` reading the call always took (``total_ms``'s) stays."""
    fn = ct.tracked_jit("acct_steady", lambda x: x * 3 + 1)
    x = jnp.ones((8,))
    fn(x)
    before = _site("acct_steady")

    class Clock:
        ns = 0

        @staticmethod
        def perf_counter():
            return time.perf_counter()

        @staticmethod
        def perf_counter_ns():
            Clock.ns += 1
            return time.perf_counter_ns()
    now = _Counting(ct._now)
    monkeypatch.setattr(ct, "time", Clock)
    monkeypatch.setattr(ct, "_now", now)
    events = EVENTS["n"]
    for _ in range(3):
        fn(x)
    assert EVENTS["n"] == events and Clock.ns == 0 and now.uses == 0
    assert _site("acct_steady") == before
    # and the counters do see a retrace
    fn(jnp.ones((9,)))
    assert EVENTS["n"] > events and Clock.ns == 2 and now.uses > 0
    assert _site("acct_steady")["count"] == 2


def test_a_site_traced_inside_another_is_counted_once():
    def slow(x):
        time.sleep(0.2)             # tracing that takes a while
        return x * 2
    inner = ct.tracked_jit("acct_inner", slow)
    outer = ct.tracked_jit("acct_outer", lambda x: inner(x) + 1)
    t0 = time.perf_counter()
    outer(jnp.ones((4,)))
    wall = (time.perf_counter() - t0) * 1e3
    i, o = _site("acct_inner"), _site("acct_outer")
    assert i["count"] == o["count"] == 1
    assert i["trace_ms"] >= 200 > o["trace_ms"] > 0
    # lowered into the outer module: one program, and it is the outer's
    assert (i["programs"], o["programs"]) == (0, 1)
    assert i["lower_ms"] == i["compile_ms"] == 0 < o["lower_ms"]
    both = sum(r[s] for r in (i, o)
               for s in ("trace_ms", "lower_ms", "compile_ms"))
    assert both <= wall


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "apart"])
def test_blocks_that_stop_sharing_a_function_move_tracing_not_programs(
        shared):
    """What the five metric files say a PR like PR 50 does: eight blocks
    through ONE jitted function are traced once, eight functions eight
    times, and either way one program is built."""
    traced = []

    def block(x):
        traced.append(1)
        return jnp.tanh(x) + 1
    one = jax.jit(block)
    blocks = [one if shared else jax.jit(lambda x: block(x))
              for _ in range(8)]

    def step(x):
        for b in blocks:
            x = b(x)
        return x
    name = f"acct_blocks_{'shared' if shared else 'apart'}"
    ct.tracked_jit(name, step)(jnp.ones((4,)))
    assert len(traced) == (1 if shared else 8)
    assert _site(name)["programs"] == 1


def test_an_eager_program_built_while_tracing_is_not_tracing():
    def fn(x):
        # concrete values: built and run at once, inside the trace
        with jax.ensure_compile_time_eval():
            table = jnp.cumsum(jnp.ones((5,))) + 0.5
        return x * table
    site = ct.tracked_jit("acct_eager_inside", fn)
    t0 = time.perf_counter()
    site(jnp.ones((5,)))
    wall = (time.perf_counter() - t0) * 1e3
    rec = _site("acct_eager_inside")
    assert rec["programs"] >= 2 and rec["count"] == 1
    assert 0 < rec["trace_ms"] + rec["lower_ms"] + rec["compile_ms"] <= wall


def test_a_bare_jit_lands_in_untracked_and_moves_no_site():
    fn = ct.tracked_jit("acct_bystander", lambda x: x - 1)
    fn(jnp.ones((3,)))
    x = jnp.ones((3, 5))            # an eager program of its own
    sites = {k: _stages(v) for k, v in observability.compiles().items()
             if k != ct.UNTRACKED}
    before = _stages(observability.compiles().get(
        ct.UNTRACKED, dict.fromkeys(ct.STAGES, 0)))
    jax.jit(lambda x: jnp.sin(x) * 7)(x)
    after = _stages(_site(ct.UNTRACKED))
    assert after["programs"] == before["programs"] + 1
    assert after["lower_ms"] > before["lower_ms"]
    assert after["compile_ms"] > before["compile_ms"]
    assert _site(ct.UNTRACKED)["count"] == 0
    assert sites == {k: _stages(v) for k, v
                     in observability.compiles().items()
                     if k != ct.UNTRACKED}


def test_a_trace_that_raises_hands_the_thread_back():
    def bad(x):
        raise RuntimeError("no program")
    with pytest.raises(RuntimeError):
        ct.tracked_jit("acct_raises", bad)(jnp.ones((2,)))
    rec = _stages(_site("acct_raises"))
    jax.jit(lambda x: x * 11 - 2)(jnp.ones((2, 7)))
    assert _stages(_site("acct_raises")) == rec and rec["programs"] == 0


def test_lower_is_the_sites_and_what_follows_is_not():
    site = ct.tracked_jit("acct_lowered", lambda x: x / 3)
    lowered = site.lower(jnp.ones((6,)))
    rec = _stages(_site("acct_lowered"))
    assert rec["trace_ms"] > 0 and rec["lower_ms"] > 0
    assert rec["programs"] == 0
    lowered.compile()               # no call of the site: nobody's
    assert _stages(_site("acct_lowered")) == rec


def test_two_threads_tracing_two_sites_keep_their_events_apart():
    meet = threading.Barrier(2, timeout=30)

    def make(k):
        def fn(x):
            meet.wait()             # both are inside their trace now
            return x * k
        return ct.tracked_jit(f"acct_thread_{k}", fn)
    sites = [make(2), make(3)]
    threads = [threading.Thread(target=s, args=(jnp.ones((4,)),))
               for s in sites]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    for k in (2, 3):
        rec = _site(f"acct_thread_{k}")
        assert rec["count"] == 1 and rec["programs"] == 1
        assert rec["trace_ms"] > 0 and rec["lower_ms"] > 0
        assert rec["compile_ms"] > 0


def test_the_totals_are_the_sum_of_the_sites():
    ct.tracked_jit("acct_summed", lambda x: x + 5)(jnp.ones((2, 2)))
    jax.jit(lambda x: x * 13)(jnp.ones((2, 3)))
    totals, sites = observability.compile_totals(), observability.compiles()
    assert set(totals) == set(ct.STAGES) and ct.UNTRACKED in sites
    for stage in ct.STAGES:
        assert totals[stage] == pytest.approx(
            sum(rec[stage] for rec in sites.values()), abs=1e-2)


# ---------------------------------------- the registry, snapshot and print

def test_the_registry_says_the_stages_and_the_mixture_is_gone():
    ct.tracked_jit("acct_scraped", lambda x: x * 17,
                   labels={"bucket": "8"})(jnp.ones((3,)))
    text = observability.prometheus_text()
    for counter in ("xla_compiles", "xla_trace_ms", "xla_lower_ms",
                    "xla_backend_compile_ms"):
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith(counter + "{")
                   and 'fn="acct_scraped"' in ln]
        assert 'bucket="8"' in line and float(line.rsplit(" ", 1)[1]) > 0
    assert "xla_compile_ms" not in text
    assert "xla_compile_ms" not in observability.INSTRUMENT_DOCS
    rec = observability.snapshot()["compiles"]["acct_scraped{bucket=8}"]
    assert rec["programs"] == 1 and set(ct.STAGES) <= set(rec)


def test_the_cache_counters_follow_jaxs_events():
    """Whatever the backend's cache does, the registry and the record say
    what JAX's own events said (here: straight from the listeners)."""
    before = _stages(observability.compiles().get(
        ct.UNTRACKED, dict.fromkeys(ct.STAGES, 0)))
    ct._on_event("/jax/compilation_cache/cache_hits")
    ct._on_event("/jax/compilation_cache/cache_misses")
    ct._on_event("/jax/compilation_cache/tasks_using_cache")
    ct._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    ct._on_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    after = _stages(_site(ct.UNTRACKED))
    grew = {s: after[s] - before[s] for s in ct.STAGES}
    assert grew == {"trace_ms": 0, "lower_ms": 0, "compile_ms": 0,
                    "programs": 0, "cache_hits": 1, "cache_misses": 1,
                    "cache_retrieval_ms": 250.0}
    text = observability.prometheus_text()
    assert 'xla_cache_hits{fn="(untracked)"}' in text
    assert 'xla_cache_misses{fn="(untracked)"}' in text


def test_the_profilers_print_says_the_stages(tmp_path):
    profiler.start_profiler()
    ct.tracked_jit("acct_printed", lambda x: x * 19)(jnp.ones((3,)))
    _, said = _stop_profiler(tmp_path)
    (line,) = [ln for ln in said.splitlines() if "acct_printed:" in ln]
    assert "1 traces, 1 programs" in line
    assert "ms tracing" in line and "lowering" in line
    assert "compiling" in line and "0 cache hits" in line


_CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
sys.path.insert(0, {root!r})
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from paddle_tpu import observability
from paddle_tpu.observability import compile_tracker as ct
out = []
for _ in range(2):
    fn = ct.tracked_jit("acct_cached", lambda x: jnp.tanh(x) @ x.T)
    fn(jnp.ones((8, 8)))
    out.append(observability.compiles()["acct_cached"])
    jax.clear_caches()
print("ACCOUNT " + json.dumps(out))
"""


def test_a_second_build_of_the_same_program_is_a_cache_hit(tmp_path):
    """A fresh cache directory, the same program built twice: a miss
    (compiled and written), then a hit whose retrieval is timed."""
    done = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT.format(
            root=ROOT, cache=str(tmp_path / "cache"))],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    (line,) = [ln for ln in done.stdout.splitlines()
               if ln.startswith("ACCOUNT ")]
    first, second = json.loads(line[len("ACCOUNT "):])
    assert (first["cache_hits"], first["cache_misses"],
            first["programs"]) == (0, 1, 1)
    if second["cache_hits"] == 0:
        pytest.skip("this backend does not serve the persistent cache")
    assert (second["cache_hits"], second["cache_misses"],
            second["programs"]) == (1, 1, 2)
    assert second["cache_retrieval_ms"] > 0
    assert second["compile_ms"] - first["compile_ms"] \
        >= second["cache_retrieval_ms"] * 0.99


# ------------------------------------------------------------ the spans

def test_one_build_span_a_built_program_and_a_trace_under_it(tmp_path):
    inner = ct.tracked_jit("acct_span_inner", lambda x: x * 23)
    site = ct.tracked_jit("acct_span", lambda x: inner(x) + 1,
                          labels={"bucket": "4"})
    profiler.start_profiler()
    site(jnp.ones((4,)))
    site(jnp.ones((4,)))            # the same shapes: nothing more
    site(jnp.ones((5,)))
    events, _ = _stop_profiler(tmp_path)
    builds = [e for e in events if e["name"] == "program.build"]
    assert len(builds) == 2 == _site("acct_span",
                                     {"bucket": "4"})["programs"]
    for e in builds:
        assert e["parent"] is None
        assert set(e["args"]) == {"site", "trace_ms", "lower_ms",
                                  "compile_ms", "cache_hit"}
        assert e["args"]["site"] == "acct_span{bucket=4}"
        assert e["args"]["cache_hit"] in (0, 1)
        # the tracing of the whole program, the inner site's with it
        assert e["args"]["trace_ms"] > 0 and e["args"]["lower_ms"] > 0
        assert e["args"]["compile_ms"] > 0
        assert sum(e["args"][k] for k in ("trace_ms", "lower_ms",
                                          "compile_ms")) <= e["dur"] / 1e3
    traces = [e for e in events if e["name"] == "program.trace"]
    assert sorted(e["args"]["site"] for e in traces) == \
        ["acct_span_inner"] * 2 + ["acct_span{bucket=4}"] * 2
    for b in builds:
        # its own trace lies inside the call the span bounds
        assert [t for t in traces if t["args"]["site"] == b["args"]["site"]
                and b["ts"] <= t["ts"]
                and t["ts"] + t["dur"] <= b["ts"] + b["dur"]]
    # the inner site's trace ran under the outer's
    by_id = {e["id"]: e for e in events}
    assert all(by_id[t["parent"]]["args"]["site"] == "acct_span{bucket=4}"
               for t in traces if t["args"]["site"] == "acct_span_inner")


def test_with_the_profiler_off_nothing_is_recorded_and_the_account_grows(
        tmp_path):
    before = observability.compile_totals()
    ct.tracked_jit("acct_unprofiled", lambda x: x * 29)(jnp.ones((3,)))
    after = observability.compile_totals()
    events, _ = _stop_profiler(tmp_path)
    assert events == []
    assert after["programs"] == before["programs"] + 1
    for stage in ("trace_ms", "lower_ms", "compile_ms"):
        assert after[stage] > before[stage]


# ------------------------------------------------------ engine.stats()

VOCAB = 97


@pytest.fixture(scope="module")
def engine():
    pt.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_layers=2, num_heads=4, ffn_hidden_size=64))
    model.eval()
    eng = ServingEngine(model, max_slots=2, max_len=48, buckets=[8, 16])
    _serve(eng, 5)                  # warms bucket 8 and the decode step
    return eng


def _serve(eng, prompt_tokens, seed=3):
    rng = np.random.default_rng(seed)
    req = eng.submit(rng.integers(1, VOCAB, size=prompt_tokens).tolist(),
                     max_new_tokens=4)
    eng.run_until_idle()
    assert req.state == "done"


def _programs(eng):
    st = eng.stats()
    return {k: st[k] for k in PROGRAM_KEYS}


def test_engine_stats_carries_the_processs_totals(engine):
    got = _programs(engine)
    assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in got.values())
    totals = observability.compile_totals()
    assert got == {"programs_built": totals["programs"],
                   **{f"programs_{s}": totals[s] for s in (
                       "trace_ms", "lower_ms", "compile_ms", "cache_hits",
                       "cache_misses")}}
    assert got["programs_built"] >= 2 and got["programs_trace_ms"] > 0


def test_a_step_that_does_not_retrace_leaves_the_account(engine):
    before = _programs(engine)
    _serve(engine, 6, seed=4)       # bucket 8 again
    assert _programs(engine) == before


def test_the_account_is_monotone_over_a_step_that_builds(engine, tmp_path):
    """A bucket the engine has not served yet, inside a profiler window:
    the account grows, and the ``program.build`` span names the step that
    recompiled."""
    before = _programs(engine)
    profiler.start_profiler()
    _serve(engine, 12, seed=5)      # bucket 16: a new prefill program
    events, _ = _stop_profiler(tmp_path)
    after = _programs(engine)
    assert all(after[k] >= before[k] for k in PROGRAM_KEYS)
    assert after["programs_built"] > before["programs_built"]
    for key in ("programs_trace_ms", "programs_lower_ms",
                "programs_compile_ms"):
        assert after[key] > before[key]
    sites = [e["args"]["site"] for e in events
             if e["name"] == "program.build"]
    assert sites and all(s.startswith("serving_prefill_paged{") and
                         "bucket=16" in s for s in sites)
    # under the engine's own span for the dispatch that traced
    by_id = {e["id"]: e for e in events}
    (trace,) = [e for e in events if e["name"] == "program.trace"]
    assert trace["args"]["site"] == sites[0]
    assert by_id[trace["parent"]]["name"].startswith("serving.")


# ------------------------------------------- the metric files that read it

#: what a change run's window leaves in the harness's counters
OBS = {"spans": {}, "span_args": {}, "samples": {}, "trace": {},
       "counters": {"engine.programs_trace_ms.close": 21500.0,
                    "engine.programs_lower_ms.close": 5250.0,
                    "engine.programs_compile_ms.close": 3125.0,
                    "engine.programs_built.close": 310,
                    "engine.programs_cache_misses.close": 0,
                    "engine.programs_built": 0, "window_s": 45.0}}
#: the account's key -> (the unit of its file, the value OBS gives it)
READ = {"programs_trace_ms": ("s", 21.5), "programs_lower_ms": ("s", 5.25),
        "programs_compile_ms": ("s", 3.125),
        "programs_built": ("count", 310.0),
        "programs_cache_misses": ("count", 0.0)}


@pytest.mark.parametrize("key", sorted(READ))
def test_a_metric_file_reads_the_account_and_nothing_from_a_parent(key):
    """Data over the counter channel: the file that reads the key's
    closing value (found by its reader, not by its name) reads a change
    run's counters to the number worked out by hand, is listed for set-up
    in every serving cell, and finds nothing (and does not raise) in what
    a parent's ``engine.stats()`` leaves."""
    from perfbench import readers
    (name,) = listing.files_reading(f"engine.{key}.close",
                                    **{"from": "counters"})
    spec, (unit, value) = listing.SPECS[name], READ[key]
    assert readers.read(name, OBS) == pytest.approx(value)
    assert (spec["unit"], spec["moves"], spec["source"]) == \
        (unit, "setup_s", "program_counter")
    serving = [c for c in listing.CELLS
               if not listing.reports(c, "train_tok_s_chip")]
    assert listing.LISTED[name]["workloads"] == serving
    parent = {**OBS, "counters": {"engine.sampler_dispatches.close": 3000,
                                  "engine.stalled_ms.close": 0.0,
                                  "window_s": 45.0}}
    assert readers.read(name, parent) is None
