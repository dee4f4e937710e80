"""Disaggregated prefill/decode serving fleet (serving/disagg.py).

Contracts: a DisaggRouter fleet is *token-identical* to the symmetric
ReplicaRouter it replaces — across prefix cache on/off, speculative
decoding, and int8 KV pools — because both roles call the same
compiled steps (the unified step cache keys on geometry, never role),
so splitting P+D workers adds **zero** XLA compiles. The KV handoff is
host-side block surgery: a same-pool splice when co-located, an
all-or-nothing block copy across pools, leak-free either way.
Prefix-affinity routing concentrates shared prefixes on one worker's
pool, so the *fleet* prefix hit rate strictly beats least-loaded
routing on a shared-system-prompt workload. Chaos: killing a prefill
worker mid-handoff sheds/re-routes with every block reference
released, and the ``serving.handoff`` fault site sheds cleanly.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor, observability
from paddle_tpu.analysis import predict_serving_compiles
from paddle_tpu.models.generation import greedy_search
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import fault_scope
from paddle_tpu.serving import (DecodeEngine, DisaggRouter, HandoffQueue,
                                QueueFullError, ReplicaRouter)
from paddle_tpu.serving.disagg import parse_disagg


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = GPTConfig(vocab_size=97, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 97, size=n).tolist() for n in sizes]


_GEOM = dict(max_slots=2, max_len=32, buckets=[8, 16], max_queue=16,
             block_size=4)


def _fleet(model, p=1, d=2, **kw):
    base = dict(_GEOM)
    base.update(kw)
    return DisaggRouter(model, n_prefill=p, n_decode=d, **base)


def _ref(model, prompt, n):
    return greedy_search(model, np.asarray([prompt]), max_new_tokens=n,
                         cache_len=32)[0].tolist()


def _leaked_per_pool(rt):
    """leaked() per *unique* pool (co-located roles share one)."""
    pools = {}
    for eng in rt.engines + rt._retiring:
        pools[id(eng.cache.pool)] = eng.cache
    out = []
    for cache in pools.values():
        cache.flush_prefix_cache()
        out.append(cache.allocator.leaked())
    return out


# ----------------------------------------------------- token identity
@pytest.mark.parametrize("kw", [
    dict(prefix_cache=True),
    dict(prefix_cache=False),
    dict(prefix_cache=True, spec_tokens=2),
    dict(prefix_cache=True, kv_dtype="int8"),
], ids=["prefix", "no-prefix", "spec2", "int8"])
@pytest.mark.parametrize("colocate", [True, False],
                         ids=["colocated", "cross-pool"])
def test_disagg_matches_symmetric_router(model, kw, colocate):
    """The core invariant: same prompts through a symmetric 2-replica
    router and a 1x2 disaggregated fleet produce identical tokens —
    the handoff moves KV, never changes math."""
    prompts = _prompts((3, 7, 5, 11, 4, 9), seed=1)
    n = 5

    sym = ReplicaRouter(model, n_replicas=2, **dict(_GEOM, **kw))
    sym_reqs = [sym.submit(p, max_new_tokens=n) for p in prompts]
    sym.run_until_idle()

    rt = _fleet(model, p=1, d=2, colocate=colocate, **kw)
    reqs = [rt.submit(p, max_new_tokens=n) for p in prompts]
    rt.run_until_idle()

    for p, sr, dr in zip(prompts, sym_reqs, reqs):
        assert sr.state == "done" and dr.state == "done"
        assert dr.output_ids == sr.output_ids, \
            f"disagg diverged from symmetric on request {dr.id}"
        if "kv_dtype" not in kw:       # int8 may round off f32 greedy
            assert dr.output_ids == _ref(model, p, n)
    assert all(lk == 1 for lk in _leaked_per_pool(rt))  # trash only
    st = rt.stats()
    assert st["completed"] == len(prompts)
    assert st["handoffs_adopted"] == len(prompts)
    if not colocate:
        assert st["handoffs_copied"] == len(prompts)


def test_disagg_adds_zero_compiles_over_symmetric(model):
    """Role-split workers reuse the symmetric fleet's compiled steps:
    after a symmetric run has paid the compiles for a geometry, a
    disagg fleet at the same geometry triggers none."""
    prompts = _prompts((3, 7, 5, 9), seed=2)
    sym = ReplicaRouter(model, n_replicas=2, **_GEOM)
    for p in prompts:
        sym.submit(p, max_new_tokens=4)
    sym.run_until_idle()

    def snap():
        return {k: v["count"]
                for k, v in observability.compiles().items()
                if k.startswith(("serving_", "decode_", "verify_"))}

    before = snap()
    rt = _fleet(model, p=2, d=2)
    reqs = [rt.submit(p, max_new_tokens=4) for p in prompts]
    rt.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    assert snap() == before, "disagg fleet re-traced a step"


def test_predict_serving_compiles_disagg_is_noop():
    """The static twin of the test above: ``disagg`` joins the
    validated no-op family in predict_serving_compiles."""
    rounds = [[(list(range(1, 9)), 4), (list(range(1, 5)), 1)],
              [(list(range(1, 9)), 4)]]
    kw = dict(buckets=[8, 16], max_len=32, block_size=4)
    plain = predict_serving_compiles(rounds, **kw)
    assert plain
    assert predict_serving_compiles(rounds, disagg=(1, 2), **kw) == plain
    assert predict_serving_compiles(rounds, disagg=(4, 4), **kw) == plain
    with pytest.raises(ValueError, match="disagg"):
        predict_serving_compiles(rounds, disagg=(0, 2), **kw)


# --------------------------------------------------- prefix affinity
def _shared_prefix_workload(n_prefixes=4, per_prefix=6, seed=3):
    """per_prefix requests each over n_prefixes distinct 8-token
    system prompts (2 full blocks at block_size=4) + unique suffixes.
    Arrival order within each wave is shuffled: positional routing
    (least-loaded alternation) must not accidentally pin a prefix to
    one worker — only *content*-aware routing should manage that."""
    rng = np.random.RandomState(seed)
    systems = [rng.randint(1, 97, size=8).tolist()
               for _ in range(n_prefixes)]
    out = []
    for i in range(per_prefix):
        for j in rng.permutation(n_prefixes):
            out.append(systems[j] + rng.randint(1, 97, size=3).tolist())
    return out


def _run_waves(rt, prompts, wave=4):
    reqs = []
    for i in range(0, len(prompts), wave):
        for p in prompts[i:i + wave]:
            reqs.append(rt.submit(p, max_new_tokens=2))
        rt.run_until_idle()   # publish prefixes before the next wave
    return reqs


def test_prefix_affinity_beats_least_loaded_hit_rate(model):
    """Shared-system-prompt workload over 2 prefill workers: affinity
    pins each prefix to one pool (one cold miss per prefix); least
    loaded spreads it across both pools (a cold miss per pool). The
    fleet-wide hit rate must be strictly higher with affinity on —
    with zero leaked blocks either way."""
    prompts = _shared_prefix_workload()
    results = {}
    for affinity in (True, False):
        rt = _fleet(model, p=2, d=2, prefix_affinity=affinity,
                    num_blocks=96)
        reqs = _run_waves(rt, prompts)
        assert all(r.state == "done" for r in reqs)
        st = rt.stats()
        assert all(lk == 1 for lk in _leaked_per_pool(rt))
        results[affinity] = st
    aff, base = results[True], results[False]
    assert aff["affinity_hits"] > 0
    assert base["affinity_hits"] == 0 and base["affinity_misses"] == 0
    assert aff["fleet_prefix_hits"] > base["fleet_prefix_hits"], \
        (aff["fleet_prefix_hits"], base["fleet_prefix_hits"])
    assert aff["fleet_prefix_hit_rate"] > base["fleet_prefix_hit_rate"]


def test_affinity_counters_published_to_metrics(model):
    rt = _fleet(model, p=2, d=2, prefix_affinity=True, num_blocks=96)
    _run_waves(rt, _shared_prefix_workload(n_prefixes=2, per_prefix=3))
    text = observability.prometheus_text()
    assert "serving_prefix_affinity_hits" in text
    assert "serving_handoff_queue_depth" in text
    assert "serving_disagg_workers" in text


# ------------------------------------------------- handoff mechanics
def test_handoff_queue_bound_gives_backpressure(model):
    """bound=1 forces strict alternation: the prefill worker stalls
    admission until the decode worker adopts — everything still
    finishes, nothing leaks."""
    rt = _fleet(model, p=1, d=1, handoff_queue=1)
    prompts = _prompts((3, 6, 4, 8, 5), seed=4)
    reqs = [rt.submit(p, max_new_tokens=3) for p in prompts]
    rt.run_until_idle()
    assert [r.state for r in reqs] == ["done"] * len(prompts)
    for p, r in zip(prompts, reqs):
        assert r.output_ids == _ref(model, p, 3)
    assert all(lk == 1 for lk in _leaked_per_pool(rt))
    assert rt.stats()["handoff_queued"] == 0


def test_handoff_queue_validates_and_orders():
    q = HandoffQueue(2)
    assert q.room == 2 and len(q) == 0
    assert q.put("a") and q.put("b") and not q.put("c")
    assert q.take() == "a"
    q.put_back("a")
    assert q.take() == "a" and q.take() == "b" and q.take() is None
    with pytest.raises(ValueError):
        HandoffQueue(0)


def test_decode_engine_rejects_direct_submissions(model):
    rt = _fleet(model, p=1, d=1)
    with pytest.raises(RuntimeError, match="DisaggRouter"):
        rt.decodes[0].submit([1, 2, 3], max_new_tokens=2)
    assert isinstance(rt.decodes[0], DecodeEngine)


def test_disagg_flag_parsing_and_validation(model):
    assert parse_disagg("2x3") == (2, 3)
    assert parse_disagg("") is None
    with pytest.raises(ValueError):
        parse_disagg("2x")
    with pytest.raises(ValueError):
        _fleet(model, p=0, d=1)
    pt.set_flags({"serving_disagg": "3x2"})
    try:
        rt = DisaggRouter(model, **_GEOM)
        assert (len(rt.prefills), len(rt.decodes)) == (3, 2)
    finally:
        pt.set_flags({"serving_disagg": ""})


def test_disagg_background_thread_and_results(model):
    rt = _fleet(model, p=1, d=2)
    rt.start()
    try:
        reqs = [rt.submit(p, max_new_tokens=3)
                for p in _prompts((3, 5, 4, 6), seed=5)]
        done = rt.results(reqs, timeout=60)
    finally:
        rt.stop()
    assert [r.state for r in done] == ["done"] * 4
    assert all(len(r.tokens) == 3 for r in done)


def test_disagg_drain_sheds_new_finishes_queued(model):
    monitor.reset()
    rt = _fleet(model, p=1, d=1)
    reqs = [rt.submit(p, max_new_tokens=3)
            for p in _prompts((3, 6, 4), seed=6)]
    rt.drain()
    assert all(r.state == "done" for r in reqs)
    with pytest.raises(QueueFullError):
        rt.submit([1, 2], max_new_tokens=2)
    assert rt.stats()["draining"] is True


# --------------------------------------------------------------- chaos
@pytest.mark.chaos
def test_chaos_kill_prefill_worker_mid_handoff(model):
    """Tear a prefill worker down with work queued, active, and
    exported-but-unadopted: survivors absorb what they can, the rest
    sheds, every block reference is released (zero leaks on every
    pool, the dead worker's included), and the accounting identity
    completed + shed == offered holds."""
    monitor.reset()
    prompts = _prompts((3, 7, 5, 11, 4, 9, 6, 8, 10, 5), seed=7)
    rt = _fleet(model, p=2, d=2, colocate=False, max_queue=8)
    reqs = [rt.submit(p, max_new_tokens=4) for p in prompts]
    rt.step()          # some admitted/exported, some still queued
    info = rt.kill_prefill_worker(0)
    assert info["prefills_left"] == 1
    rt.run_until_idle()

    done = [r for r in reqs if r.state == "done"]
    shed = [r for r in reqs if r.state == "shed"]
    assert len(done) + len(shed) == len(prompts)
    assert done, "kill must not take the whole fleet down"
    for r in done:
        p = prompts[reqs.index(r)]
        assert r.output_ids == _ref(model, p, 4)
    assert all(lk == 1 for lk in _leaked_per_pool(rt))
    st = rt.stats()
    assert st["completed"] == len(done)
    assert st["shed_total"] == len(shed)
    assert monitor.stat_get("STAT_serving_worker_killed") == 1
    # results() must not double-list re-routed requests
    ids = [r.id for r in rt.results()]
    assert len(ids) == len(set(ids)) == len(prompts)


@pytest.mark.chaos
def test_chaos_handoff_fault_skip_sheds_cleanly(model):
    """Injected `skip` at serving.handoff: affected requests shed with
    reason="fault" and their blocks released; the rest finish
    token-identical. No leaks anywhere."""
    monitor.reset()
    prompts = _prompts((3, 7, 5, 11, 4, 9, 6, 8), seed=8)
    rt = _fleet(model, p=1, d=2, colocate=False, prefix_cache=False)
    with fault_scope("serving.handoff:skip@0.4", seed=9):
        reqs = [rt.submit(p, max_new_tokens=4) for p in prompts]
        rt.run_until_idle()
    shed = [r for r in reqs if r.state == "shed"]
    done = [r for r in reqs if r.state == "done"]
    assert len(shed) + len(done) == len(prompts)
    assert 0 < len(shed) < len(prompts)    # the spec actually fired
    assert all(r.shed_reason == "fault" for r in shed)
    assert monitor.stat_get("STAT_fault_serving.handoff") >= len(shed)
    for r in done:
        p = prompts[reqs.index(r)]
        assert r.output_ids == _ref(model, p, 4)
    assert all(lk == 1 for lk in _leaked_per_pool(rt))


@pytest.mark.chaos
def test_chaos_handoff_drop_is_retried_transparently(model):
    monitor.reset()
    saved = pt.get_flags(["retry_max_attempts", "retry_base_delay",
                          "retry_max_delay"])
    pt.set_flags({"retry_max_attempts": 4, "retry_base_delay": 0.001,
                  "retry_max_delay": 0.01})
    try:
        rt = _fleet(model, p=1, d=1, prefix_cache=False)
        with fault_scope("serving.handoff:drop@0.5", seed=10):
            reqs = [rt.submit(p, max_new_tokens=3)
                    for p in _prompts((3, 6, 4, 7), seed=11)]
            rt.run_until_idle()
    finally:
        pt.set_flags(saved)
    assert all(r.state == "done" for r in reqs)
    assert monitor.stat_get("STAT_fault_serving.handoff") > 0
    assert monitor.stat_get("STAT_retry_serving.handoff") > 0
    assert all(lk == 1 for lk in _leaked_per_pool(rt))


@pytest.mark.chaos
@pytest.mark.parametrize("colocate", [True, False],
                         ids=["colocated", "split-pools"])
def test_chaos_kill_decode_worker_rehomes_inflight(model, colocate):
    """Kill a decode worker holding adopted in-flight rows: every row
    re-homes onto the surviving worker — a free same-pool splice when
    co-located, an export_row/adopt_row copy (with the source refs
    released) across pools — and finishes token-identical to the
    unkilled run. Zero leaks on every pool, the dead worker's
    included."""
    monitor.reset()
    prompts = _prompts((3, 7), seed=40)
    rt = _fleet(model, p=1, d=2, colocate=colocate,
                prefix_cache=False)
    reqs = [rt.submit(p, max_new_tokens=6) for p in prompts]
    rt.step()          # prefill + export
    rt.step()          # decode worker 0 adopts both (drains first)
    assert len(rt.decodes[0]._active) == len(prompts)
    info = rt.kill_decode_worker(0)
    assert info["rehomed"] == len(prompts) and info["shed"] == 0
    assert info["decodes_left"] == 1
    rt.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.state == "done" and r.rehomed is True
        assert r.output_ids == _ref(model, p, 6), \
            f"request {r.id} diverged after re-home"
    assert all(lk == 1 for lk in _leaked_per_pool(rt))
    st = rt.stats()
    assert st["rehomed"] == len(prompts)
    if not colocate:   # cross-pool re-home is an adopt_row copy
        assert st["handoffs_copied"] >= len(prompts)
    ids = [r.id for r in rt.results()]
    assert len(ids) == len(set(ids)) == len(prompts)
    assert monitor.stat_get("STAT_serving_rehomed") == len(prompts)


def test_kill_decode_worker_validates(model):
    rt = _fleet(model, p=1, d=2)
    with pytest.raises(IndexError):
        rt.kill_decode_worker(7)
    rt.kill_decode_worker(1)
    with pytest.raises(ValueError):   # the queue would never drain
        rt.kill_decode_worker(0)
    rt.run_until_idle()


def test_handoff_expired_deadline_shed_not_adopted(model):
    """Regression: a handoff record that outlives the request's TTFT
    deadline in the queue used to be adopted anyway. It must shed at
    adoption time (reason="deadline") with its exported block refs
    released — zero leaks, no decode cycles on a request the SLO
    already gave up on."""
    from tools.loadgen import VirtualClock
    monitor.reset()
    vc = VirtualClock()
    rt = _fleet(model, p=1, d=1, colocate=False, clock=vc.now,
                prefix_cache=False, slo_ttft_ms=50.0,
                slo_prefill_ms=1.0, slo_tpot_ms=1.0)
    req = rt.submit(_prompts((5,), seed=41)[0], max_new_tokens=4)
    for _ in range(20):                 # prefill + export only
        if rt.prefills[0].step() and len(rt._handoff) > 0:
            break
    assert len(rt._handoff) == 1, "handoff never exported"
    vc.advance(1.0)                     # 1s >> the 50ms TTFT deadline
    rt.run_until_idle()
    assert req.state == "shed" and req.shed_reason == "deadline"
    assert rt.stats()["shed"].get("deadline") == 1
    assert all(lk == 1 for lk in _leaked_per_pool(rt))


def test_colocated_roles_survive_a_step_that_consumed_their_shared_pool(
        model, monkeypatch):
    """Co-located prefill and decode workers hold one BlockPool. When a
    prefill fails after its pools were consumed, the pool is rebuilt
    zeroed: the decode worker sheds the rows whose KV went with it (it
    sees the pool's epoch move), staged and queued handoff records from
    before the rebuild are shed rather than adopted, and the fleet
    serves what comes next token-identically, with no leaked block."""
    monitor.reset()
    rt = _fleet(model, p=1, d=1, colocate=True)
    pre, dec = rt.prefills[0], rt.decodes[0]
    assert pre.cache.pool is dec.cache.pool
    prompts = _prompts((5, 7, 6, 4), seed=31)
    early = [rt.submit(p, max_new_tokens=12) for p in prompts[:2]]
    for _ in range(3):
        rt.step()
    assert any(r.state == "running" for r in early)

    ent = pre._prefill_entry(8)
    real = ent["fn"]

    def consume_then_raise(*args):
        real(*args)
        raise RuntimeError("device fault after the pools were donated")

    monkeypatch.setitem(ent, "fn", consume_then_raise)
    victim = rt.submit(prompts[2], max_new_tokens=4)
    for _ in range(3):
        rt.step()
    monkeypatch.undo()
    assert victim.state == "shed"
    assert all(r.state == "shed" for r in early), \
        [r.state for r in early]
    assert monitor.stat_get("STAT_serving_pool_rebuilds") == 1

    late = rt.submit(prompts[3], max_new_tokens=6)
    rt.run_until_idle()
    assert late.state == "done"
    assert late.output_ids == _ref(model, prompts[3], 6)
    assert all(lk == 1 for lk in _leaked_per_pool(rt))
