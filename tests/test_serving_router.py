"""ReplicaRouter — data-parallel serving replicas (serving/router.py).

Contracts: least-loaded routing actually spreads load and never
changes tokens (each replica is a full ServingEngine, so routed
requests must equal sequential greedy); N replicas share one model and
therefore compile each step exactly once total; full replicas shed
through the QueueFullError backpressure exit; ``drain()`` finishes
queued work while shedding new admissions; and a chaos run over the
``serving.route`` fault site finishes every non-shed request with zero
leaked KV blocks.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, monitor
from paddle_tpu.analysis import concurrency as ccz
from paddle_tpu.models.generation import decode_step_paged, greedy_search
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import RetryError, fault_scope
from paddle_tpu.serving import (DisaggRouter, QueueFullError, ReplicaRouter,
                                ServingEngine)


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


def _router(model, n=2, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("buckets", [8, 16])
    kw.setdefault("max_queue", 16)
    kw.setdefault("block_size", 4)
    return ReplicaRouter(model, n_replicas=n, **kw)


def test_router_routes_and_matches_sequential_greedy(model):
    """6 requests over 2 replicas: both replicas get work and every
    output is token-identical to an independent greedy run."""
    prompts = _prompts((3, 7, 5, 11, 4, 9), seed=1)
    rt = _router(model)
    reqs = [rt.submit(p, max_new_tokens=5) for p in prompts]
    rt.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    per_replica = [len(eng._all) for eng in rt.engines]
    assert all(n > 0 for n in per_replica), per_replica
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=5,
                            cache_len=32)[0].tolist()
        assert r.output_ids == ref, f"request {r.id} diverged"


def test_router_least_loaded_prefers_emptier_replica(model):
    """With replica 0 pre-loaded, the next submission must land on
    replica 1 (depth dominates the routing key)."""
    rt = _router(model)
    for p in _prompts((3, 5), seed=2):
        rt.engines[0].submit(p, max_new_tokens=2)
    r = rt.submit(_prompts((4,), seed=3)[0], max_new_tokens=2)
    assert r in rt.engines[1]._all
    rt.run_until_idle()


def test_router_replicas_share_compiled_steps(model):
    """The unified per-model step cache: N replicas compile decode
    exactly once total, and each prefill bucket once total."""
    before = decode_step_paged(model)["traces"]["count"]
    rt = _router(model, n=3)
    for p in _prompts((2, 6, 3, 9, 5, 12), seed=4):
        rt.submit(p, max_new_tokens=3)
    rt.run_until_idle()
    assert decode_step_paged(model)["traces"]["count"] - before <= 1
    counts = {}
    for eng in rt.engines:
        for b, e in eng._prefill_fns.items():
            counts[b] = e["traces"]["count"]   # shared entries: equal
    assert all(n == 1 for n in counts.values()), counts


def test_router_sheds_when_every_replica_is_full(model):
    monitor.reset()
    rt = _router(model, n=2, max_slots=1, max_queue=1)
    for p in _prompts((3, 4), seed=5):        # one per replica queue
        rt.submit(p, max_new_tokens=2)
    with pytest.raises(QueueFullError):
        rt.submit([1, 2, 3], max_new_tokens=2)
    assert monitor.stat_get("STAT_serving_route_shed") == 1
    rt.run_until_idle()
    assert monitor.stat_get("STAT_serving_routed") == 2


def test_router_drain_finishes_queued_sheds_new(model):
    monitor.reset()
    rt = _router(model)
    reqs = [rt.submit(p, max_new_tokens=3)
            for p in _prompts((3, 6, 4), seed=6)]
    rt.drain()
    assert all(r.state == "done" for r in reqs)
    with pytest.raises(QueueFullError):
        rt.submit([1, 2], max_new_tokens=2)
    assert monitor.stat_get("STAT_serving_drained") == 1
    assert rt.stats()["draining"] is True


def test_router_background_threads_and_results(model):
    rt = _router(model)
    rt.start()
    try:
        reqs = [rt.submit(p, max_new_tokens=3)
                for p in _prompts((3, 5, 4, 6), seed=7)]
        done = rt.results(reqs, timeout=60)
    finally:
        rt.stop()
    assert [r.state for r in done] == ["done"] * 4
    assert all(len(r.tokens) == 3 for r in done)


def test_router_stats_surface(model):
    rt = _router(model, n=2)
    rt.submit(_prompts((5,), seed=8)[0], max_new_tokens=2)
    st = rt.stats()
    assert st["replicas"] == 2 and st["draining"] is False
    assert st["mesh_shape"] is None
    assert len(st["queue_depths"]) == 2 and sum(st["queue_depths"]) == 1
    assert len(st["kv_blocks_free"]) == 2
    assert len(st["per_replica"]) == 2
    assert all("kv_dtype" in s for s in st["per_replica"])
    rt.run_until_idle()
    assert sum(rt.stats()["queue_depths"]) == 0


def test_router_validates_construction(model):
    with pytest.raises(ValueError):
        ReplicaRouter()                        # neither model nor engines
    with pytest.raises(ValueError):
        ReplicaRouter(model, n_replicas=0)
    with pytest.raises(ValueError):
        ReplicaRouter(engines=[])
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8])
    with pytest.raises(ValueError):            # engines XOR model+kwargs
        ReplicaRouter(model, engines=[eng])
    rt = ReplicaRouter(engines=[eng])
    assert rt.engines == [eng]


def test_router_prebuilt_engines_roundtrip(model):
    engines = [ServingEngine(model, max_slots=1, max_len=32,
                             buckets=[8], block_size=4)
               for _ in range(2)]
    rt = ReplicaRouter(engines=engines)
    reqs = [rt.submit(p, max_new_tokens=3)
            for p in _prompts((3, 5), seed=9)]
    rt.run_until_idle()
    for p, r in zip(_prompts((3, 5), seed=9), reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=3,
                            cache_len=32)[0].tolist()
        assert r.output_ids == ref


def test_router_drain_replica_rehomes_queued_requests(model):
    """Targeted scale-down: draining one replica re-routes its queued
    requests onto live peers instead of shedding them — the regression
    where a draining replica silently dropped its queue. Every request
    finishes, and results() lists each re-homed request exactly once."""
    monitor.reset()
    rt = _router(model)
    prompts = _prompts((3, 6, 4, 7), seed=20)
    reqs = [rt.engines[0].submit(p, max_new_tokens=3)
            for p in prompts]               # all queued on replica 0
    moved = rt.drain_replica(0)
    assert moved == len(prompts)
    assert monitor.stat_get("STAT_serving_rerouted") == len(prompts)
    assert len(rt.engines) == 1
    rt.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=3,
                            cache_len=32)[0].tolist()
        assert r.output_ids == ref
    ids = [r.id for r in rt.results()]
    assert len(ids) == len(set(ids)) == len(prompts)
    with pytest.raises(ValueError):         # can't drain the last one
        rt.drain_replica(0)
    with pytest.raises(IndexError):
        rt.drain_replica(5)


def test_router_submit_skips_draining_replica(model):
    """A replica marked draining must not attract routes even when it
    is the least loaded — and must not rack up shed counters from
    submissions it was never eligible for."""
    rt = _router(model)
    rt.engines[0].draining = True           # emptiest, but off-limits
    r = rt.submit(_prompts((4,), seed=21)[0], max_new_tokens=2)
    assert r in rt.engines[1]._all
    assert len(rt.engines[0]._all) == 0
    rt.engines[0].draining = False
    rt.run_until_idle()


# ---------------------------------------------------------------------------
# chaos: the serving.route fault site
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_router_chaos_skip_sheds_cleanly_zero_leaked_blocks(model):
    """Injected `skip` at serving.route sheds some submissions as
    QueueFullError; every accepted request still completes
    token-identically and no replica leaks a single KV block."""
    monitor.reset()
    prompts = _prompts((3, 7, 5, 11, 4, 9, 6, 8), seed=10)
    rt = _router(model, prefix_cache=False)
    accepted, shed = [], 0
    with fault_scope("serving.route:skip@0.4", seed=11):
        for p in prompts:
            try:
                accepted.append((p, rt.submit(p, max_new_tokens=4)))
            except QueueFullError:
                shed += 1
    rt.run_until_idle()
    assert 0 < shed < len(prompts)             # the spec actually fired
    assert shed == monitor.stat_get("STAT_serving_route_shed")
    assert monitor.stat_get("STAT_fault_serving.route") == shed
    for p, r in accepted:
        assert r.state == "done"
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=4,
                            cache_len=32)[0].tolist()
        assert r.output_ids == ref
    for eng in rt.engines:                     # only the trash block
        assert eng.cache.allocator.leaked() == 1


@pytest.mark.chaos
def test_router_chaos_drop_is_retried_transparently(model):
    """Injected `drop` (a ConnectionResetError) at serving.route rides
    RetryPolicy: with attempts left, every submission still lands and
    the retry counter proves the recovery ran."""
    monitor.reset()
    saved = pt.get_flags(["retry_max_attempts", "retry_base_delay",
                          "retry_max_delay"])
    pt.set_flags({"retry_max_attempts": 4, "retry_base_delay": 0.001,
                  "retry_max_delay": 0.01})
    try:
        rt = _router(model, prefix_cache=False)
        with fault_scope("serving.route:drop@0.5", seed=12):
            reqs = [rt.submit(p, max_new_tokens=3)
                    for p in _prompts((3, 6, 4, 7), seed=13)]
        rt.run_until_idle()
    finally:
        pt.set_flags(saved)
    assert all(r.state == "done" for r in reqs)
    assert monitor.stat_get("STAT_fault_serving.route") > 0
    assert monitor.stat_get("STAT_retry_serving.route") > 0
    assert monitor.stat_get("STAT_serving_route_shed") == 0
    for eng in rt.engines:
        assert eng.cache.allocator.leaked() == 1


@pytest.mark.chaos
def test_router_chaos_retry_exhaustion_sheds_as_backpressure(model):
    """Every attempt dropping -> RetryError -> shed as QueueFullError:
    chaos at the router never raises transport errors at callers."""
    monitor.reset()
    saved = pt.get_flags(["retry_max_attempts", "retry_base_delay",
                          "retry_max_delay"])
    pt.set_flags({"retry_max_attempts": 2, "retry_base_delay": 0.001,
                  "retry_max_delay": 0.01})
    try:
        rt = _router(model, prefix_cache=False)
        with fault_scope("serving.route:drop"):   # fires every time
            with pytest.raises(QueueFullError):
                rt.submit([1, 2, 3], max_new_tokens=2)
    finally:
        pt.set_flags(saved)
    assert monitor.stat_get("STAT_serving_route_shed") == 1
    rt.run_until_idle()                        # nothing was admitted
    for eng in rt.engines:
        assert len(eng._all) == 0
        assert eng.cache.allocator.leaked() == 1


# ---------------------------------------------------------------------------
# fleet fault tolerance: kill/restart, health states, serving.replica
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_kill_replica_rehomes_inflight_token_identical(model):
    """The headline recovery contract: kill a replica holding
    in-flight speculative (K=2) int8-KV decodes with a pinned LoRA
    tenant. Every displaced request re-homes (re-prefilled from its
    committed tokens on a survivor), finishes with greedy output
    identical to an unkilled run, ``results()`` lists each re-homed
    id exactly once, and neither KV blocks nor LoRA pages leak —
    the dead replica's included."""
    from paddle_tpu.serving import make_adapter
    monitor.reset()
    prompts = _prompts((3, 7, 5, 6), seed=30)
    refs = [greedy_search(model, np.asarray([p]), max_new_tokens=8,
                          cache_len=32)[0].tolist() for p in prompts]
    rt = _router(model, n=2, spec_tokens=2, kv_dtype="int8",
                 prefix_cache=False, lora_rank=2, lora_max_adapters=2)
    rt.load_adapter("acme", make_adapter(model.gpt.cfg, 2, seed=1))
    reqs = [rt.engines[0].submit(p, max_new_tokens=8,
                                 tenant="acme" if i == 1 else "")
            for i, p in enumerate(prompts)]   # all on the victim
    rt.engines[0].step()                      # commit some tokens
    assert any(r.tokens for r in reqs), "nothing in flight yet"
    pending = [r for r in reqs if r.state not in ("done", "shed")]
    info = rt.kill_replica(0)
    assert info["rehomed"] + info["shed"] == len(pending)
    assert info["rehomed"] > 0 and info["replicas_left"] == 1
    rt.run_until_idle()
    done = [r for r in reqs if r.state == "done" and r.rehomed]
    assert len(done) == info["rehomed"]
    for r in done:
        i = reqs.index(r)
        # LoRA-tenant output legitimately differs from the base-model
        # reference; the base-model requests must match it exactly
        if not r.tenant:
            assert r.output_ids == refs[i], f"request {r.id} diverged"
    ids = [r.id for r in rt.results()]
    assert len(ids) == len(set(ids)) == len(prompts)
    for eng in rt.engines + rt._retiring:      # only the trash block
        assert eng.cache.allocator.leaked() == 1
    assert rt.engines[0].lora_pool.leaked() == 0
    st = rt.stats()
    assert st["kills"] == 1 and st["rehomed"] == info["rehomed"]
    assert monitor.stat_get("STAT_serving_rehomed") == info["rehomed"]


def test_router_restart_replica_works_on_sole_replica(model):
    """restart_replica inserts the same-geometry replacement BEFORE
    killing the old engine, so even a 1-replica fleet restarts:
    queued work lands on the replacement and finishes
    token-identically; the replacement graduates recovering ->
    healthy on its first productive step."""
    monitor.reset()
    rt = _router(model, n=1)
    prompts = _prompts((3, 6), seed=31)
    reqs = [rt.submit(p, max_new_tokens=3) for p in prompts]
    info = rt.restart_replica(0)
    assert info["rehomed"] == len(prompts) and info["shed"] == 0
    assert len(rt.engines) == 1
    assert rt.engines[0]._health == "recovering"
    rt.run_until_idle()
    assert rt.engines[0]._health == "healthy"
    for p, r in zip(prompts, reqs):
        assert r.state == "done" and r.rehomed is True
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=3,
                            cache_len=32)[0].tolist()
        assert r.output_ids == ref
    st = rt.stats()
    assert st["kills"] == 1 and st["restarts"] == 1
    assert st["rehomed"] == len(prompts)


def test_router_kill_validates_index_and_last_replica(model):
    rt = _router(model, n=2)
    with pytest.raises(IndexError):
        rt.kill_replica(5)
    rt.kill_replica(0)
    with pytest.raises(ValueError):   # never kill the whole fleet
        rt.kill_replica(0)
    rt.run_until_idle()


def test_router_watchdog_strikes_suspect_dead_restart(model):
    """A replica whose step keeps raising walks healthy -> suspect ->
    dead in FLAGS_serving_replica_strikes supervised steps, and
    _reap_dead replaces it under auto-restart; the fleet keeps
    serving through the whole episode."""
    saved = pt.get_flags(["serving_replica_strikes"])
    pt.set_flags({"serving_replica_strikes": 2})
    try:
        rt = _router(model, n=2)
        sick = rt.engines[0]

        def _boom():
            # retiring engines step unsupervised post-teardown; only
            # sabotage the replica while it is still in the fleet
            if sick in rt.engines:
                raise RuntimeError("simulated wedged replica")
            return False

        sick.step = _boom
        r = rt.submit(_prompts((4,), seed=32)[0], max_new_tokens=2)
        rt.step()
        assert sick._health == "suspect"
        rt.step()                      # second strike -> dead -> reap
        assert sick not in rt.engines
        assert all(e._health != "dead" for e in rt.engines)
        rt.run_until_idle()
        assert r.state == "done"
        st = rt.stats()
        assert st["restarts"] == 1 and st["replicas"] == 2
        assert all(h == "healthy" for h in st["health"])
    finally:
        pt.set_flags(saved)


def test_router_routing_deprioritizes_suspect_replica(model):
    """Health rank prefixes the routing key: a suspect replica only
    attracts work when every healthy replica is worse-ranked, and a
    dead one never does."""
    rt = _router(model, n=2)
    rt.engines[0]._health = "suspect"   # emptiest but unhealthy
    r = rt.submit(_prompts((4,), seed=33)[0], max_new_tokens=2)
    assert r in rt.engines[1]._all
    rt.engines[0]._health = "healthy"
    rt.run_until_idle()


@pytest.mark.chaos
def test_chaos_serving_replica_fault_site_crash_restarts(model):
    """`error` at serving.replica crashes the round-robin victim once
    per router step; under auto-restart the fleet heals in place —
    same replica count, kills == restarts == fired faults, and the
    in-flight work still completes."""
    monitor.reset()
    rt = _router(model, n=2)
    reqs = [rt.submit(p, max_new_tokens=3)
            for p in _prompts((3, 6, 4), seed=34)]
    with fault_scope("serving.replica:error@0", seed=35):
        rt.step()                      # exactly one crash+restart
    rt.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    st = rt.stats()
    assert st["kills"] == 1 and st["restarts"] == 1
    assert st["replicas"] == 2
    assert monitor.stat_get("STAT_fault_serving.replica") == 1


@pytest.mark.chaos
def test_chaos_serving_replica_skip_kills_without_restart(model):
    """`skip` at serving.replica is permanent capacity loss: the
    victim is killed, not replaced — and the guard never takes the
    last replica."""
    monitor.reset()
    rt = _router(model, n=2)
    with fault_scope("serving.replica:skip", seed=36):
        rt.step()                      # kills one
        rt.step()                      # sole survivor: guard holds
    st = rt.stats()
    assert st["replicas"] == 1
    assert st["kills"] == 1 and st["restarts"] == 0
    rt.run_until_idle()


# ------------------------------------- FLAGS_serving_dispatch_threads
def _assert_no_leaks(router):
    """Every paged engine behind ``router`` holds only its trash block
    once the prefix cache is flushed."""
    seen = set()
    for eng in router.engines:
        alloc = eng.cache.allocator
        if id(alloc) not in seen:
            seen.add(id(alloc))
            eng.cache.flush_prefix_cache()
            assert alloc.leaked() <= 1, alloc.leaked()


@pytest.mark.parametrize("fleet", ["replicas", "disagg"])
def test_a_fleet_stepped_from_a_thread_pool_matches_greedy(model, fleet):
    """Replicas (or a prefill / decode role split) stepped from a
    bounded worker pool == the greedy oracle per request; no kills, no
    leaked blocks."""
    prompts = _prompts((3, 7, 5, 9, 4, 6), seed=7)
    kw = dict(dispatch_threads=2, max_slots=2, max_len=32,
              buckets=[8, 16], max_queue=32, block_size=4)
    rt = (ReplicaRouter(model, n_replicas=2, **kw) if fleet == "replicas"
          else DisaggRouter(model, n_prefill=1, n_decode=1, **kw))
    try:
        reqs = [rt.submit(p, max_new_tokens=6) for p in prompts]
        rt.run_until_idle()
        assert all(r.state == "done" for r in reqs)
        for p, r in zip(prompts, reqs):
            ref = greedy_search(model, np.asarray([p]), max_new_tokens=6,
                                cache_len=32)[0].tolist()
            assert r.output_ids == ref, f"request {r.id} diverged"
        assert rt.stats().get("replica_kills", 0) == 0
        _assert_no_leaks(rt)
    finally:
        rt.stop()


def test_the_sanitizer_is_clean_under_a_threaded_router(model):
    """The trace lock / step lock / router locks hold their declared
    order under concurrent replica stepping: no lock-graph cycles, no
    guarded-state violations."""
    old = flags.get_flag("sanitize_locks")
    flags.set_flags({"sanitize_locks": True})
    ccz.reset()
    try:
        rt = _router(model, dispatch_threads=2, max_queue=32)
        try:
            for p in _prompts((3, 5, 4, 6), seed=9):
                rt.submit(p, max_new_tokens=6)
            rt.run_until_idle()
        finally:
            rt.stop()
        assert ccz.cycles() == [], ccz.cycles()
        assert ccz.violations() == [], ccz.violations()
    finally:
        flags.set_flags({"sanitize_locks": old})
        ccz.reset()
