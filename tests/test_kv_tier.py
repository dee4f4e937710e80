"""Host-RAM KV block tier + session store (serving/kv_tier.py).

Contracts: a conversation demoted to host RAM resumes
*token-identically* — ``submit(session=...)`` after the device pool
flushed its chain produces exactly the tokens a never-demoted greedy
run produces, across speculative decoding (K=2), the int8 device
pool, and LoRA tenant pins. Migration is all-or-nothing both ways
(a promotion that cannot take every block it needs takes none), the
host store evicts LRU leaf-first under pressure, one fleet-shared
store dedups a prefix chain across workers, and chaos at the
``serving.replica`` + ``serving.migrate`` fault sites leaks zero
blocks on either tier. The fleet prefix index keeps (as a host-tier
marker) affinity entries whose chain outlives a killed worker — the
regression lock for the purge-everything bug.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.analysis import lifecycle, predict_serving_compiles
from paddle_tpu.models.generation import greedy_search
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import fault_scope
from paddle_tpu.serving import (DisaggRouter, HostBlockStore,
                                ReplicaRouter, ServingEngine,
                                SessionStore, TierManager, make_adapter)
from paddle_tpu.serving.kv_tier import _HostEntry


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


def _tier(cfg, blocks=64, block_size=4, idle_ms=0.0):
    return TierManager(
        HostBlockStore(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                       block_size=block_size, num_blocks=blocks),
        demote_idle_ms=idle_ms)


def _engine(model, tier=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", [8, 16, 32])
    kw.setdefault("max_queue", 16)
    kw.setdefault("block_size", 4)
    if tier is not None:
        kw["kv_tier"] = tier
    return ServingEngine(model, **kw)


def _ref(model, prompt, n, cache_len=64):
    return greedy_search(model, np.asarray([prompt]), max_new_tokens=n,
                         cache_len=cache_len)[0].tolist()


def _drain_device(eng, tier):
    """Force the conversation fully off-device: flush the device
    prefix cache (its chains were demoted by the idle sweep already)
    so the next turn can only resume through the host tier."""
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1      # trash block only
    assert tier.stats()["host_chain_entries"] > 0, \
        "nothing demoted; resume would silently re-prefill everything"


# ----------------------------------------------- resume token identity
# The end-to-end oracles below carry ``slow`` (like the heavyweight
# serving oracles since PR 8) so the capped tier-1 run stays inside
# its budget — ci.sh runs them in the full-mode suite and the serving
# gate; the host-store/session-store/linter/predictor units stay
# tier-1.
@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    dict(),
    dict(spec_tokens=2),
    dict(kv_dtype="int8"),
], ids=["greedy", "spec2", "int8"])
def test_session_resumes_token_identical_after_demotion(model, kw):
    """Turn 2 of a session whose turn-1 context was demoted to host
    RAM (and flushed off-device) == one never-demoted greedy pass over
    the concatenated conversation — the migration quantization grid
    and the re-prefilled suffix change nothing."""
    tier = _tier(model.gpt.cfg)
    eng = _engine(model, tier, **kw)
    t1, t2 = _prompts((12, 6), seed=1)

    r1 = eng.submit(t1, max_new_tokens=6, session="u1")
    eng.run_until_idle()
    assert r1.state == "done"
    assert r1.output_ids == _ref(model, t1, 6)

    _drain_device(eng, tier)

    r2 = eng.submit(t2, max_new_tokens=6, session="u1")
    eng.run_until_idle()
    assert r2.state == "done"
    # output_ids carries the full sequence (prompt included), so the
    # stored context IS r1.output_ids — the oracle replays it + turn 2
    ctx = r1.output_ids + t2
    assert r2.output_ids == _ref(model, ctx, 6), \
        "resumed turn diverged from the never-demoted conversation"
    st = tier.stats()
    assert st["sessions_resumed"] == 1
    assert st["migrated_promote_blocks"] > 0, \
        "turn 2 never touched the host tier"
    eng.cache.flush_prefix_cache()
    tier.flush()
    assert eng.cache.allocator.leaked() == 1 and tier.leaked() == 0


@pytest.mark.slow
def test_session_resume_keeps_lora_tenant_pin(model):
    """A tenant conversation survives demotion: turn 2 resumes with
    the same adapter applied (== a one-shot full-context submit with
    that tenant) and the adapter pool leaks nothing across the
    park/resume cycle."""
    cfg = model.gpt.cfg
    tier = _tier(cfg)
    eng = _engine(model, tier, lora_rank=2, lora_max_adapters=2)
    eng.load_adapter("acme", make_adapter(cfg, 2, seed=1, scale=0.5))
    t1, t2 = _prompts((10, 5), seed=2)

    r1 = eng.submit(t1, max_new_tokens=5, session="s", tenant="acme")
    eng.run_until_idle()
    assert r1.state == "done"
    _drain_device(eng, tier)
    r2 = eng.submit(t2, max_new_tokens=5, session="s", tenant="acme")
    eng.run_until_idle()
    assert r2.state == "done"
    assert eng.lora_pool.leaked() == 0

    # oracle: the same full context one-shot through a tier-free
    # engine with the same adapter — no demotion anywhere
    ref_eng = _engine(model, lora_rank=2, lora_max_adapters=2)
    ref_eng.load_adapter("acme",
                         make_adapter(cfg, 2, seed=1, scale=0.5))
    ctx = r1.output_ids + t2
    ref = ref_eng.submit(ctx, max_new_tokens=5, tenant="acme")
    ref_eng.run_until_idle()
    assert r2.output_ids == ref.output_ids


def test_session_requires_tier_and_validates(model):
    eng = _engine(model)                 # no tier attached
    with pytest.raises(ValueError, match="host KV tier"):
        eng.submit([1, 2, 3], session="u1")
    tier = _tier(model.gpt.cfg)
    eng2 = _engine(model, tier)
    with pytest.raises(ValueError, match="session"):
        eng2.submit([1, 2, 3], session="")


# --------------------------------------------------- migration machinery
@pytest.mark.slow
def test_promotion_is_all_or_nothing_under_pool_pressure(model):
    """A promotion that cannot allocate every device block it needs
    takes none: the device pool's used count is unchanged and the host
    chain stays intact for a later, roomier attempt."""
    tier = _tier(model.gpt.cfg)
    eng = _engine(model, tier, max_slots=1)
    prompt = _prompts((20,), seed=3)[0]
    r = eng.submit(prompt, max_new_tokens=2, session="u1")
    eng.run_until_idle()
    assert r.state == "done"
    _drain_device(eng, tier)
    chain_entries = tier.stats()["host_chain_entries"]
    assert chain_entries >= 3

    pool = eng.cache.pool
    alloc = eng.cache.allocator
    # squeeze the pool: leave fewer free blocks than the chain needs
    squeeze = []
    while alloc.num_free > chain_entries - 1:
        squeeze.append(pool.alloc_block())
    used_before = alloc.num_used
    promoted = tier.promote(eng.cache, prompt)
    assert promoted == 0, "partial promotion must not happen"
    assert alloc.num_used == used_before, \
        "failed promotion leaked device blocks"
    assert tier.stats()["host_chain_entries"] == chain_entries

    pool.release_blocks(squeeze)
    assert tier.promote(eng.cache, prompt) == chain_entries
    eng.cache.flush_prefix_cache()
    tier.flush()
    assert alloc.leaked() == 1 and tier.leaked() == 0


def test_host_store_evicts_lru_leaf_first():
    """Pressure eviction order: least-recently-touched unpinned entry
    goes first, and a resident child pins its parent out of reach."""
    store = HostBlockStore(num_layers=1, num_heads=2, head_dim=4,
                           block_size=4, num_blocks=3)
    blks = [store.acquire() for _ in range(3)]
    store.put(_HostEntry("k1", None, blks[0], (1, 2, 3, 4)))
    store.put(_HostEntry("k2", None, blks[1], (5, 6, 7, 8)))
    store.put(_HostEntry("k3", "k1", blks[2], (9, 10, 11, 12)))
    store.touch("k2")                 # k1 older, but pinned by k3
    nb = store.acquire()              # full: must evict exactly one
    assert nb is not None
    assert not store.has_key("k3"), "LRU unpinned leaf is k3"
    assert store.has_key("k1") and store.has_key("k2")
    assert store.evictions == 1
    store.release(nb)
    store.flush()
    assert store.leaked() == 0


def test_fleet_dedup_two_engines_share_one_host_chain(model):
    """Two engines over ONE fleet-shared tier demote the same prompt:
    the second demotion finds the chain host-resident and drops its
    device copy without a second host copy — one chain, fleet-wide."""
    tier = _tier(model.gpt.cfg)
    e1 = _engine(model, tier)
    e2 = _engine(model, tier)
    prompt = _prompts((16,), seed=4)[0]
    for eng in (e1, e2):
        r = eng.submit(prompt, max_new_tokens=2)
        eng.run_until_idle()
        assert r.state == "done"
    st = tier.stats()
    assert st["demote_dedup_entries"] > 0, \
        "second engine re-copied a chain the host already holds"
    assert st["host_blocks_used"] == st["host_chain_entries"], \
        "dedup kept duplicate host blocks alive"
    for eng in (e1, e2):
        eng.cache.flush_prefix_cache()
    tier.flush()
    assert tier.leaked() == 0


def test_session_store_roundtrip():
    ss = SessionStore()
    assert ss.get("a") is None and len(ss) == 0
    ss.save("a", [1, 2, 3])
    ss.save("b", [4])
    assert ss.get("a") == [1, 2, 3] and len(ss) == 2
    assert sorted(ss.session_ids()) == ["a", "b"]
    ss.drop("a")
    assert ss.get("a") is None and len(ss) == 1


# ------------------------------------------------------------- chaos
@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_kill_and_migrate_faults_leak_nothing(model):
    """Replica crashes (serving.replica) racing migration faults
    (serving.migrate, retried per RetryPolicy) over session traffic:
    after the dust settles, zero leaked blocks on BOTH tiers and the
    fleet still completes work."""
    from paddle_tpu import monitor
    monitor.reset()
    tier = _tier(model.gpt.cfg)
    rt = ReplicaRouter(model, n_replicas=2, max_slots=2, max_len=64,
                       buckets=[8, 16, 32], max_queue=16, block_size=4,
                       kv_tier=tier)
    prompts = _prompts((6, 10, 8, 12, 7, 9), seed=5)
    with fault_scope("serving.replica:error@0.2;"
                     "serving.migrate:error@0.3", seed=6):
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(rt.submit(p, max_new_tokens=3,
                                  session=f"c{i % 3}"))
            rt.step()
        rt.run_until_idle()
    assert any(r.state == "done" for r in reqs)
    for eng in rt.engines + rt._retiring:
        eng.cache.flush_prefix_cache()
        assert eng.cache.allocator.leaked() == 1, \
            f"device blocks leaked on {eng._eid}"
    tier.flush()
    assert tier.leaked() == 0, "host blocks leaked under chaos"


# ------------------------------------- fleet prefix index (regression)
@pytest.mark.slow
def test_killed_prefill_worker_keeps_host_reachable_affinity(model):
    """Regression: kill_prefill_worker used to purge EVERY affinity
    entry of the dead worker — orphaning fleet-shared host chains that
    any survivor could promote. Entries whose chain is host-resident
    must convert to the host-tier marker, route as affinity hits, and
    the resumed request must stay token-identical."""
    from paddle_tpu.serving.disagg import _HOST_TIER
    tier = _tier(model.gpt.cfg)
    rt = DisaggRouter(model, n_prefill=2, n_decode=1, max_slots=2,
                      max_len=64, buckets=[8, 16, 32], max_queue=16,
                      block_size=4, prefix_affinity=True, kv_tier=tier)
    prompt = _prompts((12,), seed=7)[0]
    r1 = rt.submit(prompt, max_new_tokens=4)
    rt.run_until_idle()
    assert r1.state == "done"
    assert tier.stats()["host_chain_entries"] > 0

    out = rt.kill_prefill_worker(0)
    kept = out["affinity_kept"]
    assert kept > 0, "host-reachable affinity entries were purged"
    markers = sum(1 for v in rt._affinity.values() if v is _HOST_TIER)
    assert markers == kept

    r2 = rt.submit(prompt, max_new_tokens=4)
    rt.run_until_idle()
    assert r2.state == "done" and r2.output_ids == r1.output_ids
    assert tier.stats()["migrated_promote_blocks"] > 0, \
        "survivor re-prefilled instead of promoting the host chain"
    # the survivor's publish replaced the markers with live entries
    assert sum(1 for v in rt._affinity.values()
               if v is _HOST_TIER) == 0
    for eng in rt.engines:
        eng.cache.flush_prefix_cache()
    tier.flush()
    assert tier.leaked() == 0


# ------------------------------------------------- analysis integration
def test_lifecycle_linter_clean_on_kv_tier():
    import os
    import paddle_tpu.serving as _sv
    path = os.path.join(os.path.dirname(_sv.__file__), "kv_tier.py")
    r = lifecycle.lint_files([path])
    assert not r.diagnostics, [str(d) for d in r.diagnostics]


def test_predict_serving_compiles_host_tier_is_validated_noop():
    rounds = [[(list(range(1, 13)), 4)], [(list(range(1, 13)), 4)]]
    base = predict_serving_compiles(rounds, buckets=[8, 16],
                                    max_len=64, block_size=4)
    tiered = predict_serving_compiles(rounds, buckets=[8, 16],
                                      max_len=64, block_size=4,
                                      host_tier=True, sessions=1000)
    assert tiered == base
    with pytest.raises(ValueError, match="host_tier"):
        predict_serving_compiles(rounds, buckets=[8], max_len=64,
                                 sessions=5)
