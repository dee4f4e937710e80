"""The serving plane's model seam and its cache by layer kind
(``serving/seam.py``, ``serving/kv_cache.py``): the allocator's invariants
for two kinds, what the engine reports of them, the GPT path through the
seam, and every feature the second model is refused, by name."""

import inspect
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (GPT_CONFIGS, GPTForCausalLM,    # noqa: E402
                               JAMBA_CONFIGS, JambaForCausalLM,
                               LAGUNA_CONFIGS, LagunaForCausalLM,
                               MELLUM_CONFIGS, MellumForCausalLM,
                               generation)
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from paddle_tpu.serving import engine as engine_mod            # noqa: E402
from paddle_tpu.serving.kv_cache import (AllocatorView,        # noqa: E402
                                         BlockAllocator, BlockKVCache)
from paddle_tpu.serving.seam import (FEATURES, CacheKind,      # noqa: E402
                                     ServedModel, served)

WINDOW, BS, SLOTS = 16, 8, 4
BUDGET = WINDOW // BS + 1           # blocks a row of the window kind holds


@pytest.fixture(scope="module")
def mellum():
    layers.seed(3)
    model = MellumForCausalLM(MELLUM_CONFIGS["mellum-tiny"])
    model.eval()
    return model


@pytest.fixture(scope="module")
def jamba():
    layers.seed(3)
    model = JambaForCausalLM(JAMBA_CONFIGS["jamba-tiny"])
    model.eval()
    return model


@pytest.fixture(scope="module")
def gpt():
    layers.seed(3)
    model = GPTForCausalLM(GPT_CONFIGS["gpt2-tiny"])
    model.eval()
    return model


def two_kind_cache(num_blocks=0, max_len=128):
    spec = ServedModel(
        model=None, family="toy", max_positions=max_len, vocab=8,
        cache_kinds=(CacheKind("full", (1, 3), 2, 4),
                     CacheKind("window", (0, 2), 2, 4, window=WINDOW)),
        kv_dtype="f32", features=frozenset())
    return BlockKVCache.for_model(spec, SLOTS, max_len, block_size=BS,
                                  num_blocks=num_blocks, prefix_cache=False,
                                  kv_dtype="f32")


# ------------------------------------------------------------ the cache

def test_a_window_kinds_pool_is_bounded_by_slots_window_and_a_block():
    c = two_kind_cache()
    (w,) = c._windows
    assert w.budget == BUDGET
    assert w.pool.num_blocks == SLOTS * BUDGET + 1
    assert c.pool.num_blocks == SLOTS * (128 // BS) + 1
    # one pool pair a MODEL layer, in the model's order
    arrays = c.arrays()
    assert [a[0].shape[0] for a in arrays] == \
        [w.pool.num_blocks, c.pool.num_blocks] * 2
    c.set_arrays(arrays)
    assert len(c.layers) == 2 and len(w.pool.layers) == 2
    assert isinstance(c.allocator, AllocatorView)
    assert isinstance(two_kind_cache()._windows[0].pool.allocator,
                      BlockAllocator)
    assert c.allocator.leaked() == 1           # ONE trash block in all
    assert c.blocks_free == (c.pool.num_blocks - 1) + SLOTS * BUDGET


def test_blocks_behind_the_window_return_while_the_request_lives():
    c = two_kind_cache()
    (w,) = c._windows
    row, shared = c.acquire(list(range(1, 51)), 50 + 60)
    assert shared == 0
    # a prompt of 50 rows: the next position, 50, sees keys 35..50: blocks
    # 4 (rows 32-39) onward, up to the budget
    assert (int(w.lo[row]), int(w.hi[row])) == (4, 4 + BUDGET)
    assert list(np.flatnonzero(w.tables[row] != c.TRASH)) == [4, 5, 6]
    assert int(c._nblocks[row]) == -(-110 // BS)      # the full kind: all
    c.commit_prefill(row, 50)
    version, freed, seen = c.tables_version, 0, set()
    for n in range(60):
        before = set(np.flatnonzero(w.tables[row] != c.TRASH))
        c.advance(row, 1)
        after = set(np.flatnonzero(w.tables[row] != c.TRASH))
        freed += len(before - after)        # entries back on the trash block
        seen |= after
        length = int(c.lengths[row])
        lo = max(length - WINDOW + 1, 0) // BS
        assert int(w.lo[row]) == lo
        # the block the next row is written to is held, and never more
        # than the budget
        assert w.tables[row, length // BS] != c.TRASH
        assert len(after) <= BUDGET
        assert w.live_blocks == len(after)
    assert freed == w.freed_behind == (109 - WINDOW + 1) // BS - 4
    assert c.tables_version > version           # the steps re-send tables
    assert c.kind_stats() == {
        "kv_blocks_live_full": -(-110 // BS),
        "kv_blocks_live_window": w.live_blocks,
        "window_blocks_freed": freed}
    c.release_row(row)
    assert c.allocator.leaked() == 1 and w.reserved == 0
    assert c.kind_stats()["kv_blocks_live_window"] == 0


def test_freed_blocks_are_taken_again_by_other_requests():
    c = two_kind_cache()
    (w,) = c._windows
    a, _ = c.acquire([1] * 40, 100)
    c.commit_prefill(a, 40)
    for _ in range(40):
        c.advance(a, 1)
    gone = w.freed_behind
    assert gone > 0
    b, _ = c.acquire([2] * 30, 60)
    held_b = set(w.tables[b][w.tables[b] != c.TRASH])
    # the lowest free blocks first: the ones `a` returned
    assert min(held_b) < SLOTS * BUDGET
    assert not held_b & set(w.tables[a][w.tables[a] != c.TRASH])
    c.release_row(a)
    c.release_row(b)
    assert c.allocator.leaked() == 1


def test_admission_never_over_commits_either_kind():
    c = two_kind_cache(num_blocks=20)           # 19 usable full blocks
    (w,) = c._windows
    rows = []
    for _ in range(SLOTS):
        got = c.acquire([1] * 30, 48)           # 6 full blocks each
        if got is None:
            break
        rows.append(got[0])
    assert len(rows) == 3                       # the fourth: 24 > 19
    assert c.pool.allocator.num_used - 1 == 18
    assert w.reserved == 3 * BUDGET <= w.usable
    assert c.num_free == 1                      # its row stayed free
    assert w.pool.allocator.num_used - 1 == 3 * BUDGET
    # nothing of the refused request was kept, in either kind
    for row in rows:
        c.release_row(row)
    assert c.allocator.leaked() == 1
    assert c.allocator.num_free == c.allocator.num_blocks - 2
    with pytest.raises(ValueError, match="max_len"):
        c.acquire([1] * 10, 129)


def test_a_window_kind_refuses_what_it_cannot_lend():
    spec = ServedModel(
        model=None, family="toy", max_positions=64, vocab=8,
        cache_kinds=(CacheKind("full", (0,), 2, 4),
                     CacheKind("window", (1,), 2, 4, window=WINDOW)))
    with pytest.raises(ValueError, match="no prefix cache"):
        BlockKVCache.for_model(spec, 2, 64, block_size=BS, num_blocks=0,
                               prefix_cache=True, kv_dtype="f32")
    c = two_kind_cache()
    row, _ = c.acquire([1] * 10, 20)
    with pytest.raises(ValueError, match="not handed off"):
        c.export_row(row)
    backwards = ServedModel(
        model=None, family="toy", max_positions=64, vocab=8,
        cache_kinds=(CacheKind("window", (0,), 2, 4, window=WINDOW),))
    with pytest.raises(ValueError, match="first cache kind"):
        BlockKVCache.for_model(backwards, 2, 64, block_size=BS,
                               num_blocks=0, prefix_cache=False,
                               kv_dtype="f32")


# ------------------------------------------------------------- the seam

def test_gpt_declares_one_kind_and_every_feature(gpt):
    spec = served(gpt)
    assert spec.family == "gpt" and spec.features == FEATURES
    assert [(k.name, k.window, len(k.layers)) for k in spec.cache_kinds] \
        == [("full", 0, 2)]
    assert spec.kv_dtype is None and spec.counters == ()
    assert spec.prefill_rows(64, 8) == 8
    engine = ServingEngine(gpt, max_slots=2, max_len=64, buckets=[16],
                           block_size=8, num_blocks=0)
    assert isinstance(engine.cache.allocator, BlockAllocator)
    assert engine.cache._windows == [] and engine._counted is None
    assert "kv_blocks_live_window" not in engine.stats()
    assert engine.stats()["kv_blocks_live_full"] == 0


def test_the_engine_reads_the_model_through_the_seam_only():
    src = inspect.getsource(engine_mod)
    assert "model.gpt" not in src and "gen_block_pool" not in src
    # and adds no keyword for the second model
    params = inspect.signature(ServingEngine.__init__).parameters
    assert not any("window" in p or "kind" in p or "mellum" in p
                   for p in params)


def test_a_model_without_a_seam_is_refused_by_name():
    layers.seed(1)
    laguna = LagunaForCausalLM(LAGUNA_CONFIGS["laguna-tiny"])
    with pytest.raises(TypeError, match="serving_spec"):
        ServingEngine(laguna, max_slots=2, max_len=64)
    with pytest.raises(TypeError, match="LagunaForCausalLM"):
        served(laguna)


def test_the_second_models_engine_reports_both_kinds(mellum):
    engine = ServingEngine(mellum, max_slots=SLOTS, max_len=128,
                           buckets=[32, 64], block_size=BS, num_blocks=0,
                           prefix_cache=False, max_queue=16)
    assert engine.kv_dtype == "f32" and engine.spec.family == "mellum"
    (w,) = engine.cache._windows
    assert w.pool.num_blocks == SLOTS * BUDGET + 1
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(1, 512, n).tolist(),
                          max_new_tokens=new)
            for n, new in ((40, 50), (25, 70), (60, 30), (33, 40), (50, 20))]
    for _ in range(12):
        engine.step()
    mid = engine.stats()
    assert mid["active"] == SLOTS and mid["kv_blocks_live_full"] > 0
    assert 0 < mid["kv_blocks_live_window"] <= SLOTS * BUDGET
    assert engine.cache.num_free == 0
    # cancel one mid-decode and one still queued, finish the rest
    engine.cancel(reqs[1].id)
    engine.cancel(reqs[4].id)
    engine.run_until_idle()
    engine.cache.flush_prefix_cache()
    done = engine.stats()
    assert [r.state for r in reqs] == ["done", "canceled", "done", "done",
                                       "canceled"]
    assert engine.cache.allocator.leaked() == 1      # but for the trash
    assert done["kv_blocks_live_full"] == done["kv_blocks_live_window"] == 0
    assert done["window_blocks_freed"] > 0 and done["completed"] == 3
    assert done["kv_blocks_free"] == engine.cache.allocator.num_free
    assert engine.cache.num_free == SLOTS
    assert done["experts_touched"] > 0


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "speculative": dict(spec_tokens=2),
    "lora": dict(lora_rank=4),
    "mesh": "mesh",
    "host_tier": "host_tier",
    "int8_pool": dict(kv_dtype="int8"),
    "disaggregation": "kv_pool",
}


@pytest.mark.parametrize("family", ["mellum", "jamba"])
@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_the_second_model_does_not_get_is_refused_by_name(
        request, family, feature):
    """Mellum (a window layer forgets what a later request would borrow)
    and Jamba (a recurrence would need its state snapshotted, rolled back
    or carried) declare none of the optional features yet."""
    model = request.getfixturevalue(family)
    kw = REFUSED[feature]
    if kw == "mesh":
        from paddle_tpu.distributed.sharding import serving_mesh
        kw = dict(mesh=serving_mesh(1, 2))
    elif kw == "host_tier":
        from paddle_tpu.serving.kv_tier import HostBlockStore, TierManager
        kw = dict(kv_tier=TierManager(HostBlockStore(
            1, 2, 16, block_size=BS, num_blocks=4)))
    elif kw == "kv_pool":
        from paddle_tpu.serving.kv_cache import BlockPool
        kw = dict(kv_pool=BlockPool(1, 2, 16, block_size=BS, num_blocks=4))
    base = dict(max_slots=2, max_len=64, buckets=[32], block_size=BS,
                num_blocks=0, prefix_cache=False)
    with pytest.raises(ValueError) as e:
        ServingEngine(model, **dict(base, **kw))
    assert f"{family} is not served with {feature}" in str(e.value)
    assert feature in FEATURES
    # GPT keeps it: the same request builds (or fails on its own terms,
    # never on the seam)
    assert feature in served(GPTForCausalLM(GPT_CONFIGS["gpt2-tiny"])
                             ).features


def test_the_flags_default_prefix_cache_is_refused_too(mellum):
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingEngine(mellum, max_slots=2, max_len=64, buckets=[32],
                      block_size=BS, num_blocks=0)     # the flag says True
    with pytest.raises(ValueError, match="keeps its KV pools"):
        ServingEngine(mellum, max_slots=2, max_len=64, buckets=[32],
                      block_size=BS, num_blocks=0, prefix_cache=False,
                      kv_dtype="bf16")


@pytest.mark.parametrize("path", ["greedy_search", "sample", "beam_search",
                                  "router_lora", "router_host_tier",
                                  "disagg"])
def test_the_paths_that_are_gpts_refuse_another_model_by_name(mellum, path):
    """No path fails with an AttributeError on a model that is not GPT."""
    import paddle_tpu as pt
    from paddle_tpu.serving import DisaggRouter, ReplicaRouter
    ids = np.ones((1, 4), np.int32)
    if path in ("greedy_search", "sample", "beam_search"):
        with pytest.raises(TypeError, match="GPTForCausalLM only"):
            getattr(generation, path)(mellum, ids, max_new_tokens=2)
    elif path == "router_lora":
        with pytest.raises(ValueError, match="not served with lora"):
            ReplicaRouter(mellum, n_replicas=1, lora_rank=4,
                          prefix_cache=False)
    elif path == "router_host_tier":
        pt.set_flags({"serving_host_tier": True})
        try:
            with pytest.raises(ValueError, match="not served with host_tier"):
                ReplicaRouter(mellum, n_replicas=1, prefix_cache=False)
        finally:
            pt.set_flags({"serving_host_tier": False})
    else:
        with pytest.raises(ValueError,
                           match="not served with disaggregation"):
            DisaggRouter(mellum, n_prefill=1, n_decode=1)
