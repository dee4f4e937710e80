"""Serving plane: continuous batching == sequential decoding, with the
compile budget pinned.

The correctness contract is strong: N concurrent mixed-length requests
scheduled through ServingEngine (slots shared, prefills bucketed,
finished rows retired mid-batch) must produce token-for-token the ids
that N independent ``greedy_search`` calls produce — and do it with ONE
decode compile plus one prefill compile per length bucket, regardless
of how many requests flow through.
"""

import http.client
import json
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.models.generation import (beam_search, decode_step,
                                          decode_step_paged, draft_ngram,
                                          greedy_search, sample,
                                          verify_step_paged)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (BlockAllocator, BlockKVCache,
                                QueueFullError, ServingEngine,
                                ServingHTTPServer)


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


def test_engine_matches_sequential_greedy(model):
    """5 mixed-length requests through 2 slots (forcing slot reuse and
    mid-batch retirement) == 5 sequential greedy calls, exactly."""
    prompts = _prompts((3, 7, 5, 11, 4))
    eng = ServingEngine(model, max_slots=2, max_len=32,
                        buckets=[4, 8, 16], max_queue=16)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    # more requests than slots: every slot was reused
    assert len(prompts) > eng.max_slots
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=6,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref, f"request {r.id} diverged"


def test_decode_compiles_once_prefill_once_per_bucket(model):
    """The compile-reuse contract: across many requests of many lengths,
    decode traces exactly once and each prefill bucket exactly once
    (block remapping, prefix sharing and COW must never retrace)."""
    before = decode_step_paged(model)["traces"]["count"]
    eng = ServingEngine(model, max_slots=3, max_len=32,
                        buckets=[4, 8, 16], max_queue=32)
    for p in _prompts((2, 3, 4, 6, 7, 9, 13, 15), seed=1):
        eng.submit(p, max_new_tokens=4)
    eng.run_until_idle()
    assert decode_step_paged(model)["traces"]["count"] - before == 1
    used = {b: e["traces"]["count"] for b, e in eng._prefill_fns.items()}
    assert used == {4: 1, 8: 1, 16: 1}


def test_eos_stops_early_and_matches_greedy(model):
    prompts = _prompts((4, 6), seed=2)
    # pick an eos id that actually occurs: the 2nd generated token of
    # request 0 in an eos-free reference run
    ref0 = greedy_search(model, np.asarray([prompts[0]]),
                         max_new_tokens=8, cache_len=32)[0].tolist()
    eos = ref0[len(prompts[0]) + 1]
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8],
                        eos_token_id=eos)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=8,
                            eos_token_id=eos,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref
    # request 0 provably stopped at its eos, before the token budget
    assert reqs[0].tokens[-1] == eos
    assert len(reqs[0].tokens) < 8


def test_queue_full_rejection(model):
    """Admission control: submissions beyond FLAGS_serving_max_queue are
    shed with QueueFullError and counted, not silently queued."""
    monitor.reset()
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=2)
    eng.submit([1, 2], max_new_tokens=2)
    eng.submit([3, 4], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        eng.submit([5, 6], max_new_tokens=2)
    assert monitor.stat_get("STAT_serving_rejected") == 1
    eng.run_until_idle()   # the admitted two still complete
    assert monitor.stat_get("STAT_serving_completed") == 2


def test_submit_validates_geometry(model):
    eng = ServingEngine(model, max_slots=1, max_len=16, buckets=[8])
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=2)
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 15)), max_new_tokens=4)  # 14+4 > 16
    with pytest.raises(ValueError):
        ServingEngine(model, max_len=999)  # > max_position_embeddings


def test_background_thread_results(model):
    """start()/results(): the daemon scheduler drains submissions that
    arrive while it runs."""
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8])
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=3)
                for p in _prompts((3, 5, 4), seed=3)]
        done = eng.results(reqs, timeout=60)
    finally:
        eng.stop()
    assert [r.state for r in done] == ["done"] * 3
    assert all(len(r.tokens) == 3 for r in done)


def test_http_endpoint(model):
    """The JSON front door: generate == greedy, health/stats live, bad
    bodies 400."""
    prompt = _prompts((5,), seed=4)[0]
    ref = greedy_search(model, np.asarray([prompt]), max_new_tokens=4,
                        cache_len=32)[0].tolist()
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8])
    srv = ServingHTTPServer(eng, port=0)
    srv.start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        body = json.dumps({"ids": prompt, "max_new_tokens": 4})
        c.request("POST", "/v1/generate", body=body)
        r = c.getresponse()
        assert r.status == 200
        out = json.loads(r.read())
        assert out["output_ids"] == ref
        assert out["generated"] == 4
        c.request("GET", "/health")
        assert json.loads(c.getresponse().read())["ok"] is True
        c.request("GET", "/v1/stats")
        stats = json.loads(c.getresponse().read())
        assert stats["STAT_serving_completed"] >= 1
        c.request("POST", "/v1/generate", body=json.dumps({"ids": []}))
        assert c.getresponse().status == 400
        c.request("POST", "/v1/generate", body="not json")
        assert c.getresponse().status == 400
        c.close()
    finally:
        srv.stop()


# the three callers that keep ``decode_step`` (the oracle's compiled
# step) alive, each at a decode batch no other test of this file traces
@pytest.mark.parametrize("search,batch", [
    (greedy_search, 4),
    (lambda m, ids, **kw: sample(m, ids, temperature=0.8, top_k=5,
                                 seed=3, **kw), 5),
    (lambda m, ids, **kw: beam_search(m, ids, beam_size=3, **kw), 2),
], ids=["greedy_search", "sample", "beam_search"])
def test_greedy_search_single_compile(model, search, batch):
    """A decode of many steps on the fixed-capacity cache traces the
    step function exactly once per batch shape (a cache that grew with
    each token would recompile every step)."""
    before = decode_step(model)["traces"]["count"]
    ids = np.asarray(_prompts((5,) * batch, seed=5))
    search(model, ids, max_new_tokens=8)
    # same batch shape again: zero new traces
    search(model, ids + 1, max_new_tokens=8)
    assert decode_step(model)["traces"]["count"] - before == 1


def test_cache_without_cache_pos_names_the_two_modes(model):
    """A KV cache is a paged block pool (the engine's) or a
    fixed-capacity pair (the oracle's), both written at ``cache_pos``;
    a cache handed in without it is refused with both named."""
    from paddle_tpu.dygraph.tensor import Tensor
    ids = Tensor(np.asarray([[1, 2, 3]], np.int32), stop_gradient=True)
    with pytest.raises(ValueError,
                       match="gen_block_pool.*gen_fixed_cache"):
        model(ids, cache=model.gpt.gen_fixed_cache(1, 8))
    assert not hasattr(model.gpt, "gen_cache")


# -- speculative decoding ------------------------------------------------

def test_spec_engine_matches_nonspec_and_greedy(model):
    """The correctness oracle: with spec_tokens > 0, mixed-length
    concurrent requests through 2 slots (slot reuse + mid-batch
    retirement + rollback every verify) produce token-for-token the
    non-speculative engine's output, which itself equals sequential
    greedy."""
    # mix repetitive prompts (high acceptance) with random ones (low):
    # both acceptance regimes must stay exact
    prompts = _prompts((3, 7, 5, 11, 4), seed=6)
    prompts[1] = [5, 9, 5, 9, 5, 9, 5]
    prompts[3] = [2, 3, 4] * 3 + [2, 3]
    kw = dict(max_slots=2, max_len=32, buckets=[4, 8, 16], max_queue=16)
    spec = ServingEngine(model, spec_tokens=3, **kw)
    plain = ServingEngine(model, spec_tokens=0, **kw)
    sreqs = [spec.submit(p, max_new_tokens=6) for p in prompts]
    preqs = [plain.submit(p, max_new_tokens=6) for p in prompts]
    spec.run_until_idle()
    plain.run_until_idle()
    assert all(r.state == "done" for r in sreqs + preqs)
    assert len(prompts) > spec.max_slots   # every slot was reused
    for p, s, q in zip(prompts, sreqs, preqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=6,
                            cache_len=spec.max_len)[0].tolist()
        assert s.output_ids == q.output_ids == ref, \
            f"request {s.id} diverged under speculation"


def test_spec_verify_compiles_once(model):
    """Compile budget under speculation: verify traces exactly once
    for the engine's K, decode is never traced (the verify step IS the
    decode), and prefill still compiles once per bucket."""
    k = 4
    before_v = verify_step_paged(model, k)["traces"]["count"]
    before_d = decode_step_paged(model)["traces"]["count"]
    eng = ServingEngine(model, max_slots=3, max_len=32,
                        buckets=[4, 8, 16], max_queue=32, spec_tokens=k)
    for p in _prompts((2, 3, 4, 6, 7, 9, 13, 15), seed=7):
        eng.submit(p, max_new_tokens=4)
    eng.run_until_idle()
    assert verify_step_paged(model, k)["traces"]["count"] - before_v == 1
    assert decode_step_paged(model)["traces"]["count"] - before_d == 0
    used = {b: e["traces"]["count"] for b, e in eng._prefill_fns.items()}
    assert used == {4: 1, 8: 1, 16: 1}


def test_spec_eos_mid_verify_matches_greedy(model):
    """EOS discovered inside a verify window finishes the request
    mid-commit, exactly where sequential greedy stops."""
    prompts = _prompts((4, 6), seed=8)
    ref0 = greedy_search(model, np.asarray([prompts[0]]),
                         max_new_tokens=8, cache_len=32)[0].tolist()
    eos = ref0[len(prompts[0]) + 1]
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8],
                        eos_token_id=eos, spec_tokens=3)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=8,
                            eos_token_id=eos,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref
    assert reqs[0].tokens[-1] == eos and len(reqs[0].tokens) < 8


def test_spec_acceptance_stats(model):
    """Acceptance accounting: a strongly periodic prompt drives the
    n-gram drafter's acceptance rate up, and both the engine stats and
    the monitor counters see proposed/accepted."""
    monitor.reset()
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        spec_tokens=3)
    eng.submit([5, 9, 5, 9, 5, 9], max_new_tokens=8)
    eng.run_until_idle()
    st = eng.stats()
    assert st["spec_tokens"] == 3
    assert st["spec_proposed"] > 0
    assert 0 <= st["spec_accepted"] <= st["spec_proposed"]
    assert st["spec_acceptance_rate"] == pytest.approx(
        st["spec_accepted"] / st["spec_proposed"], abs=1e-3)
    assert monitor.stat_get("STAT_serving_spec_proposed") == \
        st["spec_proposed"]
    assert monitor.stat_get("STAT_serving_spec_accepted") == \
        st["spec_accepted"]
    # fewer verify steps than tokens generated = speculation paid off
    assert monitor.stat_get("STAT_serving_verify_calls") < \
        monitor.stat_get("STAT_serving_tokens")


def test_spec_headroom_validation(model):
    """Speculation reserves K rows of slot headroom at admission: a
    geometry that fits without speculation is rejected with it (the
    verify scatter-write must never clamp onto committed rows)."""
    plain = ServingEngine(model, max_slots=1, max_len=16, buckets=[8])
    plain.submit(list(range(1, 11)), max_new_tokens=6)   # 10+6 == 16 ok
    spec = ServingEngine(model, max_slots=1, max_len=16, buckets=[8],
                         spec_tokens=4)
    with pytest.raises(ValueError, match="spec_tokens"):
        spec.submit(list(range(1, 11)), max_new_tokens=6)  # 10+6+4 > 16
    spec.submit(list(range(1, 7)), max_new_tokens=6)       # 6+6+4 ok


def test_draft_ngram():
    """The self-drafter: longest-suffix match, most recent occurrence
    wins, short continuations cycle, no match repeats the last token."""
    assert draft_ngram([1, 2, 3, 1, 2], 2) == [3, 1]      # bigram match
    assert draft_ngram([4, 4, 4, 4], 3) == [4, 4, 4]      # periodic
    assert draft_ngram([1, 2, 3, 4], 2) == [4, 4]         # no match
    assert draft_ngram([7], 2) == [7, 7]                  # single token
    # most recent match preferred: ...2,9 (old) vs ...2,5 (recent)
    assert draft_ngram([2, 9, 8, 2, 5, 2], 1) == [5]


def test_slot_reuse_after_rollback_interleaved_retirement(model):
    """The bug class speculative rollback introduces: release -> alloc
    -> write must land at the NEW request's offsets, never a stale
    rolled-back offset. Interleave a long request with a short one so
    the slot retires mid-batch and is re-prefilled while its neighbor
    keeps verifying; outputs must still be exact."""
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[4, 8],
                        spec_tokens=3)
    long1 = eng.submit([3, 1, 4, 1, 5, 9, 2], max_new_tokens=9)
    short = eng.submit([2, 7], max_new_tokens=2)      # retires early
    eng.step()
    while short.state != "done":
        eng.step()
    reused = eng.submit([8, 2, 8, 2, 8], max_new_tokens=6)
    eng.run_until_idle()
    for r, p in ((long1, [3, 1, 4, 1, 5, 9, 2]), (short, [2, 7]),
                 (reused, [8, 2, 8, 2, 8])):
        ref = greedy_search(model, np.asarray([p]),
                            max_new_tokens=r.max_new_tokens,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref


# -- batched prefill admission -------------------------------------------

def test_prefill_batched_one_dispatch_per_bucket(model):
    """All queued same-bucket admissions in a step share ONE prefill
    dispatch (the compile-count contract already pins one trace per
    bucket; this pins the dispatch count too)."""
    monitor.reset()
    eng = ServingEngine(model, max_slots=3, max_len=32, buckets=[4, 8])
    prompts = _prompts((2, 3, 4), seed=9)      # all fit bucket 4
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.step()
    assert monitor.stat_get("STAT_serving_prefill_calls") == 1
    assert monitor.stat_get("STAT_serving_prefills") == 3
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=3,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref


# -- latency stats + HTTP surface ----------------------------------------

def test_ttft_tpot_stats(model):
    """TTFT / TPOT percentiles appear in engine.stats() once requests
    complete, and TTFT <= total latency."""
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8])
    reqs = [eng.submit(p, max_new_tokens=4)
            for p in _prompts((3, 5, 4), seed=10)]
    eng.run_until_idle()
    st = eng.stats()
    assert st["latency_samples"] == 3
    for key in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                "tpot_p99_ms"):
        assert st[key] is not None and st[key] >= 0
    for r in reqs:
        assert r.ttft is not None and r.tpot is not None
        assert r.ttft <= r.latency


def test_http_429_retry_after_and_stats_surface(model):
    """Queue-full over HTTP carries Retry-After; /v1/stats exposes the
    TTFT/TPOT percentile keys."""
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=0)   # every submission is shed
    srv = ServingHTTPServer(eng, port=0)
    srv.start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        c.request("POST", "/v1/generate",
                  body=json.dumps({"ids": [1, 2], "max_new_tokens": 2}))
        r = c.getresponse()
        assert r.status == 429
        assert int(r.getheader("Retry-After")) >= 1
        r.read()
        c.request("GET", "/v1/stats")
        stats = json.loads(c.getresponse().read())
        for key in ("ttft_p50_ms", "tpot_p99_ms", "latency_samples",
                    "spec_tokens"):
            assert key in stats
        c.close()
    finally:
        srv.stop()


# -- block-paged KV cache ------------------------------------------------

def test_block_allocator_exhaustion_and_reclaim():
    """Free-list exhaustion returns None; refcounts reclaim on the
    drop to zero; assignment order is deterministic (lowest id first)."""
    a = BlockAllocator(4)
    got = [a.alloc() for _ in range(4)]
    assert got == [0, 1, 2, 3]            # deterministic, sorted
    assert a.alloc() is None              # exhausted
    a.ref(2)                              # prefix-style second holder
    a.deref(2)
    assert a.num_free == 0                # still held once
    a.deref(2)
    assert a.num_free == 1 and a.alloc() == 2   # reclaimed, reused
    with pytest.raises(ValueError):
        a.deref(1) or a.deref(1) or a.deref(1)  # double-free guarded
    a2 = BlockAllocator(4)
    assert [a2.alloc() for _ in range(4)] == got   # replayed schedule


def test_block_kv_cache_acquire_release_accounting():
    """Row + block accounting round-trips: acquire reserves
    ceil(need/bs) blocks, release returns every one, nothing leaks
    but the trash block."""
    c = BlockKVCache(num_layers=1, num_heads=2, head_dim=4, max_slots=2,
                     max_len=16, block_size=4, prefix_cache=False)
    assert c.blocks_used == 1             # the trash block
    row, shared = c.acquire([1, 2, 3], need=9)   # 3 blocks
    assert shared == 0 and c.blocks_used == 4
    assert c.tables[row, :3].tolist() != [c.TRASH] * 3
    assert c.tables[row, 3] == c.TRASH    # unreserved tail stays trash
    c.release_row(row)
    assert c.blocks_used == 1 and c.allocator.leaked() == 1
    # all-or-nothing: a request too big for the remaining pool takes
    # nothing (2 rows x 4 blocks needs 8, pool has 8 free after trash)
    r1 = c.acquire(list(range(1, 14)), need=16)   # 4 blocks
    r2 = c.acquire(list(range(1, 14)), need=16)   # 4 more
    assert r1 and r2 and c.blocks_free == 0
    assert c.acquire([1], need=1) is None         # no row AND no block
    c.release_row(r1[0])
    assert c.blocks_free == 4                     # exact unwind


def test_block_kv_prefix_hit_and_cow():
    """A republished prompt is matched block-for-block; a prompt whose
    shared coverage ends mid-block privatizes the boundary block
    (copy-on-write) so the original's rows stay intact."""
    import jax.numpy as jnp
    c = BlockKVCache(num_layers=1, num_heads=1, head_dim=2, max_slots=2,
                     max_len=16, block_size=4)
    prompt = list(range(10, 19))               # 9 tokens: 2 full blocks
    row, shared = c.acquire(prompt, need=12)
    assert shared == 0
    # fake a prefill: mark valid rows, publish the full blocks
    k, v = c.arrays()[0]
    k = k.at[c.tables[row, 0]].set(1.0).at[c.tables[row, 1]].set(2.0)
    c.set_arrays([(k, v)])
    c.commit_prefill(row, len(prompt))
    c.insert_prefix(row, prompt)
    assert c.prefix_entries == 2
    # same prompt again: both full blocks reused, last token recomputed
    row2, shared2 = c.acquire(prompt, need=12)
    assert shared2 == 8
    assert c.tables[row2, :2].tolist() == c.tables[row, :2].tolist()
    assert c.prefix_hits == 8 and c.prefix_misses >= 9
    c.release_row(row2)
    # prompt sharing exactly 2 blocks then diverging BUT only 8 tokens
    # long: shared caps at len-1=7 -> boundary block 1 is partially
    # shared -> COW: row3 gets a PRIVATE copy of block 1's rows
    p3 = prompt[:8]
    row3, shared3 = c.acquire(p3, need=12)
    assert shared3 == 7
    assert c.tables[row3, 0] == c.tables[row, 0]       # full block shared
    assert c.tables[row3, 1] != c.tables[row, 1]       # boundary is COW
    k3 = c.arrays()[0][0]
    assert jnp.array_equal(k3[c.tables[row3, 1]], k3[c.tables[row, 1]])
    c.release_row(row)
    c.release_row(row3)


def test_block_kv_prefix_eviction_under_pressure():
    """Idle prefix entries are evicted LRU to satisfy new allocations;
    entries still referenced by a live row survive."""
    c = BlockKVCache(num_layers=1, num_heads=1, head_dim=2, max_slots=3,
                     max_len=16, block_size=4, num_blocks=4)
    pa = [1] * 4
    ra, _ = c.acquire(pa, need=8)          # 2 blocks
    c.commit_prefill(ra, 4)
    c.insert_prefix(ra, pa)                # 1 cached block
    c.release_row(ra)                      # now cache-only
    assert c.prefix_entries == 1 and c.blocks_free == 2
    rb, _ = c.acquire([2] * 6, need=12)    # needs 3 blocks: evicts a's
    assert rb is not None
    assert c.prefix_entries == 0 and c.blocks_free == 0
    c.release_row(rb)
    assert c.allocator.leaked() == 1       # only the trash block


def test_block_kv_rollback_across_block_boundary():
    """Speculative rollback that crosses a block boundary is pure
    length arithmetic: blocks stay reserved, re-advance reuses them."""
    c = BlockKVCache(num_layers=1, num_heads=2, head_dim=4, max_slots=1,
                     max_len=16, block_size=4, prefix_cache=False)
    row, _ = c.acquire([1, 2, 3], need=12)
    c.commit_prefill(row, 3)
    c.advance(row, 4)                      # verify commit: 3 -> 7
    assert c.lengths[row] == 7             # spans blocks 0 and 1
    used = c.blocks_used
    c.rollback(row, 3)                     # back to 4: crosses boundary
    assert c.lengths[row] == 4 and c.blocks_used == used
    c.advance(row, 8)                      # 4 -> 12: fills reservation
    with pytest.raises(ValueError):
        c.advance(row, 1)                  # beyond reserved blocks
    with pytest.raises(ValueError):
        c.rollback(row, 13)


def test_block_assignment_deterministic_replay():
    """The same submit/retire schedule maps requests to identical
    physical blocks on replay — the equivalence tests and the chaos
    suite's seeded specs rely on this."""
    def run():
        c = BlockKVCache(num_layers=1, num_heads=1, head_dim=2,
                         max_slots=2, max_len=16, block_size=4)
        log = []
        r1, _ = c.acquire([1, 2, 3, 4, 5], need=8)
        r2, _ = c.acquire([9, 8, 7], need=12)
        log.append(c.tables.copy())
        c.release_row(r1)
        r3, _ = c.acquire([5, 5], need=8)
        log.append(c.tables.copy())
        return log
    a, b = run(), run()
    for ta, tb in zip(a, b):
        assert np.array_equal(ta, tb)


def test_paged_engine_matches_greedy_without_prefix_cache(model):
    """The paged oracle holds with prefix caching disabled (every
    prompt prefills from scratch through the block tables)."""
    prompts = _prompts((3, 7, 5, 11, 4), seed=11)
    eng = ServingEngine(model, max_slots=2, max_len=32,
                        buckets=[4, 8, 16], block_size=4,
                        prefix_cache=False)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    assert eng.cache.prefix_hits == 0
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=6,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref


def test_paged_prefix_reuse_is_exact_and_counted(model):
    """A shared system prompt prefills once; later requests reference
    its blocks and still match sequential greedy token for token, and
    the hit shows up in stats() + STAT_serving_prefix_hits."""
    monitor.reset()
    system = _prompts((12,), seed=13)[0]       # 3 full blocks at bs=4
    tails = _prompts((3, 5, 2), seed=14)
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8, 16],
                        block_size=4)
    r0 = eng.submit(system, max_new_tokens=4)
    eng.run_until_idle()                       # publishes the prefix
    reqs = [eng.submit(system + t, max_new_tokens=4) for t in tails]
    eng.run_until_idle()
    st = eng.stats()
    assert st["prefix_hit_requests"] == 3
    assert st["prefix_hit_tokens"] >= 3 * 8    # >=2 full blocks each
    assert monitor.stat_get("STAT_serving_prefix_hits") == 3
    for p, r in zip([system] + [system + t for t in tails],
                    [r0] + reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=4,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref, "prefix reuse changed tokens"


def test_paged_pool_exhaustion_blocks_head_of_line_then_completes(model):
    """An undersized block pool stalls admission head-of-line (FIFO
    preserved) until retirements free blocks; every request still
    completes and matches greedy."""
    prompts = _prompts((6, 6, 6, 6), seed=15)
    # each request needs ceil((6+4)/4)=3 blocks; pool of 7 usable
    # blocks fits two in flight, so admission must wait for releases
    eng = ServingEngine(model, max_slots=4, max_len=32, buckets=[8],
                        block_size=4, num_blocks=8,
                        prefix_cache=False)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=4,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref
    # drained: only the trash block may stay referenced
    assert eng.cache.allocator.leaked() == 1
    # a request that can NEVER fit the pool is a geometry error
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 26)), max_new_tokens=4)  # 8 blocks > 7


def test_paged_spec_rollback_across_block_boundary_matches_greedy(model):
    """Speculation with K+1 spanning block boundaries: rejected draft
    rows land in a later block and must be invisible after rollback."""
    # repetitive prompts -> high acceptance -> commits cross the bs=2
    # boundary every verify; mixed with a random prompt for rejections
    prompts = [[5, 9] * 4, _prompts((7,), seed=16)[0], [3, 3, 3, 3]]
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8, 16],
                        block_size=2, spec_tokens=3)
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=9,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref, "spec rollback corrupted a block"
    assert eng.stats()["spec_accepted"] > 0   # boundary was exercised


def test_paged_health_and_stats_surface(model):
    """GET /health exposes block headroom; stats() carries the paged
    block/prefix keys."""
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8],
                        block_size=4)
    srv = ServingHTTPServer(eng, port=0)
    srv.start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        c.request("GET", "/health")
        h = json.loads(c.getresponse().read())
        assert h["kv_blocks_free"] + h["kv_blocks_used"] == \
            eng.cache.num_blocks
        c.request("GET", "/v1/stats")
        st = json.loads(c.getresponse().read())
        for key in ("kv_blocks_used", "kv_blocks_free", "block_size",
                    "prefix_hit_rate"):
            assert key in st
        c.close()
    finally:
        srv.stop()


def test_engine_has_one_kv_manager(model):
    """There is no switch between KV managers: the constructor takes no
    ``paged``, the flag plane knows no flag of that name, and an engine
    built with no option set runs on the block pool and reports it."""
    import inspect
    from paddle_tpu import flags
    gone = "paged"
    assert gone not in inspect.signature(
        ServingEngine.__init__).parameters
    with pytest.raises(TypeError):
        ServingEngine(model, **{gone: False})
    assert f"serving_{gone}" not in flags.list_flags()
    with pytest.raises(ValueError, match="unknown flag"):
        flags.set_flags({f"serving_{gone}": False})
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8])
    assert isinstance(eng.cache, BlockKVCache)
    assert not hasattr(eng, gone)
    st = eng.stats()
    assert gone not in st
    assert st["kv_blocks_free"] + st["kv_blocks_used"] == st["num_blocks"]
    assert st["pool_dispatches"] == 0 and st["prefix_hit_rate"] is None


# -- cancellation over HTTP ----------------------------------------------

def test_http_delete_cancels_and_status_combos(model):
    """DELETE /v1/requests/<id> is the cancel front door: 200 with the
    reclaimed stage for an in-flight request, 400 for a non-integer
    id, 404 for unknown ids, finished requests and foreign paths —
    cancel-after-done is a no-op, never a double release."""
    prompt = _prompts((4,), seed=9)[0]
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=8, block_size=4)
    srv = ServingHTTPServer(eng, port=0)
    srv.start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        # an in-flight victim: submitted straight to the engine so the
        # HTTP DELETE races a real scheduler thread. The scheduler is
        # held to 20 ms a step while it does: on an idle machine it
        # otherwise finishes all 24 tokens before the handler thread
        # gets the GIL, and the DELETE finds a finished request (404)
        fast_step = eng.step
        eng.step = lambda: (time.sleep(0.02), fast_step())[1]
        victim = eng.submit(prompt, max_new_tokens=24)
        c.request("DELETE", f"/v1/requests/{victim.id}")
        r = c.getresponse()
        eng.step = fast_step
        assert r.status == 200
        out = json.loads(r.read())
        assert out["id"] == victim.id and out["reason"] == "client"
        assert out["stage"] in ("queued", "prefill", "decode")
        assert victim.wait(30)
        assert victim.state == "canceled"
        assert victim.shed_reason == "client"
        # double-cancel over HTTP: the request is already terminal
        c.request("DELETE", f"/v1/requests/{victim.id}")
        assert c.getresponse().status == 404
        c.request("DELETE", "/v1/requests/abc")
        assert c.getresponse().status == 400
        c.request("DELETE", "/v1/requests/999999")
        assert c.getresponse().status == 404
        c.request("DELETE", "/v1/other/1")
        assert c.getresponse().status == 404
        # a completed request: DELETE afterwards is 404, not a release
        body = json.dumps({"ids": prompt, "max_new_tokens": 2})
        c.request("POST", "/v1/generate", body=body)
        done = json.loads(c.getresponse().read())
        assert done["state"] == "done"
        c.request("DELETE", f"/v1/requests/{done['id']}")
        assert c.getresponse().status == 404
        c.close()
    finally:
        srv.stop()
    assert eng.stats()["canceled"] == {"client": 1}
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1     # trash block only


def test_http_broken_pipe_cancels_inflight_request(model):
    """A client that hangs up before its result lands must not leak
    the request: the response writer turns BrokenPipeError into
    cancel(reason="disconnect"), reclaiming queue slot / KV row."""
    import types

    from paddle_tpu.serving.http import _ServingHandler

    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=8, block_size=4)
    req = eng.submit(_prompts((4,), seed=10)[0], max_new_tokens=4)

    h = _ServingHandler.__new__(_ServingHandler)
    h.server = types.SimpleNamespace(engine=eng)
    h._json = lambda code, payload, headers=None: (
        (_ for _ in ()).throw(BrokenPipeError()))
    _ServingHandler._json_or_cancel(h, 200, {"id": req.id}, req.id)
    assert req.state == "canceled" and req.shed_reason == "disconnect"
    assert eng.stats()["canceled"] == {"disconnect": 1}
    # finished request: the hang-up cancel is a no-op, not a release
    h2 = _ServingHandler.__new__(_ServingHandler)
    h2.server = types.SimpleNamespace(engine=eng)
    h2._json = h._json
    _ServingHandler._json_or_cancel(h2, 200, {}, req.id)
    assert eng.stats()["canceled"] == {"disconnect": 1}


# ------------------------------------------------- the pools' ownership
@pytest.mark.parametrize("mode", [
    {}, {"spec_tokens": 2}, {"kv_dtype": "int8"}])
def test_every_paged_dispatch_consumes_its_pools(model, mode):
    """The paged entries own the pools they are handed: after a step
    the arrays the cache held before it are deleted (their rows were
    written in place), the cache holds their successors, and
    ``STAT_serving_pool_inplace`` counts every dispatch — prefill,
    decode and verify alike."""
    monitor.reset()
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8, 16],
                        max_queue=16, block_size=4, **mode)
    reqs = [eng.submit(p, max_new_tokens=9)
            for p in _prompts((3, 6, 11), seed=21)]
    steps = quiet = 0
    while not eng.idle:
        # the K and V pools (an int8 pool's scales may be replaced by
        # the allocator before the step's first dispatch sees them)
        before = [a for layer in eng.cache.arrays() for a in layer[:2]]
        assert not any(a.is_deleted() for a in before)
        dispatched = eng.stats()["pool_dispatches"]
        eng.step()
        steps += 1
        if eng.stats()["pool_dispatches"] == dispatched:
            # the round only committed the step that was in flight (the
            # single step is dispatched one ahead of its fetch)
            assert not any(a.is_deleted() for a in before), steps
            quiet += 1
            continue
        assert all(a.is_deleted() for a in before), steps
    assert all(r.state == "done" for r in reqs)
    st = eng.stats()
    assert st["pool_dispatches"] >= steps - quiet
    assert quiet <= len(reqs)    # at most the last round of a request
    assert st["pool_inplace"] == st["pool_dispatches"]
    assert st["pool_inplace_share"] == 1.0
    assert monitor.stat_get("STAT_serving_pool_inplace") == \
        st["pool_dispatches"]
    # one dispatch for each timed call
    assert st["pool_dispatches"] == sum(
        monitor.stat_get(f"STAT_serving_{k}_calls")
        for k in ("prefill", "decode", "verify"))
    for p, r in zip(_prompts((3, 6, 11), seed=21), reqs):
        if mode.get("kv_dtype") == "int8":
            continue        # int8 KV is not token-identical to greedy
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=9,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref


@pytest.mark.parametrize("where,err", [("decode", RuntimeError),
                                       ("decode", OSError),
                                       ("prefill", RuntimeError)])
def test_a_step_that_fails_after_consuming_the_pools_keeps_serving(
        model, monkeypatch, where, err):
    """An entry that raises once its pools are gone has taken every
    row's KV with it. The engine sheds what was running, rebuilds
    zeroed pools with the prefix cache flushed, and serves the next
    request token-identically; no later step meets a deleted array and
    no block leaks."""
    monitor.reset()
    eng = ServingEngine(model, max_slots=2, max_len=32, buckets=[8],
                        max_queue=16, block_size=4)
    prompts = _prompts((5, 7, 6), seed=22)
    first = eng.submit(prompts[0], max_new_tokens=8)
    eng.step()                      # prefill + one decode, both sound
    assert first.state == "running"

    ent = (decode_step_paged(model) if where == "decode"
           else eng._prefill_entry(8))
    real = ent["fn"]

    def consume_then_raise(*args):
        real(*args)
        raise err("device fault after the pools were donated")

    monkeypatch.setitem(ent, "fn", consume_then_raise)
    second = eng.submit(prompts[1], max_new_tokens=8)
    # (the round that admits the second dispatches the step behind its
    # prefill: the failing decode is met in that round, PR 48)
    eng.step()
    monkeypatch.undo()

    assert first.state == "shed"
    assert monitor.stat_get("STAT_serving_pool_rebuilds") == 1
    leaves = [a for layer in eng.cache.arrays() for a in layer]
    assert not any(a.is_deleted() for a in leaves)
    assert eng.cache.prefix_entries == 0
    assert eng.cache.num_used == 0

    third = eng.submit(prompts[2], max_new_tokens=8)
    eng.run_until_idle()
    assert third.state == "done"
    ref = greedy_search(model, np.asarray([prompts[2]]), max_new_tokens=8,
                        cache_len=eng.max_len)[0].tolist()
    assert third.output_ids == ref
    # admitted before the failing decode, the second went with the rest
    assert second.state == "shed"
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1     # trash block only
