"""Device-resident decode megasteps (FLAGS_serving_megastep) + async
fleet dispatch.

The contracts under test:

- **token identity**: a megastep=N engine commits token-for-token what
  the megastep=1 engine (and the ``greedy_search`` oracle) commits —
  across greedy and seeded sampling, f32 and int8 KV pools, prefix
  cache on/off, stop sequences that fire mid-megastep, and through
  ReplicaRouter / DisaggRouter fleets with threaded dispatch;
- **the stop automaton is exact**: the incremental host KMP matcher
  (``StopMatcher``) equals the naive full-suffix rescan on random
  streams, and its device mirror (``stops_advance`` over the fixed
  stop tables) tracks the host states token for token — which is why
  host and compiled matching can never disagree;
- **compile plane**: under megastep=N the decode plane has exactly TWO
  surfaces (``decode_megastep_paged{n=N}`` + the single-token
  fallback) and the live engine's per-phase compile delta equals
  ``predict_serving_compiles(megastep=N)``; requests whose stops
  exceed the device-table caps fall back to N=1 without ever tracing
  the megastep entry;
- **telemetry stays honest**: TPOT EWMA is per *token committed* (not
  per dispatch), TTFT still comes from prefill and the blame
  accounting identity holds exactly under megastep > 1, and the
  fleet's decode blame share strictly drops vs the same workload at
  N=1 (the whole point of the feature);
- **no resource regressions**: zero leaked KV blocks / LoRA pages,
  and the lock sanitizer sees no cycles or guarded-state violations
  under a threaded router driving megastep engines.
"""

import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, observability
from paddle_tpu.analysis import concurrency as ccz
from paddle_tpu.analysis import predict_serving_compiles
from paddle_tpu.models.generation import (decode_megastep_paged,
                                          decode_step_paged,
                                          greedy_search)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import tracing
from paddle_tpu.serving import (DisaggRouter, ReplicaRouter, ServingEngine,
                                make_adapter)
from paddle_tpu.serving.decoding import (STOP_MAX_LEN, STOP_MAX_SEQS,
                                         StopMatcher, stop_table_rows,
                                         stops_advance, stops_fit,
                                         stops_matched)

VOCAB = 97


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = GPTConfig(vocab_size=VOCAB, max_position_embeddings=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    ffn_hidden_size=64)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, size=n).tolist() for n in sizes]


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("buckets", [8, 16])
    kw.setdefault("max_queue", 16)
    kw.setdefault("block_size", 4)
    return ServingEngine(model, **kw)


def _run(target, prompts, mnt=6, **kw):
    reqs = [target.submit(p, max_new_tokens=mnt, **kw) for p in prompts]
    target.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    return reqs


def _assert_no_leaks(target):
    """Every paged engine behind ``target`` holds only its trash block
    once the prefix cache is flushed (the loadgen zero-leak check)."""
    engs = getattr(target, "engines", None) or [target]
    seen = set()
    for eng in engs:
        alloc = eng.cache.allocator
        if id(alloc) in seen:
            continue
        seen.add(id(alloc))
        eng.cache.flush_prefix_cache()
        assert alloc.leaked() <= 1, alloc.leaked()


class TickClock:
    """A deterministic engine clock: every read advances 1 ms, so any
    'time spent' measure is exactly a count of host-side clock reads —
    which is precisely the per-token host work megasteps remove."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


@pytest.fixture
def sanitize():
    old = flags.get_flag("sanitize_locks")
    flags.set_flags({"sanitize_locks": True})
    ccz.reset()
    try:
        yield ccz
    finally:
        flags.set_flags({"sanitize_locks": old})
        ccz.reset()


# ------------------------------------------------------ token identity
def test_megastep_matches_sequential_greedy(model):
    """Mixed lengths through 2 slots at megastep=4 (slot reuse and
    mid-batch retirement inside the scan) == sequential greedy."""
    prompts = _prompts((3, 7, 5, 11, 4), seed=1)
    eng = _engine(model, megastep=4)
    reqs = _run(eng, prompts)
    for p, r in zip(prompts, reqs):
        ref = greedy_search(model, np.asarray([p]), max_new_tokens=6,
                            cache_len=eng.max_len)[0].tolist()
        assert r.output_ids == ref, f"request {r.id} diverged"
    _assert_no_leaks(eng)


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("prefix_cache", [True, False])
def test_megastep_identity_matrix(model, kv_dtype, prefix_cache):
    """megastep=4 == megastep=1 token-for-token across the KV pool
    dtype x prefix-cache matrix, including a cross-round repeat of the
    same prompt (published prefix blocks feeding a megastep)."""
    prompts = _prompts((5, 9, 5), seed=2)
    outs = []
    for n in (1, 4):
        eng = _engine(model, megastep=n, kv_dtype=kv_dtype,
                      prefix_cache=prefix_cache)
        first = _run(eng, prompts)
        again = _run(eng, [prompts[0]])      # round 2: prefix hit
        outs.append([r.output_ids for r in first + again])
        _assert_no_leaks(eng)
    assert outs[0] == outs[1]


def test_megastep_identity_sampled(model):
    """Seeded sampling is megastep-invariant: the per-token RNG keys
    ride the scan as data, so N=4 draws the same tokens N=1 draws."""
    prompts = _prompts((4, 6, 5), seed=3)
    kw = dict(temperature=0.8, top_k=8, top_p=0.95, seed=21)
    a = _run(_engine(model, megastep=1), prompts, **kw)
    b = _run(_engine(model, megastep=4), prompts, **kw)
    assert [r.output_ids for r in a] == [r.output_ids for r in b]
    # and a different seed actually changes the stream (the invariance
    # above is not vacuous greediness)
    c = _run(_engine(model, megastep=4), prompts,
             **{**kw, "seed": 22})
    assert [r.output_ids for r in b] != [r.output_ids for r in c]


def test_megastep_stop_fires_mid_megastep(model):
    """A device-table stop that matches at iteration 3 of an 8-wide
    megastep freezes the slot inside the scan: output truncates at the
    match exactly like megastep=1's host-side check."""
    [prompt] = _prompts((5,), seed=4)
    [full] = _run(_engine(model, megastep=1), [prompt], mnt=12)
    gen = full.output_ids[len(prompt):]
    assert len(gen) >= 5
    stop = gen[2:4]                     # fits the device tables
    assert stops_fit([stop])
    # the exact truncation point, from the matcher itself (a repeating
    # stream can satisfy the stop before the slice it was cut from)
    m = StopMatcher([stop])
    cut = next(i + 1 for i, t in enumerate(gen) if m.feed(t))
    assert cut < len(gen)               # fires strictly mid-stream
    r1 = _run(_engine(model, megastep=1), [prompt], mnt=12,
              stop=[stop])[0]
    r8 = _run(_engine(model, megastep=8), [prompt], mnt=12,
              stop=[stop])[0]
    assert r8.tokens == r1.tokens == gen[:cut]


def test_oversized_stops_fall_back_without_megastep_trace(model):
    """Stops beyond the device-table caps (too many patterns, or one
    too long) make the whole batch ineligible: the engine decodes at
    N=1, tokens unchanged, and the megastep entry never traces."""
    prompts = _prompts((5, 7), seed=5)
    many = [[90 + j] for j in range(STOP_MAX_SEQS + 1)]
    long = [list(range(1, STOP_MAX_LEN + 2))]
    for bad in (many, long):
        assert not stops_fit(bad)
        eng = _engine(model, megastep=4)
        before = decode_megastep_paged(model, 4)["traces"]["count"]
        reqs = _run(eng, prompts, stop=bad)
        assert decode_megastep_paged(model, 4)["traces"]["count"] == \
            before
        ref = _run(_engine(model, megastep=1), prompts, stop=bad)
        assert [r.output_ids for r in reqs] == \
            [r.output_ids for r in ref]


# ------------------------------------------------- the stop automaton
def test_stop_matcher_equals_naive_rescan():
    """Property: the incremental KMP matcher agrees with the O(len^2)
    full-suffix rescan at every step of random streams."""
    rng = np.random.RandomState(11)
    for trial in range(20):
        k = rng.randint(1, STOP_MAX_SEQS + 1)
        pats = [rng.randint(0, 4, size=rng.randint(1, 5)).tolist()
                for _ in range(k)]
        m = StopMatcher(pats)
        hist = []
        for tok in rng.randint(0, 4, size=40):
            hist.append(int(tok))
            got = m.feed(tok)
            naive = any(len(h := hist) >= len(p) and
                        h[-len(p):] == list(p) for p in pats)
            # hit latches; the naive check is per-position
            if naive:
                assert got, (pats, hist)
            if not m.hit:
                assert not naive, (pats, hist)


def test_stop_tables_device_mirror_matches_host():
    """stops_advance over the packed tables tracks StopMatcher state
    for state, and stops_matched fires exactly when .hit latches."""
    pats_a = [[3, 1, 3], [2, 2]]
    pats_b = [[1]]
    ma, mb = StopMatcher(pats_a), StopMatcher(pats_b)
    rows = [stop_table_rows(ma), stop_table_rows(mb)]
    pat = np.stack([r[0] for r in rows])
    plen = np.stack([r[1] for r in rows])
    fail = np.stack([r[2] for r in rows])
    state = np.stack([r[3] for r in rows])
    stream_a = [3, 1, 2, 3, 1, 3, 0]
    stream_b = [0, 2, 3, 0, 0, 1, 0]
    for ta, tb in zip(stream_a, stream_b):
        state = np.asarray(stops_advance(
            np.asarray([ta, tb], np.int32), pat, plen, fail, state))
        ha, hb = ma.hit, mb.hit
        ma.feed(ta), mb.feed(tb)
        dev = np.asarray(stops_matched(state, plen))
        # the device scan freezes a slot at the match; before the
        # first hit, states agree exactly
        if not ha:
            assert state[0].tolist()[:len(pats_a)] == \
                ma.states or bool(dev[0]) == ma.hit
            assert bool(dev[0]) == ma.hit
        if not hb:
            assert bool(dev[1]) == mb.hit
    assert ma.hit and mb.hit


def test_stop_table_caps_validated():
    assert stops_fit([[1] * STOP_MAX_LEN] * STOP_MAX_SEQS)
    assert not stops_fit([[1]] * (STOP_MAX_SEQS + 1))
    assert not stops_fit([[1] * (STOP_MAX_LEN + 1)])
    with pytest.raises(ValueError, match="stops_fit"):
        stop_table_rows(StopMatcher([[1] * (STOP_MAX_LEN + 1)]))
    # inert tables for an empty slot: nothing can ever match
    pat, plen, fail, state = stop_table_rows(None)
    assert plen.sum() == 0 and not np.asarray(
        stops_matched(state[None], plen[None]))[0]


# ----------------------------------------------------- compile plane
def test_megastep_zero_new_compiles_predicted_vs_observed():
    """Predicted == observed for a megastep=8 workload that exercises
    both decode surfaces (one request's stops force the N=1 fallback)
    — the in-process version of the obs_smoke CI gate."""
    pt.seed(13)
    cfg = GPTConfig(vocab_size=53, max_position_embeddings=64,
                    hidden_size=16, num_layers=1, num_heads=2,
                    ffn_hidden_size=32)
    m = GPTForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 53, size=n).tolist() for n in (3, 6)]
    big = [[40 + j] for j in range(STOP_MAX_SEQS + 1)]
    before = {s: c["count"] for s, c in observability.compiles().items()
              if s.startswith(("serving_", "decode_", "verify_"))}
    eng = ServingEngine(m, max_slots=2, max_len=24, buckets=[8],
                        block_size=4, megastep=8)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    reqs.append(eng.submit(prompts[0], max_new_tokens=10, stop=big))
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    after = {s: c["count"] for s, c in observability.compiles().items()
             if s.startswith(("serving_", "decode_", "verify_"))}
    observed = {s: n - before.get(s, 0) for s, n in after.items()
                if n - before.get(s, 0)}
    predicted = predict_serving_compiles(
        [[(p, 10) for p in prompts] + [(prompts[0], 10)]],
        buckets=[8], max_len=24, block_size=4, megastep=8)
    assert observed == predicted, (predicted, observed)
    assert f"decode_megastep_paged{{n=8}}" in predicted
    _assert_no_leaks(eng)


def test_megastep_validation_errors(model):
    with pytest.raises(ValueError, match="megastep"):
        _engine(model, megastep=0)
    with pytest.raises(ValueError, match="speculative"):
        _engine(model, megastep=4, spec_tokens=2)
    with pytest.raises(ValueError, match="dispatch_ahead"):
        _engine(model, megastep=1, dispatch_ahead=True)
    # the predictor rejects exactly what the engine rejects
    wl = [[(list(range(1, 6)), 4)]]
    with pytest.raises(ValueError, match="megastep"):
        predict_serving_compiles(wl, buckets=[8], max_len=32,
                                 megastep=0)
    with pytest.raises(ValueError, match="spec_tokens"):
        predict_serving_compiles(wl, buckets=[8], max_len=32,
                                 spec_tokens=2, megastep=4)


# ------------------------------------------------ async fleet dispatch
def test_dispatch_ahead_hits_and_identity(model):
    """Megastep k+1 enqueued against k's un-synced carries validates
    (ahead_hits) on a steady decode batch, with tokens untouched."""
    prompts = _prompts((4, 6), seed=6)
    eng = _engine(model, megastep=4, dispatch_ahead=True)
    reqs = _run(eng, prompts, mnt=20)
    st = eng.stats()
    assert st["megastep"] == 4 and st["dispatch_ahead"]
    assert st["ahead_hits"] >= 1, st
    ref = _run(_engine(model, megastep=1), prompts, mnt=20)
    assert [r.output_ids for r in reqs] == [r.output_ids for r in ref]
    _assert_no_leaks(eng)


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("every", [1, 2])
def test_forced_dispatch_ahead_misses_stay_token_identical(model, every,
                                                           kv_dtype):
    """A discarded speculation has already consumed step k's pools, so
    the cache holds the pools *it* returned: its rows sit at or beyond
    every slot's committed length and are written again before anything
    reads them. With the speculation spoiled after every step (or every
    other one), on top of the misses that finishes and admissions cause
    by themselves, the tokens are those of the single-step engine."""
    prompts = _prompts((4, 6, 9, 5, 7), seed=16)
    eng = _engine(model, megastep=4, dispatch_ahead=True,
                  kv_dtype=kv_dtype)
    reqs = [eng.submit(p, max_new_tokens=22) for p in prompts]
    steps = 0
    while not eng.idle:
        eng.step()
        steps += 1
        assert not eng.cache.arrays()[0][0].is_deleted()
        if eng._ahead is not None and steps % every == 0:
            eng._ahead["n"] = -1          # _take_ahead will refuse it
    assert all(r.state == "done" for r in reqs)
    st = eng.stats()
    assert st["ahead_misses"] >= 3, st
    if every == 2:
        assert st["ahead_hits"] >= 1, st
    assert st["pool_inplace_share"] == 1.0, st
    ref = _run(_engine(model, megastep=1, kv_dtype=kv_dtype), prompts,
               mnt=22)
    assert [r.output_ids for r in reqs] == [r.output_ids for r in ref]
    _assert_no_leaks(eng)


def test_threaded_replica_router_megastep_identity(model):
    """2 replicas stepped from a bounded worker pool, each running
    megastep=4 decodes == the greedy oracle per request; no kills, no
    leaked blocks."""
    prompts = _prompts((3, 7, 5, 9, 4, 6), seed=7)
    rt = ReplicaRouter(model, n_replicas=2, dispatch_threads=2,
                       max_slots=2, max_len=32, buckets=[8, 16],
                       max_queue=32, block_size=4, megastep=4)
    try:
        reqs = _run(rt, prompts)
        for p, r in zip(prompts, reqs):
            ref = greedy_search(model, np.asarray([p]),
                                max_new_tokens=6,
                                cache_len=32)[0].tolist()
            assert r.output_ids == ref, f"request {r.id} diverged"
        st = rt.stats()
        assert st.get("replica_kills", 0) == 0, st
        _assert_no_leaks(rt)
    finally:
        rt.stop()


def test_threaded_disagg_router_megastep_identity(model):
    """Prefill/decode role split with threaded dispatch + megastep
    decode workers == the greedy oracle per request."""
    prompts = _prompts((3, 7, 5, 9), seed=8)
    rt = DisaggRouter(model, n_prefill=1, n_decode=1,
                      dispatch_threads=2, max_slots=2, max_len=32,
                      buckets=[8, 16], max_queue=32, block_size=4,
                      megastep=4)
    try:
        reqs = _run(rt, prompts)
        for p, r in zip(prompts, reqs):
            ref = greedy_search(model, np.asarray([p]),
                                max_new_tokens=6,
                                cache_len=32)[0].tolist()
            assert r.output_ids == ref, f"request {r.id} diverged"
        _assert_no_leaks(rt)
    finally:
        rt.stop()


def test_sanitizer_clean_under_threaded_megastep_router(model, sanitize):
    """The trace lock / step lock / router locks hold their declared
    order under concurrent replica stepping: no lock-graph cycles, no
    guarded-state violations."""
    prompts = _prompts((3, 5, 4, 6), seed=9)
    rt = ReplicaRouter(model, n_replicas=2, dispatch_threads=2,
                       max_slots=2, max_len=32, buckets=[8, 16],
                       max_queue=32, block_size=4, megastep=4)
    try:
        _run(rt, prompts)
    finally:
        rt.stop()
    assert sanitize.cycles() == [], sanitize.cycles()
    assert sanitize.violations() == [], sanitize.violations()


def test_lora_tenant_megastep_identity_and_zero_page_leaks(model):
    """Per-tenant adapter gathers ride the scan: megastep=4 tenant
    traffic == megastep=1, and the adapter pool leaks no pages."""
    cfg = model.gpt.cfg
    prompts = _prompts((4, 6), seed=10)
    outs = []
    for n in (1, 4):
        eng = _engine(model, megastep=n, lora_rank=2,
                      lora_max_adapters=2)
        eng.load_adapter("acme", make_adapter(cfg, 2, seed=1,
                                              scale=0.5))
        reqs = _run(eng, prompts, tenant="acme")
        outs.append([r.output_ids for r in reqs])
        assert eng.lora_pool.leaked() == 0
        _assert_no_leaks(eng)
    assert outs[0] == outs[1]


# -------------------------------------------------- telemetry honesty
def test_tpot_is_per_token_not_per_dispatch(model, monkeypatch):
    """TPOT EWMA divides a megastep's time by tokens committed, so
    the per-token pace at N=4 lands well under the N=1 pace (a
    per-dispatch division would land at or above it — that's the
    regression bound). Since PR 35 the EWMA samples the readings the
    step's spans take (``profiler.RecordEvent``: from a step's launch, or
    the fetch before it, to its tokens fetched); here that clock is
    stepped (every read advances 1 ms), so a step's time is the count of
    the reads in it, and the comparison holds whatever the CPU's speed
    and load (on the real clock it was a race: under six workers a slow
    N=4 sample made the take-up run of PR 33 fail)."""
    from paddle_tpu import profiler as profiler_mod
    stepped = TickClock()

    class SteppedTime:
        @staticmethod
        def perf_counter_ns():
            return int(round(stepped() * 1e9))

        def __getattr__(self, name):        # sleep, monotonic, ...
            return getattr(time, name)
    prompts = _prompts((4, 5), seed=11)
    ewma = {}
    for n in (1, 4):
        eng = _engine(model, megastep=n)
        _run(eng, prompts, mnt=16)          # warm: compiles land here
        eng._tpot_ewma = None
        monkeypatch.setattr(profiler_mod, "time", SteppedTime())
        _run(eng, prompts, mnt=16)
        monkeypatch.undo()
        assert eng._tpot_ewma is not None and eng._tpot_ewma > 0
        ewma[n] = eng._tpot_ewma
    # a megastep's launch-to-fetched holds no more reads than a single
    # step's and commits four tokens a row (three in a megastep cut short
    # by the budget)
    assert ewma[4] < ewma[1] * 0.5, ewma

    # per-request TPOT on the engine's own (injected) clock IS strict:
    # one commit per megastep means fewer host clock reads between the
    # first token and finish, so each request's measured pace drops
    tpot = {}
    for n in (1, 4):
        eng = _engine(model, megastep=n, clock=TickClock())
        reqs = _run(eng, prompts, mnt=16)
        assert all(r.tpot is not None and r.tpot > 0 for r in reqs)
        tpot[n] = [r.tpot for r in reqs]
    for t4, t1 in zip(tpot[4], tpot[1]):
        assert t4 < t1, (tpot[4], tpot[1])


def test_ttft_and_blame_identity_under_megastep(model):
    """TTFT still comes from prefill (megasteps only batch *decode*
    host work) and the blame decomposition of every finished request
    sums exactly to its E2E, with the prefix up to first_token equal
    to the engine's own TTFT."""
    tracing.reset()
    clock = TickClock()
    eng = _engine(model, megastep=4, clock=clock)
    reqs = _run(eng, _prompts((3, 5, 7), seed=12), mnt=12)
    for r in reqs:
        info = tracing.get(r.id)
        assert info is not None and info["outcome"] == "done"
        assert sum(info["blame_ms"].values()) == \
            pytest.approx(info["e2e_ms"], abs=1e-6), info
        kinds = [m["kind"] for m in info["marks"]]
        assert kinds[0] == "submit" and kinds[-1] == "finish"
        assert "first_token" in kinds
        assert info["ttft_ms"] == pytest.approx(r.ttft * 1e3,
                                                rel=1e-9)
    tracing.reset()


def test_blame_decode_share_strictly_down(model):
    """The point of the feature, measured where it lives: with every
    host-side clock read billed 1 ms, the fleet's decode blame at
    megastep=8 is strictly below the same workload at N=1 (one commit
    per megastep instead of one per token)."""
    prompts = _prompts((3, 4), seed=13)

    def decode_ms(n):
        tracing.reset()
        eng = _engine(model, megastep=n, clock=TickClock())
        _run(eng, prompts, mnt=24)
        s = tracing.blame_summary()
        assert s["requests"] == len(prompts)
        comp = s["components"]["decode"]
        tracing.reset()
        return comp["total_ms"], comp["share"]

    serial_ms, serial_share = decode_ms(1)
    mega_ms, mega_share = decode_ms(8)
    assert mega_ms < serial_ms, (mega_ms, serial_ms)
    assert mega_share < serial_share, (mega_share, serial_share)
