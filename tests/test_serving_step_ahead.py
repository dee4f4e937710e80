"""The single decode step is dispatched one ahead of its fetch (PR 34).

``ServingEngine._decode`` gives the device step k+1, built from step k's
device outputs (its tokens, keys and pools) and host arithmetic (every
live row one position further), **before** the host waits for k; fetch
and commit of k then run under the device's time. Validity is by row: a
row of k+1 is committed iff its slot still holds the request it was
computed for, and a valid row's step is never run twice.

The oracle is the synchronous drain: the same engine with the ahead
dispatch switched off *in the test* (``_rows_ahead`` answering None, so
every step is fetched before the next is built: what the engine did
before). Each scenario is run both ways and must agree, after every
round a request's tokens were committed in, on tokens, states, request
keys, and at the end on the cache's accounting (lengths, free blocks,
nothing leaked). What differs is when: a request admitted while a step is
in flight joins one step later, so the runs are compared by request and
by commit, not round by round.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as pt                                        # noqa: E402
from paddle_tpu import monitor, observability                  # noqa: E402
from paddle_tpu.analysis import predict_serving_compiles       # noqa: E402
from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (JAMBA_CONFIGS, MELLUM_CONFIGS,  # noqa: E402
                               JambaForCausalLM, MellumForCausalLM)
from paddle_tpu.models.generation import (decode_step_paged,   # noqa: E402
                                          greedy_search)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM    # noqa: E402
from paddle_tpu.resilience import fault_scope                  # noqa: E402
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from paddle_tpu.serving.decoding import (JsonGrammar,          # noqa: E402
                                         json_token_strings)
from paddle_tpu.serving.kv_tier import (HostBlockStore,        # noqa: E402
                                        TierManager)

VOCAB = 97
SAMPLED = dict(temperature=0.8, top_k=12, top_p=0.9)


def _gpt(seed=7):
    pt.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, max_position_embeddings=64, hidden_size=32,
        num_layers=2, num_heads=4, ffn_hidden_size=64))
    m.eval()
    return m


@pytest.fixture(scope="module")
def gpt():
    return _gpt()


@pytest.fixture(scope="module")
def mellum():
    layers.seed(3)
    m = MellumForCausalLM(MELLUM_CONFIGS["mellum-tiny"])
    m.eval()
    return m


@pytest.fixture(scope="module")
def jamba():
    layers.seed(3)
    m = JambaForCausalLM(JAMBA_CONFIGS["jamba-tiny"])
    m.eval()
    return m


def _prompts(sizes, seed=0, vocab=VOCAB):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=n).tolist() for n in sizes]


def _engine(model, sync=False, **kw):
    kw = dict(dict(max_slots=3, max_len=48, buckets=[8, 16], max_queue=16,
                   block_size=4), **kw)
    eng = ServingEngine(model, **kw)
    if sync:
        # the synchronous drain: no step is dispatched before the step
        # before it was fetched and committed
        eng._rows_ahead = lambda fl: None
    return eng


class Run:
    """One engine stepped through a script; keeps what the two runs of a
    scenario must agree on."""

    def __init__(self, eng):
        self.eng, self.reqs = eng, []

    def submit(self, prompt, **kw):
        self.reqs.append(self.eng.submit(prompt, **kw))
        return self.reqs[-1]

    def step(self, n=1):
        for _ in range(n):
            self.eng.step()

    def until(self, cond, limit=300):
        while not cond():
            self.eng.step()
            limit -= 1
            assert limit > 0, "the scenario never got there"

    def until_idle(self):
        self.until(lambda: self.eng.idle)

    def outcome(self):
        eng = self.eng
        with eng._step_lock:
            eng._drain()
        eng.cache.flush_prefix_cache()
        return {
            "requests": [(r.state, r.shed_reason, tuple(r.tokens),
                          tuple(int(x) for x in np.asarray(r._key).ravel()))
                         for r in self.reqs],
            "lengths": eng.cache.lengths.tolist(),
            "blocks_free": eng.cache.blocks_free,
            "rows_free": eng.cache.num_free,
            "leaked": eng.cache.allocator.leaked(),     # 1: the trash block
        }


def both(scenario, model, **kw):
    """-> (the ahead run, the synchronous run), outcomes compared."""
    ahead, sync = scenario(model, False, **kw), scenario(model, True, **kw)
    got, want = ahead.outcome(), sync.outcome()
    assert got == want
    assert got["leaked"] == 1 and got["lengths"] == [0] * len(got["lengths"])
    assert sync.eng.stats()["ahead_dispatches"] == 0
    return ahead, sync


# ---------------------------------------------------------------- scenarios

def greedy_and_sampled(model, sync):
    """Three rows of unequal budgets, one of them sampled from a seed: the
    batch stands for several steps, then thins out row by row."""
    r = Run(_engine(model, sync))
    pa, pb, pc = _prompts((5, 7, 6), seed=1)
    a = r.submit(pa, max_new_tokens=12)
    r.submit(pb, max_new_tokens=9, seed=8, **SAMPLED)
    r.submit(pc, max_new_tokens=5, seed=9, temperature=1.2)
    r.until_idle()
    ref = greedy_search(model, np.asarray([pa]), max_new_tokens=12,
                        cache_len=r.eng.max_len)[0].tolist()
    assert a.output_ids == ref
    return r


def finishes_in_flight(model, sync):
    """A ends by budget (the host knows before it dispatches ahead), B by a
    stop sequence and C by EOS (the commit of the step before tells, with
    the step after already in flight: its row is dropped)."""
    pa, pb, pc = _prompts((5, 7, 6), seed=1)
    probe = _engine(model, True)
    rb = probe.submit(pb, max_new_tokens=10)
    rc = probe.submit(pc, max_new_tokens=10, seed=8, **SAMPLED)
    probe.run_until_idle()
    # C ends at a token of its own stream that none before it equals
    k = min(i for i, t in enumerate(rc.tokens)
            if i >= 2 and t not in rc.tokens[:i])
    assert k < 9, rc.tokens
    r = Run(_engine(model, sync))
    a = r.submit(pa, max_new_tokens=7)
    b = r.submit(pb, max_new_tokens=10, stop=[rb.tokens[3:5]])
    c = r.submit(pc, max_new_tokens=10, eos_token_id=rc.tokens[k], seed=8,
                 **SAMPLED)
    r.until_idle()
    assert [x.state for x in (a, b, c)] == ["done"] * 3
    assert len(a.tokens) == 7
    assert b.tokens == rb.tokens[:5] and c.tokens == rc.tokens[:k + 1]
    return r


def admission_in_flight(model, sync):
    """B and C are admitted while a step of A's is in flight: they join the
    step after the one in flight, A's tokens are what they were, and D
    takes the slot a finished row left."""
    pa, pb, pc, pd = _prompts((5, 7, 6, 4), seed=2)
    r = Run(_engine(model, sync))
    a = r.submit(pa, max_new_tokens=14)
    r.step(3)
    if not sync:
        assert r.eng._flight is not None and r.eng._flight.ahead
    r.submit(pb, max_new_tokens=6, seed=5, **SAMPLED)
    r.submit(pc, max_new_tokens=9)
    r.step(4)
    r.submit(pd, max_new_tokens=5)
    r.until_idle()
    assert all(x.state == "done" for x in r.reqs) and len(a.tokens) == 14
    return r


def cancel_in_flight(model, sync):
    pa, pb = _prompts((6, 5), seed=3)
    r = Run(_engine(model, sync))
    a = r.submit(pa, max_new_tokens=12)
    b = r.submit(pb, max_new_tokens=12, seed=5, **SAMPLED)
    r.until(lambda: len(a.tokens) == 4)
    if not sync:
        assert r.eng._flight is not None
    assert r.eng.cancel(a.id) is not None
    r.until_idle()
    assert a.state == "canceled" and len(a.tokens) == 4
    assert b.state == "done" and len(b.tokens) == 12
    return r


def hard_deadline_in_flight(model, sync):
    """A's patience ends on the engine's clock between two rounds: the
    sweep cancels it with its next step in flight."""
    now = [0.0]
    pa, pb = _prompts((6, 5), seed=4)
    r = Run(_engine(model, sync, clock=lambda: now[0]))
    a = r.submit(pa, max_new_tokens=12, deadline_ms=100.0)
    b = r.submit(pb, max_new_tokens=10)
    r.until(lambda: len(a.tokens) == 5)
    now[0] = 1.0
    r.until_idle()
    assert a.state == "canceled" and a.shed_reason == "deadline"
    assert len(a.tokens) == 5 and b.state == "done"
    return r


def swap_weights_in_flight(model, sync):
    """The step in flight is the old weights' last: it is fetched and
    committed before the parameters are rebound, and the step after is
    built from the host. The cut falls one token later than where the
    caller saw the request stand (the device had the step already), so
    the synchronous run swaps one round later."""
    pa, pb = _prompts((5, 6), seed=5)
    m = _gpt(7)
    other = {n: np.asarray(p.value) for n, p in _gpt(11).named_parameters()}
    r = Run(_engine(m, sync))
    a = r.submit(pa, max_new_tokens=10)
    r.submit(pb, max_new_tokens=10, seed=2, **SAMPLED)
    r.until(lambda: len(a.tokens) == (5 if sync else 4))
    assert (r.eng._flight is not None) == (not sync)
    r.eng.swap_weights(other)
    assert r.eng._flight is None
    assert len(a.tokens) == 5                     # drained, committed
    r.until_idle()
    return r


def grammar_row(model, sync):
    grammar = JsonGrammar(json_token_strings(VOCAB))
    pa, pj = _prompts((5, 4), seed=6)
    r = Run(_engine(model, sync, grammar=grammar))
    a = r.submit(pa, max_new_tokens=16)
    r.step(3)
    before = r.eng.stats()["ahead_dispatches"]
    j = r.submit(pj, max_new_tokens=6, json_mode=True)
    r.until(lambda: j.state != "running" and j.state != "queued")
    # while the cursored row lived, its mask came from the host each step
    assert r.eng.stats()["ahead_dispatches"] == before
    r.until_idle()
    assert a.state == j.state == "done"
    json.loads(grammar.decode(j.tokens))
    if not sync:
        assert r.eng.stats()["ahead_dispatches"] > before   # and after it
    return r


def skipped_round(model, sync):
    """An injected skip of a decode round dispatches and commits nothing:
    the step in flight stays there and lands a round later."""
    pa, pb = _prompts((6, 5), seed=7)
    with fault_scope("serving.step:skip@3;serving.step:skip@6"):
        r = Run(_engine(model, sync))
        a = r.submit(pa, max_new_tokens=9)      # call 0: the prefill
        b = r.submit(pb, max_new_tokens=8, seed=4, **SAMPLED)
        r.until_idle()
    assert a.state == b.state == "done"
    assert len(a.tokens) == 9 and len(b.tokens) == 8
    return r


def host_tier_sweep(model, sync):
    """A session's finished chain is demoted to the host between steps:
    the copies read the pools, so the step in flight is drained first."""
    pa, pb = _prompts((8, 6), seed=8)
    cfg = model.gpt.cfg
    tier = TierManager(
        HostBlockStore(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                       block_size=4, num_blocks=64), demote_idle_ms=0.0)
    r = Run(_engine(model, sync, kv_tier=tier))
    a = r.submit(pa, max_new_tokens=4, session="s1")
    b = r.submit(pb, max_new_tokens=12)
    r.until(lambda: a.state == "done")
    r.step(2)
    assert r.eng.stats()["kv_tier"]["migrated_demote_blocks"] > 0
    r.until_idle()
    assert b.state == "done" and len(b.tokens) == 12
    return r


SCENARIOS = [greedy_and_sampled, finishes_in_flight, admission_in_flight,
             cancel_in_flight, hard_deadline_in_flight,
             swap_weights_in_flight, grammar_row, skipped_round,
             host_tier_sweep]


@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda f: f.__name__)
def test_ahead_and_synchronous_runs_agree(scenario, gpt):
    ahead, sync = both(scenario, gpt)
    st = ahead.eng.stats()
    assert st["ahead_dispatches"] > 0
    assert st["ahead_rows_committed"] > 0
    # every dispatch is a step the synchronous run made too, plus the rows
    # computed for nobody: one a request that ended or left in flight
    assert st["ahead_rows_dropped"] <= len(ahead.reqs)
    extra = st["sampler_dispatches"] - sync.eng.stats()["sampler_dispatches"]
    assert 0 <= extra <= len(ahead.reqs)


# ------------------------------------------------------- what forces a sync

@pytest.mark.parametrize("kw", [dict(spec_tokens=2), dict(megastep=3)],
                         ids=["spec_tokens", "megastep"])
def test_what_decodes_another_way_dispatches_nothing_ahead(gpt, kw):
    """Drafts come from host tokens, and a megastep has its own
    pipelining (behind its flag): the counters read 0, and are there all
    the same."""
    eng = _engine(gpt, **kw)
    reqs = [eng.submit(p, max_new_tokens=9) for p in _prompts((5, 6), 9)]
    eng.run_until_idle()
    assert all(r.state == "done" for r in reqs)
    st = eng.stats()
    assert (st["ahead_dispatches"], st["ahead_rows_committed"],
            st["ahead_rows_dropped"]) == (0, 0, 0)
    assert eng._flight is None
    for p, r in zip(_prompts((5, 6), 9), reqs):
        assert r.output_ids == greedy_search(
            gpt, np.asarray([p]), max_new_tokens=9,
            cache_len=eng.max_len)[0].tolist()


# --------------------------------------------------------------- the counter

def test_the_counters_read_what_a_steady_batch_implies(gpt):
    """Two rows of one budget: one step from the host, every other ahead,
    and none behind the last (the host knows that both rows end there).
    Budgets apart: one dispatch carries the shorter row for nobody."""
    monitor.reset()
    eng = _engine(gpt)
    reqs = [eng.submit(p, max_new_tokens=8) for p in _prompts((5, 7), 10)]
    rounds = eng.run_until_idle()
    st = eng.stats()
    assert st["sampler_dispatches"] == 7          # 8 tokens, 1 by prefill
    assert st["ahead_dispatches"] == 6
    assert st["ahead_rows_committed"] == 12 and st["ahead_rows_dropped"] == 0
    assert rounds == 7
    assert monitor.stat_get("STAT_serving_ahead_dispatches") == 6
    assert monitor.stat_get("STAT_serving_ahead_hits") == 12
    assert monitor.stat_get("STAT_serving_ahead_misses") == 0
    # budgets apart: the longer row's step is dispatched with the shorter
    # one's row still in the batch, once
    more = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts((5, 7), 11), (5, 8))]
    eng.run_until_idle()
    st2 = eng.stats()
    assert st2["ahead_rows_dropped"] == 1
    assert st2["ahead_rows_committed"] - 12 == 3 + 6
    assert monitor.stat_get("STAT_serving_ahead_misses") == 1
    assert all(r.state == "done" for r in reqs + more)


def test_a_token_is_counted_once_the_host_has_fetched_it(gpt):
    """``Request.tokens`` and ``token_at`` describe committed tokens: a
    round commits one step, whatever is in flight behind it."""
    eng = _engine(gpt)
    a = eng.submit(_prompts((5,), 12)[0], max_new_tokens=8)
    seen = []
    while not eng.idle:
        eng.step()
        seen.append(len(a.tokens))
        assert len(a.token_at) == len(a.tokens)
    assert seen == [2, 3, 4, 5, 6, 7, 8]          # prefill + 1, then 1 each
    assert eng._flight is None


def test_stop_commits_the_step_in_flight(gpt):
    eng = _engine(gpt)
    a = eng.submit(_prompts((5,), 13)[0], max_new_tokens=12)
    eng.step()
    eng.step()
    had = len(a.tokens)
    assert eng._flight is not None
    eng.stop()
    assert eng._flight is None and len(a.tokens) == had + 1
    eng.run_until_idle()
    assert a.output_ids == greedy_search(
        gpt, np.asarray([a.prompt]), max_new_tokens=12,
        cache_len=eng.max_len)[0].tolist()


# ------------------------------------------------------------ pools lost

def test_pools_lost_with_a_step_in_flight(gpt, monkeypatch):
    """The step dispatched ahead raises after it consumed the pools: what
    ran is shed with the step in flight (its tokens are nobody's now), the
    pools are rebuilt, and the next request is served to the token."""
    monitor.reset()
    eng = _engine(gpt)
    pa, pb, pc = _prompts((5, 7, 6), seed=14)
    a = eng.submit(pa, max_new_tokens=12)
    b = eng.submit(pb, max_new_tokens=12)
    eng.step()
    eng.step()
    assert eng._flight is not None and eng._flight.ahead
    had = len(a.tokens)
    ent = decode_step_paged(gpt)
    real = ent["fn"]

    def consume_then_raise(*args):
        real(*args)
        raise RuntimeError("device fault after the pools were donated")

    monkeypatch.setitem(ent, "fn", consume_then_raise)
    eng.step()
    monkeypatch.setitem(ent, "fn", real)
    assert a.state == b.state == "shed" and len(a.tokens) == had
    assert eng._flight is None
    assert monitor.stat_get("STAT_serving_pool_rebuilds") == 1
    c = eng.submit(pc, max_new_tokens=8)
    eng.run_until_idle()
    assert c.output_ids == greedy_search(
        gpt, np.asarray([pc]), max_new_tokens=8,
        cache_len=eng.max_len)[0].tolist()
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1
    assert eng.cache.lengths.tolist() == [0] * eng.max_slots


def test_a_step_that_fails_at_its_fetch_loses_the_pools_too(gpt):
    """A failure of the device surfaces where the host first waits for the
    step: by then its pools are bound and the step after is dispatched on
    them. The engine sheds, rebuilds and serves on (ROADMAP D13)."""
    class Poisoned:
        def __array__(self, *a, **kw):
            raise RuntimeError("the device reported the step failed")

    monitor.reset()
    eng = _engine(gpt)
    pa, pb = _prompts((5, 7), seed=16)
    a = eng.submit(pa, max_new_tokens=12)
    eng.step()
    eng.step()
    with eng._step_lock:
        eng._flight = eng._flight._replace(nxt=Poisoned())
    # (the stand-in is no array: the step after cannot be given it)
    eng._rows_ahead = lambda fl: None
    eng.step()
    del eng._rows_ahead
    assert a.state == "shed" and eng._flight is None
    assert monitor.stat_get("STAT_serving_pool_rebuilds") == 1
    b = eng.submit(pb, max_new_tokens=8)
    eng.run_until_idle()
    assert b.output_ids == greedy_search(
        gpt, np.asarray([pb]), max_new_tokens=8,
        cache_len=eng.max_len)[0].tolist()
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1


# ------------------------------------------------------------- no program

def test_the_ahead_path_adds_no_program():
    """The step dispatched ahead is the compiled ``decode_step_paged``
    entry given device arrays where it was given host arrays: the tracker
    sees what it sees for the synchronous engine, which is what
    ``predict_serving_compiles`` says, and a further window of ahead steps
    adds nothing."""
    def compiles():
        return {s: c["count"] for s, c in observability.compiles().items()
                if s.startswith(("serving_", "decode_", "verify_"))}

    prompts = _prompts((5, 7, 6), seed=15)
    observed = []
    for sync in (False, True):
        model = _gpt(21 + sync)
        before = compiles()
        eng = _engine(model, sync, buckets=[8], max_len=32)
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.run_until_idle()
        first = compiles()
        more = [eng.submit(p, max_new_tokens=n, seed=3, **SAMPLED)
                for p, n in zip(prompts, (6, 11, 8))]
        eng.run_until_idle()
        assert compiles() == first          # a second window: nothing new
        assert all(r.state == "done" for r in reqs + more)
        observed.append({s: n - before.get(s, 0) for s, n in first.items()
                         if n - before.get(s, 0)})
        assert decode_step_paged(model)["traces"]["count"] == 1
        assert (eng.stats()["ahead_dispatches"] > 10) == (not sync)
    predicted = predict_serving_compiles(
        [[(p, 9) for p in prompts]], buckets=[8], max_len=32, block_size=4)
    assert observed[0] == observed[1] == predicted, (observed, predicted)


# -------------------------------------------------- the other two models

def _served(model, sync, requests, **kw):
    kw = dict(dict(max_slots=4, max_len=128, buckets=[16, 32, 64],
                   block_size=8, num_blocks=0, prefix_cache=False,
                   eos_token_id=None), **kw)
    r = Run(_engine(model, sync, **kw))
    for p, n in requests:
        r.submit(p, max_new_tokens=n)
    return r


def window_edge(model, sync):
    """mellum-tiny keeps 16 rows of a window layer in blocks of 8: every
    request crosses block edges while it decodes, the window kind returns
    the block behind and takes one ahead *with the step dispatched ahead*
    (``ahead_lengths``), and its table is re-sent with that dispatch. A
    late request takes blocks the others returned."""
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, 512, n).tolist(), k)
            for n, k in ((5, 40), (13, 30), (22, 26))]
    r = _served(model, sync, reqs)
    r.until(lambda: len(r.reqs[0].tokens) >= 12)
    r.submit(rng.integers(1, 512, 9).tolist(), max_new_tokens=28)
    r.until(lambda: len(r.reqs[1].tokens) >= 20)
    assert r.eng.cancel(r.reqs[1].id) is not None
    r.until_idle()
    st = r.eng.stats()
    assert st["window_blocks_freed"] >= 8
    assert [x.state for x in r.reqs] == ["done", "canceled", "done", "done"]
    return r


def test_the_window_kind_moves_with_the_step_dispatched_ahead(mellum):
    ahead, sync = both(window_edge, mellum)
    st, st0 = ahead.eng.stats(), sync.eng.stats()
    assert st["ahead_dispatches"] > 30
    assert st["window_blocks_freed"] == st0["window_blocks_freed"]
    assert st["kv_blocks_live_window"] == st["kv_blocks_live_full"] == 0
    # a table that moved went with the ahead dispatch: those are the ahead
    # dispatches that were not resident
    assert st["inputs_resident"] < st["inputs_dispatches"]


def _states(eng):
    with eng._step_lock:
        eng._drain()
    return [[np.asarray(a) for a in layer]
            for st in eng.cache._states for layer in st.layers]


def recurrent_rows(model, sync, stop_at):
    """jamba-tiny: two Mamba layers' state a row beside the attention
    layer's blocks. Three requests decode; the run is stopped with all of
    them live so that the state itself can be compared."""
    rng = np.random.default_rng(6)
    reqs = [(rng.integers(1, 512, n).tolist(), k)
            for n, k in ((5, 40), (13, 36), (16, 30))]
    r = _served(model, sync, reqs)
    r.until(lambda: len(r.reqs[0].tokens) >= stop_at)
    return r


def test_the_recurrent_state_advances_once_a_committed_token(jamba):
    """``state_replay``-style: after N steps dispatched ahead the state
    rows, the attention blocks' lengths and the tokens are what the
    synchronous path leaves, to the bit; then to the end, with a cancel
    and a late admission into the freed row (whose state the dropped step
    had written last)."""
    ahead = recurrent_rows(jamba, False, 14)
    sync = recurrent_rows(jamba, True, 14)
    assert [r.tokens for r in ahead.reqs] == [r.tokens for r in sync.reqs]
    # the state the device holds is one step further than the tokens
    # committed: drain that step, and give the synchronous run its round
    assert ahead.eng._flight is not None and sync.eng._flight is None
    got = _states(ahead.eng)
    sync.step()
    want = _states(sync.eng)
    assert ahead.eng.cache.lengths.tolist() == sync.eng.cache.lengths.tolist()
    assert [r.tokens for r in ahead.reqs] == [r.tokens for r in sync.reqs]
    live = sorted(ahead.eng._active)
    assert len(live) == 3 and len(got) == len(want) > 0
    for g, w in zip(got, want):
        for x, y in zip(g, w):
            np.testing.assert_array_equal(x[live], y[live])
    assert ahead.eng.stats()["ahead_dispatches"] >= 12
    rng = np.random.default_rng(7)
    late = rng.integers(1, 512, 11).tolist()
    for r in (ahead, sync):
        assert r.eng.cancel(r.reqs[2].id) is not None
        r.submit(late, max_new_tokens=20)
        r.until_idle()
    assert ahead.outcome() == sync.outcome()
    assert ahead.eng.stats()["state_rows_live"] == 0


def test_every_engine_reports_the_counters(gpt, mellum, jamba):
    for model, kw in ((gpt, {}), (gpt, dict(kv_dtype="int8")),
                      (mellum, dict(prefix_cache=False, max_len=64,
                                    block_size=8, buckets=[16])),
                      (jamba, dict(prefix_cache=False, max_len=64,
                                   block_size=8, buckets=[16]))):
        st = _engine(model, **kw).stats()
        assert (st["ahead_dispatches"], st["ahead_rows_committed"],
                st["ahead_rows_dropped"]) == (0, 0, 0)
