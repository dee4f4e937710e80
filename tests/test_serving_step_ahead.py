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

Since PR 48 a prefill group is a flight too: the decode step behind it is
dispatched from its device outputs (its rows' first tokens and keys, merged
by the prompt program into what the step in flight left) before the host
fetches them, unless a row of the group draws its first token on the host.
The same oracle holds it: with ``_rows_ahead`` answering None no step is
dispatched behind a prefill either, and its first tokens are fetched before
the step that reads them is built (``admit_ahead_dispatches`` 0).
"""

import json
import os
import sys
import threading

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as pt                                        # noqa: E402
from paddle_tpu import monitor, observability                  # noqa: E402
from paddle_tpu.analysis import predict_serving_compiles       # noqa: E402
from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (JAMBA_CONFIGS, KEYE_CONFIGS,    # noqa: E402
                               LFM2_CONFIGS, MELLUM_CONFIGS,
                               JambaForCausalLM, KeyeForCausalLM,
                               Lfm2ForCausalLM, MellumForCausalLM)
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


@pytest.fixture(scope="module")
def lfm2():
    layers.seed(3)
    m = Lfm2ForCausalLM(LFM2_CONFIGS["lfm2-tiny"])
    m.eval()
    return m


@pytest.fixture(scope="module")
def keye():
    layers.seed(3)
    m = KeyeForCausalLM(KEYE_CONFIGS["keye-tiny"])
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
    assert sync.eng.stats()["admit_ahead_dispatches"] == 0
    assert not ahead.eng._prefills and not sync.eng._prefills
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


# ------------------------------------- a prefill group is a flight (PR 48)

def _admitted_ahead(r, sync, n=1):
    """``n`` more prefill groups were followed by their step unfetched."""
    got = r.eng.stats()["admit_ahead_dispatches"]
    assert got == (0 if sync else r.counted + n), got
    r.counted = got


def admission_behind_a_step(model, sync):
    """B is admitted with a step of A's in flight: its prefill goes out
    behind that step and the step after, with B's row in it, behind the
    prefill, all before the host has B's first token. A round later both
    rows have one more token: no host-built step stood between."""
    pa, pb, pc = _prompts((5, 7, 6), seed=21)
    r = Run(_engine(model, sync))
    r.counted = 0
    a = r.submit(pa, max_new_tokens=14)
    r.step(3)
    _admitted_ahead(r, sync)            # the first round's, into an idle engine
    b = r.submit(pb, max_new_tokens=6)
    had = len(a.tokens)
    r.step()
    _admitted_ahead(r, sync)
    # (the synchronous round builds its step from the host after the
    # prefill's commit and lands it: B has its second token already)
    assert (len(a.tokens), len(b.tokens)) == (had + 1, 1 + sync)
    if not sync:
        rows = {slot: (req, n) for slot, req, n in r.eng._flight.rows}
        assert rows[b.slot] == (b, 1) and rows[a.slot] == (a, had + 1)
        assert r.eng._flight.ahead
    r.step()
    assert (len(a.tokens), len(b.tokens)) == (had + 2, 2 + sync)
    r.step(2)
    c = r.submit(pc, max_new_tokens=5)   # into the third slot, two running
    r.until_idle()
    _admitted_ahead(r, sync)
    assert [x.state for x in (a, b, c)] == ["done"] * 3
    return r


def two_buckets_in_one_round(model, sync):
    """Two prompts of two buckets arrive in one round: two prefill groups
    go out back to back, then the one step behind both, then the fetches."""
    pa, pb, pc = _prompts((5, 6, 13), seed=22)
    r = Run(_engine(model, sync))
    r.counted = 0
    a = r.submit(pa, max_new_tokens=12)
    r.step(3)
    _admitted_ahead(r, sync)
    b = r.submit(pb, max_new_tokens=7)
    c = r.submit(pc, max_new_tokens=9)
    seq = r.eng._dispatch_seq
    r.step()
    _admitted_ahead(r, sync, 2)
    # two prefills and one step: behind them, or built by the host once
    # both were committed
    assert r.eng._dispatch_seq - seq == 3
    assert len(b.tokens) == len(c.tokens) == 1 + sync
    r.until_idle()
    assert [x.state for x in (a, b, c)] == ["done"] * 3
    return r


def ends_on_its_prefill_token(model, sync):
    """B's budget is one token and C's first token is its EOS: both end
    at their prefill's commit, with the step behind the prefill in flight
    holding a row for each. Those rows are dropped, the slots are free a
    round later, and D and E take them."""
    pa, pb, pc, pd, pe = _prompts((5, 7, 6, 4, 5), seed=23)
    probe = _engine(model, True)
    first = probe.submit(pc, max_new_tokens=1)
    probe.run_until_idle()
    r = Run(_engine(model, sync))
    a = r.submit(pa, max_new_tokens=12)
    r.step(3)
    b = r.submit(pb, max_new_tokens=1)
    c = r.submit(pc, max_new_tokens=8, eos_token_id=first.tokens[0])
    dropped = r.eng.stats()["ahead_rows_dropped"]
    r.step()
    assert b.state == c.state == "done"
    assert len(b.tokens) == len(c.tokens) == 1
    if not sync:
        # B's row was left out (the host knew its budget), C's is in the
        # step in flight, for nobody
        assert r.eng._flight is not None
        assert {req for _, req, _ in r.eng._flight.rows} == {a, c}
    d = r.submit(pd, max_new_tokens=6)
    e = r.submit(pe, max_new_tokens=4)
    r.until_idle()
    if not sync:
        assert r.eng.stats()["ahead_rows_dropped"] >= dropped + 2
    assert [x.state for x in (a, d, e)] == ["done"] * 3
    assert len(a.tokens) == 12
    return r


def host_drawn_first_tokens(model, sync):
    """A sampled row and a grammar row in the admitted group: their first
    tokens are drawn on the host from the logits, so the group is fetched
    at once, as before (same tokens, same keys), and a greedy group of
    the same round behind it is not left behind them."""
    grammar = JsonGrammar(json_token_strings(VOCAB))
    pa, pb, pj, pc = _prompts((5, 7, 4, 13), seed=24)
    r = Run(_engine(model, sync, grammar=grammar, max_slots=4))
    a = r.submit(pa, max_new_tokens=14)
    r.step(3)
    before = r.eng.stats()["admit_ahead_dispatches"]
    b = r.submit(pb, max_new_tokens=6, seed=5, **SAMPLED)
    j = r.submit(pj, max_new_tokens=6, json_mode=True)
    c = r.submit(pc, max_new_tokens=5)          # bucket 16: a group of its own
    r.step()
    assert len(b.tokens) == len(j.tokens) == len(c.tokens) == 1 + sync
    assert r.eng.stats()["admit_ahead_dispatches"] == before
    r.until_idle()
    assert [x.state for x in (a, b, j, c)] == ["done"] * 4
    json.loads(grammar.decode(j.tokens))
    return r


def prefix_hit_behind_its_publisher(model, sync):
    """A publishes its prompt's blocks at its prefill's commit; B, with
    the same first two blocks, is admitted a round later behind the step
    that followed A's prefill, and prefills its suffix alone."""
    (pa,) = _prompts((11,), seed=25)
    (tail,) = _prompts((4,), seed=26)
    r = Run(_engine(model, sync))
    a = r.submit(pa, max_new_tokens=9)
    r.step()
    b = r.submit(pa[:9] + tail, max_new_tokens=7)
    r.step()
    assert r.eng.stats()["prefix_hit_requests"] == 1
    assert r.eng.stats()["prefix_hit_tokens"] == 8
    assert r.eng.stats()["admit_ahead_dispatches"] == (0 if sync else 2)
    r.until_idle()
    assert a.state == b.state == "done"
    ref = greedy_search(model, np.asarray([b.prompt]), max_new_tokens=7,
                        cache_len=r.eng.max_len)[0].tolist()
    assert b.output_ids == ref
    return r


def _between_dispatch_and_commit(eng, what):
    """Run ``what()`` once a prefill group is dispatched and before it is
    committed, where only another holder of the scheduler's lock could:
    the lock is made re-entrant for the engine of this scenario."""
    eng._step_lock = threading.RLock()
    land = eng._land_prefill

    def hooked(pf):
        del eng._land_prefill
        what()
        return land(pf)
    eng._land_prefill = hooked


def cancel_across_an_admission(model, sync):
    """B is cancelled between its prefill's dispatch and its commit, with
    the step behind the prefill already on the device: B gets no token,
    its row of that step is computed for nobody, and C takes the slot."""
    pa, pb, pc = _prompts((6, 5, 7), seed=27)
    r = Run(_engine(model, sync))
    a = r.submit(pa, max_new_tokens=12)
    r.step(2)
    b = r.submit(pb, max_new_tokens=12)
    got = []
    _between_dispatch_and_commit(
        r.eng, lambda: got.append(r.eng.cancel(b.id)))
    r.step()
    assert got[0]["stage"] == "prefill" and b.state == "canceled"
    if not sync:
        assert any(req is b for _, req, _ in r.eng._flight.rows)
    c = r.submit(pc, max_new_tokens=6)          # takes B's slot behind it
    r.until_idle()
    assert b.tokens == [] and r.eng.stats()["prefill_flights"] == 2
    assert a.state == c.state == "done" and len(a.tokens) == 12
    return r


def hard_deadline_across_an_admission(model, sync):
    """B's patience ends on the engine's clock while its prefill runs and
    a sweep finds it before the prefill's commit: cancelled at the stage
    ``prefill``, no token, its row of the step behind dropped. (A
    deadline that the next round's sweep finds, with the first token
    committed, is ``hard_deadline_in_flight``'s case.)"""
    now = [0.0]
    pa, pb = _prompts((6, 5), seed=28)
    r = Run(_engine(model, sync, clock=lambda: now[0]))
    a = r.submit(pa, max_new_tokens=10)
    r.step(2)
    b = r.submit(pb, max_new_tokens=10, deadline_ms=100.0)

    def sweep():
        now[0] = 1.0
        assert r.eng._reap_expired() == 1
    _between_dispatch_and_commit(r.eng, sweep)
    r.step()
    assert b.state == "canceled" and b.shed_reason == "deadline"
    assert b.tokens == []
    r.until_idle()
    assert a.state == "done" and len(a.tokens) == 10
    return r


SCENARIOS = [greedy_and_sampled, finishes_in_flight, admission_in_flight,
             cancel_in_flight, hard_deadline_in_flight,
             swap_weights_in_flight, grammar_row, skipped_round,
             host_tier_sweep, admission_behind_a_step,
             two_buckets_in_one_round, ends_on_its_prefill_token,
             host_drawn_first_tokens, prefix_hit_behind_its_publisher,
             cancel_across_an_admission, hard_deadline_across_an_admission]


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

def test_what_decodes_another_way_dispatches_nothing_ahead(gpt):
    """Drafts come from host tokens: the counters read 0, and are there
    all the same."""
    eng = _engine(gpt, spec_tokens=2)
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
    """Two rows of one budget: every step ahead, the first behind the
    prefill that admitted both (PR 48: none from the host), and none
    behind the last (the host knows that both rows end there). Budgets
    apart: one dispatch carries the shorter row for nobody."""
    monitor.reset()
    eng = _engine(gpt)
    reqs = [eng.submit(p, max_new_tokens=8) for p in _prompts((5, 7), 10)]
    rounds = eng.run_until_idle()
    st = eng.stats()
    assert st["sampler_dispatches"] == 7          # 8 tokens, 1 by prefill
    assert st["ahead_dispatches"] == 7
    assert (st["admit_ahead_dispatches"], st["prefill_flights"]) == (1, 1)
    assert st["ahead_rows_committed"] == 14 and st["ahead_rows_dropped"] == 0
    assert rounds == 8      # the prefill's round commits no decode step
    assert monitor.stat_get("STAT_serving_ahead_dispatches") == 7
    assert monitor.stat_get("STAT_serving_admit_ahead_dispatches") == 1
    assert monitor.stat_get("STAT_serving_ahead_hits") == 14
    assert monitor.stat_get("STAT_serving_ahead_misses") == 0
    # budgets apart: the longer row's step is dispatched with the shorter
    # one's row still in the batch, once
    more = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts((5, 7), 11), (5, 8))]
    eng.run_until_idle()
    st2 = eng.stats()
    assert st2["ahead_rows_dropped"] == 1
    assert st2["ahead_rows_committed"] - 14 == 4 + 7
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
    assert seen == [1, 2, 3, 4, 5, 6, 7, 8]       # the prefill's, then 1 each
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


def test_a_prefill_that_fails_at_its_fetch_loses_the_step_behind_it(gpt):
    """The twin for a prefill flight: B's prompt program fails on the
    device. Its dispatch had bound the pools it returns and the step
    behind it was dispatched on them, so the failure surfaces where the
    host first waits for B's first token: what ran is shed (A, whose step
    in flight was committed first, in dispatch order, keeps that token),
    the step behind the prefill goes with the pools, and the next request
    is served to the token."""
    class Poisoned:
        def __array__(self, *a, **kw):
            raise RuntimeError("the device reported the prefill failed")

    monitor.reset()
    eng = _engine(gpt)
    pa, pb, pc = _prompts((5, 7, 6), seed=17)
    a = eng.submit(pa, max_new_tokens=12)
    eng.step()
    eng.step()
    had = len(a.tokens)
    b = eng.submit(pb, max_new_tokens=8)
    land = eng._land_prefill

    def poisoned(pf):
        # by now the step behind the prefill is on the device
        assert eng._flight is not None and any(
            req is b for _, req, _ in eng._flight.rows)
        return land(pf._replace(nxt=Poisoned()))
    eng._land_prefill = poisoned
    eng.step()
    del eng._land_prefill
    assert a.state == b.state == "shed"
    assert len(a.tokens) == had + 1 and b.tokens == []
    assert eng._flight is None and not eng._prefills
    assert monitor.stat_get("STAT_serving_pool_rebuilds") == 1
    assert eng.stats()["admit_ahead_dispatches"] == 1     # A's, not B's
    c = eng.submit(pc, max_new_tokens=8)
    eng.run_until_idle()
    assert c.output_ids == greedy_search(
        gpt, np.asarray([pc]), max_new_tokens=8,
        cache_len=eng.max_len)[0].tolist()
    eng.cache.flush_prefix_cache()
    assert eng.cache.allocator.leaked() == 1
    assert eng.cache.lengths.tolist() == [0] * eng.max_slots


# ------------------------------------------------------------- no program

def _executables():
    """Executables jax has compiled in this process (its own counter of
    backend compiles: a program jit re-lowered for an argument that came
    from another place, with the tracked function not traced again, is
    counted here and not by the tracker)."""
    return _BACKEND_COMPILES[0]


_BACKEND_COMPILES = [0]
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **_kw: _BACKEND_COMPILES.__setitem__(
        0, _BACKEND_COMPILES[0]
        + (event == "/jax/core/compile/backend_compile_duration")))


def test_the_ahead_path_adds_no_program():
    """The step dispatched ahead is the compiled ``decode_step_paged``
    entry given device arrays where it was given host arrays: the tracker
    sees what it sees for the synchronous engine, which is what
    ``predict_serving_compiles`` says, and a further window of ahead steps
    adds nothing. The step behind a prefill neither (PR 48): the prompt
    program merges its rows' first tokens and keys itself, so a warmed
    window with admissions behind steps in flight, two buckets in a
    a slot refilled and a sampled group compiles nothing, by the
    tracker's count and, for the greedy admissions, by the number of
    executables jax built."""
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
        # a third, of admissions behind steps in flight: greedy rows
        # join one by one as slots free, then a sampled one
        built = _executables()
        late = []
        for p, n in zip(prompts + prompts[:1], (9, 4, 6, 5)):
            late.append(eng.submit(p, max_new_tokens=n))
            eng.step()
            eng.step()
        assert compiles() == first and _executables() == built
        assert (eng.stats()["admit_ahead_dispatches"] >= 4) == (not sync)
        # (a sampled row's first token is drawn on the host by eager
        # operations, which jax compiles as it meets them: the tracked
        # programs are what this one is held to)
        late.append(eng.submit(prompts[1], max_new_tokens=7, seed=5,
                               **SAMPLED))
        eng.run_until_idle()
        assert compiles() == first
        assert all(r.state == "done" for r in reqs + more + late)
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


def joins_behind_its_prefill(model, sync, vocab=512, **kw):
    """Two requests decode; a third is admitted with a step in flight,
    then one of a single token and one of another bucket: their rows join
    the step dispatched behind their prefill."""
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(1, vocab, n).tolist(), k)
            for n, k in ((5, 30), (13, 24))]
    r = _served(model, sync, reqs, **kw)
    r.until(lambda: len(r.reqs[0].tokens) >= 6)
    r.submit(rng.integers(1, vocab, 21).tolist(), max_new_tokens=18)
    r.until(lambda: len(r.reqs[0].tokens) >= 12)
    r.submit(rng.integers(1, vocab, 9).tolist(), max_new_tokens=1)
    r.submit(rng.integers(1, vocab, 40).tolist(), max_new_tokens=14)
    r.until(lambda: len(r.reqs[4].tokens) >= 5)
    return r


def _row_cache(eng, req):
    """Everything the cache holds for ``req`` below its committed length,
    with the step in flight drained: each layer's arrays of the kind that
    keeps every row (K, V and what a token keeps beside them) block by
    block, and the row's record of every state kind."""
    with eng._step_lock:
        eng._drain()
    c, slot = eng.cache, req.slot
    length, bs = int(c.lengths[slot]), c.block_size
    out = [length]
    for layer in c.layers:
        for a in layer:
            for j in range(-(-length // bs)):
                blk = np.asarray(a[int(c.tables[slot, j])])
                out.append(blk[:, :min(bs, length - j * bs)])
    out += [np.asarray(a[slot]) for st in c._states
            for layer in st.layers for a in layer]
    return out


@pytest.mark.parametrize("family", ["mellum", "jamba", "lfm2", "keye"])
def test_an_admitted_row_joins_the_step_behind_its_prefill(family, request):
    """The admission case beside each model's step-ahead test. The row
    that joined behind its prefill is compared mid-run, at the same count
    of tokens in both runs, on its tokens and on everything the cache
    holds for it: the state advanced once a committed token, the third
    array's rows stand. Then to the end, on the cache's accounting."""
    model = request.getfixturevalue(family)
    kw = dict(buckets=[32, 64]) if family == "keye" else {}
    ahead = joins_behind_its_prefill(model, False, **kw)
    assert ahead.eng._flight is not None
    for i in (2, 4):
        got = _row_cache(ahead.eng, ahead.reqs[i])
        sync = joins_behind_its_prefill(model, True, **kw)
        n = len(ahead.reqs[i].tokens)
        sync.until(lambda: len(sync.reqs[i].tokens) >= n)
        assert sync.reqs[i].tokens == ahead.reqs[i].tokens
        want = _row_cache(sync.eng, sync.reqs[i])
        assert got[0] == want[0] > 0 and len(got) == len(want) > 2
        for x, y in zip(got[1:], want[1:]):
            np.testing.assert_array_equal(x, y)
    for r in (ahead, sync):
        r.until_idle()
    assert ahead.outcome() == sync.outcome()
    st, st0 = ahead.eng.stats(), sync.eng.stats()
    assert st["admit_ahead_dispatches"] == st["prefill_flights"] >= 4
    assert st0["admit_ahead_dispatches"] == 0
    assert st["ahead_dispatches"] > st["admit_ahead_dispatches"]
    for key in ("window_blocks_freed", "state_rows_live",
                "kv_blocks_live_full", "index_cache_bytes"):
        assert st.get(key) == st0.get(key), key


def test_every_engine_reports_the_counters(gpt, mellum, jamba):
    for model, kw in ((gpt, {}), (gpt, dict(kv_dtype="int8")),
                      (mellum, dict(prefix_cache=False, max_len=64,
                                    block_size=8, buckets=[16])),
                      (jamba, dict(prefix_cache=False, max_len=64,
                                   block_size=8, buckets=[16]))):
        st = _engine(model, **kw).stats()
        assert (st["ahead_dispatches"], st["ahead_rows_committed"],
                st["ahead_rows_dropped"]) == (0, 0, 0)
        assert (st["admit_ahead_dispatches"], st["prefill_flights"]) == (0, 0)
