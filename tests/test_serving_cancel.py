"""Request-lifecycle robustness: cancellation with full resource
reclaim, in-flight hard-deadline enforcement, hedged prefill, and the
fleet-wide retry budget.

The contracts under test:

- ``ServingEngine.cancel`` terminates a request at whatever stage it
  has reached (queued / in a slot mid-decode) releasing its KV row and
  LoRA pin; it is idempotent (double-cancel and unknown ids are
  no-ops, never double-releases) and pure host-side (zero compiles);
- a ``deadline_ms`` hard deadline expires a request *between decode
  steps*: the slot is reclaimed in the very step that notices, and is
  reusable for admission within that same step;
- hedged prefill on the ReplicaRouter: a predicted-slow primary arms
  a hedge, the clone on the fast replica wins the race, the loser is
  canceled leak-free with the winner's tokens mirrored onto the
  caller's handle token-identical to greedy — and fired volume stays
  inside the ``1 + hedge_budget * offered`` token-bucket envelope;
- the shared :class:`RetryBudget` bounds *fleet-wide* retry volume
  under correlated failure (retry storms shed as backpressure instead
  of multiplying offered load), and ``RetryPolicy.from_flags`` attaches
  it automatically for the serving sites.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.models.generation import greedy_search
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import (BUDGETED_SITES, RetryBudget,
                                   RetryError, RetryPolicy,
                                   default_budget, reset_default_budget)
from paddle_tpu.serving import ReplicaRouter, ServingEngine, make_adapter


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


def _leaked(eng):
    eng.cache.flush_prefix_cache()
    return eng.cache.allocator.leaked()


# ------------------------------------------------------- cancellation

def test_cancel_queued_releases_and_is_idempotent(model):
    """Cancel a request that never left the queue: the slot count is
    untouched, the handle flips terminal, and double-cancel / unknown
    ids are Nones, not double-releases."""
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=8, block_size=4)
    p1, p2 = _prompts((4, 5), seed=1)
    r1 = eng.submit(p1, max_new_tokens=8)
    r2 = eng.submit(p2, max_new_tokens=4)
    eng.step()                       # r1 takes the only slot
    out = eng.cancel(r2.id)
    assert out == {"id": r2.id, "stage": "queued", "reason": "client"}
    assert r2.state == "canceled" and r2.shed_reason == "client"
    assert r2.finished_at is not None and r2._done.is_set()
    assert eng.cancel(r2.id) is None          # idempotent
    assert eng.cancel(10_000_000) is None     # unknown id
    eng.run_until_idle()
    assert r1.state == "done"
    assert eng.cancel(r1.id) is None          # terminal: no-op
    st = eng.stats()
    assert st["canceled"] == {"client": 1}
    assert st["completed"] == 1
    assert _leaked(eng) == 1                  # trash block only


def test_cancel_mid_decode_releases_slot_for_reuse(model):
    """Cancel after the first token: the slot and its KV blocks come
    back immediately and the next queued request decodes in them,
    token-identical to greedy."""
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=8, block_size=4)
    p1, p2 = _prompts((4, 6), seed=2)
    r1 = eng.submit(p1, max_new_tokens=12)
    r2 = eng.submit(p2, max_new_tokens=4)
    eng.step()
    assert r1.first_token_at is not None and r1.state == "running"
    out = eng.cancel(r1.id, reason="disconnect")
    assert out is not None and out["stage"] == "decode"
    assert r1.state == "canceled" and r1.shed_reason == "disconnect"
    assert eng.cache.num_free == 1            # slot reclaimed
    eng.run_until_idle()
    ref = greedy_search(model, np.asarray([p2]), max_new_tokens=4,
                        cache_len=eng.max_len)[0].tolist()
    assert r2.state == "done" and r2.output_ids == ref
    assert eng.stats()["canceled"] == {"disconnect": 1}
    assert _leaked(eng) == 1


def test_cancel_spec_int8_pinned_tenant_zero_leaks(model):
    """The hard mode: speculative decoding (K=2 draft-verify, partial
    KV rollbacks in flight) over the int8-quantized paged pool with a
    LoRA tenant pinned — cancel mid-decode must still release the KV
    row AND the adapter pin, and the freed slot must serve the next
    tenant request token-identical to an uncanceled run."""
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=8, block_size=4, spec_tokens=2,
                        kv_dtype="int8", lora_rank=2)
    eng.load_adapter("acme", make_adapter(model.cfg, 2, seed=1))
    p1, p2 = _prompts((4, 5), seed=3)
    r1 = eng.submit(p1, max_new_tokens=12, tenant="acme")
    r2 = eng.submit(p2, max_new_tokens=4, tenant="acme")
    eng.step()
    assert r1.first_token_at is not None
    assert r1._lora_held
    out = eng.cancel(r1.id)
    assert out is not None and out["stage"] == "decode"
    assert not r1._lora_held
    assert eng.lora_pool.leaked() == 0        # pin released
    eng.run_until_idle()
    assert r2.state == "done" and len(r2.tokens) == 4
    assert eng.lora_pool.leaked() == 0
    assert _leaked(eng) == 1


# ------------------------------------------------ hard deadline (SLA)

def test_deadline_ms_validation(model):
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8])
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.submit(_prompts((4,))[0], max_new_tokens=2, deadline_ms=0)
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.submit(_prompts((4,))[0], max_new_tokens=2,
                   deadline_ms=-5.0)


def test_hard_deadline_expires_mid_decode_within_one_step(model):
    """A request whose ``deadline_ms`` passes mid-decode is canceled
    (reason="deadline") by the very next step's reap sweep, and its
    slot admits the waiting request within that SAME step — a dead
    client never burns a decode slot past its patience."""
    now = [0.0]
    eng = ServingEngine(model, max_slots=1, max_len=32, buckets=[8],
                        max_queue=8, block_size=4,
                        clock=lambda: now[0])
    p1, p2 = _prompts((4, 6), seed=4)
    r1 = eng.submit(p1, max_new_tokens=12, deadline_ms=100.0)
    r2 = eng.submit(p2, max_new_tokens=2)     # queued behind r1
    eng.step()
    assert r1.first_token_at is not None      # decoding normally
    assert r1.hard_deadline == pytest.approx(0.1)
    now[0] = 0.25                             # client patience lapsed
    eng.step()
    assert r1.state == "canceled" and r1.shed_reason == "deadline"
    # the reap ran before admission: r2 took the freed slot and got
    # its first token in the same step that expired r1
    assert r2.first_token_at is not None
    eng.run_until_idle()
    assert r2.state == "done"
    st = eng.stats()
    assert st["canceled"] == {"deadline": 1}
    assert st["completed"] == 1               # expired != completed
    assert _leaked(eng) == 1


# ----------------------------------------------------- hedged prefill

def _straggler(eng, skip=8, pin_ms=500.0):
    """Make ``eng`` a deterministic straggler: predicted slow (pinned
    prefill cost, so the hedge gate sees it coming) and actually slow
    (its first ``skip`` steps do nothing)."""
    eng._prefill_ms_pin = pin_ms
    orig = eng.step
    state = {"n": 0}

    def lazy_step():
        state["n"] += 1
        if state["n"] <= skip:
            return False
        return orig()
    eng.step = lazy_step
    return state


def _steps_to_first_token(rt, req, budget=400):
    import time
    time.sleep(0.01)          # let the hedge delay lapse (hedged runs)
    for n in range(1, budget + 1):
        rt.step()
        if req.first_token_at is not None:
            return n
    raise AssertionError(f"no first token in {budget} steps")


def test_hedge_fires_wins_and_beats_unhedged_ttft(model):
    """The hedge race end to end: on a straggler primary the clone
    fires after the delay, wins on the fast replica, the caller's
    tokens are mirrored token-identical to greedy, the loser is
    canceled leak-free (reason="hedge_lose"), and the rescue lands the
    first token in strictly fewer router steps than the identical
    unhedged run — at a fired volume inside the budget envelope."""
    # (3 tokens: the clone's result is mirrored when it retires, and since
    # PR 48 the round of a prefill commits no decode step (the step is
    # dispatched behind the prefill and fetched a round later), so a
    # request of n tokens retires in n rounds, not n - 1)
    prompt = _prompts((4,), seed=5)[0]
    ref = greedy_search(model, np.asarray([prompt]), max_new_tokens=3,
                        cache_len=32)[0].tolist()

    def run(hedge_ms):
        rt = ReplicaRouter(model, n_replicas=2, max_slots=2,
                           max_len=32, buckets=[8, 16], max_queue=16,
                           block_size=4, hedge_ms=hedge_ms)
        _straggler(rt.engines[0])
        req = rt.submit(prompt, max_new_tokens=3)
        steps = _steps_to_first_token(rt, req)
        rt.run_until_idle()
        return rt, req, steps

    rt_u, r_u, steps_u = run(hedge_ms=0.0)    # hedging off
    rt_h, r_h, steps_h = run(hedge_ms=5.0)
    assert r_u.state == "done" and r_u.output_ids == ref
    assert r_h.state == "done" and r_h.output_ids == ref
    assert "hedges" not in rt_u.stats()
    h = rt_h.stats()["hedges"]
    assert h["fired"] == 1 and h["wins"] == 1 and h["pending"] == 0
    assert h["fired"] <= 1 + rt_h._hedge_budget_frac * 1
    assert steps_h < steps_u, (steps_h, steps_u)
    assert rt_h.stats()["canceled"].get("hedge_lose") == 1
    for rt in (rt_u, rt_h):
        for eng in rt.engines:
            assert _leaked(eng) == 1          # trash block only


def test_hedge_budget_zero_bounds_fired_volume(model):
    """``hedge_budget=0``: the bucket's single starting token funds
    exactly one hedge; the next armed hedge is dropped dry, never
    fired — fired <= 1 + 0 * offered — and the unhedged request still
    completes on its straggler."""
    rt = ReplicaRouter(model, n_replicas=2, max_slots=2, max_len=32,
                       buckets=[8, 16], max_queue=16, block_size=4,
                       hedge_ms=5.0, hedge_budget=0.0)
    state = _straggler(rt.engines[0])
    p1, p2 = _prompts((4, 5), seed=6)
    r1 = rt.submit(p1, max_new_tokens=4)
    _steps_to_first_token(rt, r1)
    rt.run_until_idle()
    assert rt.stats()["hedges"]["fired"] == 1     # token spent
    state["n"] = 0                                # straggle again
    r2 = rt.submit(p2, max_new_tokens=4)
    _steps_to_first_token(rt, r2)
    rt.run_until_idle()
    assert r2.state == "done"
    h = rt.stats()["hedges"]
    assert h["fired"] == 1, h                     # bucket dry: no fire
    assert h["pending"] == 0
    for eng in rt.engines:
        assert _leaked(eng) == 1


# ------------------------------------------------- fleet retry budget

def test_retry_budget_bucket_semantics():
    b = RetryBudget(ratio=0.5, reserve=2.0)
    assert b.remaining() == 2.0
    assert b.cap == 20.0
    assert b.try_withdraw() and b.try_withdraw()
    assert not b.try_withdraw()                   # dry
    assert b.remaining() == 0.0
    b.deposit()
    assert b.remaining() == 0.5                   # ratio per success
    assert not b.try_withdraw()                   # 0.5 < 1 token
    b.deposit()
    assert b.try_withdraw()
    snap = b.snapshot()
    assert snap["withdrawals"] == 3 and snap["denials"] == 2
    assert snap["deposits"] == 2


def test_retry_budget_caps_banked_allowance():
    b = RetryBudget(ratio=5.0, reserve=1.0)
    for _ in range(100):
        b.deposit()
    assert b.remaining() == b.cap == 10.0         # 10x reserve


def test_retry_budget_bounds_fleet_storm():
    """Correlated failure across a 10-call fleet: without a budget the
    storm would be offered * (max_attempts-1) = 40 retries; the shared
    bucket bounds it to the reserve, the rest shed immediately as
    budget-exhausted RetryErrors."""
    budget = RetryBudget(ratio=0.1, reserve=3.0)
    attempts = [0]

    def always_down():
        attempts[0] += 1
        raise ConnectionResetError("fleet-wide outage")

    policies = [RetryPolicy(max_attempts=5, base_delay=0.0,
                            jitter=0.0, site="serving.route",
                            sleep=lambda d: None, budget=budget)
                for _ in range(10)]
    shed_as_budget = 0
    for p in policies:
        with pytest.raises(RetryError) as ei:
            p.call(always_down)
        if "RetryBudget is exhausted" in str(ei.value):
            shed_as_budget += 1
    # total fleet attempts = 10 first tries + exactly `reserve` funded
    # retries — not 10 * 5
    assert attempts[0] == 10 + 3, attempts[0]
    assert budget.remaining() == 0.0
    assert shed_as_budget >= 7                    # the storm was shed
    assert budget.snapshot()["denials"] >= 7


def test_retry_budget_refills_on_success_and_unblocks():
    budget = RetryBudget(ratio=1.0, reserve=1.0)
    assert budget.try_withdraw()                  # drain the reserve
    flaky_calls = [0]

    def flaky():
        flaky_calls[0] += 1
        if flaky_calls[0] == 1:
            raise ConnectionResetError("transient")
        return "ok"

    p = RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0,
                    site="serving.route", sleep=lambda d: None,
                    budget=budget)
    with pytest.raises(RetryError, match="exhausted"):
        p.call(flaky)                             # dry: no retry funded
    assert flaky_calls[0] == 1
    p.call(lambda: "fine")                        # success deposits
    assert budget.remaining() == 1.0
    flaky_calls[0] = 0
    assert p.call(flaky) == "ok"                  # retry funded again


def test_budgeted_sites_share_the_default_budget():
    """``RetryPolicy.from_flags`` auto-attaches ONE process-wide bucket
    for every serving site — sharing the object is what makes the
    bound fleet-wide — and leaves per-call sites unbudgeted."""
    reset_default_budget()
    try:
        assert BUDGETED_SITES == ("serving.route", "serving.handoff",
                                  "serving.replica")
        pols = [RetryPolicy.from_flags(s) for s in BUDGETED_SITES]
        shared = default_budget()
        assert all(p.budget is shared for p in pols)
        assert RetryPolicy.from_flags("checkpoint.save").budget is None
        mine = RetryBudget(ratio=0.1, reserve=1.0)
        override = RetryPolicy.from_flags("serving.route", budget=mine)
        assert override.budget is mine            # explicit wins
    finally:
        reset_default_budget()


def test_retry_budget_denial_still_counts_retry_site_stat():
    """The budget gate sits *after* the transient classification:
    non-transient errors never touch the bucket."""
    budget = RetryBudget(ratio=0.1, reserve=5.0)
    p = RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0,
                    site="unit_budget", sleep=lambda d: None,
                    budget=budget)
    with pytest.raises(FileNotFoundError):
        p.call(lambda: (_ for _ in ()).throw(FileNotFoundError("x")))
    assert budget.snapshot()["withdrawals"] == 0
    assert budget.snapshot()["denials"] == 0
