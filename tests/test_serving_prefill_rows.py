"""A prefill dispatch computes the rows it admitted (PR 32).

The rows of a bucket's prefill entry follow the bucket's length: the
model's ``tokens_a_dispatch`` over the bucket, from 1 to ``max_slots``
(``serving/seam.py``). GPT declares 512 tokens, so at ``max_slots`` 4 a
bucket of 32 keeps 4 rows, one of 256 has 2 and one of 512 has 1; a group
of more same-bucket admissions than that goes out as several dispatches in
admission order.

The contracts under test:

- **one behaviour**: the same scripted admissions through an engine with
  rows by bucket and through one forced to ``max_slots`` rows, as before
  PR 32, give every request the same state and the same tokens: 1, 3 and ``max_slots`` prompts at
  once, short and long buckets, a prefix hit among them, one request shed
  by an injected ``serving.step`` skip while the others of its group
  survive in FIFO order;
- **one program a bucket** whatever the admissions, and
  ``predict_serving_compiles`` says the same;
- **the counters say what the dispatches carried**:
  ``prefill_rows_live`` / ``prefill_rows_computed``;
- **a request that is the k-th dispatch of its group waits k of them** in
  the TTFT prediction.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor, observability
from paddle_tpu.analysis.recompile import predict_serving_compiles
from paddle_tpu.models.gpt import (PREFILL_TOKENS_A_DISPATCH, GPTConfig,
                                   GPTForCausalLM)
from paddle_tpu.resilience import fault_scope
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.seam import CacheKind, ServedModel, served

VOCAB = 97
SLOTS = 4
BUCKETS = [32, 256, 512]
MAX_LEN = 544
ROWS = {32: 4, 256: 2, 512: 1}      # 512 tokens a dispatch, 4 slots


def _make_model(seed=7):
    pt.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, max_position_embeddings=640, hidden_size=32,
        num_layers=2, num_heads=4, ffn_hidden_size=64))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _make_model()


def _engine(model, **kw):
    return ServingEngine(model, max_slots=SLOTS, max_len=MAX_LEN,
                         buckets=BUCKETS, block_size=16, max_queue=64,
                         eos_token_id=None, **kw)


def _prompt(rng, n):
    return rng.integers(1, VOCAB, size=n).tolist()


def _script():
    """Rounds of ``(prompts submitted at once, fault spec or None)``."""
    rng = np.random.default_rng(11)
    first = _prompt(rng, 40)
    return [
        ([_prompt(rng, 20)], None),                             # 1, short
        ([_prompt(rng, n) for n in (10, 25, 31)], None),        # 3, short
        ([_prompt(rng, n) for n in (300, 400, 500, 512)], None),  # 4, long
        ([_prompt(rng, n) for n in (100, 200, 250)], None),     # 3, middle
        ([_prompt(rng, 480)], None),                            # 1, long
        ([first], None),
        # a prefix hit (two blocks of `first`) among two misses: the hit's
        # bucket is its unshared suffix's
        ([first + _prompt(rng, 10), _prompt(rng, 20), _prompt(rng, 30)],
         None),
        # the second of three long prompts is shed at its own fault site
        ([_prompt(rng, n) for n in (310, 410, 510)],
         "serving.step:skip@1"),
        # and the second of three that share one short dispatch
        ([_prompt(rng, n) for n in (12, 22, 30)], "serving.step:skip@1"),
    ]


def _play(engine):
    """-> one ``(state, tokens)`` a request, in submission order, and
    the stamps of the survivors' first tokens, a round each; and the
    prefix cache's hits."""
    out, order = [], []
    for prompts, fault in _script():
        if fault:
            with fault_scope(fault):
                reqs = [engine.submit(p, max_new_tokens=5) for p in prompts]
                engine.run_until_idle()
        else:
            reqs = [engine.submit(p, max_new_tokens=5) for p in prompts]
            engine.run_until_idle()
        out.append([(r.state, list(r.tokens)) for r in reqs])
        alive = [r for r in reqs if r.state == "done"]
        order.append([r.first_token_at for r in alive])
        assert engine.cache.num_free == engine.max_slots
    return out, order, engine.cache.prefix_hits


# ------------------------------------------------------------- the rule

@pytest.mark.parametrize("budget, bucket, slots, rows", [
    (None, 64, 8, 8), (None, 1024, 8, 8),       # nothing declared
    (512, 64, 8, 8), (512, 128, 8, 4), (512, 256, 8, 2), (512, 512, 8, 1),
    (512, 768, 8, 1), (512, 1024, 8, 1),        # GPT's at the cells' slots
    (512, 16, 2, 2), (512, 64, 4, 4),           # never over max_slots
    (1, 16, 8, 1), (1, 12288, 16, 1),           # one prompt a dispatch
])
def test_rows_follow_the_bucket(budget, bucket, slots, rows):
    spec = ServedModel(
        model=None, family="toy", max_positions=64, vocab=8,
        cache_kinds=(CacheKind("full", (0,), 2, 4),),
        tokens_a_dispatch=budget)
    assert spec.prefill_rows(bucket, slots) == rows


def test_gpt_declares_its_budget(model):
    spec = served(model)
    assert spec.tokens_a_dispatch == PREFILL_TOKENS_A_DISPATCH == 512
    assert {b: spec.prefill_rows(b, SLOTS) for b in BUCKETS} == ROWS
    # the engine's entry is built for those rows, and keyed by them
    engine = _engine(model)
    keys = {engine._prefill_entry(b)["fn"] for b in BUCKETS}
    assert len(keys) == 3
    cache = model._step_compile_cache
    assert sorted(k[1:3] for k in cache if k[0] == "prefill_paged") == \
        [(32, 4), (256, 2), (512, 1)]


# ------------------------------------------------------- one behaviour

@pytest.fixture(scope="module")
def by_bucket(model):
    return _play(_engine(model))


def test_tokens_are_those_of_max_slots_rows(model, by_bucket):
    wide = _engine(model)
    wide.spec.tokens_a_dispatch = None
    assert all(wide.spec.prefill_rows(b, SLOTS) == SLOTS for b in BUCKETS)
    want, _, wide_hits = _play(wide)
    got, _, hits = by_bucket
    assert hits == wide_hits >= 1
    for n, (a, b) in enumerate(zip(got, want)):
        assert a == b, f"round {n}"
    # every round completed what it should have
    states = [[s for s, _ in rnd] for rnd in got]
    assert states[:7] == [["done"] * len(r) for r in states[:7]]
    assert states[7] == states[8] == ["done", "shed", "done"]
    assert all(len(t) == 5 for rnd in got for s, t in rnd if s == "done")


def test_a_group_goes_out_in_admission_order(by_bucket):
    _, order, _ = by_bucket
    for stamps in order:
        assert stamps == sorted(stamps)
    # four long prompts at once are four dispatches: four distinct stamps
    assert len(set(order[2])) == 4
    # three short ones share a dispatch and its one commit stamp
    assert len(set(order[1])) == 1


def test_a_prefix_hit_rides_its_suffix_bucket():
    model = _make_model(9)
    engine = _engine(model)
    rng = np.random.default_rng(5)
    first = _prompt(rng, 300)               # bucket 512, one row
    engine.submit(first, max_new_tokens=2)
    engine.run_until_idle()
    before = engine.stats()
    req = engine.submit(first + _prompt(rng, 10), max_new_tokens=2)
    engine.run_until_idle()
    after = engine.stats()
    assert req.state == "done"
    assert engine.cache.prefix_hits >= 1
    # 288 shared tokens: the suffix of 22 falls in bucket 32 (4 rows)
    assert after["prefill_rows_live"] - before["prefill_rows_live"] == 1
    assert after["prefill_rows_computed"] - \
        before["prefill_rows_computed"] == ROWS[32]


# ------------------------------------------------ one program a bucket

def _prefill_compiles():
    return {k: v["count"] for k, v in observability.compiles().items()
            if k.startswith("serving_prefill_paged")}


def test_one_compile_a_bucket_whatever_the_admissions():
    model = _make_model(13)
    engine = _engine(model, prefix_cache=False)
    rng = np.random.default_rng(3)
    before = _prefill_compiles()
    rounds = []
    for lens in ((20,), (300,), (100,),                 # one at a time
                 (10, 25, 31), (300, 400, 500), (100, 200, 250),
                 (5, 6, 7, 8), (290, 390, 490, 512), (90, 190, 230, 256)):
        prompts = [_prompt(rng, n) for n in lens]
        rounds.append([(p, 3) for p in prompts])
        reqs = [engine.submit(p, max_new_tokens=3) for p in prompts]
        engine.run_until_idle()
        assert all(r.state == "done" for r in reqs)
    after = _prefill_compiles()
    grew = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}
    want = {f"serving_prefill_paged{{bucket={b}}}": 1 for b in BUCKETS}
    assert grew == want
    predicted = predict_serving_compiles(
        rounds, buckets=BUCKETS, max_len=MAX_LEN, block_size=16,
        prefix_cache=False)
    assert {k: v for k, v in predicted.items()
            if k.startswith("serving_prefill_paged")} == want
    assert all(ent["traces"]["count"] == 1 for ent in engine._prefill_fns.values())


# --------------------------------------------------------- the counters

@pytest.mark.parametrize("lens, live, computed", [
    ((500,), 1, 1),                 # a long bucket reads 100%
    ((20,), 1, 4),                  # a short one with one prompt 1 / rows
    ((10, 20, 30, 31), 4, 4),       # and 100% when the burst fills it
    ((100, 200, 250), 3, 4),        # two dispatches of two rows
    ((300, 400, 500, 512), 4, 4),   # four dispatches of one
], ids=["long-1", "short-1", "short-4", "middle-3", "long-4"])
def test_the_counters_say_what_the_dispatches_carried(model, lens, live,
                                                      computed):
    engine = _engine(model, prefix_cache=False)
    rng = np.random.default_rng(17)
    stat = [monitor.stat_get("STAT_serving_prefill_rows_live"),
            monitor.stat_get("STAT_serving_prefill_rows_computed")]
    assert engine.stats()["prefill_rows_computed"] == 0
    for n in lens:
        engine.submit(_prompt(rng, n), max_new_tokens=2)
    engine.run_until_idle()
    s = engine.stats()
    assert (s["prefill_rows_live"], s["prefill_rows_computed"]) == \
        (live, computed)
    assert monitor.stat_get("STAT_serving_prefill_rows_live") \
        - stat[0] == live
    assert monitor.stat_get("STAT_serving_prefill_rows_computed") \
        - stat[1] == computed


def test_a_shed_row_is_not_counted_live(model):
    engine = _engine(model, prefix_cache=False)
    rng = np.random.default_rng(19)
    with fault_scope("serving.step:skip@0"):
        reqs = [engine.submit(_prompt(rng, n), max_new_tokens=2)
                for n in (12, 22)]
        engine.run_until_idle()
    assert [r.state for r in reqs] == ["shed", "done"]
    s = engine.stats()
    assert (s["prefill_rows_live"], s["prefill_rows_computed"]) == (1, 4)


# ------------------------------------------------- the TTFT prediction

def test_the_kth_dispatch_of_a_group_waits_k_of_them(model):
    engine = _engine(model, slo_ttft_ms=1e6, slo_prefill_ms=10.0,
                     slo_tpot_ms=1.0)
    long = [engine.predict_ttft_ms(prompt_len=500, queue_ahead=q)
            for q in range(4)]
    short = [engine.predict_ttft_ms(prompt_len=20, queue_ahead=q)
             for q in range(4)]
    # one prompt a dispatch in the long bucket: each one ahead is one more
    assert [b - a for a, b in zip(long, long[1:])] == \
        pytest.approx([10.0] * 3)
    # four rows in the short one: up to four ahead are one dispatch
    assert short == pytest.approx([10.0, 20.0, 20.0, 20.0])
    assert all(b >= a for a, b in zip(short, short[1:]))


# ------------------------------------- lost pools between two dispatches

def test_a_later_dispatch_does_not_read_a_prefix_the_pools_took(
        monkeypatch):
    """Three long prompts admitted at once are three dispatches. The first
    fails after consuming the pools: the rebuilt pools hold nothing of the
    prefix the second acquired before, so it is shed too, and the third,
    which shares nothing, is served as a clean engine serves it."""
    model = _make_model(29)
    engine = _engine(model)
    rng = np.random.default_rng(31)
    first = _prompt(rng, 300)
    engine.submit(first, max_new_tokens=2)
    engine.run_until_idle()
    a, c = _prompt(rng, 400), _prompt(rng, 450)
    b = first[:64] + _prompt(rng, 300)      # four shared blocks, bucket 512
    ent = engine._prefill_entry(512)
    real, calls = ent["fn"], []

    def consume_then_raise(*args):
        calls.append(1)
        out = real(*args)
        if len(calls) == 1:
            raise RuntimeError("device fault after the pools were donated")
        return out

    monkeypatch.setitem(ent, "fn", consume_then_raise)
    reqs = [engine.submit(p, max_new_tokens=4) for p in (a, b, c)]
    engine.run_until_idle()
    monkeypatch.undo()
    assert [r.state for r in reqs] == ["shed", "shed", "done"]
    assert "shared prefix" in str(reqs[1].error)
    assert len(calls) == 2 and engine.cache.num_free == SLOTS
    clean = _engine(model, prefix_cache=False)
    ref = clean.submit(c, max_new_tokens=4)
    clean.run_until_idle()
    assert reqs[2].tokens == ref.tokens
