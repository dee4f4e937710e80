"""Recurrent state as a third kind of the one cache manager
(``serving/seam.StateKind``, ``serving/kv_cache._StateKind``) and the
engine over it: the manager's invariants for three kinds, prefill then
decode through the engine against the reference's full forward (logits,
not tokens) with prompts of unequal length sharing one dispatch and one
bucket, slots reused by a second wave, cancel and re-admit, and what the
engine reports."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu import monitor                                 # noqa: E402
from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (GPT_CONFIGS, GPTForCausalLM,    # noqa: E402
                               JAMBA_CONFIGS, JambaForCausalLM)
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from paddle_tpu.serving import kv_cache                        # noqa: E402
from paddle_tpu.serving.kv_cache import BlockKVCache           # noqa: E402
from paddle_tpu.serving.seam import (CacheKind, ServedModel,   # noqa: E402
                                     StateKind)
from perfbench.families import jamba as family                 # noqa: E402

TINY = JAMBA_CONFIGS["jamba-tiny"]
WINDOW, BS, SLOTS = 16, 8, 4
STATE = StateKind("rec", (1, 4), (((3, 6), "bfloat16"), ((2, 6), "float32")))


def three_kind_cache(num_blocks=0, max_len=128):
    spec = ServedModel(
        model=None, family="toy", max_positions=max_len, vocab=8,
        cache_kinds=(CacheKind("full", (0, 3), 2, 4),
                     CacheKind("window", (2,), 2, 4, window=WINDOW)),
        state_kinds=(STATE,), kv_dtype="f32", features=frozenset())
    assert spec.num_layers == 5
    return BlockKVCache.for_model(spec, SLOTS, max_len, block_size=BS,
                                  num_blocks=num_blocks, prefix_cache=False,
                                  kv_dtype="f32")


# ------------------------------------------------------------ the manager

def test_the_arrays_go_out_in_the_models_order_and_come_back_by_kind():
    c = three_kind_cache(num_blocks=20)
    arrays = c.arrays()
    assert len(arrays) == 5
    shapes = [tuple(a.shape for a in layer) for layer in arrays]
    assert shapes[1] == shapes[4] == ((SLOTS, 3, 6), (SLOTS, 2, 6))
    assert [str(a.dtype) for a in arrays[1]] == ["bfloat16", "float32"]
    assert shapes[0] == shapes[3] == ((20, 2, BS, 4),) * 2
    assert shapes[2][0][0] == SLOTS * (WINDOW // BS + 1) + 1
    marked = [tuple(a + i for a in layer) for i, layer in enumerate(arrays)]
    c.set_arrays(marked)
    back = c.arrays()
    for i, layer in enumerate(back):
        assert all(float(a.reshape(-1)[0]) == i for a in layer)
    (st,) = c._states
    assert float(st.layers[1][1][0, 0, 0]) == 4.0
    c.rebuild_pools()
    assert all(float(jnp.abs(a).max()) == 0.0
               for layer in c.arrays() for a in layer)
    assert c.state_bytes == SLOTS * 2 * (3 * 6 * 2 + 2 * 6 * 4)


def test_the_state_kinds_entry_of_the_tables_is_the_row():
    c = three_kind_cache(num_blocks=20)
    a, _ = c.acquire([1] * 10, 40)
    b, _ = c.acquire([1] * 10, 40)
    full, window, rows = c.tables_arg()
    np.testing.assert_array_equal(rows, np.arange(SLOTS))
    assert full.shape == window.shape == (SLOTS, 128 // BS)
    full, window, rows = c.table_rows([b], 3)
    assert full.shape == (3, 128 // BS)
    # the admitted row, then rows no request has: out of range, dropped
    np.testing.assert_array_equal(rows, [b, SLOTS, SLOTS])
    c.release_row(a)
    c.release_row(b)


def test_the_invariants_hold_for_three_kinds():
    """num_used counts rows, admission never over-commits, a recurrent
    kind has no blocks and leaks none, and after cancel and flush only the
    trash block is referenced."""
    c = three_kind_cache(num_blocks=20)         # 19 usable full blocks
    assert (c.num_used, c.state_rows_live) == (0, 0)
    rows = []
    for _ in range(SLOTS):
        got = c.acquire([1] * 30, 48)           # 6 full blocks each
        if got is None:
            break
        rows.append(got[0])
    assert len(rows) == 3                       # the fourth: 24 > 19
    assert c.num_used == c.state_rows_live == 3 and c.num_free == 1
    assert c.kind_stats()["kv_blocks_live_full"] == 18
    c.commit_prefill(rows[0], 30)
    c.advance(rows[0], 5)
    for row in rows:
        c.release_row(row)
    c.flush_prefix_cache()
    assert c.allocator.leaked() == 1            # the one trash block
    assert (c.num_used, c.state_rows_live) == (0, 0)
    # the state is allocated whole, rows live or not
    assert c.state_bytes == SLOTS * 2 * (3 * 6 * 2 + 2 * 6 * 4)


def test_a_recurrent_kind_refuses_what_it_cannot_lend():
    spec = ServedModel(
        model=None, family="toy", max_positions=64, vocab=8,
        cache_kinds=(CacheKind("full", (0,), 2, 4),),
        state_kinds=(StateKind("rec", (1,), (((2, 6), "float32"),)),),
        kv_dtype="f32", features=frozenset())
    with pytest.raises(ValueError, match="recurrent state has no prefix"):
        BlockKVCache.for_model(spec, SLOTS, 64, block_size=BS, num_blocks=0,
                               prefix_cache=True, kv_dtype="f32")
    c = BlockKVCache.for_model(spec, SLOTS, 64, block_size=BS, num_blocks=0,
                               prefix_cache=False, kv_dtype="f32")
    row, _ = c.acquire([1] * 10, 20)
    with pytest.raises(ValueError, match="not handed off"):
        c.export_row(row)


def test_a_model_with_no_recurrent_kind_keeps_its_cache_and_its_gauges():
    layers.seed(3)
    gpt = GPTForCausalLM(GPT_CONFIGS["gpt2-tiny"])
    gpt.eval()
    engine = ServingEngine(gpt, max_slots=2, max_len=64, buckets=[16],
                           block_size=8, num_blocks=0, prefix_cache=False)
    assert engine.cache._order is None and engine.cache._states == []
    assert isinstance(engine.cache.tables_arg(), np.ndarray)
    req = engine.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    engine.run_until_idle()
    assert req.state == "done"
    stats = engine.stats()
    assert (stats["state_bytes"], stats["state_rows_live"]) == (0, 0)
    assert (stats["prefill_tokens_live"],
            stats["prefill_tokens_computed"]) == (5, 16 * 2)


# ------------------------------------------------------------- the engine

def build(mc=TINY, seed=3):
    layers.seed(seed)
    model = JambaForCausalLM(mc)
    model.eval()
    return model, {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def tiny():
    return build()


class Tap:
    """Records the logits the engine's own compiled decode entry returns,
    by request and position, and the rows each prefill dispatch carried."""

    def __init__(self, engine):
        self.rows, self.dispatches = {}, []
        real_decode, real_prefill = (engine.spec.decode_entry,
                                     engine._prefill_group_attempt)

        def decode_entry(*a, **kw):
            ent = real_decode(*a, **kw)

            def fn(*args):
                out = ent["fn"](*args)
                lengths = np.asarray(args[1])
                for slot, req in engine._active.items():
                    made = int(lengths[slot]) - len(req.prompt) + 1
                    if made >= req.max_new_tokens:
                        # dispatched ahead of the commit that ends this
                        # request by budget: the row is for nobody
                        continue
                    self.rows.setdefault(req.id, []).append(
                        np.asarray(out[1][slot]))
                return out
            return dict(ent, fn=fn)

        def prefill_group_attempt(bucket, group):
            self.dispatches.append(
                (bucket, [len(req.context) for req, _, _ in group]))
            return real_prefill(bucket, group)
        engine.spec.decode_entry = decode_entry
        engine._prefill_group_attempt = prefill_group_attempt


def engine_of(model, **kw):
    kw = dict(dict(max_slots=4, max_len=128, buckets=[16, 32, 64],
                   block_size=8, num_blocks=0, prefix_cache=False,
                   max_queue=16, eos_token_id=None), **kw)
    engine = ServingEngine(model, **kw)
    return engine, Tap(engine)


def file_of(mc):
    return {f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)}


def worst_against_the_reference(params, reqs, tap, mc=TINY):
    """(largest |decode logits - reference|, largest deficit of an emitted
    token) over ``reqs``, the reference run on each final sequence."""
    cfg = file_of(mc)
    worst_logit = worst_deficit = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ref = np.asarray(family.forward(
            params, jnp.asarray([seq], jnp.int32), cfg)[0])
        p, n = len(r.prompt), len(r.tokens)
        got = np.stack(tap.rows[r.id])
        assert got.shape[0] == n - 1
        worst_logit = max(worst_logit,
                          float(np.abs(got - ref[p:p + n - 1]).max()))
        d = ref[p - 1:p + n - 1].max(-1) \
            - ref[np.arange(p - 1, p + n - 1), seq[p:]]
        worst_deficit = max(worst_deficit, float(d.max()))
    return worst_logit, worst_deficit


def wave(engine, rng, lengths, new):
    return [engine.submit(rng.integers(1, 512, n).tolist(),
                          max_new_tokens=k) for n, k in zip(lengths, new)]


def test_prefill_then_decode_through_the_engine_matches_the_reference(tiny):
    """Four prompts of unequal length (5-16 rows) share ONE dispatch of the
    16-row bucket: each one's state is taken at its own last token. A
    second wave reuses their slots as they free (a stale state would
    show); one request is cancelled while it decodes and another admitted
    into its row."""
    model, params = tiny
    engine, tap = engine_of(model)
    rng = np.random.default_rng(1)
    first = wave(engine, rng, (5, 13, 16, 9), (12, 20, 8, 16))
    engine.step()
    assert tap.dispatches[0] == (16, [5, 13, 16, 9])
    second = wave(engine, rng, (30, 20, 40, 3), (10, 14, 9, 12))
    victim = wave(engine, rng, (11,), (40,))[0]
    while victim.state != "running" or len(victim.tokens) < 5:
        engine.step()
    engine.cancel(victim.id)
    late = wave(engine, rng, (7, 25), (10, 6))
    engine.run_until_idle()
    reqs = first + second + late
    assert all(r.state == "done" for r in reqs)
    assert victim.state == "canceled"
    # the later ones were admitted as slots freed, one a dispatch
    assert len(tap.dispatches) > 4
    worst_logit, worst_deficit = worst_against_the_reference(params, reqs,
                                                             tap)
    assert worst_logit < 5e-5
    assert worst_deficit == 0.0
    stats = engine.stats()
    assert stats["state_rows_live"] == 0 and stats["kv_blocks_live_full"] == 0
    assert stats["state_bytes"] == engine.cache.state_bytes \
        == 4 * 3 * (3 * 128 * 4 + 8 * 128 * 4)
    engine.cache.flush_prefix_cache()
    assert engine.cache.allocator.leaked() == 1      # the one trash block
    # every dispatch handed its pools AND its state over, in place
    assert stats["pool_inplace"] == stats["pool_dispatches"] > 20


@pytest.mark.parametrize("fault", ["stale_state", "state_at_the_buckets_end",
                                   "tail_off_by_one"])
def test_a_planted_fault_in_the_hand_over_fails_the_same_comparison(
        monkeypatch, fault):
    """The comparison above has the power it claims: a prefill that leaves
    the slot's old state, one that takes the state at the bucket's end (the
    padding advanced it) and a convolution tail one row late each move the
    decode logits by far more than its tolerance. (A model of its own:
    the compiled entries are cached by model, and a faulty one must not
    outlive the test.)"""
    model, params = build()
    if fault == "stale_state":
        monkeypatch.setattr(
            kv_cache._StateKind, "pick",
            lambda self, rows, n: np.full(n, self.max_slots, np.int32))
    elif fault == "state_at_the_buckets_end":
        from paddle_tpu.ops import ssm_ops
        real = ssm_ops.selective_scan

        def at_the_end(x, dt, a, b, c, d_skip, z, last):
            return real(x, dt, a, b, c, d_skip, z,
                        jnp.full_like(last, x.shape[1] - 1))
        monkeypatch.setattr(ssm_ops, "selective_scan", at_the_end)
    else:
        from paddle_tpu.ops import ssm_ops
        real_tail = ssm_ops.conv_tail
        monkeypatch.setattr(ssm_ops, "conv_tail",
                            lambda xp, last, k: real_tail(xp, last - 1, k))
    engine, tap = engine_of(model)
    rng = np.random.default_rng(2)
    reqs = wave(engine, rng, (5, 13, 16, 9), (6, 6, 6, 6))
    engine.run_until_idle()
    reqs += wave(engine, rng, (12, 7), (6, 6))          # reused slots
    engine.run_until_idle()
    worst_logit, _ = worst_against_the_reference(params, reqs, tap)
    assert worst_logit > 1e-2


def test_the_engine_counts_the_positions_its_prefills_scan(tiny):
    model, _ = tiny
    engine, tap = engine_of(model)
    before = [monitor.stat_get("STAT_serving_prefill_tokens_live"),
              monitor.stat_get("STAT_serving_prefill_tokens_computed")]
    rng = np.random.default_rng(3)
    wave(engine, rng, (5, 9), (2, 2))           # one dispatch: 4 rows x 16
    engine.run_until_idle()
    wave(engine, rng, (40,), (2,))              # one row x 64
    engine.run_until_idle()
    s = engine.stats()
    assert (s["prefill_tokens_live"], s["prefill_tokens_computed"]) == \
        (5 + 9 + 40, 4 * 16 + 64)
    assert (s["prefill_rows_live"], s["prefill_rows_computed"]) == (3, 5)
    assert monitor.stat_get("STAT_serving_prefill_tokens_live") \
        - before[0] == 54
    assert monitor.stat_get("STAT_serving_prefill_tokens_computed") \
        - before[1] == 128
