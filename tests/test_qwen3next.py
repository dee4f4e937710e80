"""Qwen3-Next-class hybrid decoder (``models/qwen3next.py``) against its
plain reference (``perfbench/families/qwen3next.py``) at a small size on
the CPU, seeded random weights: the program's forward (logits and every
layer's output); the three forms of the gated delta rule against one
another with and without right padding, and the hand-over of ``S`` at
``last``; one token against the carried state through the decode kernel's
row table; prefill then decode through the engine against the reference's
full forward (logits, not tokens); the four shares of the experts adding up
to the uncut layer; and each of those failing when its fault is planted."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (QWEN3NEXT_CONFIGS,              # noqa: E402
                               Qwen3NextConfig, Qwen3NextForCausalLM)
from paddle_tpu.models import qwen3next as program             # noqa: E402
from paddle_tpu.ops import gated_delta_ops as gdn              # noqa: E402
from paddle_tpu.ops.pallas import gated_delta as kernel        # noqa: E402
from perfbench.families import qwen3next as family             # noqa: E402
from test_serving_state_kind import engine_of, wave            # noqa: E402

TINY = QWEN3NEXT_CONFIGS["qwen3next-tiny"]


def file_of(mc):
    """The family's view of a program configuration."""
    cfg = {f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)}
    lo, hi = mc.experts
    return dict(cfg, num_experts=hi - lo,
                expert_share=mc.num_experts // (hi - lo))


def build(mc=TINY, seed=3):
    layers.seed(seed)
    model = Qwen3NextForCausalLM(mc)
    model.eval()
    return model, {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def tiny():
    return build()


def err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# ---------------------------------------------------------------- the model

def test_the_defaults_are_the_published_model():
    mc = Qwen3NextConfig()
    assert mc.num_params() == 79_674_391_296           # the published 80B
    assert mc.layers_of("full_attention") == tuple(range(3, 48, 4))
    assert len(mc.layers_of("linear_attention")) == 36
    assert (mc.key_dim, mc.value_dim, mc.conv_dim, mc.rotary_dim) == \
        (2048, 4096, 8192, 64)
    assert mc.state_arrays() == (((3, 8192), "bfloat16"),
                                 ((32, 128, 128), "float32"))
    model, params = build()
    assert sum(int(np.prod(p.shape)) for p in params.values()) \
        == TINY.num_params()
    assert "lm_head.weight" in params                   # the head is untied
    # the recurrence's leaves are float32 whatever the matrices are
    assert {str(params[f"model.layers.0.gdn.{n}"].dtype)
            for n in ("A_log", "dt_bias", "conv_weight")} == {"float32"}
    # a layer's mixers by the published bytes' count
    one = dataclasses.replace(mc, num_hidden_layers=4, layer_types=(),
                              num_attention_heads_per_layer=(),
                              mlp_layer_types=(), held_experts=(0, 128))
    linear, full, rest = 33_718_464, 27_263_488, 4_196_352 + 4_096
    assert one.num_params() == 2 * 151936 * 2048 + 2048 \
        + 3 * linear + full + 4 * (rest + 128 * 3_145_728)


def test_the_forward_matches_the_reference(tiny):
    model, params = tiny
    ids = np.random.default_rng(0).integers(1, 512, (2, 64))
    got_layers, want_layers = [], []
    got = model(ids, collect=got_layers).value
    want = family.forward(params, jnp.asarray(ids), file_of(TINY),
                          collect=want_layers)
    assert err(got, want) < 5e-6
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert len(got_layers) == len(want_layers) == TINY.num_hidden_layers
    for g, w in zip(got_layers, want_layers):
        assert err(g, w) < 2e-6


@pytest.mark.parametrize("fault", ["plain_norm", "gate_dropped",
                                   "whole_rotary", "beta_one"])
def test_a_departure_from_the_equations_fails_the_forward(monkeypatch,
                                                          fault):
    """The forward's comparison has the power it claims: a norm whose gain
    is ``w`` and not ``1 + w``, the shared expert's gate dropped, a rotary
    over the whole head and a write of the whole error (``beta`` = 1) each
    move the logits by far more than its tolerance."""
    if fault == "plain_norm":
        monkeypatch.setattr(
            program, "zero_centred_rms",
            lambda x, w, eps: x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * (0.5 + w.astype(jnp.float32)))
    elif fault == "gate_dropped":
        monkeypatch.setattr(program.Qwen3NextMoE, "_shared",
                            lambda self, u: self.shared(u))
    elif fault == "whole_rotary":
        monkeypatch.setattr(Qwen3NextConfig, "rotary_dim",
                            property(lambda self: self.head_dim))
    else:
        real = gdn.gated_delta_rule
        monkeypatch.setattr(
            gdn, "gated_delta_rule",
            lambda q, k, v, g, beta, last: real(
                q, k, v, g, jnp.ones_like(beta), last))
    model, params = build()
    ids = np.random.default_rng(0).integers(1, 512, (1, 64))
    want = family.forward(params, jnp.asarray(ids), file_of(TINY))
    assert err(model(ids).value, want) > 1e-3


# ---------------------------------------------------------------- the rule

def rule_inputs(b, t, hk, hv, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gdn.l2norm(jax.random.normal(ks[0], (b, t, hk, dk))) * dk ** -0.5
    k = gdn.l2norm(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (b, t, hv)) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return tuple(a.astype(jnp.float32) for a in (q, k, v, g, beta))


#: 256 rows of time in chunks of 64
LASTS = {"inside": (100, 37), "at_the_edge": (127, 63),
         "first_of_a_chunk": (128, 64), "ends": (255, 0)}


@pytest.mark.parametrize("form", ["chunked", "kernel"])
@pytest.mark.parametrize("where", sorted(LASTS))
def test_a_form_of_the_rule_is_the_sequential_one(form, where):
    """``o`` on every row up to ``last`` and ``S`` AT ``last``: the rows
    behind it (a bucket's padding) neither decay nor write."""
    args = rule_inputs(2, 256, 2, 4, 128, 128)
    last = jnp.asarray(LASTS[where], jnp.int32)
    o0, s0 = gdn.gated_delta_sequential(*args, last)
    if form == "chunked":
        o1, s1 = gdn.gated_delta_chunked(*args, last)
    else:
        assert kernel.tiles(256, 128, 128)
        g, beta = gdn.mask_past_last(args[3], args[4], last)
        o1, s1 = kernel.gdn_prefill(*args[:3], g, beta, last,
                                    interpret=True)
    for row, at in enumerate(LASTS[where]):
        assert err(o1[row, :at + 1], o0[row, :at + 1]) < 2e-6
    assert err(s1, s0) < 2e-6
    assert float(jnp.max(jnp.abs(s0))) > 0.3
    # a prompt cut at `last` hands over the same S
    at = LASTS[where][1]
    _, cut = gdn.gated_delta_sequential(
        *(a[1:, :at + 1] for a in args), jnp.asarray([at], jnp.int32))
    assert err(cut[0], s1[1]) < 2e-6
    # the state at `last` is not the state at the end
    _, s_end = gdn.gated_delta_sequential(
        *args, jnp.full((2,), 255, jnp.int32))
    if where != "ends":
        assert err(s_end, s0) > 0.05


def test_a_state_advanced_over_the_padding_is_another_state():
    """What ``mask_past_last`` guards: without it the kernel's final state
    is the state at the bucket's end."""
    args = rule_inputs(1, 128, 2, 4, 128, 128, seed=1)
    last = jnp.asarray([70], jnp.int32)
    _, s0 = gdn.gated_delta_sequential(*args, last)
    _, s1 = kernel.gdn_prefill(*args, last, interpret=True)
    assert err(s1, s0) > 0.05


def test_a_bfloat16_state_fails_the_forms_tolerance():
    args = rule_inputs(1, 256, 2, 4, 128, 128, seed=2)
    last = jnp.asarray([255], jnp.int32)
    o0, s0 = gdn.gated_delta_sequential(*args, last)
    o1, s1 = gdn.gated_delta_sequential(*args, last,
                                        state_dtype=jnp.bfloat16)
    assert err(s1, s0) > 1e-3 and err(o1, o0) > 2e-4


def test_the_kernel_tiles_whole_lanes_and_whole_chunks_only():
    assert kernel.tiles(8192, 128, 128) and kernel.tiles(64, 128, 256)
    assert not kernel.tiles(8192, 96, 128)      # not whole lanes
    assert not kernel.tiles(100, 128, 128)      # not whole chunks
    with pytest.raises(ValueError, match="cannot tile"):
        kernel.gdn_prefill(*rule_inputs(1, 64, 2, 4, 16, 16),
                           jnp.zeros((1,), jnp.int32), interpret=True)


ROWS = {"every_row": (0, 1, 2, 3, 4, 5), "reversed": (5, 4, 3, 2, 1, 0),
        "some_are_none": (6, 3, 6, 1, 0, 6), "none_at_all": (6,) * 6,
        "none_first_and_last": (6, 6, 2, 5, 6, 6)}


@pytest.mark.parametrize("which", sorted(ROWS))
def test_one_token_rewrites_the_rows_its_table_names(which):
    """``gdn_decode`` against the plain step: row ``i`` of the step reads
    and rewrites the state's row ``rows[i]``; a row that is none reads and
    writes nothing, wherever it stands among the others."""
    slots = 6
    q, k, v, g, beta = (a[:, 0] for a in rule_inputs(6, 1, 2, 4, 128, 128,
                                                     seed=3))
    state = jax.random.normal(jax.random.PRNGKey(9), (slots, 4, 128, 128),
                              jnp.float32)
    rows = jnp.asarray(ROWS[which], jnp.int32)
    o0, new = gdn.gated_delta_step_plain(
        q, k, v, g, beta, state[jnp.minimum(rows, slots - 1)])
    want = state.at[rows].set(new, mode="drop")
    o1, got = kernel.gdn_decode(q, k, v, g, beta, state, rows,
                                interpret=True)
    assert err(got, want) < 2e-6
    live = np.asarray(rows) < slots
    if live.any():
        assert err(o1[live], o0[live]) < 2e-6
        assert err(want, state) > 0.1


def test_one_token_against_the_carried_state_continues_the_rule():
    args = rule_inputs(3, 40, 2, 4, 16, 16, seed=4)
    last = jnp.asarray([38, 20, 0], jnp.int32)
    o, s = gdn.gated_delta_sequential(*args, last)
    at = last + 1
    pick = lambda a: a[jnp.arange(3), at]                     # noqa: E731
    o1, s1 = gdn.gated_delta_step(*(pick(a) for a in args), s,
                                  jnp.arange(3, dtype=jnp.int32))
    _, s_next = gdn.gated_delta_sequential(*args, at)
    np.testing.assert_allclose(s1, s_next, rtol=2e-5, atol=2e-6)
    full, _ = gdn.gated_delta_sequential(*args, jnp.full((3,), 39,
                                                         jnp.int32))
    np.testing.assert_allclose(o1, pick(full), rtol=2e-5, atol=2e-6)


# -------------------------------------------------------------- the shares

@pytest.mark.parametrize("fault", [None, "off_by_one_block", "gate_dropped"])
def test_the_four_shares_and_the_shared_expert_add_up_to_the_layer(fault):
    """The routed parts of the four shares (experts 0-3, .., 12-15 of the
    toy's 16) plus the shared expert counted ONCE equal the uncut
    reference's expert block, and each share's layer is the reference
    given the same share; a share whose experts are off by one block, or a
    shared expert without its gate, does not add up."""
    whole = dataclasses.replace(TINY, num_hidden_layers=1, layer_types=(),
                                num_attention_heads_per_layer=(),
                                mlp_layer_types=())
    _, params = build(whole, seed=5)
    pre = "model.layers.0.moe."
    u = jnp.asarray(np.random.default_rng(6).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        once = family.shared_expert(u, params, pre)
        want = family.routed(u, params, pre, file_of(whole)) + once
        total = once
        for lo in range(0, 16, 4):
            mc = dataclasses.replace(whole, held_experts=(lo, lo + 4))
            moe = program.Qwen3NextMoE(mc)
            first = (lo + 4) % 16 if fault == "off_by_one_block" and lo == 4 \
                else lo
            for name, p in moe.named_parameters():
                full = params[pre + name]
                p.value = full[first:first + 4] \
                    if name.startswith("experts_") else full
            if fault == "gate_dropped":
                moe._shared = moe.shared
            out = moe.served(u[None], None)[0][0]
            held = {pre + n: p.value for n, p in moe.named_parameters()}
            if fault is None:
                # the program's share is the reference given the same share
                assert err(out, family.routed(u, held, pre, file_of(mc),
                                              (lo, lo + 4)) + once) < 2e-6
            total = total + out - once
    if fault is None:
        assert err(total, want) < 5e-6
        assert float(jnp.max(jnp.abs(want - once))) > 1e-3
    else:
        assert err(total, want) > 1e-4


# -------------------------------------------------------------- the engine

_REFERENCES = {}


def reference(mc, state_dtype=jnp.float32):
    """The family's forward on one sequence right-padded to 128 rows (a
    row's logits do not depend on the rows behind it), jitted once a
    (configuration, state dtype)."""
    key = (id(mc), jnp.dtype(state_dtype).name)
    if key not in _REFERENCES:
        cfg = file_of(mc)
        _REFERENCES[key] = jax.jit(lambda params, ids: family.forward(
            params, ids, cfg, state_dtype=state_dtype)[0])
    return _REFERENCES[key]


def worst_against_the_reference(params, reqs, tap, mc=TINY, **kw):
    """(largest |decode logits - reference|, largest deficit of an emitted
    token) over ``reqs``, the reference run on each final sequence."""
    worst_logit = worst_deficit = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ids = np.zeros((1, 128), np.int32)
        ids[0, :len(seq)] = seq
        ref = np.asarray(reference(mc, **kw)(params, jnp.asarray(ids)))
        p, n = len(r.prompt), len(r.tokens)
        got = np.stack(tap.rows[r.id])
        assert got.shape[0] == n - 1
        worst_logit = max(worst_logit,
                          float(np.abs(got - ref[p:p + n - 1]).max()))
        d = ref[p - 1:p + n - 1].max(-1) \
            - ref[np.arange(p - 1, p + n - 1), seq[p:]]
        worst_deficit = max(worst_deficit, float(d.max()))
    return worst_logit, worst_deficit


#: the decode logits' tolerance: float32 program against float32 reference,
#: the chunked rule against the scan over time, a few dozen tokens through
#: eight layers; a bfloat16 state or a bfloat16 router reads 20x over it
LOGITS = 2e-5


def test_prefill_then_decode_through_the_engine_matches_the_reference(tiny):
    """Four prompts of unequal length (5-16 rows) share ONE dispatch of the
    16-row bucket: each one's ``S`` and tail are taken at its own last
    token. A second wave reuses their slots as they free (a stale state
    would show); one request is cancelled while it decodes and another
    admitted into its row."""
    model, params = tiny
    spec = model.serving_spec()
    (blocks,), (state,) = spec.cache_kinds, spec.state_kinds
    assert (blocks.layers, blocks.kv_heads, blocks.head_dim) == \
        ((3, 7), 2, 32)
    assert state.layers == (0, 1, 2, 4, 5, 6)
    assert state.arrays == (((3, 128), "float32"), ((4, 16, 16), "float32"))
    assert spec.features == frozenset()
    engine, tap = engine_of(model)
    rng = np.random.default_rng(1)
    first = wave(engine, rng, (5, 13, 16, 9), (12, 30, 8, 16))
    engine.step()
    assert tap.dispatches[0] == (16, [5, 13, 16, 9])
    second = wave(engine, rng, (30, 20, 40, 3), (10, 24, 9, 12))
    victim = wave(engine, rng, (11,), (40,))[0]
    while victim.state != "running" or len(victim.tokens) < 5:
        engine.step()
    engine.cancel(victim.id)
    late = wave(engine, rng, (7, 25), (10, 36))
    engine.run_until_idle()
    reqs = first + second + late
    assert all(r.state == "done" for r in reqs)
    worst_logit, worst_deficit = worst_against_the_reference(params, reqs,
                                                             tap)
    assert worst_logit < LOGITS
    assert worst_deficit == 0.0
    # the same comparison against a reference whose state is bfloat16
    rounded, _ = worst_against_the_reference(
        params, late, tap, state_dtype=jnp.bfloat16)
    assert rounded > 20 * LOGITS
    stats = engine.stats()
    assert stats["state_rows_live"] == 0 and stats["kv_blocks_live_full"] == 0
    assert stats["state_bytes"] == engine.cache.state_bytes \
        == 4 * 6 * (3 * 128 * 4 + 4 * 16 * 16 * 4)
    assert stats["experts_touched"] > 0 and stats["expert_pairs"] > 0
    engine.cache.flush_prefix_cache()
    assert engine.cache.allocator.leaked() == 1      # the one trash block
    # every dispatch handed its pools AND its state over, in place
    assert stats["pool_inplace"] == stats["pool_dispatches"] > 20


@pytest.mark.parametrize("fault", [
    "stale_state", "state_at_the_buckets_end", "tail_off_by_one",
    "bfloat16_state", "bfloat16_router"])
def test_a_planted_fault_in_the_served_path_fails_the_same_comparison(
        monkeypatch, fault):
    """The comparison above has the power it claims: a prefill that leaves
    the slot's old state, one that takes ``S`` at the bucket's end (the
    padding advanced it), a convolution tail one row late, a state rounded
    to bfloat16 after every step and a router that scores in bfloat16 each
    move the decode logits by far more than its tolerance. (A model of its
    own: the compiled entries are cached by model, and a faulty one must
    not outlive the test.)"""
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.serving import kv_cache
    model, params = build()
    if fault == "stale_state":
        monkeypatch.setattr(
            kv_cache._StateKind, "pick",
            lambda self, rows, n: np.full(n, self.max_slots, np.int32))
    elif fault == "state_at_the_buckets_end":
        monkeypatch.setattr(gdn, "mask_past_last",
                            lambda g, beta, last: (g, beta))
    elif fault == "tail_off_by_one":
        real_tail = ssm_ops.conv_tail
        monkeypatch.setattr(ssm_ops, "conv_tail",
                            lambda xp, last, k: real_tail(xp, last - 1, k))
    elif fault == "bfloat16_state":
        real_step = gdn.gated_delta_step

        def rounded(q, k, v, g, beta, state, rows):
            o, s = real_step(q, k, v, g, beta, state, rows)
            return o, gdn.round_to(s, jnp.bfloat16)
        monkeypatch.setattr(gdn, "gated_delta_step", rounded)
    else:
        from paddle_tpu.models import laguna
        real_router = laguna._moe_router

        def rounded(ctx, ins, attrs):
            return real_router(ctx, dict(
                ins, X=[gdn.round_to(ins["X"][0], jnp.bfloat16)],
                W=[gdn.round_to(w, jnp.bfloat16) for w in ins["W"]]), attrs)
        monkeypatch.setattr(laguna, "_moe_router", rounded)
    engine, tap = engine_of(model)
    rng = np.random.default_rng(2)
    reqs = wave(engine, rng, (5, 13, 16, 9), (24, 24, 24, 24))
    engine.run_until_idle()
    reqs += wave(engine, rng, (12, 7), (24, 24))          # reused slots
    engine.run_until_idle()
    worst_logit, _ = worst_against_the_reference(params, reqs, tap)
    assert worst_logit > 5 * LOGITS
