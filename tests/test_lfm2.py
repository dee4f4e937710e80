"""LFM2-class hybrid decoder (``models/lfm2.py``) against its plain
reference (``perfbench/families/lfm2.py``) at a small size on the CPU,
seeded random weights, float32: whole-sequence logits and every layer's
output; prefill then decode through ``ServingEngine`` (logits, not tokens)
with prompts that pad their bucket and requests admitted into a running
step; each fault the comparison must see, injected; a decode batch over
``INPLACE_WRITE_MAX_ROWS``; the router op without a bias, bit for bit.

Tolerances: the toy is float32 and conftest pins matmuls to ``highest``, so
the program and the reference differ by the order of their reductions only
(measured 2.4e-7 on whole logits of scale ~1.4, ~1e-6 through the cache);
5e-5 leaves that 50x and is 1,000x under what bfloat16 weights move
(``test_the_served_precision...`` reads > 1e-3)."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from paddle_tpu import profiler                                # noqa: E402
from paddle_tpu.observability import compile_tracker           # noqa: E402
from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.models import (LFM2_CONFIGS, Lfm2Config,       # noqa: E402
                               Lfm2ForCausalLM)
from paddle_tpu.models import laguna                           # noqa: E402
from paddle_tpu.ops import attention_ops, decoder_ops, ssm_ops  # noqa: E402
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from perfbench.families import lfm2 as family                  # noqa: E402
from test_serving_state_kind import Tap                        # noqa: E402

TINY = LFM2_CONFIGS["lfm2-tiny"]
TOL = 5e-5


def file_of(mc):
    """The family's view of a program configuration: the published keys."""
    d = {f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)}
    d.update(norm_eps=mc.rms_norm_eps,
             routed_scaling_factor=mc.moe_routed_scaling_factor,
             rope_parameters=mc.rope_parameters["full_attention"])
    return d


def build(mc=TINY, seed=3):
    layers.seed(seed)
    model = Lfm2ForCausalLM(mc)
    model.eval()
    return model, {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def tiny():
    return build()


def engine_of(model, **kw):
    kw = dict(dict(max_slots=4, max_len=128, buckets=[16, 32, 64],
                   block_size=8, num_blocks=0, prefix_cache=False,
                   max_queue=128, eos_token_id=None), **kw)
    engine = ServingEngine(model, **kw)
    return engine, Tap(engine)


def wave(engine, rng, lengths, new):
    return [engine.submit(rng.integers(1, 512, n).tolist(),
                          max_new_tokens=k) for n, k in zip(lengths, new)]


_REFERENCES = {}


def _reference(params, ids, mc):
    """The family's forward, jitted once a configuration."""
    key = repr(mc)
    if key not in _REFERENCES:
        cfg = file_of(mc)
        _REFERENCES[key] = jax.jit(lambda p, i: family.forward(p, i, cfg))
    return _REFERENCES[key](params, ids)


def worst_against_the_reference(params, reqs, tap, mc=TINY):
    """(largest |decode logits - reference|, largest deficit of an emitted
    token) over ``reqs``, the reference run on each final sequence."""
    worst_logit = worst_deficit = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        # right-padded to one length (one compile): causal, so the rows
        # before the padding do not see it
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(seq)] = seq
        ref = np.asarray(_reference(params, jnp.asarray(ids), mc)[0])
        p, n = len(r.prompt), len(r.tokens)
        got = np.stack(tap.rows[r.id])
        assert got.shape[0] == n - 1
        worst_logit = max(worst_logit,
                          float(np.abs(got - ref[p:p + n - 1]).max()))
        d = ref[p - 1:p + n - 1].max(-1) \
            - ref[np.arange(p - 1, p + n - 1), seq[p:]]
        worst_deficit = max(worst_deficit, float(d.max()))
    return worst_logit, worst_deficit


# ------------------------------------------------------------ the model

def test_the_defaults_are_the_published_model():
    mc = Lfm2Config()
    # 38 x 604.0M experts + 2 x 72.4M dense + 30 x 16.8M convolution
    # mixers + 10 x 10.5M attention mixers + the 134.2M tied embedding
    assert mc.num_params() == 23_843_661_440
    assert mc.layers_of("full_attention") == tuple(range(2, 40, 4))
    assert len(mc.layers_of("conv")) == 30
    assert mc.mlp_layer_types == ("dense",) * 2 + ("sparse",) * 38
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim,
            mc.conv_L_cache) == (32, 8, 64, 3)
    assert (mc.qk_norm, mc.router_bias, mc.attention_gate,
            mc.router_score) == (True, True, False, "sigmoid")
    model, params = build()
    assert sum(int(np.prod(p.shape)) for p in params.values()) \
        == TINY.num_params()
    assert "lm_head.weight" not in params               # the head is tied
    assert TINY.layer_types == ("conv", "full_attention") + ("conv",) * 3
    # the bias is drawn, not zero: the toy's router is skewed on purpose
    assert float(jnp.std(params["model.layers.2.moe.expert_bias"])) > 0.05
    with pytest.raises(ValueError, match="a share of the model"):
        Lfm2ForCausalLM(dataclasses.replace(TINY, held_experts=(0, 4)))
    with pytest.raises(ValueError, match="conv or full_attention"):
        dataclasses.replace(TINY, layer_types=("sliding_attention",) * 5)


def test_the_published_file_is_the_program_s_default():
    cfg = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "lfm2-24b-a2b-d9.json")))
    mc = family.model_config(cfg)
    want = dataclasses.replace(
        Lfm2Config(), num_hidden_layers=9, num_dense_layers=1,
        layer_types=Lfm2Config().layer_types[1:10],
        num_attention_heads_per_layer=(), mlp_layer_types=(),
        max_position_embeddings=4096,
        embed_init_std=cfg["embed_init_std"],
        final_norm_init=tuple(cfg["final_norm_init"]),
        router_bias_init_std=cfg["expert_bias_init_std"],
        tokens_a_dispatch=cfg["tokens_a_dispatch"])
    assert mc == want
    assert mc.layer_types == ("conv", "full_attention", "conv", "conv",
                              "conv", "full_attention", "conv", "conv",
                              "conv")
    assert mc.num_params() == cfg["params_held"] == 5_177_950_976


def test_the_forward_matches_the_reference(tiny):
    model, params = tiny
    ids = np.random.default_rng(0).integers(1, 512, (2, 64))
    got_layers, want_layers = [], []
    got = model(ids, collect=got_layers).value
    want = family.forward(params, jnp.asarray(ids), file_of(TINY),
                          collect=want_layers)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert len(got_layers) == len(want_layers) == TINY.num_hidden_layers
    for g, w in zip(got_layers, want_layers):
        assert float(jnp.max(jnp.abs(g.value - w))) < TOL


def test_the_gated_convolution_carries_its_tail_from_the_prompts_own_end():
    """``conv_tail`` at ``last`` then one row against it is the whole
    convolution's next row, K = 3 and no bias."""
    r = np.random.default_rng(1)
    v = jnp.asarray(r.standard_normal((2, 24, 16)), jnp.float32)
    w = jnp.asarray(r.standard_normal((16, 3)), jnp.float32)
    whole = ssm_ops.causal_conv(v, w)
    np.testing.assert_allclose(
        whole[:, 5], sum(w[None, :, j] * v[:, 3 + j] for j in range(3)),
        rtol=1e-6, atol=1e-6)
    last = jnp.asarray([9, 0], jnp.int32)
    tail = ssm_ops.conv_tail(v, last, 3)
    np.testing.assert_array_equal(tail[0], v[0, 8:10])
    np.testing.assert_array_equal(tail[1, 0], np.zeros(16))
    for row, at in enumerate((10, 1)):
        nxt = ssm_ops.causal_conv(v[row:row + 1, at:at + 1], w, None,
                                  tail[row:row + 1])
        np.testing.assert_allclose(nxt[0, 0], whole[row, at], rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------- the router op

@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_router_without_a_bias_is_what_it_was(score):
    """No ``Bias``, no ``renorm_eps``: TopkIdx / TopkWeight bit for bit the
    parent's arithmetic (``scale * top / sum(top)`` of ``top_k(score(x
    W))``), and the traced program has no more equations than it."""
    r = np.random.default_rng(4)
    x = jnp.asarray(r.standard_normal((33, 64)), jnp.float32)
    w = jnp.asarray(r.standard_normal((64, 16)) * 0.3, jnp.float32)
    attrs = {"top_k": 4, "scale": 2.5, "score": score}

    def parent(x, w):
        f = {"sigmoid": jax.nn.sigmoid,
             "softmax": lambda z: jax.nn.softmax(z, axis=-1)}[score]
        scores = f(jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST))
        top, idx = jax.lax.top_k(scores, 4)
        return idx.astype(jnp.int32), \
            2.5 * top / jnp.sum(top, axis=-1, keepdims=True)

    def ours(x, w):
        out = decoder_ops._moe_router(None, {"X": [x], "W": [w]}, attrs)
        return out["TopkIdx"][0], out["TopkWeight"][0]
    for got, want in zip(ours(x, w), parent(x, w)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert str(jax.make_jaxpr(ours)(x, w)) == str(jax.make_jaxpr(parent)(x, w))


def test_the_bias_steers_the_choice_and_never_the_weights():
    r = np.random.default_rng(5)
    x = jnp.asarray(r.standard_normal((40, 64)), jnp.float32)
    w = jnp.asarray(r.standard_normal((64, 8)) * 0.3, jnp.float32)
    bias = jnp.asarray([2.0, 0, 0, 0, 0, 0, 0, -2.0], jnp.float32)
    attrs = {"top_k": 2, "scale": 1.0, "score": "sigmoid",
             "renorm_eps": 1e-6}
    out = decoder_ops._moe_router(None, {"X": [x], "W": [w], "Bias": [bias]},
                                  attrs)
    idx, weight = np.asarray(out["TopkIdx"][0]), \
        np.asarray(out["TopkWeight"][0])
    assert (idx == 0).any(axis=1).all() and not (idx == 7).any()
    s = np.asarray(jax.nn.sigmoid(x @ w))
    chosen = np.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(
        weight, chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6)


# ------------------------------------------------------------ the engine

def test_prefill_then_decode_through_the_engine_matches_the_reference(tiny):
    """Four prompts of unequal length (5-16 rows) share ONE dispatch of the
    16-row bucket, three of them padded: each one's tail is taken at its
    own last token. A second wave is admitted into the step that is already
    running and reuses the slots as they free (a stale tail would show);
    contexts run past the 3 taps and past the 8-row block."""
    model, params = tiny
    engine, tap = engine_of(model)
    assert engine.spec.family == "lfm2"
    assert engine.spec.counters == laguna.DECODE_COUNTERS
    rng = np.random.default_rng(1)
    first = wave(engine, rng, (5, 13, 16, 9), (12, 20, 8, 16))
    engine.step()
    assert tap.dispatches[0] == (16, [5, 13, 16, 9])
    second = wave(engine, rng, (30, 20, 40, 3), (10, 14, 9, 12))
    engine.run_until_idle()
    reqs = first + second
    assert all(r.state == "done" for r in reqs)
    assert len(tap.dispatches) > 2      # admitted as slots freed
    worst_logit, worst_deficit = worst_against_the_reference(params, reqs,
                                                             tap)
    assert worst_logit < TOL
    assert worst_deficit == 0.0
    stats = engine.stats()
    assert stats["state_rows_live"] == 0 and stats["kv_blocks_live_full"] == 0
    # one array a convolution layer: 4 slots x 4 layers x [2, 64] float32
    assert stats["state_bytes"] == engine.cache.state_bytes \
        == 4 * 4 * 2 * 64 * 4
    # the device counters: 4 sparse layers, at most 8 experts each a step,
    # and the largest expert has at least the mean's rows
    steps = stats["sampler_dispatches"]
    assert 0 < stats["experts_touched"] <= steps * 4 * 8
    assert stats["experts_touched"] / 8 <= stats["expert_rows_max"] \
        <= steps * 4 * 4
    engine.cache.flush_prefix_cache()
    assert engine.cache.allocator.leaked() == 1      # the one trash block
    assert stats["pool_inplace"] == stats["pool_dispatches"] > 20
    for feature, kw in (("prefix_cache", {"prefix_cache": True}),
                        ("speculative", {"spec_tokens": 2})):
        with pytest.raises(ValueError, match=f"lfm2 is not served with "
                                             f"{feature}"):
            engine_of(model, **kw)


# Each fault as (what is patched, where it shows). The model-side faults
# show in whole-sequence logits; the hand-over's only through the cache.

def _biased_weights(real):
    """The bias added to the weights too (the choice is right)."""
    def router(ctx, ins, attrs):
        out = real(ctx, ins, attrs)
        x, w, b = ins["X"][0], ins["W"][0], ins["Bias"][0]
        s = jax.nn.sigmoid(jnp.matmul(x, w)) + b
        top = jnp.take_along_axis(s, out["TopkIdx"][0], axis=-1)
        return dict(out, TopkWeight=[top / jnp.sum(top, -1, keepdims=True)])
    return router


def _no_bias(real):
    """The bias left out of the choice."""
    return lambda ctx, ins, attrs: real(
        ctx, {k: v for k, v in ins.items() if k != "Bias"}, attrs)


@pytest.mark.parametrize("fault", [
    "bias_added_to_the_weights", "bias_left_out_of_the_choice",
    "qk_norm_dropped", "one_expert_left_out", "dense_layer_run_as_sparse"])
def test_a_fault_in_the_model_fails_the_whole_sequence_comparison(
        monkeypatch, fault):
    mc = dataclasses.replace(TINY)
    model, params = build(mc)
    cfg = file_of(mc)
    if fault.startswith("bias"):
        monkeypatch.setattr(laguna, "_moe_router",
                            {"bias_added_to_the_weights": _biased_weights,
                             "bias_left_out_of_the_choice": _no_bias}[fault](
                                 laguna._moe_router))
    elif fault == "qk_norm_dropped":
        mc.qk_norm = False      # every layer reads this one object
    elif fault == "one_expert_left_out":
        moe = model.model.layers[2].moe      # the expert its bias favours
        moe.experts_down.value = moe.experts_down.value.at[
            int(jnp.argmax(moe.expert_bias.value))].set(0.0)
    else:
        # a program that ignores num_dense_layers: its layer 0 is sparse;
        # the reference keeps the published dense layer (its weights from
        # the sound build), and agrees once told the same layer kinds
        sparse, theirs = build(dataclasses.replace(
            TINY, num_dense_layers=0, mlp_layer_types=()))
        ids = np.random.default_rng(0).integers(1, 512, (1, 48))
        same = family.forward(theirs, jnp.asarray(ids),
                              dict(cfg, num_dense_layers=0))
        assert float(jnp.max(jnp.abs(sparse(ids).value - same))) < TOL
        model, params = sparse, dict(
            theirs, **{k: v for k, v in params.items() if ".mlp." in k})
    ids = np.random.default_rng(0).integers(1, 512, (1, 48))
    got = model(ids).value
    want = family.forward(params, jnp.asarray(ids), cfg)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * TOL


@pytest.mark.parametrize("fault", ["tail_a_row_late",
                                   "tail_at_the_buckets_end"])
def test_a_fault_in_the_hand_over_fails_the_served_comparison(
        monkeypatch, fault):
    """(A model of its own: the compiled entries are cached by model, and a
    faulty one must not outlive the test.)"""
    model, params = build()
    real = ssm_ops.conv_tail
    monkeypatch.setattr(ssm_ops, "conv_tail", {
        "tail_a_row_late": lambda v, last, k: real(v, last - 1, k),
        "tail_at_the_buckets_end": lambda v, last, k: real(
            v, jnp.full_like(last, v.shape[1] - 1), k)}[fault])
    engine, tap = engine_of(model)
    rng = np.random.default_rng(2)
    reqs = wave(engine, rng, (5, 13, 16, 9), (6, 6, 6, 6))
    engine.run_until_idle()
    worst_logit, _ = worst_against_the_reference(params, reqs, tap)
    assert worst_logit > 10 * TOL


def test_a_decode_batch_over_the_inplace_rows_is_the_same_rows_8_at_a_time():
    """72 rows a step take the many-row form of the pool write (over
    ``INPLACE_WRITE_MAX_ROWS``: the kernel over the touched chunks) and
    the state write of 72 rows; the same requests decoded 8 at a time
    take the row updates: row by row the decode logits agree to the
    reduction order."""
    rows = attention_ops.INPLACE_WRITE_MAX_ROWS + 8
    model, _ = build()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 512, int(n)).tolist()
               for n in rng.integers(3, 17, rows)]
    got = []
    for slots in (rows, 8):
        engine, tap = engine_of(model, max_slots=slots, max_len=32,
                                buckets=[16])
        reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
        engine.run_until_idle()
        assert all(r.state == "done" for r in reqs)
        got.append([np.stack(tap.rows[r.id]) for r in reqs])
        if slots == rows:
            assert engine.stats()["state_bytes"] == rows * 4 * 2 * 64 * 4
    for big, small in zip(*got):
        np.testing.assert_allclose(big, small, rtol=0, atol=TOL)


# --------------------------------------------------------------- the rest

def test_the_build_and_the_first_trace_have_spans(tmp_path):
    profiler.start_profiler()
    model, _ = build(TINY, seed=5)
    forward = compile_tracker.tracked_jit(
        "test_lfm2_forward", lambda ids: model(ids).value)
    forward(np.ones((1, 8), np.int32))
    forward(np.ones((1, 8), np.int32))
    path = str(tmp_path / "spans.json")
    profiler.stop_profiler(profile_path=path)
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert names.count("lfm2.build") == 1
    assert not [n for n in names if n.endswith(".first_trace")]
    # the first forward's tracing is the site's account, not a span's
    assert forward.record.count == 1 and forward.record.trace_ms > 0


def test_the_served_precision_is_bfloat16_where_the_configuration_says():
    mc = dataclasses.replace(TINY, dtype="bfloat16", embed_init_std=1.0)
    model, params = build(mc, seed=7)
    dtypes = {n.split(".layers.2.")[1]: str(p.dtype)
              for n, p in params.items() if ".layers.2." in n}
    assert dtypes["conv.conv_weight"] == dtypes["moe.expert_bias"] \
        == "float32"
    assert dtypes["conv.in_proj.weight"] == dtypes["moe.experts_down"] \
        == "bfloat16"
    spec = model.serving_spec()
    assert spec.kv_dtype == "bf16"
    (state,) = spec.state_kinds
    assert state.arrays == (((2, 64), "bfloat16"),)
    ids = np.random.default_rng(0).integers(1, 512, (1, 48))
    got = model(ids).value
    want = family.forward(params, jnp.asarray(ids), file_of(mc))
    assert got.dtype == jnp.float32
    # bfloat16 weights fail the float32 tolerance by far
    assert 20 * TOL < float(jnp.max(jnp.abs(got - want))) < 0.3


@pytest.mark.parametrize("name,pack,read", [
    ("lfm2-24b-a2b", 2, "kernel"),      # 8 KV heads of 64: two a row of 128
    ("lfm2-tiny", 2, "kernel"),         # 2 KV heads of 16: both in a row
    ("mellum", 1, "by layer"),          # window layers: a head a row
    ("full-d128", 1, "kernel"),         # a head of 128 fills the lanes
])
def test_the_pool_rows_packing_follows_from_the_head_size(name, pack, read,
                                                          monkeypatch):
    """No option picks the pool's layout or the served decode read: heads
    narrower than the 128 lanes share a pool row where no layer has a
    window, and a layer without a window reads its decode row through the
    paged kernel whatever the packing (Mellum's full layers too:
    ``tests/test_paged_attention.py`` reads its step's jaxpr)."""
    from paddle_tpu.models import MELLUM_CONFIGS
    pa = sys.modules["paddle_tpu.ops.pallas.paged_attention"]
    mc = {"mellum": lambda: MELLUM_CONFIGS["mellum-tiny"],
          "full-d128": lambda: dataclasses.replace(
              TINY, head_dim=128, num_attention_heads=2,
              num_key_value_heads=2)}.get(
                  name, lambda: LFM2_CONFIGS[name])()
    assert mc.kv_pack == pack
    assert not hasattr(mc, "paged_decode_kernel")
    if name in ("lfm2-24b-a2b", "mellum"):
        return          # the rule; the toys below run the read it picks
    calls = []
    real = pa.paged_attention
    monkeypatch.setattr(pa, "paged_attention", lambda *a, **k: (
        calls.append(a[1].shape), real(*a, **k))[1])
    model, _ = build(mc)
    engine = ServingEngine(model, max_slots=2, max_len=64, buckets=[16],
                           block_size=8, prefix_cache=False,
                           eos_token_id=None)
    (kind,) = model.serving_spec().cache_kinds
    assert (kind.kv_heads, kind.head_dim) == (
        mc.num_key_value_heads // pack, mc.head_dim * pack)
    engine.submit(list(range(1, 12)), max_new_tokens=3)
    engine.run_until_idle()
    assert bool(calls) == (read == "kernel")
