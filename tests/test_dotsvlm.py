"""dots.vlm1.inst's language model (``models/dotsvlm.py``: a configuration
of the decoder in ``models/laguna.py`` plus a latent-attention layer)
against its plain reference (``perfbench/families/dotsvlm.py``, the
MATERIALISED form only) at a small size on the CPU, seeded random weights:
prefill then decode through the serving engine's latent cache (logits, not
tokens), the absorbed read against the materialised one, the choice by
groups against the reference with ties and a bias, a share's partial
results adding up to the uncut layer, and the latent cache kind in the one
allocator. The faults the comparison must see are in
``tests/test_dotsvlm_faults.py``.

Tolerance: float32 at toy size against float32 at ``highest``: only the
order of the reductions differs (the absorbed read folds ``W_uk`` into the
query and applies ``W_uv`` after the sum, a different association of the
same products), so whole logits of ~0.6 agree to 2e-6 here; ``LOGITS`` =
5e-5 is what Mellum's, LFM2's and Keye's tests hold and bfloat16 scores
fail it by two orders (``tests/test_dotsvlm_faults.py``)."""

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
from paddle_tpu.models import (DOTSVLM_CONFIGS, DotsVlmConfig,  # noqa: E402
                               DotsVlmForCausalLM)
from paddle_tpu.models import dotsvlm as program               # noqa: E402
from paddle_tpu.ops import decoder_ops as D                    # noqa: E402
from paddle_tpu.ops.pallas import mla_attention as M           # noqa: E402
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from paddle_tpu.serving.seam import FEATURES                   # noqa: E402
from perfbench.families import dotsvlm as family               # noqa: E402
from test_mellum import Tap                                    # noqa: E402

TINY = DOTSVLM_CONFIGS["dotsvlm-tiny"]
LOGITS = 5e-5       # float32 both sides: reduction order only


def file_of(mc):
    """The family's view of a program configuration (what a configuration
    file would hold)."""
    rope = mc.rope_parameters["full_attention"]
    return dict(
        num_attention_heads=mc.num_attention_heads,
        qk_nope_head_dim=mc.qk_nope_head_dim,
        qk_rope_head_dim=mc.qk_rope_head_dim, v_head_dim=mc.v_head_dim,
        kv_lora_rank=mc.kv_lora_rank, q_lora_rank=mc.q_lora_rank,
        rms_norm_eps=mc.rms_norm_eps,
        num_hidden_layers=mc.num_hidden_layers,
        first_k_dense_replace=mc.first_k_dense_replace,
        rope_theta=rope["rope_theta"],
        rope_scaling=dict(
            type="yarn", factor=rope["factor"],
            beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
            mscale=rope["mscale"], mscale_all_dim=rope["mscale_all_dim"],
            original_max_position_embeddings=rope[
                "original_max_position_embeddings"]),
        n_group=mc.router_groups, topk_group=mc.router_topk_groups,
        num_experts_per_tok=mc.num_experts_per_tok,
        routed_scaling_factor=mc.moe_routed_scaling_factor)


def build(mc=TINY, seed=3):
    layers.seed(seed)
    model = DotsVlmForCausalLM(mc)
    model.eval()
    return model, {n: p.value for n, p in model.named_parameters()}


def serve(model, requests, **kw):
    kw = dict(dict(max_slots=4, max_len=128, buckets=[32, 64], block_size=8,
                   num_blocks=0, prefix_cache=False, max_queue=16,
                   eos_token_id=None), **kw)
    engine = ServingEngine(model, **kw)
    tap = Tap(engine)
    reqs = [engine.submit(list(p), max_new_tokens=n) for p, n in requests]
    engine.run_until_idle()
    return engine, tap, reqs


def prompts_of(seed, requests):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, 512, n).tolist(), new) for n, new in requests]


_REFERENCE = {}


def reference(cfg):
    key = repr(sorted(cfg.items(), key=str))
    if key not in _REFERENCE:
        _REFERENCE[key] = jax.jit(
            lambda params, ids: family.forward(params, ids, cfg))
    return _REFERENCE[key]


def served_against_the_reference(params, reqs, tap, cfg, pad=128):
    """-> (the largest difference between a decode step's logits and the
    reference's full forward pass on the final sequence, the largest
    deficit of an emitted token as the benchmark's check reads it)."""
    worst_logit, worst_deficit = 0.0, 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        ref = np.asarray(reference(cfg)(params, jnp.asarray(ids))[0])
        p, n = len(r.prompt), len(r.tokens)
        assert r.state == "done" and n == r.max_new_tokens
        got = np.stack(tap.rows[r.id])
        assert got.shape[0] == n - 1
        worst_logit = max(worst_logit,
                          float(np.abs(got - ref[p:p + n - 1]).max()))
        d = ref[p - 1:p + n - 1].max(-1) \
            - ref[np.arange(p - 1, p + n - 1), seq[p:]]
        worst_deficit = max(worst_deficit, float(d.max()))
    return worst_logit, worst_deficit


@pytest.fixture(scope="module")
def tiny():
    return build()


REQUESTS = ((20, 6), (45, 9), (33, 4), (60, 12), (9, 5))


def test_the_defaults_are_the_published_model():
    mc = DotsVlmConfig()
    # attention: q_a, its norm, q_b, kv_a, its norm, kv_b, o
    assert mc.attention_params() == 187_107_328
    dense = mc.attention_params() + 2 * 7168 + 3 * 7168 * 18432
    sparse = mc.attention_params() + 2 * 7168 + 7168 * 256 + 256 \
        + 257 * 3 * 7168 * 2048
    assert mc.num_params() == 3 * dense + 58 * sparse \
        + 2 * 129280 * 7168 + 7168 == 671_026_419_200   # the published 671B
    assert mc.mlp_layer_types == ("dense",) * 3 + ("sparse",) * 58
    assert (mc.latent_width, mc.router_groups, mc.router_topk_groups,
            mc.router_score, mc.router_bias, mc.attention_gate) == \
        (576, 8, 4, "sigmoid", True, False)
    attn = mc.attention(0)
    # m(1) = 0.1 ln 40 + 1: the whole score is scaled by its square
    assert attn.scale == pytest.approx(1.8739 / 192 ** 0.5, rel=1e-4)
    assert attn.rot_scale == 1.0


def test_the_cells_share_is_what_the_issue_reckoned():
    share = DotsVlmConfig(
        num_hidden_layers=6, first_k_dense_replace=1,
        held_experts=(0, 8), held_vocab=(0, 16160))
    assert share.num_params() == 3_741_753_600
    assert share.mlp_layer_types == ("dense",) + ("sparse",) * 5


@pytest.mark.parametrize("held", [None, (0, 4)], ids=["whole", "share"])
def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        held):
    """The engine's normal path (materialised prompts of two buckets,
    absorbed decode rows over blocks of 8, several requests a step; whole,
    and on a quarter share of the experts in both forms of the expert
    layer) against the reference's full forward on the final sequences."""
    mc = dataclasses.replace(TINY, held_experts=held)
    model, params = build(mc)
    engine, tap, reqs = serve(model, prompts_of(0, REQUESTS))
    worst, deficit = served_against_the_reference(params, reqs, tap,
                                                  file_of(mc))
    assert worst < LOGITS, worst
    assert deficit == 0.0
    assert engine.cache.allocator.leaked() == 1        # the trash block
    stats = engine.stats()
    assert stats["latent_cache_bytes"] == \
        engine.cache.num_blocks * mc.latent_width * 8 * 4 * 3
    assert stats["latent_rows_read"] > 0 and stats["mla_prompt_pairs"] > 0


def _layer_inputs(seed, b, s):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(b, s, TINY.hidden_size)),
                       jnp.float32)


def test_the_absorbed_read_is_the_materialised_read(tiny, monkeypatch):
    """One function of the same weights: a row read absorbed over the
    latent pool gives what the materialised form gives for that row, and
    the prompt's passes of heads give what one pass gives."""
    model, _ = tiny
    attn = model.model.layers[1].attn
    b, s, bs = 2, 24, 8
    u = _layer_inputs(1, b, s)
    rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    c_q, kept = attn.latents_of(u, rows)
    whole = attn.materialised(c_q, kept, rows)
    monkeypatch.setattr(program, "PROMPT_HEADS", 2)       # two passes
    assert np.allclose(attn.materialised(c_q, kept, rows), whole, atol=1e-6)
    # the rows in a pool whose blocks are not contiguous
    tables = jnp.asarray([[5, 2, 7], [1, 6, 3]], jnp.int32)
    pool = program.latent_pool_write(
        jnp.zeros((9, TINY.latent_width, bs), jnp.float32), kept,
        jnp.zeros((b,), jnp.int32), tables)
    for t in (0, 7, 8, 23):
        pos = jnp.full((b,), t, jnp.int32)
        got = attn.absorbed(c_q[:, t:t + 1], rows[:, t:t + 1], pool, tables,
                            pos)
        assert np.allclose(got[:, 0], whole[:, t], atol=2e-6), t


def test_the_latent_write_lands_rows_where_the_few_rows_form_does():
    """A prompt's many rows (the kernel over touched blocks) and the
    decode step's few rows (in-place columns) write the same pool."""
    from paddle_tpu.ops import attention_ops as A
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=(12, 24, 8)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, 70, 24)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 12))[:10]
                         .reshape(2, 5), jnp.int32)
    pos = jnp.asarray([0, 3], jnp.int32)
    many = A.latent_pool_write(pool, new[:, :33], pos, tables)
    few = pool
    for t in range(33):
        few = A.index_pool_write(few, new[:, t:t + 1], pos + t, tables)
    assert np.array_equal(np.asarray(many)[1:], np.asarray(few)[1:])


# ------------------------------------------------------- the grouped choice

def _route(scores, bias, groups, topk_group, k, scale=2.5):
    """The program's router on given pre-sigmoid logits: an identity
    weight makes ``x W`` the logits themselves."""
    e = scores.shape[-1]
    out = D._moe_router(
        None, {"X": [jnp.asarray(scores)], "W": [jnp.eye(e)],
               "Bias": [jnp.asarray(bias)]},
        {"top_k": k, "scale": scale, "score": "sigmoid", "n_group": groups,
         "topk_group": topk_group})
    return np.asarray(out["TopkIdx"][0]), np.asarray(out["TopkWeight"][0])


def test_the_grouped_choice_matches_the_reference_on_random_scores():
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(64, 16)).astype(np.float32) * 2
    bias = rng.normal(size=16).astype(np.float32) * 0.1
    idx, weight = _route(logits, bias, 4, 2, 4)
    s = jax.nn.sigmoid(jnp.asarray(logits))
    ref_idx, ref_top = family.grouped_choice(s, bias, 4, 2, 4)
    assert np.array_equal(idx, np.asarray(ref_idx))
    ref_w = np.asarray(ref_top / jnp.sum(ref_top, -1, keepdims=True) * 2.5)
    assert np.allclose(weight, ref_w, atol=1e-6)
    # every token's choices lie in exactly two groups of four
    assert all(len({int(i) // 4 for i in row}) <= 2 for row in idx)


def test_a_plain_top_k_would_choose_otherwise():
    """The best single expert sits in a group whose second best is poor:
    the grouped choice leaves it out, a plain top k would take it."""
    z = np.full((1, 16), -4.0, np.float32)
    z[0, 0] = 6.0                       # group 0: one high score
    z[0, 4:6] = 2.0                     # group 1: two good ones
    z[0, 8:10] = 1.5                    # group 2: two good ones
    idx, _ = _route(z, np.zeros(16, np.float32), 4, 2, 4)
    assert set(idx[0]) <= set(range(4, 12)) and 0 not in idx[0]
    assert 0 in np.asarray(jax.lax.top_k(jnp.asarray(z), 4)[1][0])


def test_ties_go_to_the_lower_index_of_groups_and_of_experts():
    z = np.zeros((1, 16), np.float32)          # every score the same
    idx, weight = _route(z, np.zeros(16, np.float32), 4, 2, 4)
    assert idx[0].tolist() == [0, 1, 2, 3]     # groups 0 and 1, experts 0-3
    assert np.allclose(weight[0], 2.5 / 4)
    ref_idx, _ = family.grouped_choice(jax.nn.sigmoid(jnp.asarray(z)),
                                       np.zeros(16, np.float32), 4, 2, 4)
    assert np.asarray(ref_idx)[0].tolist() == [0, 1, 2, 3]


def test_a_bias_flips_a_choice_and_never_its_weight():
    rng = np.random.default_rng(7)
    z = rng.normal(size=(1, 16)).astype(np.float32)
    plain, w0 = _route(z, np.zeros(16, np.float32), 4, 4, 4)
    out = next(e for e in range(16) if e not in plain[0])
    bias = np.zeros(16, np.float32)
    bias[out] = 1.0                     # lifts an expert into the choice
    idx, w1 = _route(z, bias, 4, 4, 4)
    assert out in idx[0] and set(idx[0]) != set(plain[0])
    s = np.asarray(jax.nn.sigmoid(jnp.asarray(z)))[0]
    # the weights are the chosen experts' unbiased scores, renormalised
    assert np.allclose(w1[0], 2.5 * s[idx[0]] / s[idx[0]].sum(), atol=1e-6)


# ---------------------------------------------------------- the shares add up

@pytest.mark.parametrize("rows", [1, 24], ids=["decode", "prompt"])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(rows):
    """Four chips hold 4 of 16 experts each: every share's partial result
    less the shared expert (which every chip computes and a deployment
    counts once), summed, plus the shared expert once, is the uncut
    layer's result, in the few-rows form and in a prompt's."""
    whole_model, _ = build(TINY)
    whole = whole_model.model.layers[1].moe
    u = _layer_inputs(11, 3, rows)
    live = jnp.ones((3,), bool) if rows == 1 else None
    want, counted = whole.served(u, live)
    shared = whole.shared(program.Tensor(u, stop_gradient=True)).value
    total, pairs = np.zeros_like(np.asarray(want)), 0
    for lo in range(0, 16, 4):
        mc = dataclasses.replace(TINY, held_experts=(lo, lo + 4))
        share_model, _ = build(mc)
        moe = share_model.model.layers[1].moe
        # the whole layer's weights, the share's stack its slice of them
        for (name, mine), (_, theirs) in zip(moe.named_parameters(),
                                             whole.named_parameters()):
            mine.value = theirs.value[lo:lo + 4] \
                if name.startswith("experts_") else theirs.value
        part, c = moe.served(u, live)
        total += np.asarray(part) - np.asarray(shared)
        pairs += int(c[1])
    assert np.allclose(total + np.asarray(shared), np.asarray(want),
                       atol=2e-6)
    if rows == 1:
        # every (row, chosen expert) pair is some share's
        assert TINY.expert_counters[1] == "expert_pairs"
        assert pairs == int(counted[1]) == 3 * TINY.num_experts_per_tok


# -------------------------------------------------------- the latent cache kind

def test_the_latent_kind_allocates_frees_zeroes_and_leaks_nothing(tiny):
    model, _ = tiny
    engine, _, reqs = serve(model, prompts_of(4, ((30, 5), (12, 7))))
    cache = engine.cache
    (kind,) = engine.spec.cache_kinds
    assert (kind.kv_heads, kind.head_dim, kind.extra) == \
        (0, 0, (("latent_cache", TINY.latent_width),))
    # ONE array a layer, no K and V pair, one allocator, one table
    assert all(len(layer) == 1 and layer[0].shape ==
               (cache.num_blocks, TINY.latent_width, 8)
               for layer in cache.pool.layers)
    assert len(cache.pool.layers) == TINY.num_hidden_layers
    assert cache.pool.extra_bytes == {
        "latent_cache": cache.num_blocks * TINY.latent_width * 8 * 4 * 3}
    assert all(r.state == "done" for r in reqs)
    assert cache.allocator.leaked() == 1 and cache.blocks_used == 1
    assert float(jnp.abs(cache.pool.layers[0][0]).max()) > 0
    cache.pool.rebuild()
    assert all(float(jnp.abs(a).max()) == 0.0
               for layer in cache.pool.layers for a in layer)
    # the engine sheds what ran on the pool that is gone, and serves again
    states = []
    for _ in range(2):
        again = engine.submit(list(range(1, 20)), max_new_tokens=3)
        engine.run_until_idle()
        states.append(again.state)
    assert states[-1] == "done" and cache.allocator.leaked() == 1


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "speculative": dict(spec_tokens=2),
    "lora": dict(lora_rank=4),
    "int8_pool": dict(kv_dtype="int8"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_the_seam_refuses_each_optional_feature_by_name(tiny, feature):
    model, _ = tiny
    kw = dict(dict(max_slots=2, max_len=64, buckets=[32], block_size=8,
                   prefix_cache=False, eos_token_id=None),
              **REFUSED[feature])
    with pytest.raises(ValueError, match=f"dotsvlm is not served with "
                                         f"{feature}"):
        ServingEngine(model, **kw)


def test_the_seam_declares_none_of_the_optional_features(tiny):
    model, _ = tiny
    spec = model.serving_spec()
    assert spec.features == frozenset() and FEATURES
    for feature in FEATURES:
        with pytest.raises(ValueError, match=feature):
            spec.require(feature, "a test")
    assert spec.counters == ("experts_touched", "expert_pairs",
                             "latent_rows_read")
    share = dataclasses.replace(TINY, held_vocab=(128, 256))
    with pytest.raises(ValueError, match="from row 0"):
        DotsVlmForCausalLM(share).serving_spec()


def test_the_family_is_served_not_trained(tiny):
    model, _ = tiny
    ids = program.Tensor(jnp.zeros((1, 8), jnp.int32), stop_gradient=True)
    with pytest.raises(ValueError, match="served, not trained"):
        model(ids)
    with pytest.raises(SystemExit, match="no loss"):
        family.loss({}, None, None, {})
    with pytest.raises(SystemExit, match="no training job"):
        family.train_job({}, {})


def test_the_counters_follow_what_the_step_reads(tiny):
    """``latent_rows_read`` is every decode row's context summed over the
    layers; ``mla_prompt_pairs`` the prompt's live causal triangle."""
    model, _ = tiny
    engine, _, reqs = serve(model, prompts_of(6, ((20, 4),)))
    stats = engine.stats()
    (r,) = reqs
    # the first token comes from the prompt's last row; decode steps read
    # contexts of 21, 22, 23 rows (one may be dispatched ahead and unused)
    steps = int(stats["sampler_dispatches"])
    want = sum(20 + j + 1 for j in range(steps)) * TINY.num_hidden_layers
    assert int(stats["latent_rows_read"]) == want
    # one pass of the toy's 4 heads a layer
    assert stats["mla_prompt_pairs"] == \
        TINY.num_hidden_layers * M.prompt_pairs(1, 32, 20) == \
        TINY.num_hidden_layers * 210
    assert stats["mla_prompt_reads"] == TINY.num_hidden_layers
