"""The faults the comparison of ``tests/test_dotsvlm.py`` must see, each
planted in the program and each read OVER the tolerance the sound program
is under (ISSUE 49): the rotated key dropped from the cache's row, the
latent's norm skipped, ``m^2`` left out of the score's scale, the
selection bias used in the weights, the choice by groups replaced by a
plain top k, an expert left out, the shared expert left out, and the
scores of both reads rounded to bfloat16. Every fault is read through the
engine (prefill, then decode rows over the latent cache) against the
reference's full forward."""

import dataclasses
import math
import os
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.models import dotsvlm as program               # noqa: E402
from paddle_tpu.models import laguna                           # noqa: E402
from perfbench.families import dotsvlm as family               # noqa: E402
from test_dotsvlm import (LOGITS, TINY, build, file_of,        # noqa: E402
                          prompts_of, serve,
                          served_against_the_reference)

#: one dense and one expert layer are enough: a fault of a prompt's rows
#: reaches the decode steps through the latents the layer above caches.
#: Weights ten times the preset's: at 0.02 a toy's scores are so small
#: that its softmax is flat whatever their scale or rounding, and an
#: expert's term is lost in the stream; and a bias that moves choices
TWO = dataclasses.replace(TINY, num_hidden_layers=2, init_std=0.2,
                          router_bias_init_std=0.3)
REQUESTS = [(5, 24), (40, 26)]


def served(model, params):
    _, tap, reqs = serve(model, prompts_of(1, REQUESTS), max_slots=2,
                         buckets=[64])
    return served_against_the_reference(params, reqs, tap, file_of(TWO))[0]


def test_the_sound_program_is_under_the_tolerance():
    model, params = build(TWO)
    assert served(model, params) < LOGITS


def router_with(bias_in_weights=False, plain=False):
    """The router with one mechanism wrong."""
    def router(ctx, ins, attrs):
        x, w, b = ins["X"][0], ins["W"][0], ins["Bias"][0]
        s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                      w.astype(jnp.float32),
                                      precision=jax.lax.Precision.HIGHEST))
        k = int(attrs["top_k"])
        if plain:
            idx = jax.lax.top_k(s + b, k)[1]
        else:
            idx, _ = family.grouped_choice(s, b, int(attrs["n_group"]),
                                           int(attrs["topk_group"]), k)
        top = jnp.take_along_axis(s + b if bias_in_weights else s, idx,
                                  axis=-1)
        weight = float(attrs["scale"]) * top \
            / jnp.sum(top, axis=-1, keepdims=True)
        return {"TopkIdx": [idx.astype(jnp.int32)], "TopkWeight": [weight]}
    return router


def _scores(q_n, q_r, k_n, k_r, scale):
    lg = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n)
          + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r)) * scale
    return lg.astype(jnp.bfloat16).astype(jnp.float32)


def prompt_with_bfloat16_scores(q_n, q_r, k_n, k_r, v, *, scale, live=None):
    s = q_n.shape[2]
    lg = _scores(q_n, q_r, k_n, k_r, scale)
    seen = jnp.tril(jnp.ones((s, s), bool))
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), -1), v)


def decode_with_bfloat16_scores(q_lat, q_rope, pool, tables, pos, *, scale):
    r = q_lat.shape[-1]
    g = pool[tables]                                        # [b, T, w, bs]
    b, T, w, bs = g.shape
    g = g.transpose(0, 1, 3, 2).reshape(b, T * bs, w)
    lg = _scores(q_lat[:, :, None], q_rope[:, :, None],
                 g[:, None, :, :r], g[..., r:], scale)[:, :, 0]
    seen = jnp.arange(T * bs)[None, None] <= pos[:, None, None]
    return jnp.einsum("bhk,bkr->bhr",
                      jax.nn.softmax(jnp.where(seen, lg, -jnp.inf), -1),
                      g[..., :r])


def plant(monkeypatch, fault):
    """Plants ``fault`` in the program -> (model, the sound parameters)."""
    attention = program.LatentAttention
    real_rotate, real_rms = attention._rotate, program._rms
    if fault == "rotated_key_dropped":
        # [b, s, dr] is the one key; the queries' parts are [b, h, s, dr]
        monkeypatch.setattr(
            attention, "_rotate", lambda self, x, rows:
            jnp.zeros_like(x) if x.ndim == 3 else real_rotate(self, x, rows))
    elif fault == "latent_norm_skipped":
        monkeypatch.setattr(
            program, "_rms", lambda x, norm: x if norm.weight.value.shape[0]
            == TWO.kv_lora_rank else real_rms(x, norm))
    elif fault == "bias_in_the_weights":
        monkeypatch.setattr(laguna, "_moe_router",
                            router_with(bias_in_weights=True))
    elif fault == "plain_top_k":
        monkeypatch.setattr(laguna, "_moe_router", router_with(plain=True))
    elif fault == "bfloat16_scores":
        monkeypatch.setattr(program, "mla_prompt_attention",
                            prompt_with_bfloat16_scores)
        monkeypatch.setattr(program, "mla_paged_attention",
                            decode_with_bfloat16_scores)
    model, params = build(TWO)
    moe = model.model.layers[1].moe
    if fault == "no_mscale":
        for blk in model.model.layers:
            blk.attn.scale = 1.0 / math.sqrt(TWO.head_dim)
    elif fault == "an_expert_left_out":
        # the one the bias favours most
        e = int(jnp.argmax(moe.expert_bias.value))
        moe.experts_down.value = moe.experts_down.value.at[e].set(0.0)
    elif fault == "shared_expert_left_out":
        moe.shared.down.weight.value = \
            jnp.zeros_like(moe.shared.down.weight.value)
    return model, params


FAULTS = ("rotated_key_dropped", "latent_norm_skipped", "no_mscale",
          "bias_in_the_weights", "plain_top_k", "an_expert_left_out",
          "shared_expert_left_out", "bfloat16_scores")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(monkeypatch, fault):
    model, params = plant(monkeypatch, fault)
    assert served(model, params) > 20 * LOGITS


def test_the_planting_itself_changes_nothing(monkeypatch):
    """The faulty router and reads with their fault switched off are the
    sound program: what the cases above read is the fault."""
    monkeypatch.setattr(laguna, "_moe_router", router_with())
    model, params = build(TWO)
    assert served(model, params) < LOGITS
