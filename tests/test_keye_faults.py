"""The faults the comparison of ``tests/test_keye.py`` must see, each
planted in the program and each read OVER the tolerance the sound program
is under (ISSUE 46, Tentpole 3): the selection dropped, scores taken
without the causal mask, dead table entries eligible, the indexer's key a
row late in its pool, the weights left out, the ReLU left out, the
indexer's keys unrotated, one set for all the queries of a chunk,
``topk - 1`` keys read. A fault of the prompt path is read on the whole
forward (and reaches the served path through the K and V of the layers
above); a fault of the decode path alone through the engine."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.models import laguna                           # noqa: E402
from paddle_tpu.ops import attention_ops as A                  # noqa: E402
from test_keye import (LOGITS, TINY, TOPK, build, file_of,     # noqa: E402
                       prompts_of, reference, serve,
                       served_against_the_reference)

#: two layers are enough: a fault in layer 0 reaches the steps through the
#: K and V layer 1 writes; a prompt under topk and one past it
TWO = dataclasses.replace(TINY, num_hidden_layers=2)
REQUESTS = [(5, 24), (40, 26)]


def whole(model, params):
    ids = np.random.default_rng(0).integers(1, 512, (2, 64))
    got = model(ids).value
    want = reference(file_of(TWO))(params, jnp.asarray(ids))
    return float(jnp.max(jnp.abs(got - want)))


def served(model, params):
    _, tap, reqs = serve(model, prompts_of(1, REQUESTS), max_slots=2,
                         buckets=[64])
    return served_against_the_reference(params, reqs, tap, file_of(TWO))[0]


def test_the_sound_program_is_under_the_tolerance_on_both_measures():
    model, params = build(TWO)
    assert whole(model, params) < LOGITS
    assert served(model, params) < LOGITS


def scores_without(relu=True, weights=True):
    def index_scores(q_idx, w, k_idx):
        dots = jnp.einsum("...tjd,...sd->...tjs", q_idx, k_idx)
        dots = jax.nn.relu(dots) if relu else dots
        return jnp.sum(dots * (w[..., None] if weights else 1.0), axis=-2)

    def index_scores_paged(q_idx, w, pool, tables):
        kg = pool[tables]                                   # [b, T, d, bs]
        b, T, _, bs = kg.shape
        dots = jnp.einsum("bjd,btdk->bjtk", q_idx, kg)
        dots = jax.nn.relu(dots) if relu else dots
        return jnp.sum(dots * (w[:, :, None, None] if weights else 1.0),
                       axis=1).reshape(b, T * bs)
    return index_scores, index_scores_paged


def plant(monkeypatch, fault):
    """Plants ``fault`` in the program -> the configuration to build."""
    real_mask, real_decode = A.topk_mask, A.sparse_decode_attention
    real_write, real_rotate = A.index_pool_write, laguna._rotate_half
    mc = TWO
    if fault == "selection_dropped":
        mc = dataclasses.replace(TWO, sa_config=dict(TWO.sa_config,
                                                     topk=4096))
    elif fault == "one_key_fewer":
        mc = dataclasses.replace(TWO, sa_config=dict(TWO.sa_config,
                                                     topk=TOPK - 1))
    elif fault == "no_causal_mask":
        # a prompt's query picks among ALL the rows of the call
        monkeypatch.setattr(A, "topk_mask", lambda s, valid, k: real_mask(
            s, jnp.ones_like(valid), k))
    elif fault == "one_set_a_chunk":
        # the chunk's last query chooses for all of them
        monkeypatch.setattr(A, "topk_mask", lambda s, valid, k:
                            jnp.logical_and(real_mask(s, valid, k)[..., -1:, :],
                                            valid))
    elif fault == "dead_entries_eligible":
        # a decode row picks among its table's rows past its own
        def decode(q, kp, vp, tables, pos, scores, topk, **kw):
            n = scores.shape[1]
            return real_decode(q, kp, vp, tables,
                               jnp.full_like(pos, n - 1), scores, topk, **kw)
        monkeypatch.setattr(laguna, "sparse_decode_attention", decode)
    elif fault == "index_key_a_row_late":
        monkeypatch.setattr(
            laguna, "index_pool_write", lambda pool, new, pos, tables:
            real_write(pool, new, jnp.asarray(pos, jnp.int32) + 1, tables))
    elif fault in ("no_weights", "no_relu"):
        prompt, paged = scores_without(relu=fault != "no_relu",
                                       weights=fault != "no_weights")
        monkeypatch.setattr(A, "index_scores", prompt)
        monkeypatch.setattr(laguna, "index_scores_paged", paged)
    elif fault == "keys_unrotated":
        # [b, s, di] is the indexer's key; its queries are [b, s, hi, di]
        monkeypatch.setattr(
            laguna, "_rotate_half", lambda x, rows, theta:
            x if x.ndim == 3 else real_rotate(x, rows, theta))
    else:
        raise KeyError(fault)
    return mc


#: fault -> the measure that must read it
FAULTS = {"selection_dropped": whole, "one_key_fewer": whole,
          "no_causal_mask": whole, "one_set_a_chunk": whole,
          "no_weights": whole, "no_relu": whole, "keys_unrotated": whole,
          "dead_entries_eligible": served, "index_key_a_row_late": served}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(monkeypatch, fault):
    mc = plant(monkeypatch, fault)
    model, params = build(mc)
    assert FAULTS[fault](model, params) > 20 * LOGITS


def test_a_fault_of_the_prompt_path_reaches_the_decode_steps(monkeypatch):
    """The decode steps read K and V the prompt wrote: what a prompt's
    rows chose wrongly below shows in the logits of the steps above."""
    mc = plant(monkeypatch, "no_weights")
    model, params = build(mc)
    assert served(model, params) > 20 * LOGITS
