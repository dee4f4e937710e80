"""Keye-VL-2.0-30B-A3B's language model (``models/keye.py``: a configuration
of the decoder in ``models/laguna.py`` with a learned sparse attention's
indexer) against its plain reference (``perfbench/families/keye.py``) at a
small size on the CPU, seeded random weights: the program's forward and the
sets its queries choose, prefill then decode through the serving engine's
paged cache with a third per-token array (logits, not tokens), the ops on a
table whose blocks are not contiguous, and that a model without ``sa_config``
is what it was. The faults the comparison must see are in
``tests/test_keye_faults.py``.

Tolerances: float32 at toy size against float32 at ``highest``: only the
order of the reductions differs, so whole logits agree to 5e-5 (Mellum's
and LFM2's tests hold the same) and bfloat16 weights fail it by three
orders. A near tie at the ``topk``-th score that falls the other way under
another reduction order would show as a different set: the seeds here are
ones where the sets are EQUAL at every layer and row, which the tests
assert (at toy size two indexer heads leave a quarter of the scores exactly
0, so ties at the cut are common and the tie rule, lower index first, is
exercised)."""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu import profiler                                # noqa: E402
from paddle_tpu.observability import compile_tracker           # noqa: E402
from paddle_tpu.dygraph import layers                          # noqa: E402
from paddle_tpu.dygraph.tensor import Tensor                   # noqa: E402
from paddle_tpu.models import (KEYE_CONFIGS, MELLUM_CONFIGS,   # noqa: E402
                               KeyeConfig, KeyeForCausalLM,
                               LagunaForCausalLM, MellumForCausalLM)
from paddle_tpu.ops import attention_ops as A                  # noqa: E402
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from perfbench.families import keye as family                  # noqa: E402
from test_mellum import Tap                                    # noqa: E402

TINY = KEYE_CONFIGS["keye-tiny"]
TOPK = TINY.indexer[2]
LOGITS = 5e-5       # float32 both sides: reduction order only


def file_of(mc):
    """The family's view of a program configuration (what a configuration
    file would hold)."""
    return dict(num_attention_heads=mc.num_attention_heads,
                num_key_value_heads=mc.num_key_value_heads,
                head_dim=mc.head_dim, rms_norm_eps=mc.rms_norm_eps,
                num_hidden_layers=mc.num_hidden_layers,
                rope_theta=mc.rope_parameters["full_attention"]["rope_theta"],
                sa_config=dict(mc.sa_config),
                num_experts_per_tok=mc.num_experts_per_tok)


def build(mc=TINY, seed=3):
    layers.seed(seed)
    model = KeyeForCausalLM(mc)
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
    """The family's ``forward`` for ``cfg``, jitted once a configuration
    (eager, its blocks of queries compile one by one)."""
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
        # decode step j fed token j-1 of the answer at position p + j - 1
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


def test_the_defaults_are_the_published_model():
    mc = KeyeConfig()
    assert mc.num_params() == 30_640_656_384          # the published 30B
    assert set(mc.layer_types) == {"full_attention"}
    assert set(mc.mlp_layer_types) == {"sparse"}
    assert (mc.num_experts, mc.num_experts_per_tok, mc.moe_intermediate_size,
            mc.indexer, mc.qk_norm, mc.attention_gate, mc.router_score) == \
        (128, 8, 768, (16, 64, 2048), True, False, "softmax")
    # a layer: attention, the two norms, the indexer (three projections
    # and a LayerNorm with bias) with the q/k norms' gains, router, experts
    layer = 2048 * 40 * 128 + 4096 * 2048 + 2 * 2048 \
        + 2048 * (1024 + 64 + 16) + 2 * 64 + 2 * 128 \
        + 2048 * 128 + 128 * 3 * 2048 * 768
    assert layer == 625_381_760
    assert KeyeConfig(num_hidden_layers=6).num_params() \
        == 6 * layer + 2 * 151936 * 2048 + 2048 == 4_374_622_464
    # one decoder: the model IS the Laguna decoder, with an indexer a layer
    assert issubclass(KeyeForCausalLM, LagunaForCausalLM)
    names = [n for n, _ in build()[0].named_parameters()]
    assert not any("g_proj" in n or "shared" in n for n in names)
    assert sum("index_" in n for n in names) == 5 * TINY.num_hidden_layers
    assert sum(np.size(p.value) for _, p in build()[0].named_parameters()) \
        == TINY.num_params()
    with pytest.raises(ValueError, match="window"):
        dataclasses.replace(TINY, layer_types=("sliding_attention",) * 4)


def program_sets(model, ids, states):
    """Every layer's chosen sets, bool [b, s, s], as the program chooses
    them: its own indexer (``LagunaAttention._index``) on the stream that
    entered the layer (``states``: the forward's ``collect``), then the
    scores and the selection ``sparse_prompt_attention`` takes."""
    b, s = ids.shape
    rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    causal = jnp.broadcast_to(rows[0][None, :] <= rows[0][:, None], (b, s, s))
    entered = [model.model.embed(Tensor(jnp.asarray(ids)))] + states[:-1]
    return [A.topk_mask(A.index_scores(*blk.attn._index(
        blk.attn_norm(x), rows)), causal, TOPK)
        for blk, x in zip(model.model.layers, entered)]


@pytest.mark.parametrize("chunk,tile", [
    (A.SPARSE_QUERY_CHUNK, A.SPARSE_KEY_TILE), (16, 8)])
def test_the_forward_and_the_chosen_sets_match_the_reference(
        tiny, monkeypatch, chunk, tile):
    """Whole-sequence logits, and THE SETS: every layer's chosen keys of
    every row equal the reference's (rows under ``topk`` keep all their
    keys, rows past it exactly ``topk``). With 16 queries a chunk and 8
    keys a tile the 64 rows pass the read's loop over chunks four times
    and its loop over key tiles two to eight times a chunk, as a served
    prompt does at 256 a chunk and 512 a tile."""
    model, params = tiny
    monkeypatch.setattr(A, "SPARSE_QUERY_CHUNK", chunk)
    monkeypatch.setattr(A, "SPARSE_KEY_TILE", tile)
    ids = np.random.default_rng(0).integers(1, 512, (2, 64))
    states, theirs = [], []
    got = model(ids, collect=states).value
    mine = program_sets(model, ids, states)
    want = family.forward(params, jnp.asarray(ids), file_of(TINY),
                          sets=theirs)
    assert float(jnp.max(jnp.abs(got - want))) < LOGITS
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert len(mine) == len(theirs) == TINY.num_hidden_layers
    sizes = np.minimum(np.arange(64) + 1, TOPK)
    for a, b in zip(mine, theirs):
        assert a.shape == b.shape == (2, 64, 64)
        assert bool(jnp.all(a == b))
        assert (np.asarray(a).sum(-1) == sizes).all()
    # the sets differ from row to row and are not the most recent keys
    last = np.asarray(mine[-1][0, -1])
    assert last[:64 - TOPK].any() and not last[64 - TOPK:].all()


def test_bfloat16_weights_fail_the_float32_tolerance():
    mc = dataclasses.replace(TINY, dtype="bfloat16")
    model, params = build(mc, seed=7)
    ids = np.random.default_rng(0).integers(1, 512, (1, 64))
    got = model(ids).value
    want = family.forward(params, jnp.asarray(ids), file_of(mc))
    assert 100 * LOGITS < float(jnp.max(jnp.abs(got - want))) < 0.2


#: (prompt rows, new tokens): a prompt under topk (5 < 8) that grows past
#: it while decoding; prompts past topk; prompts that pad their bucket
#: (20, 33 and 50 of 32 / 64) and one that fills it (64); six over four
#: slots, so two are admitted into a step that is already running
REQUESTS = [(5, 30), (20, 40), (64, 50), (33, 30), (50, 60), (7, 45)]


def test_prefill_then_decode_through_the_engine_matches_the_reference(tiny):
    model, params = tiny
    engine, tap, reqs = serve(model, prompts_of(1, REQUESTS))
    worst_logit, worst_deficit = served_against_the_reference(
        params, reqs, tap, file_of(TINY))
    assert worst_logit < LOGITS
    assert worst_deficit == 0.0
    # every request passed topk and spans several blocks of 8
    assert all(len(r.prompt) + len(r.tokens) > 3 * 8 > TOPK for r in reqs)
    stats = engine.stats()
    assert engine.cache.allocator.leaked() == 1      # the one trash block
    # the third array: [blocks, 8, 8] float32 a layer, beside K and V under
    # the same table; the gauge is its bytes as held
    pools = engine.cache.arrays()
    assert all(len(layer) == 3 and layer[2].shape == (65, 8, 8)
               for layer in pools) and len(pools) == 4
    assert stats["index_cache_bytes"] == 4 * 65 * 8 * 8 * 4
    # the device counters, counted by the selected read itself over a
    # decode step's live rows and the 4 layers: the keys eligible (a row's
    # context) and the keys whose K and V were read (min(context, topk));
    # a step dispatched ahead of a request's last commit counts too
    assert engine.spec.counters == ("experts_touched", "sparse_keys_live",
                                    "sparse_keys_read")
    steps = stats["sampler_dispatches"]
    assert steps > 60
    contexts = [len(r.prompt) + j for r in reqs
                for j in range(1, len(r.tokens))]
    made = 4 * sum(contexts)
    kept = 4 * sum(min(c, TOPK) for c in contexts)
    assert made <= stats["sparse_keys_live"] <= made + steps * 4 * 128
    assert kept <= stats["sparse_keys_read"] <= kept + steps * 4 * TOPK
    assert stats["sparse_keys_read"] < 0.5 * stats["sparse_keys_live"]
    assert 1 <= stats["experts_touched"] / steps / 4 <= 8


def test_the_counter_of_keys_read_follows_what_the_program_reads(tiny):
    """With the selection gone (``topk`` past every context) the SAME
    traffic reads every eligible key and the counters say so: the share
    ``sparse_keys_read_share_pct.keye`` reads is 100."""
    model, _ = build(dataclasses.replace(
        TINY, sa_config=dict(TINY.sa_config, topk=4096)))
    engine, _, _ = serve(model, prompts_of(1, REQUESTS[:3]))
    stats = engine.stats()
    assert stats["sparse_keys_read"] == stats["sparse_keys_live"] > 0


# ---- a prompt's read follows its live causal triangle (PR 47)

@pytest.fixture
def toy_tiling(monkeypatch):
    """16 queries a chunk, 8 keys a tile: 64 rows are four chunks, eight
    tiles and four selection slices of 16 keys (read when a read is
    traced, so a test under it builds its own model)."""
    monkeypatch.setattr(A, "SPARSE_QUERY_CHUNK", 16)
    monkeypatch.setattr(A, "SPARSE_KEY_TILE", 8)


def read_operands(seed, s=64, b=2):
    """A read's operands at toy widths: 4 query / 2 KV heads of ``s``
    values, the indexer's view from the toy model's own ``_index()`` of
    random rows, and V the IDENTITY, so that a row's output is its
    softmax weights over the ``s`` keys and its chosen set the keys whose
    weight is not 0."""
    rng = np.random.default_rng(seed)
    attn = build(seed=seed)[0].model.layers[0].attn
    rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    qi, w, ki = attn._index(
        jnp.asarray(rng.normal(size=(b, s, TINY.hidden_size)), jnp.float32),
        rows)
    q = jnp.asarray(rng.normal(size=(b, 4, s, s)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, 2, s, s)), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(s, dtype=jnp.float32), (b, 2, s, s))
    return q, k, v, qi, w, ki


def rectangle(q, k, v, chosen):
    """The read as the whole ``[s, s]`` rectangle under the chosen sets'
    mask: every query times every key, one softmax a row."""
    g = q.shape[1] // k.shape[1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, axis=1)) \
        / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(chosen[:, None], logits, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, jnp.repeat(v, g, axis=1))


def chosen_sets(qi, w, ki):
    """Every row's chosen set over ALL the keys, bool [b, s, s], as the
    rectangle took them: the scores and the selection under the causal
    mask alone."""
    b, s = w.shape[:2]
    at = jnp.arange(s)
    return A.topk_mask(A.index_scores(qi, w, ki), jnp.broadcast_to(
        at[None, :] <= at[:, None], (b, s, s)), TOPK)


#: the call's own rows in a bucket of 64 -> the key tiles its four chunks
#: multiply (16 queries a chunk, 8 keys a tile)
LIVES = {"the_bucket": (64, [2, 4, 6, 8]),
         "under_one_chunk": (10, [2, 0, 0, 0]),
         "one_row_past_a_chunks_edge": (33, [2, 4, 5, 0]),
         "one_row_past_a_tiles_edge": (25, [2, 4, 0, 0]),
         "under_topk": (5, [1, 0, 0, 0])}


@pytest.mark.parametrize("case", sorted(LIVES))
def test_the_bounded_read_is_the_rectangles_on_every_live_row(toy_tiling,
                                                              case):
    """The read that stops at a chunk's frontier and at the call's own
    rows against the rectangle written here: the live rows' weights within
    the float32 tolerance, their chosen sets EQUAL (read off the weights:
    V is the identity), every head of a row the same set, rows past
    ``live`` zeros, and the tiles the loop counted the ones
    ``sparse_prompt_tiles`` gives it."""
    live, tiles = LIVES[case]
    q, k, v, qi, w, ki = read_operands(11)
    want = chosen_sets(qi, w, ki)
    got, ran = jax.jit(lambda n: A.sparse_prompt_attention(
        q, k, v, qi, w, ki, TOPK, live=n))(live)
    got = np.asarray(got)
    assert got.shape == (2, 4, 64, 64)
    assert np.abs(got - np.asarray(rectangle(q, k, v, want)))[:, :, :live] \
        .max() < LOGITS
    assert ((got[:, :, :live] > 0)
            == np.asarray(want)[:, None, :live]).all()
    assert (np.asarray(want).sum(-1)[:, :live]
            == np.minimum(np.arange(live) + 1, TOPK)).all()
    assert (got[:, :, live:] == 0).all()
    assert A.sparse_prompt_tiles(64, live).tolist() == tiles
    assert int(ran) == sum(tiles)
    assert A.sparse_prompt_pairs(2, 64, live) \
        == (2 * 16 * 8 * sum(tiles), 2 * 64 * 64)


def test_a_tile_with_none_of_a_rows_keys_adds_no_weight(toy_tiling):
    """8 chosen keys over up to 64: most rows choose NO key in some tile
    they pass, the first tile among them (nothing chosen yet, the running
    maximum still at its start). Such a tile's masked logits equal the
    running maximum and must not become weights: exactly 0 there, and the
    row's weights sum to 1 over its chosen keys. Without a length
    (``forward()``'s call) the causal bound alone holds: every chunk
    runs."""
    q, k, v, qi, w, ki = read_operands(12)
    want = np.asarray(chosen_sets(qi, w, ki))
    got, ran = A.sparse_prompt_attention(q, k, v, qi, w, ki, TOPK)
    got = np.asarray(got)
    assert int(ran) == 2 + 4 + 6 + 8
    by_tile = want.reshape(2, 64, 8, 8).any(-1)             # [b, row, tile]
    passed = np.arange(8)[None, :] * 8 <= np.arange(64)[:, None]
    empty = np.logical_and(~by_tile, passed[None])
    assert empty[:, :, 0].sum() >= 10 and empty[:, :, 1:].sum() > 100
    weights = got.reshape(2, 4, 64, 8, 8)
    assert (weights[np.broadcast_to(empty[:, None], (2, 4, 64, 8))]
            == 0).all()
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-6)
    assert ((got > 0) == want[:, None]).all()


@pytest.mark.parametrize("lengths", [(37, 60), (64, 17)])
def test_two_lengths_share_a_buckets_one_program(toy_tiling, lengths):
    """Rows past ``live`` are read by nothing: two prompts of one bucket,
    one after the other through ONE compiled prefill program (the length
    is data), each with the reference's logits on its last live row and
    the reference's tokens decoded after it."""
    model, params = build(seed=5)
    engine = ServingEngine(model, max_slots=2, max_len=128, buckets=[64],
                           block_size=8, num_blocks=0, prefix_cache=False,
                           eos_token_id=None)
    tap = Tap(engine)
    for n, (prompt, new) in zip(lengths, prompts_of(
            5, [(n, 12) for n in lengths])):
        req = engine.submit(prompt, max_new_tokens=new)
        engine.step()                       # the prompt's own dispatch
        ids = np.zeros((1, 128), np.int32)
        ids[0, :n] = prompt
        ref = np.asarray(reference(file_of(TINY))(params, jnp.asarray(ids)))
        assert np.abs(tap.pending[0] - ref[0, n - 1]).max() < LOGITS
        engine.run_until_idle()
        worst_logit, worst_deficit = served_against_the_reference(
            params, [req], tap, file_of(TINY))
        assert worst_logit < LOGITS and worst_deficit == 0.0
    assert engine._prefill_fns[64]["traces"]["count"] == 1
    assert engine.stats()["sparse_prompt_keys_rect"] == 2 * 4 * 64 * 64


def test_the_prompt_counters_are_the_loops_own_count(toy_tiling):
    """``sparse_prompt_keys_read`` / ``_rect`` of ``engine.stats()``: a
    dispatch and layer, the pairs the read multiplies as the loop itself
    counts its tiles (the op called here at each prompt's bucket and
    length), and ``rows x bucket`` of them."""
    model, _ = build(seed=5)
    lengths = [5, 25, 33, 64, 10, 32]
    engine, _, _ = serve(model, prompts_of(2, [(n, 2) for n in lengths]))
    stats = engine.stats()
    ran = {bucket: jax.jit(lambda live, ops=read_operands(
        13, s=bucket, b=1): A.sparse_prompt_attention(
            *ops, TOPK, live=live)[1]) for bucket in (32, 64)}
    read = rect = 0
    for n in lengths:
        bucket = 32 if n <= 32 else 64
        assert A.sparse_prompt_tiling(bucket)[:2] == (16, 8)
        read += 4 * 16 * 8 * int(ran[bucket](n))
        rect += 4 * bucket * bucket
    assert (stats["sparse_prompt_keys_read"],
            stats["sparse_prompt_keys_rect"]) == (read, rect)
    assert 0.2 * rect < read < 0.5 * rect


def test_the_ops_follow_a_table_whose_blocks_are_not_contiguous():
    """Two requests whose blocks interleave in the pool (as two admitted in
    turn leave them), rows written by the prompt's many-row form and the
    decode step's one-row form: the chosen rows are read at the right
    (block, offset)."""
    rng = np.random.default_rng(4)
    bs, d, di, hkv, hq, hi, topk, T = 8, 16, 8, 2, 4, 2, 8, 6
    tables = jnp.asarray([[1, 3, 5, 7, 9, 11], [2, 4, 6, 8, 10, 12]],
                         jnp.int32)
    f32 = jnp.float32
    kp = jnp.zeros((13, hkv, bs, d), f32)
    vp, ip = kp, jnp.zeros((13, di, bs), f32)
    lens = [37, 22]
    k = jnp.asarray(rng.normal(size=(2, hkv, 40, d)), f32)
    v = jnp.asarray(rng.normal(size=(2, hkv, 40, d)), f32)
    ki = jnp.asarray(rng.normal(size=(2, 40, di)), f32)
    # a prompt's rows at once (more than 64 rows in all), then row by row
    first = jnp.zeros((2,), jnp.int32)
    kp = A.block_scatter_write(kp, k[:, :, :36], first, tables)
    vp = A.block_scatter_write(vp, v[:, :, :36], first, tables)
    ip = A.index_pool_write(ip, ki[:, :36], first, tables)
    for row in range(36, 40):
        at = jnp.full((2,), row, jnp.int32)
        kp = A.block_scatter_write(kp, k[:, :, row:row + 1], at, tables)
        vp = A.block_scatter_write(vp, v[:, :, row:row + 1], at, tables)
        ip = A.index_pool_write(ip, ki[:, row:row + 1], at, tables)
    # a many-row write that starts inside a block keeps the block's rows
    again = A.index_pool_write(ip, ki[:, 3:39], jnp.full((2,), 3, jnp.int32),
                               tables)
    assert bool(jnp.all(again == ip))
    q = jnp.asarray(rng.normal(size=(2, hq, 1, d)), f32)
    qi = jnp.asarray(rng.normal(size=(2, hi, di)), f32)
    w = jnp.asarray(rng.normal(size=(2, hi)), f32)
    pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
    scores = A.index_scores_paged(qi, w, ip, tables)
    got, reads = A.sparse_decode_attention(q, kp, vp, tables, pos, scores,
                                           topk)
    assert scores.shape == (2, T * bs)
    # what the read counted: a row's context eligible, topk keys read
    assert np.asarray(reads).tolist() == [[n, topk] for n in lens]
    for r, n in enumerate(lens):
        index = np.maximum(np.einsum("jd,sd->js", qi[r], ki[r, :n]), 0)
        index = (index * np.asarray(w[r])[:, None]).sum(0)
        np.testing.assert_allclose(scores[r, :n], index, rtol=1e-5,
                                   atol=1e-5)
        chosen = np.sort(np.argsort(-index, kind="stable")[:topk])
        for j in range(hq):
            logit = np.asarray(k[r, j // 2, chosen]) @ np.asarray(q[r, j, 0]) \
                / np.sqrt(d)
            p = np.exp(logit - logit.max())
            want = (p / p.sum()) @ np.asarray(v[r, j // 2, chosen])
            np.testing.assert_allclose(got[r, j, 0], want, rtol=1e-4,
                                       atol=1e-5)


@pytest.mark.parametrize("k", [1, 7, 39, 40, 64])
def test_the_selection_is_exact_with_ties_to_the_lower_index(k):
    """``topk_mask`` (a bisection on the scores' bits, no sort) against
    ``lax.top_k`` on scores rounded so that ties are common, negative and
    zero scores among them, under a mask."""
    rng = np.random.default_rng(k)
    x = jnp.asarray(rng.normal(size=(6, 40)).round(1), jnp.float32)
    valid = jnp.asarray(rng.random((6, 40)) < 0.8)
    got = np.asarray(A.topk_mask(x, valid, k))
    _, idx = jax.lax.top_k(jnp.where(valid, x, -jnp.inf), min(k, 40))
    want = np.zeros((6, 40), bool)
    for r in range(6):
        want[r, np.asarray(idx[r])] = True
    assert (got == (want & np.asarray(valid))).all()
    assert (got.sum(-1) == np.minimum(np.asarray(valid).sum(-1), k)).all()
    assert (np.asarray(family.chosen_keys(x, valid, k)) == got).all()


def test_zeros_of_either_sign_are_one_score_and_tie_by_index():
    """A score of -0.0 (a negative weight times a ReLU's 0) is the score
    0.0: the cut falls among them by index, in the mask's bisection and
    behind the decode row's ``lax.top_k`` alike."""
    x = jnp.asarray([[0.0, -0.0, -0.0, 0.0, -1.0, 0.0, -0.0, 2.0]])
    valid = jnp.ones((1, 8), bool)
    want = [[True, True, True, False, False, False, False, True]]
    assert np.asarray(A.topk_mask(x, valid, 4)).tolist() == want
    assert np.asarray(family.chosen_keys(x, valid, 4)).tolist() == want
    pool = jnp.arange(8 * 4, dtype=jnp.float32).reshape(1, 1, 8, 4)
    read, counted = A.sparse_decode_attention(
        jnp.zeros((1, 1, 1, 4)), pool, pool, jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([7]), x, 4)
    # uniform weights over the chosen rows 0, 1, 2 and 7 of the one block
    np.testing.assert_allclose(read[0, 0, 0], pool[0, 0, [0, 1, 2, 7]].mean(0))
    assert counted.tolist() == [[8, 4]]


def test_a_model_without_an_indexer_is_what_it_was():
    """Mellum's decoder builds no indexer parameter, declares no third
    array, and its engine reports none of the selection's numbers."""
    layers.seed(3)
    model = MellumForCausalLM(MELLUM_CONFIGS["mellum-tiny"])
    model.eval()
    assert not any("index_" in n for n, _ in model.named_parameters())
    spec = model.serving_spec()
    assert all(k.extra == () for k in spec.cache_kinds)
    assert spec.counters == ("experts_touched",)
    engine = ServingEngine(model, max_slots=2, max_len=64, buckets=[32],
                           block_size=8, num_blocks=0, prefix_cache=False,
                           eos_token_id=None)
    assert all(len(layer) == 2 for layer in engine.cache.arrays())
    assert engine.cache.pool.extra == ()
    assert not {"index_cache_bytes", "sparse_keys_live",
                "sparse_keys_read"} & set(engine.stats())


def test_the_seam_refuses_what_the_steps_cannot_run(tiny):
    model, _ = tiny
    with pytest.raises(ValueError, match="keye is not served with "
                                         "prefix_cache"):
        ServingEngine(model, max_slots=2, max_len=64, buckets=[32],
                      block_size=8, prefix_cache=True, eos_token_id=None)
    with pytest.raises(ValueError, match="int8"):
        ServingEngine(model, max_slots=2, max_len=64, buckets=[32],
                      block_size=8, prefix_cache=False, kv_dtype="int8",
                      eos_token_id=None)
    share = dataclasses.replace(TINY, held_experts=(0, 4))
    with pytest.raises(ValueError, match="share of the model"):
        KeyeForCausalLM(share).serving_spec()


def test_a_model_with_an_indexer_refuses_a_loss_by_name(tiny):
    """No gradient passes the selection, so a training call would train
    neither q, k, v nor the indexer: refused, not run silently."""
    model, _ = tiny
    ids = np.ones((1, 16), np.int32)
    with pytest.raises(ValueError, match="KeyeForCausalLM with an indexer "
                                         r"\(sa_config\) is served, not "
                                         "trained"):
        model(ids, labels=ids)


def test_the_build_and_the_first_trace_have_spans():
    profiler.start_profiler()
    try:
        model, _ = build()
        forward = compile_tracker.tracked_jit(
            "test_keye_forward", lambda ids: model(ids).value)
        forward(np.ones((1, 16), np.int32))
        forward(np.ones((1, 16), np.int32))
    finally:
        import contextlib
        import io
        import json
        import tempfile
        with tempfile.TemporaryDirectory() as d, \
                contextlib.redirect_stdout(io.StringIO()):
            path = os.path.join(d, "spans.json")
            profiler.stop_profiler(profile_path=path)
            names = [ev["name"] for ev in json.load(open(path))["traceEvents"]]
    assert names.count("keye.build") == 1
    assert not [n for n in names if n.endswith(".first_trace")]
    # the first forward's tracing is the site's account, not a span's
    assert forward.record.count == 1 and forward.record.trace_ms > 0
    assert "laguna.build" not in names
