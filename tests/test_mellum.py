"""Mellum2-class decoder (``models/mellum.py``: a configuration of the
decoder in ``models/laguna.py``) against its plain reference
(``perfbench/families/mellum.py``) at a small size on the CPU, seeded random
weights: the program's forward, prefill then decode through the serving
engine's paged cache (logits, not tokens), the decode-regime expert layer
against the training-regime one, and the served precision."""

import dataclasses
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
from paddle_tpu.models import (MELLUM_CONFIGS, LagunaForCausalLM,  # noqa: E402
                               MellumConfig, MellumForCausalLM)
from paddle_tpu.ops import decoder_ops as D                    # noqa: E402
from paddle_tpu.serving import ServingEngine                   # noqa: E402
from perfbench.families import mellum as family                # noqa: E402

TINY = MELLUM_CONFIGS["mellum-tiny"]


def file_of(mc):
    """The family's view of a program configuration (what a configuration
    file would hold)."""
    return dict(num_attention_heads=mc.num_attention_heads,
                num_key_value_heads=mc.num_key_value_heads,
                head_dim=mc.head_dim, rms_norm_eps=mc.rms_norm_eps,
                num_hidden_layers=mc.num_hidden_layers,
                layer_types=list(mc.layer_types),
                rope_parameters=mc.rope_parameters,
                sliding_window=mc.sliding_window,
                num_experts_per_tok=mc.num_experts_per_tok)


def build(mc, seed=3):
    layers.seed(seed)
    model = MellumForCausalLM(mc)
    model.eval()
    return model, {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


def test_the_defaults_are_the_published_model():
    mc = MellumConfig()
    assert mc.num_params() == 12_149_915_904          # the published 12B
    assert mc.layer_types[:4] == ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert set(mc.num_attention_heads_per_layer) == {32}
    assert (mc.num_experts, mc.num_experts_per_tok,
            mc.moe_intermediate_size, mc.sliding_window) == (64, 8, 896, 1024)
    assert MellumConfig(num_hidden_layers=12).num_params() == 5_465_956_608
    # one decoder serves both families: the model IS the Laguna decoder
    assert issubclass(MellumForCausalLM, LagunaForCausalLM)
    names = [n for n, _ in build(TINY)[0].named_parameters()]
    assert not any("g_proj" in n or "shared" in n for n in names)


def test_the_forward_matches_the_reference(tiny):
    model, params = tiny
    ids = np.random.default_rng(0).integers(1, 512, (2, 64))
    got = model(ids).value
    want = family.forward(params, jnp.asarray(ids), file_of(TINY))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_the_references_expert_loop_is_exact_whatever_the_imbalance():
    """One token repeated (the harness's padding): every row chooses the
    same experts, far more rows an expert than one chunk of the loop."""
    rng = np.random.default_rng(5)
    t, h, f, e, k = 96, 16, 8, 8, 2
    u = jnp.asarray(np.repeat(rng.normal(size=(3, h)), 32, axis=0),
                    jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)), jnp.float32)
    w13 = jnp.asarray(rng.normal(size=(e, h, 2 * f)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(e, f, h)), jnp.float32)
    was = family._EXPERT_ROWS
    family._EXPERT_ROWS = 20        # 32 rows an expert: two chunks, ragged
    try:
        got = family._experts(u, router, w13, w2, k)
    finally:
        family._EXPERT_ROWS = was
    r = jax.nn.softmax(u @ router, -1)
    top, idx = jax.lax.top_k(r, k)
    w = top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(u)
    for j in range(k):
        gu = jnp.einsum("th,thn->tn", u, w13[idx[:, j]])
        y = jnp.einsum("tf,tfh->th",
                       jax.nn.silu(gu[:, :f]) * gu[:, f:], w2[idx[:, j]])
        want = want + w[:, j, None] * y
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


class Tap:
    """Records the logits the engine's own compiled entries return, by
    request and position."""

    def __init__(self, engine):
        self.engine, self.rows = engine, {}
        spec = engine.spec
        real_decode, real_prefill = spec.decode_entry, engine._prefill_entry

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

        def prefill_entry(bucket):
            ent = real_prefill(bucket)

            def fn(*args):
                out = ent["fn"](*args)
                self.pending = np.asarray(out[0])
                return out
            return dict(ent, fn=fn)
        spec.decode_entry = decode_entry
        engine._prefill_entry = prefill_entry


def serve(model, requests, **kw):
    kw = dict(dict(max_slots=4, max_len=128, buckets=[32, 64], block_size=8,
                   num_blocks=0, prefix_cache=False, max_queue=16,
                   eos_token_id=None), **kw)
    engine = ServingEngine(model, **kw)
    tap = Tap(engine)
    reqs = [engine.submit(list(p), max_new_tokens=n) for p, n in requests]
    engine.run_until_idle()
    return engine, tap, reqs


REQUESTS = [(20, 40), (50, 60), (33, 30), (60, 50), (10, 100), (27, 70)]


def prompts_of(seed, requests=REQUESTS):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, 512, n).tolist(), new) for n, new in requests]


def test_prefill_then_decode_through_the_engine_matches_the_reference(tiny):
    """Six requests over four slots, contexts of 60-110 rows against a
    window of 16 and blocks of 8: every window layer frees blocks behind
    its window while its request decodes and writes blocks other requests
    freed. The DECODE STEPS' LOGITS (what the step computed at every
    position through the paged cache) against the reference's full forward
    pass on the final sequence, and the emitted tokens' deficits as the
    benchmark's check reads them."""
    model, params = tiny
    engine, tap, reqs = serve(model, prompts_of(1))
    assert all(r.state == "done" for r in reqs)
    cfg = file_of(TINY)
    worst_logit, worst_deficit = 0.0, 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        pad = np.zeros((1, 128), np.int32)
        pad[0, :len(seq)] = seq
        ref = np.asarray(family.forward(params, jnp.asarray(pad), cfg)[0])
        p, n = len(r.prompt), len(r.tokens)
        assert n == r.max_new_tokens and len(seq) > 16 + 2 * 8
        # decode step j fed token j-1 of the answer at position p + j - 1
        got = np.stack(tap.rows[r.id])
        assert got.shape[0] == n - 1
        want = ref[p:p + n - 1]
        worst_logit = max(worst_logit, float(np.abs(got - want).max()))
        d = ref[p - 1:p + n - 1].max(-1) \
            - ref[np.arange(p - 1, p + n - 1), seq[p:]]
        worst_deficit = max(worst_deficit, float(d.max()))
    assert worst_logit < 5e-5
    assert worst_deficit == 0.0
    stats = engine.stats()
    assert stats["window_blocks_freed"] > 6 * 3
    assert stats["kv_blocks_live_full"] == stats["kv_blocks_live_window"] == 0
    assert engine.cache.allocator.leaked() == 1      # the one trash block
    # the device counter: experts touched a layer of a decode step
    steps = stats["sampler_dispatches"]
    assert steps > 100
    assert 1 <= stats["experts_touched"] / steps / 4 <= 8


def test_the_served_precision_is_bfloat16_where_the_configuration_says():
    mc = dataclasses.replace(TINY, dtype="bfloat16", embed_init_std=1.0)
    model, params = build(mc, seed=7)
    assert {str(p.dtype) for p in params.values()} == {"bfloat16"}
    engine, tap, reqs = serve(model, prompts_of(2, REQUESTS[:3]))
    assert engine.kv_dtype == "bf16"
    pools = engine.cache.arrays()
    assert len(pools) == 4 and all(
        str(a.dtype) == "bfloat16" for layer in pools for a in layer)
    assert str(engine._counted.dtype) == "float32"   # the device counter
    cfg = file_of(mc)
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens)
        pad = np.zeros((1, 128), np.int32)
        pad[0, :len(seq)] = seq
        ref = np.asarray(family.forward(params, jnp.asarray(pad), cfg)[0])
        p, n = len(r.prompt), len(r.tokens)
        got = np.stack(tap.rows[r.id])
        assert got.dtype == np.float32              # the logits stay float32
        # bfloat16 matmul inputs against float32 at `highest`: hundredths
        assert float(np.abs(got - ref[p:p + n - 1]).max()) < 0.08
        d = ref[p - 1:p + n - 1].max(-1) \
            - ref[np.arange(p - 1, p + n - 1), seq[p:]]
        assert float(d.max()) < 0.05


@pytest.mark.parametrize("live_rows", [16, 5, 0])
def test_the_decode_regime_expert_layer_is_the_training_one_on_its_rows(
        live_rows):
    rng = np.random.default_rng(11)
    t, h, f, e, k = 16, 32, 16, 64, 8
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    w13 = jnp.asarray(rng.normal(size=(e, h, 2 * f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(e, f, h)) * 0.1, jnp.float32)
    scores = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    top, idx = jax.lax.top_k(jax.nn.softmax(scores, -1), k)
    weight = top / top.sum(-1, keepdims=True)
    idx = idx.astype(jnp.int32)
    live = jnp.arange(t) < live_rows
    got, touched = D.moe_experts_decode(x, weight, idx, w13, w2, live, tm=8)
    want = D.moe_experts(x, weight, idx, w13, w2, 0, e, 8)[0]
    keep = np.asarray(live)
    assert float(jnp.max(jnp.abs(got - want)[keep], initial=0.0)) < 1e-5
    assert float(jnp.max(jnp.abs(got[~keep]), initial=0.0)) == 0.0
    chosen = np.unique(np.asarray(idx)[keep])
    assert int(touched) == len(chosen)
    # an expert no live row chose owns no tile: its weights are not read
    route = D._route(idx, jnp.broadcast_to(live[:, None], idx.shape), e, 8,
                     -(-t * k // 8) + e, min_tiles=0)
    tiles = int(route["n_active"][0])
    counts = np.bincount(np.asarray(idx)[keep].ravel(), minlength=e)
    assert tiles == max(int(np.sum(-(-counts // 8))), 1)
    assert set(np.asarray(route["tile_group"])[:tiles]) <= \
        (set(chosen) or {e - 1})


def test_the_build_and_the_first_trace_have_spans():
    profiler.start_profiler()
    try:
        model, _ = build(TINY)
        forward = compile_tracker.tracked_jit(
            "test_mellum_forward", lambda ids: model(ids).value)
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
    assert names.count("mellum.build") == 1
    assert not [n for n in names if n.endswith(".first_trace")]
    # the first forward's tracing is the site's account, not a span's
    assert forward.record.count == 1 and forward.record.trace_ms > 0
    assert "laguna.build" not in names


@pytest.mark.parametrize("rows", [1, 32], ids=["decode", "prompt"])
def test_a_served_program_closes_over_no_position_table(rows):
    """cos and sin of a served call's rows are computed in the program: a
    table of ``max_position_embeddings`` rows gathered there was a constant
    a layer and a table, and made every served executable of the benchmark's
    cell 46 MB, more than the chip machine's compile cache keeps."""
    from paddle_tpu.dygraph.tape import no_grad
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.models.generation import _wrap_pools
    mc = dataclasses.replace(TINY, max_position_embeddings=8192)
    model, _ = build(mc)
    engine = ServingEngine(model, max_slots=2, max_len=128, buckets=[32],
                           block_size=8, num_blocks=0, prefix_cache=False,
                           eos_token_id=None)

    def call(ids, pos, tables, pools):
        with no_grad():
            logits, pools = model(
                Tensor(ids, stop_gradient=True), cache=_wrap_pools(pools),
                cache_pos=pos, block_tables=tables,
                last=None if rows == 1 else jnp.zeros((2,), jnp.int32))
        return logits.value
    closed = jax.make_jaxpr(call)(
        jnp.zeros((2, rows), jnp.int32), jnp.ones((2,), jnp.int32),
        jax.tree_util.tree_map(jnp.asarray, engine.cache.tables_arg()),
        engine.cache.arrays())
    # the parameters are closed over here (the engine passes them in);
    # nothing else is as large as a table of 8192 x head_dim / 2 values
    params = {id(p.value) for _, p in model.named_parameters()}
    others = [c for c in closed.consts if id(c) not in params]
    assert max([np.size(c) for c in others], default=0) < 8192 * 8


def test_the_routers_and_the_embedding_are_drawn_at_their_own_scales():
    mc = dataclasses.replace(TINY, hidden_size=256, embed_init_std=1.0,
                             router_init_std=0.08)
    model, params = build(mc)
    std = {n: float(jnp.std(v)) for n, v in params.items() if v.ndim >= 2}
    assert std["model.embed.weight"] == pytest.approx(1.0, rel=0.05)
    for n, s in std.items():
        if n.endswith("router.weight"):
            assert s == pytest.approx(0.08, rel=0.1)
        elif n.endswith("qkv_proj.weight") or n.endswith("experts_gate_up"):
            assert s == pytest.approx(0.02, rel=0.1)
