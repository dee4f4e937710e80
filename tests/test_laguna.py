"""The Laguna-class model (``models/laguna.py``), its operations
(``ops/decoder_ops.py``) and kernels (``ops/pallas/grouped_matmul.py``, the
window and grouped-KV paths of ``ops/pallas/flash_attention.py``) against
the plain reference of ``perfbench/families/laguna.py`` and against
composed ``jax.numpy``, at toy sizes on the CPU (Pallas in interpret mode).
"""

import dataclasses
import hashlib
import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as pt                                       # noqa: E402
from paddle_tpu import flags, jit                             # noqa: E402
from paddle_tpu.models import (LAGUNA_CONFIGS, LagunaConfig,  # noqa: E402
                               LagunaForCausalLM)
from paddle_tpu.models.laguna import LagunaAttention          # noqa: E402
from paddle_tpu.ops import decoder_ops as dops                # noqa: E402
from paddle_tpu.ops.attention_ops import _composed_attention  # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as gm        # noqa: E402
from paddle_tpu.ops.pallas.flash_attention import flash_attention  # noqa
from perfbench.families import laguna as ref                  # noqa: E402

TINY = LAGUNA_CONFIGS["laguna-tiny"]
WHOLE = {"experts": [0, 8], "kv_heads": [0, 2], "vocab_rows": [0, 512]}
SHARE = {"experts": [2, 6], "kv_heads": [1, 2], "vocab_rows": [128, 384]}


@pytest.fixture
def flash_from_seq_32():
    """The flash kernels take over at the toy sequences too."""
    was = flags.get_flag("pallas_min_seq")
    pt.set_flags({"pallas_min_seq": 32})
    yield
    pt.set_flags({"pallas_min_seq": was})


def file_config(held):
    with open(os.path.join(ROOT, "perfbench", "rehearsal",
                           "laguna-tiny.json")) as f:
        cfg = json.load(f)
    cfg["held"] = held
    cfg["vocab_size"] = held["vocab_rows"][1] - held["vocab_rows"][0]
    return cfg


def build(held, seed=3):
    cfg = file_config(held)
    pt.seed(seed)
    model = LagunaForCausalLM(ref.model_config(cfg))
    params = {n: p.value for n, p in model.named_parameters()}
    lo, hi = held["vocab_rows"]
    ids = jnp.asarray(np.random.RandomState(seed).randint(lo, hi, (2, 32)),
                      jnp.int32)
    return cfg, model, params, ids, jnp.roll(ids, -1, axis=1)


# ------------------------------------------------- program against reference

GRAD_NAMES = ("model.layers.1.moe.router.weight",
              "model.layers.2.moe.experts_gate_up",
              "model.layers.1.moe.experts_down",
              "model.layers.1.attn.qkv_proj.weight",
              "model.layers.0.attn.g_proj.weight",
              "model.embed.weight")


@pytest.fixture(scope="module", params=[WHOLE, SHARE],
                ids=["whole", "share"])
def against_reference(request):
    """One forward and one backward of the program and of the reference on
    the same seeded float32 weights, for the whole model and for a share."""
    was = flags.get_flag("pallas_min_seq")
    pt.set_flags({"pallas_min_seq": 32})
    try:
        cfg, model, params, ids, labels = build(request.param)

        # the program as it is run: compiled (an eager pass compiles op by
        # op and takes three times as long)
        def step(ids, labels):
            logits = model(ids)
            loss = model(ids, labels=labels)
            model.clear_gradients()
            loss.backward()
            return logits, loss
        logits, loss = jit.to_static(step, layers=[model],
                                     donate_state=False)(ids, labels)
        stats = model.moe_stats()
        grads = {n: p.grad.value for n, p in model.named_parameters()
                 if n in GRAD_NAMES}

        def ref_loss(p):
            logits = ref.forward(p, ids, cfg)
            lo = cfg["held"]["vocab_rows"][0]
            picked = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1),
                (labels - lo)[..., None], axis=-1)
            return -jnp.mean(picked), logits
        (want_loss, want_logits), want_grads = jax.value_and_grad(
            ref_loss, has_aux=True)(params)
        np.testing.assert_allclose(ref.loss(params, ids, labels, cfg),
                                   want_loss, rtol=1e-6)
        return dict(logits=logits.value, loss=loss.value, stats=stats,
                    grads=grads, want_logits=want_logits,
                    want_loss=want_loss, want_grads=want_grads)
    finally:
        pt.set_flags({"pallas_min_seq": was})


def test_logits_match_the_reference(against_reference):
    r = against_reference
    np.testing.assert_allclose(r["logits"], r["want_logits"], atol=2e-5)


def test_loss_matches_the_reference_and_nothing_is_dropped(
        against_reference):
    r = against_reference
    np.testing.assert_allclose(r["loss"], r["want_loss"], rtol=1e-5)
    assert sorted(r["stats"]) == [1, 2]
    assert all(s["dropped_pairs"] == 0 for s in r["stats"].values())


def test_the_model_reports_the_combines_rows(against_reference):
    """Two choices a token leave the prefix form nothing to save at the toy
    size: every layer reads the k-slot form's 1.0, in ``moe_stats()`` and
    as ``STAT_moe_combine_rows_permille_l<i>``."""
    from paddle_tpu import monitor
    stats = against_reference["stats"]
    assert all(s["fast_path"] and s["combine_rows_share"] == 1.0
               for s in stats.values())
    published = monitor.stats_with_prefix("STAT_moe_combine_rows_permille")
    assert published == {f"STAT_moe_combine_rows_permille_l{i}": 1000
                         for i in stats}


@pytest.mark.parametrize("name", GRAD_NAMES)
def test_gradient_matches_the_reference(against_reference, name):
    got, want = (against_reference[k][name] for k in ("grads", "want_grads"))
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_a_held_share_is_a_parameter_of_the_one_model():
    whole = LagunaConfig()
    assert whole.num_params() == 33_442_596_864        # the published 33.4B
    share = dataclasses.replace(
        whole, num_hidden_layers=9, held_experts=(0, 32),
        held_kv_heads=(0, 1), held_vocab=(0, 12544))
    assert share.num_params() == 975_874_048
    assert [share.query_heads(i) for i in range(5)] == [6, 8, 8, 8, 6]
    with pytest.raises(ValueError):
        dataclasses.replace(whole, held_experts=(250, 260)).experts


# ----------------------------------------------------------- the shares add

def test_expert_shares_add_up_to_the_whole_expert_layer():
    """Eight shares' routed parts plus the shared expert once are the whole
    expert layer of the uncut reference."""
    cfg, model, params, _, _ = build(WHOLE)
    pre = "model.layers.1.moe."
    u = jnp.asarray(np.random.RandomState(1).randn(2, 24, 64), jnp.float32)
    router = params[pre + "router.weight"]
    w13, w2 = params[pre + "experts_gate_up"], params[pre + "experts_down"]
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(u, router, w13, w2, cfg) + ref._swiglu(
            u, params[pre + "shared.gate_up.weight"],
            params[pre + "shared.down.weight"])
        r = dops._moe_router(None, {"X": [u], "W": [router]},
                             {"top_k": 2, "scale": 2.5})
        total = ref._swiglu(u, params[pre + "shared.gate_up.weight"],
                            params[pre + "shared.down.weight"])
        pairs = 0
        for e in range(8):
            out = dops._moe_experts(
                None, {"X": [u], "TopkIdx": r["TopkIdx"],
                       "TopkWeight": r["TopkWeight"],
                       "WGateUp": [w13[e:e + 1]], "WDown": [w2[e:e + 1]]},
                {"expert_lo": e, "num_experts": 8, "tile_m": 8})
            total = total + out["Out"][0]
            pairs += int(out["Stats"][0][0])
            assert int(out["Stats"][0][2]) == 0
    assert pairs == 2 * 24 * 2                  # every choice computed once
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_head_shares_partial_sums_add_up_to_whole_attention(
        flash_from_seq_32):
    """Each KV head's share (its query group, gate columns, Wo rows) gives
    a partial sum; the shares' sum is whole attention."""
    cfg = dataclasses.replace(TINY, num_hidden_layers=2)
    layer, d = 1, cfg.head_dim                  # a window layer, 8 over 2
    pt.seed(5)
    whole = LagunaAttention(cfg, layer)
    h = pt.to_tensor(np.random.RandomState(2).randn(2, 64, 64)
                     .astype(np.float32))
    want = whole(h).value
    hq, group = 8, 4
    wqkv = whole.qkv_proj.weight.value
    total = 0.0
    for j in range(2):
        part = LagunaAttention(dataclasses.replace(
            cfg, held_kv_heads=(j, j + 1)), layer)
        q = slice(j * group * d, (j + 1) * group * d)
        part.qkv_proj.weight.value = jnp.concatenate(
            [wqkv[:, q], wqkv[:, (hq + j) * d:(hq + j + 1) * d],
             wqkv[:, (hq + 2 + j) * d:(hq + 2 + j + 1) * d]], axis=1)
        part.g_proj.weight.value = \
            whole.g_proj.weight.value[:, j * group:(j + 1) * group]
        part.o_proj.weight.value = whole.o_proj.weight.value[q]
        total = total + part(h).value
    np.testing.assert_allclose(total, want, atol=1e-5)


# ------------------------------------------------------------------ window

def test_a_key_outside_the_window_does_not_move_the_output(
        flash_from_seq_32):
    cfg = dataclasses.replace(TINY, num_hidden_layers=2)
    pt.seed(7)
    attn = LagunaAttention(cfg, 1)              # window 16
    x = np.random.RandomState(3).randn(1, 64, 64).astype(np.float32)
    base = attn(pt.to_tensor(x)).value
    query = 40
    for key, moves in ((query - 16, False), (query - 15, True),
                       (query, True), (query + 1, False)):
        y = x.copy()
        y[0, key] += 1.0
        got = attn(pt.to_tensor(y)).value
        changed = not np.allclose(got[0, query], base[0, query], atol=1e-7)
        assert changed == moves, (key, moves)


# ------------------------------------------------------------------ rotary

def test_plain_rotary_table_is_the_formula():
    cos, sin = dops.rotary_tables(32, 128, 10000.0)
    inv = 10000.0 ** (-np.arange(0, 128, 2) / 128.0)
    ang = np.arange(32)[:, None] * inv[None]
    np.testing.assert_allclose(cos, np.cos(ang), atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(ang), atol=1e-6)


def test_yarn_table_is_the_formula_on_the_published_keys():
    rope = LagunaConfig().rope_parameters["full_attention"]
    inv, att = dops.rotary_inv_freq(64, rope["rope_theta"], rope)
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64.0)
    # correction dims: 64 ln(4096 / (beta 2 pi)) / (2 ln 500000) is 5.66 for
    # beta_fast 64 and 15.80 for beta_slow 1 -> the ramp runs from 5 to 16
    ramp = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)
    np.testing.assert_allclose(inv, base / 64 * ramp + base * (1 - ramp),
                               rtol=1e-12)
    assert inv[5] == base[5] and inv[16] == base[16] / 64
    assert att == pytest.approx(0.1 * math.log(64) + 1, abs=1e-9)
    assert att == pytest.approx(1.4158883, abs=1e-7)
    # the reference computes its own, from the same keys
    rinv, ratt, r = ref._inv_freq(rope, 128)
    assert r == 64 and ratt == att
    np.testing.assert_allclose(rinv, inv, rtol=1e-5)
    cos, _ = dops.rotary_tables(8, 64, rope["rope_theta"], rope)
    np.testing.assert_allclose(cos[3], att * np.cos(3 * inv), atol=1e-6)


def test_rotary_rotates_half_pairs_and_passes_the_rest():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 2, 4, 8), jnp.float32)
    cos, sin = dops.rotary_tables(4, 4, 10000.0)      # r = 4 of d = 8
    out = dops._rotary_embedding(None, {"X": [x], "Cos": [jnp.asarray(cos)],
                                        "Sin": [jnp.asarray(sin)]},
                                 {})["Out"][0]
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    np.testing.assert_allclose(
        out[0, 1, 3, 0], x[0, 1, 3, 0] * cos[3, 0] - x[0, 1, 3, 2] * sin[3, 0],
        rtol=1e-6)
    np.testing.assert_allclose(
        out[0, 1, 3, 2], x[0, 1, 3, 2] * cos[3, 0] + x[0, 1, 3, 0] * sin[3, 0],
        rtol=1e-6)


# ------------------------------------------------------------ expert layer

def dense_experts(x, weight, idx, w13, w2, lo):
    f = w2.shape[1]
    out = jnp.zeros_like(x)
    for e in range(w13.shape[0]):
        w_e = jnp.sum(jnp.where(idx == lo + e, weight, 0.0), axis=-1)
        hid = x @ w13[e]
        out = out + w_e[:, None] * (
            (jax.nn.silu(hid[:, :f]) * hid[:, f:]) @ w2[e])
    return out


@pytest.mark.parametrize("t,k,lo,held,experts,paths", [
    (64, 2, 2, 2, 8, {"uniform": (True, 1.0), "one held expert": (True, 1.0),
                      "every choice held": (False, 1.0)}),
    (128, 4, 3, 4, 32, {"uniform": (True, (112 + 32 + 8 + 8 + 128) / 512),
                        "one held expert": (True, 1.0),
                        "every choice held": (False, 1.0)})],
    ids=["k-slot", "prefix"])
def test_no_drop_and_no_compile_whatever_the_routing(t, k, lo, held, experts,
                                                     paths):
    """All tokens on one held expert, all choices on held experts (more
    pairs than the fast buffer holds: the chunked path) and a uniform
    routing give the dense result, 0 dropped, through one compilation. At
    the second shape ``_plan`` chooses the combine's prefix form: the
    uniform routing runs it, every token on one held expert overflows the
    first prefix (112 rows for 128 tokens) inside a buffer that still holds
    the pairs and takes the fast buffer with the k-slot combine, every
    choice held overflows both and takes the chunks."""
    h, f = 32, 16
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(t, h), jnp.float32)
    w13 = jnp.asarray(r.randn(held, h, 2 * f) * 0.2, jnp.float32)
    w2 = jnp.asarray(r.randn(held, f, h) * 0.2, jnp.float32)
    weight = jnp.asarray(r.rand(t, k) + 0.5, jnp.float32)
    absent = [e for e in range(experts) if not lo <= e < lo + held]
    routings = {
        "uniform": np.stack([r.permutation(experts)[:k] for _ in range(t)]),
        "one held expert": np.stack(
            [np.concatenate([[lo + 1], r.permutation(absent)[:k - 1]])
             for _ in range(t)]),
        "every choice held": np.tile(
            np.arange(lo, lo + k) if held >= k else [lo, lo + 1], (t, 1)),
    }
    assert bool(dops._plan(t, k, held, experts, 8)[2]) == (k == 4)
    fn = jax.jit(lambda x, weight, idx, w13, w2: dops.moe_experts(
        x, weight, idx, w13, w2, lo, experts, 8))
    grad = jax.jit(jax.grad(
        lambda x, weight, idx, w13, w2: jnp.sum(dops.moe_experts(
            x, weight, idx, w13, w2, lo, experts, 8)[0] ** 2),
        argnums=(0, 1, 3, 4)))
    got_paths = {}
    for name, idx in routings.items():
        idx = jnp.asarray(idx, jnp.int32)
        with jax.default_matmul_precision("highest"):
            out, stats = fn(x, weight, idx, w13, w2)
            want = dense_experts(x, weight, idx, w13, w2, lo)
            got = grad(x, weight, idx, w13, w2)
            ref_grad = jax.grad(
                lambda x, weight, w13, w2: jnp.sum(dense_experts(
                    x, weight, idx, w13, w2, lo) ** 2),
                argnums=(0, 1, 2, 3))(x, weight, w13, w2)
        np.testing.assert_allclose(out, want, atol=1e-5, err_msg=name)
        for a, b in zip(got, ref_grad):     # 128 rows on one expert: ~2e2
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=2e-6,
                                       err_msg=name)
        held_pairs = int(np.sum((np.asarray(idx) >= lo)
                                & (np.asarray(idx) < lo + held)))
        assert int(stats[0]) == held_pairs and int(stats[2]) == 0, name
        got_paths[name] = (bool(stats[3]), pytest.approx(float(stats[4])))
    assert got_paths == paths
    assert fn._cache_size() == 1 and grad._cache_size() == 1


@pytest.fixture(scope="module", params=[0, 1, 2])
def both_combines(request):
    """The expert layer and its four gradients on one random routing at a
    share of 4 of 32 experts, 4 choices a token, through the combine's
    prefix form (what ``_plan`` chooses there) and through the k-slot form
    (``_plan``'s choice overridden)."""
    t, h, f, k, lo, held, experts, tm = 256, 32, 16, 4, 8, 4, 32, 8
    r = np.random.RandomState(request.param)
    x = jnp.asarray(r.randn(t, h), jnp.float32)
    w13 = jnp.asarray(r.randn(held, h, 2 * f) * 0.2, jnp.float32)
    w2 = jnp.asarray(r.randn(held, f, h) * 0.2, jnp.float32)
    weight = jnp.asarray(r.rand(t, k) + 0.5, jnp.float32)
    # some rows of zeros under negative weights: the terms are -0.0, and
    # the sign of a zero is held to the k-slot form's too
    x = x.at[-64:].set(0.0)
    weight = weight.at[-64:].multiply(-1.0)
    ct = jnp.asarray(r.randn(t, h), jnp.float32)
    idx = jnp.asarray(np.stack([r.permutation(experts)[:k]
                                for _ in range(t)]), jnp.int32)
    plan = dops._plan
    assert plan(t, k, held, experts, tm) == (36, 4, (224, 48, 8, 8))

    def run():                  # jit keys its cache by the function: two
        def layer(x, weight, w13, w2):
            return dops.moe_experts(x, weight, idx, w13, w2, lo, experts, tm)
        (out, stats), vjp = jax.vjp(layer, x, weight, w13, w2)
        return (out, *vjp((ct, jnp.zeros_like(stats)))), stats
    forms = {"prefix": jax.jit(lambda: run())()}
    dops._plan = lambda *a: plan(*a)[:2] + ((),)
    try:                        # the layer's own jits know the shapes only
        jax.clear_caches()
        forms["k-slot"] = jax.jit(lambda: run())()
    finally:
        dops._plan = plan
        jax.clear_caches()
    return forms


@pytest.mark.parametrize("which", range(5),
                         ids=["out", "dx", "dweight", "dw13", "dw2"])
def test_the_prefix_form_gives_the_k_slot_forms_bits(both_combines, which):
    """Skipping an absent slot leaves out a ``+ 0.0`` and the held choices
    keep their order: not close, equal."""
    (got, stats), (want, base) = (both_combines[f] for f in ("prefix",
                                                            "k-slot"))
    assert np.asarray(want[which]).any()
    assert np.asarray(got[which]).tobytes() == \
        np.asarray(want[which]).tobytes()
    assert stats[3] == 1 and base[3] == 1
    assert float(stats[4]) == (224 + 48 + 8 + 8 + 256) / 1024
    assert float(base[4]) == 1.0


def test_the_held_order_is_a_stable_sort_and_its_prefixes_hold_the_ranks():
    """``_held_order`` against numpy: the tokens by their number of held
    choices, most first, ties in token order; ``inv`` undoes ``perm``; the
    compacted rows are each token's held rows in slot order; ``fits`` says
    whether every rank's tokens are within its prefix."""
    t, k = 512, 4
    r = np.random.RandomState(4)
    valid = r.rand(t, k) < 0.3
    pos = r.randint(0, 1000, (t, k)).astype(np.int32)
    held = valid.sum(1)
    more = [int((held > j).sum()) for j in range(k)]
    order = dops._held_order(jnp.asarray(valid), jnp.asarray(pos), more)
    perm = np.asarray(order["perm"])
    np.testing.assert_array_equal(perm, np.argsort(-held, kind="stable"))
    np.testing.assert_array_equal(np.asarray(order["inv"])[perm],
                                  np.arange(t))
    np.testing.assert_array_equal(order["held"], held[perm])
    for j in range(k):          # rank j's prefix: the tokens with a j-th
        assert np.all(held[perm[:more[j]]] > j)
        np.testing.assert_array_equal(
            np.asarray(order["pos"])[:more[j], j],
            [pos[tok][valid[tok]][j] for tok in perm[:more[j]]])
    assert bool(order["fits"])
    for j in range(k):
        short = list(more)
        short[j] -= 1
        assert not bool(dops._held_order(jnp.asarray(valid),
                                         jnp.asarray(pos), short)["fits"])


def _row_gathers(jaxpr, shape):
    """Gathers of ``shape`` anywhere in a jaxpr, and its branch points
    (the kernels' own ``pl.when`` apart)."""
    gathers, branches = 0, []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "gather" \
                and eqn.outvars[0].aval.shape == shape:
            gathers += 1
        if eqn.primitive.name == "cond":
            branches.append(len(eqn.params["branches"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            g, b = _row_gathers(sub, shape)
            gathers, branches = gathers + g, branches + b
    return gathers, branches


@pytest.mark.parametrize("form", ["whole", "decode", "share"])
def test_where_every_expert_is_held_the_combine_is_todays_k_gathers(form):
    """Mellum's prompt pass and the whole Laguna model hold every expert,
    and so does a decode step: ``_plan`` keeps the k-slot form (k gathers of
    ``[t, h]``, no branch point at all). A share small enough gets the one
    switch of three arms: the chunks, the fast buffer with k gathers, the
    fast buffer with a gather a prefix and the un-permute."""
    t, h, f, k, experts, tm = 96, 32, 16, 4, 32, 8
    held = 4 if form == "share" else experts
    x = jnp.zeros((t, h), jnp.float32)
    weight = jnp.zeros((t, k), jnp.float32)
    idx = jnp.zeros((t, k), jnp.int32)
    w13 = jnp.zeros((held, h, 2 * f), jnp.float32)
    w2 = jnp.zeros((held, f, h), jnp.float32)
    if form == "decode":
        jaxpr = jax.make_jaxpr(dops.moe_experts_decode)(x, weight, idx, w13,
                                                        w2)
    else:
        jaxpr = jax.make_jaxpr(lambda *a: dops.moe_experts(
            *a, 0, experts, tm))(x, weight, idx, w13, w2)
    gathers, branches = _row_gathers(jaxpr.jaxpr, (t, h))
    sizes = dops._plan(t, k, held, experts, tm)[2]
    if form == "share":
        assert sizes == (88, 24, 8, 8)
        # two arms with k gathers of [t, h] (the chunks gather [t / 4, h]);
        # the prefix arm's one [t, h] gather is its un-permute
        assert (gathers, branches) == (k + 1, [3])
    else:
        assert sizes == () and (gathers, branches) == (k, [])


@pytest.mark.parametrize("pairs", [1024, 96], ids=["blocked", "plain"])
def test_the_counting_sort_keeps_order_and_finds_every_row(pairs):
    """The route's table (blocks of 256 summed by a triangular product, or
    a plain cumulative sum) and the search down its columns, against numpy:
    rows sorted by expert, the choices' order kept within an expert."""
    groups, k, tm = 5, 4, 8
    r = np.random.RandomState(pairs)
    local = r.randint(0, 8, (pairs // k, k)).astype(np.int32)   # 5..7 absent
    valid = local < groups
    key = np.where(valid, local, groups).reshape(-1)
    running = dops._running_counts(
        jnp.asarray(key[:, None] == np.arange(groups)[None]))
    np.testing.assert_array_equal(
        running, np.cumsum(key[:, None] == np.arange(groups)[None], axis=0))
    tiles = sum(max(1, -(-int(np.sum(key == g)) // tm))
                for g in range(groups)) + 1
    route = dops._route(jnp.asarray(local), jnp.asarray(valid), groups, tm,
                        tiles)
    want = [p for g in range(groups) for p in np.flatnonzero(key == g)]
    live = np.asarray(route["live"])
    np.testing.assert_array_equal(np.asarray(route["pair"])[live], want)
    np.testing.assert_array_equal(np.asarray(route["tok"])[live],
                                  np.asarray(want) // k)
    assert np.all(np.asarray(route["tok"])[~live] == pairs // k)
    rows = np.flatnonzero(live)
    np.testing.assert_array_equal(
        np.asarray(route["pos"]).reshape(-1)[want], rows)
    assert int(route["dropped"]) == 0


@pytest.mark.parametrize("sizes", [(5, 0, 17, 8), (0, 0, 3, 0), (16, 8, 8, 8)],
                         ids=["ragged", "mostly-empty", "full-tiles"])
def test_grouped_products_follow_the_group_sizes(sizes):
    tm, k, n = 8, 16, 24
    groups = len(sizes)
    tiles = sum(max(1, -(-s // tm)) for s in sizes) + 2   # two spare tiles
    tg, na, row_start = gm.tile_layout(jnp.asarray(sizes), tm, tiles)
    assert int(na[0]) == tiles - 2
    r = np.random.RandomState(1)
    lhs = np.zeros((tiles * tm, k), np.float32)
    other = np.zeros((tiles * tm, n), np.float32)
    rhs = r.randn(groups, k, n).astype(np.float32)
    want = np.zeros((tiles * tm, n), np.float32)
    want_t = np.zeros((groups, k, n), np.float32)
    for g, s in enumerate(sizes):
        a = int(row_start[g])
        lhs[a:a + s] = r.randn(s, k)
        other[a:a + s] = r.randn(s, n)
        want[a:a + s] = lhs[a:a + s] @ rhs[g]
        want_t[g] = lhs[a:a + s].T @ other[a:a + s]
    live = int(na[0]) * tm
    with jax.default_matmul_precision("highest"):
        out = gm.gmm(jnp.asarray(lhs), jnp.asarray(rhs), tg, na, name="t",
                     tm=tm, tn=8)
        back = gm.gmm(jnp.asarray(want), jnp.asarray(rhs), tg, na, name="t",
                      tm=tm, tn=8, transpose_rhs=True)
        out_t = gm.tgmm(jnp.asarray(lhs), jnp.asarray(other), tg, na, groups,
                        name="t", tm=tm, tk=8, tn=8)
    np.testing.assert_allclose(out[:live], want[:live], atol=1e-5)
    for g, s in enumerate(sizes):
        a = int(row_start[g])
        np.testing.assert_allclose(back[a:a + s], want[a:a + s] @ rhs[g].T,
                                   atol=1e-4)
    np.testing.assert_allclose(out_t, want_t, atol=1e-5)


# ------------------------------------------------------------ flash kernels

@pytest.mark.parametrize("heads,kv_heads,window", [
    (4, 4, 0), (4, 2, 0), (4, 1, 48), (6, 1, 64), (2, 2, 100)],
    ids=["mha", "gqa2", "gqa4-win48", "gqa6-win64", "mha-win100"])
def test_flash_window_and_grouped_kv_match_composed_attention(
        heads, kv_heads, window):
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, heads, 256, 32), jnp.float32)
    k, v = (jnp.asarray(r.randn(2, kv_heads, 256, 32), jnp.float32)
            for _ in range(2))
    ct = jnp.asarray(r.randn(2, heads, 256, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=32, window=window,
            tag="t"), q, k, v)
        want, vjp_ref = jax.vjp(lambda q, k, v: _composed_attention(
            q, k, v, None, True, 32 ** -0.5, window), q, k, v)
        for a, b in zip((got, *vjp(ct)), (want, *vjp_ref(ct))):
            np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_causal_path_without_window_or_groups_is_bit_identical():
    """One seeded input through the forward and the backward kernels: the
    bytes the kernels gave before they learned the window and the groups
    (sha256 taken on the parent commit, same interpreter)."""
    r = np.random.RandomState(7)
    q, k, v = (jnp.asarray(r.randn(1, 2, 128, 32), jnp.float32)
               for _ in range(3))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64), q, k, v)
    digest = hashlib.sha256()
    for a in (out, *vjp(jnp.asarray(r.randn(*out.shape), jnp.float32))):
        digest.update(np.asarray(a).tobytes())
    assert digest.hexdigest() == ("d07f2bb79c10faa1822d69966ab32d35"
                                  "a69599284acd1d1562fa80200fd187c0")


def test_the_kernels_of_window_and_full_layers_carry_their_own_names(
        flash_from_seq_32):
    cfg, model, _, ids, labels = build(SHARE)
    text = str(jax.make_jaxpr(
        lambda i: model(i, labels=labels).value)(ids))
    for name in ("flash_fwd_win", "flash_fwd_full", "moe_up", "moe_down"):
        assert name in text, name


def _leaf_kind(name):
    for kind in ("router", "experts_gate_up", "experts_down", "shared",
                 "qkv_proj", "g_proj", "o_proj", "norm", "embed", "lm_head",
                 "mlp."):
        if kind in name:
            return kind
    raise AssertionError(name)


@pytest.fixture(scope="module", params=[WHOLE, SHARE],
                ids=["whole", "share"])
def one_adamw_step(request):
    """One step of the family's train job (``jit.to_static`` over the
    model's loss, backward and ``AdamW.step``) in float32, beside the
    update AdamW's formula gives from the REFERENCE's gradients on the same
    weights: every leaf's change, the expert stacks and the router among
    them. The benchmark's own check does not see a wrong update of a leaf
    (PERF.md section 4, Correctness); this does, at toy size."""
    was = flags.get_flag("pallas_min_seq")
    pt.set_flags({"pallas_min_seq": 32})
    try:
        cfg = file_config(request.param)
        lr, decay = 1e-3, 0.01
        cfg["trainer"] = {"amp_level": "O0", "moment_dtype": "float32",
                          "recompute": True, "retain_grads": False,
                          "learning_rate": lr}
        pt.seed(5)
        model, opt, fn, retain = ref.train_job(cfg, {"seq": 32})
        before = {n: np.asarray(p.value)
                  for n, p in model.named_parameters()}
        lo, hi = request.param["vocab_rows"]
        ids = jnp.asarray(np.random.RandomState(5).randint(lo, hi, (2, 32)),
                          jnp.int32)
        labels = jnp.roll(ids, -1, axis=1)
        jit.to_static(fn, layers=[model], optimizers=[opt],
                      retain_grads=retain)(ids, labels)
        after = {n: np.asarray(p.value) for n, p in model.named_parameters()}
        m1 = {n: np.asarray(opt._eager_state[(id(p), "m1")])
              for n, p in model.named_parameters()}
        grads = jax.grad(lambda p: ref.loss(p, ids, labels, cfg))(
            {n: jnp.asarray(v) for n, v in before.items()})
        # AdamW's first step as ops/optimizer_ops.py writes it: the bias
        # corrections go into the rate, so epsilon stands beside
        # sqrt(1 - beta2) |g|
        eps = 1e-8 / math.sqrt(1 - 0.999)
        want = {n: -lr * (np.asarray(g) / (np.abs(np.asarray(g)) + eps)
                          + decay * before[n]) for n, g in grads.items()}
        share = tuple(request.param["experts"]) != (0, 8)
        return dict(got={n: after[n] - before[n] for n in before},
                    want=want, m1=m1, grads=grads, share=share, lr=lr)
    finally:
        pt.set_flags({"pallas_min_seq": was})


@pytest.mark.parametrize("kind", ["experts_gate_up", "experts_down",
                                  "shared", "qkv_proj", "g_proj", "o_proj",
                                  "norm", "embed", "lm_head", "mlp."])
def test_a_step_moves_every_leaf_as_adamw_on_the_reference_gradient(
        one_adamw_step, kind):
    """Every entry of every leaf of the kind within 2% of the rate: a first
    AdamW step is ``lr * g / (|g| + eps)``, which float32 noise in a
    gradient near 0 moves by a fraction of a percent of the rate."""
    r = one_adamw_step
    names = [n for n in r["got"] if _leaf_kind(n) == kind]
    assert names
    for n in names:
        assert np.abs(r["want"][n]).max() > 0.5 * r["lr"], n
        np.testing.assert_allclose(r["got"][n], r["want"][n],
                                   atol=0.02 * r["lr"], rtol=0, err_msg=n)


def test_a_share_computes_its_router_gradient_and_withholds_the_update(
        one_adamw_step):
    """The whole model's router moves like any leaf. A share's router is
    one replica of the group's: its gradient here is one chip's part of the
    group's sum (it reaches the moments, as the reference's partial
    gradient), and the weight waits for the all-reduce: it does not move."""
    r = one_adamw_step
    names = [n for n in r["got"] if _leaf_kind(n) == "router"]
    assert len(names) == 2
    for n in names:
        g = np.asarray(r["grads"][n])
        assert np.abs(g).max() > 0
        np.testing.assert_allclose(r["m1"][n], 0.1 * g, rtol=2e-3,
                                   atol=2e-3 * np.abs(g).max(), err_msg=n)
        if r["share"]:
            assert not r["got"][n].any(), n
        else:
            np.testing.assert_allclose(r["got"][n], r["want"][n],
                                       atol=0.02 * r["lr"], rtol=0,
                                       err_msg=n)
