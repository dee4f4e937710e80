"""Numerical validation of the Pallas kernels against XLA-composed
references (run in Pallas interpreter mode on CPU; the same kernel code
compiles via Mosaic on the real chip).

Mirrors the reference's OpTest discipline (tests/unittests/op_test.py):
forward outputs and every input gradient are checked against an
independent implementation at fp32 tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention_ops import _composed_attention
from paddle_tpu.ops.pallas.flash_attention import (DKV, DQ, FWD, BlockPlan,
                                                   block_plan,
                                                   flash_attention)
from paddle_tpu.ops.pallas.layer_norm import fused_layer_norm


def composed_attention(q, k, v, causal, scale):
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), s_k - s_q)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    rng = np.random.RandomState(0)
    b, h, s, d = 2, 3, 256, 64
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    out = flash_attention(q, k, v, causal=causal, scale=scale,
                          block_q=64, block_k=64)
    ref = composed_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward(causal):
    rng = np.random.RandomState(1)
    b, h, s, d = 1, 2, 128, 32
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    w = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=32, block_k=32)
        return jnp.sum(o * w)

    def loss_ref(q, k, v):
        return jnp.sum(composed_attention(q, k, v, causal, scale) * w)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_uneven_seq_raises():
    q = jnp.zeros((1, 1, 100, 32), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=64, block_k=64)


def test_flash_attention_bf16():
    rng = np.random.RandomState(2)
    b, h, s, d = 1, 2, 128, 64
    q32 = rng.randn(b, h, s, d).astype(np.float32)
    k32 = rng.randn(b, h, s, d).astype(np.float32)
    v32 = rng.randn(b, h, s, d).astype(np.float32)
    q = jnp.asarray(q32, jnp.bfloat16)
    out = flash_attention(q, jnp.asarray(k32, jnp.bfloat16),
                          jnp.asarray(v32, jnp.bfloat16),
                          causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = composed_attention(jnp.asarray(q32), jnp.asarray(k32),
                             jnp.asarray(v32), True, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)


# ------------------------------------------------------------ block plan
#
# One head's logits are cut into grid steps and tiles by a BlockPlan
# (ops/pallas/flash_attention.py). Every form of it gives the values of
# the composed form: the dq kernel's edge tiles walked in sub-tiles (tile
# 32, sub 8 has the quadtree of the chip's 512 / 128), a grid step that
# holds the whole sequence, a dq step of two tiles beside one-tile steps
# of the other two kernels; under windows on every side of a sub-tile's
# edge. All cases share one set of inputs.

PLAN_S, PLAN_D = 64, 8
SUB = BlockPlan(32, 32, 8)                      # sub-tiled edges
PLAN_CASES = {
    # id: (window, query heads a KV head, plan, window of the reference)
    "causal-auto": (0, 1, None, 0),
    "causal-gqa4-sub": (0, 4, SUB, 0),
    "win16-multiple-of-sub": (16, 1, SUB, 16),
    "win15-one-less-gqa4": (15, 4, SUB, 15),
    "win17-one-more-whole-seq-step": (17, 1, BlockPlan(32, 32, 16, 2, 2),
                                      17),
    "win100-past-the-seq-gqa4-dq-2-tiles": (
        100, 4, BlockPlan(16, 16, 8, 2), 100),
    "win5-under-a-sub-tile-dq-2-tiles": (5, 1, BlockPlan(32, 32, 16, 2), 5),
    "win40-interior-run": (40, 1, BlockPlan(16, 16, 8), 40),
    # teeth: the same comparison fails with the window off by one key
    "win16-against-17-fails": (16, 1, SUB, 17),
}


@pytest.fixture(scope="module")
def plan_inputs():
    r = np.random.RandomState(59)
    q, ct = (jnp.asarray(r.randn(1, 4, PLAN_S, PLAN_D), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(r.randn(1, 4, PLAN_S, PLAN_D), jnp.float32)
            for _ in range(2))
    return q, k, v, ct


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_flash_attention_under_a_block_plan_matches_composed_attention(
        plan_inputs, case):
    window, group, plan, ref_window = PLAN_CASES[case]
    q, k, v, ct = plan_inputs
    k, v = k[:, :4 // group], v[:, :4 // group]
    scale = PLAN_D ** -0.5

    def compare():
        got, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, scale=scale, block_q=32, block_k=32,
            window=window, plan=plan), q, k, v)
        want, vjp_ref = jax.vjp(lambda q, k, v: _composed_attention(
            q, k, v, None, True, scale, ref_window), q, k, v)
        for a, b, name in zip((got, *vjp(ct)), (want, *vjp_ref(ct)),
                              ("out", "dq", "dk", "dv")):
            np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)

    if window == ref_window:
        compare()
    else:
        with pytest.raises(AssertionError, match="out"):
            compare()


# [query heads, KV heads, seq, d, window] of every flash call
# tests/test_chip_compile.py compiles, with the cells they are from
PLAN_SHAPES = {
    "gpt_d128_s1024": (128, 128, 1024, 128, 0),
    "gpt_d64_s1024": (128, 128, 1024, 64, 0),
    "d128_s8192": (16, 16, 8192, 128, 0),
    "laguna_win": (16, 2, 8192, 128, 512),
    "laguna_full": (12, 2, 8192, 128, 0),
    "mellum_win": (32, 4, 12288, 128, 1024),
    "mellum_full": (32, 4, 12288, 128, 0),
    "qwen3next_3072": (16, 2, 3072, 256, 0),
    "qwen3next_8192": (16, 2, 8192, 256, 0),
}


DQ_TILES = {"gpt_d128_s1024": (2, 2), "gpt_d64_s1024": (2, 2),
            "d128_s8192": (1, 2), "laguna_win": (1, 2),
            "laguna_full": (1, 2), "qwen3next_3072": (1, 2)}


@pytest.mark.parametrize("shape", sorted(PLAN_SHAPES))
def test_the_block_plan_never_visits_more_than_whole_masked_tiles_did(shape):
    hq, hkv, s, d, window = PLAN_SHAPES[shape]
    plan = block_plan(s, s, d, True, window)
    tile = plan.block_q
    before = BlockPlan(tile, tile, tile).visited_share(s, s, True, window)
    shares = [plan.visited_share(s, s, True, window, kernel)
              for kernel in (FWD, DQ, DKV)]
    assert all(before <= share <= 1.0 for share in shares), shares
    # two tiles are one grid step in all three kernels; of more, the
    # forward and the dk/dv kernel take one a step and the dq kernel as
    # many, up to four, as leave its step a MiB under the default VMEM
    # limit (a head's K and V at s 8192, d 128 are 8 MiB of it)
    assert (plan.tiles, plan.dq_tiles) == DQ_TILES.get(shape, (1, 1))
    assert (s // tile) % plan.dq_tiles == 0
    # where the plan walks an edge tile in sub-tiles (the dq kernel: the
    # other two were timed and mask it whole), what the issue asked of it
    floor = {"gpt_d128_s1024": 0.78, "gpt_d64_s1024": 0.78,
             "laguna_win": 0.64}.get(shape)
    if floor:
        assert shares[DQ] >= floor > before


def test_visited_share_counts_the_pieces_of_the_walk():
    """By hand: causal s 1024 in tiles of 512 is 3 tiles for the 524,800
    live logits; in sub-tiles of 128 each diagonal tile is a 256, two 128s
    and four masked 128s: 10 of its 16."""
    assert BlockPlan(512, 512, 512).visited_products(1024, 1024, True) \
        == 3 * 512 * 512
    assert BlockPlan(512, 512, 128).visited_products(1024, 1024, True) \
        == 512 * 512 + 2 * 10 * 128 * 128
    assert BlockPlan(512, 512, 128).visited_share(1024, 1024, True) \
        == 524800 / 589824
    # a window of 512 at s 8192: each block of 512 rows but the first meets
    # two edge tiles of 10 sub-tiles each; 496 keys a query are live
    plan = BlockPlan(512, 512, 128)
    assert plan.visited_products(8192, 8192, True, 512) \
        == (2 * 16 - 1) * 10 * 128 * 128
    assert round(plan.visited_share(8192, 8192, True, 512), 3) == 0.8
    assert BlockPlan(512, 512, 512).visited_share(8192, 8192, True, 512) \
        == 4063488 / ((2 * 16 - 1) * 512 * 512)
    # no edge, no dead logit; a window past the sequence is no window
    assert BlockPlan(512, 256, 512).visited_share(1024, 2048, False) == 1.0
    assert plan.visited_share(1024, 1024, True, 4096) \
        == plan.visited_share(1024, 1024, True)


def test_fused_layer_norm_forward_backward():
    rng = np.random.RandomState(3)
    n, h = 48, 256
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    g = jnp.asarray(rng.rand(h) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(h), jnp.float32)
    w = jnp.asarray(rng.randn(n, h), jnp.float32)

    def ref(x, g, b):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.var(x, axis=-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b

    y = fused_layer_norm(x, g, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, g, b)),
                               rtol=1e-5, atol=1e-5)

    gf = jax.grad(lambda *a: jnp.sum(fused_layer_norm(*a) * w),
                  argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(x, g, b)
    for a, r, name in zip(gf, gr, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_attention_op_uses_flash_when_enabled():
    """The registered op must route long sequences through the kernel."""
    from paddle_tpu import flags
    from paddle_tpu.dygraph.tape import run_op
    from paddle_tpu.dygraph.tensor import Tensor

    rng = np.random.RandomState(4)
    q = Tensor(jnp.asarray(rng.randn(1, 2, 1024, 64), jnp.float32))
    old = flags.get_flag("pallas_min_seq")
    try:
        flags.set_flags({"pallas_min_seq": 1024})
        out = run_op("fused_attention_qkv",
                     {"Q": [q], "K": [q], "V": [q]},
                     {"causal": True})["Out"][0]
    finally:
        flags.set_flags({"pallas_min_seq": old})
    ref = composed_attention(q.value, q.value, q.value, True,
                             1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_attention_op_fallback_is_visible_per_shape(caplog):
    """A shape the kernel cannot tile still takes the XLA-composed form,
    but never in silence: it is recorded (chip_smoke.py fails on any)
    and logged once for each distinct shape."""
    import logging

    from paddle_tpu import flags
    from paddle_tpu.dygraph.tape import run_op
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.ops import attention_ops

    rng = np.random.RandomState(5)
    # 40 = 8 * 5: no power-of-two block >= 16 divides it
    q = Tensor(jnp.asarray(rng.randn(1, 2, 40, 8), jnp.float32))
    old = flags.get_flag("pallas_min_seq")
    attention_ops.flash_fallback_shapes.clear()
    try:
        flags.set_flags({"pallas_min_seq": 32})
        with caplog.at_level(logging.WARNING,
                             logger=attention_ops.__name__):
            for _ in range(2):
                out = run_op("fused_attention_qkv",
                             {"Q": [q], "K": [q], "V": [q]},
                             {"causal": True})["Out"][0]
    finally:
        flags.set_flags({"pallas_min_seq": old})
    shape = (1, 2, 40, 8)
    assert attention_ops.flash_fallback_shapes == {(shape, shape)}
    assert len([r for r in caplog.records
                if "cannot tile" in r.getMessage()]) == 1
    attention_ops.flash_fallback_shapes.clear()
    ref = composed_attention(q.value, q.value, q.value, True,
                             1.0 / np.sqrt(8))
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
