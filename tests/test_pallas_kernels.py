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

from paddle_tpu.ops.pallas.flash_attention import flash_attention
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
