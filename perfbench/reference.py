"""The plain reference of the GPT-2 architecture both configurations run:
forward pass and next-token loss in straightforward ``jax.numpy`` float32,
no kernels, no cache, no batching tricks, matmuls at ``highest`` precision.

It follows the GPT-2 description (learned positions, pre-LayerNorm blocks,
full multi-head causal attention, tied output head) with one stated
departure, which it shares with the program: GELU in its tanh form
(GPT-2's ``gelu_new``), where Cerebras-GPT's config says ``gelu``.

Weights come in as a dict keyed by the program's parameter names
(``gpt.blocks.<i>.attn.qkv_proj.weight`` ...); linear weights are
``[in, out]``; ``qkv_proj`` packs q, k, v as ``[3, heads, head_dim]`` along
its output axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(params: dict, ids, *, num_layers: int, num_heads: int):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], which :func:`head` turns into logits."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        b, s = ids.shape
        x = p["gpt.wte.weight"][ids] + p["gpt.wpe.weight"][jnp.arange(s)]
        h = x.shape[-1]
        d = h // num_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(num_layers):
            pre = f"gpt.blocks.{i}."
            y = _ln(x, p[pre + "ln1.weight"], p[pre + "ln1.bias"])
            qkv = y @ p[pre + "attn.qkv_proj.weight"] \
                + p[pre + "attn.qkv_proj.bias"]
            qkv = qkv.reshape(b, s, 3, num_heads, d)
            q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
            att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
            att = jnp.where(causal, att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", att, v)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, h)
            x = x + o @ p[pre + "attn.out_proj.weight"] \
                + p[pre + "attn.out_proj.bias"]
            y = _ln(x, p[pre + "ln2.weight"], p[pre + "ln2.bias"])
            y = _gelu_tanh(y @ p[pre + "fc1.weight"] + p[pre + "fc1.bias"])
            x = x + y @ p[pre + "fc2.weight"] + p[pre + "fc2.bias"]
        return _ln(x, p["gpt.ln_f.weight"], p["gpt.ln_f.bias"])


def head(params: dict, *, vocab_size: int):
    """The output matrix float32 [h, vocab_size]: the embedding, tied,
    transposed and cut to the vocabulary's rows."""
    wte = jnp.asarray(params["gpt.wte.weight"], jnp.float32)
    return wte.T[:, :vocab_size]


def forward(params: dict, ids, *, num_layers: int, num_heads: int,
            vocab_size: int):
    """``ids`` int [b, s] -> logits float32 [b, s, vocab_size]."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, num_layers=num_layers, num_heads=num_heads)
        return x @ head(params, vocab_size=vocab_size)


def loss(params: dict, ids, labels, **cfg):
    """Mean next-token cross-entropy of ``labels`` [b, s] under the
    reference's logits, as the program's training loss defines it."""
    logp = jax.nn.log_softmax(forward(params, ids, **cfg), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)
