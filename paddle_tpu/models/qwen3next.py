"""Qwen3-Next-class hybrid decoder LM (Qwen/Qwen3-Next-80B-A3B-Instruct):
three Gated DeltaNet (linear-attention) layers to one gated full-attention
layer, sparse experts with a gated shared expert in every layer, an untied
head. The seventh model behind the serving plane's model seam, and the
first whose recurrent state is a MATRIX a head (2 MB a layer a request).

The published ``config.json`` (``model_type`` ``qwen3_next``): 48 layers,
layer ``l`` full attention iff ``(l + 1) % full_attention_interval == 0``,
hidden 2048; 16 key heads of 128 serving 32 value heads of 128 in a linear
layer (``linear_*``, a depthwise causal convolution of 4 taps); 16 query
heads of 256 over 2 KV heads in a full layer, rotary (theta 1e7) on the
first 64 of the 256; 512 experts of 512 (top 10, softmax over all 512,
renormalised) and a shared expert of 512 behind a sigmoid gate; vocabulary
151936. A layer, on ``T`` rows (``h`` float32; ``norm`` is the zero-centred
RMSNorm ``x / rms(x) * (1 + w)``, ``w`` initialised 0, eps 1e-6; no bias
anywhere)::

    every layer   u = norm(h);  h = h + mixer(u);  u2 = norm(h);  h = h + moe(u2)
    DeltaNet      [q | k | v | z] = u W_qkvz      2048 -> 2 x 2048 + 2 x 4096
                  [b | a] = u W_ba                2048 -> 2 x 32
                  (q | k | v) = silu(conv4(q | k | v))       depthwise, causal
                  q, k L2-normalised over 128, q x 128^-1/2; key head j
                  serves value heads 2j, 2j + 1
                  beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
                  S'_t = exp(g_t) S_{t-1}                  S [128, 128] a head
                  S_t = S'_t + k_t (x) (beta_t (v_t - S'_t^T k_t));  o_t = S_t^T q_t
                  mixer = (RMSNorm_128(o) * w_o * silu(z)) W_out   4096 -> 2048
    attention     [q | gate] a head = u W_q  (16 x 512), k, v = u W_k, u W_v
                  q, k normed a head (zero-centred, over 256), the first 64
                  dimensions rotated (rotate-half), softmax(q k^T / 16,
                  causal) v; mixer = (o * sigmoid(gate)) W_o
    moe           p = softmax(u2 W_r) over 512; the 10 largest, / their sum
                  sum_e p_e SwiGLU_e(u2) + sigmoid(u2 w_s) SwiGLU_shared(u2)

**Shared**, called and not copied: :class:`~paddle_tpu.models.laguna.LagunaMoE`
(the float32 router, the held experts' grouped products in both forms, the
device counters; its shared expert gains the gate here),
``ops.ssm_ops.causal_conv`` / ``conv_tail`` (Jamba's), the flash forward
with grouped KV (``fused_attention_qkv``), ``paged_attention`` and
``block_scatter_write``. **New here**: the delta rule
(``ops/gated_delta_ops.py``, ``ops/pallas/gated_delta.py``), the attention
layer (a gate from the query projection, zero-centred norms, a partial
rotary; Laguna's gate is one value a head from a projection of its own) and
the zero-centred norm.

**One chip's share**: ``held_experts`` of ``num_experts`` (the router
scores all, normalises over the 10 chosen whether held or not; choices on
absent experts add nothing, and nothing stands in for the absent chips:
``models/laguna.py``'s module docstring); the vocabulary a share holds is
simply ``vocab_size`` (rows 0 .. of embedding and head; the traffic draws
its ids from them). The multi-token-prediction module of the published
checkpoint is not built (the config has no key for it; a step yields one
token).

Serving: per request a DeltaNet layer keeps ``S`` ``[32, 128, 128]``
float32 and the last three rows of the convolution's input ``[3, 8192]``
(the in-projection's output, kept exactly), declared as a
:class:`~paddle_tpu.serving.seam.StateKind`; a full layer its K (normed and
rotated) and V rows in the paged pool. A prefill dispatch hands ``S`` and
the tail over **at each prompt's own last token**; the decode step rewrites
every row's ``S`` where it lies (``gdn_decode``: the array aliased in and
out). The family is served, not trained (the chunked rule has no backward
yet: ROADMAP R8).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from ..dygraph.layers import Layer, LayerList
from ..dygraph.tape import run_op
from ..dygraph.tensor import Tensor
from ..initializer import ConstantInitializer, NormalInitializer
from ..nn.layers_common import Embedding
from ..ops import gated_delta_ops, ssm_ops
from ..ops.attention_ops import block_scatter_write
from ..ops.decoder_ops import rotary_inv_freq
from ..param_attr import ParamAttr
from ..profiler import RecordEvent
from .laguna import LagunaConfig, LagunaMoE, _linear, _w, no_counts


@dataclass
class Qwen3NextConfig(LagunaConfig):
    """The sparse-expert decoder's configuration with
    Qwen3-Next-80B-A3B-Instruct's values as defaults, and the two mixers'
    own sizes. ``layer_types`` holds ``"linear_attention"`` beside
    ``"full_attention"`` and follows from ``full_attention_interval``."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    sliding_window: int = 0
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 262144
    attention_gate: bool = False        # the gate here is the layer's own
    router_score: str = "softmax"
    expert_counters: Tuple[str, ...] = ("experts_touched", "expert_pairs")
    dtype: str = "bfloat16"
    moe_chunk_rows: int = 2048
    # the recurrence's leaves as (mean, std) of a normal: A_log (g = -exp(
    # A_log) softplus(a + dt_bias)) and dt_bias, so that a random-weight
    # model's state remembers over hundreds of tokens
    a_log_init: Tuple[float, float] = (-0.5, 1.0)
    dt_bias_init: Tuple[float, float] = (-4.6, 1.3)
    # tokens a prefill dispatch computes (``ServedModel.tokens_a_dispatch``)
    tokens_a_dispatch: int = 4096

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.layer_types:
            self.layer_types = tuple(
                "full_attention" if (i + 1) % self.full_attention_interval
                == 0 else "linear_attention" for i in range(n))
        if not self.num_attention_heads_per_layer:
            self.num_attention_heads_per_layer = \
                (self.num_attention_heads,) * n
        if not self.mlp_layer_types:
            self.mlp_layer_types = ("sparse",) * n
        super().__post_init__()
        unknown = set(self.layer_types) - {"linear_attention",
                                           "full_attention"}
        if unknown:
            raise ValueError(f"a qwen3next layer is linear_attention or "
                             f"full_attention; got {sorted(unknown)}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads over "
                f"{self.linear_num_key_heads} key heads")

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution passes: q, k and v."""
        return 2 * self.key_dim + self.value_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def state_arrays(self):
        """(shape, dtype) of what a request keeps in a DeltaNet layer: the
        convolution's tail and ``S``."""
        return (((self.linear_conv_kernel_dim - 1, self.conv_dim),
                 self.dtype),
                ((self.linear_num_value_heads, self.linear_key_head_dim,
                  self.linear_value_head_dim), "float32"))

    def num_params(self) -> int:
        """Parameters this share holds (all of them for the whole model)."""
        h, d = self.hidden_size, self.head_dim
        hv = self.linear_num_value_heads
        linear = h * (self.conv_dim + self.value_dim) + h * 2 * hv \
            + self.conv_dim * self.linear_conv_kernel_dim + 2 * hv \
            + self.linear_value_head_dim + self.value_dim * h
        q, kv = self.num_attention_heads, self.num_key_value_heads
        full = h * (2 * q + 2 * kv) * d + 2 * d + q * d * h
        e = self.experts[1] - self.experts[0]
        moe = h * self.num_experts + 3 * h * self.moe_intermediate_size * e \
            + 3 * h * self.shared_expert_intermediate_size + h
        n = 2 * self.vocab_size * h + h
        for kind in self.layer_types:
            n += (full if kind == "full_attention" else linear) + moe + 2 * h
        return n


def zero_centred_rms(x, weight, eps: float):
    """``x / rms(x) * (1 + w)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + weight.astype(jnp.float32))


class ZeroCentredRMSNorm(Layer):
    """RMSNorm whose gain is ``1 + w``, ``w`` initialised 0."""

    def __init__(self, size: int, eps: float, dtype="float32"):
        super().__init__(dtype=dtype)
        self.eps = eps
        self.weight = self.create_parameter(
            [size], attr=ParamAttr(initializer=ConstantInitializer(0.0)))

    def forward(self, x):
        x = x.value if isinstance(x, Tensor) else x
        return zero_centred_rms(x, self.weight.value, self.eps)


def _project(layer, x):
    """``x`` through a bias-free ``Linear``: inputs in the parameters'
    dtype, float32 accumulation and result."""
    w = layer.weight.value
    return jnp.einsum("bsh,hn->bsn", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


class GatedDeltaNet(Layer):
    """The linear-attention mixer. ``forward(u)`` runs ``T`` rows from a
    zero state; ``served`` is the engine's call, a prompt or one token."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        h, hv = cfg.hidden_size, cfg.linear_num_value_heads
        k = cfg.linear_conv_kernel_dim
        # columns: q, k (key_dim each), v, z (value_dim each); b then a
        self.in_proj = _linear(h, cfg.conv_dim + cfg.value_dim,
                               cfg.init_std, cfg.dtype)
        self.ba_proj = _linear(h, 2 * hv, cfg.init_std, cfg.dtype)
        self.conv_weight = self.create_parameter(
            [cfg.conv_dim, k], attr=_w(1.0 / math.sqrt(k)), dtype="float32")
        self.A_log = self.create_parameter(
            [hv], attr=ParamAttr(initializer=NormalInitializer(
                *cfg.a_log_init)), dtype="float32")
        self.dt_bias = self.create_parameter(
            [hv], attr=ParamAttr(initializer=NormalInitializer(
                *cfg.dt_bias_init)), dtype="float32")
        # the output norm's gain is plain (initialised 1), one a channel
        self.norm_weight = self.create_parameter(
            [cfg.linear_value_head_dim],
            attr=ParamAttr(initializer=ConstantInitializer(1.0)),
            dtype=cfg.dtype)
        self.out_proj = _linear(
            cfg.value_dim, h,
            cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers), cfg.dtype)

    def _inputs(self, u, tail):
        """``u`` float32 [b, T, h] (the norm's output) and the rows of the
        convolution's input before row 0 -> (that input [b, T, conv_dim]
        in the parameters' dtype: what the tail keeps; q, k [b, T, h_k,
        d_k], v [b, T, h_v, d_v], g, beta [b, T, h_v], z like v), float32
        from the convolution on."""
        cfg = self.cfg
        b, t, _ = u.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        dt = self.in_proj.weight.value.dtype
        qkvz = _project(self.in_proj, u)
        xp = qkvz[..., :cfg.conv_dim].astype(dt)
        z = qkvz[..., cfg.conv_dim:].reshape(b, t, hv, dv)
        x = jax.nn.silu(ssm_ops.causal_conv(xp, self.conv_weight.value,
                                            None, tail))
        q = x[..., :cfg.key_dim].reshape(b, t, hk, dk)
        k = x[..., cfg.key_dim:2 * cfg.key_dim].reshape(b, t, hk, dk)
        v = x[..., 2 * cfg.key_dim:].reshape(b, t, hv, dv)
        ba = _project(self.ba_proj, u)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(self.A_log.value) * jax.nn.softplus(
            ba[..., hv:] + self.dt_bias.value)
        q = gated_delta_ops.l2norm(q) * dk ** -0.5
        return xp, q, gated_delta_ops.l2norm(k), v, g, beta, z

    def _out(self, o, z):
        """The heads' output [b, T, h_v, d_v] normed a head, gated by
        ``silu(z)`` and projected."""
        cfg = self.cfg
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps) \
            * self.norm_weight.value.astype(jnp.float32) * jax.nn.silu(z)
        return _project(self.out_proj,
                        y.reshape(y.shape[:2] + (cfg.value_dim,)))

    def forward(self, u):
        """``u`` float32 [b, T, h] -> float32 [b, T, h]."""
        b, t, _ = u.shape
        _, q, k, v, g, beta, z = self._inputs(u, None)
        o, _ = gated_delta_ops.gated_delta_rule(
            q, k, v, g, beta, jnp.full((b,), t - 1, jnp.int32))
        return self._out(o, z)

    def served(self, u, state, rows, last):
        """The serving engine's call. ``state`` = (tail [slots, K - 1,
        conv_dim], S [slots, h_v, d_k, d_v]) of this layer. A prompt (``T``
        > 1): ``rows`` [b] the cache row of each row of the dispatch (out
        of range: none), ``last`` [b] its last token's row; the rule starts
        from zero and ``S`` and the tail after ``last`` are written to the
        row, whole. One token (``T`` = 1, ``b`` = slots): every row's
        state is read and rewritten where it lies. -> (float32 [b, T, h],
        the state)."""
        tail, s = state[0].value, state[1].value
        if u.shape[1] > 1:
            xp, q, k, v, g, beta, z = self._inputs(u, None)
            o, s_last = gated_delta_ops.gated_delta_rule(q, k, v, g, beta,
                                                         last)
            new_tail = ssm_ops.conv_tail(xp, last,
                                         self.cfg.linear_conv_kernel_dim)
            tail = tail.at[rows].set(new_tail.astype(tail.dtype),
                                     mode="drop")
            s = s.at[rows].set(s_last, mode="drop")
        else:
            xp, q, k, v, g, beta, z = self._inputs(u, tail)
            o, s = gated_delta_ops.gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s, rows)
            o = o[:, None]
            tail = jnp.concatenate([tail[:, 1:], xp.astype(tail.dtype)],
                                   axis=1)
        return self._out(o, z), (Tensor(tail, stop_gradient=True),
                                 Tensor(s, stop_gradient=True))


class GatedAttention(Layer):
    """Causal grouped-query attention whose query projection also yields
    an output gate; q and k normed a head, the first ``rotary_dim``
    dimensions rotated."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q, self.kv = cfg.num_attention_heads, cfg.num_key_value_heads
        # columns: a query head's [q | gate], head after head; then the K
        # heads; then V
        self.qkv_proj = _linear(h, (2 * self.q + 2 * self.kv) * d,
                                cfg.init_std, cfg.dtype)
        self.q_norm = ZeroCentredRMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
        self.k_norm = ZeroCentredRMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
        self.o_proj = _linear(
            self.q * d, h,
            cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers), cfg.dtype)
        self.inv_freq, _ = rotary_inv_freq(cfg.rotary_dim, cfg.rope_theta)

    def _rotate(self, x, rows):
        """``x`` float32 [b, s, heads, d]: its first ``rotary_dim``
        dimensions rotated (rotate-half) at the positions ``rows`` [b, s],
        the others as they are."""
        r = self.cfg.rotary_dim
        ang = rows.astype(jnp.float32)[..., None, None] \
            * jnp.asarray(self.inv_freq, jnp.float32)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :r // 2], x[..., r // 2:r]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                x[..., r:]], axis=-1)

    def _heads(self, u, rows):
        """-> (q [b, heads, s, d], k, v [b, kv, s, d] in the parameters'
        dtype, the gate float32 [b, s, heads, d])."""
        cfg, d = self.cfg, self.cfg.head_dim
        b, s, _ = u.shape
        dt = self.qkv_proj.weight.value.dtype
        qkv = _project(self.qkv_proj, u)
        qg = qkv[..., :2 * self.q * d].reshape(b, s, self.q, 2 * d)
        kv = qkv[..., 2 * self.q * d:].reshape(b, s, 2, self.kv, d)
        q = self._rotate(self.q_norm(qg[..., :d]), rows)
        k = self._rotate(self.k_norm(kv[:, :, 0]), rows)

        def heads(x):
            return x.astype(dt).transpose(0, 2, 1, 3)
        return heads(q), heads(k), heads(kv[:, :, 1]), qg[..., d:]

    def _out(self, o, gate):
        """The read's output [b, heads, s, d] gated and projected."""
        b, _, s, d = o.shape
        y = o.astype(jnp.float32).transpose(0, 2, 1, 3) \
            * jax.nn.sigmoid(gate)
        return _project(self.o_proj, y.reshape(b, s, self.q * d))

    def _attend(self, q, k, v):
        return run_op(
            "fused_attention_qkv",
            {"Q": [Tensor(q, stop_gradient=True)],
             "K": [Tensor(k, stop_gradient=True)],
             "V": [Tensor(v, stop_gradient=True)]},
            {"causal": True, "kernel_tag": "full"})["Out"][0].value

    def forward(self, u):
        b, s, _ = u.shape
        rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        q, k, v, gate = self._heads(u, rows)
        return self._out(self._attend(q, k, v), gate)

    def served(self, u, cache, pos, tables):
        """``cache`` this layer's (k, v) pool pair, ``tables`` [b, T] the
        block tables, ``pos`` [b] each request's first row of this call ->
        (float32 output, the pools with the call's K and V written). A
        prompt attends over its own rows (no prefix is ever shared), one
        token over its paged rows."""
        from ..ops.pallas.paged_attention import paged_attention
        cfg = self.cfg
        b, s, _ = u.shape
        rows = jnp.clip(pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None],
                        0, cfg.max_position_embeddings - 1)
        q, k, v, gate = self._heads(u, rows)
        kp = block_scatter_write(cache[0].value, k, pos, tables)
        vp = block_scatter_write(cache[1].value, v, pos, tables)
        if s > 1:
            o = self._attend(q, k, v)
        else:
            o = paged_attention(q, kp, vp, tables, pos,
                                scale=1.0 / math.sqrt(cfg.head_dim))
        return self._out(o, gate), (Tensor(kp, stop_gradient=True),
                                    Tensor(vp, stop_gradient=True))


class Qwen3NextMoE(LagunaMoE):
    """The decoder's expert layer whose shared expert sits behind a
    sigmoid gate of one value a token (``w_s`` 2048 -> 1)."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(cfg)
        self.shared_gate = _linear(cfg.hidden_size, 1, cfg.init_std,
                                   cfg.dtype)

    def _shared(self, u):
        gate = jax.nn.sigmoid(_project(self.shared_gate, u.value))
        return super()._shared(u) * Tensor(
            gate.astype(u.value.dtype), stop_gradient=True)


class Qwen3NextBlock(Layer):
    """Pre-norm block: a DeltaNet or an attention mixer, then the experts.
    The residual stream is float32."""

    def __init__(self, cfg: Qwen3NextConfig, layer: int):
        super().__init__()
        self.cfg = cfg
        self.kind = cfg.layer_types[layer]
        self.input_norm = ZeroCentredRMSNorm(cfg.hidden_size,
                                             cfg.rms_norm_eps, cfg.dtype)
        if self.kind == "linear_attention":
            self.gdn = GatedDeltaNet(cfg)
        else:
            self.attn = GatedAttention(cfg)
        self.post_norm = ZeroCentredRMSNorm(cfg.hidden_size,
                                            cfg.rms_norm_eps, cfg.dtype)
        self.moe = Qwen3NextMoE(cfg)

    def _moe(self, x, live=None):
        """-> (x + moe(norm(x)), the expert layer's counts)."""
        y, counted = self.moe.served(self.post_norm(x), live)
        return x + y, counted

    def forward(self, x):
        mixer = self.gdn if self.kind == "linear_attention" else self.attn
        return self._moe(x + mixer(self.input_norm(x)))[0]

    def served(self, x, cache, pos, tables, last, live):
        """The serving engine's call, on arrays -> (x, this layer's cache,
        the expert layer's counts). ``tables``: the block tables of an
        attention layer, the cache rows of a DeltaNet layer."""
        u = self.input_norm(x)
        if self.kind == "linear_attention":
            y, cache = self.gdn.served(u, cache, tables, last)
        else:
            y, cache = self.attn.served(u, cache, pos, tables)
        x, counted = self._moe(x + y, live)
        return x, cache, counted


class Qwen3NextModel(Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_w(cfg.init_std if cfg.embed_init_std is None
                           else cfg.embed_init_std))
        self.layers = LayerList([Qwen3NextBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = ZeroCentredRMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                       cfg.dtype)

    def _embed(self, input_ids):
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return self.embed(Tensor(ids, stop_gradient=True)) \
            .value.astype(jnp.float32)

    def forward(self, input_ids, collect=None):
        x = self._embed(input_ids)
        for blk in self.layers:
            x = blk(x)
            if collect is not None:
                collect.append(x)
        return self.norm(x)

    def served(self, input_ids, cache, cache_pos, block_tables, last=None,
               collect=None):
        """The serving engine's call -> (the final norm's output float32
        [b, s, h], the caches, the expert layers' counts summed over the
        layers). ``cache``: one tuple a layer, (k, v) pools of an attention
        layer, (tail, S) of a DeltaNet layer. ``block_tables``: (the
        attention layers' block tables [b, T], the cache row of each row
        [b]). ``last`` [b]: each prompt's last row in this call (None:
        one token a row)."""
        cfg = self.cfg
        tables, rows = (jnp.asarray(t, jnp.int32) for t in block_tables)
        x = self._embed(input_ids)
        b, s = x.shape[0], x.shape[1]
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        if s > 1 and last is None:
            last = jnp.full((b,), s - 1, jnp.int32)
        # a slot with no request has no row yet: it routes nowhere
        live = pos > 0 if s == 1 else None
        caches, counted = [], no_counts(cfg.expert_counters)
        for i, blk in enumerate(self.layers):
            x, c, n = blk.served(
                x, cache[i], pos,
                rows if blk.kind == "linear_attention" else tables, last,
                live)
            caches.append(c)
            counted = counted + n
            if collect is not None:
                collect.append(x)
        return self.norm(x), caches, counted


class Qwen3NextForCausalLM(Layer):
    """The model with its untied head. ``forward(ids)`` -> float32 logits
    [b, s, vocab]; with ``cache`` the serving engine's call -> (float32
    logits, caches[, the device counters])."""

    span_prefix = "qwen3next"

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        with RecordEvent(f"{self.span_prefix}.build",
                         {"layers": cfg.num_hidden_layers,
                          "params": cfg.num_params()}):
            self.cfg = cfg
            self.model = Qwen3NextModel(cfg)
            self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size,
                                   cfg.init_std, cfg.dtype)

    def _head(self, h):
        return Tensor(_project(self.lm_head, h), stop_gradient=True)

    def forward(self, input_ids, collect=None, cache=None, cache_pos=None,
                block_tables=None, lora=None, last=None, counters=None):
        if cache is None:
            return self._head(self.model(input_ids, collect))
        if lora is not None:
            raise ValueError(f"{type(self).__name__} has no LoRA path")
        h, caches, counted = self.model.served(
            input_ids, cache, cache_pos, block_tables, last, collect)
        if last is not None:
            # the head never multiplies a bucket's padding
            h = jnp.take_along_axis(
                h, jnp.asarray(last, jnp.int32)[:, None, None], axis=1)
        if counters is None:
            return self._head(h), caches
        return self._head(h), caches, \
            counters + counted.astype(counters.dtype)

    def serving_spec(self):
        """One kind of blocks (the full-attention layers keep every row),
        one kind of recurrent state (a DeltaNet layer's convolution tail
        and its matrix state), pools in the parameters' dtype, the expert
        layers' device counters, none of the engine's optional features:
        prefix reuse needs a snapshot of ``S`` at a block's edge (2 MB a
        layer: ROADMAP R4), speculation a way to roll it back."""
        from ..serving.seam import CacheKind, ServedModel, StateKind
        cfg = self.cfg
        return ServedModel(
            model=self, family="qwen3next",
            max_positions=cfg.max_position_embeddings,
            vocab=cfg.vocab_size,
            cache_kinds=(CacheKind("full", cfg.layers_of("full_attention"),
                                   cfg.num_key_value_heads, cfg.head_dim),),
            state_kinds=(StateKind("gdn", cfg.layers_of("linear_attention"),
                                   cfg.state_arrays()),),
            kv_dtype={"bfloat16": "bf16", "float32": "f32"}[cfg.dtype],
            features=frozenset(), counters=cfg.expert_counters,
            tokens_a_dispatch=cfg.tokens_a_dispatch, head_on_last_row=True)


QWEN3NEXT_CONFIGS = {
    "qwen3-next-80b-a3b": Qwen3NextConfig(),
    # a toy of every mechanism for tests and CPU rehearsals: two periods
    # of three DeltaNet layers to one attention layer, key heads that
    # serve two value heads each, a rotary on 8 of 32, 16 experts (top 4)
    # behind a gated shared expert
    "qwen3next-tiny": Qwen3NextConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, max_position_embeddings=256,
        moe_tile_m=8, moe_chunk_rows=0, dtype="float32",
        tokens_a_dispatch=64),
}
