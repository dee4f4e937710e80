"""LFM2-class hybrid decoder LM (LiquidAI/LFM2-24B-A2B): most mixers a gated
short convolution that carries its last rows from token to token, the rest
grouped-query attention with a per-head norm on q and k, a dense SwiGLU in
the leading layers and sparse experts behind a bias-selected router in the
others, a tied head. The fourth model behind the serving plane's model
seam, and the first to have a recurrent kind of layer, experts and device
counters at once.

The published ``config.json``: 40 layers, ``layer_types`` puts
``full_attention`` at ``l % 4 == 2`` (32 query / 8 KV heads of 64, rotary
theta 1e6 over the whole 64) and ``conv`` everywhere else (``conv_L_cache``
3, no bias), hidden 2048, ``num_dense_layers`` 2 (SwiGLU of 11776), the
others 64 experts of 1536 (4 a token, ``use_expert_bias``,
``norm_topk_prob``, ``routed_scaling_factor`` 1), vocabulary 65536. A
layer, on ``T`` rows (``h`` float32, RMSNorm eps 1e-5, no bias anywhere)::

    every layer   u = RMSNorm_op(h);  h = h + mixer(u)
                  u2 = RMSNorm_ffn(h);  h = h + ffn(u2)
    conv mixer    [B, C, x] = u W_in           thirds in this order
                  v = B * x
                  c_t = sum_{j=0..2} w[:, j] * v_{t-2+j}    depthwise, causal
                  mixer = (C * c) W_out
    attn mixer    q, k normed a head (RMSNorm over 64), then rotated;
                  softmax(q k^T / 8, causal) v Wo, query head j on KV head
                  j // 4
    sparse ffn    s = sigmoid(u2 Wr);  E = top_4(s + b)
                  g_e = s_e / (sum_{e in E} s_e + 1e-6)
                  ffn = sum_{e in E} g_e SwiGLU_e(u2)

**Shared**, called and not copied: ``RMSNorm``, ``SwiGLU``,
:class:`~paddle_tpu.models.laguna.LagunaAttention` (its ``qk_norm`` on, no
window, no gate, one head count; heads of 64 pack two a pool row, whose
served decode read is ``paged_decode_attn``'s) and
:class:`~paddle_tpu.models.laguna.LagunaMoE` (the router op with its
``Bias`` input, every expert held, no shared expert) of
``models/laguna.py``; ``ops.ssm_ops.causal_conv`` / ``conv_tail``, which
are Jamba's. **New here**: the gates around the convolution and the layer
list by ``layer_types`` and ``num_dense_layers``.

Serving: per request a convolution layer keeps the last two rows of ``v``
(``[2, 2048]`` in the parameters' dtype, exactly as computed), declared as a
:class:`~paddle_tpu.serving.seam.StateKind` of one array; an attention
layer its K (after norm and rotary) and V rows in the paged pool, two heads
of 64 side by side in a row of 128 (``LagunaConfig.kv_pack``: the chip lays
an array's last axis out in whole tiles of 128 lanes). A prefill
dispatch hands the tail over **at each prompt's own last token**, the
decode step rewrites every row's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

from ..dygraph.layers import Layer, LayerList
from ..dygraph.tensor import Tensor
from ..initializer import NormalInitializer
from ..nn.layers_common import Embedding
from ..ops import ssm_ops
from ..param_attr import ParamAttr
from ..profiler import RecordEvent
from .laguna import (DECODE_COUNTERS, LagunaAttention, LagunaConfig,
                     LagunaMoE, RMSNorm, SwiGLU, _linear, _matmul_in, _w,
                     no_counts)

_PERIOD = ("conv", "conv", "full_attention", "conv")


@dataclass
class Lfm2Config(LagunaConfig):
    """The sparse-expert decoder's configuration with LFM2-24B-A2B's
    values as defaults. ``layer_types`` holds ``"conv"`` beside
    ``"full_attention"``; ``mlp_layer_types`` and the per-layer head
    counts follow from ``num_dense_layers`` and ``num_attention_heads``."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    layer_types: Tuple[str, ...] = _PERIOD * 10
    num_dense_layers: int = 2
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    conv_L_cache: int = 3
    sliding_window: int = 0
    rope_parameters: dict = field(default_factory=lambda: {
        "full_attention": {"rope_type": "default", "rope_theta": 1e6}})
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    shared_expert_intermediate_size: int = 0
    moe_routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    attention_gate: bool = False
    router_score: str = "sigmoid"
    qk_norm: bool = True
    router_bias: bool = True
    router_renorm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # the final norm's gain as (mean, std) of a normal (None: the constant
    # 1 of a fresh model): the head is tied, see ``models/jamba.py``
    final_norm_init: Optional[Tuple[float, float]] = None
    # tokens a prefill dispatch computes (``ServedModel.tokens_a_dispatch``)
    tokens_a_dispatch: int = 512

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.num_attention_heads_per_layer:
            self.num_attention_heads_per_layer = \
                (self.num_attention_heads,) * n
        if not self.mlp_layer_types:
            self.mlp_layer_types = tuple(
                "dense" if i < self.num_dense_layers else "sparse"
                for i in range(n))
        super().__post_init__()
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"an lfm2 layer is conv or full_attention; "
                             f"got {sorted(unknown)}")

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def num_params(self) -> int:
        h, d = self.hidden_size, self.head_dim
        q, kv = self.num_attention_heads, self.num_key_value_heads
        conv = 3 * h * h + h * self.conv_L_cache + h * h
        attn = h * (q + 2 * kv) * d + q * d * h + 2 * d
        sparse = h * self.num_experts + self.num_experts \
            + 3 * h * self.moe_intermediate_size * self.num_experts
        n = self.vocab_size * h + h
        for kind, mlp in zip(self.layer_types, self.mlp_layer_types):
            n += (conv if kind == "conv" else attn) + 2 * h \
                + (3 * h * self.intermediate_size if mlp == "dense"
                   else sparse)
        return n


class Lfm2ShortConv(Layer):
    """The gated short convolution. ``forward(u)`` runs ``T`` rows from a
    zero tail; ``served`` is the engine's call, a prompt or one token."""

    def __init__(self, cfg: Lfm2Config):
        super().__init__()
        self.cfg = cfg
        h, k = cfg.hidden_size, cfg.conv_L_cache
        # columns: B, then C, then x
        self.in_proj = _linear(h, 3 * h, cfg.init_std, cfg.dtype)
        self.conv_weight = self.create_parameter(
            [h, k], attr=ParamAttr(initializer=NormalInitializer(
                0.0, 1.0 / math.sqrt(k))), dtype="float32")
        self.out_proj = _linear(
            h, h, cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers),
            cfg.dtype)

    def _gated(self, u):
        """``u`` [b, T, h] -> (``v = B * x`` in the parameters' dtype: what
        the convolution reads and the tail keeps, ``C`` float32)."""
        h = self.cfg.hidden_size
        bcx = self.in_proj(_matmul_in(u, self.cfg.dtype)).value
        f32 = jnp.float32
        v = bcx[..., :h].astype(f32) * bcx[..., 2 * h:].astype(f32)
        return v.astype(bcx.dtype), bcx[..., h:2 * h].astype(f32)

    def _out(self, y):
        w = self.out_proj.weight.value
        return Tensor(jnp.einsum("bth,hk->btk", y.astype(w.dtype), w,
                                 preferred_element_type=jnp.float32),
                      stop_gradient=True)

    def forward(self, u):
        """``u`` [b, T, h] (the norm's output) -> float32 [b, T, h]."""
        v, c = self._gated(u)
        return self._out(c * ssm_ops.causal_conv(v, self.conv_weight.value))

    def served(self, u, state, rows, last):
        """The serving engine's call. ``state`` = (tail [slots, K-1, h],)
        of this layer. A prompt (``T`` > 1): ``rows`` [b] the cache row of
        each row of the dispatch (out of range: none), ``last`` [b] its
        last token's row; the convolution starts from zeros and the tail
        after ``last`` is written to the row. One token (``T`` = 1, ``b`` =
        slots): every row's tail is read and shifted. -> (float32 [b, T,
        h], the state)."""
        tail = state[0].value
        v, c = self._gated(u)
        w = self.conv_weight.value
        if v.shape[1] > 1:
            y = ssm_ops.causal_conv(v, w)
            new = ssm_ops.conv_tail(v, last, self.cfg.conv_L_cache)
            tail = tail.at[rows].set(new.astype(tail.dtype), mode="drop")
        else:
            y = ssm_ops.causal_conv(v, w, None, tail)
            tail = jnp.concatenate([tail[:, 1:], v.astype(tail.dtype)],
                                   axis=1)
        return self._out(c * y), (Tensor(tail, stop_gradient=True),)


class Lfm2Block(Layer):
    """Pre-norm block: a convolution or an attention mixer, then the dense
    MLP (a leading layer) or the experts. The residual stream is float32."""

    def __init__(self, cfg: Lfm2Config, layer: int):
        super().__init__()
        self.cfg = cfg
        self.kind = cfg.layer_types[layer]
        self.sparse = cfg.mlp_layer_types[layer] == "sparse"
        self.operator_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                     cfg.dtype)
        if self.kind == "conv":
            self.conv = Lfm2ShortConv(cfg)
        else:
            self.attn = LagunaAttention(cfg, layer)
        self.ffn_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        if self.sparse:
            self.moe = LagunaMoE(cfg)
        else:
            self.mlp = SwiGLU(
                cfg.hidden_size, cfg.intermediate_size, cfg.init_std,
                cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers),
                cfg.dtype)

    def _ffn(self, x, live=None):
        """-> (x + ffn(norm(x)), the expert layer's counts)."""
        u = self.ffn_norm(x)
        if self.sparse:
            y, counted = self.moe.served(u.value, live)
            return x + Tensor(y, stop_gradient=True), counted
        y = self.mlp(_matmul_in(u, self.cfg.dtype)).astype("float32")
        return x + y, no_counts()

    def forward(self, x):
        u = self.operator_norm(x)
        if self.kind == "conv":
            y = self.conv(u)
        else:
            y = self.attn(_matmul_in(u, self.cfg.dtype)).astype("float32")
        return self._ffn(x + y)[0]

    def served(self, x, cache, pos, tables, ctx_len, last, live):
        """The serving engine's call -> (x, this layer's cache, the expert
        layer's counts). ``tables``: the block tables of an attention
        layer, the cache rows of a convolution layer."""
        u = self.operator_norm(x)
        if self.kind == "conv":
            y, cache = self.conv.served(u, cache, tables, last)
        else:
            y, cache, _ = self.attn(_matmul_in(u, self.cfg.dtype), cache,
                                    pos, tables, ctx_len)
            y = y.astype("float32")
        x, counted = self._ffn(x + y, live)
        return x, cache, counted


class Lfm2Model(Layer):
    def __init__(self, cfg: Lfm2Config):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_w(cfg.init_std if cfg.embed_init_std is None
                           else cfg.embed_init_std))
        self.layers = LayerList([Lfm2Block(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        if cfg.final_norm_init is not None:
            self.norm.weight = self.norm.create_parameter(
                [cfg.hidden_size], dtype=cfg.dtype, attr=ParamAttr(
                    initializer=NormalInitializer(*cfg.final_norm_init)))

    def _embed(self, input_ids):
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return self.embed(Tensor(ids, stop_gradient=True)).astype("float32")

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
        layers, int32 [2]). ``cache``: one tuple a layer, (k, v) pools of
        an attention layer, (tail,) of a convolution layer.
        ``block_tables``: (the attention layers' block tables [b, T], the
        cache row of each row [b]). ``last`` [b]: each prompt's last row
        in this call (None: every row is one)."""
        tables, rows = (jnp.asarray(t, jnp.int32) for t in block_tables)
        x = self._embed(input_ids)
        b, s = x.shape[0], x.shape[1]
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        if s > 1 and last is None:
            last = jnp.full((b,), s - 1, jnp.int32)
        ctx_len = pos + (s if last is None else last + 1)
        # a slot with no request has no row yet: it routes nowhere
        live = pos > 0 if s == 1 else None
        caches, counted = [], no_counts()
        for i, blk in enumerate(self.layers):
            x, c, n = blk.served(x, cache[i], pos,
                                 rows if blk.kind == "conv" else tables,
                                 ctx_len, last, live)
            caches.append(c)
            counted = counted + n
            if collect is not None:
                collect.append(x)
        return self.norm(x), caches, counted


class Lfm2ForCausalLM(Layer):
    """The model with its tied head. ``forward(ids)`` -> float32 logits
    [b, s, vocab]; with ``cache`` the serving engine's call -> (float32
    logits, caches[, the device counters])."""

    span_prefix = "lfm2"

    def __init__(self, cfg: Lfm2Config):
        super().__init__()
        if (cfg.kv_heads, cfg.vocab, cfg.experts) != (
                (0, cfg.num_key_value_heads), (0, cfg.vocab_size),
                (0, cfg.num_experts)):
            raise ValueError("a share of the model is not built: "
                             f"{type(self).__name__} holds every head, "
                             "expert and vocabulary row")
        with RecordEvent(f"{self.span_prefix}.build",
                         {"layers": cfg.num_hidden_layers,
                          "params": cfg.num_params()}):
            self.cfg = cfg
            self.model = Lfm2Model(cfg)

    def _head(self, h):
        w = self.model.embed.weight.value
        return Tensor(jnp.einsum("bsh,vh->bsv", h.astype(w.dtype), w,
                                 preferred_element_type=jnp.float32),
                      stop_gradient=True)

    def forward(self, input_ids, collect=None, cache=None, cache_pos=None,
                block_tables=None, lora=None, last=None, counters=None):
        if cache is None:
            return self._head(self.model(input_ids, collect).value)
        if lora is not None:
            raise ValueError(f"{type(self).__name__} has no LoRA path")
        h, caches, counted = self.model.served(
            input_ids, cache, cache_pos, block_tables, last, collect)
        h = h.value
        if last is not None:
            # the head never multiplies a bucket's padding
            h = jnp.take_along_axis(
                h, jnp.asarray(last, jnp.int32)[:, None, None], axis=1)
        if counters is None:
            return self._head(h), caches
        return self._head(h), caches, \
            counters + counted.astype(counters.dtype)

    def serving_spec(self):
        """One kind of blocks (the attention layers keep every row), one
        kind of recurrent state of ONE array (a convolution layer's tail),
        pools and tails in the parameters' dtype, the expert layers'
        device counters, none of the engine's optional features yet:
        prefix reuse needs a snapshot of the tail at a block's edge
        (ROADMAP R4), speculation a way to roll it back."""
        from ..serving.seam import CacheKind, ServedModel, StateKind
        cfg = self.cfg
        return ServedModel(
            model=self, family="lfm2",
            max_positions=cfg.max_position_embeddings,
            vocab=cfg.vocab_size,
            # a pool row holds kv_pack KV heads side by side (whole lanes)
            cache_kinds=(CacheKind("full", cfg.layers_of("full_attention"),
                                   cfg.num_key_value_heads // cfg.kv_pack,
                                   cfg.head_dim * cfg.kv_pack),),
            state_kinds=(StateKind(
                "conv", cfg.layers_of("conv"),
                (((cfg.conv_L_cache - 1, cfg.hidden_size), cfg.dtype),)),),
            kv_dtype={"bfloat16": "bf16", "float32": "f32"}[cfg.dtype],
            features=frozenset(), counters=DECODE_COUNTERS,
            tokens_a_dispatch=cfg.tokens_a_dispatch, head_on_last_row=True)


LFM2_CONFIGS = {
    "lfm2-24b-a2b": Lfm2Config(),
    # a toy of the same layer kinds and period for tests and CPU
    # rehearsals: a leading dense convolution layer, then one whole period
    # of sparse layers; contexts longer than the taps and than a block
    "lfm2-tiny": Lfm2Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, layer_types=("conv", "full_attention") + ("conv",) * 3,
        num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, max_position_embeddings=256,
        moe_tile_m=8, router_bias_init_std=0.3, dtype="float32",
        tokens_a_dispatch=64),
}
