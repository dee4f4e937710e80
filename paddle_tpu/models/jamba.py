"""Jamba-class hybrid decoder LM (ai21labs/AI21-Jamba2-3B): state-space
(Mamba) layers with an attention layer every ``attn_layer_period``, a
dense SwiGLU MLP in every layer, a tied head. The third model behind the
serving plane's model seam, and the first whose layers carry state from
token to token other than keys and values.

The published ``config.json``: 28 layers, layer ``l`` is attention iff
``l % 14 == 7`` (layers 7 and 21), hidden 2560, ``mamba_expand`` 2 (5120
channels), ``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_dt_rank`` 160,
a bias on the convolution and none on the projections, 20 query heads over
1 KV head of 128, no positional encoding, ``num_experts`` 1 (no router),
MLP 8192, vocabulary 65536. A layer, on ``T`` rows::

    mamba      u = RMSNorm(h);  [x', z] = u W_in
               x = silu(conv(x'))                     depthwise, causal, K = 4
               [dl, B, C] = x W_x;  each RMSNorm'd    Jamba's inner norms
               dt = softplus(dl W_dt + b_dt);  A = -exp(A_log)
               s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t;  y_t = s_t C_t + D x_t
               h = h + (y * silu(z)) W_out
    attention  u = RMSNorm(h);  h = h + softmax(q k^T / sqrt(128), causal) v Wo
    both       u2 = RMSNorm(h);  h = h + (silu(u2 Wg) * (u2 Wu)) Wd

**Shared with Laguna / Mellum**, called and not copied: ``RMSNorm`` and
``SwiGLU`` (``models/laguna.py``), the flash forward with grouped KV
(``fused_attention_qkv``, 20 query heads on one KV head through the index
map), the paged read and the pool write of a decode step
(``paged_attention``, the kernel that walks a row's live blocks, its 20
query rows on the one KV head through the matrix unit /
``block_scatter_write``), the head on a prompt's last row, the build
span, bfloat16 pools.
**What could not be shared**: Laguna's attention module is built around
its rotary tables, its window and its gate, none of which exist here, so
the attention layer is its own small class over the same ops; and the
Mamba mixer (``ops/ssm_ops.py``, ``ops/pallas/selective_scan.py``) is new.

Serving: per request a Mamba layer keeps its scan state ``[16, 5120]``
(float32) and the last three rows of ``x'`` ``[3, 5120]`` (bfloat16), which
the seam declares as a :class:`~paddle_tpu.serving.seam.StateKind`; the
cache holds them ``[max_slots, ...]`` a layer beside the two attention
layers' blocks. A prefill dispatch writes the state each prompt leaves **at
its own last token** into its row, whole; the decode step rewrites every
row.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..dygraph.layers import Layer, LayerList
from ..dygraph.tape import run_op
from ..dygraph.tensor import Tensor
from ..initializer import ConstantInitializer, NormalInitializer
from ..nn.layers_common import Embedding
from ..ops import ssm_ops
from ..ops.attention_ops import block_scatter_write
from ..param_attr import ParamAttr
from ..profiler import RecordEvent
from .laguna import RMSNorm, SwiGLU, _linear, _matmul_in, _w


@dataclass
class JambaConfig:
    """AI21-Jamba2-3B's values as defaults."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: int = 128
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    init_std: float = 0.02
    embed_init_std: Optional[float] = None
    # the recurrence's leaves as (mean, std) of a normal: A_log (A =
    # -exp(A_log)) and the bias of dt (dt = softplus(. + bias)), so that
    # a random-weight model remembers over tens to hundreds of tokens
    a_log_init: Tuple[float, float] = (1.5, 0.8)
    dt_bias_init: Tuple[float, float] = (-4.6, 1.3)
    # the final norm's gain as (mean, std) of a normal (None: the constant
    # 1 of a fresh model). The head is tied: with a gain of 1 a random
    # model's logit of the token it was just fed is |e|^2 / rms(h), ten
    # times the largest of the others, so it repeats its input whatever
    # its layers do; a zero-mean gain takes that term away
    final_norm_init: Optional[Tuple[float, float]] = None
    # the parameters' dtype (and the attention layers' pools'); the scan
    # state, dt, A and the norms' arithmetic are float32 whatever it is
    dtype: str = "bfloat16"
    # tokens a prefill dispatch computes (``ServedModel.tokens_a_dispatch``)
    tokens_a_dispatch: int = 1024

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def layer_kind(self, layer: int) -> str:
        return "attention" if layer % self.attn_layer_period \
            == self.attn_layer_offset else "mamba"

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_kind(i) == kind)

    def num_params(self) -> int:
        h, d, n = self.hidden_size, self.d_inner, self.mamba_d_state
        r, k = self.mamba_dt_rank, self.mamba_d_conv
        mamba = h * 2 * d + d * k + d + d * (r + 2 * n) + r + 2 * n \
            + r * d + d + d * n + d + d * h
        q, kv, hd = (self.num_attention_heads, self.num_key_value_heads,
                     self.head_dim)
        attn = h * (q + 2 * kv) * hd + q * hd * h
        both = 3 * h * self.intermediate_size + 2 * h
        return self.vocab_size * h + h \
            + len(self.layers_of("mamba")) * (mamba + both) \
            + len(self.layers_of("attention")) * (attn + both)


def _normal(mean_std, dtype="float32"):
    return {"attr": ParamAttr(initializer=NormalInitializer(*mean_std)),
            "dtype": dtype}


def _normed(norm, x):
    """An RMSNorm layer on a float32 array."""
    return norm(Tensor(x, stop_gradient=True)).value


class JambaMamba(Layer):
    """The Mamba mixer. ``forward(u)`` runs ``T`` rows from a zero state;
    ``served`` is the engine's call, a prompt or one token."""

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        h, d, n = cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state
        r, k = cfg.mamba_dt_rank, cfg.mamba_d_conv
        out_std = cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers)
        self.in_proj = _linear(h, 2 * d, cfg.init_std, cfg.dtype)
        self.conv_weight = self.create_parameter(
            [d, k], **_normal((0.0, 1.0 / math.sqrt(k))))
        self.conv_bias = self.create_parameter(
            [d], attr=ParamAttr(initializer=ConstantInitializer(0.0)),
            dtype="float32")
        self.x_proj = _linear(d, r + 2 * n, 1.0 / math.sqrt(d), cfg.dtype)
        self.dt_norm = RMSNorm(r, cfg.rms_norm_eps, cfg.dtype)
        self.b_norm = RMSNorm(n, cfg.rms_norm_eps, cfg.dtype)
        self.c_norm = RMSNorm(n, cfg.rms_norm_eps, cfg.dtype)
        self.dt_proj = self.create_parameter(
            [r, d], attr=_w(1.0 / math.sqrt(r)), dtype=cfg.dtype)
        self.dt_bias = self.create_parameter(
            [d], **_normal(cfg.dt_bias_init))
        self.A_log = self.create_parameter([d, n], **_normal(cfg.a_log_init))
        self.D = self.create_parameter(
            [d], attr=ParamAttr(initializer=ConstantInitializer(1.0)),
            dtype="float32")
        self.out_proj = _linear(d, h, out_std, cfg.dtype)

    def _inputs(self, xz, tail):
        """``xz`` [b, T, 2d] (the in-projection's output) and the rows of
        ``x'`` before row 0 -> what the recurrence reads: (x', x, dt, B, C,
        z), the last five float32."""
        cfg = self.cfg
        d, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        xp, z = xz[..., :d], xz[..., d:].astype(jnp.float32)
        x = jax.nn.silu(ssm_ops.causal_conv(
            xp, self.conv_weight.value, self.conv_bias.value, tail))
        w = self.x_proj.weight.value
        dbc = jnp.einsum("btd,dk->btk", x.astype(w.dtype), w,
                         preferred_element_type=jnp.float32)
        dl = _normed(self.dt_norm, dbc[..., :r])
        b = _normed(self.b_norm, dbc[..., r:r + n])
        c = _normed(self.c_norm, dbc[..., r + n:])
        wdt = self.dt_proj.value
        dt = jax.nn.softplus(
            jnp.einsum("btr,rd->btd", dl.astype(wdt.dtype), wdt,
                       preferred_element_type=jnp.float32)
            + self.dt_bias.value[None, None])
        return xp, x, dt, b, c, z

    def _a(self):
        return -jnp.exp(self.A_log.value.astype(jnp.float32)).T   # [n, d]

    def _out(self, y):
        w = self.out_proj.weight.value
        return jnp.einsum("btd,dh->bth", y.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    def _xz(self, u):
        return self.in_proj(_matmul_in(u, self.cfg.dtype)).value

    def forward(self, u):
        """``u`` [b, T, h] (the norm's output) -> float32 [b, T, h]."""
        b, t, _ = u.shape
        _, x, dt, bb, cc, z = self._inputs(self._xz(u), None)
        y, _ = ssm_ops.selective_scan(
            x, dt, self._a(), bb, cc, self.D.value, z,
            jnp.full((b,), t - 1, jnp.int32))
        return Tensor(self._out(y), stop_gradient=True)

    def served(self, u, state, rows, last):
        """The serving engine's call. ``state`` = (tail [slots, K-1, d],
        s [slots, n, d]) of this layer. A prompt (``T`` > 1): ``rows`` [b]
        the cache row of each row of the dispatch (out of range: none),
        ``last`` [b] its last token's row; the recurrence starts from zero
        and the state after ``last`` is written to the row, whole. One
        token (``T`` = 1, ``b`` = slots): every row's state is read and
        rewritten. -> (float32 [b, T, h], the state)."""
        tail, s = state[0].value, state[1].value
        xz = self._xz(u)
        if xz.shape[1] > 1:
            xp, x, dt, bb, cc, z = self._inputs(xz, None)
            y, s_last = ssm_ops.selective_scan(
                x, dt, self._a(), bb, cc, self.D.value, z, last)
            new_tail = ssm_ops.conv_tail(xp, last, self.cfg.mamba_d_conv)
            tail = tail.at[rows].set(new_tail.astype(tail.dtype),
                                     mode="drop")
            s = s.at[rows].set(s_last, mode="drop")
        else:
            xp, x, dt, bb, cc, z = self._inputs(xz, tail)
            y, s = ssm_ops.selective_step(
                x[:, 0], dt[:, 0], self._a(), bb[:, 0], cc[:, 0],
                self.D.value, z[:, 0], s)
            y = y[:, None]
            tail = jnp.concatenate([tail[:, 1:], xp.astype(tail.dtype)],
                                   axis=1)
        return Tensor(self._out(y), stop_gradient=True), \
            (Tensor(tail, stop_gradient=True), Tensor(s, stop_gradient=True))


class JambaAttention(Layer):
    """Causal grouped-query attention with no positional encoding."""

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q, self.kv = cfg.num_attention_heads, cfg.num_key_value_heads
        # columns: the query heads, then the K heads, then V
        self.qkv_proj = _linear(h, (self.q + 2 * self.kv) * d, cfg.init_std,
                                cfg.dtype)
        self.o_proj = _linear(
            self.q * d, h,
            cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers), cfg.dtype)

    def _heads(self, u):
        d = self.cfg.head_dim
        qkv = self.qkv_proj(_matmul_in(u, self.cfg.dtype))
        b, s, _ = qkv.shape

        def heads(lo, n):
            return qkv[:, :, lo * d:(lo + n) * d].reshape(
                [b, s, n, d]).transpose([0, 2, 1, 3])
        return (heads(0, self.q), heads(self.q, self.kv),
                heads(self.q + self.kv, self.kv))

    def _project(self, o):
        """The heads' output [b, heads, s, d] through ``o_proj``."""
        b, _, s, d = o.shape
        return self.o_proj(o.transpose([0, 2, 1, 3]).reshape(
            [b, s, self.q * d])).astype("float32")

    def _attend(self, q, k, v):
        return self._project(run_op(
            "fused_attention_qkv", {"Q": [q], "K": [k], "V": [v]},
            {"causal": True, "kernel_tag": "full"})["Out"][0])

    def forward(self, u):
        return self._attend(*self._heads(u))

    def served(self, u, cache, pos, tables):
        """``cache`` this layer's (k, v) pool pair, ``tables`` [b, T] the
        block tables, ``pos`` [b] each request's first row of this call ->
        (float32 output, the pools with the call's K and V written). A
        prompt attends over its own rows (no prefix is ever shared), one
        token over its paged rows."""
        from ..ops.pallas.paged_attention import paged_attention
        q, k, v = self._heads(u)
        kp = block_scatter_write(cache[0].value, k.value, pos, tables)
        vp = block_scatter_write(cache[1].value, v.value, pos, tables)
        if q.shape[2] > 1:
            out = self._attend(q, k, v)
        else:
            out = self._project(Tensor(
                paged_attention(q.value, kp, vp, tables, pos),
                stop_gradient=True))
        return out, (Tensor(kp, stop_gradient=True),
                     Tensor(vp, stop_gradient=True))


class JambaBlock(Layer):
    """Pre-norm block: a Mamba or an attention mixer, then the dense MLP.
    The residual stream is float32."""

    def __init__(self, cfg: JambaConfig, layer: int):
        super().__init__()
        self.cfg = cfg
        self.kind = cfg.layer_kind(layer)
        self.input_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                  cfg.dtype)
        if self.kind == "mamba":
            self.mamba = JambaMamba(cfg)
        else:
            self.attn = JambaAttention(cfg)
        self.mlp_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        self.mlp = SwiGLU(
            cfg.hidden_size, cfg.intermediate_size, cfg.init_std,
            cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers), cfg.dtype)

    def _mlp(self, x):
        u = _matmul_in(self.mlp_norm(x), self.cfg.dtype)
        return x + self.mlp(u).astype("float32")

    def forward(self, x):
        mixer = self.mamba if self.kind == "mamba" else self.attn
        return self._mlp(x + mixer(self.input_norm(x)))

    def served(self, x, cache, pos, tables, last):
        u = self.input_norm(x)
        if self.kind == "mamba":
            y, cache = self.mamba.served(u, cache, tables, last)
        else:
            y, cache = self.attn.served(u, cache, pos, tables)
        return self._mlp(x + y), cache


class JambaModel(Layer):
    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_w(cfg.init_std if cfg.embed_init_std is None
                           else cfg.embed_init_std))
        self.layers = LayerList([JambaBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        if cfg.final_norm_init is not None:
            self.norm.weight = self.norm.create_parameter(
                [cfg.hidden_size], **_normal(cfg.final_norm_init, cfg.dtype))

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
        [b, s, h], the caches). ``cache``: one tuple a layer, (k, v) pools
        of an attention layer, (tail, state) of a Mamba layer.
        ``block_tables``: (the attention layers' block tables [b, T], the
        cache row of each row [b]). ``last`` [b]: each prompt's last row
        in this call (None: one token a row)."""
        tables, rows = (jnp.asarray(t, jnp.int32) for t in block_tables)
        x = self._embed(input_ids)
        b, s = x.shape[0], x.shape[1]
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        if s > 1 and last is None:
            last = jnp.full((b,), s - 1, jnp.int32)
        caches = []
        for i, blk in enumerate(self.layers):
            x, c = blk.served(x, cache[i], pos,
                              rows if blk.kind == "mamba" else tables, last)
            caches.append(c)
            if collect is not None:
                collect.append(x)
        return self.norm(x), caches


class JambaForCausalLM(Layer):
    """The model with its tied head. ``forward(ids)`` -> float32 logits
    [b, s, vocab]; with ``cache`` the serving engine's call -> (float32
    logits, caches)."""

    span_prefix = "jamba"

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        with RecordEvent(f"{self.span_prefix}.build",
                         {"layers": cfg.num_hidden_layers,
                          "params": cfg.num_params()}):
            self.cfg = cfg
            self.model = JambaModel(cfg)

    def _head(self, h):
        w = self.model.embed.weight.value
        return Tensor(jnp.einsum("bsh,vh->bsv", h.astype(w.dtype), w,
                                 preferred_element_type=jnp.float32),
                      stop_gradient=True)

    def forward(self, input_ids, collect=None, cache=None, cache_pos=None,
                block_tables=None, lora=None, last=None):
        if cache is None:
            return self._head(self.model(input_ids, collect).value)
        if lora is not None:
            raise ValueError(f"{type(self).__name__} has no LoRA path")
        h, caches = self.model.served(input_ids, cache, cache_pos,
                                      block_tables, last, collect)
        h = h.value
        if last is not None:
            # the head never multiplies a bucket's padding
            h = jnp.take_along_axis(
                h, jnp.asarray(last, jnp.int32)[:, None, None], axis=1)
        return self._head(h), caches

    def serving_spec(self):
        """One kind of blocks (the attention layers keep every row), one
        kind of recurrent state (a Mamba layer's convolution tail and scan
        state), pools in the parameters' dtype, none of the engine's
        optional features yet: prefix reuse needs a snapshot of the state
        at a block's edge, speculation a way to roll it back."""
        from ..serving.seam import CacheKind, ServedModel, StateKind
        cfg = self.cfg
        d, n, k = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
        return ServedModel(
            model=self, family="jamba",
            max_positions=cfg.max_position_embeddings,
            vocab=cfg.vocab_size,
            cache_kinds=(CacheKind("full", cfg.layers_of("attention"),
                                   cfg.num_key_value_heads, cfg.head_dim),),
            state_kinds=(StateKind(
                "mamba", cfg.layers_of("mamba"),
                (((k - 1, d), cfg.dtype), ((n, d), "float32"))),),
            kv_dtype={"bfloat16": "bf16", "float32": "f32"}[cfg.dtype],
            features=frozenset(),
            tokens_a_dispatch=cfg.tokens_a_dispatch, head_on_last_row=True)


JAMBA_CONFIGS = {
    "jamba2-3b": JambaConfig(),
    # a toy of the same layer kinds for tests and CPU rehearsals: one
    # attention layer among Mamba layers, 128 channels (one lane tile of
    # the scan kernel), contexts longer than d_conv
    "jamba-tiny": JambaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, attn_layer_period=4, attn_layer_offset=2,
        mamba_d_state=8, mamba_dt_rank=8, max_position_embeddings=256,
        dtype="float32", tokens_a_dispatch=64),
}
