"""dots.vlm1.inst's language model (rednote-hilab): a configuration of the
sparse-expert decoder of ``models/laguna.py``, as ``models/mellum.py`` and
``models/keye.py`` are, plus the one layer the decoder lacked, multi-head
latent attention, and the sixth model behind the serving plane's model
seam. The vision tower and the multi-token-prediction module
(``num_nextn_predict_layers``: a draft head the main forward never calls)
are not the served language model's and are not built.

The published ``config.json`` (``model_type`` ``dots_vlm``; the language
keys are DeepSeek-V3's): 61 layers, hidden 7168, 128 heads; the first 3
layers' MLP a SwiGLU of 18432, every later layer 256 routed experts of 2048
(top 8, sigmoid scores, chosen by ``s + e_score_correction_bias`` within
the 4 best of 8 groups and weighed by the chosen ``s`` renormalised x 2.5)
and one shared expert; vocabulary 129280, untied head, 163840 positions
under YaRN (factor 40, original 4096, beta 32 / 1, ``mscale`` =
``mscale_all_dim`` = 1). What the config leaves open is settled as
``perfbench/configs/dots-vlm1-share32-d6.json`` lists under ``assumed``.

**Latent attention** (:class:`LatentAttention`), on ``u = RMSNorm(x)``:

    c_q = RMSNorm(u W_qa) [1536]      q = c_q W_qb -> a head [q_n 128 | q_r 64]
    [c | k_r] = u W_kva [512 | 64]    c = RMSNorm(c)
    q_r = rot(q_r), k_r = rot(k_r)    ONE rotated key for all 128 heads
    [k_n | v] = c W_kvb               a head [128 | 128]
    score = (q_n . k_n + q_r . k_r) x 192^-0.5 x m(mscale_all_dim)^2
    y = concat_h(softmax_causal(score) v) W_o

**The cache keeps ``(c, k_r)`` and nothing else**: 576 values a token a
layer for all 128 heads (1152 B in bfloat16, where 128 heads' K and V would
be 65,536), after the norm and the rotary, in a pool with no head axis
(``serving.seam.CacheKind`` with no pair). A decode row reads it
**absorbed** (``q_l[h] = q_n[h] W_uk[h]^T``, ``score = q_l . c + q_r .
k_r``, ``o_l = P c``, ``o[h] = o_l W_uv[h]`` with ``W_uk`` / ``W_uv`` the
halves of ``W_kvb``): one paged kernel over the pool as it is held
(``ops/pallas/mla_attention.mla_paged_attention``), K and V of the heads
never materialised. A prompt **materialises** ``k_n`` and ``v`` from the
rows it just cached (``mla_prompt_attention``). Both forms are one function
of the same weights and ``tests/test_dotsvlm.py`` holds them equal.

**What the decoder is configured to**: every layer ``full_attention``
through :meth:`DotsVlmConfig.attention`, ``first_k_dense_replace`` dense
layers then sparse ones, sigmoid scores under a selection bias
(``router_bias``), the choice by groups (``router_groups`` /
``router_topk_groups``), one shared expert, no output gate.

**One chip's share.** In the deployment this is served in, 32 chips share
each expert layer (``held_experts``) and the vocabulary is cut in 8
(``held_vocab``); attention, the dense MLP, the router and the shared
expert are whole on every chip. The router keeps all 256 outputs, its 8
groups and top 8; the layer adds the held chosen experts' terms and the
shared expert, and what the absent experts would add is left out: nothing
stands in for the other chips (``models/laguna.py``'s module docstring).

The family is served, not trained.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..dygraph.layers import Layer
from ..dygraph.tensor import Tensor
from ..ops.attention_ops import latent_pool_write
from ..ops.decoder_ops import rotary_inv_freq
from ..ops.pallas.mla_attention import (mla_paged_attention,
                                        mla_prompt_attention, prompt_pairs)
from .laguna import (LagunaConfig, LagunaForCausalLM, RMSNorm, _linear)

#: the one rotary of every layer, as published (``rope_scaling``)
_ROPE = {"full_attention": {
    "rope_type": "yarn", "rope_theta": 10000.0, "factor": 40.0,
    "original_max_position_embeddings": 4096, "beta_fast": 32.0,
    "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0}}
#: rows of one pass of the expert layer over a served prompt (the buckets
#: are multiples of it)
PROMPT_CHUNK_ROWS = 2048
#: heads of one pass of a prompt's materialised read
PROMPT_HEADS = 32
#: what a decode row's read counts: the cached rows it read (its context)
LATENT_COUNTERS = ("latent_rows_read",)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(a) = 0.1 a ln(factor) + 1`` (1 without a factor over 1)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass
class DotsVlmConfig(LagunaConfig):
    """The decoder's configuration with dots.vlm1.inst's language model's
    values as defaults, and the latent attention's own sizes."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    num_key_value_heads: int = 128          # no grouping: a latent instead
    head_dim: int = 192                     # a key: 128 without position + 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 3
    layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    rope_parameters: dict = field(default_factory=lambda: dict(_ROPE))
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    shared_expert_intermediate_size: int = 2048
    moe_routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 163840
    attention_gate: bool = False
    router_score: str = "sigmoid"
    router_bias: bool = True
    router_groups: int = 8
    router_topk_groups: int = 4
    expert_counters: Tuple[str, ...] = ("experts_touched", "expert_pairs")
    dtype: str = "bfloat16"
    moe_chunk_rows: int = PROMPT_CHUNK_ROWS
    # the stds of W_qb and of W_o where a random-weight model needs others
    # than init_std and init_std / sqrt(2 layers) (None: those). At 0.02 a
    # softmax over thousands of random keys is nearly flat (score std 1.5)
    # and averages the values away: W_qb sets how peaked it is, W_o the
    # layer's share of the stream (a large share under a flat softmax is
    # one common vector for every position: the layers add the keys' mean)
    attn_q_init_std: Optional[float] = None
    attn_out_init_std: Optional[float] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        dense = min(self.first_k_dense_replace, n)
        self.layer_types = self.layer_types or ("full_attention",) * n
        self.mlp_layer_types = self.mlp_layer_types or \
            ("dense",) * dense + ("sparse",) * (n - dense)
        if not self.num_attention_heads_per_layer:
            self.num_attention_heads_per_layer = \
                (self.num_attention_heads,) * n
        super().__post_init__()
        if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError(
                f"a key is its part without position and its rotated part: "
                f"head_dim {self.head_dim} is not {self.qk_nope_head_dim} + "
                f"{self.qk_rope_head_dim}")
        if self.num_experts % self.router_groups:
            raise ValueError(f"{self.num_experts} experts in "
                             f"{self.router_groups} groups")

    @property
    def latent_width(self) -> int:
        """Values a token keeps in a layer's cache: the latent and the one
        rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def read_counters(self):
        return LATENT_COUNTERS

    def attention(self, layer: int):
        return LatentAttention(self, layer)

    def attention_params(self) -> int:
        h, heads = self.hidden_size, self.num_attention_heads
        return h * self.q_lora_rank + self.q_lora_rank \
            + self.q_lora_rank * heads * self.head_dim \
            + h * self.latent_width + self.kv_lora_rank \
            + self.kv_lora_rank * heads * (self.qk_nope_head_dim
                                           + self.v_head_dim) \
            + heads * self.v_head_dim * h

    def num_params(self) -> int:
        """Parameters this share holds (all of them for the whole model)."""
        h = self.hidden_size
        e = self.experts[1] - self.experts[0]
        n = 2 * (self.vocab[1] - self.vocab[0]) * h + h
        for kind in self.mlp_layer_types:
            n += self.attention_params() + 2 * h
            if kind == "dense":
                n += 3 * h * self.intermediate_size
            else:
                n += h * self.num_experts + self.num_experts \
                    + 3 * h * self.moe_intermediate_size * e \
                    + 3 * h * self.shared_expert_intermediate_size
        return n


def _rms(x, norm: RMSNorm):
    """``norm`` of a float32 array, in float32 (the ``rms_norm`` op's
    arithmetic)."""
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + norm.eps)
    return y * norm.weight.value.astype(jnp.float32)


class LatentAttention(Layer):
    """Multi-head latent attention, served (see the module)."""

    def __init__(self, cfg: DotsVlmConfig, layer: int):
        super().__init__()
        del layer                           # every layer is alike
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.num_attention_heads
        rq, r = cfg.q_lora_rank, cfg.kv_lora_rank
        out_std = cfg.attn_out_init_std \
            or cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers)
        self.q_a_proj = _linear(h, rq, cfg.init_std, cfg.dtype)
        self.q_a_norm = RMSNorm(rq, cfg.rms_norm_eps, cfg.dtype)
        # columns: a head's [q_n | q_r], head after head
        self.q_b_proj = _linear(rq, heads * cfg.head_dim,
                                cfg.attn_q_init_std or cfg.init_std,
                                cfg.dtype)
        # columns: the latent, then the one key that is rotated
        self.kv_a_proj = _linear(h, cfg.latent_width, cfg.init_std,
                                 cfg.dtype)
        self.kv_a_norm = RMSNorm(r, cfg.rms_norm_eps, cfg.dtype)
        # columns: a head's [k_n | v], head after head
        self.kv_b_proj = _linear(
            r, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            cfg.init_std, cfg.dtype)
        self.o_proj = _linear(heads * cfg.v_head_dim, h, out_std, cfg.dtype)
        rope = cfg.rope_parameters["full_attention"]
        yarn = rope if rope.get("rope_type") == "yarn" else None
        factor = float(rope.get("factor", 1.0)) if yarn else 1.0
        all_dim = yarn_mscale(factor, float(rope.get("mscale_all_dim", 0.0)))
        # YaRN here scales the whole score, not only the rotated part: the
        # cosines and sines by m(mscale) / m(mscale_all_dim) (1 as
        # published) and the softmax's scale by m(mscale_all_dim)^2
        self.inv_freq, _ = rotary_inv_freq(
            cfg.qk_rope_head_dim, float(rope["rope_theta"]), yarn)
        self.rot_scale = yarn_mscale(factor, float(rope.get("mscale", 1.0))) \
            / all_dim
        self.scale = all_dim * all_dim / math.sqrt(cfg.head_dim)

    def _rotate(self, x, rows):
        """``x`` float32 [b, .., s, dr] rotated (rotate-half) at the
        positions ``rows`` [b, .., s] (broadcast against ``x``)."""
        d = x.shape[-1]
        ang = rows.astype(jnp.float32)[..., None] \
            * jnp.asarray(self.inv_freq, jnp.float32)
        cos, sin = jnp.cos(ang) * self.rot_scale, jnp.sin(ang) * self.rot_scale
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def up_projections(self):
        """``W_kvb`` as ``[r, heads, dn + dv]``: a head's key
        up-projection (``W_uk``) then its value's (``W_uv``)."""
        cfg = self.cfg
        return self.kv_b_proj.weight.value.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)

    def latents_of(self, u, rows):
        """The layer's normed input ``u`` [b, s, h] at positions ``rows``
        [b, s] -> (the query's latent ``c_q`` [b, s, rq], float32; what
        the cache keeps of the rows, [b, s, r + dr] in the parameters'
        dtype: the normed latent then the rotated key). Projections
        accumulate in float32; norms and rotary are float32."""
        dt = self.q_a_proj.weight.value.dtype
        r = self.cfg.kv_lora_rank

        def proj(layer):
            w = layer.weight.value
            return jnp.einsum("bsh,hn->bsn", u.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)
        kv = proj(self.kv_a_proj)
        kept = jnp.concatenate(
            [_rms(kv[..., :r], self.kv_a_norm),
             self._rotate(kv[..., r:], rows)], axis=-1).astype(dt)
        return _rms(proj(self.q_a_proj), self.q_a_norm), kept

    def queries(self, c_q, rows, w_qb):
        """``c_q`` [b, s, rq] through the heads ``w_qb`` [rq, heads, dn +
        dr] -> (q_n [b, heads, s, dn], the rotated q_r [b, heads, s, dr]),
        in the parameters' dtype. A head's two parts are projected apart:
        the part without position leaves its product in the parameters'
        dtype, only the rotated part is ever held in float32."""
        dn = self.cfg.qk_nope_head_dim
        q_n = jnp.einsum("bsq,qhd->bhsd", c_q, w_qb[..., :dn],
                         preferred_element_type=c_q.dtype)
        q_r = jnp.einsum("bsq,qhd->bhsd", c_q, w_qb[..., dn:],
                         preferred_element_type=jnp.float32)
        return q_n, self._rotate(q_r, rows[:, None]).astype(c_q.dtype)

    def materialised(self, c_q, kept, rows, live=None):
        """A prompt's read and its output projection (``c_q`` float32,
        scaled here by the score's scale before it is rounded: the query
        is linear in it, and the read then multiplies no logit by it),
        :data:`PROMPT_HEADS` heads a pass (of a 16384-row prompt's 128 heads, q, k_n, v and o
        at once are 2.4 GB; a pass of 32 holds 0.6): the pass's q from
        ``c_q``, its k_n and v from the rows' latents as the cache holds
        them, its read, and its rows of ``W_o`` added to the sum -> [b, s,
        h] float32."""
        cfg = self.cfg
        dn, r, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        heads = cfg.num_attention_heads
        g = math.gcd(heads, PROMPT_HEADS)
        b, s = kept.shape[:2]
        w_o = self.o_proj.weight.value
        c_q = (c_q * self.scale).astype(kept.dtype)

        def one(y, w):
            wq, wkv, wo = w
            q_n, q_r = self.queries(c_q, rows, wq)
            k_n, v = (jnp.einsum("bsr,rhd->bhsd", kept[..., :r], part,
                                 preferred_element_type=kept.dtype)
                      for part in (wkv[..., :dn], wkv[..., dn:]))
            o = mla_prompt_attention(q_n, q_r, k_n, kept[..., r:], v,
                                     scale=1.0, live=live)
            return y + jnp.einsum("bhsd,hdn->bsn", o, wo,
                                  preferred_element_type=jnp.float32), None

        def passes(w, width):
            """``w`` [n, heads, width] as [passes, n, g, width]."""
            return w.reshape(-1, heads // g, g, width).transpose(1, 0, 2, 3)
        return jax.lax.scan(
            one, jnp.zeros((b, s, w_o.shape[1]), jnp.float32),
            (passes(self.q_b_proj.weight.value.reshape(-1, heads,
                                                       cfg.head_dim),
                    cfg.head_dim),
             passes(self.up_projections(), dn + dv),
             w_o.reshape(heads // g, g, dv, -1)))[0]

    def absorbed(self, c_q, rows, pool, tables, pos):
        """A decode row's read over the latent pool and its output
        projection: ``c_q`` [b, 1, rq] -> [b, 1, h] float32."""
        cfg = self.cfg
        dn = cfg.qk_nope_head_dim
        dt = pool.dtype
        w = self.up_projections()
        q_n, q_r = self.queries(
            c_q.astype(dt), rows, self.q_b_proj.weight.value.reshape(
                -1, cfg.num_attention_heads, cfg.head_dim))
        q_l = jnp.einsum("bhd,rhd->bhr", q_n[:, :, 0], w[..., :dn],
                         preferred_element_type=jnp.float32)
        o_l = mla_paged_attention(q_l, q_r[:, :, 0], pool, tables, pos,
                                  scale=self.scale)
        o = jnp.einsum("bhr,rhd->bhd", o_l.astype(dt), w[..., dn:],
                       preferred_element_type=jnp.float32)
        w_o = self.o_proj.weight.value
        return jnp.einsum("bsn,nh->bsh",
                          o.reshape(o.shape[0], 1, -1).astype(w_o.dtype),
                          w_o, preferred_element_type=jnp.float32)

    def forward(self, h, cache=None, cache_pos=None, block_tables=None,
                ctx_len=None):
        """The serving engine's call (:meth:`LagunaAttention._served`'s
        contract): ``cache`` this layer's ONE pool ``[blocks, r + dr,
        block_size]``, ``block_tables`` [b, T], ``cache_pos`` [b] each
        request's first row of this call, ``ctx_len`` [b] its rows once the
        call is done -> (output, the pool with the call's rows written, a
        decode row's cached rows read int32 [b, 1] or None for a prompt)."""
        cfg = self.cfg
        if cache is None:
            raise ValueError(
                f"{type(self).__name__} is served through its latent cache "
                f"(ServingEngine): the family has no path without one")
        u = h.value if isinstance(h, Tensor) else h
        b, s, _ = u.shape
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        rows = jnp.clip(pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None],
                        0, cfg.max_position_embeddings - 1)
        c_q, kept = self.latents_of(u, rows)
        pool = latent_pool_write(cache[0].value, kept, pos, block_tables)
        if s > 1:
            y = self.materialised(c_q, kept, rows, ctx_len - pos)
            reads = None
        else:
            y = self.absorbed(c_q, rows, pool, block_tables, pos)
            reads = (pos + 1)[:, None]
        return (Tensor(y.astype(kept.dtype), stop_gradient=True),
                (Tensor(pool, stop_gradient=True),), reads)


class DotsVlmForCausalLM(LagunaForCausalLM):
    """The decoder under a dots.vlm1 configuration, with what the serving
    plane needs of it."""

    span_prefix = "dotsvlm"

    def forward(self, input_ids, labels=None, cache=None, **kw):
        if cache is None:
            raise ValueError(
                f"{type(self).__name__} is served, not trained: its "
                f"attention reads a latent cache (ServingEngine)")
        return super().forward(input_ids, labels=labels, cache=cache, **kw)

    def serving_spec(self):
        """One kind of layer whose token keeps ONE array and no K and V
        (the latent and the rotated key, 576 values), a pool in the
        parameters' dtype, none of the engine's optional features (prefix
        reuse over latent rows would need a prefix's rows materialised by
        block: ROADMAP R2), prompts one a dispatch with the head on the
        last row, the experts this share holds, and the device counters of
        the experts and of the latent read."""
        from ..serving.seam import CacheKind, ServedModel
        cfg = self.cfg
        lo, hi = cfg.vocab
        if cfg.kv_heads != (0, cfg.num_key_value_heads) or lo != 0:
            raise ValueError(
                "the serving path holds every head, and a share of the "
                "vocabulary from row 0 (a sampled column is the next id)")
        (_, layers, _), = cfg.cache_kinds()

        def prompt_counts(bucket, rows, live):
            # every layer's materialised read of one dispatch: a head's
            # live (query, key) pairs (the causal triangle, not the tiles
            # the kernel runs) and the read's calls: a pass of heads a layer
            passes = len(layers) * cfg.num_attention_heads \
                // math.gcd(cfg.num_attention_heads, PROMPT_HEADS)
            return {"mla_prompt_pairs":
                    passes * prompt_pairs(rows, bucket, live),
                    "mla_prompt_reads": passes}
        return ServedModel(
            model=self, family="dotsvlm",
            max_positions=cfg.max_position_embeddings, vocab=hi - lo,
            cache_kinds=(CacheKind(
                "latent", layers, 0, 0,
                extra=(("latent_cache", cfg.latent_width),)),),
            kv_dtype={"bfloat16": "bf16", "float32": "f32"}[cfg.dtype],
            features=frozenset(), counters=cfg.decode_counters,
            # one prompt a dispatch: a 16384-row prompt's q, k_n and v of
            # 128 heads are 2.1 GB beside 11.3 GB of weights and pool
            tokens_a_dispatch=1, head_on_last_row=True,
            prompt_counts=prompt_counts)


DOTSVLM_CONFIGS = {
    "dots-vlm1": DotsVlmConfig(),
    # a toy of every mechanism for tests and CPU rehearsals: 2 query
    # ranks' worth of latent, a rotary on 8 of 24, 4 groups of 4 experts
    # with the top 4 in 2 groups, one dense layer, a shared expert
    "dotsvlm-tiny": DotsVlmConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        head_dim=24, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, router_groups=4,
        router_topk_groups=2, max_position_embeddings=128,
        rope_parameters={"full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 32.0,
            "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0}},
        moe_tile_m=8, moe_chunk_rows=0, dtype="float32",
        router_bias_init_std=0.05),
}
