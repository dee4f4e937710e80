"""Laguna-class decoder LM: RMSNorm, rotary positions that differ by layer
kind, grouped-query attention whose head count differs by layer, a
sliding window on some layers, a per-head sigmoid output gate, SwiGLU, a
sparse-expert MLP with a shared expert, an untied output head.

The layer list follows the configuration's ``layer_types`` (``full_attention``
/ ``sliding_attention``), ``num_attention_heads_per_layer`` and
``mlp_layer_types`` (``dense`` / ``sparse``), as the published
``poolside/Laguna-XS.2`` ``config.json`` gives them. What the config leaves
open is settled by stated conventions (``perfbench/configs/
laguna-xs2-share8.json`` lists them under ``assumed``): SiLU, no q/k
normalisation, a gate of one sigmoid value a head computed from the normed
input, sigmoid router scores normalised over the chosen experts and scaled
by ``moe_routed_scaling_factor``, pre-norm placement.

**One chip's share is a parameter of the model, not a fork.** In the
deployment this model is written for, a group of chips shares each layer:
tensor-parallel over attention heads and vocabulary rows, expert-parallel
over the routed experts, every chip of the group seeing the same tokens.
``held_experts``, ``held_kv_heads`` and ``held_vocab`` are the ranges this
process holds (``[lo, hi)``); the defaults hold everything, which is the
whole model. A KV head comes with its query group (``H_l / num_kv_heads``
query heads), their columns of the gate and their rows of the output
projection; the router always scores all experts and chooses ``top_k`` of
them, and choices on absent experts are left out of the sum; ids outside
the held vocabulary rows embed to zero and the head gives the held
columns' logits. What every chip computes alike (norms, router, shared
expert, dense MLP) is held whole. Nothing stands in for the absent chips:
a share's outputs are the partial sums a collective would complete. So are
its gradients, and for one parameter that matters: the router is replicated
over the group, and what a share of the experts computes for it (through
the routing weights of the experts it holds) is one chip's part of a sum
the group's all-reduce completes. That part alone favours the absent
experts, which cost this chip's loss nothing. A share of the experts
therefore computes the router's gradient and withholds its update (the
weight's learning-rate factor is 0): the update belongs after the
all-reduce, which this process does not run.

**One decoder for both sparse-expert families.** What differs between
Laguna and its relatives is configuration: ``attention_gate`` off, no
shared expert (``shared_expert_intermediate_size`` 0), ``router_score``
``"softmax"`` (over all experts, renormalised over the chosen), one head
count for every layer (``models/mellum.py`` is such a configuration); a
per-head RMSNorm on q and k before the rotary (``qk_norm``) and a
selection bias on the router's choice (``router_bias``), both off here and
in Mellum (``models/lfm2.py`` turns them on and reuses
:class:`LagunaAttention` and :class:`LagunaMoE` beside a mixer of its own).

**Serving.** With ``cache`` / ``cache_pos`` / ``block_tables`` the forward
is the serving engine's: K and V go through per-request block tables into
paged pools, one pool and one table a *layer kind* (``cache_kinds``): a
full layer keeps every row of a request, a sliding-window layer the blocks
its window still covers. A call of more than one row a request is a prompt
(positions from 0, attention by the training path's kernels over the rows
of the call itself, the rows the window still covers written to the pool);
a call of one row is a decode step (K rotated at its position and written,
Q rotated and read against the pool through the table: a full layer by the
paged kernel, which copies the blocks the row's length stands on
(``ops/pallas/paged_attention.py``), a window layer by the composed read of
the table entries its window covers; the expert layer in its few-rows
form, ``ops.decoder_ops.moe_experts_decode``). With ``dtype``
``"bfloat16"`` the parameters, the pools and the matmuls' inputs are
bfloat16; the residual stream, the norms, the router, the softmax and the
logits stay float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..dygraph.layers import Layer, LayerList
from ..dygraph.tape import run_op
from ..dygraph.tensor import Tensor
from ..initializer import ConstantInitializer, NormalInitializer
from ..nn import functional as F
from ..nn.layers_common import Embedding, Linear
from ..ops.attention_ops import (block_attention_gqa, block_scatter_write,
                                 index_pool_write, index_scores_paged,
                                 sparse_decode_attention,
                                 sparse_prompt_attention)
from ..ops.decoder_ops import (DECODE_TILE_M, _moe_router, moe_experts,
                               moe_experts_decode_counts, rotary_inv_freq,
                               rotary_tables)
from ..param_attr import ParamAttr
from ..profiler import RecordEvent

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3
#: what a decode step's expert layers count on the device, summed over the
#: layers (``LagunaMoE.served``), unless the configuration names others
#: (``LagunaConfig.expert_counters``, of :data:`EXPERT_COUNTS`)
DECODE_COUNTERS = ("experts_touched", "expert_rows_max")
#: what an expert layer can count of a decode step: the held experts some
#: live row chose, the largest expert's rows, the (row, held expert) pairs
EXPERT_COUNTS = DECODE_COUNTERS + ("expert_pairs",)
#: what a decode step of a model with an indexer (``sa_config``) counts
#: after ``experts_touched``, summed over its live rows and its layers by
#: the selected read itself (``ops.attention_ops.sparse_decode_attention``):
#: the keys that were eligible for a row's selection, and the keys its
#: read kept (the chosen set, as the mask the paged walk was given)
SPARSE_COUNTERS = ("sparse_keys_live", "sparse_keys_read")
#: rotary parameters by layer kind, as Laguna-XS.2 publishes them
_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0,
        "original_max_position_embeddings": 4096, "beta_slow": 1.0,
        "beta_fast": 64.0, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000.0,
        "partial_rotary_factor": 1.0},
}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192           # the dense layers' MLP
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = _PERIOD * 10
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 39
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=lambda: dict(_ROPE))
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    init_std: float = 0.02
    # the share this process holds: [lo, hi) of the routed experts, of the
    # KV heads (each with its query group) and of the vocabulary rows;
    # None = all
    held_experts: Optional[Tuple[int, int]] = None
    held_kv_heads: Optional[Tuple[int, int]] = None
    held_vocab: Optional[Tuple[int, int]] = None
    # rows of a tile of the expert layer's sorted buffer: the grouped
    # kernels' 128, less only at toy sizes
    moe_tile_m: int = 128
    # rematerialize each block's activations in backward
    recompute: bool = False
    # what differs between the families of this decoder (the defaults are
    # Laguna's): the per-head sigmoid output gate; the router's scores
    # ("sigmoid", or "softmax" over all experts)
    attention_gate: bool = True
    router_score: str = "sigmoid"
    # a per-head RMSNorm (its gain over head_dim) on q and on k, before
    # the rotary
    qk_norm: bool = False
    # the router chooses by ``s + expert_bias`` and weighs by the chosen
    # ``s`` (``expert_bias`` [experts] float32, a parameter); what is added
    # to the chosen scores' sum before the division
    router_bias: bool = False
    router_bias_init_std: float = 0.0
    router_renorm_eps: float = 0.0
    # the choice by groups: the experts in ``router_groups`` equal groups,
    # of which the ``router_topk_groups`` best (by the sum of their 2
    # largest selection scores) stay eligible (1 group: a plain top k)
    router_groups: int = 1
    router_topk_groups: int = 1
    # what a decode step's expert layers count, by name (EXPERT_COUNTS)
    expert_counters: Tuple[str, ...] = DECODE_COUNTERS
    # the parameters' dtype (and the served pools')
    dtype: str = "float32"
    # the std of the embedding's rows and of the routers' weights where a
    # random-weight model needs another than init_std (None: init_std):
    # they set the share of the residual stream the layers carry and how
    # unevenly a token's chosen experts weigh
    embed_init_std: Optional[float] = None
    router_init_std: Optional[float] = None
    # served prompts: rows of one pass of the expert layer (0: the whole
    # call), so that its grouped products have one shape whatever the
    # prompt's bucket and its buffers stay bounded
    moe_chunk_rows: int = 0
    # learned sparse attention: None, or the published ``sa_config``
    # (``indexer_num_heads`` of ``indexer_head_dim`` over
    # ``indexer_num_kv_heads`` = 1 key head, ``topk``): every layer gains
    # an indexer and every query reads only the ``topk`` keys it picks
    sa_config: Optional[dict] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        for key in ("layer_types", "num_attention_heads_per_layer",
                    "mlp_layer_types"):
            seq = tuple(getattr(self, key))
            if len(seq) < n:
                raise ValueError(f"{key} has {len(seq)} entries for "
                                 f"{n} layers")
            setattr(self, key, seq[:n])
        for h in self.num_attention_heads_per_layer:
            if h % self.num_key_value_heads:
                raise ValueError(f"{h} query heads over "
                                 f"{self.num_key_value_heads} KV heads")
        if self.sa_config is not None:
            if int(self.sa_config.get("indexer_num_kv_heads", 1)) != 1:
                raise ValueError("the indexer has one key head; sa_config "
                                 f"says {self.sa_config}")
            if "sliding_attention" in self.layer_types:
                raise ValueError("an indexer picks among every key of a "
                                 "request: no layer of a model with "
                                 "sa_config has a window")

    @property
    def indexer(self):
        """None, or the indexer's (heads, head size, keys a query
        reads)."""
        sa = self.sa_config
        return None if sa is None else (
            int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))

    @property
    def read_counters(self):
        """What a decode row's read of its cache counts, by name: the
        columns of the third result of the attention layer's served call
        (:meth:`LagunaAttention._served`)."""
        return SPARSE_COUNTERS if self.sa_config is not None else ()

    @property
    def decode_counters(self):
        """What a decode step of this model counts on the device, in the
        order of :meth:`LagunaModel.served`'s vector: the expert layers'
        counts (the first of them where an indexer counts beside it), then
        the reads'."""
        if self.sa_config is not None:
            return self.expert_counters[:1] + SPARSE_COUNTERS
        return self.expert_counters + self.read_counters

    def attention(self, layer: int):
        """The attention layer of ``layer`` (a family with another kind of
        attention gives its own, with :class:`LagunaAttention`'s calls)."""
        return LagunaAttention(self, layer)

    @staticmethod
    def _range(held, whole):
        lo, hi = (0, whole) if held is None else held
        if not 0 <= lo < hi <= whole:
            raise ValueError(f"held range {held} outside [0, {whole})")
        return int(lo), int(hi)

    @property
    def experts(self):
        return self._range(self.held_experts, self.num_experts)

    @property
    def kv_heads(self):
        return self._range(self.held_kv_heads, self.num_key_value_heads)

    @property
    def vocab(self):
        return self._range(self.held_vocab, self.vocab_size)

    @property
    def kv_pack(self) -> int:
        """KV heads that share one row of the served pool, side by side on
        its last axis (1: a head a row). On the chip an array's last axis
        is laid out in whole tiles of 128 lanes, so a pool of rows
        narrower than that takes up to twice its bytes and the paged
        kernel cannot copy a block of it: where no layer has a window, as
        many heads as fill the 128 (and divide the held heads) go in a row,
        ``[blocks, kv / pack, block, pack x d]``. Nothing sets this: it
        follows from ``head_dim``, and a head of 128 or more keeps a row
        to itself. It is the pool's layout only: a full layer's decode
        read is the paged kernel's either way
        (:meth:`LagunaAttention._paged_read`, which spreads a packed
        pool's query heads over their lanes), a window layer's the
        composed read of the entries its window covers."""
        if "sliding_attention" in self.layer_types or self.sa_config:
            # (the selected read's mask is a value a key, not a lane)
            return 1
        kv = self.kv_heads[1] - self.kv_heads[0]
        return max(p for p in range(1, kv + 1)
                   if kv % p == 0 and p * self.head_dim <= 128)

    def query_heads(self, layer: int) -> int:
        """Query heads of ``layer`` this share holds."""
        lo, hi = self.kv_heads
        return (hi - lo) * (self.num_attention_heads_per_layer[layer]
                            // self.num_key_value_heads)

    def num_params(self) -> int:
        """Parameters this share holds (all of them for the whole model)."""
        h, d = self.hidden_size, self.head_dim
        kv = self.kv_heads[1] - self.kv_heads[0]
        e = self.experts[1] - self.experts[0]
        n = 2 * (self.vocab[1] - self.vocab[0]) * h + h
        for i in range(self.num_hidden_layers):
            q = self.query_heads(i)
            n += h * (q + 2 * kv) * d + q * d * h + 2 * h
            if self.attention_gate:
                n += h * q
            if self.qk_norm:
                n += 2 * d
            if self.indexer:
                hi, di, _ = self.indexer
                n += h * (hi * di + di + hi) + 2 * di
            if self.mlp_layer_types[i] == "dense":
                n += 3 * h * self.intermediate_size
            else:
                n += h * self.num_experts \
                    + 3 * h * self.moe_intermediate_size * e \
                    + 3 * h * self.shared_expert_intermediate_size
        return n

    def cache_kinds(self):
        """The layer kinds a paged cache keeps apart, full layers first:
        ``(name, layers, window)`` with ``window`` 0 for a kind that keeps
        every row of a request."""
        out = []
        for name, window in (("full_attention", 0),
                             ("sliding_attention", self.sliding_window)):
            layers = tuple(i for i, t in enumerate(self.layer_types)
                           if t == name)
            if layers:
                out.append((name, layers, window))
        return out


def _w(std):
    return ParamAttr(initializer=NormalInitializer(0.0, std))


def _linear(n_in, n_out, std, dtype="float32"):
    return Linear(n_in, n_out, weight_attr=_w(std), bias_attr=False,
                  dtype=dtype)


def no_counts(names=DECODE_COUNTERS):
    """What a layer with no experts, or a prompt's pass, counts."""
    return jnp.zeros((len(names),), jnp.int32)


def _rotate_half(x, rows, theta: float):
    """``x`` float32 [b, s, .., d] rotated (rotate-half over all ``d``) at
    the positions ``rows`` [b, s]."""
    d = x.shape[-1]
    inv, _ = rotary_inv_freq(d, theta)
    ang = rows.astype(jnp.float32)[..., None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _matmul_in(x, dtype: str):
    """``x`` as a matmul's input: in the parameters' dtype."""
    return x if str(x.dtype) == dtype else x.astype(dtype)


class RMSNorm(Layer):
    def __init__(self, size: int, eps: float, dtype="float32"):
        super().__init__(dtype=dtype)
        self.eps = eps
        self.weight = self.create_parameter(
            [size], attr=ParamAttr(initializer=ConstantInitializer(1.0)))

    def forward(self, x):
        return run_op("rms_norm", {"X": [x], "Scale": [self.weight]},
                      {"epsilon": self.eps})["Out"][0]


class SwiGLU(Layer):
    """``(silu(x W1) * (x W3)) W2``; W1 and W3 side by side in one
    ``gate_up`` matrix (gate first)."""

    def __init__(self, hidden: int, width: int, std: float, out_std: float,
                 dtype="float32"):
        super().__init__()
        self.width = width
        self.gate_up = _linear(hidden, 2 * width, std, dtype)
        self.down = _linear(width, hidden, out_std, dtype)

    def forward(self, x):
        gu = self.gate_up(x)
        return self.down(F.silu(gu[:, :, :self.width])
                         * gu[:, :, self.width:])


class LagunaAttention(Layer):
    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.cfg = cfg
        self.kind = cfg.layer_types[layer]
        self.kv = cfg.kv_heads[1] - cfg.kv_heads[0]
        self.q = cfg.query_heads(layer)
        h, d = cfg.hidden_size, cfg.head_dim
        out_std = cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers)
        # columns: the held query heads, then the held K heads, then V
        self.qkv_proj = _linear(h, (self.q + 2 * self.kv) * d, cfg.init_std,
                                cfg.dtype)
        if cfg.attention_gate:
            self.g_proj = _linear(h, self.q, cfg.init_std, cfg.dtype)
        self.o_proj = _linear(self.q * d, h, out_std, cfg.dtype)
        if cfg.qk_norm:
            self.q_norm = RMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
            self.k_norm = RMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
        rope = cfg.rope_parameters[self.kind]
        self.rot_dim = int(d * float(rope.get("partial_rotary_factor", 1.0)))
        self.rope = rope
        self.window = cfg.sliding_window \
            if self.kind == "sliding_attention" else 0
        self._tables = {}
        if cfg.indexer:
            # the indexer: 16 small query heads over ONE key head, a weight
            # a head; its key passes a LayerNorm (with bias)
            hi, di, _ = cfg.indexer
            self.index_q = _linear(h, hi * di, cfg.init_std, cfg.dtype)
            self.index_k = _linear(h, di, cfg.init_std, cfg.dtype)
            self.index_w = _linear(h, hi, cfg.init_std, cfg.dtype)
            self.index_k_norm_weight = self.create_parameter(
                [di], attr=ParamAttr(initializer=ConstantInitializer(1.0)))
            self.index_k_norm_bias = self.create_parameter(
                [di], attr=ParamAttr(initializer=ConstantInitializer(0.0)))

    def _rotary(self, seq: int):
        if seq not in self._tables:
            yarn = self.rope if self.rope.get("rope_type") == "yarn" else None
            self._tables[seq] = rotary_tables(
                seq, self.rot_dim, float(self.rope["rope_theta"]), yarn)
        return [Tensor(jnp.asarray(t), stop_gradient=True)
                for t in self._tables[seq]]

    def _heads(self, qkv, cos, sin):
        """The projection's columns as q [b, heads, s, d] and k, v
        [b, kv, s, d]; q and k normed a head (``qk_norm``) and rotated."""
        d = self.cfg.head_dim
        b, s, _ = qkv.shape

        def heads(lo, n):
            x = qkv[:, :, lo * d:(lo + n) * d].reshape([b, s, n, d])
            return x.transpose([0, 2, 1, 3])

        def rotated(x, norm):
            if norm is not None:
                x = norm(x)
            return run_op("rotary_embedding",
                          {"X": [x], "Cos": [cos], "Sin": [sin]},
                          {})["Out"][0]
        normed = self.cfg.qk_norm
        return (rotated(heads(0, self.q), self.q_norm if normed else None),
                rotated(heads(self.q, self.kv),
                        self.k_norm if normed else None),
                heads(self.q + self.kv, self.kv))

    def _attend(self, q, k, v):
        """Causal (and windowed) attention of a call's rows over
        themselves -> [b, s, heads, d]."""
        o = run_op("fused_attention_qkv", {"Q": [q], "K": [k], "V": [v]},
                   {"causal": True, "window": self.window,
                    "kernel_tag": "win" if self.window else "full"}
                   )["Out"][0]
        return o.transpose([0, 2, 1, 3])

    def _index(self, u, rows):
        """The indexer's view of the rows ``u`` [b, s, h] (the layer's
        normed input) at positions ``rows`` [b, s] -> (its queries
        [b, s, heads, di] and its keys [b, s, di], both rotated over all
        ``di`` values and in the parameters' dtype, and a weight a head
        float32 [b, s, heads], scaled by ``1 / sqrt(heads x di)``).
        Projections accumulate in float32; the key's LayerNorm and the
        rotary are float32."""
        hi, di, _ = self.cfg.indexer
        u = u.value if isinstance(u, Tensor) else u
        b, s, _ = u.shape
        theta = float(self.rope["rope_theta"])

        def proj(layer):
            w = layer.weight.value
            return jnp.einsum("bsh,hn->bsn", u.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)
        kx = proj(self.index_k)
        mean = jnp.mean(kx, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(kx - mean), axis=-1, keepdims=True)
        kx = (kx - mean) * jax.lax.rsqrt(var + self.cfg.rms_norm_eps) \
            * self.index_k_norm_weight.value.astype(jnp.float32) \
            + self.index_k_norm_bias.value.astype(jnp.float32)
        dt = self.index_q.weight.value.dtype
        q = _rotate_half(proj(self.index_q).reshape(b, s, hi, di), rows,
                         theta)
        return (q.astype(dt), proj(self.index_w) / math.sqrt(hi * di),
                _rotate_half(kx, rows, theta).astype(dt))

    def forward(self, h, cache=None, cache_pos=None, block_tables=None,
                ctx_len=None):
        cfg, d = self.cfg, self.cfg.head_dim
        b, s, _ = h.shape
        if cache is not None:
            return self._served(h, cache, cache_pos, block_tables, ctx_len)
        if s > cfg.max_position_embeddings:
            raise ValueError(f"sequence length {s} exceeds "
                             f"max_position_embeddings="
                             f"{cfg.max_position_embeddings}")
        cos, sin = self._rotary(s)
        if cfg.indexer:
            # every query reads the keys its indexer picks. No gradient
            # passes the selection: a model with an indexer is served, not
            # trained (``LagunaForCausalLM.forward`` refuses labels)
            q, k, v = self._heads(self.qkv_proj(h), cos, sin)
            rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            o, _ = sparse_prompt_attention(
                q.value, k.value, v.value, *self._index(h, rows),
                cfg.indexer[2])
            o = Tensor(o, stop_gradient=True).transpose([0, 2, 1, 3])
        else:
            o = self._attend(*self._heads(self.qkv_proj(h), cos, sin))
        if cfg.attention_gate:
            o = self._gate(h, o)
        return self.o_proj(o.reshape([b, s, self.q * d]))

    def _gate(self, h, o):
        """Scale every head's output ``o`` [b, s, heads, d] by its sigmoid
        gate, one value a head from the normed input."""
        b, s, _ = h.shape
        return o * F.sigmoid(self.g_proj(h)).reshape([b, s, self.q, 1])

    def _served(self, h, cache, cache_pos, tables, ctx_len):
        """The serving engine's call: ``cache`` this layer's (k, v) pool
        pair ``[blocks, kv, block_size, d]`` (with an indexer a third
        array after them, its keys' pool ``[blocks, di, block_size]``),
        ``tables`` [b, T] its kind's
        block tables, ``cache_pos`` [b] each request's first row of this
        call, ``ctx_len`` [b] its rows once the call is done. -> (output,
        the pools with the call's K and V written, and what a decode row's
        selected read counted, int32 [b, 2]: the keys eligible and the
        keys read; None for a prompt and for a layer without an indexer)."""
        cfg, d = self.cfg, self.cfg.head_dim
        b, s, _ = h.shape
        kp, vp = cache[0].value, cache[1].value
        bs = kp.shape[2]
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        rows = jnp.clip(pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None],
                        0, cfg.max_position_embeddings - 1)
        # cos and sin of the rows' own positions, computed in the program:
        # a table of max_position_embeddings rows gathered here is a
        # constant of 4 MB a layer and a table, and made every served
        # executable 46 MB (PERF.md section 6)
        inv, att = rotary_inv_freq(
            self.rot_dim, float(self.rope["rope_theta"]),
            self.rope if self.rope.get("rope_type") == "yarn" else None)
        ang = rows.astype(jnp.float32)[:, None, :, None] \
            * jnp.asarray(inv, jnp.float32)
        cos, sin = (Tensor(f(ang) * att, stop_gradient=True)
                    for f in (jnp.cos, jnp.sin))
        q, k, v = self._heads(self.qkv_proj(h), cos, sin)
        pack = cfg.kv_pack

        def rows_of(x):
            """[b, kv, s, d] -> the pool's rows [b, kv / pack, s, pack x
            d]: ``pack`` neighbouring heads side by side."""
            if pack == 1:
                return x
            return x.reshape(b, self.kv // pack, pack, s, d).transpose(
                0, 1, 3, 2, 4).reshape(b, self.kv // pack, s, pack * d)
        kw, vw, wpos = rows_of(k.value), rows_of(v.value), pos
        keep = (-(-self.window // bs) + 1) * bs
        if self.window and s > keep:
            # a prompt longer than the window keeps the rows its last
            # blocks hold (BlockKVCache's window kind has no block for the
            # ones behind): one slice of `keep` rows from the first block
            # the window of the next position reaches
            first = jnp.maximum(ctx_len - self.window + 1, 0) // bs * bs
            start = jnp.clip(first - pos, 0, s - keep)

            def tail(a):
                return jax.vmap(lambda x, st: jax.lax.dynamic_slice_in_dim(
                    x, st, keep, axis=1))(a, start)
            kw, vw, wpos = tail(kw), tail(vw), pos + start
        kp = block_scatter_write(kp, kw, wpos, tables)
        vp = block_scatter_write(vp, vw, wpos, tables)
        pools, reads = (kp, vp), None
        if cfg.indexer:
            # the indexer's keys go to their own pool under the same table;
            # a prompt's rows pick among themselves, a decode row among
            # the rows its table holds up to its own
            qi, wi, ki = self._index(h, rows)
            ip = index_pool_write(cache[2].value, ki, pos, tables)
            pools = (kp, vp, ip)
            if s > 1:
                # the call's own rows, as data: the read stops at them
                read, _ = sparse_prompt_attention(
                    q.value, k.value, v.value, qi, wi, ki, cfg.indexer[2],
                    live=jnp.max(ctx_len - pos))
            else:
                read, reads = sparse_decode_attention(
                    q.value, kp, vp, tables, pos,
                    index_scores_paged(qi[:, 0], wi[:, 0], ip, tables),
                    cfg.indexer[2])
                read = read.astype(q.dtype)
            o = Tensor(read, stop_gradient=True).transpose([0, 2, 1, 3])
        elif s > 1:
            o = self._attend(q, k, v)
        else:
            # a window layer gathers the table entries its window covers:
            # the kernel's walk starts at a row's first block
            read = block_attention_gqa(
                q.value, kp, vp, tables, pos, self.window).astype(q.dtype) \
                if self.window \
                else self._paged_read(q.value, kp, vp, tables, pos)
            o = Tensor(read, stop_gradient=True).transpose([0, 2, 1, 3])
        if cfg.attention_gate:
            o = self._gate(h, o)
        return (self.o_proj(o.reshape([b, s, self.q * d])),
                tuple(Tensor(p, stop_gradient=True) for p in pools), reads)

    def _paged_read(self, q, kp, vp, tables, pos):
        """One decode row a request through ``paged_decode_attn``: ``q``
        [b, hq, 1, d] -> [b, hq, 1, d]. Where the pool packs ``pack`` KV
        heads in a row, a query head's ``d`` values go to its KV head's
        lanes of the row (zeros elsewhere, which add nothing to its
        logits) and its output is read back from the same lanes."""
        from ..ops.pallas.paged_attention import paged_attention
        cfg, d = self.cfg, self.cfg.head_dim
        pack = cfg.kv_pack
        scale = 1.0 / math.sqrt(d)
        if pack == 1:
            return paged_attention(q, kp, vp, tables, pos, scale=scale)
        b, hq = q.shape[0], q.shape[1]
        lanes = jax.nn.one_hot(
            (jnp.arange(hq) // (hq // self.kv)) % pack, pack,
            dtype=q.dtype)[None, :, None, :, None]      # [1, hq, 1, pack, 1]
        wide = (q[:, :, :, None, :] * lanes).reshape(b, hq, 1, pack * d)
        o = paged_attention(wide, kp, vp, tables, pos, scale=scale)
        return jnp.sum(o.reshape(b, hq, 1, pack, d) * lanes, axis=3)


class LagunaMoE(Layer):
    """Router over all experts, the held experts' grouped products, the
    shared expert (where the configuration has one). ``forward`` ->
    (output, stats of ``moe_experts``)."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        lo, hi = cfg.experts
        out_std = cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers)
        # a share's router gradient is one chip's part of the group's sum:
        # computed, and its update withheld (see the module's docstring)
        whole = (lo, hi) == (0, cfg.num_experts)
        self.router = Linear(
            h, cfg.num_experts, bias_attr=False, dtype=cfg.dtype,
            weight_attr=ParamAttr(
                initializer=NormalInitializer(
                    0.0, cfg.init_std if cfg.router_init_std is None
                    else cfg.router_init_std),
                learning_rate=1.0 if whole else 0.0))
        # stacked over the held experts; gate and up side by side
        self.experts_gate_up = self.create_parameter(
            [hi - lo, h, 2 * f], attr=_w(cfg.init_std), dtype=cfg.dtype)
        self.experts_down = self.create_parameter(
            [hi - lo, f, h], attr=_w(out_std), dtype=cfg.dtype)
        if cfg.router_bias:
            # a buffer of the load balancer's in the published model: it
            # takes no gradient (the op's ``Bias`` slot has none)
            self.expert_bias = self.create_parameter(
                [cfg.num_experts], attr=_w(cfg.router_bias_init_std),
                dtype="float32")
        if cfg.shared_expert_intermediate_size:
            self.shared = SwiGLU(h, cfg.shared_expert_intermediate_size,
                                 cfg.init_std, out_std, cfg.dtype)

    def _router_attrs(self):
        cfg = self.cfg
        attrs = {"top_k": cfg.num_experts_per_tok,
                 "scale": cfg.moe_routed_scaling_factor,
                 "score": cfg.router_score}
        if cfg.router_renorm_eps:
            attrs["renorm_eps"] = cfg.router_renorm_eps
        if cfg.router_groups > 1:
            attrs.update(n_group=cfg.router_groups,
                         topk_group=cfg.router_topk_groups)
        return attrs

    def forward(self, u):
        cfg = self.cfg
        ins = {"X": [u], "W": [self.router.weight]}
        if cfg.router_bias:
            ins["Bias"] = [self.expert_bias]
        r = run_op("moe_router", ins, self._router_attrs())
        e = run_op("moe_experts",
                   {"X": [u], "TopkIdx": r["TopkIdx"],
                    "TopkWeight": r["TopkWeight"],
                    "WGateUp": [self.experts_gate_up],
                    "WDown": [self.experts_down]},
                   {"expert_lo": cfg.experts[0],
                    "num_experts": cfg.num_experts,
                    "tile_m": cfg.moe_tile_m})
        out = e["Out"][0]
        if cfg.shared_expert_intermediate_size:
            out = out + self._shared(u)
        return out, e["Stats"][0]

    def _shared(self, u):
        """The shared expert's term for ``u`` (a Tensor in the parameters'
        dtype); a family whose shared expert is gated scales it here."""
        return self.shared(u)

    def served(self, u, live=None):
        """The serving engine's call, on arrays: ``u`` float32 [b, s, h]
        (the norm's output; the router reads it as it is, the experts in
        the parameters' dtype) -> (output float32 [b, s, h], int32
        [len(cfg.expert_counters)]: those of :data:`EXPERT_COUNTS` the
        configuration names, counted where ``s`` is 1). One row a request (``live`` [b]: which
        are requests at all) takes the few-rows form over the experts this
        share holds; a prompt takes the training path's, ``moe_chunk_rows``
        rows a pass. Choices on experts that are not held add nothing."""
        cfg = self.cfg
        b, s, h = u.shape
        dt = jnp.dtype(cfg.dtype)
        w13, w2 = self.experts_gate_up.value, self.experts_down.value
        router = self.router.weight.value

        ins = {"W": [router]}
        if cfg.router_bias:
            ins["Bias"] = [self.expert_bias.value]

        def route(x):
            r = _moe_router(None, dict(ins, X=[x]), self._router_attrs())
            return r["TopkIdx"][0], r["TopkWeight"][0].astype(dt)

        flat = u.reshape(b * s, h)
        if s == 1:
            idx, weight = route(flat)
            lo, hi = cfg.experts
            held = None
            if (lo, hi) != (0, cfg.num_experts):
                # a share of the experts: only the choices among them count
                idx = idx - lo
                held = jnp.logical_and(idx >= 0, idx < hi - lo)
            out, rows = moe_experts_decode_counts(
                flat.astype(dt), weight, idx, w13, w2, live,
                min(cfg.moe_tile_m, DECODE_TILE_M), held=held)
            counts = {"experts_touched": jnp.sum(rows > 0),
                      "expert_rows_max": jnp.max(rows),
                      "expert_pairs": jnp.sum(rows)}
            counted = jnp.stack([counts[n] for n in cfg.expert_counters]
                                ).astype(jnp.int32)
        else:
            def one(x):
                idx, weight = route(x)
                return moe_experts(x.astype(dt), weight, idx, w13, w2,
                                   cfg.experts[0], cfg.num_experts,
                                   cfg.moe_tile_m)[0].astype(jnp.float32)
            rows, chunk = b * s, cfg.moe_chunk_rows
            if chunk and rows > chunk and rows % chunk == 0:
                out = jax.lax.map(one, flat.reshape(-1, chunk, h))
            else:
                out = one(flat)
            counted = no_counts(cfg.expert_counters)
        out = out.reshape(b, s, h).astype(jnp.float32)
        if cfg.shared_expert_intermediate_size:
            sh = self._shared(Tensor(u.astype(dt), stop_gradient=True))
            out = out + sh.value.astype(jnp.float32)
        return out, counted


class LagunaBlock(Layer):
    """Pre-norm block. A sparse block returns ``(x, stats)``."""

    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.cfg = cfg
        self.sparse = cfg.mlp_layer_types[layer] == "sparse"
        self.attn_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                 cfg.dtype)
        self.attn = cfg.attention(layer)
        self.mlp_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                cfg.dtype)
        if self.sparse:
            self.moe = LagunaMoE(cfg)
        else:
            self.mlp = SwiGLU(
                cfg.hidden_size, cfg.intermediate_size, cfg.init_std,
                cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers),
                cfg.dtype)

    def forward(self, x):
        x = x + self.attn(self.attn_norm(x))
        u = self.mlp_norm(x)
        if not self.sparse:
            return x + self.mlp(u)
        y, stats = self.moe(u)
        return x + y, stats

    def served(self, x, cache, cache_pos, tables, ctx_len, live):
        """The serving engine's call: ``x`` the float32 residual stream
        -> (x, this layer's pools, the expert layer's counts, the selected
        read's counts a row or None: :meth:`LagunaAttention._served`)."""
        dt = self.cfg.dtype
        a, cache, reads = self.attn(_matmul_in(self.attn_norm(x), dt), cache,
                                    cache_pos, tables, ctx_len)
        x = x + a.astype("float32")
        u = self.mlp_norm(x)
        if not self.sparse:
            y = self.mlp(_matmul_in(u, dt)).astype("float32")
            return x + y, cache, no_counts(self.cfg.expert_counters), reads
        y, counted = self.moe.served(u.value, live)
        return x + Tensor(y, stop_gradient=True), cache, counted, reads


class LagunaModel(Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        lo, hi = cfg.vocab
        self.embed = Embedding(
            hi - lo, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_w(cfg.init_std if cfg.embed_init_std is None
                           else cfg.embed_init_std))
        self.layers = LayerList([LagunaBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        self.sparse_layers = [i for i, t in enumerate(cfg.mlp_layer_types)
                              if t == "sparse"]
        # the expert layers' counters of the last forward, one row a sparse
        # layer (a buffer, so a compiled step carries them out as state)
        self.register_buffer("moe_stats_last", Tensor(
            jnp.zeros((max(len(self.sparse_layers), 1), 5), jnp.float32),
            stop_gradient=True), persistable=False)

    def _embed(self, input_ids):
        cfg = self.cfg
        lo, hi = cfg.vocab
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if (lo, hi) == (0, cfg.vocab_size):
            return self.embed(Tensor(ids, stop_gradient=True))
        held = jnp.logical_and(ids >= lo, ids < hi)
        x = self.embed(Tensor(jnp.where(held, ids - lo, 0),
                              stop_gradient=True))
        return x * Tensor(held[..., None].astype(x.value.dtype),
                          stop_gradient=True)

    def forward(self, input_ids, collect=None):
        cfg = self.cfg
        x = self._embed(input_ids)
        stats = []
        for blk in self.layers:
            if cfg.recompute:
                from ..distributed.fleet.utils.recompute import recompute
                out = recompute(blk, x)
            else:
                out = blk(x)
            if blk.sparse:
                x, st = out
                stats.append(st.value)
            else:
                x = out
            if collect is not None:
                collect.append(x)
        if stats:
            self.moe_stats_last.value = jnp.stack(stats)
        return self.norm(x)

    def served(self, input_ids, cache, cache_pos, block_tables,
               last=None, collect=None):
        """The serving engine's call -> (the final norm's output float32
        [b, s, h], the caches, the expert layers' counts summed over the
        layers: ``cfg.expert_counters``; where the reads count
        too, ``cfg.decode_counters``: the reads' counts of the step's live
        rows after the expert layers'). ``cache``: one (k, v) pool pair a
        layer (with an indexer (k, v, its keys); whatever the layer's
        attention keeps).
        ``block_tables``: one
        table a layer kind in ``cfg.cache_kinds()``'s order (the table
        itself where there is one kind). ``last`` [b]: each prompt's last
        row in this call (None: every row is one)."""
        cfg = self.cfg
        kinds = cfg.cache_kinds()
        tables = block_tables if isinstance(block_tables, (tuple, list)) \
            else (block_tables,)
        if len(tables) != len(kinds):
            raise ValueError(f"{len(tables)} block tables for the layer "
                             f"kinds {[k[0] for k in kinds]}")
        table_of = {name: jnp.asarray(t, jnp.int32)
                    for (name, _, _), t in zip(kinds, tables)}
        x = self._embed(input_ids).astype("float32")
        b, s = x.shape[0], x.shape[1]
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
        ctx_len = pos + (s if last is None
                         else jnp.asarray(last, jnp.int32) + 1)
        # a slot with no request has no row yet: it routes nowhere
        live = pos > 0 if s == 1 else None
        caches, touched = [], no_counts(cfg.expert_counters)
        counted = cfg.read_counters
        reads = jnp.zeros((b, len(counted)), jnp.int32)
        for i, blk in enumerate(self.layers):
            x, c, t, r = blk.served(x, cache[i], pos,
                                    table_of[cfg.layer_types[i]], ctx_len,
                                    live)
            caches.append(c)
            touched = touched + t
            if r is not None:
                reads = reads + r
            if collect is not None:
                collect.append(x)
        if counted:
            # what the reads counted, of a decode step's live rows (a
            # prompt counts nothing; a step's sum is ~1e6, far inside
            # int32)
            if live is not None:
                reads = jnp.where(live[:, None], reads, 0)
            touched = jnp.concatenate(
                [touched[:len(cfg.decode_counters) - len(counted)],
                 jnp.sum(reads, axis=0)])
        return self.norm(x), caches, touched


class LagunaForCausalLM(Layer):
    """The model with its untied head. ``forward(ids)`` -> logits over the
    held vocabulary rows; with ``labels`` the mean next-token
    cross-entropy over them (labels outside the held rows are ignored);
    with ``cache`` the serving engine's call -> (float32 logits, caches)."""

    #: the name the build's span carries
    span_prefix = "laguna"

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        with RecordEvent(f"{self.span_prefix}.build",
                         {"layers": cfg.num_hidden_layers,
                          "params": cfg.num_params()}):
            self.cfg = cfg
            self.model = LagunaModel(cfg)
            lo, hi = cfg.vocab
            self.lm_head = _linear(cfg.hidden_size, hi - lo, cfg.init_std,
                                   cfg.dtype)

    def forward(self, input_ids, labels=None, collect=None, cache=None,
                cache_pos=None, block_tables=None, lora=None, last=None,
                counters=None):
        if labels is not None and self.cfg.indexer:
            raise ValueError(
                f"{type(self).__name__} with an indexer (sa_config) is "
                f"served, not trained: no gradient passes the selection, so "
                f"a loss would train neither q, k, v nor the indexer")
        if cache is not None:
            if lora is not None:
                raise ValueError(f"{type(self).__name__} has no LoRA path")
            logits, caches, touched = self._served(
                input_ids, cache, cache_pos, block_tables, last, collect)
            if counters is None:
                return logits, caches
            # the decode step's device counters (``serving_spec``'s names:
            # a prefix of ``cfg.decode_counters``)
            return logits, caches, counters \
                + touched[:counters.shape[0]].astype(counters.dtype)
        logits = self.lm_head(self.model(input_ids, collect))
        if labels is None:
            return logits
        lo, hi = self.cfg.vocab
        lab = labels.value if isinstance(labels, Tensor) \
            else jnp.asarray(labels)
        if (lo, hi) != (0, self.cfg.vocab_size):
            lab = jnp.where(jnp.logical_and(lab >= lo, lab < hi),
                            lab - lo, -100)
        return F.cross_entropy(
            logits.reshape([-1, hi - lo]),
            Tensor(lab.reshape(-1, 1), stop_gradient=True),
            ignore_index=-100)

    def _served(self, input_ids, cache, cache_pos, block_tables, last,
                collect):
        """-> (logits float32 [b, s, vocab], or [b, 1, vocab] of each
        prompt's ``last`` row: the head never multiplies a bucket's
        padding; the caches; the expert layers' counts)."""
        h, caches, touched = self.model.served(
            input_ids, cache, cache_pos, block_tables, last, collect)
        h = h.value
        if last is not None:
            h = jnp.take_along_axis(
                h, jnp.asarray(last, jnp.int32)[:, None, None], axis=1)
        w = self.lm_head.weight.value
        logits = jnp.einsum("bsh,hv->bsv", h.astype(w.dtype), w,
                            preferred_element_type=jnp.float32)
        return Tensor(logits, stop_gradient=True), caches, touched

    def moe_stats(self) -> dict:
        """The expert layers' counters of the last step, read from the
        device now (the step itself never waits for them) and published as
        ``STAT_moe_*``: per sparse layer the (token, held expert) pairs,
        the largest expert load over the mean in thousandths, the dropped
        pairs (always 0), whether the fast buffer held the step and the
        rows its combine gathered over ``tokens x top_k`` (1.0 for the
        k-slot form; less where the prefix form ran)."""
        import numpy as np
        from .. import monitor
        rows = np.asarray(self.model.moe_stats_last.value)
        out = {}
        for layer, row in zip(self.model.sparse_layers, rows):
            out[layer] = {"assignments": int(row[0]),
                          "max_load_over_mean": float(row[1]),
                          "dropped_pairs": int(row[2]),
                          "fast_path": bool(row[3]),
                          "combine_rows_share": float(row[4])}
            monitor.stat_set(f"STAT_moe_assignments_l{layer}", int(row[0]))
            monitor.stat_set(f"STAT_moe_max_load_permille_l{layer}",
                             int(round(1000 * float(row[1]))))
            monitor.stat_set(f"STAT_moe_dropped_pairs_l{layer}",
                             int(row[2]))
            monitor.stat_set(f"STAT_moe_combine_rows_permille_l{layer}",
                             int(round(1000 * float(row[4]))))
        monitor.stat_set("STAT_moe_dropped_pairs",
                         int(rows[:len(out), 2].sum()))
        return out


LAGUNA_CONFIGS = {
    "laguna-xs2": LagunaConfig(),
    # a toy of the same layer kinds (full/window, dense/sparse, grouped KV,
    # a head count that differs by layer) for tests and CPU rehearsals
    "laguna-tiny": LagunaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_key_value_heads=2, head_dim=16,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention"),
        num_attention_heads_per_layer=(4, 8, 8),
        mlp_layer_types=("dense", "sparse", "sparse"),
        sliding_window=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        max_position_embeddings=128, moe_tile_m=8),
}
