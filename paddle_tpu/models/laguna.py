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
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

from ..dygraph.layers import Layer, LayerList
from ..dygraph.tape import run_op
from ..dygraph.tensor import Tensor
from ..initializer import ConstantInitializer, NormalInitializer
from ..nn import functional as F
from ..nn.layers_common import Embedding, Linear
from ..ops.decoder_ops import rotary_tables
from ..param_attr import ParamAttr
from ..profiler import RecordEvent

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3
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
            n += h * (q + 2 * kv) * d + h * q + q * d * h + 2 * h
            if self.mlp_layer_types[i] == "dense":
                n += 3 * h * self.intermediate_size
            else:
                n += h * self.num_experts \
                    + 3 * h * self.moe_intermediate_size * e \
                    + 3 * h * self.shared_expert_intermediate_size
        return n


def _w(std):
    return ParamAttr(initializer=NormalInitializer(0.0, std))


def _linear(n_in, n_out, std):
    return Linear(n_in, n_out, weight_attr=_w(std), bias_attr=False)


class RMSNorm(Layer):
    def __init__(self, size: int, eps: float):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter(
            [size], attr=ParamAttr(initializer=ConstantInitializer(1.0)))

    def forward(self, x):
        return run_op("rms_norm", {"X": [x], "Scale": [self.weight]},
                      {"epsilon": self.eps})["Out"][0]


class SwiGLU(Layer):
    """``(silu(x W1) * (x W3)) W2``; W1 and W3 side by side in one
    ``gate_up`` matrix (gate first)."""

    def __init__(self, hidden: int, width: int, std: float, out_std: float):
        super().__init__()
        self.width = width
        self.gate_up = _linear(hidden, 2 * width, std)
        self.down = _linear(width, hidden, out_std)

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
        self.qkv_proj = _linear(h, (self.q + 2 * self.kv) * d, cfg.init_std)
        self.g_proj = _linear(h, self.q, cfg.init_std)
        self.o_proj = _linear(self.q * d, h, out_std)
        rope = cfg.rope_parameters[self.kind]
        self.rot_dim = int(d * float(rope.get("partial_rotary_factor", 1.0)))
        self.rope = rope
        self._tables = {}

    def _rotary(self, seq: int):
        if seq not in self._tables:
            yarn = self.rope if self.rope.get("rope_type") == "yarn" else None
            self._tables[seq] = rotary_tables(
                seq, self.rot_dim, float(self.rope["rope_theta"]), yarn)
        return [Tensor(jnp.asarray(t), stop_gradient=True)
                for t in self._tables[seq]]

    def forward(self, h):
        cfg, d = self.cfg, self.cfg.head_dim
        b, s, _ = h.shape
        if s > cfg.max_position_embeddings:
            raise ValueError(f"sequence length {s} exceeds "
                             f"max_position_embeddings="
                             f"{cfg.max_position_embeddings}")
        qkv = self.qkv_proj(h)
        cos, sin = self._rotary(s)

        def heads(lo, n, rotate):
            x = qkv[:, :, lo * d:(lo + n) * d].reshape([b, s, n, d])
            x = x.transpose([0, 2, 1, 3])
            if rotate:
                x = run_op("rotary_embedding",
                           {"X": [x], "Cos": [cos], "Sin": [sin]},
                           {})["Out"][0]
            return x
        q = heads(0, self.q, True)
        k = heads(self.q, self.kv, True)
        v = heads(self.q + self.kv, self.kv, False)
        window = self.kind == "sliding_attention"
        o = run_op("fused_attention_qkv", {"Q": [q], "K": [k], "V": [v]},
                   {"causal": True,
                    "window": cfg.sliding_window if window else 0,
                    "kernel_tag": "win" if window else "full"})["Out"][0]
        o = self._gate(h, o.transpose([0, 2, 1, 3]))
        return self.o_proj(o.reshape([b, s, self.q * d]))

    def _gate(self, h, o):
        """Scale every head's output ``o`` [b, s, heads, d] by its sigmoid
        gate, one value a head from the normed input."""
        b, s, _ = h.shape
        return o * F.sigmoid(self.g_proj(h)).reshape([b, s, self.q, 1])


class LagunaMoE(Layer):
    """Router over all experts, the held experts' grouped products, the
    shared expert. ``forward`` -> (output, stats of ``moe_experts``)."""

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
            h, cfg.num_experts, bias_attr=False, weight_attr=ParamAttr(
                initializer=NormalInitializer(0.0, cfg.init_std),
                learning_rate=1.0 if whole else 0.0))
        # stacked over the held experts; gate and up side by side
        self.experts_gate_up = self.create_parameter(
            [hi - lo, h, 2 * f], attr=_w(cfg.init_std))
        self.experts_down = self.create_parameter(
            [hi - lo, f, h], attr=_w(out_std))
        self.shared = SwiGLU(h, cfg.shared_expert_intermediate_size,
                             cfg.init_std, out_std)

    def forward(self, u):
        cfg = self.cfg
        r = run_op("moe_router", {"X": [u], "W": [self.router.weight]},
                   {"top_k": cfg.num_experts_per_tok,
                    "scale": cfg.moe_routed_scaling_factor})
        e = run_op("moe_experts",
                   {"X": [u], "TopkIdx": r["TopkIdx"],
                    "TopkWeight": r["TopkWeight"],
                    "WGateUp": [self.experts_gate_up],
                    "WDown": [self.experts_down]},
                   {"expert_lo": cfg.experts[0],
                    "num_experts": cfg.num_experts,
                    "tile_m": cfg.moe_tile_m})
        return e["Out"][0] + self.shared(u), e["Stats"][0]


class LagunaBlock(Layer):
    """Pre-norm block. A sparse block returns ``(x, stats)``."""

    def __init__(self, cfg: LagunaConfig, layer: int):
        super().__init__()
        self.sparse = cfg.mlp_layer_types[layer] == "sparse"
        self.attn_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = LagunaAttention(cfg, layer)
        self.mlp_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if self.sparse:
            self.moe = LagunaMoE(cfg)
        else:
            self.mlp = SwiGLU(
                cfg.hidden_size, cfg.intermediate_size, cfg.init_std,
                cfg.init_std / math.sqrt(2.0 * cfg.num_hidden_layers))

    def forward(self, x):
        x = x + self.attn(self.attn_norm(x))
        u = self.mlp_norm(x)
        if not self.sparse:
            return x + self.mlp(u)
        y, stats = self.moe(u)
        return x + y, stats


class LagunaModel(Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        lo, hi = cfg.vocab
        self.embed = Embedding(hi - lo, cfg.hidden_size,
                               weight_attr=_w(cfg.init_std))
        self.layers = LayerList([LagunaBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.sparse_layers = [i for i, t in enumerate(cfg.mlp_layer_types)
                              if t == "sparse"]
        # the expert layers' counters of the last forward, one row a sparse
        # layer (a buffer, so a compiled step carries them out as state)
        self.register_buffer("moe_stats_last", Tensor(
            jnp.zeros((max(len(self.sparse_layers), 1), 4), jnp.float32),
            stop_gradient=True), persistable=False)

    def forward(self, input_ids, collect=None):
        cfg = self.cfg
        lo, hi = cfg.vocab
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if (lo, hi) == (0, cfg.vocab_size):
            x = self.embed(Tensor(ids, stop_gradient=True))
        else:
            held = jnp.logical_and(ids >= lo, ids < hi)
            x = self.embed(Tensor(jnp.where(held, ids - lo, 0),
                                  stop_gradient=True))
            x = x * Tensor(held[..., None].astype(x.value.dtype),
                           stop_gradient=True)
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


class LagunaForCausalLM(Layer):
    """The model with its untied head. ``forward(ids)`` -> logits over the
    held vocabulary rows; with ``labels`` the mean next-token
    cross-entropy over them (labels outside the held rows are ignored)."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        with RecordEvent("laguna.build",
                         {"layers": cfg.num_hidden_layers,
                          "params": cfg.num_params()}):
            self.cfg = cfg
            self.model = LagunaModel(cfg)
            lo, hi = cfg.vocab
            self.lm_head = _linear(cfg.hidden_size, hi - lo, cfg.init_std)
        self._traced = False

    def forward(self, input_ids, labels=None, collect=None):
        # the first forward is the one a compiled step traces
        span = contextlib.nullcontext() if self._traced \
            else RecordEvent("laguna.first_trace")
        self._traced = True
        with span:
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

    def moe_stats(self) -> dict:
        """The expert layers' counters of the last step, read from the
        device now (the step itself never waits for them) and published as
        ``STAT_moe_*``: per sparse layer the (token, held expert) pairs,
        the largest expert load over the mean in thousandths, the dropped
        pairs (always 0) and whether the fast buffer held the step."""
        import numpy as np
        from .. import monitor
        rows = np.asarray(self.model.moe_stats_last.value)
        out = {}
        for layer, row in zip(self.model.sparse_layers, rows):
            out[layer] = {"assignments": int(row[0]),
                          "max_load_over_mean": float(row[1]),
                          "dropped_pairs": int(row[2]),
                          "fast_path": bool(row[3])}
            monitor.stat_set(f"STAT_moe_assignments_l{layer}", int(row[0]))
            monitor.stat_set(f"STAT_moe_max_load_permille_l{layer}",
                             int(round(1000 * float(row[1]))))
            monitor.stat_set(f"STAT_moe_dropped_pairs_l{layer}",
                             int(row[2]))
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
