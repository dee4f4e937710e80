"""Mellum2-class decoder LM (JetBrains/Mellum2-12B-A2.5B-Instruct): a
configuration of the sparse-expert decoder of ``models/laguna.py``, and the
second model behind the serving plane's model seam.

The published ``config.json``: 28 layers in periods of three
``sliding_attention`` (window 1024, plain rotary, theta 500000) and one
``full_attention`` (YaRN: factor 16, original 8192, beta 32 / 1, attention
factor 1.27726), hidden 2304, 32 query / 4 KV heads of 128, in every layer
64 routed experts of width 896 (top 8, no shared expert, softmax router
renormalised over the chosen: ``norm_topk_prob``), vocabulary 98304, untied
head. What it leaves open is settled as ``perfbench/configs/
mellum2-12b-d8.json`` lists under ``assumed``.

**Shared with Laguna** (one decoder module): RMSNorm, the rotary tables by
layer kind with YaRN, grouped-query attention with its window, SwiGLU
experts, the router op, the dropless expert layer and its grouped
products, the untied head, the build span. **Configured
off**: the per-head output gate, the shared expert, the sigmoid scores and
their 2.5 scale, the dense leading layer, head counts that differ by
layer, the per-head norm on q and k (``qk_norm``) and the router's
selection bias (``router_bias``; ``models/lfm2.py`` turns both on), and the
learned sparse attention's indexer (``sa_config``; ``models/keye.py`` turns
it on). **What could not be shared** is what serving adds and training has
not: the paged cache by layer kind, positions applied at a row's own
offset, the few-rows form of the expert layer; that lives in the same
classes (``LagunaAttention._served``, ``LagunaMoE.served``), so Laguna is
served by declaring a ``serving_spec`` too, when a cell asks for it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Tuple

from .laguna import LagunaConfig, LagunaForCausalLM

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
#: rotary parameters by layer kind, as the model publishes them
_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 8192, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0},
}
#: rows of one pass of the expert layer over a served prompt: the buckets
#: a prompt pads to are multiples of it, so ``moe_up`` / ``moe_down`` have
#: one shape a call and their buffers stay under 0.5 GB
PROMPT_CHUNK_ROWS = 3072


@dataclass
class MellumConfig(LagunaConfig):
    """The decoder's configuration with Mellum2-12B-A2.5B's values as
    defaults (``num_attention_heads`` is the one head count)."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168           # unused: every layer is sparse
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = _PERIOD * 7
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ("sparse",) * 28
    sliding_window: int = 1024
    rope_parameters: dict = field(default_factory=lambda: dict(_ROPE))
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    shared_expert_intermediate_size: int = 0
    moe_routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 131072
    attention_gate: bool = False
    router_score: str = "softmax"
    dtype: str = "bfloat16"
    moe_chunk_rows: int = PROMPT_CHUNK_ROWS

    def __post_init__(self):
        if not self.num_attention_heads_per_layer:
            self.num_attention_heads_per_layer = \
                (self.num_attention_heads,) * self.num_hidden_layers
        super().__post_init__()


class MellumForCausalLM(LagunaForCausalLM):
    """The decoder under a Mellum configuration, with what the serving
    plane needs of it."""

    span_prefix = "mellum"

    def serving_spec(self):
        """Two kinds of layer (full layers keep every row, window layers
        the last ``sliding_window``), pools in the parameters' dtype, none
        of the engine's optional features yet, prompts one a dispatch with
        the head on the last row only, and one device counter."""
        from ..serving.seam import CacheKind, ServedModel
        cfg = self.cfg
        if cfg.kv_heads != (0, cfg.num_key_value_heads) or \
                cfg.vocab != (0, cfg.vocab_size) or \
                cfg.experts != (0, cfg.num_experts):
            raise ValueError("a share of the model is not served: the "
                             "serving path holds every head, expert and "
                             "vocabulary row")
        kinds = tuple(
            CacheKind({"full_attention": "full",
                       "sliding_attention": "window"}[name], layers,
                      cfg.num_key_value_heads // cfg.kv_pack,
                      cfg.head_dim * cfg.kv_pack, window)
            for name, layers, window in cfg.cache_kinds())
        return ServedModel(
            model=self, family="mellum",
            max_positions=cfg.max_position_embeddings,
            vocab=cfg.vocab_size, cache_kinds=kinds,
            kv_dtype={"bfloat16": "bf16", "float32": "f32"}[cfg.dtype],
            features=frozenset(), counters=("experts_touched",),
            # a budget under every bucket, so one prompt a dispatch: a
            # 12288-row prompt's activations and expert buffers are
            # ~1.5 GB, and a closed or paced loop admits one request at a
            # time anyway
            tokens_a_dispatch=1, head_on_last_row=True)


MELLUM_CONFIGS = {
    "mellum2-12b-a2p5b": MellumConfig(),
    # a toy of the same layer kinds and period for tests and CPU
    # rehearsals: contexts cross its window of 16
    "mellum-tiny": MellumConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        layer_types=_PERIOD, mlp_layer_types=("sparse",) * 4,
        sliding_window=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, max_position_embeddings=128,
        moe_tile_m=8, moe_chunk_rows=0, dtype="float32"),
}
