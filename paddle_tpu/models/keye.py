"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye): a configuration of
the sparse-expert decoder of ``models/laguna.py``, as ``models/mellum.py``
is, and the fifth model behind the serving plane's model seam. The vision
tower is not the language model's and is not built.

The published ``config.json`` (``model_type`` ``KeyeVL2``): 48 layers,
hidden 2048, 32 query / 4 KV heads of 128, rotary theta 1e7
(``mrope_section`` [16, 24, 24]: for text the three sections carry one and
the same position, so it is the plain rotary), in every layer 128 routed
experts of width 768 (top 8, softmax renormalised over the chosen, none
shared, no dense layer), vocabulary 151936, untied head, 262144 positions,
and ``sa_config``: an indexer of 16 heads of 64 over ONE key head and
``topk`` 2048, the parts of the published DeepSeek sparse attention's
lightning indexer. What the config leaves open is settled as
``perfbench/configs/keye-vl2-30b-a3b-stage0.json`` lists under ``assumed``.

**What the decoder is configured to**: every layer ``full_attention``,
``qk_norm`` on (the decoder's keys are Qwen3-MoE's, whose attention has
it), 128 experts under the softmax router, no gate, no shared expert, no
dense layer, and the indexer (``sa_config``): every query, in a prompt as
in a decode step, scores the keys before it with the indexer
(``I[t, s] = sum_j w_t[j] relu(qI_t[j] . kI_s)``), keeps the 2048 largest
(all of them while the context is that short; a set of its own a row,
shared by its 32 heads) and takes its softmax over those alone. **What
serving adds**: a third per-token array beside K and V, the indexer's key
(64 values, after its LayerNorm and rotary), in the same blocks under the
same table (``serving.seam.CacheKind.extra``). The ops are
``ops.attention_ops``'s (``sparse_prompt_attention``,
``sparse_decode_attention``), plain XLA.

The family is served, not trained: no gradient passes the selection.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

from ..ops.attention_ops import sparse_prompt_pairs
from .laguna import LagunaConfig, LagunaForCausalLM

#: the one rotary of every layer (M-RoPE at text positions)
_ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 1e7}}
#: the indexer as published (``sa_config``; the two chunk sizes are the
#: tiling of the published scoring loop and change no result)
_SA = {"indexer_head_dim": 64, "indexer_num_heads": 16,
       "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
       "q_chunk_size": 512, "topk": 2048}
#: rows of one pass of the expert layer over a served prompt (the buckets
#: are multiples of it)
PROMPT_CHUNK_ROWS = 4096


@dataclass
class KeyeConfig(LagunaConfig):
    """The decoder's configuration with Keye-VL-2.0-30B-A3B's language
    model's values as defaults."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144           # unused: every layer is sparse
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    rope_parameters: dict = field(default_factory=lambda: dict(_ROPE))
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    shared_expert_intermediate_size: int = 0
    moe_routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 262144
    attention_gate: bool = False
    router_score: str = "softmax"
    qk_norm: bool = True
    dtype: str = "bfloat16"
    moe_chunk_rows: int = PROMPT_CHUNK_ROWS
    sa_config: Optional[dict] = field(default_factory=lambda: dict(_SA))

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = self.layer_types or ("full_attention",) * n
        self.mlp_layer_types = self.mlp_layer_types or ("sparse",) * n
        if not self.num_attention_heads_per_layer:
            self.num_attention_heads_per_layer = \
                (self.num_attention_heads,) * n
        super().__post_init__()
        if self.sa_config is None:
            raise ValueError("a keye model has an indexer (sa_config)")


class KeyeForCausalLM(LagunaForCausalLM):
    """The decoder under a Keye configuration, with what the serving plane
    needs of it."""

    span_prefix = "keye"

    def serving_spec(self):
        """One kind of layer whose token keeps THREE arrays (K, V and the
        indexer's key), pools in the parameters' dtype, none of the
        engine's optional features (prefix reuse would need the indexer's
        blocks published with a prefix's: ROADMAP R2), prompts one a
        dispatch with the head on the last row, and the device counters of
        the experts and of the selection."""
        from ..serving.seam import CacheKind, ServedModel
        cfg = self.cfg
        if cfg.kv_heads != (0, cfg.num_key_value_heads) or \
                cfg.vocab != (0, cfg.vocab_size) or \
                cfg.experts != (0, cfg.num_experts):
            raise ValueError("a share of the model is not served: the "
                             "serving path holds every head, expert and "
                             "vocabulary row")
        (_, layers, _), = cfg.cache_kinds()

        def prompt_counts(bucket, rows, live):
            # every layer's selected read of one dispatch: the (query,
            # key) pairs its loops multiply (their own bounds) and the
            # pairs of the whole ``rows x bucket`` rectangle
            read, rect = sparse_prompt_pairs(rows, bucket, live)
            return {"sparse_prompt_keys_read": len(layers) * read,
                    "sparse_prompt_keys_rect": len(layers) * rect}
        return ServedModel(
            model=self, family="keye",
            max_positions=cfg.max_position_embeddings,
            vocab=cfg.vocab_size,
            cache_kinds=(CacheKind(
                "full", layers, cfg.num_key_value_heads, cfg.head_dim,
                extra=(("index_cache", cfg.indexer[1]),)),),
            kv_dtype={"bfloat16": "bf16", "float32": "f32"}[cfg.dtype],
            features=frozenset(), counters=cfg.decode_counters,
            # one prompt a dispatch: a 16384-row prompt's program holds
            # 2.7 GB of temporaries beside 10.6 GB of weights and pools
            tokens_a_dispatch=1, head_on_last_row=True,
            prompt_counts=prompt_counts)


KEYE_CONFIGS = {
    "keye-vl2-30b-a3b": KeyeConfig(),
    # a toy of the same mechanisms for tests and CPU rehearsals: contexts
    # of 24-64 rows pass its topk of 8 and span several blocks of 8
    "keye-tiny": KeyeConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        max_position_embeddings=128, moe_tile_m=8, moe_chunk_rows=0,
        dtype="float32",
        sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 8}),
}
