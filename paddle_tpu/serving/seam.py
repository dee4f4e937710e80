"""The model seam of the serving plane: what a model declares so that
:class:`~paddle_tpu.serving.ServingEngine` can run it.

The engine schedules, admits, allocates blocks, samples and keeps its step
inputs on the device; everything it needs to know of the *model* it reads
from one :class:`ServedModel`, which the model builds
(``model.serving_spec()``):

- its limits: positions, rows of the logits;
- its paged cache, one :class:`CacheKind` a layer kind: which layers, KV
  heads and head size, and ``window`` > 0 for a kind that needs only the
  last ``window`` rows of a request (``BlockKVCache`` then bounds that
  kind's pool and frees its blocks behind the window). A kind's token may
  keep arrays BESIDE K and V (``extra``: a name and a width each, no head
  axis; a learned sparse attention's indexer keys are one): each is one
  more pool a layer, ``[blocks, width, block_size]``, after the (k, v)
  pair in the layer's tuple, under the SAME block table and allocator, so
  it is allocated, freed, donated, rebound and zeroed with them. A kind
  with ``kv_heads`` 0 has NO pair: its token's row is the ``extra`` arrays
  alone (a latent-attention layer keeps ONE vector a token for all its
  heads, the normed latent and the rotated key, ``models/dotsvlm.py``), in
  the one pool under the one table and allocator like any other;
- its recurrent state, one :class:`StateKind` a kind of layer that carries
  a fixed-size record from token to token whatever the context: which
  layers, and the shape and dtype of each array a request keeps, one array
  or several (a state-space layer's scan state and its convolution's tail
  are two; a gated short convolution's tail alone is one). The one
  cache manager holds them beside the blocks, ``[max_slots, ...]`` a layer
  and indexed by the request's row; a kind's entry of the block tables is
  the row index of each row of the dispatch (``max_slots`` for a row that
  is none: its write is dropped);
- the pools' dtype where the model fixes it (``kv_dtype``; None: the
  engine's ``FLAGS_serving_kv_dtype``);
- which of the engine's optional features its steps can run
  (:data:`FEATURES`); the engine refuses the others at construction, by
  name;
- its step builders: how a prompt's rows become logits of the last one
  (:meth:`ServedModel.prefill_logits`, traced inside the engine's
  ``serving_prefill_paged`` entry), how many rows a prefill dispatch of
  a bucket computes (:meth:`ServedModel.prefill_rows`: the model's
  ``tokens_a_dispatch`` over the bucket's length, from 1 to ``max_slots``,
  so a long bucket computes the prompt it admitted and a short one shares
  a read of the weights between several), and the compiled decode step
  (:meth:`ServedModel.decode_entry`);
- the counters its decode step keeps on the device (``counters``: names of
  the entries of one float32 vector that the step takes as its last
  argument and returns as its last result; the engine carries it from
  step to step beside the step's tokens and keys, so the step's fetch
  stays what it is and only ``engine.stats()`` reads them);
- what a prefill dispatch of it reads, where the model counts that
  (``prompt_counts``: host arithmetic from the model's own loop bounds,
  added up by the engine under the names the model gives).

A model may declare blocks, a recurrent kind, experts' device counters and
any number of rows at once (``models/lfm2.py`` does: 128 rows a decode
step), blocks whose token keeps a third array (``models/keye.py``), or
blocks whose token keeps one vector and no K and V (``models/dotsvlm.py``);
nothing in the engine or the cache manager is sized by a model's name or
by another model's arrays.

The defaults are the generic paged forward every model of this repo with a
``model(ids, cache=, cache_pos=, block_tables=, lora=)`` call shares, so
:class:`~paddle_tpu.models.gpt.GPTForCausalLM` declares numbers only.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

#: what an engine can be asked for beyond the plain paged loop
FEATURES = frozenset({
    "prefix_cache",     # prefix reuse (prefix_cache=True)
    "speculative",      # spec_tokens > 0
    "lora",             # lora_rank > 0 / lora_pool=
    "mesh",             # mesh= / FLAGS_serving_mesh
    "host_tier",        # kv_tier= / FLAGS_serving_host_tier
    "int8_pool",        # kv_dtype="int8"
    "disaggregation",   # kv_pool= (a pool shared between engine roles)
})


@dataclass(frozen=True)
class CacheKind:
    """One kind of layer of a paged cache: per layer a (k, v) pair of
    ``[blocks, kv_heads, block_size, head_dim]`` pools and, for each
    ``extra`` entry ``(name, width)``, one more pool ``[blocks, width,
    block_size]`` of the pools' dtype after them: ``width`` values a token
    with no head axis, a token a lane (a width under 128 then pads
    nothing on the chip), in the same blocks as the token's K and V. Only
    the kind that keeps every row may have them, and only a float pool.
    ``kv_heads`` 0 (with ``head_dim`` 0) is a kind with no pair: a layer's
    tuple is the ``extra`` arrays alone, the first of them first."""
    name: str
    layers: Tuple[int, ...]     # the model's layer indices, ascending
    kv_heads: int
    head_dim: int
    window: int = 0             # 0: every row of a request is kept
    extra: Tuple[Tuple[str, int], ...] = ()


@dataclass(frozen=True)
class StateKind:
    """One kind of layer that keeps a fixed-size record a request: any
    number of arrays of any shape and dtype."""
    name: str
    layers: Tuple[int, ...]     # the model's layer indices, ascending
    #: (shape, dtype name) of each array one request keeps in one layer
    arrays: Tuple[Tuple[Tuple[int, ...], str], ...]


@dataclass
class ServedModel:
    """A model as the serving engine sees it (see the module)."""
    model: object
    family: str                         # for error messages
    max_positions: int
    vocab: int
    cache_kinds: Tuple[CacheKind, ...]  # the unbounded kind first
    state_kinds: Tuple[StateKind, ...] = ()
    kv_dtype: Optional[str] = None
    features: frozenset = FEATURES
    counters: Tuple[str, ...] = ()
    lora_config: object = None          # what LoRAPool sizes its pages by
    #: tokens a prefill dispatch computes, about where the model's
    #: products stop being bound by the read of its weights: a bucket's
    #: entry has ``tokens_a_dispatch // bucket`` rows, from 1 (any bucket
    #: over the budget: one prompt a dispatch) to ``max_slots`` (None:
    #: ``max_slots`` whatever the bucket)
    tokens_a_dispatch: Optional[int] = None
    #: the model's call takes ``last=`` and multiplies the head on each
    #: prompt's last row only (else the seam gathers that row's logits)
    head_on_last_row: bool = False
    #: None, or ``(bucket, rows, live) -> {name: count}``: what one
    #: prefill dispatch of ``rows`` x ``bucket`` positions whose longest
    #: prompt has ``live`` rows adds to ``engine.stats()`` under those
    #: names, reckoned on the host from the bounds of the model's own
    #: loops (a prompt's program returns logits and pools, no counters)
    prompt_counts: Optional[Callable[[int, int, int], Dict[str, int]]] = None

    @property
    def num_layers(self) -> int:
        return sum(len(k.layers)
                   for k in self.cache_kinds + self.state_kinds)

    def require(self, feature: str, asked_by: str):
        """Refuse, by name, a feature this model's steps cannot run."""
        if feature not in FEATURES:
            raise KeyError(feature)
        if feature not in self.features:
            raise ValueError(
                f"{self.family} is not served with {feature} "
                f"({asked_by}): the serving seam of "
                f"{type(self.model).__name__} does not declare it "
                f"(it declares {sorted(self.features) or 'none'})")

    # ------------------------------------------------------ step builders
    def prefill_rows(self, bucket: int, max_slots: int) -> int:
        """Rows the prefill entry of ``bucket`` computes, which is the
        most prompts one dispatch of it carries."""
        if self.tokens_a_dispatch is None:
            return int(max_slots)
        return max(1, min(int(max_slots),
                          int(self.tokens_a_dispatch) // int(bucket)))

    def prefill_logits(self, ids, last, pos, tables, pools, lora):
        """Inside the engine's prefill entry: the prompts' rows ``ids``
        [rows, bucket] written through ``tables`` into ``pools`` from
        ``pos`` on -> (float32 logits [rows, vocab] of each prompt's
        ``last`` row, the model's returned caches)."""
        from ..dygraph.tensor import Tensor
        from ..models.generation import _wrap_pools
        ids, cache = Tensor(ids, stop_gradient=True), _wrap_pools(pools)
        if self.head_on_last_row:
            logits, newp = self.model(ids, cache=cache, cache_pos=pos,
                                      block_tables=tables, lora=lora,
                                      last=last)
            return logits.value[:, 0], newp
        logits, newp = self.model(ids, cache=cache, cache_pos=pos,
                                  block_tables=tables, lora=lora)
        return jnp.take_along_axis(logits.value, last[:, None, None],
                                   axis=1)[:, 0], newp

    def decode_entry(self, mesh, kv_dtype: str, lora_shape):
        """The compiled decode step (a ``step_entry``)."""
        from ..models.generation import decode_step_paged
        return decode_step_paged(self.model, mesh, kv_dtype, lora_shape,
                                 counters=bool(self.counters))


def served(model) -> ServedModel:
    """``model``'s declaration to the serving plane, or a TypeError that
    says what a model needs to be served."""
    spec = getattr(model, "serving_spec", None)
    if spec is None:
        raise TypeError(
            f"{type(model).__name__} is not a served model: the serving "
            f"plane reads a model through model.serving_spec() -> "
            f"paddle_tpu.serving.seam.ServedModel (GPTForCausalLM, "
            f"MellumForCausalLM, JambaForCausalLM, Lfm2ForCausalLM, "
            f"KeyeForCausalLM, DotsVlmForCausalLM and "
            f"Qwen3NextForCausalLM have one)")
    return spec()


def served_with(model, feature: str, asked_by: str) -> ServedModel:
    """:func:`served`, refusing by name a feature the model's steps cannot
    run: what the router and the disaggregated roles share between engines
    (a LoRA pool, a host tier, a block pool) is sized from the seam, never
    from a model's own attributes."""
    spec = served(model)
    spec.require(feature, asked_by)
    return spec


def require_gpt(model, what: str):
    """For the paths that are GPT's alone (the fixed-capacity cache of
    ``models.generation``, the router's and the disaggregated roles'
    block shipping): refuse another model by name instead of failing on a
    missing attribute."""
    if getattr(model, "gpt", None) is None:
        raise TypeError(
            f"{what} runs GPTForCausalLM only; {type(model).__name__} is "
            f"served by ServingEngine through its serving_spec()")
    return model.gpt.cfg
