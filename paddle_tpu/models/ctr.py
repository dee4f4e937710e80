"""CTR model family: Wide&Deep and DeepFM over sparse id slots.

Capability analog of BASELINE configs[4] (dist_fleet_ctr.py workload:
sparse embeddings on the PS tier, dense net on the accelerator). Both
models consume padded slot-id batches [b, slots] (or [b, slots, k]
multi-hot with 0 padding) exactly as the slot Dataset emits them.

Two execution tiers, mirroring the reference split:
- dygraph classes (WideDeep / DeepFM) keep the embedding ON-DEVICE —
  the dense-capable regime;
- ``build_wide_deep_program`` emits the STATIC PS-tier program whose
  embedding pull/push rides distributed_lookup_table (host sparse
  table or remote PS servers), the dist_fleet_ctr.py regime where the
  feasign space dwarfs device memory.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import paddle_tpu as pt

from ..initializer import NormalInitializer
from ..nn import Embedding, Layer, LayerList, Linear
from ..param_attr import ParamAttr


def _small_init():
    # CTR tables init near zero (large-scale-kv convention): logits
    # start ~0 so rare ids don't inject unit-scale noise
    return ParamAttr(initializer=NormalInitializer(0.0, 0.01))


class _SlotEmbedding(Layer):
    """One embedding table shared by all slots (CTR convention: a
    single feasign space; slot identity is positional). Multi-hot
    slots ([b, slots, k] with 0 padding) sum-pool over k — padding_idx
    0 embeds to zeros, so the pool ignores it (the standard CTR
    sum-pooling)."""

    def __init__(self, vocab_size: int, dim: int):
        super().__init__()
        self.embedding = Embedding(vocab_size, dim, padding_idx=0,
                                   weight_attr=_small_init())

    def forward(self, ids):
        emb = self.embedding(ids)             # [b, slots(, k), dim]
        if len(ids.shape) == 3:
            emb = emb.sum(axis=2)             # pool the k hot ids
        return emb                            # [b, slots, dim]


class WideDeep(Layer):
    """Wide & Deep: a linear (order-1) wide part over the same ids +
    an MLP deep tower over concatenated slot embeddings."""

    def __init__(self, vocab_size: int = 100000, embed_dim: int = 8,
                 num_slots: int = 8,
                 hidden_sizes: Sequence[int] = (64, 32)):
        super().__init__()
        self.embed = _SlotEmbedding(vocab_size, embed_dim)
        self.wide = Embedding(vocab_size, 1, padding_idx=0,
                              weight_attr=_small_init())
        dims = [num_slots * embed_dim] + list(hidden_sizes)
        self.deep = LayerList([Linear(a, b)
                               for a, b in zip(dims[:-1], dims[1:])])
        self.head = Linear(dims[-1], 1)

    def forward(self, slot_ids):
        b = slot_ids.shape[0]
        emb = self.embed(slot_ids)                   # [b, s, d]
        deep = emb.reshape([b, -1])
        for fc in self.deep:
            deep = pt.nn.functional.relu(fc(deep))
        wide = self.wide(slot_ids).reshape([b, -1])  # [b, s(*k)]
        return self.head(deep) + wide.sum(axis=-1, keepdim=True)


class DeepFM(Layer):
    """DeepFM: order-1 + pairwise FM interaction (the sum-square trick,
    O(s*d) instead of O(s^2)) + deep tower, sharing one embedding."""

    def __init__(self, vocab_size: int = 100000, embed_dim: int = 8,
                 num_slots: int = 8,
                 hidden_sizes: Sequence[int] = (64, 32)):
        super().__init__()
        self.embed = _SlotEmbedding(vocab_size, embed_dim)
        self.first_order = Embedding(vocab_size, 1, padding_idx=0,
                                     weight_attr=_small_init())
        dims = [num_slots * embed_dim] + list(hidden_sizes)
        self.deep = LayerList([Linear(a, b)
                               for a, b in zip(dims[:-1], dims[1:])])
        self.head = Linear(dims[-1], 1)

    def forward(self, slot_ids):
        b = slot_ids.shape[0]
        emb = self.embed(slot_ids)                   # [b, s, d]
        # FM second order: 0.5 * ((sum_i v_i)^2 - sum_i v_i^2)
        sum_v = emb.sum(axis=1)                      # [b, d]
        sum_sq = (emb * emb).sum(axis=1)
        fm = 0.5 * (sum_v * sum_v - sum_sq).sum(axis=-1, keepdim=True)
        first = self.first_order(slot_ids).reshape([b, -1]) \
            .sum(axis=-1, keepdim=True)
        deep = emb.reshape([b, -1])
        for fc in self.deep:
            deep = pt.nn.functional.relu(fc(deep))
        return self.head(deep) + fm + first





def build_wide_deep_program(num_slots: int = 8, embed_dim: int = 8,
                            hidden_sizes: Sequence[int] = (64, 32),
                            table_name: str = "ctr_embedding",
                            sparse_lr: float = 0.1,
                            dense_lr: float = 0.01,
                            host_paced: bool = False):
    """Static PS-tier Wide&Deep: sparse embedding on the PS tier, dense
    tower trained with SGD on device. Two sparse transports:

    - default (in-graph): embedding pull/push rides the
      distributed_lookup_table op's ordered io_callback inside the
      compiled step — lowest host overhead when the runtime services
      in-graph host calls;
    - ``host_paced=True``: the embedding rows become plain DENSE feed
      vars (``ctr_emb``/``ctr_wide``, stop_gradient=False) and their
      gradients materialize as fetchable ``@GRAD`` vars — the
      pull → compute → push loop then lives on the HOST
      (ps/host_paced.py; downpour_worker.cc:726 structure). This
      transport needs nothing of the runtime but feed and fetch.

    Returns (main, startup, loss_var, logit_var); feed ``ids``
    [b, num_slots] int64 and ``label`` [b, 1] float32 (plus the two row
    feeds in host_paced mode).
    """
    import paddle_tpu.layers as L
    from ..framework import Program, program_guard, unique_name
    from ..optimizer import SGD

    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        blk = main.global_block()
        L.data("ids", [num_slots], dtype="int64")
        label = L.data("label", [1])
        if host_paced:
            emb = L.data("ctr_emb", [num_slots, embed_dim])
            emb.stop_gradient = False
        else:
            emb = blk.create_var("ctr_emb",
                                 shape=[-1, num_slots, embed_dim])
            blk.append_op("distributed_lookup_table", {"Ids": "ids"},
                          {"Out": "ctr_emb"},
                          {"table_names": [table_name],
                           "value_dim": embed_dim,
                           "sparse_lr": sparse_lr})
        deep = L.reshape(emb, [-1, num_slots * embed_dim])
        for h in hidden_sizes:
            deep = L.fc(deep, h, act="relu")
        deep_logit = L.fc(deep, 1)
        # wide order-1 path: its own dim-1 table summed straight into
        # the logit — the direct gradient route that lets the sparse
        # tier learn before the deep tower warms up
        if host_paced:
            wide = L.data("ctr_wide", [num_slots, 1])
            wide.stop_gradient = False
        else:
            wide = blk.create_var("ctr_wide", shape=[-1, num_slots, 1])
            blk.append_op("distributed_lookup_table", {"Ids": "ids"},
                          {"Out": "ctr_wide"},
                          {"table_names": [table_name + "_wide"],
                           "value_dim": 1, "sparse_lr": sparse_lr})
        wide_sum = L.reduce_sum(wide, dim=[1])
        logit = L.elementwise_add(deep_logit, wide_sum)
        loss = L.reduce_mean(
            L.sigmoid_cross_entropy_with_logits(logit, label))
        SGD(learning_rate=dense_lr).minimize(loss)
    return main, startup, loss, logit

__all__ = ["DeepFM", "WideDeep", "build_wide_deep_program"]
