"""GPT-2-class decoder-only LM — the flagship transformer workload.

Capability analog: the reference's transformer stack
(python/paddle/nn/layer/transformer.py:387-950) powering the GPT-2/ERNIE
baselines in BASELINE.json (configs[2]). TPU-first design decisions:

- attention goes through the single differentiable ``fused_attention_qkv``
  op with ``causal=True`` (no materialized [s, s] mask var; XLA/pallas
  decide the kernel), instead of the reference's composed matmul+softmax
  with an additive mask tensor;
- pre-LN blocks (stable in bf16 — the AMP O2 path keeps master fp32
  params and casts matmul inputs to bf16 for the MXU);
- vocab padded to a multiple of 128 so the LM-head matmul tiles the MXU
  exactly; the pad rows are masked out of the loss with ignore_index.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from ..dygraph.layers import Layer, LayerList
from ..dygraph.tape import run_op
from ..dygraph.tensor import Tensor
from ..nn import functional as F
from ..nn.layers_common import Dropout, Embedding, LayerNorm, Linear
from ..param_attr import ParamAttr
from ..initializer import NormalInitializer


@dataclass
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded up to a 128 multiple
    max_position_embeddings: int = 1024
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    ffn_hidden_size: int = 4096
    dropout: float = 0.0
    init_std: float = 0.02
    # rematerialize each block's activations in backward (batch-size
    # lever; fleet.utils.recompute over every decoder block)
    recompute: bool = False
    # pad the vocab embedding rows up to a multiple of this, so a
    # vocab-parallel sharding axis always divides the table (the
    # standard 50257 -> 50304 trick as a knob). Logits are sliced back
    # to vocab_size, pad rows never receive lookups or gradients.
    vocab_pad_to: int = 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def padded_vocab_size(self) -> int:
        pad = max(1, int(self.vocab_pad_to))
        return -(-self.vocab_size // pad) * pad

    def num_params(self, include_embeddings: bool = True) -> int:
        h, f, L = self.hidden_size, self.ffn_hidden_size, self.num_layers
        per_layer = (4 * h * h + 4 * h) + (2 * h * f + h + f) + 4 * h
        n = L * per_layer + 2 * h  # final LN
        if include_embeddings:
            n += (self.padded_vocab_size
                  + self.max_position_embeddings) * h
        return n



#: query rows a request up to which the paged branch reads through the
#: kernel (``ops/pallas/paged_attention``): decode (1) and speculative
#: verify (K+1) blocks. Read off the query block's own shape, not off the
#: rows a dispatch holds: a lone bucket-64 prompt is a prefill.
PAGED_KERNEL_MAX_ROWS = 8

GPT_CONFIGS = {
    # name: (hidden, layers, heads, ffn)
    "gpt2-tiny": GPTConfig(hidden_size=128, num_layers=2, num_heads=4,
                           ffn_hidden_size=512, vocab_size=1024,
                           max_position_embeddings=128),
    "gpt2-small": GPTConfig(hidden_size=768, num_layers=12, num_heads=12,
                            ffn_hidden_size=3072),
    "gpt2-medium": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                             ffn_hidden_size=4096),   # the 345M baseline
    # BASELINE configs[2] 1.3B-class flagship: GPT-3-style geometry —
    # head_dim 128 fills the full 128-lane MXU contraction (d=64 GPT-2
    # heads run at half MXU width; PERF.md "where the time goes")
    "gpt2-1p3b": GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                           ffn_hidden_size=8192),
    # 1.112B sibling: the largest config that trains at BATCH 8 on one
    # 16 GB v5e chip (1.3B fits at batch 4) — needs the
    # jit.to_static(retain_grads=False) grads-internal contract; full
    # measured capacity curve in PERF.md
    "gpt2-1p1b": GPTConfig(hidden_size=2048, num_layers=20, num_heads=16,
                           ffn_hidden_size=8192),
    "gpt2-xl": GPTConfig(hidden_size=1600, num_layers=48, num_heads=25,
                         ffn_hidden_size=6400),
}


def _lora_delta(x, ids, A, B):
    """Per-row paged-LoRA delta: gather each batch row's adapter page
    from the stacked pool factors (``A [pages, din, r]`` / ``B [pages,
    r, dout]``) and apply ``x @ A_page @ B_page`` — two thin matmuls
    (rank << hidden). ``ids`` is the per-row int32 page vector; page 0
    is the all-zero base page, so base rows add an exact zero and mix
    freely with adapter rows in one compiled step.  Inference-only by
    construction (the delta bypasses the tape)."""
    Ag = jnp.take(A, ids, axis=0)                 # [b, din, r]
    Bg = jnp.take(B, ids, axis=0)                 # [b, r, dout]
    d = jnp.einsum("bsi,bir->bsr", x, Ag)
    return jnp.einsum("bsr,bro->bso", d, Bg)


class GPTAttention(Layer):
    """Causal self-attention: fused qkv projection (one [h, 3h] matmul on
    the MXU) + the differentiable fused attention op."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        w = ParamAttr(initializer=NormalInitializer(0.0, cfg.init_std))
        # single qkv projection — one MXU matmul instead of three
        self.qkv_proj = Linear(cfg.hidden_size, 3 * cfg.hidden_size,
                               weight_attr=w)
        wo = ParamAttr(initializer=NormalInitializer(
            0.0, cfg.init_std / math.sqrt(2.0 * cfg.num_layers)))
        self.out_proj = Linear(cfg.hidden_size, cfg.hidden_size,
                               weight_attr=wo)
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x, cache=None, cache_pos=None, block_tables=None,
                lora=None):
        cfg = self.cfg
        b, s, _ = x.shape
        if cache is not None and cache_pos is None:
            raise ValueError(
                "a KV cache needs cache_pos: with block_tables it is a "
                "paged block pool (gen_block_pool, the serving "
                "engine's), without them a fixed-capacity "
                "[b, h, max_len, d] pair (gen_fixed_cache, "
                "generation.py's)")
        qkv = self.qkv_proj(x)
        if lora is not None:
            # lora = (page_ids [b] i32, Aq, Bq, Ao, Bo) — this layer's
            # slice of the paged adapter pool, a plain jit input
            qkv = qkv + Tensor(
                _lora_delta(x.value, lora[0], lora[1], lora[2]),
                stop_gradient=True)
        qkv = qkv.reshape([b, s, 3, cfg.num_heads, cfg.head_dim])
        qkv = qkv.transpose([2, 0, 3, 1, 4])  # [3, b, h, s, d]
        q, k, v = qkv[0], qkv[1], qkv[2]

        def _out(o):
            y = self.out_proj(o)
            if lora is not None:
                y = y + Tensor(
                    _lora_delta(o.value, lora[0], lora[3], lora[4]),
                    stop_gradient=True)
            return self.dropout(y)
        if block_tables is not None:
            # block-paged KV cache: `cache` is a (k, v) pool pair of
            # [num_blocks, h, block_size, d] blocks shared by every
            # request; each batch row's logical positions route through
            # its `block_tables` row to physical blocks. Pools and
            # tables are both fixed-shape jit inputs, so remapping or
            # sharing blocks (prefix cache, COW) never recompiles —
            # same compile-once contract as the slotted path below,
            # with per-request memory paid in blocks instead of a full
            # max_len row. Inference-only by construction.
            # The cache tuple's width selects the storage format
            # structurally (no dtype flag reaches the model): 2 wide is
            # a float pool, 4 wide is int8 codes + per-block-per-head
            # absmax scales (written by block_scatter_write_quant; an
            # int8 step returns a 5th element, the max-abs dequant error
            # of the rows just written, which the engine surfaces as a
            # drift metric). The query block's shape picks the read: a
            # decode or verify block (a few rows a request) goes through
            # the paged kernel, which copies each request's live blocks
            # and gathers nothing table-sized; a prefill bucket composes
            # gather (+ dequant) with the masked softmax in XLA.
            from ..ops.attention_ops import (block_attention,
                                             block_gather_dequant,
                                             block_scatter_write,
                                             block_scatter_write_quant,
                                             decode_attention_mask)
            pos = jnp.asarray(cache_pos, jnp.int32)
            if pos.ndim == 0:
                pos = jnp.broadcast_to(pos, (b,))
            tables = jnp.asarray(block_tables, jnp.int32)
            quant = len(cache) >= 4
            if quant:
                kp, vp, ksc, vsc = (c.value for c in cache[:4])
                kp, ksc, kerr = block_scatter_write_quant(
                    kp, ksc, k.value, pos, tables)
                vp, vsc, verr = block_scatter_write_quant(
                    vp, vsc, v.value, pos, tables)
                cache = (Tensor(kp, stop_gradient=True),
                         Tensor(vp, stop_gradient=True),
                         Tensor(ksc, stop_gradient=True),
                         Tensor(vsc, stop_gradient=True),
                         Tensor(jnp.maximum(kerr, verr),
                                stop_gradient=True))
            else:
                kp, vp = cache[0].value, cache[1].value
                ksc = vsc = None
                kp = block_scatter_write(kp, k.value, pos, tables)
                vp = block_scatter_write(vp, v.value, pos, tables)
                cache = (Tensor(kp, stop_gradient=True),
                         Tensor(vp, stop_gradient=True))
            if s <= PAGED_KERNEL_MAX_ROWS:
                from ..ops.pallas.paged_attention import paged_attention
                out = Tensor(paged_attention(q.value, kp, vp, tables, pos,
                                             k_scale=ksc, v_scale=vsc),
                             stop_gradient=True)
            elif not quant:
                out = Tensor(block_attention(q.value, kp, vp, tables, pos),
                             stop_gradient=True)
            else:
                kg = block_gather_dequant(kp, ksc, tables)
                vg = block_gather_dequant(vp, vsc, tables)
                mask = decode_attention_mask(pos, s, kg.shape[2],
                                             kg.dtype)
                out = run_op("fused_attention_qkv",
                             {"Q": [q],
                              "K": [Tensor(kg, stop_gradient=True)],
                              "V": [Tensor(vg, stop_gradient=True)],
                              "Mask": [Tensor(mask, stop_gradient=True)]},
                             {"causal": False})["Out"][0]
            out = out.transpose([0, 2, 1, 3]).reshape(
                [b, s, cfg.hidden_size])
            return _out(out), cache
        if cache is not None:
            # fixed-capacity (slotted) KV cache: `cache` is a
            # preallocated [b, h, max_len, d] pair and the new keys are
            # written in place at each row's own offset, so every
            # decode step has ONE shape and XLA compiles it once. The
            # same path serves s > 1 blocks — the prompt pass
            # scatter-writes s rows at once; the per-row position mask
            # keeps each query row causal within the written block.
            # Inference-only by construction (writes bypass the tape).
            from ..ops.attention_ops import (cache_scatter_write,
                                             decode_attention_mask)
            kc, vc = cache[0].value, cache[1].value
            pos = jnp.asarray(cache_pos, jnp.int32)
            if pos.ndim == 0:
                pos = jnp.broadcast_to(pos, (b,))
            kc = cache_scatter_write(kc, k.value, pos)
            vc = cache_scatter_write(vc, v.value, pos)
            mask = decode_attention_mask(pos, s, kc.shape[2], kc.dtype)
            cache = (Tensor(kc, stop_gradient=True),
                     Tensor(vc, stop_gradient=True))
            out = run_op("fused_attention_qkv",
                         {"Q": [q], "K": [cache[0]], "V": [cache[1]],
                          "Mask": [Tensor(mask, stop_gradient=True)]},
                         {"causal": False})["Out"][0]
            out = out.transpose([0, 2, 1, 3]).reshape(
                [b, s, cfg.hidden_size])
            return _out(out), cache
        out = run_op("fused_attention_qkv",
                     {"Q": [q], "K": [k], "V": [v]},
                     {"causal": True})["Out"][0]
        out = out.transpose([0, 2, 1, 3]).reshape([b, s, cfg.hidden_size])
        return _out(out)


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        w = ParamAttr(initializer=NormalInitializer(0.0, cfg.init_std))
        wo = ParamAttr(initializer=NormalInitializer(
            0.0, cfg.init_std / math.sqrt(2.0 * cfg.num_layers)))
        self.ln1 = LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size)
        self.fc1 = Linear(cfg.hidden_size, cfg.ffn_hidden_size,
                          weight_attr=w)
        self.fc2 = Linear(cfg.ffn_hidden_size, cfg.hidden_size,
                          weight_attr=wo)
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x, cache=None, cache_pos=None, block_tables=None,
                lora=None):
        # lora = (page_ids [b] i32, this layer's 8 pool factors
        # (Aq, Bq, Ao, Bo, A1, B1, A2, B2)); attn consumes the first
        # four, the MLP pair the rest
        attn_lora = None
        if lora is not None:
            ids, arrs = lora
            attn_lora = (ids,) + tuple(arrs[:4])
        if cache is None:
            x = x + self.attn(self.ln1(x), lora=attn_lora)
        else:
            a, cache = self.attn(self.ln1(x), cache, cache_pos=cache_pos,
                                 block_tables=block_tables,
                                 lora=attn_lora)
            x = x + a
        h = self.ln2(x)
        f = self.fc1(h)
        if lora is not None:
            f = f + Tensor(_lora_delta(h.value, ids, arrs[4], arrs[5]),
                           stop_gradient=True)
        g = F.gelu(f, approximate=True)
        o = self.fc2(g)
        if lora is not None:
            o = o + Tensor(_lora_delta(g.value, ids, arrs[6], arrs[7]),
                           stop_gradient=True)
        x = x + self.dropout(o)
        return x if cache is None else (x, cache)


class GPTModel(Layer):
    """Embeddings + pre-LN decoder stack + final LN."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        w = ParamAttr(initializer=NormalInitializer(0.0, cfg.init_std))
        self.wte = Embedding(cfg.padded_vocab_size, cfg.hidden_size,
                             weight_attr=w)
        self.wpe = Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                             weight_attr=w)
        self.drop = Dropout(cfg.dropout)
        self.blocks = LayerList([GPTBlock(cfg)
                                 for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, cache=None, position_offset=0,
                cache_pos=None, block_tables=None, lora=None):
        s = input_ids.shape[1]
        if lora is not None:
            # (page_ids [b] i32, 8-tuple of stacked [layers, pages, ..]
            # pool factors) — each block slices its own layer below
            lora = (jnp.asarray(lora[0], jnp.int32), tuple(lora[1]))
        if cache_pos is not None:
            # fixed-capacity cache mode: positions come from each row's
            # cache write offset (int, or a [b] vector for slotted
            # serving where every row is at a different length). Traced
            # offsets can't be range-checked here — the callers
            # (generation.py / serving.engine) validate capacity against
            # max_position_embeddings up front.
            if isinstance(cache_pos, int) and \
                    cache_pos + s > self.cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence length {cache_pos + s} exceeds "
                    f"max_position_embeddings="
                    f"{self.cfg.max_position_embeddings}")
            p = jnp.asarray(cache_pos, jnp.int32)
            p = p[None] if p.ndim == 0 else p
            # clamp: bucketed-prefill padding rows carry positions past
            # a short request's real length; an out-of-range position
            # gather would produce NaN embeddings (jnp.take fill mode)
            # that poison even *masked* attention lanes (finfo.min +
            # NaN = NaN through the softmax). The clamp is an identity
            # for every valid row.
            pos = jnp.minimum(
                p[:, None] + jnp.arange(s, dtype=jnp.int32)[None],
                self.cfg.max_position_embeddings - 1)
            pos = Tensor(pos, stop_gradient=True)
        else:
            if position_offset + s > self.cfg.max_position_embeddings:
                # out-of-range position gathers would silently produce
                # NaN embeddings (jnp.take fill mode) — fail with
                # guidance instead
                raise ValueError(
                    f"sequence length {position_offset + s} exceeds "
                    f"max_position_embeddings="
                    f"{self.cfg.max_position_embeddings}"
                    "; raise it in the GPTConfig (dataclasses.replace) "
                    "or truncate the input")
            pos = Tensor(jnp.arange(position_offset, position_offset + s,
                                    dtype=jnp.int32)[None, :],
                         stop_gradient=True)
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        new_caches = []
        for i, blk in enumerate(self.blocks):
            if cache is None:
                if self.cfg.recompute:
                    from ..distributed.fleet.utils.recompute import \
                        recompute as _rc
                    x = _rc(blk, x)
                else:
                    x = blk(x)
            else:
                x, c = blk(x, cache[i], cache_pos=cache_pos,
                           block_tables=block_tables,
                           lora=None if lora is None else
                           (lora[0], tuple(a[i] for a in lora[1])))
                new_caches.append(c)
        x = self.ln_f(x)
        return x if cache is None else (x, new_caches)

    def gen_fixed_cache(self, batch_size, max_len):
        """Preallocated fixed-capacity KV cache: one [b, h, max_len, d]
        zero pair per layer. Used with ``cache_pos`` so every decode
        step sees a single shape (compiles once); serving stacks slots
        on the batch axis."""
        z = Tensor(jnp.zeros((batch_size, self.cfg.num_heads, max_len,
                              self.cfg.head_dim), jnp.float32),
                   stop_gradient=True)
        return [(z, z) for _ in range(self.cfg.num_layers)]

    def gen_block_pool(self, num_blocks, block_size, kv_dtype="f32"):
        """Preallocated block-paged KV pool: one
        [num_blocks, h, block_size, d] zero pair per layer, addressed
        through per-request block tables (``block_tables`` forward
        kwarg). Physical block 0 is reserved by the serving plane as
        the trash block for padding/overflow writes. ``kv_dtype``
        'int8' yields 4-wide layers (code pools + zeroed
        [num_blocks, h] absmax scale pair) matching BlockKVCache's
        int8 layout; 'bf16' halves the pool bytes without scales."""
        shape = (num_blocks, self.cfg.num_heads, block_size,
                 self.cfg.head_dim)
        if kv_dtype == "int8":
            z = Tensor(jnp.zeros(shape, jnp.int8), stop_gradient=True)
            sc = Tensor(jnp.zeros((num_blocks, self.cfg.num_heads),
                                  jnp.float32), stop_gradient=True)
            return [(z, z, sc, sc) for _ in range(self.cfg.num_layers)]
        dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32
        z = Tensor(jnp.zeros(shape, dt), stop_gradient=True)
        return [(z, z) for _ in range(self.cfg.num_layers)]


#: tokens one serving prefill dispatch computes (``ServedModel.
#: tokens_a_dispatch``): about where a dense decoder's products stop being
#: bound by the read of its weights, so a bucket of this length or more
#: computes one prompt a dispatch and eight 64-token prompts share one.
#: Chosen from a sweep of 1 / 2 / 4 / 8 rows at buckets 64-1024 on a v5e
#: at 1.3B float32 (``perfbench/study/runs_pr32.jsonl``, PERF.md PR 32).
PREFILL_TOKENS_A_DISPATCH = 512


class GPTForCausalLM(Layer):
    """LM head tied to the token embedding (weight sharing, like GPT-2)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, labels=None, cache=None,
                position_offset=0, cache_pos=None, block_tables=None,
                lora=None):
        if cache is None:
            # forward the offset: chunked-prefill callers without a cache
            # must get real positions (and the out-of-range guard)
            h = self.gpt(input_ids, position_offset=position_offset)
        else:
            h, cache = self.gpt(input_ids, cache, position_offset,
                                cache_pos=cache_pos,
                                block_tables=block_tables, lora=lora)
        # tied LM head: h @ wte.T
        logits = run_op("matmul_v2",
                        {"X": [h], "Y": [self.gpt.wte.weight]},
                        {"trans_y": True})["Out"][0]
        if self.cfg.padded_vocab_size != self.cfg.vocab_size:
            # pad rows exist only for sharding divisibility: slice the
            # tied head back so argmax/softmax never see them (the
            # slice op is differentiable — pad rows get zero grad)
            logits = logits[:, :, :self.cfg.vocab_size]
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.cfg.vocab_size]),
                labels.reshape([-1, 1]), ignore_index=-100)
            return loss
        return logits if cache is None else (logits, cache)

    def serving_spec(self):
        """What the serving plane needs of this model
        (``serving/seam.py``): one kind of layer that keeps every row,
        every optional feature, the seam's generic step builders, and
        the tokens a prefill dispatch computes
        (``PREFILL_TOKENS_A_DISPATCH``)."""
        from ..serving.seam import CacheKind, ServedModel
        cfg = self.cfg
        return ServedModel(
            model=self, family="gpt",
            max_positions=cfg.max_position_embeddings,
            vocab=cfg.vocab_size,
            cache_kinds=(CacheKind("full", tuple(range(cfg.num_layers)),
                                   cfg.num_heads, cfg.head_dim),),
            lora_config=cfg,
            tokens_a_dispatch=PREFILL_TOKENS_A_DISPATCH)


def gpt2_tiny() -> GPTForCausalLM:
    return GPTForCausalLM(GPT_CONFIGS["gpt2-tiny"])


def gpt2_small() -> GPTForCausalLM:
    return GPTForCausalLM(GPT_CONFIGS["gpt2-small"])


def gpt2_medium() -> GPTForCausalLM:
    return GPTForCausalLM(GPT_CONFIGS["gpt2-medium"])
