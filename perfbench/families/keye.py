"""The Keye family (Kwai-Keye/Keye-VL-2.0-30B-A3B's language model; the
program's model is ``paddle_tpu/models/keye.py``, a configuration of the
decoder in ``models/laguna.py``): RMSNorm, plain rotary positions (theta
1e7) over all 128 dims, grouped-query attention (32 query heads over 4 KV
heads) with a per-head RMSNorm on q and k, in which EVERY QUERY READS ONLY
THE 2048 KEYS A LEARNED INDEXER PICKS (``sa_config``: 16 indexer heads of
64 over one key head), in every layer 128 SwiGLU experts of width 768
(softmax router over all 128, top 8, renormalised over the chosen; no
shared expert, no dense layer), an untied head. The vision tower is not
the language model's and is not built.

**A configuration of this family** is the published ``config.json`` key for
key (``sa_config`` and ``rope_scaling`` whole), with the cuts its
``reduced`` names, and the groups the other families' files have:
``published``, ``assumed``, ``deployment``, ``engine`` / ``engine_why``,
``dtype``, ``embed_init_std`` / ``router_init_std`` (the indexer's three
projections are drawn at ``init_std`` as every other projection is), and
for a toy twin ``moe_tile_m`` / ``moe_chunk_rows``.

**The plain reference** (``hidden`` x ``head`` = ``forward``): the
equations of ISSUE 46 in ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernels, no
paging; weights keyed by the program's parameter names and upcast where
they are used, a layer and an expert at a time. On rows ``t`` of one
request, ``u = RMSNorm_in(h)``:

    q_t = rot(RMSNorm_q(u_t Wq as [32, 128]), t)    k_t likewise, [4, 128]
    v_t = u_t Wv as [4, 128]                 query head j reads KV head j // 8
    qI_t = rot(u_t WqI as [16, 64], t)       kI_t = rot(LayerNorm(u_t WkI), t)
    w_t  = (u_t Ww) / sqrt(16 x 64)
    I[t, s] = sum_j w_t[j] relu(qI_t[j] . kI_s)            for s <= t
    S_t = the 2048 keys s <= t of largest I[t, s] (all of them while
          t + 1 <= 2048), ties to the lower s: ``lax.top_k`` of the masked
          scores, whose 2048th value is the cut; a set for EVERY row
    o_t[j] = softmax_{s in S_t}(q_t[j] . k_s[j // 8] / sqrt(128)) v_s[j // 8]

then ``h += o Wo`` and the expert layer of the Mellum family
(``families/mellum._experts``: softmax over all, top 8, renormalised,
expert by expert over the tokens that chose it). Attention is a masked
softmax over ALL keys with ``s in S_t`` as the mask, a block of
``_QUERY_BLOCK`` queries at a time (``lax.map``), so that a request padded
to the engine's ``max_len`` fits beside the served copy: neither ``[T, T]`` nor a layer's expert stack in
float32 is ever held.

Departures from the published description, shared with the program and
listed in the configuration's ``assumed``: the per-head RMSNorm on q and k
(Qwen3-MoE's, whose keys the decoder's are; the config has no key for it);
M-RoPE's three sections carry one position for text, so the rotary is
plain; the indexer takes the layer's normed input (the published lightning
indexer takes a query latent this model has not), its key passes a
LayerNorm with bias, its q and k are rotated over all 64 values with the
layer's theta (the published one rotates 64 of 128; the config gives no
split), its weights are scaled by ``1 / sqrt(heads x head_dim)``; the
published Hadamard rotation (orthogonal: no dot product changes) and
float8 storage of the indexer's keys are left out; ``q_chunk_size`` /
``kv_chunk_size`` are a tiling and change no result;
``intermediate_size`` unused; weights random from ``--seed``.

**Operation counts** (``kernel_counts``): the expert layer's four grouped
products, which are Mellum's kernels at this model's shapes (a prompt's
pass of 4096 rows x 8 choices over the whole stack of 128; a decode step's
8 rows x 8 choices over the experts the engine counted,
``engine.experts_touched`` over ``engine.sampler_dispatches`` over the 6
layers). A decode row's selected read is ``paged_decode_attn``'s walk of
the row's live blocks under the chosen set's mask (one call a layer a
step): like the GPT cells' and LFM2's its work follows the data, so its
count is the mean live blocks a decode flight over the traced interval
(``engine.kv_blocks_live`` over ``engine.decode_flights``) times one
block's K and V; the mask's bytes (a value a table position) are left
out, so the count is a floor. The selection itself (``lax.top_k`` of a
decode row's scores, the bisection of a prompt's) and a prompt's read are
XLA ops with no named kernel: they reach the ledger through a traced
line's ``breakdown`` (PERF.md section 5). The prompt path calls no flash
kernel.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import mellum
from .. import flops
from .mellum import _experts, _f32, _rms, _rotate

_QUERY_BLOCK = 128      # rows of one block of the reference's attention
_MOE_KERNELS = ("moe_up", "moe_down", "moe_up_dec", "moe_down_dec")


# ------------------------------------------------------------------ shapes

def _program():
    """The program's module of this model, or an exit by name where the
    program has none (the parent of the PR that added it)."""
    try:
        from paddle_tpu.models import keye
    except ImportError:
        raise SystemExit(
            "the keye family needs paddle_tpu.models.keye (KeyeConfig, "
            "KeyeForCausalLM), which this program does not have") from None
    return keye


def model_config(cfg: dict):
    """The program's KeyeConfig for a configuration file, checked against
    the file's own numbers."""
    KeyeConfig = _program().KeyeConfig
    extra = {k: cfg[k] for k in ("moe_tile_m", "moe_chunk_rows",
                                 "embed_init_std", "router_init_std")
             if k in cfg}
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("norm_topk_prob", True),
                      ("num_local_experts", cfg["num_experts"]),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise SystemExit(f"configuration {cfg['name']}: a keye layer "
                             f"is sparse under a renormalised router and "
                             f"the head is untied; {key} is {cfg[key]!r}")
    mc = KeyeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        rope_parameters={"full_attention": {
            "rope_type": "default", "rope_theta": float(cfg["rope_theta"])}},
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        sa_config=dict(cfg["sa_config"]), dtype=cfg["dtype"], **extra)
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    return _program().KeyeForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    raise SystemExit(
        "the keye family has no training job: its cell serves it, and no "
        "gradient passes the indexer's selection here "
        "(laguna_pretrain_8k trains the benchmark's sparse-expert model)")


# --------------------------------------------------------------- reference

def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def chosen_keys(scores, causal, topk: int):
    """``scores`` [q, s] with ``causal`` [q, s] -> the mask of each row's
    ``topk`` largest scores among its causal keys (all of them where they
    are ``topk`` or fewer): ``lax.top_k`` of the masked scores, whose last
    value is the cut; keys above it are in, keys equal to it in index
    order while there is room (how ``lax.top_k`` itself breaks ties)."""
    s = scores.shape[-1]
    if topk >= s:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    cut = jax.lax.top_k(masked, topk)[0][:, -1:]
    above = masked > cut
    tie = masked == cut
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return jnp.logical_and(causal, jnp.logical_or(
        above, jnp.logical_and(tie, jnp.cumsum(tie, axis=-1) <= room)))


def _sparse_attention(q, k, v, qi, wi, ki, topk: int, keep_sets: bool):
    """q [hq, s, d], k / v [hkv, s, d], the indexer's qi [hi, s, di], wi
    [s, hi], ki [s, di] -> ([hq, s, d], the chosen sets bool [s, s] or
    None). One block of queries at a time over all the keys."""
    hq, s, d = q.shape
    group = hq // k.shape[0]
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"the reference's attention takes rows in blocks "
                         f"of {block}; got {s}")
    col = jnp.arange(s)[None, :]

    def one(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        qib = jax.lax.dynamic_slice_in_dim(qi, lo, block, axis=1)
        wb = jax.lax.dynamic_slice_in_dim(wi, lo, block, axis=0)
        causal = col <= lo + jnp.arange(block)[:, None]
        index = jnp.sum(jax.nn.relu(jnp.einsum("jqd,kd->jqk", qib, ki))
                        * wb.T[:, :, None], axis=0)             # [q, s]
        chosen = chosen_keys(index, causal, topk)
        att = jnp.einsum("hgqd,hkd->hgqk",
                         qb.reshape(-1, group, block, d), k) \
            / jnp.sqrt(float(d))
        att = jax.nn.softmax(jnp.where(chosen, att, -jnp.inf), axis=-1)
        out = jnp.einsum("hgqk,hkd->hgqd", att, v).reshape(hq, block, d)
        return (out, chosen) if keep_sets else out
    got = jax.lax.map(one, jnp.arange(0, s, block))
    out, chosen = got if keep_sets else (got, None)
    out = out.transpose(1, 0, 2, 3).reshape(hq, s, d)
    return out, (chosen.reshape(s, s) if keep_sets else None)


def hidden(params: dict, ids, cfg: dict, collect=None, sets=None):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], one request at a time. ``collect``, a list, receives the
    hidden state after every layer; ``sets``, a list, every layer's chosen
    sets, bool [b, s, s] (toy sizes only)."""
    with jax.default_matmul_precision("highest"):
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        sa = cfg["sa_config"]
        hi, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                        sa["topk"])
        eps = cfg["rms_norm_eps"]
        rope = {"rope_theta": cfg["rope_theta"]}
        n = cfg["num_hidden_layers"]
        outs, kept, chose = [], [], []
        for row in range(ids.shape[0]):
            x = _f32(params["model.embed.weight"][ids[row]])      # [s, h]
            s = x.shape[0]
            for i in range(n):
                pre = f"model.layers.{i}."
                u = _rms(x, params[pre + "attn_norm.weight"], eps)
                qkv = u @ _f32(params[pre + "attn.qkv_proj.weight"])

                def heads(lo, count):
                    return qkv[:, lo * d:(lo + count) * d].reshape(
                        s, count, d).transpose(1, 0, 2)
                q = _rotate(_rms(
                    heads(0, hq), params[pre + "attn.q_norm.weight"], eps),
                    rope)
                k = _rotate(_rms(
                    heads(hq, kv), params[pre + "attn.k_norm.weight"], eps),
                    rope)
                # the indexer, from the same normed input
                qi = _rotate((u @ _f32(params[pre + "attn.index_q.weight"])
                              ).reshape(s, hi, di).transpose(1, 0, 2), rope)
                ki = _rotate(_layer_norm(
                    u @ _f32(params[pre + "attn.index_k.weight"]),
                    params[pre + "attn.index_k_norm_weight"],
                    params[pre + "attn.index_k_norm_bias"], eps)[None],
                    rope)[0]
                wi = u @ _f32(params[pre + "attn.index_w.weight"]) \
                    / math.sqrt(hi * di)
                o, chosen = _sparse_attention(
                    q, k, heads(hq + kv, kv), qi, wi, ki, topk,
                    sets is not None)
                x = x + o.transpose(1, 0, 2).reshape(s, hq * d) \
                    @ _f32(params[pre + "attn.o_proj.weight"])
                u = _rms(x, params[pre + "mlp_norm.weight"], eps)
                x = x + _experts(u, params[pre + "moe.router.weight"],
                                 params[pre + "moe.experts_gate_up"],
                                 params[pre + "moe.experts_down"],
                                 cfg["num_experts_per_tok"])
                if collect is not None:
                    kept.append(x)
                if sets is not None:
                    chose.append(chosen)
            outs.append(_rms(x, params["model.norm.weight"], eps))
        if collect is not None:
            collect.extend(jnp.stack(kept[i::n]) for i in range(n))
        if sets is not None:
            sets.extend(jnp.stack(chose[i::n]) for i in range(n))
        return jnp.stack(outs)


def head(params: dict, cfg: dict):
    """The output matrix float32 [h, vocab] (untied)."""
    return _f32(params["lm_head.weight"])


def forward(params: dict, ids, cfg: dict, collect=None, sets=None):
    """``ids`` int [b, s] -> logits float32 [b, s, vocab]: :func:`hidden`
    times :func:`head`."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, collect, sets) @ head(params, cfg)


def loss(params: dict, ids, labels, cfg: dict):
    """Mean next-token cross-entropy of ``labels`` [b, s]."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


# ------------------------------------------------------------------ counts

def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 of what one token passes (the chosen experts, the
    indexer's scores of the keys before it and the attention over the keys
    it keeps); no cell trains this family, the count is for a reader's
    arithmetic."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    seen = (seq + 1) / 2.0
    fwd = 2.0 * h * cfg["vocab_size"]
    fwd += cfg["num_hidden_layers"] * (
        2.0 * h * (hq + 2 * kv) * d + 2.0 * hq * d * h
        + 2.0 * h * (hi * di + di + hi) + 2.0 * hi * di * seen
        + 4.0 * hq * d * min(seen, float(sa["topk"]))
        + 2.0 * h * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * 6.0 * h
        * cfg["moe_intermediate_size"])
    return 3.0 * fwd


def paged_decode_counts(cfg: dict, counters=None):
    """(flops, bytes) of one call of ``paged_decode_attn`` (one layer of
    one decode step: every live block of every row, under the chosen
    set's mask) at the mean live blocks a decode flight over the traced
    interval; without ``counters`` the least any call reads (one block a
    slot at one byte a value); None where the run read no flight there."""
    e = cfg["engine"]
    if counters is None:
        blocks, item = float(e["max_slots"]), 1
    else:
        blocks = flops.traced_mean(counters, "kv_blocks_live",
                                   "decode_flights")
        item = counters.get("kv_item_bytes")
        if not blocks or not item:
            return None
    d = cfg["head_dim"]
    # QK^T and PV of every query head over a block's rows (masked keys are
    # multiplied too) | K and V of the block, all KV heads
    return (2 * 2.0 * cfg["num_attention_heads"] * e["block_size"] * d
            * blocks,
            2.0 * cfg["num_key_value_heads"] * e["block_size"] * d * item
            * blocks)


def kernel_counts(name: str, cfg: dict, job: dict, counters=None):
    """(flops, bytes) of one call of a named kernel in a serving job on one
    chip: the expert layer's grouped products, Mellum's kernels and its
    count (``families/mellum.kernel_counts``) at this configuration's
    shapes, the decode products' expert stack from the run's ``counters``
    where given; the decode rows' selected read
    (:func:`paged_decode_counts`). None for every other kernel."""
    if name == "paged_decode_attn":
        return paged_decode_counts(cfg, counters)
    if name not in _MOE_KERNELS:
        return None
    return mellum.kernel_counts(
        name, dict(cfg, moe_chunk_rows=int(cfg.get(
            "moe_chunk_rows", _program().PROMPT_CHUNK_ROWS))),
        job, counters)
