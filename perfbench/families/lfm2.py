"""The LFM2 family (LiquidAI/LFM2-24B-A2B; the program's model is
``paddle_tpu/models/lfm2.py``): pre-norm layers whose mixer is, by
``layer_types``, a gated short convolution (``conv``) or grouped-query
attention with a per-head RMSNorm on q and k (``full_attention``), a dense
SwiGLU in the first ``num_dense_layers`` layers and 64 SwiGLU experts
behind a sigmoid router with a selection bias in the others, RMSNorm
(``x / rms(x) * w``, eps ``norm_eps``), a tied head, no bias anywhere.
With ``T`` rows, ``h`` float32 ``[T, 2048]``::

    every layer   u = RMSNorm_op(h);  h = h + mixer(u)
                  u2 = RMSNorm_ffn(h);  h = h + ffn(u2)
    conv mixer    [B, C, x] = u W_in            W_in [2048, 6144]
                  v = B * x
                  c_t = sum_{j=0..2} w[:, j] * v_{t-2+j}     v_{<0} = 0
                  mixer = (C * c) W_out
    attn mixer    q = RMSNorm_q(u Wq as [32, 64]);  k = RMSNorm_k(u Wk as
                  [8, 64]);  v = u Wv as [8, 64];  q, k rotated (theta 1e6,
                  the whole 64);  query head j reads KV head j // 4
                  mixer = softmax(q k^T / 8, causal) v  Wo
    dense ffn     (silu(u2 W1) * (u2 W3)) W2        width 11776
    sparse ffn    s = sigmoid(u2 Wr);  E = top_4(s + b)
                  g_e = s_e / (sum_{e in E} s_e + 1e-6)
                  ffn = sum_{e in E} g_e (silu(u2 W1_e) * (u2 W3_e)) W2_e
    head          logits = RMSNorm_out(h) Emb^T

**A configuration of this family** is the published ``config.json`` key for
key, with the cuts its ``reduced`` names, and groups of its own:
``published``, ``assumed`` (what the config leaves open, each with its
reason), ``deployment``, ``engine`` / ``engine_why``, and the keys that say
how the program runs it: ``dtype``, ``head_dim`` (the config has none: 2048
/ 32), ``first_layer`` (the published layer the cut starts at:
``layer_types`` and ``num_dense_layers`` stay as published and are read
from that offset, :func:`layer_plan`), ``embed_init_std`` /
``router_init_std`` / ``final_norm_init`` / ``expert_bias_init_std`` (see
``assumed``), ``tokens_a_dispatch``, and for a toy twin ``moe_tile_m``.

**The plain reference** (``hidden`` x ``head`` = ``forward``; ``loss``):
the equations above in ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching: the convolution as three shifted products, attention as a masked
softmax a block of 256 queries at a time (Mellum's reference's, imported),
the experts as a loop over ALL 64 with a mask (a ``lax.scan`` over the
stack, so one expert at a time is upcast to float32: a whole layer's stack
in float32 is 2.4 GB beside the run's 12 GB). Weights keyed by the
program's parameter names (linear weights ``[in, out]``; ``conv.in_proj``
holds B, then C, then x along its output axis; ``attn.qkv_proj`` the 32
query heads, then K, then V; ``gate_up`` gate first). Written from the
equations, not from ``models/lfm2.py``.

Departures from the published description, shared with the program and
listed in the configuration's ``assumed``: the tied head; the order of
``W_in``'s thirds; the q/k norm before the rotary; 1e-6 added to the chosen
scores' sum (the published code's); ``v = B * x`` rounded to the served
dtype where the program serves bfloat16 (the reference keeps float32: that
rounding is part of what the tolerance covers); random weights, a zero-mean
final norm gain and ``expert_bias ~ N(0, sigma_b)``.

**Operation counts** (``kernel_counts``; the serving job is the cell's
traffic file), each operand and the result once:

- ``moe_up_dec`` / ``moe_down_dec`` (a decode step's grouped products,
  tiles of 16 rows): ``max_slots x 4`` pairs a call; the expert stack read
  is the engine's own count over the traced interval
  (``engine.experts_touched`` over ``engine.sampler_dispatches``, both
  ``.traced``, over the sparse layers), else the expectation at
  ``max_slots`` uniform rows. The weights are the bytes.
- ``moe_up`` / ``moe_down`` (a prefill dispatch's): ``rows x bucket x 4``
  pairs at the traffic's MEAN dispatch and the whole stack of 64.
- ``flash_fwd_full``: the attention layers' forward over a dispatch's own
  rows, 32 query heads on 8 KV heads of 64, causal, at the mean dispatch
  that reaches the kernel (``FLAGS_pallas_min_seq``).
- ``paged_decode_attn``: one layer of one decode step; it copies the
  blocks the rows' lengths stand on (K and V, all 8 KV heads) and
  multiplies the 4 query rows of a KV head with each:
  ``engine.kv_blocks_live`` over ``engine.decode_flights`` (``.traced``)
  blocks a call; the dead slots' one block is left out, so a floor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import flops
from .mellum import (_attention, _f32, _mean_keys, _rms, _rotate,
                     expected_experts_touched)

_BYTES = 2              # the served copy is bfloat16
RENORM_EPS = 1e-6       # added to the chosen scores' sum (``assumed``)


# ------------------------------------------------------------------ shapes

def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_plan(cfg: dict):
    """(mixer kind, whether its MLP is the dense one) of every layer the
    file runs: ``num_hidden_layers`` layers from the published layer
    ``first_layer`` (0 where the file has no such key) of the published
    ``layer_types`` and ``num_dense_layers``, both kept as published."""
    first = int(cfg.get("first_layer", 0))
    return [(cfg["layer_types"][first + i],
             first + i < cfg["num_dense_layers"])
            for i in range(cfg["num_hidden_layers"])]


def model_config(cfg: dict):
    """The program's Lfm2Config for a configuration file, checked against
    the file's own numbers."""
    from paddle_tpu.models import Lfm2Config
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("norm_topk_prob", True), ("model_type", "lfm2_moe")):
        if cfg.get(key, want) != want:
            raise SystemExit(f"configuration {cfg['name']}: the lfm2 family "
                             f"runs {key} = {want!r}; the file has "
                             f"{cfg[key]!r}")
    extra = {k: (tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
             for k in ("embed_init_std", "router_init_std", "moe_tile_m",
                       "final_norm_init", "tokens_a_dispatch") if k in cfg}
    rope = dict(cfg["rope_parameters"])
    plan = layer_plan(cfg)
    mc = Lfm2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=_head_dim(cfg), layer_types=tuple(k for k, _ in plan),
        num_dense_layers=sum(dense for _, dense in plan),
        conv_L_cache=cfg["conv_L_cache"],
        rope_parameters={"full_attention": {
            "rope_type": rope.get("rope_type", "default"),
            "rope_theta": float(rope["rope_theta"])}},
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=cfg["norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        router_bias_init_std=float(cfg.get("expert_bias_init_std", 0.0)),
        router_renorm_eps=RENORM_EPS, dtype=cfg["dtype"], **extra)
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    try:
        from paddle_tpu.models import Lfm2ForCausalLM
    except ImportError:
        # a program from before the model (the benchmark's files laid over
        # a parent checkout): refused at once and by name
        raise SystemExit(
            f"configuration {cfg['name']}: the lfm2 family needs "
            f"paddle_tpu.models.Lfm2ForCausalLM, which this program does "
            f"not have") from None
    return Lfm2ForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    raise SystemExit(
        "the lfm2 family has no training job: its cell serves it "
        "(laguna_pretrain_8k trains the benchmark's sparse-expert model, "
        "and one chip's eighth of this one would repeat it)")


# --------------------------------------------------------------- reference

def _short_conv(u, params: dict, pre: str, taps: int):
    """The gated short convolution on ``u`` [s, h] -> [s, h]."""
    bcx = u @ _f32(params[pre + "in_proj.weight"])
    h = bcx.shape[1] // 3
    b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    v = b * x
    s = v.shape[0]
    w = _f32(params[pre + "conv_weight"])                      # [h, taps]
    rows = jnp.concatenate([jnp.zeros((taps - 1, h), jnp.float32), v])
    conv = sum(w[None, :, j] * rows[j:j + s] for j in range(taps))
    return (c * conv) @ _f32(params[pre + "out_proj.weight"])


def _experts(u, router, bias, w13, w2, top_k: int, scale: float):
    """``sum_{e in E} g_e FFN_e(u)`` for u [t, h]: ``s = sigmoid(u Wr)``,
    ``E = top_k(s + bias)``, ``g_e = scale * s_e / (sum_E s + 1e-6)``.
    Every expert over every row, masked: one expert's weights in float32
    at a time."""
    width = w2.shape[1]
    s = jax.nn.sigmoid(u @ _f32(router))
    _, idx = jax.lax.top_k(s + _f32(bias)[None, :], top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    gate = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                             + RENORM_EPS)

    def one(out, e_w):
        e, g_u, dn = e_w
        g_e = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)     # [t]
        gu = u @ _f32(g_u)
        y = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ _f32(dn)
        return out + g_e[:, None] * y, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (jnp.arange(w13.shape[0]), w13, w2))
    return out


def hidden(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], one request at a time. ``collect``, a list, receives the
    hidden state after every layer."""
    with jax.default_matmul_precision("highest"):
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     _head_dim(cfg))
        eps, width = cfg["norm_eps"], cfg["intermediate_size"]
        plan = layer_plan(cfg)
        n_layers = len(plan)
        rope = {"rope_type": "default",
                "rope_theta": float(cfg["rope_parameters"]["rope_theta"])}
        outs, kept = [], []
        for row in range(ids.shape[0]):
            x = _f32(params["model.embed.weight"][ids[row]])      # [s, h]
            s = x.shape[0]
            for i, (kind, dense) in enumerate(plan):
                pre = f"model.layers.{i}."
                u = _rms(x, params[pre + "operator_norm.weight"], eps)
                if kind == "conv":
                    x = x + _short_conv(u, params, pre + "conv.",
                                        cfg["conv_L_cache"])
                else:
                    qkv = u @ _f32(params[pre + "attn.qkv_proj.weight"])

                    def heads(lo, n):
                        return qkv[:, lo * d:(lo + n) * d].reshape(
                            s, n, d).transpose(1, 0, 2)
                    q = _rms(heads(0, hq),
                             params[pre + "attn.q_norm.weight"], eps)
                    k = _rms(heads(hq, kv),
                             params[pre + "attn.k_norm.weight"], eps)
                    o = _attention(_rotate(q, rope), _rotate(k, rope),
                                   heads(hq + kv, kv), 0)
                    x = x + o.transpose(1, 0, 2).reshape(s, hq * d) \
                        @ _f32(params[pre + "attn.o_proj.weight"])
                u = _rms(x, params[pre + "ffn_norm.weight"], eps)
                if dense:
                    gu = u @ _f32(params[pre + "mlp.gate_up.weight"])
                    x = x + (jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
                        @ _f32(params[pre + "mlp.down.weight"])
                else:
                    x = x + _experts(
                        u, params[pre + "moe.router.weight"],
                        params[pre + "moe.expert_bias"],
                        params[pre + "moe.experts_gate_up"],
                        params[pre + "moe.experts_down"],
                        cfg["num_experts_per_tok"],
                        float(cfg["routed_scaling_factor"]))
                if collect is not None:
                    kept.append(x)
            outs.append(_rms(x, params["model.norm.weight"], eps))
        if collect is not None:
            collect.extend(jnp.stack(kept[i::n_layers])
                           for i in range(n_layers))
        return jnp.stack(outs)


def head(params: dict, cfg: dict):
    """The output matrix float32 [h, vocab]: the embedding, tied,
    transposed."""
    return _f32(params["model.embed.weight"]).T


def forward(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> logits float32 [b, s, vocab]: :func:`hidden`
    times :func:`head`."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, collect) @ head(params, cfg)


def loss(params: dict, ids, labels, cfg: dict):
    """Mean next-token cross-entropy of ``labels`` [b, s]."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


# ------------------------------------------------------------------ counts

def sparse_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in layer_plan(cfg))


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 of what one token passes (the chosen experts only); no
    cell trains this family, the count is for a reader's arithmetic."""
    h, d = cfg["hidden_size"], _head_dim(cfg)
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    fwd = 2.0 * h * cfg["vocab_size"]
    for kind, dense in layer_plan(cfg):
        if kind == "conv":
            fwd += 2.0 * h * 3 * h + 2.0 * h * h \
                + 2.0 * h * (cfg["conv_L_cache"] + 1)
        else:
            fwd += 2.0 * h * (hq + 2 * kv) * d + 2.0 * hq * d * h \
                + 4.0 * hq * d * _mean_keys(seq, 0)
        if dense:
            fwd += 6.0 * h * cfg["intermediate_size"]
        else:
            fwd += 2.0 * h * cfg["num_experts"] \
                + cfg["num_experts_per_tok"] * 6.0 * h \
                * cfg["moe_intermediate_size"]
    return 3.0 * fwd


def experts_touched(cfg: dict, counters=None) -> float:
    """Experts whose weights one expert layer reads in a decode step: the
    engine's count over the traced interval a sparse layer, where the
    run's ``counters`` have it; the expectation at ``max_slots`` uniform
    rows where they do not."""
    a_step = flops.traced_mean(counters, "experts_touched",
                               "sampler_dispatches")
    if a_step:
        return a_step / sparse_layers(cfg)
    return expected_experts_touched(cfg, cfg["engine"]["max_slots"])


def _dispatches(cfg: dict, job: dict):
    """(rows, bucket) of the prefill dispatch of every prompt of the job's
    multiset."""
    from .. import traffic as T
    from paddle_tpu.models import Lfm2Config
    e = cfg["engine"]
    budget = int(cfg.get("tokens_a_dispatch", Lfm2Config.tokens_a_dispatch))
    out = []
    for p, _ in T.multiset(job):
        bucket = T.bucket_for(p, e["buckets"])
        out.append((max(1, min(e["max_slots"], budget // bucket)), bucket))
    return out


def _mean(calls):
    return tuple(sum(c) / len(calls) for c in zip(*calls)) if calls else None


def paged_decode_counts(cfg: dict, counters=None):
    """(flops, bytes) of one call of ``paged_decode_attn`` (one attention
    layer of one decode step) at the mean live blocks a decode flight over
    the traced interval; without ``counters`` the least any call reads
    (one block a slot at one byte a value); None where the run read no
    flight there."""
    e = cfg["engine"]
    if counters is None:
        blocks, item = float(e["max_slots"]), 1
    else:
        blocks = flops.traced_mean(counters, "kv_blocks_live",
                                   "decode_flights")
        item = counters.get("kv_item_bytes")
        if not blocks or not item:
            return None
    d = _head_dim(cfg)
    # QK^T and PV of every query head over a block's rows | K and V of the
    # block, all KV heads
    return (2 * 2.0 * cfg["num_attention_heads"] * e["block_size"] * d
            * blocks,
            2.0 * cfg["num_key_value_heads"] * e["block_size"] * d * item
            * blocks)


def kernel_counts(name: str, cfg: dict, job: dict, counters=None):
    """(flops, bytes) of one call of a named kernel in a serving job on one
    chip (see the module's docstring)."""
    if job.get("kind") not in ("closed_loop", "open_loop"):
        return None
    h, d, f = cfg["hidden_size"], _head_dim(cfg), \
        cfg["moe_intermediate_size"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    if name == "paged_decode_attn":
        return paged_decode_counts(cfg, counters)
    wide = {"moe_up": (h, 2 * f), "moe_down": (f, h)}
    stem = name[:-4] if name.endswith("_dec") else name
    if stem in wide:
        n_in, n_out = wide[stem]

        def one(rows, stack):
            return (2.0 * rows * n_in * n_out,
                    (rows * n_in + stack * n_in * n_out + rows * n_out)
                    * float(_BYTES))
        if name.endswith("_dec"):
            return one(cfg["engine"]["max_slots"] * k,
                       experts_touched(cfg, counters))
        return _mean([one(rows * s * k, e)
                      for rows, s in _dispatches(cfg, job)])
    if name == "flash_fwd_full":
        from paddle_tpu import flags
        least = int(flags.get_flag("pallas_min_seq"))
        # QK^T and PV; reads q | k v, writes o and the float32 lse
        return _mean([
            (rows * 2 * 2.0 * hq * s * _mean_keys(s, 0) * d,
             rows * ((2 * hq + 2 * kv) * s * d * float(_BYTES)
                     + hq * s * 4.0))
            for rows, s in _dispatches(cfg, job) if s >= least])
    return None
