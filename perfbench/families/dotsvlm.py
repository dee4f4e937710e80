"""The dots.vlm1 family (rednote-hilab/dots.vlm1.inst's language model; the
program's model is ``paddle_tpu/models/dotsvlm.py``, a configuration of the
decoder in ``models/laguna.py`` plus a latent-attention layer): RMSNorm,
MULTI-HEAD LATENT ATTENTION (128 heads whose keys and values are
up-projections of ONE 512-value latent a token, a rotary on 64 of a key's
192 values with one rotated key for all heads, YaRN that scales the whole
score), a SwiGLU of 18432 in the first ``first_k_dense_replace`` layers and
in every later layer 256 sigmoid-scored experts of 2048 CHOSEN BY GROUP
(top 8 inside the 4 best of 8 groups, by ``s + e_score_correction_bias``,
weighed by the chosen ``s`` renormalised x 2.5) beside one shared expert, an
untied head. The vision tower and the multi-token-prediction module are not
the served language model's and are not built.

**A configuration of this family** is the published ``config.json`` key for
key (``rope_scaling`` whole), with the cuts its ``reduced`` names, and the
groups the other families' files have: ``published``, ``assumed``,
``deployment``, ``engine`` / ``engine_why``, ``dtype``, ``embed_init_std``
/ ``router_init_std`` / ``router_bias_init_std`` / ``attn_q_init_std`` /
``attn_out_init_std``, and for a toy twin ``moe_tile_m`` /
``moe_chunk_rows``. **A share**: ``n_routed_experts`` is what THIS chip
holds of each expert layer (experts ``0 .. n_routed_experts``) and
``expert_share`` how many chips share a layer, so the router is
``n_routed_experts x expert_share`` wide; ``vocab_size`` is the held rows
(from row 0) and ``vocab_share`` the cut.

**The plain reference** (``hidden`` x ``head`` = ``forward``): the
equations of ISSUE 49 in ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, the MATERIALISED form only (no
cache, no absorbed read, no kernels, no paging), the same share; weights
keyed by the program's parameter names and upcast where they are used, a
group of heads, a slice of the dense MLP or an expert at a time, so that a
request padded to the engine's ``max_len`` fits beside the served copy. On
rows ``t`` of one request, ``u = RMSNorm_in(h)``:

    c_q = RMSNorm(u W_qa)                 q[j] = c_q W_qb[j] = [q_n | q_r]
    [c | k_r] = u W_kva                   c = RMSNorm(c)
    q_r = rot(q_r, t), k_r = rot(k_r, t)  one k_r for all heads
    [k_n[j] | v[j]] = c W_kvb[j]
    o_t[j] = softmax_{s <= t}((q_n[j] . k_n_s[j] + q_r[j] . k_r_s)
                              x (dn + dr)^-0.5 x m^2) v_s[j]

then ``h += concat(o) W_o``, ``u = RMSNorm(h)`` and the dense SwiGLU or

    s = sigmoid(u W_g)   sel = s + b   a group's score = its 2 largest sel
    keep the topk_group best groups; S = the top_k largest sel inside them
    h += sum_{e in S, e held} 2.5 s_e / sum_S s  FFN_e(u) + FFN_shared(u)

Departures from the published description, shared with the program and
listed in the configuration's ``assumed``: see the file.

**Operation counts** (``kernel_counts``): ``mla_decode_attn`` (one call a
layer a decode step: every live block of every row, both products) at the
mean live blocks a decode flight over the traced interval
(``engine.kv_blocks_live`` over ``engine.decode_flights``);
``mla_prompt_attn`` (one call a pass of 32 heads a layer a prompt) at the
mean live (query, key) pairs a head (``engine.mla_prompt_pairs`` over
``engine.mla_prompt_reads``: the prompt's causal triangle, ``live (live +
1) / 2``; neither the bucket's rectangle nor the tiles the kernel runs); the expert layer's grouped products at
the rows the held experts got (a prompt's pass: the expectation; a decode
step: ``engine.expert_pairs`` and ``engine.experts_touched`` a step a
layer).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import flops
from .mellum import _f32, _inv_freq, _rms

_QUERY_BLOCK = 128      # rows of one block of the reference's attention
_HEAD_GROUP = 16        # heads whose q, k, v are held at once
_MLP_SLICE = 2048       # columns of the dense MLP multiplied at a time
_EXPERT_ROWS = 1024     # tokens of one expert multiplied at a time
_BYTES = 2              # the served copy is bfloat16
_MOE_KERNELS = ("moe_up", "moe_down", "moe_up_dec", "moe_down_dec")


# ------------------------------------------------------------------ shapes

def _program():
    """The program's module of this model, or an exit by name where the
    program has none (the parent of the PR that added it)."""
    try:
        from paddle_tpu.models import dotsvlm
    except ImportError:
        raise SystemExit(
            "the dotsvlm family needs paddle_tpu.models.dotsvlm "
            "(DotsVlmConfig, DotsVlmForCausalLM), which this program does "
            "not have") from None
    return dotsvlm


def router_width(cfg: dict) -> int:
    return cfg["n_routed_experts"] * int(cfg.get("expert_share", 1))


def _rope(cfg: dict) -> dict:
    """``rope_scaling`` as the rotary's parameters: the cosines' factor is
    ``m(mscale) / m(mscale_all_dim)``."""
    rs = dict(cfg["rope_scaling"])
    return {"rope_type": rs["type"], "rope_theta": float(cfg["rope_theta"]),
            "factor": rs["factor"], "beta_fast": rs["beta_fast"],
            "beta_slow": rs["beta_slow"],
            "original_max_position_embeddings":
                rs["original_max_position_embeddings"],
            "mscale": rs["mscale"], "mscale_all_dim": rs["mscale_all_dim"]}


def _mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def model_config(cfg: dict):
    """The program's DotsVlmConfig for a configuration file, checked
    against the file's own numbers."""
    DotsVlmConfig = _program().DotsVlmConfig
    extra = {k: cfg[k] for k in ("moe_tile_m", "moe_chunk_rows",
                                 "embed_init_std", "router_init_std",
                                 "router_bias_init_std",
                                 "attn_q_init_std", "attn_out_init_std")
             if k in cfg}
    for key, want in (("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                      ("n_shared_experts", 1), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise SystemExit(
                f"configuration {cfg['name']}: a dotsvlm expert layer has "
                f"sigmoid scores chosen by a bias and by group, one shared "
                f"expert, and the head is untied; {key} is {cfg[key]!r}")
    held, width = cfg["n_routed_experts"], router_width(cfg)
    rows, share = cfg["vocab_size"], int(cfg.get("vocab_share", 1))
    mc = DotsVlmConfig(
        vocab_size=rows * share, hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        rope_parameters={"full_attention": _rope(cfg)},
        num_experts=width, num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        moe_routed_scaling_factor=cfg["routed_scaling_factor"],
        router_groups=cfg["n_group"], router_topk_groups=cfg["topk_group"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_experts=None if held == width else (0, held),
        held_vocab=None if share == 1 else (0, rows),
        dtype=cfg["dtype"], **extra)
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    return _program().DotsVlmForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    raise SystemExit(
        "the dotsvlm family has no training job: its cell serves it (at 16 "
        "bytes a parameter no cut inside the floors fits one chip; "
        "laguna_pretrain_8k trains the benchmark's sparse-expert model)")


# --------------------------------------------------------------- reference

def _rotate(x, inv, att):
    """x [s, .., d] rotated (rotate-half) at the positions 0 .. s - 1."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attend(q_n, q_r, k_n, k_r, v, scale: float):
    """One group of heads: q_n / k_n [s, g, dn], q_r [s, g, dr], k_r
    [s, dr], v [s, g, dv] -> [s, g x dv]; causal, a block of queries at a
    time over all the keys."""
    s, g, _ = q_n.shape
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"the reference's attention takes rows in blocks "
                         f"of {block}; got {s}")
    col = jnp.arange(s)[None, :]

    def one(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_n, lo, block, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, lo, block, axis=0)
        seen = col <= lo + jnp.arange(block)[:, None]           # [q, s]
        att = (jnp.einsum("qgd,kgd->gqk", qn, k_n)
               + jnp.einsum("qgd,kd->gqk", qr, k_r)) * scale
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kgd->qgd", att, v).reshape(block, -1)
    return jax.lax.map(one, jnp.arange(0, s, block)).reshape(s, -1)


def _latent_attention(u, params, pre: str, cfg: dict):
    """The layer's normed input u [s, h] -> [s, h]."""
    heads = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    rope = _rope(cfg)
    all_dim = _mscale(rope["factor"], rope["mscale_all_dim"])
    inv, _ = _inv_freq(dict(rope, attention_factor=1.0), dr)
    att = _mscale(rope["factor"], rope["mscale"]) / all_dim
    scale = all_dim * all_dim / math.sqrt(dn + dr)
    s = u.shape[0]
    c_q = _rms(u @ _f32(params[pre + "q_a_proj.weight"]),
               params[pre + "q_a_norm.weight"], eps)
    kv = u @ _f32(params[pre + "kv_a_proj.weight"])
    c = _rms(kv[:, :r], params[pre + "kv_a_norm.weight"], eps)
    k_r = _rotate(kv[:, r:], inv, att)
    g = min(_HEAD_GROUP, heads)
    # a group of heads at a time: its columns of W_qb and W_kvb, its rows
    # of W_o
    w_qb = params[pre + "q_b_proj.weight"].reshape(
        -1, heads // g, g * (dn + dr)).transpose(1, 0, 2)
    w_kvb = params[pre + "kv_b_proj.weight"].reshape(
        r, heads // g, g * (dn + dv)).transpose(1, 0, 2)
    w_o = params[pre + "o_proj.weight"].reshape(heads // g, g * dv, -1)

    def group(y, w):
        qb, kvb, ob = w
        q = (c_q @ _f32(qb)).reshape(s, g, dn + dr)
        k_v = (c @ _f32(kvb)).reshape(s, g, dn + dv)
        o = _attend(q[..., :dn], _rotate(q[..., dn:], inv, att),
                    k_v[..., :dn], k_r, k_v[..., dn:], scale)
        return y + o @ _f32(ob), None
    return jax.lax.scan(group, jnp.zeros_like(u), (w_qb, w_kvb, w_o))[0]


def _swiglu(u, gate_up, down):
    """``(silu(u W1) * (u W3)) W2`` with W1 and W3 side by side in
    ``gate_up``, ``_MLP_SLICE`` columns at a time."""
    width = down.shape[0]
    step = min(_MLP_SLICE, width)
    if width % step:
        raise ValueError(f"an MLP of {width} in slices of {step}")
    n = width // step
    w1 = gate_up[:, :width].reshape(-1, n, step).transpose(1, 0, 2)
    w3 = gate_up[:, width:].reshape(-1, n, step).transpose(1, 0, 2)

    def one(y, w):
        a, b, d = w
        return y + (jax.nn.silu(u @ _f32(a)) * (u @ _f32(b))) @ _f32(d), None
    return jax.lax.scan(one, jnp.zeros_like(u),
                        (w1, w3, down.reshape(n, step, -1)))[0]


def grouped_choice(s, bias, groups: int, topk_group: int, top_k: int):
    """``s`` [t, e] the experts' scores, ``bias`` [e] -> (chosen int [t,
    top_k], their ``s`` [t, top_k]): by ``sel = s + bias``, the ``top_k``
    largest inside the ``topk_group`` groups whose 2 largest ``sel`` sum
    highest; ties to the lower index (``lax.top_k``'s order)."""
    t, e = s.shape
    sel = s + _f32(bias)
    by_group = sel.reshape(t, groups, e // groups)
    best = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
    kept = jax.lax.top_k(best, topk_group)[1]                  # [t, kg]
    eligible = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    sel = jnp.where(jnp.repeat(eligible, e // groups, axis=1), sel,
                    -jnp.inf)
    idx = jax.lax.top_k(sel, top_k)[1]
    return idx, jnp.take_along_axis(s, idx, axis=-1)


def _experts(u, params, pre: str, cfg: dict):
    """The expert layer of this share for u [t, h]: the held experts'
    terms of the routed sum, and the shared expert."""
    t, _ = u.shape
    w13, w2 = params[pre + "experts_gate_up"], params[pre + "experts_down"]
    width = w2.shape[1]
    s = jax.nn.sigmoid(u @ _f32(params[pre + "router.weight"]))
    idx, top = grouped_choice(s, params[pre + "expert_bias"], cfg["n_group"],
                              cfg["topk_group"], cfg["num_experts_per_tok"])
    weight = top / jnp.sum(top, axis=-1, keepdims=True) \
        * cfg["routed_scaling_factor"]
    rows = min(_EXPERT_ROWS, t)

    def one(out, e_w):
        e, g_u, dn = e_w
        g_u, dn = _f32(g_u), _f32(dn)
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)   # [t]
        chose = jnp.any(idx == e, axis=-1)
        n = jnp.sum(chose)
        order = jnp.argsort(jnp.logical_not(chose), stable=True)

        def more(state):
            return state[0] * rows < n

        def chunk(state):
            k, out = state
            start = jnp.minimum(k * rows, t - rows)
            tok = jax.lax.dynamic_slice_in_dim(order, start, rows)
            at = start + jnp.arange(rows)
            mine = jnp.logical_and(at >= k * rows, at < n)
            gu = u[tok] @ g_u
            y = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ dn
            y = jnp.where(mine[:, None], w_e[tok][:, None] * y, 0.0)
            return k + 1, out.at[tok].add(y)
        return jax.lax.while_loop(more, chunk, (0, out))[1], None
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (jnp.arange(w13.shape[0]), w13, w2))
    return out + _swiglu(u, params[pre + "shared.gate_up.weight"],
                         params[pre + "shared.down.weight"])


def hidden(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], one request at a time. ``collect``, a list, receives the
    hidden state after every layer."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n = cfg["num_hidden_layers"]
        outs, kept = [], []
        for row in range(ids.shape[0]):
            x = _f32(params["model.embed.weight"][ids[row]])      # [s, h]
            for i in range(n):
                pre = f"model.layers.{i}."
                u = _rms(x, params[pre + "attn_norm.weight"], eps)
                x = x + _latent_attention(u, params, pre + "attn.", cfg)
                u = _rms(x, params[pre + "mlp_norm.weight"], eps)
                if i < cfg["first_k_dense_replace"]:
                    x = x + _swiglu(u, params[pre + "mlp.gate_up.weight"],
                                    params[pre + "mlp.down.weight"])
                else:
                    x = x + _experts(u, params, pre + "moe.", cfg)
                if collect is not None:
                    kept.append(x)
            outs.append(_rms(x, params["model.norm.weight"], eps))
        if collect is not None:
            collect.extend(jnp.stack(kept[i::n]) for i in range(n))
        return jnp.stack(outs)


def head(params: dict, cfg: dict):
    """The output matrix float32 [h, held vocabulary rows] (untied)."""
    return _f32(params["lm_head.weight"])


def forward(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> logits float32 [b, s, vocab]: :func:`hidden`
    times :func:`head`."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, collect) @ head(params, cfg)


def loss(params: dict, ids, labels, cfg: dict):
    raise SystemExit(
        "the dotsvlm family has no loss: its cell serves it, and the check "
        "compares logits (families/dotsvlm.forward)")


# ------------------------------------------------------------------ counts

def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 of what one token passes on this share (the latent
    attention's five projections, the materialised read over the keys
    before it, the dense MLP or the router, the shared expert and the held
    chosen experts); no cell trains this family, the count is for a
    reader's arithmetic."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    seen = (seq + 1) / 2.0
    attn = 2.0 * (h * rq + rq * heads * (dn + dr) + h * (r + dr)
                  + r * heads * (dn + dv) + heads * dv * h) \
        + 2.0 * heads * (dn + dr + dv) * seen
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    held = k * cfg["n_routed_experts"] / float(router_width(cfg))
    fwd = 2.0 * h * cfg["vocab_size"] \
        + cfg["num_hidden_layers"] * attn \
        + dense * 6.0 * h * cfg["intermediate_size"] \
        + sparse * (2.0 * h * router_width(cfg)
                    + (cfg["n_shared_experts"] + held) * 6.0 * h * f)
    return 3.0 * fwd


def mla_decode_counts(cfg: dict, counters=None):
    """(flops, bytes) of one call of ``mla_decode_attn`` (one layer of one
    decode step: every live block of every row) at the mean live blocks a
    decode flight over the traced interval; without ``counters`` the least
    any call reads (one block a slot); None where the run read no flight
    there."""
    e = cfg["engine"]
    if counters is None:
        blocks, item = float(e["max_slots"]), _BYTES
    else:
        blocks = flops.traced_mean(counters, "kv_blocks_live",
                                   "decode_flights")
        item = counters.get("kv_item_bytes")
        if not blocks or not item:
            return None
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    keys = e["block_size"] * blocks
    # every head's score over the row's r + dr values and its weighted sum
    # of the r latents | the block as the pool holds it
    return (2.0 * cfg["num_attention_heads"] * (2 * r + dr) * keys,
            float(r + dr) * item * keys)


def mla_prompt_counts(cfg: dict, job: dict, counters=None):
    """(flops, bytes) of one call of ``mla_prompt_attn`` (one pass of
    heads of one layer of one prompt) at the mean LIVE pairs a head over
    the traced interval (``engine.mla_prompt_pairs`` over
    ``engine.mla_prompt_reads``: the causal triangle ``live (live + 1) /
    2``, NOT the bucket's rectangle and NOT the tiles the kernel runs: the
    masked half of a diagonal tile is the kernel's cost); without ``counters`` the mean
    over the job's multiset, each prompt in its bucket; None where the run
    read no prompt there."""
    program = _program()
    from paddle_tpu.ops.pallas.mla_attention import prompt_pairs
    if counters is None:
        from .. import traffic as T
        each = [prompt_pairs(1, T.bucket_for(p, cfg["engine"]["buckets"]), p)
                for p, _ in T.multiset(job)]
        pairs = sum(each) / float(len(each))
    else:
        pairs = flops.traced_mean(counters, "mla_prompt_pairs",
                                  "mla_prompt_reads")
        if not pairs:
            return None
    heads = math.gcd(cfg["num_attention_heads"], program.PROMPT_HEADS)
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    # rows of a square of that many pairs: q, k_n, v read and o written
    # once a head at least (the key tiles are read once a query block: a
    # floor)
    rows = math.sqrt(2.0 * pairs)
    return (2.0 * heads * (dn + dr + dv) * pairs,
            heads * rows * (2 * dn + dr + 2 * dv) * float(_BYTES))


def kernel_counts(name: str, cfg: dict, job: dict, counters=None):
    """(flops, bytes) of one call of a named kernel in a serving job on one
    chip: the two latent reads (:func:`mla_decode_counts`,
    :func:`mla_prompt_counts`) and the expert layer's grouped products at
    the rows the held experts got: a prompt's pass of ``moe_chunk_rows``
    rows at the expectation (``top_k x held / routed`` pairs a row) over
    the held stack; a decode step's from the run's ``counters``
    (``engine.expert_pairs`` rows over ``engine.experts_touched`` experts,
    a step a sparse layer; without them 32 live rows under uniform
    routing). None for every other kernel."""
    if job.get("kind") not in ("closed_loop", "open_loop"):
        return None
    if name == "mla_decode_attn":
        return mla_decode_counts(cfg, counters)
    if name == "mla_prompt_attn":
        return mla_prompt_counts(cfg, job, counters)
    if name not in _MOE_KERNELS:
        return None
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_in, n_out = {"moe_up": (h, 2 * f), "moe_down": (f, h)}[
        name[:-4] if name.endswith("_dec") else name]
    held = cfg["n_routed_experts"]
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    if name.endswith("_dec") and counters is None:
        # every slot live under uniform routing
        rows = cfg["engine"]["max_slots"] * cfg["num_experts_per_tok"] \
            * held / float(router_width(cfg))
        stack = held * (1.0 - (1.0 - 1.0 / held) ** rows)
    elif name.endswith("_dec"):
        rows = flops.traced_mean(counters, "expert_pairs",
                                 "sampler_dispatches")
        stack = flops.traced_mean(counters, "experts_touched",
                                  "sampler_dispatches")
        if not rows or not stack:
            return None
        rows, stack = rows / sparse, stack / sparse
    else:
        chunk = int(cfg.get("moe_chunk_rows",
                            _program().PROMPT_CHUNK_ROWS))
        rows = chunk * cfg["num_experts_per_tok"] * held \
            / float(router_width(cfg))
        stack = held
    return (2.0 * rows * n_in * n_out,
            (rows * n_in + stack * n_in * n_out + rows * n_out)
            * float(_BYTES))
