"""The Laguna family (poolside/Laguna-XS.2; the program's model is
``paddle_tpu/models/laguna.py``): RMSNorm, rotary positions by layer kind
(plain on sliding-window layers, partial YaRN on full layers), grouped-query
attention with a head count that differs by layer, a sliding window, a
per-head sigmoid output gate, SwiGLU, a sparse-expert MLP (sigmoid router,
top-k, one shared expert), an untied head.

**A configuration of this family** is the published ``config.json`` key for
key, with the cuts its ``reduced`` names, and three groups of its own:

- ``published``: the published value of every key the file runs smaller;
- ``held``: the share of each layer this chip holds, ``[lo, hi)`` of the
  routed ``experts``, of the ``kv_heads`` (each with its query group, the
  group's gate columns and output-projection rows) and of the
  ``vocab_rows`` (embedding rows and head columns). The top-level
  ``vocab_size`` is the number of held rows, because the harness draws its
  ids below it; ``num_experts`` and the head counts stay the published
  ones, because the router scores all experts and the query grouping
  follows the published counts;
- ``trainer``: AMP level, moment dtype, recompute, retain_grads, learning
  rate, as the ``gpt2`` family's, and optionally ``lr_scale``: parameter
  name -> the factor on its learning rate (the framework's per-parameter
  ``lr_scale``, what ``ParamAttr(learning_rate=...)`` sets).

A toy twin may give ``moe_tile_m`` (rows of a tile of the expert layer's
buffer; the kernels' 128 otherwise). No other key reaches the program's
configuration: what a share implies (its router's update withheld) the
model derives from ``held`` itself.

The stated deployment is a group of chips that shares each layer
(tensor-parallel over heads and vocabulary, expert-parallel over the routed
experts, every chip seeing the same tokens); the program and this reference
both compute ONE chip's part: choices that fall on absent experts are left
out of the sum, absent heads are not computed, the loss is the
cross-entropy over the held vocabulary rows. Nothing stands in for the
absent chips.

**The plain reference** (``hidden`` x ``head`` = ``forward``; ``loss``):
``jax.numpy`` float32 at ``highest`` precision, no kernels, a loop over
the held experts with a mask
of the tokens routed to each, attention computed in query blocks so that a
row of 8192 fits. Departures from the published model, shared with the
program and listed in the configuration's ``assumed``: SiLU (the config has
no ``hidden_act``); no q/k normalisation (no key for it); the gate is one
sigmoid value a head computed from the normed input (``gating: true`` and
the published 33.4B parameters admit no larger gate); router scores are
sigmoid, normalised over the chosen experts and multiplied by
``moe_routed_scaling_factor``, applied to the experts' outputs
(``moe_apply_router_weight_on_input`` false); pre-norm placement; weights
random from ``--seed``. Weights are keyed by the program's parameter names;
linear weights are ``[in, out]``; ``qkv_proj`` holds the held query heads,
then the held K heads, then V along its output axis; ``experts_gate_up``
is ``[held, hidden, 2 x width]`` with gate first.

**Operation counts**: ``train_flops_per_token`` counts what a token passes
on this chip (held heads, the keys the causal mask and the window leave,
the router, the shared expert and ``top_k x held / num_experts`` routed
experts); ``kernel_counts`` gives every named kernel's FLOPs and bytes a
call. A grouped product's work follows the routing, which no shape
tells and no channel of the training runner reports (PERF.md, Open
questions), so its count is at the EXPECTED load under the harness's uniform
ids, ``tokens x top_k x held / num_experts`` rows a call (16,384 in
``laguna_pretrain_8k``), each operand and the result once. That holds while
the routing stays near its expectation, which the configuration's trainer
sees to (a share's router update withheld and a small learning rate:
15.4k-17.4k pairs a layer over 280 steps of three seeds, my chip runs, PR
27; PERF.md section 6 has what happens otherwise). A share computed so reads a few percent high in a step whose
load is under the expectation and low in one over it; the kernels pad every
expert to tiles of 128 rows (~12% more rows than pairs at this load), which
the count leaves out as it leaves out the blocks a flash kernel visits
beyond its mask.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_AMP_O2_BYTES = 2       # the train step hands the kernels bfloat16
_QUERY_BLOCK = 512      # rows of one block of the reference's attention


# ------------------------------------------------------------------ shapes

def _held(cfg: dict):
    h = cfg["held"]
    return tuple(h["experts"]), tuple(h["kv_heads"]), tuple(h["vocab_rows"])


def _layers(cfg: dict):
    """Per layer: (kind, held query heads, mlp kind)."""
    (_, _), (klo, khi), _ = _held(cfg)
    kv_all = cfg["num_key_value_heads"]
    return [(cfg["layer_types"][i],
             (khi - klo) * (cfg["num_attention_heads_per_layer"][i] // kv_all),
             cfg["mlp_layer_types"][i])
            for i in range(cfg["num_hidden_layers"])]


def model_config(cfg: dict):
    """The program's LagunaConfig for a configuration file, checked against
    the file's own numbers."""
    from paddle_tpu.models import LagunaConfig
    experts, kv, vocab = _held(cfg)
    pub = cfg.get("published", {})
    if vocab[1] - vocab[0] != cfg["vocab_size"]:
        raise SystemExit(f"configuration {cfg['name']}: vocab_size "
                         f"{cfg['vocab_size']} is not the held rows {vocab}")
    mc = LagunaConfig(
        vocab_size=int(pub.get("vocab_size", cfg["vocab_size"])),
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=cfg["sliding_window"],
        rope_parameters={k: v for k, v in cfg["rope_parameters"].items()
                         if isinstance(v, dict)},
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        moe_routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_experts=experts, held_kv_heads=kv, held_vocab=vocab,
        # a toy twin's tiles are smaller than the kernels' 128 rows
        **({"moe_tile_m": int(cfg["moe_tile_m"])}
           if "moe_tile_m" in cfg else {}))
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    raise SystemExit(
        "the laguna family has no serving model: no cell serves it (the "
        "engine reads a model through serving/seam.py; the same decoder "
        "is served as the mellum family)")


def train_job(cfg: dict, job: dict):
    """The train job through the framework's public API: the model with
    recompute per block, AdamW, AMP in the step -> (model, optimizer, train
    function, retain_grads)."""
    import dataclasses
    from paddle_tpu import amp
    from paddle_tpu.models import LagunaForCausalLM
    from paddle_tpu.optimizer import AdamW
    tr = cfg["trainer"]
    if int(job["seq"]) > cfg["max_position_embeddings"]:
        raise SystemExit(f"job seq {job['seq']} exceeds the configuration's "
                         f"max_position_embeddings")
    model = LagunaForCausalLM(dataclasses.replace(
        model_config(cfg), recompute=bool(tr["recompute"])))
    opt = AdamW(learning_rate=float(tr["learning_rate"]),
                parameters=model.parameters(),
                moment_dtype=("bfloat16" if tr["moment_dtype"] == "bfloat16"
                              else None))
    named = dict(model.named_parameters())
    for name, scale in tr.get("lr_scale", {}).items():
        named[name].lr_scale = float(scale)
    # the moments exist before the first step: the step compiles once
    opt.init_state()

    def train_step(ids, labels):
        with amp.auto_cast(level=tr["amp_level"]):
            loss = model(ids, labels=labels)
        model.clear_gradients()
        loss.backward()
        opt.step()
        return loss
    return model, opt, train_step, bool(tr["retain_grads"])


# --------------------------------------------------------------- reference

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _inv_freq(rope: dict, head_dim: int):
    """(inv_freq [r/2], attention factor, r) of one layer kind, from the
    config's keys as ``transformers`` computes them."""
    r = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if rope.get("rope_type") != "yarn":
        return inv, 1.0, r
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):
        return r * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    att = rope.get("attention_factor")
    return inv, float(att if att is not None
                      else 0.1 * math.log(factor) + 1.0), r


def _rotate(x, rope: dict):
    """x [b, heads, s, d]: rotate-half on the first r dims."""
    inv, att, r = _inv_freq(rope, x.shape[-1])
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def _attention(q, k, v, window: int):
    """q [b, hq, s, d], k/v [b, hkv, s, d] -> [b, hq, s, d]; causal, query
    head j reads KV head j // (hq / hkv); one block of queries at a time."""
    b, hq, s, d = q.shape
    group = hq // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    col = jnp.arange(s)[None, :]
    outs = []
    for lo in range(0, s, _QUERY_BLOCK):
        qb = q[:, :, lo:lo + _QUERY_BLOCK]
        row = lo + jnp.arange(qb.shape[2])[:, None]
        keep = col <= row
        if window:
            keep = jnp.logical_and(keep, col > row - window)
        att = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / jnp.sqrt(float(d))
        att = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bhkd->bhqd", att, v))
    return jnp.concatenate(outs, axis=2)


def _swiglu(x, gate_up, down):
    width = down.shape[0]
    gu = x @ gate_up
    return (jax.nn.silu(gu[..., :width]) * gu[..., width:]) @ down


def _experts(u, router, w13, w2, cfg: dict):
    """The held experts' part of ``sum_e w_e FFN_e(u)``: every held expert
    runs over all tokens, masked to the tokens that chose it."""
    (elo, ehi), _, _ = _held(cfg)
    scores = jax.nn.sigmoid(u @ router)
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    weight = cfg["moe_routed_scaling_factor"] * top \
        / jnp.sum(top, axis=-1, keepdims=True)

    def one(acc, e):
        w_e = jnp.sum(jnp.where(idx == elo + e, weight, 0.0), axis=-1)
        return acc + w_e[..., None] * _swiglu(u, w13[e], w2[e]), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(ehi - elo))
    return out


def hidden(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h]. ``collect``, a list, receives the hidden state after every
    layer."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        _, (klo, khi), (vlo, vhi) = _held(cfg)
        kv, d, eps = khi - klo, cfg["head_dim"], cfg["rms_norm_eps"]
        b, s = ids.shape
        held = jnp.logical_and(ids >= vlo, ids < vhi)
        x = jnp.where(held[..., None],
                      p["model.embed.weight"][jnp.where(held, ids - vlo, 0)],
                      0.0)
        for i, (kind, hq, mlp) in enumerate(_layers(cfg)):
            pre = f"model.layers.{i}."
            h = _rms(x, p[pre + "attn_norm.weight"], eps)
            qkv = h @ p[pre + "attn.qkv_proj.weight"]

            def heads(lo, n):
                return qkv[..., lo * d:(lo + n) * d].reshape(
                    b, s, n, d).transpose(0, 2, 1, 3)
            rope = cfg["rope_parameters"][kind]
            o = _attention(_rotate(heads(0, hq), rope),
                           _rotate(heads(hq, kv), rope), heads(hq + kv, kv),
                           cfg["sliding_window"]
                           if kind == "sliding_attention" else 0)
            gate = jax.nn.sigmoid(h @ p[pre + "attn.g_proj.weight"])
            o = o.transpose(0, 2, 1, 3) * gate[..., None]
            x = x + o.reshape(b, s, hq * d) @ p[pre + "attn.o_proj.weight"]
            u = _rms(x, p[pre + "mlp_norm.weight"], eps)
            if mlp == "dense":
                x = x + _swiglu(u, p[pre + "mlp.gate_up.weight"],
                                p[pre + "mlp.down.weight"])
            else:
                x = x + _experts(u, p[pre + "moe.router.weight"],
                                 p[pre + "moe.experts_gate_up"],
                                 p[pre + "moe.experts_down"], cfg) \
                    + _swiglu(u, p[pre + "moe.shared.gate_up.weight"],
                              p[pre + "moe.shared.down.weight"])
            if collect is not None:
                collect.append(x)
        return _rms(x, p["model.norm.weight"], eps)


def head(params: dict, cfg: dict):
    """The output matrix float32 [h, held vocabulary rows]."""
    return jnp.asarray(params["lm_head.weight"], jnp.float32)


def forward(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> logits float32 [b, s, held vocabulary rows]:
    :func:`hidden` times :func:`head`."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, collect) @ head(params, cfg)


def loss(params: dict, ids, labels, cfg: dict):
    """Mean next-token cross-entropy over the held vocabulary rows, as the
    program's training loss defines it (labels inside the held rows)."""
    _, _, (vlo, _) = _held(cfg)
    logp = jax.nn.log_softmax(forward(params, ids, cfg), axis=-1)
    picked = jnp.take_along_axis(logp, (labels - vlo)[..., None], axis=-1)
    return -jnp.mean(picked)


# ------------------------------------------------------------------ counts

def _mean_keys(seq: int, window: int) -> float:
    """Keys a query sees on average: causal, and inside the window."""
    if not window or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def _window_of(cfg: dict, kind: str) -> int:
    return cfg["sliding_window"] if kind == "sliding_attention" else 0


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """Forward matmul FLOPs of one token on this chip, by part."""
    (elo, ehi), (klo, khi), (vlo, vhi) = _held(cfg)
    h, d, kv = cfg["hidden_size"], cfg["head_dim"], khi - klo
    out = {"attention_projections": 0.0, "attention_scores": 0.0,
           "dense_mlp": 0.0, "router": 0.0, "shared_expert": 0.0,
           "routed_experts": 0.0, "head": 2.0 * h * (vhi - vlo)}
    routed = cfg["num_experts_per_tok"] * (ehi - elo) / cfg["num_experts"]
    for kind, hq, mlp in _layers(cfg):
        out["attention_projections"] += \
            2.0 * h * (hq + 2 * kv) * d + 2.0 * h * hq + 2.0 * hq * d * h
        out["attention_scores"] += \
            4.0 * hq * d * _mean_keys(seq, _window_of(cfg, kind))
        if mlp == "dense":
            out["dense_mlp"] += 6.0 * h * cfg["intermediate_size"]
        else:
            out["router"] += 2.0 * h * cfg["num_experts"]
            out["shared_expert"] += \
                6.0 * h * cfg["shared_expert_intermediate_size"]
            out["routed_experts"] += \
                routed * 6.0 * h * cfg["moe_intermediate_size"]
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 (backward = 2x forward); recomputation not counted."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


#: (matmuls, [bh, s, d] arrays of the query heads, of the KV heads, float32
#: [bh, s] rows) of one flash kernel call: fwd reads q | k v, writes o, lse;
#: dq reads q do | k v, lse delta, writes dq; dkv reads q do | k v, lse
#: delta, writes dk dv (counted once a KV head: what the algorithm needs)
_FLASH = {"flash_fwd": (2, 2, 2, 1), "flash_bwd_dq": (3, 3, 2, 2),
          "flash_bwd_dkv": (4, 2, 4, 2)}
_TAG = {"win": "sliding_attention", "full": "full_attention"}
#: the grouped products: up = gate and up side by side [h -> 2f], down [f -> h];
#: plain = forward, _dx = the rows' gradient, _dw = the stacked weights'
_MOE = ("moe_up", "moe_up_dx", "moe_up_dw",
        "moe_down", "moe_down_dx", "moe_down_dw")


def kernel_counts(name: str, cfg: dict, job: dict):
    """(flops, bytes) of one call of a named kernel in a training job on
    one chip. Flash kernels: the layer kind's held heads, the keys the mask
    and the window leave (as ``train_flops_per_token`` counts them), each
    operand and result once. Grouped products: the expected load under
    uniform ids, ``tokens x top_k x held / num_experts`` rows, each operand
    and the result once (see the module's docstring)."""
    if job.get("kind") != "train":
        return None
    batch, s = int(job["batch_per_chip"]), int(job["seq"])
    (elo, ehi), (klo, khi), _ = _held(cfg)
    h, d = cfg["hidden_size"], cfg["head_dim"]
    stem, _, tag = name.rpartition("_")
    if stem in _FLASH and tag in _TAG:
        heads = {hq for kind, hq, _ in _layers(cfg) if kind == _TAG[tag]}
        if len(heads) != 1:
            return None      # absent, or more than one shape under the name
        matmuls, q_arrays, kv_arrays, rows = _FLASH[stem]
        bh, bh_kv = batch * heads.pop(), batch * (khi - klo)
        keys = _mean_keys(s, _window_of(cfg, _TAG[tag]))
        return (matmuls * 2.0 * bh * s * keys * d,
                (q_arrays * bh + kv_arrays * bh_kv) * s * d * _AMP_O2_BYTES
                + rows * bh * s * 4.0)
    if name in _MOE:
        g, f = ehi - elo, cfg["moe_intermediate_size"]
        rows = batch * s * cfg["num_experts_per_tok"] * g \
            / float(cfg["num_experts"])
        n = 2 * f if name.startswith("moe_up") else f
        # rows x [h <-> n] against the held stack [g, h, n], whichever of
        # the three arrays the kernel writes
        return (2.0 * rows * h * n,
                (rows * h + g * h * n + rows * n) * float(_AMP_O2_BYTES))
    return None
