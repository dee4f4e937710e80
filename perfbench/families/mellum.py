"""The Mellum family (JetBrains/Mellum2-12B-A2.5B-Instruct; the program's
model is ``paddle_tpu/models/mellum.py``, a configuration of the decoder in
``models/laguna.py``): RMSNorm, rotary positions by layer kind (plain on
sliding-window layers, YaRN on full layers, both over all 128 dims),
grouped-query attention (32 query heads over 4 KV heads), a sliding window
on three layers of four, in every layer 64 SwiGLU experts of width 896
(softmax router over all 64, top 8, renormalised over the chosen; no
shared expert), an untied head.

**A configuration of this family** is the published ``config.json`` key for
key, with the cuts its ``reduced`` names, and groups of its own:
``published`` (the published value of every key the file runs smaller),
``assumed`` (what the config leaves open, each with its reason),
``deployment``, ``engine`` / ``engine_why`` (the harness's ``ServingEngine``
call), and the keys that say how the program runs it: ``dtype`` (the served
precision), ``embed_init_std`` / ``router_init_std`` (see ``assumed``), and
for a toy twin ``moe_tile_m`` / ``moe_chunk_rows``.

**The plain reference** (``hidden`` x ``head`` = ``forward``; ``loss``):
the equations of ISSUE 31 in ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``,
no cache, no kernels, no batching; weights keyed by the program's parameter
names (linear weights ``[in, out]``; ``qkv_proj`` holds the 32 query heads,
then the 4 K heads, then V along its output axis; ``experts_gate_up`` is
``[64, hidden, 2 x 896]`` with gate first) and upcast where they are used,
a layer and an expert at a time: the served copy is bfloat16 and 5.47B
parameters in float32 do not fit beside it. Attention runs a block of 256
queries at a time (a window layer over the keys its window can reach, a
full layer over all of them), so that 16384 rows fit. The expert layer
loops over the experts and, for each, over the tokens that chose it, 1024
at a time: the work of the pairs there are, not of every token by every
expert (8x that), and exact whatever the imbalance (the harness pads a
request to ``max_len`` with one token id, and those rows all choose alike).

Departures from the published description, shared with the program and
listed in the configuration's ``assumed``: no q/k normalisation and no
router bias (the config has no key for either); softmax before the top-8
(what ``norm_topk_prob`` renormalises); pre-norm placement; the window's
edge (key ``t`` visible to query ``i`` iff ``i - 1024 < t <= i``);
``intermediate_size`` and ``max_window_layers`` unused; no MTP module
(``described_as`` names one, the config has no key for it); weights random
from ``--seed``, the embedding's at unit scale and the routers' at four
times the other matrices' (``assumed``: the scales at which the harness's
check can see an expert left out; ``study/init_sweep_mellum.py``).

**Operation counts** (``kernel_counts``; the serving job is the cell's
traffic file). A served prompt runs the training path's forward kernels
one prompt a call: ``flash_fwd_win`` / ``flash_fwd_full`` over the bucket's
rows, and ``moe_up`` / ``moe_down`` over 3072 rows a call
(``models/mellum.PROMPT_CHUNK_ROWS``, so one shape whatever the bucket). A
decode step runs ``moe_up_dec`` / ``moe_down_dec`` (the sorted buffer in
tiles of 16 rows, a tile only for an expert some row chose). Their work
follows the data, which no shape tells and no per-call counter reaches the
roofline reader (PERF.md section 7), so each is counted at the cell's
EXPECTED load, each operand and the result once:

- ``moe_up`` / ``moe_down``: 3072 x 8 = 24,576 pairs a call and the whole
  stack of 64 experts; exact but for the padding rows of a bucket, which
  all choose alike (fewer tiles, the same pairs);
- ``moe_up_dec`` / ``moe_down_dec``: 16 live rows x 8 choices touch
  ``64 x (1 - (7/8)^16) = 56.4`` experts under uniform routing; their
  weights are the bytes (the rows' own are thousandths of them). The
  engine's counter reads the real number (``experts_touched_per_step
  .mellum``): a step that touches fewer reads that much high, so the share
  is right to the few percent the counter's mean differs from 56.4;
- ``flash_fwd_win`` / ``flash_fwd_full``: one name covers the four
  buckets' shapes, so each is counted at the traffic's MEAN call: the mean,
  over the 16 prompts' buckets (3072 x 4, 6144 x 6, 9216 x 3, 12288 x 3),
  of a call's operations and of its bytes, with the keys the mask leaves
  (about 1024 a query on a window layer, half of the rows on a full one).
  A traced window holds the ~8 prompts its 6 s happen to admit, so the
  share swings with which buckets they fell in: ``flash_fwd_win`` is linear
  in the rows (a window of the shortest bucket only reads 2.3x high, of
  the longest 1.7x low); ``flash_fwd_full`` is quadratic (the mean call has
  6.4x the work of the shortest bucket's and 0.40x of the longest's: a
  window of the shortest only reads 6.4x high, of the longest 2.5x low;
  eight prompts drawn from a shuffled multiset stay within 0.5x-1.9x of
  the mean 99% of the time). A count by the call's own rows needs the
  trace reader to take a call's shape (PERF.md section 7).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 256      # rows of one block of the reference's attention
_EXPERT_ROWS = 1024     # tokens of one expert multiplied at a time
_BYTES = 2              # the served copy is bfloat16


# ------------------------------------------------------------------ shapes

def model_config(cfg: dict):
    """The program's MellumConfig for a configuration file, checked against
    the file's own numbers."""
    from paddle_tpu.models import MellumConfig
    n = cfg["num_hidden_layers"]
    extra = {k: cfg[k] for k in ("moe_tile_m", "moe_chunk_rows",
                                 "embed_init_std", "router_init_std")
             if k in cfg}
    mc = MellumConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=n,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=cfg["sliding_window"],
        rope_parameters={k: v for k, v in cfg["rope_parameters"].items()
                         if isinstance(v, dict)},
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=cfg["dtype"], **extra)
    if any(t != "sparse" for t in mc.mlp_layer_types):
        raise SystemExit(f"configuration {cfg['name']}: a mellum layer is "
                         f"sparse; the file has {set(mc.mlp_layer_types)}")
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    from paddle_tpu.models import MellumForCausalLM
    return MellumForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    raise SystemExit(
        "the mellum family has no training job: its cell serves it "
        "(laguna_pretrain_8k trains the benchmark's sparse-expert model)")


# --------------------------------------------------------------- reference

def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _inv_freq(rope: dict, d: int):
    """(inv_freq [d/2], attention factor) of one layer kind, from the
    config's keys as ``transformers`` computes them."""
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rope.get("rope_type") != "yarn":
        return inv, 1.0
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    att = rope.get("attention_factor")
    return inv, float(att if att is not None
                      else 0.1 * math.log(factor) + 1.0)


def _rotate(x, rope: dict):
    """x [heads, s, d]: rotate-half over all d dims."""
    d = x.shape[-1]
    inv, att = _inv_freq(rope, d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(q, k, v, window: int):
    """q [hq, s, d], k / v [hkv, s, d] -> [hq, s, d]: causal, query head j
    reads KV head j // (hq / hkv), with ``window`` key t visible to query
    i iff i - window < t <= i; one block of queries at a time, a window
    layer over the keys its window can reach only."""
    hq, s, d = q.shape
    group = hq // k.shape[0]
    block = min(_QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"the reference's attention takes rows in blocks "
                         f"of {block}; got {s}")
    span = min(s, block + window) if window else s

    def one(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        first = jnp.clip(lo + block - span, 0, s - span)
        kb = jax.lax.dynamic_slice_in_dim(k, first, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, span, axis=1)
        row = lo + jnp.arange(block)[:, None]
        col = first + jnp.arange(span)[None, :]
        keep = col <= row
        if window:
            keep = jnp.logical_and(keep, col > row - window)
        att = jnp.einsum("hgqd,hkd->hgqk",
                         qb.reshape(-1, group, block, d), kb) \
            / jnp.sqrt(float(d))
        att = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,hkd->hgqd", att, vb).reshape(hq, block, d)
    out = jax.lax.map(one, jnp.arange(0, s, block))     # [s/block, hq, ..]
    return out.transpose(1, 0, 2, 3).reshape(hq, s, d)


def _experts(u, router, w13, w2, top_k: int):
    """``sum_{e in S} w_e FFN_e(u)`` for u [t, h]: ``r = softmax(u Wr)``,
    ``S`` its ``top_k`` largest, ``w_e = r_e / sum_S r``. Expert by expert,
    and for each the tokens that chose it, ``_EXPERT_ROWS`` at a time."""
    t, _ = u.shape
    width = w2.shape[1]
    r = jax.nn.softmax(u @ _f32(router), axis=-1)
    top, idx = jax.lax.top_k(r, top_k)
    weight = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = min(_EXPERT_ROWS, t)

    def one(out, e_w):
        e, g_u, dn = e_w
        g_u, dn = _f32(g_u), _f32(dn)
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)   # [t]
        chose = w_e > 0.0
        n = jnp.sum(chose)
        order = jnp.argsort(jnp.logical_not(chose), stable=True)

        def more(state):
            return state[0] * rows < n

        def chunk(state):
            k, out = state
            start = jnp.minimum(k * rows, t - rows)
            tok = jax.lax.dynamic_slice_in_dim(order, start, rows)
            # rows before k * rows were done by an earlier chunk (only the
            # last chunk can be moved back to fit), rows past n chose
            # another expert
            at = start + jnp.arange(rows)
            mine = jnp.logical_and(at >= k * rows, at < n)
            x = u[tok]
            gu = x @ g_u
            y = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ dn
            y = jnp.where(mine[:, None], w_e[tok][:, None] * y, 0.0)
            return k + 1, out.at[tok].add(y)
        return jax.lax.while_loop(more, chunk, (0, out))[1], None
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (jnp.arange(w13.shape[0]), w13, w2))
    return out


def hidden(params: dict, ids, cfg: dict, collect=None):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], one request at a time. ``collect``, a list, receives the
    hidden state after every layer."""
    with jax.default_matmul_precision("highest"):
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        eps = cfg["rms_norm_eps"]
        outs, kept = [], []
        for row in range(ids.shape[0]):
            x = _f32(params["model.embed.weight"][ids[row]])      # [s, h]
            s = x.shape[0]
            for i in range(cfg["num_hidden_layers"]):
                kind = cfg["layer_types"][i]
                pre = f"model.layers.{i}."
                u = _rms(x, params[pre + "attn_norm.weight"], eps)
                qkv = u @ _f32(params[pre + "attn.qkv_proj.weight"])

                def heads(lo, n):
                    return qkv[:, lo * d:(lo + n) * d].reshape(
                        s, n, d).transpose(1, 0, 2)
                rope = cfg["rope_parameters"][kind]
                o = _attention(
                    _rotate(heads(0, hq), rope), _rotate(heads(hq, kv), rope),
                    heads(hq + kv, kv),
                    cfg["sliding_window"] if kind == "sliding_attention"
                    else 0)
                x = x + o.transpose(1, 0, 2).reshape(s, hq * d) \
                    @ _f32(params[pre + "attn.o_proj.weight"])
                u = _rms(x, params[pre + "mlp_norm.weight"], eps)
                x = x + _experts(u, params[pre + "moe.router.weight"],
                                 params[pre + "moe.experts_gate_up"],
                                 params[pre + "moe.experts_down"],
                                 cfg["num_experts_per_tok"])
                if collect is not None:
                    kept.append(x)
            outs.append(_rms(x, params["model.norm.weight"], eps))
        if collect is not None:
            n = cfg["num_hidden_layers"]
            collect.extend(jnp.stack(kept[i::n]) for i in range(n))
        return jnp.stack(outs)


def head(params: dict, cfg: dict):
    """The output matrix float32 [h, vocab] (untied)."""
    return _f32(params["lm_head.weight"])


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

def _mean_keys(seq: int, window: int) -> float:
    """Keys a query sees on average: causal, and inside the window."""
    if not window or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 of what one token passes (the chosen experts only);
    no cell trains this family, the count is for a reader's arithmetic."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    fwd = 2.0 * h * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        window = cfg["sliding_window"] \
            if cfg["layer_types"][i] == "sliding_attention" else 0
        fwd += 2.0 * h * (hq + 2 * kv) * d + 2.0 * hq * d * h \
            + 4.0 * hq * d * _mean_keys(seq, window) \
            + 2.0 * h * cfg["num_experts"] \
            + cfg["num_experts_per_tok"] * 6.0 * h \
            * cfg["moe_intermediate_size"]
    return 3.0 * fwd


def expected_experts_touched(cfg: dict, rows: int) -> float:
    """Experts some row of ``rows`` chooses, under uniform routing."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / float(e)) ** rows)


def _buckets_reached(cfg: dict, job: dict):
    """The prefill bucket of every prompt of the job's multiset."""
    from .. import traffic as T
    buckets = cfg["engine"]["buckets"]
    return [T.bucket_for(p, buckets) for p, _ in T.multiset(job)]


def kernel_counts(name: str, cfg: dict, job: dict):
    """(flops, bytes) of one call of a named kernel in a serving job on one
    chip, at the cell's expected load (see the module's docstring)."""
    if job.get("kind") not in ("closed_loop", "open_loop"):
        return None
    h, d, f = cfg["hidden_size"], cfg["head_dim"], \
        cfg["moe_intermediate_size"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    wide = {"moe_up": (h, 2 * f), "moe_down": (f, h)}
    stem = name[:-4] if name.endswith("_dec") else name
    if stem in wide:
        n_in, n_out = wide[stem]
        if name.endswith("_dec"):
            rows = cfg["engine"]["max_slots"] * k
            stack = expected_experts_touched(cfg, cfg["engine"]["max_slots"])
        else:
            from paddle_tpu.models.mellum import PROMPT_CHUNK_ROWS
            rows = int(cfg.get("moe_chunk_rows", PROMPT_CHUNK_ROWS)) * k
            stack = e
        return (2.0 * rows * n_in * n_out,
                (rows * n_in + stack * n_in * n_out + rows * n_out)
                * float(_BYTES))
    if name in ("flash_fwd_win", "flash_fwd_full"):
        window = cfg["sliding_window"] if name.endswith("_win") else 0
        # QK^T and PV; reads q | k v, writes o and the float32 lse
        calls = [(2 * 2.0 * hq * s * _mean_keys(s, window) * d,
                  (2 * hq + 2 * kv) * s * d * float(_BYTES) + hq * s * 4.0)
                 for s in _buckets_reached(cfg, job)]
        return tuple(sum(c) / len(calls) for c in zip(*calls))
    return None
