"""The Jamba family (ai21labs/AI21-Jamba2-3B; the program's model is
``paddle_tpu/models/jamba.py``): 28 pre-norm layers, layer ``l`` attention
iff ``l % attn_layer_period == attn_layer_offset`` (7 and 21) and a
state-space (Mamba) mixer otherwise, a dense SwiGLU MLP of 8192 in every
layer (``num_experts`` 1: no router anywhere), RMSNorm, a tied head of
65536 rows. With ``T`` tokens, ``d = 2560``, ``d_in = 5120``, ``N = 16``,
``R = 160``, ``K = 4``::

    mamba layer
    u          = RMSNorm(h; g1)
    [x', z]    = u W_in                                 2560 -> 2 x 5120
    x_t        = silu(b_c + sum_j w_c[:, j] x'_{t-3+j})   depthwise, causal
    [dl, B, C] = x W_x                                  5120 -> 160 + 16 + 16
    dl, B, C   = RMSNorm of each, with its own gain       Jamba's inner norms
    Delta_t    = softplus(dl_t W_dt + b_dt)             160 -> 5120
    A          = -exp(A_log)                            [5120, 16]
    s_t        = exp(Delta_t[:, None] A) s_{t-1} + (Delta_t x_t)[:, None] B_t[None, :]
    y_t        = s_t C_t + D x_t
    h          = h + (y * silu(z)) W_out                5120 -> 2560

    attention layer (no rotary, no learned positions, no bias)
    u = RMSNorm(h; g1);  q = u Wq [T, 20, 128];  k, v = u Wk, u Wv [T, 1, 128]
    h = h + softmax(q k^T / sqrt(128), causal) v Wo

    both:   u2 = RMSNorm(h; g2);   h = h + (silu(u2 Wg) * (u2 Wu)) Wd
    logits = RMSNorm(h_28; g) E^T

**A configuration of this family** is the published ``config.json`` key for
key, with the cuts its ``reduced`` names, and groups of its own:
``published``, ``assumed`` (what the config leaves open, each with its
reason), ``deployment``, ``engine`` / ``engine_why`` (the harness's
``ServingEngine`` call), and the keys that say how the program runs it:
``dtype``, ``embed_init_std``, ``a_log_init`` / ``dt_bias_init`` /
``final_norm_init`` (mean and std of the recurrence's leaves and of the
final norm's gain; see ``assumed``), ``tokens_a_dispatch``.

**The plain reference** (``hidden`` x ``head`` = ``forward``; ``loss``):
the equations above in ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``,
no cache, no kernel, no batching; weights keyed by the program's parameter
names (linear weights ``[in, out]``; ``attn.qkv_proj`` holds the 20 query
heads, then K, then V along its output axis; ``mlp.gate_up`` gate first)
and upcast where they are used, a layer at a time. The recurrence is a
``lax.scan`` over time whose carry is one ``[5120, 16]`` state, so 8192
positions fit; attention runs a block of 256 queries at a time (the
Mellum reference's, imported).

Departures from the published description, shared with the program and
listed in the configuration's ``assumed``: no positional encoding in the
attention layers (the config has no key for any); the inner norms carry a
gain and no bias; ``expert_layer_*`` unused (``num_experts`` 1);
``sliding_window`` null; a float32 scan state; random weights, the
recurrence's ``A_log`` and ``b_dt`` drawn as normals of about the spread of
Mamba's published initialisers (``perfbench/weights.py`` draws Normal and
Constant leaves only).

**Operation counts** (``kernel_counts``; the serving job is the cell's
traffic file), each operand and the result once at the dtype the kernel
sees, at the traffic's MEAN call (one name covers the buckets' shapes):

- ``selective_scan``: a call scans ``rows x bucket`` positions of one layer
  (``rows`` = ``tokens_a_dispatch // bucket``, at least 1). It reads ``x``,
  ``Delta``, ``z`` (float32 ``[positions, 5120]``), ``B``, ``C`` (float32
  ``[positions, 16]``), ``A`` ``[16, 5120]``, ``D``; writes ``y`` (float32
  ``[positions, 5120]``) and the state at ``last`` (``[rows, 16, 5120]``).
  Operations a position: a channel and state pair costs 7 (the product
  with ``A``, the exponential counted as one, decay x state, input x ``B``,
  their sum, x ``C``, the sum into ``y``), a channel 6 more (``Delta x``,
  ``D x``, the gate's sigmoid and two products, the sum): ``5120 x (7 x 16 +
  6)`` = 604,160. The work is the vector unit's and the floor is the
  harness's ``max(flops / bf16 peak, bytes / HBM bandwidth)``, which the
  bytes decide: the share reads far under 100 and cannot pass it. Linear in
  the positions, so a traced window of other buckets than the mean reads
  high or low by their ratio (0.5x-2x).
- ``flash_fwd_full``: the two attention layers' forward over a dispatch's
  own rows, 20 query heads on one KV head, causal (half of ``bucket x
  bucket`` a row of the dispatch); quadratic in the bucket. The 512-row
  bucket is under ``FLAGS_pallas_min_seq`` (1024) and takes the composed
  form: the mean is over the dispatches that reach the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .mellum import _attention, _f32, _mean_keys, _rms

_BYTES = 2              # the served copy is bfloat16


# ------------------------------------------------------------------ shapes

def model_config(cfg: dict):
    """The program's JambaConfig for a configuration file, checked against
    the file's own numbers."""
    from paddle_tpu.models import JambaConfig
    for key, want in (("num_experts", 1), ("tie_word_embeddings", True),
                      ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("hidden_act", "silu")):
        if cfg.get(key, want) != want:
            raise SystemExit(f"configuration {cfg['name']}: the jamba "
                             f"family runs {key} = {want!r}; the file has "
                             f"{cfg[key]!r}")
    extra = {k: (tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
             for k in ("embed_init_std", "a_log_init", "dt_bias_init",
                       "final_norm_init", "tokens_a_dispatch") if k in cfg}
    mc = JambaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or
        cfg["hidden_size"] // cfg["num_attention_heads"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=cfg["dtype"], **extra)
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    from paddle_tpu.models import JambaForCausalLM
    return JambaForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    raise SystemExit(
        "the jamba family has no training job: its cell serves it (the "
        "scan has no backward; ROADMAP R4)")


# --------------------------------------------------------------- reference

def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def _mamba(u, params: dict, pre: str, cfg: dict, keep=None):
    """The Mamba mixer on ``u`` [s, h] -> [s, h]. ``keep``, a dict with
    a row ``"at"``, receives under ``pre`` the state ``s_at`` [d_in, N]
    (the tests and the study compare a carried state with it)."""
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    xz = u @ _f32(params[pre + "in_proj.weight"])
    d = xz.shape[1] // 2
    xp, z = xz[:, :d], xz[:, d:]
    s = xp.shape[0]
    w, bias = _f32(params[pre + "conv_weight"]), _f32(params[pre + "conv_bias"])
    rows = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), xp])
    x = bias[None, :]
    for j in range(k):
        x = x + w[None, :, j] * rows[j:j + s]
    x = jax.nn.silu(x)
    dbc = x @ _f32(params[pre + "x_proj.weight"])
    dl = _rms(dbc[:, :r], params[pre + "dt_norm.weight"], eps)
    b = _rms(dbc[:, r:r + n], params[pre + "b_norm.weight"], eps)
    c = _rms(dbc[:, r + n:], params[pre + "c_norm.weight"], eps)
    delta = jax.nn.softplus(dl @ _f32(params[pre + "dt_proj"])
                            + _f32(params[pre + "dt_bias"])[None, :])
    a = -jnp.exp(_f32(params[pre + "A_log"]))                  # [d, N]

    at = -1 if keep is None else keep["at"]

    def step(carry, inp):
        state, kept = carry
        i, xt, dt, bt, ct = inp
        state = jnp.exp(dt[:, None] * a) * state \
            + (dt * xt)[:, None] * bt[None, :]
        return (state, jnp.where(i == at, state, kept)), state @ ct
    zero = jnp.zeros((d, n), jnp.float32)
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(s), x, delta, b, c))
    if keep is not None:
        keep[pre] = kept
    y = y + _f32(params[pre + "D"])[None, :] * x
    return (y * jax.nn.silu(z)) @ _f32(params[pre + "out_proj.weight"])


def hidden(params: dict, ids, cfg: dict, collect=None, keep=None):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], one request at a time. ``collect``, a list, receives the
    hidden state after every layer; ``keep``, a dict with a row ``"at"``,
    every Mamba layer's state at that row (of the last request)."""
    with jax.default_matmul_precision("highest"):
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     _head_dim(cfg))
        eps, width = cfg["rms_norm_eps"], cfg["intermediate_size"]
        n_layers = cfg["num_hidden_layers"]
        outs, kept = [], []
        for row in range(ids.shape[0]):
            x = _f32(params["model.embed.weight"][ids[row]])      # [s, h]
            s = x.shape[0]
            for i in range(n_layers):
                pre = f"model.layers.{i}."
                u = _rms(x, params[pre + "input_norm.weight"], eps)
                if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
                    qkv = u @ _f32(params[pre + "attn.qkv_proj.weight"])

                    def heads(lo, n):
                        return qkv[:, lo * d:(lo + n) * d].reshape(
                            s, n, d).transpose(1, 0, 2)
                    o = _attention(heads(0, hq), heads(hq, kv),
                                   heads(hq + kv, kv), 0)
                    x = x + o.transpose(1, 0, 2).reshape(s, hq * d) \
                        @ _f32(params[pre + "attn.o_proj.weight"])
                else:
                    x = x + _mamba(u, params, pre + "mamba.", cfg, keep)
                u = _rms(x, params[pre + "mlp_norm.weight"], eps)
                gu = u @ _f32(params[pre + "mlp.gate_up.weight"])
                x = x + (jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
                    @ _f32(params[pre + "mlp.down.weight"])
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


def forward(params: dict, ids, cfg: dict, collect=None, keep=None):
    """``ids`` int [b, s] -> logits float32 [b, s, vocab]: :func:`hidden`
    times :func:`head`."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, collect, keep) @ head(params, cfg)


def loss(params: dict, ids, labels, cfg: dict):
    """Mean next-token cross-entropy of ``labels`` [b, s]."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


# ------------------------------------------------------------------ counts

def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 of the matrix products one token passes; no cell trains
    this family, the count is for a reader's arithmetic."""
    h, d_in = cfg["hidden_size"], cfg["mamba_expand"] * cfg["hidden_size"]
    hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 _head_dim(cfg))
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    fwd = 2.0 * h * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
            fwd += 2.0 * h * (hq + 2 * kv) * d + 2.0 * hq * d * h \
                + 4.0 * hq * d * _mean_keys(seq, 0)
        else:
            fwd += 2.0 * h * 2 * d_in + 2.0 * d_in * (r + 2 * n) \
                + 2.0 * r * d_in + 2.0 * d_in * h + scan_flops(cfg)
        fwd += 6.0 * h * cfg["intermediate_size"]
    return 3.0 * fwd


def scan_flops(cfg: dict) -> float:
    """Operations of the scan a position of one layer (the docstring)."""
    d_in = cfg["mamba_expand"] * cfg["hidden_size"]
    return d_in * (7.0 * cfg["mamba_d_state"] + 6.0)


def _dispatches(cfg: dict, job: dict):
    """(rows, bucket) of the prefill dispatch of every prompt of the job's
    multiset."""
    from .. import traffic as T
    from paddle_tpu.models import JambaConfig
    e = cfg["engine"]
    budget = int(cfg.get("tokens_a_dispatch", JambaConfig.tokens_a_dispatch))
    out = []
    for p, _ in T.multiset(job):
        bucket = T.bucket_for(p, e["buckets"])
        out.append((max(1, min(e["max_slots"], budget // bucket)), bucket))
    return out


def _mean(calls):
    return tuple(sum(c) / len(calls) for c in zip(*calls))


def kernel_counts(name: str, cfg: dict, job: dict):
    """(flops, bytes) of one call of a named kernel in a serving job on one
    chip, at the traffic's mean call (see the module's docstring)."""
    if job.get("kind") not in ("closed_loop", "open_loop"):
        return None
    d_in, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    if name == "selective_scan":
        return _mean([
            (rows * s * scan_flops(cfg),
             4.0 * (4 * rows * s * d_in + 2 * rows * s * n + n * d_in + d_in
                    + rows * n * d_in))
            for rows, s in _dispatches(cfg, job)])
    if name == "flash_fwd_full":
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     _head_dim(cfg))
        # QK^T and PV; reads q | k v, writes o and the float32 lse; a
        # bucket under FLAGS_pallas_min_seq takes the composed form
        from paddle_tpu import flags
        least = int(flags.get_flag("pallas_min_seq"))
        return _mean([
            (rows * 2 * 2.0 * hq * s * _mean_keys(s, 0) * d,
             rows * ((2 * hq + 2 * kv) * s * d * float(_BYTES)
                     + hq * s * 4.0))
            for rows, s in _dispatches(cfg, job) if s >= least])
    return None
