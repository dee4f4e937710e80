"""The Qwen3-Next family (Qwen/Qwen3-Next-80B-A3B-Instruct; the program's
model is ``paddle_tpu/models/qwen3next.py``): pre-norm layers, layer ``l``
gated full attention iff ``(l + 1) % full_attention_interval == 0`` and a
Gated DeltaNet mixer otherwise, sparse experts with a gated shared expert
in every layer, a final norm and an untied head. ``norm`` is the
zero-centred RMSNorm ``x / rms(x) * (1 + w)``. With ``T`` tokens, ``h =
2048``::

    DeltaNet layer (16 key heads of 128 serving 32 value heads of 128)
    u          = norm(x; w1)
    [q|k|v|z]  = u W_qkvz                         2048 -> 2 x 2048 + 2 x 4096
    [b|a]      = u W_ba                           2048 -> 2 x 32
    (q|k|v)_t  = silu(sum_j w_c[:, j] (q|k|v)_{t-3+j})     depthwise, causal
    q, k       = L2-normalised over 128 (eps 1e-6), q x 128^-1/2; key head j
                 serves value heads 2j, 2j + 1
    beta_t     = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
    S'_t       = exp(g_t) S_{t-1}                 S [128, 128] a value head
    S_t        = S'_t + k_t (x) (beta_t (v_t - S'_t^T k_t));   o_t = S_t^T q_t
    x          = x + (RMSNorm_128(o; w_o) * silu(z)) W_out       4096 -> 2048

    attention layer (16 query heads over 2 KV heads of 256)
    u = norm(x; w1);  [q | gate] a head = u W_q;  k, v = u W_k, u W_v
    q, k = norm over 256 a head (one gain each), the first 64 dimensions
    rotated (rotate-half, theta 1e7), the other 192 as they are
    x = x + ((softmax(q k^T / 16, causal) v) * sigmoid(gate)) W_o

    both:   u2 = norm(x; w2);  p = softmax(u2 W_r) over all 512
            E = the 10 largest;  g_e = p_e / sum_E p
            x = x + sum_{e in E, held} g_e SwiGLU_e(u2)
                  + sigmoid(u2 w_s) SwiGLU_shared(u2)
    logits = norm(x_L; w) W_head

**A configuration of this family** is the published ``config.json`` key for
key, with the cuts its ``reduced`` names (``num_experts`` and
``vocab_size`` are what THIS chip holds; ``expert_share`` and
``vocab_share`` say of how many: the router keeps ``num_experts x
expert_share`` outputs), and groups of its own: ``published``, ``assumed``,
``deployment``, ``engine`` / ``engine_why``, and the keys that say how the
program runs it: ``dtype``, ``embed_init_std``, ``a_log_init`` /
``dt_bias_init``, ``tokens_a_dispatch``,
``moe_chunk_rows``.

**The plain reference** (``hidden`` x ``head`` = ``forward``): the
equations above in ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching; weights keyed by the program's parameter names (linear weights
``[in, out]``; ``gdn.in_proj`` holds q, k, v, z and ``gdn.ba_proj`` b, a
along their output axes; ``attn.qkv_proj`` a query head's ``[q | gate]``
head after head, then the K heads, then V; ``gate_up`` gate first), upcast
where they are used. The delta rule is a ``lax.scan`` over time whose carry
is one layer's ``[32, 128, 128]`` state; attention runs a block of 256
queries at a time (the Mellum reference's, imported); the experts are the
held ones, dense over the rows that chose each, masked to the share: what
the absent experts would add is left out, as in the program.

**Operation counts** (``kernel_counts``), each operand and the result once
at the dtype the kernel sees, at the traffic's MEAN call:

- ``gdn_prefill``: a call runs ``rows x bucket`` positions of one layer's
  32 value heads in chunks of 64. Operations a chunk a head (``C`` = 64,
  ``d`` = 128): ``K K^T`` and ``Q K^T`` (2 x 2 C^2 d), the inverse's ten
  ``C^3`` products, ``K S``, ``Q S`` and the state's update (3 x 2 C d^2),
  the solve's and the output's ``[C, C] x [C, d]`` (2 x 2 C^2 d): 15.7
  MFLOP, counted ONCE though every product runs at float32 precision (six
  passes of the matrix unit): the share reads under the truth of the
  unit's occupancy and cannot pass 100. Bytes: q, k (a key head's rows are
  read by both of its value heads), v read and o written as float32, g and
  beta, the final state written.
- ``gdn_decode``: a call is one layer of one decode step: every row's
  state (``h_v x d_k x d_v`` float32) counted once read, once written, and
  q, k, v, o; 4 multiply-adds a state element (decay, the prediction,
  the write, the read). The bytes decide.
- ``flash_fwd_full``, ``paged_decode_attn``, ``moe_up`` / ``moe_down`` and
  their ``_dec`` forms: as the other families count them, at heads of 256
  over 2 KV heads and at the held share of the experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import flops
from .lfm2 import _mean, paged_decode_counts
from .mellum import _attention, _f32, _mean_keys

_BYTES = 2              # the served copy is bfloat16
_MOE_KERNELS = ("moe_up", "moe_down", "moe_up_dec", "moe_down_dec")
_EXPERT_ROWS = 1024     # rows of one pass of an expert of the reference
_KEYS = {"decoder_sparse_step": 1, "mlp_only_layers": [],
         "norm_topk_prob": True, "tie_word_embeddings": False,
         "use_sliding_window": False, "hidden_act": "silu",
         "rope_scaling": None}


def _program():
    try:
        from paddle_tpu.models import qwen3next
    except ImportError:
        raise SystemExit(
            "the qwen3next family needs paddle_tpu/models/qwen3next.py, "
            "which this program does not have (a commit before PR 58)") \
            from None
    return qwen3next


def router_width(cfg: dict) -> int:
    return cfg["num_experts"] * int(cfg.get("expert_share", 1))


# ------------------------------------------------------------------ shapes

def model_config(cfg: dict):
    """The program's Qwen3NextConfig for a configuration file, checked
    against the file's own numbers."""
    for key, want in _KEYS.items():
        if cfg.get(key, want) != want:
            raise SystemExit(f"configuration {cfg['name']}: the qwen3next "
                             f"family runs {key} = {want!r}; the file has "
                             f"{cfg[key]!r}")
    extra = {k: (tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
             for k in ("embed_init_std", "router_init_std", "a_log_init",
                       "dt_bias_init", "tokens_a_dispatch",
                       "moe_tile_m", "moe_chunk_rows") if k in cfg}
    held, width = cfg["num_experts"], router_width(cfg)
    mc = _program().Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        full_attention_interval=cfg["full_attention_interval"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=width, num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        held_experts=None if held == width else (0, held),
        dtype=cfg["dtype"], **extra)
    want = cfg.get("params_held")
    if want is not None and mc.num_params() != want:
        raise SystemExit(f"configuration {cfg['name']}: the program holds "
                         f"{mc.num_params()} parameters, the file says "
                         f"{want}")
    return mc


def serving_model(cfg: dict):
    return _program().Qwen3NextForCausalLM(model_config(cfg))


def train_job(cfg: dict, job: dict):
    raise SystemExit(
        "the qwen3next family has no training job: its cell serves it (the "
        "chunked delta rule has no backward, and a packed document would "
        "need the state reset at its edge: ROADMAP R8)")


# --------------------------------------------------------------- reference

def _norm(x, w, eps):
    """The zero-centred RMSNorm ``x / rms(x) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + _f32(w))


def _is_full(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def _round_to(x, dtype):
    """float32 ``x`` rounded to ``dtype``'s precision and kept float32
    (``lax.reduce_precision``: a cast there and back is compiled away on
    the chip, where XLA allows excess precision)."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _delta_net(u, params: dict, pre: str, cfg: dict, keep=None,
               state_dtype=jnp.float32):
    """The Gated DeltaNet mixer on ``u`` [s, h] -> [s, h]. ``keep``, a
    dict with a row ``"at"``, receives under ``pre`` the state ``S_at``
    [h_v, d_k, d_v] (the tests and the study compare a carried state with
    it). ``state_dtype``: what the state is rounded to after every step
    (the study's planted fault; float32 as stated)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    s = u.shape[0]
    key_dim, value_dim = hk * dk, hv * dv
    conv_dim = 2 * key_dim + value_dim
    qkvz = u @ _f32(params[pre + "in_proj.weight"])
    z = qkvz[:, conv_dim:].reshape(s, hv, dv)
    rows = jnp.concatenate([jnp.zeros((taps - 1, conv_dim), jnp.float32),
                            qkvz[:, :conv_dim]])
    w = _f32(params[pre + "conv_weight"])
    x = sum(w[None, :, j] * rows[j:j + s] for j in range(taps))
    x = jax.nn.silu(x)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)
    group = hv // hk
    q = jnp.repeat(unit(x[:, :key_dim].reshape(s, hk, dk)) * dk ** -0.5,
                   group, axis=1)
    k = jnp.repeat(unit(x[:, key_dim:2 * key_dim].reshape(s, hk, dk)),
                   group, axis=1)
    v = x[:, 2 * key_dim:].reshape(s, hv, dv)
    ba = u @ _f32(params[pre + "ba_proj.weight"])
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_f32(params[pre + "A_log"])) * jax.nn.softplus(
        ba[:, hv:] + _f32(params[pre + "dt_bias"]))
    at = -1 if keep is None else keep["at"]

    def step(carry, inp):
        state, kept = carry
        i, qt, kt, vt, gt, bt = inp
        state = jnp.exp(gt)[:, None, None] * state
        told = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + kt[:, :, None] * (bt[:, None] * (vt - told)
                                          )[:, None, :]
        state = _round_to(state, state_dtype)
        return (state, jnp.where(i == at, state, kept)), \
            jnp.einsum("hkv,hk->hv", state, qt)
    zero = jnp.zeros((hv, dk, dv), jnp.float32)
    (_, kept), o = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(s), q, k, v, g, beta))
    if keep is not None:
        keep[pre] = kept
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"]) \
        * _f32(params[pre + "norm_weight"]) * jax.nn.silu(z)
    return o.reshape(s, value_dim) @ _f32(params[pre + "out_proj.weight"])


def _rotate(x, r: int, theta: float):
    """x [s, heads, d]: its first ``r`` dimensions rotated (rotate-half) at
    the positions 0 .. s - 1."""
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def _gated_attention(u, params: dict, pre: str, cfg: dict):
    """The gated attention mixer on ``u`` [s, h] -> [s, h]."""
    hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, s = cfg["rms_norm_eps"], u.shape[0]
    r = int(d * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    qkv = u @ _f32(params[pre + "qkv_proj.weight"])
    qg = qkv[:, :2 * hq * d].reshape(s, hq, 2 * d)
    k_v = qkv[:, 2 * hq * d:].reshape(s, 2, kv, d)
    q = _rotate(_norm(qg[..., :d], params[pre + "q_norm.weight"], eps),
                r, theta)
    k = _rotate(_norm(k_v[:, 0], params[pre + "k_norm.weight"], eps),
                r, theta)
    o = _attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                   k_v[:, 1].transpose(1, 0, 2), 0)
    o = o.transpose(1, 0, 2) * jax.nn.sigmoid(qg[..., d:])
    return o.reshape(s, hq * d) @ _f32(params[pre + "o_proj.weight"])


def _swiglu(u, gate_up, down):
    width = down.shape[0]
    gu = u @ _f32(gate_up)
    return (jax.nn.silu(gu[:, :width]) * gu[:, width:]) @ _f32(down)


def routed(u, params: dict, pre: str, cfg: dict, experts=None):
    """The routed part of the expert layer for u [t, h]: ``p = softmax(u
    W_r)`` over ALL experts, the ``top_k`` largest renormalised, and the
    terms of the experts ``experts`` = (lo, hi) of the router's outputs,
    whose weights are ``params[pre + "experts_*"]`` (None: the file's
    share, experts 0 .. ``num_experts``). Expert by expert, and for each
    the tokens that chose it, ``_EXPERT_ROWS`` at a time."""
    t, _ = u.shape
    w13, w2 = params[pre + "experts_gate_up"], params[pre + "experts_down"]
    lo = 0 if experts is None else experts[0]
    width = w2.shape[1]
    p = jax.nn.softmax(u @ _f32(params[pre + "router.weight"]), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    weight = top / jnp.sum(top, axis=-1, keepdims=True)
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
                          (lo + jnp.arange(w13.shape[0]), w13, w2))
    return out


def shared_expert(u, params: dict, pre: str):
    """The shared expert behind its sigmoid gate, for u [t, h]."""
    return jax.nn.sigmoid(u @ _f32(params[pre + "shared_gate.weight"])) \
        * _swiglu(u, params[pre + "shared.gate_up.weight"],
                  params[pre + "shared.down.weight"])


def hidden(params: dict, ids, cfg: dict, collect=None, keep=None,
           state_dtype=jnp.float32):
    """``ids`` int [b, s] -> the final normed hidden state float32
    [b, s, h], one request at a time. ``collect``, a list, receives the
    hidden state after every layer; ``keep``, a dict with a row ``"at"``,
    every DeltaNet layer's state at that row (of the last request)."""
    with jax.default_matmul_precision("highest"):
        eps, n = cfg["rms_norm_eps"], cfg["num_hidden_layers"]
        outs, kept = [], []
        for row in range(ids.shape[0]):
            x = _f32(params["model.embed.weight"][ids[row]])      # [s, h]
            for i in range(n):
                pre = f"model.layers.{i}."
                u = _norm(x, params[pre + "input_norm.weight"], eps)
                if _is_full(cfg, i):
                    x = x + _gated_attention(u, params, pre + "attn.", cfg)
                else:
                    x = x + _delta_net(u, params, pre + "gdn.", cfg, keep,
                                       state_dtype)
                u = _norm(x, params[pre + "post_norm.weight"], eps)
                x = x + routed(u, params, pre + "moe.", cfg) \
                    + shared_expert(u, params, pre + "moe.")
                if collect is not None:
                    kept.append(x)
            outs.append(_norm(x, params["model.norm.weight"], eps))
        if collect is not None:
            collect.extend(jnp.stack(kept[i::n]) for i in range(n))
        return jnp.stack(outs)


def head(params: dict, cfg: dict):
    """The output matrix float32 [h, held vocabulary rows] (untied)."""
    return _f32(params["lm_head.weight"])


def forward(params: dict, ids, cfg: dict, collect=None, keep=None,
            state_dtype=jnp.float32):
    """``ids`` int [b, s] -> logits float32 [b, s, vocab]: :func:`hidden`
    times :func:`head`."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, collect, keep, state_dtype) \
            @ head(params, cfg)


def loss(params: dict, ids, labels, cfg: dict):
    raise SystemExit(
        "the qwen3next family has no loss: its cell serves it, and the "
        "check compares logits (families/qwen3next.forward)")


# ------------------------------------------------------------------ counts

def _layers(cfg: dict):
    """(DeltaNet layers, full-attention layers) of the configuration."""
    full = sum(_is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward x3 of what one token passes on this share (the two mixers'
    projections, the delta rule's state products, the read over the keys
    before it, the router, the shared expert and the held chosen experts);
    no cell trains this family, the count is for a reader's arithmetic."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    f, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    linear, full = _layers(cfg)
    held = k * cfg["num_experts"] / float(router_width(cfg))
    gdn = 2.0 * h * (2 * hk * dk + 2 * hv * dv + 2 * hv) \
        + 2.0 * hv * dv * h + 4 * 2.0 * hv * dk * dv
    attn = 2.0 * h * (2 * hq + 2 * kv) * d + 2.0 * hq * d * h \
        + 4.0 * hq * d * _mean_keys(seq, 0)
    moe = 2.0 * h * router_width(cfg) + 2.0 * h \
        + 6.0 * h * cfg["shared_expert_intermediate_size"] \
        + held * 6.0 * h * f
    return 3.0 * (2.0 * h * cfg["vocab_size"] + linear * gdn + full * attn
                  + cfg["num_hidden_layers"] * moe)


def _dispatches(cfg: dict, job: dict):
    """(rows, bucket) of the prefill dispatch of every prompt of the job's
    multiset."""
    from .. import traffic as T
    e = cfg["engine"]
    budget = int(cfg.get("tokens_a_dispatch",
                         _program().Qwen3NextConfig.tokens_a_dispatch))
    out = []
    for p, _ in T.multiset(job):
        bucket = T.bucket_for(p, e["buckets"])
        out.append((max(1, min(e["max_slots"], budget // bucket)), bucket))
    return out


def gdn_chunk_flops(d_k: int, d_v: int, chunk: int) -> float:
    """Operations of one chunk of one value head of ``gdn_prefill`` (the
    docstring), each product counted once."""
    import math
    doublings = int(math.log2(chunk)) - 1
    return 2.0 * (2 * chunk * chunk * d_k            # K K^T, Q K^T
                  + 2 * doublings * chunk ** 3       # the inverse
                  + 2 * chunk * d_k * d_v            # K S, Q S
                  + 2 * chunk * chunk * d_v          # the solve, the output
                  + chunk * d_k * d_v)               # the state's update


def gdn_prefill_counts(cfg: dict, job: dict):
    from paddle_tpu.ops.pallas.gated_delta import CHUNK
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return _mean([
        (rows * (s // CHUNK) * hv * gdn_chunk_flops(dk, dv, CHUNK),
         4.0 * rows * (s * (2 * hk * dk + 2 * hv * dv + 2 * hv)
                       + hv * dk * dv))
        for rows, s in _dispatches(cfg, job)])


def gdn_decode_counts(cfg: dict):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    rows = cfg["engine"]["max_slots"]
    return (rows * 4 * 2.0 * hv * dk * dv,
            4.0 * rows * (2 * hv * dk * dv + 2 * hk * dk + 2 * hv * dv
                          + 2 * hv))


def kernel_counts(name: str, cfg: dict, job: dict, counters=None):
    """(flops, bytes) of one call of a named kernel in a serving job on one
    chip (see the module's docstring): the two delta-rule kernels by
    shape; the full layers' forward and paged read (the LFM2 family's
    count, by the configuration's own heads); the expert layer's
    grouped products at the rows the held experts got: a prompt's pass of
    ``moe_chunk_rows`` rows (0: the dispatch's own, at the traffic's mean
    dispatch) at the expectation (``top_k x held / routed`` pairs a row)
    over the held stack; a decode step's from the run's
    ``counters`` (``engine.expert_pairs`` rows over
    ``engine.experts_touched`` experts, a step a layer; without them every
    slot live under uniform routing). None for every other kernel."""
    if job.get("kind") not in ("closed_loop", "open_loop"):
        return None
    if name == "gdn_prefill":
        return gdn_prefill_counts(cfg, job)
    if name == "gdn_decode":
        return gdn_decode_counts(cfg)
    if name == "paged_decode_attn":
        return paged_decode_counts(cfg, counters)
    if name == "flash_fwd_full":
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        from paddle_tpu import flags
        least = int(flags.get_flag("pallas_min_seq"))
        # QK^T and PV; reads q | k v, writes o and the float32 lse
        return _mean([
            (rows * 2 * 2.0 * hq * s * _mean_keys(s, 0) * d,
             rows * ((2 * hq + 2 * kv) * s * d * float(_BYTES)
                     + hq * s * 4.0))
            for rows, s in _dispatches(cfg, job) if s >= least])
    if name not in _MOE_KERNELS:
        return None
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_in, n_out = {"moe_up": (h, 2 * f), "moe_down": (f, h)}[
        name[:-4] if name.endswith("_dec") else name]
    held, layers = cfg["num_experts"], cfg["num_hidden_layers"]
    share = cfg["num_experts_per_tok"] * held / float(router_width(cfg))
    if name.endswith("_dec") and counters is None:
        rows = cfg["engine"]["max_slots"] * share
        stack = held * (1.0 - (1.0 - 1.0 / held) ** rows)
    elif name.endswith("_dec"):
        rows = flops.traced_mean(counters, "expert_pairs",
                                 "sampler_dispatches")
        stack = flops.traced_mean(counters, "experts_touched",
                                  "sampler_dispatches")
        if not rows or not stack:
            return None
        rows, stack = rows / layers, stack / layers
    else:
        chunk = int(cfg.get("moe_chunk_rows",
                            _program().Qwen3NextConfig.moe_chunk_rows))
        # one pass of the call's rows (0: the whole dispatch), at the mean
        # dispatch of the traffic's prompts
        rows = _mean([(chunk or n * s,)
                      for n, s in _dispatches(cfg, job)])[0] * share
        stack = held
    return (2.0 * rows * n_in * n_out,
            (rows * n_in + stack * n_in * n_out + rows * n_out)
            * float(_BYTES))
