"""Operations of rotary / RMSNorm / sparse-expert decoders
(``models/laguna.py``): ``rms_norm``, ``rotary_embedding``, ``moe_router``
and ``moe_experts``.

The expert layer is *dropless* at static shapes. The router scores every
token over ALL experts and chooses ``top_k``; ``moe_experts`` is told which
contiguous range of experts it holds (its stacked weights' leading axis,
starting at ``expert_lo``) and computes every (token, held expert) pair,
whatever the imbalance; choices that fall on absent experts are left out
of the sum. A counting sort lays the pairs out by expert in a row buffer
(``pallas.grouped_matmul.tile_layout``: every expert starts on a tile of
rows) and the three expert products run as grouped matrix products whose
work follows the group sizes. On the TPU a gather or scatter costs by the
element, so the layout is made of dense pieces (a table of running counts
by a triangular product, lookups in tables of a few entries as masked sums)
and ONE scatter of choice numbers; what remains are the gathers of whole
rows: one into the buffer, and out of it one a slot (``_gather_sum``) or,
where the chip holds a small share of the experts and most slots of most
tokens are absent, the rows the held choices own (``_held_sum``).

The buffer has ``CAPACITY_FACTOR`` times the expected number of pairs; a
step whose pairs exceed it takes, by the ONE branch point inside the same
compiled program (a ``lax.switch`` on the step's own counts), a second path
that walks the tokens in chunks, each with room for all its pairs. So
routing never recompiles and never drops, and the common case does not pay
for the worst one. Where ``_plan`` chose the combine's prefix form for the
shapes the switch has a third arm: the fast buffer with that form, taken
when no prefix overflows; a step whose prefixes overflow inside a buffer
that fits keeps the fast buffer with the k-slot combine (the chunks cost
2.2x that layer on the v5e: PERF.md section 6, PR 36). Backward is written
out (no residual of the untaken branch is ever materialised): the fast path
keeps its sorted input and hidden rows, the chunked path recomputes them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import grouped_matmul as gm
from .registry import register

# ------------------------------------------------------------------ norm


@register("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """``x * rsqrt(mean(x^2) + eps) * w`` over the last axis, computed in
    float32 whatever the input's dtype, returned in the input's."""
    x, w = ins["X"][0], ins["Scale"][0]
    eps = float(attrs.get("epsilon", 1e-6))
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return {"Out": [(y * w.astype(jnp.float32)).astype(x.dtype)]}


# ---------------------------------------------------------------- rotary

def rotary_inv_freq(rot_dim: int, theta: float, yarn: dict = None):
    """-> (``inv_freq`` float64 [rot_dim / 2], ``attention_factor``).
    Plain: ``theta^(-2i / rot_dim)``. YaRN (as ``transformers`` computes it
    from ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``): a linear ramp between the two correction dimensions
    blends ``inv_freq`` (fast dimensions, extrapolated) with
    ``inv_freq / factor`` (slow ones, interpolated); cos and sin are then
    multiplied by ``attention_factor`` (``0.1 ln(factor) + 1`` when the
    configuration gives none)."""
    pos_freqs = theta ** (np.arange(0, rot_dim, 2, dtype=np.float64)
                          / rot_dim)
    inv = 1.0 / pos_freqs
    if not yarn:
        return inv, 1.0
    factor = float(yarn["factor"])
    orig = float(yarn["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rot_dim * math.log(orig / (rotations * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(yarn["beta_slow"]))),
               rot_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv = inv / factor * (1.0 - extrapolation) + inv * extrapolation
    att = yarn.get("attention_factor")
    return inv, float(att if att is not None
                      else 0.1 * math.log(factor) + 1.0)


def rotary_tables(seq: int, rot_dim: int, theta: float, yarn: dict = None):
    """cos and sin of ``position * inv_freq``, float32 [seq, rot_dim / 2],
    times the attention factor."""
    inv, att = rotary_inv_freq(rot_dim, theta, yarn)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None]
    return ((np.cos(ang) * att).astype(np.float32),
            (np.sin(ang) * att).astype(np.float32))


@register("rotary_embedding", no_grad_slots=("Cos", "Sin"))
def _rotary_embedding(ctx, ins, attrs):
    """Rotate the first ``r = 2 * Cos.shape[-1]`` dims of every head of X
    [b, h, s, d], pairs ``(i, i + r/2)`` (rotate-half); the other dims pass.
    Cos/Sin: [s, r/2]."""
    x, cos, sin = ins["X"][0], ins["Cos"][0], ins["Sin"][0]
    half = cos.shape[-1]
    cos, sin = cos.astype(jnp.float32), sin.astype(jnp.float32)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    out = jnp.concatenate(
        [p.astype(x.dtype) for p in parts] + [x[..., 2 * half:]], axis=-1)
    return {"Out": [out]}


# ---------------------------------------------------------------- router

@register("moe_router", no_grad_slots=("Bias",),
          nondiff_outputs=("TopkIdx",))
def _moe_router(ctx, ins, attrs):
    """X [.., h] x W [h, experts] -> the ``top_k`` experts of every token
    (TopkIdx int32 [.., k]) and their weights (TopkWeight float32 [.., k]):
    ``scale * s_e / sum of the chosen s``, ``s = sigmoid(x W)`` (attr
    ``score`` ``"softmax"``: the softmax over all experts) in float32 at
    full precision (under AMP the op is on the float32 list). With the
    optional input ``Bias`` [experts] the choice is ``top_k(s + Bias)`` and
    the weights are the chosen experts' *unbiased* ``s``, renormalised: a
    selection bias steers the load and never the mixture. Attr
    ``renorm_eps`` is added to the chosen scores' sum (0: none). Attrs
    ``n_group`` / ``topk_group`` (the choice by groups): the experts are
    ``n_group`` equal groups in index order, a group's score is the sum of
    its 2 largest selection scores (``s + Bias``), only the ``topk_group``
    best groups stay eligible and the ``top_k`` largest are taken inside
    them (ties to the lower index, of groups and of experts)."""
    x, w = ins["X"][0], ins["W"][0]
    k = int(attrs["top_k"])
    score = {"sigmoid": jax.nn.sigmoid,
             "softmax": lambda z: jax.nn.softmax(z, axis=-1)}[
                 attrs.get("score") or "sigmoid"]
    scores = score(jnp.matmul(
        x.astype(jnp.float32), w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    bias = (ins.get("Bias") or [None])[0]
    groups = int(attrs.get("n_group", 1))
    if bias is None and groups == 1:
        top, idx = jax.lax.top_k(scores, k)
    else:
        chosen_by = scores if bias is None \
            else scores + bias.astype(jnp.float32)
        if groups > 1:
            by_group = chosen_by.reshape(chosen_by.shape[:-1] + (groups, -1))
            best = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(best, int(attrs["topk_group"]))
            eligible = jnp.any(
                kept[..., None] == jnp.arange(groups, dtype=kept.dtype),
                axis=-2)                                    # [.., groups]
            chosen_by = jnp.where(eligible[..., None], by_group,
                                  -jnp.inf).reshape(chosen_by.shape)
        _, idx = jax.lax.top_k(chosen_by, k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    scaled = float(attrs.get("scale", 1.0)) * top
    total = jnp.sum(top, axis=-1, keepdims=True)
    eps = float(attrs.get("renorm_eps", 0.0))
    if eps:
        total = total + eps
    weight = scaled / total
    return {"TopkIdx": [idx.astype(jnp.int32)], "TopkWeight": [weight]}


# --------------------------------------------------------------- experts

def _silu_mul(h, f):
    g, u = h[:, :f].astype(jnp.float32), h[:, f:].astype(jnp.float32)
    return (jax.nn.silu(g) * u).astype(h.dtype)


def _gather_sum(rows, pos, valid, weight=None):
    """``sum_k where(valid[:, k], weight[:, k] * rows[pos[:, k]], 0)`` in
    float32: one gather of ``[t, h]`` for each of the ``k`` slots, whatever
    the routing (a slot gathered only when some token needs it would be a
    ``cond`` a slot, which costs a copy of the sum and makes the step's
    time follow the draw). The combine where every slot is live (all
    experts held, the decode regime) and of the chunked path; where few
    are, ``_plan`` chooses ``_held_sum``."""
    acc = jnp.zeros((pos.shape[0], rows.shape[1]), jnp.float32)
    for k in range(pos.shape[1]):
        r = jnp.take(rows, pos[:, k], axis=0).astype(jnp.float32)
        if weight is not None:
            r = r * weight[:, k, None].astype(jnp.float32)
        acc = acc + jnp.where(valid[:, k, None], r, 0.0)
    return acc


def _held_sum(rows, order, sizes, weight, dtype):
    """The same sum over the rows the held choices own. ``order``
    (``_held_order``) has the tokens sorted by their number of held choices,
    most first, and every token's held choices moved to the front in their
    own order: the tokens that have a ``j``-th held choice are then a prefix
    of that order, and rank ``j`` gathers ``sizes[j]`` rows (static, from
    ``_plan``; the caller takes another path when a prefix overflows)
    instead of ``t``. One more gather of ``t`` rows puts the sums back in
    token order, after the cast to ``dtype`` the caller would make next.
    Bit-identical to ``_gather_sum(...).astype(dtype)``: the held choices
    are added in the same order in float32, and what is skipped is what
    that form adds as ``+ 0.0``."""
    t, k = order["inv"].shape[0], len(sizes)
    if weight is not None:      # compacted like the rows, then ordered
        w = jnp.select([order["sel"][:, :, s] for s in range(k)],
                       [weight[:, s, None] for s in range(k)], 0)
        w = jnp.take(w, order["perm"], axis=0).astype(jnp.float32)
    # the tokens between two prefixes' ends have the same ranks to add
    bounds = sorted(set(sizes) | {0, t})
    parts = []
    for lo, hi in zip(bounds, bounds[1:]):
        held = order["held"][lo:hi, None]
        acc = jnp.zeros((hi - lo, rows.shape[1]), jnp.float32)
        for j, n in enumerate(sizes):
            if n < hi:
                break
            r = jnp.take(rows, order["pos"][lo:hi, j], axis=0) \
                .astype(jnp.float32)
            if weight is not None:
                r = r * w[lo:hi, j, None]
            acc = acc + jnp.where(j < held, r, 0.0)
        # an absent slot's + 0.0 also turns a sum of -0.0 into 0.0
        acc = acc + jnp.where(held < k, 0.0, -0.0)
        parts.append(acc.astype(dtype))
    return jnp.take(jnp.concatenate(parts), order["inv"], axis=0)


_COUNT_BLOCK = 256


def _running_counts(hot):
    """``hot`` bool [pairs, groups] (choice ``p`` chose group ``g``) ->
    ``c[p, g]`` = how many of the choices ``0 .. p`` chose ``g`` (int32): a
    counting sort's table, made without a sort. Blocks of 256 choices are
    summed by a product with a triangular matrix (0/1 values in bfloat16,
    float32 sums: exact), the blocks' totals by a short cumulative sum."""
    pairs, groups = hot.shape
    if pairs % _COUNT_BLOCK:
        return jnp.cumsum(hot.astype(jnp.int32), axis=0)
    tri = jnp.tril(jnp.ones((_COUNT_BLOCK, _COUNT_BLOCK), jnp.bfloat16))
    inner = jnp.einsum(
        "ij,bjg->big", tri,
        hot.astype(jnp.bfloat16).reshape(-1, _COUNT_BLOCK, groups),
        preferred_element_type=jnp.float32)
    totals = inner[:, -1, :]
    before = jnp.cumsum(totals, axis=0) - totals
    return (inner + before[:, None, :]).reshape(pairs, groups) \
        .astype(jnp.int32)


def _lookup(table, index):
    """``table[index]`` for a table of a few entries, as a masked sum (on
    the TPU a gather costs by the element whatever the table's size)."""
    hot = index[..., None] == jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(hot, table, 0), axis=-1)


def _route(local, valid, groups, tm, num_tiles, min_tiles=1):
    """The sorted layout of one set of tokens: ``local`` [t, k] the chosen
    experts as indices into the held range, ``valid`` which of them are
    held. -> dict of ``tile_group`` / ``n_active`` (the kernels' tables),
    ``tok`` [rows] the token of every buffer row (``t`` = none: a zero
    row), ``pair`` [rows] its flat choice, ``live`` [rows], ``pos`` [t, k]
    the row of every held choice, ``counts`` [groups], ``dropped``. A counting sort by expert that keeps the choices' order
    within an expert: the table gives every choice its row, one scatter of
    choice numbers gives every row its choice."""
    t, k = local.shape
    pairs = t * k
    key = jnp.where(valid, local, groups).reshape(pairs).astype(jnp.int32)
    hot = key[:, None] == jnp.arange(groups, dtype=jnp.int32)[None]
    running = _running_counts(hot)
    counts = running[-1]
    tile_group, n_active, row_start = gm.tile_layout(counts, tm, num_tiles,
                                                     min_tiles)
    rank = jnp.sum(jnp.where(hot, running, 0), axis=1) - 1
    rows = num_tiles * tm
    pos = jnp.where(valid.reshape(pairs), _lookup(row_start, key) + rank,
                    rows)
    dropped = jnp.sum(jnp.logical_and(valid.reshape(pairs), pos >= rows))
    pair = jnp.full((rows,), pairs, jnp.int32).at[pos].set(
        jnp.arange(pairs, dtype=jnp.int32), mode="drop",
        unique_indices=True)
    live = pair < pairs
    pair = jnp.minimum(pair, pairs - 1)
    pos = jnp.where(valid, jnp.minimum(pos, rows - 1).reshape(t, k), 0)
    return dict(tile_group=tile_group, n_active=n_active, live=live,
                tok=jnp.where(live, pair // k, t), pair=pair, pos=pos,
                counts=counts, dropped=dropped)


def _held_order(valid, pos, sizes):
    """What ``_held_sum`` reads, from ``valid`` / ``pos`` [t, k] (``_route``'s)
    and the prefixes' static ``sizes``: ``perm`` [t] the tokens by their
    number of held choices, most first, ties in token order (a counting sort
    over its ``k + 1`` values, made like ``_route``'s), ``inv`` its inverse,
    ``held`` [t] that number in the sorted order, ``sel`` [t, k, k] (slot
    ``s`` is the token's ``j``-th held choice, in token order), ``pos``
    [t, k] the row of the ``j``-th held choice of the sorted order's tokens
    (compacted in token order, then ONE gather of ``[t, k]``: on the v5e a
    gather of a few thousand scalars a rank costs as much as this one, so
    eight of them cost more), and ``fits``: no rank has more tokens than
    its prefix."""
    t, k = valid.shape
    key = k - jnp.sum(valid, axis=1, dtype=jnp.int32)
    hot = key[:, None] == jnp.arange(k + 1, dtype=jnp.int32)[None]
    running = _running_counts(hot)
    upto = jnp.cumsum(running[-1])          # tokens with k - i or more held
    inv = _lookup(upto - running[-1], key) \
        + jnp.sum(jnp.where(hot, running, 0), axis=1) - 1
    perm = jnp.zeros((t,), jnp.int32).at[inv].set(
        jnp.arange(t, dtype=jnp.int32), unique_indices=True)
    rank = jnp.cumsum(valid, axis=1, dtype=jnp.int32) - 1
    sel = jnp.logical_and(
        valid[:, None, :],
        rank[:, None, :] == jnp.arange(k, dtype=jnp.int32)[None, :, None])
    front = jnp.sum(jnp.where(sel, pos[:, None, :], 0), axis=2)
    return dict(
        perm=perm, inv=inv, sel=sel,
        pos=jnp.take(front, perm, axis=0),
        # the sorted order's i-th token is past the tokens of so many keys
        held=k - jnp.sum(jnp.arange(t, dtype=jnp.int32)[:, None]
                         >= upto[None, :k], axis=1, dtype=jnp.int32),
        fits=jnp.all(upto[:k][::-1] <= jnp.asarray(sizes, jnp.int32)))


def _set_fwd(x, weight, route, valid, w13, w2, tm, names=("moe_up",
                                                         "moe_down"),
             sizes=()):
    """Forward of one set of tokens -> (out [t, h], (xs, hid, y)); ``out``
    is float32, or with ``sizes`` (the prefix form of the combine, which
    reads ``route["order"]``) already in ``x``'s dtype."""
    f = w2.shape[1]
    tg, na = route["tile_group"], route["n_active"]
    xs = jnp.take(x, route["tok"], axis=0, mode="fill", fill_value=0)
    hid = gm.gmm(xs, w13, tg, na, name=names[0], tm=tm)
    y = gm.gmm(_silu_mul(hid, f), w2, tg, na, name=names[1], tm=tm)
    if sizes:
        out = _held_sum(y, route["order"], sizes, weight, x.dtype)
    else:
        out = _gather_sum(y, route["pos"], valid, weight)
    return out, (xs, hid, y)


def _set_bwd(x, weight, route, valid, w13, w2, tm, dout, saved, sizes=()):
    """Backward of one set of tokens -> (dx, dweight, dw13, dw2); ``saved``
    is the forward's (xs, hid, y) or None to recompute them; ``sizes`` as
    in ``_set_fwd``, for the input gradient's combine."""
    f = w2.shape[1]
    tg, na, pos = route["tile_group"], route["n_active"], route["pos"]
    groups = w13.shape[0]
    if saved is None:
        _, saved = _set_fwd(x, weight, route, valid, w13, w2, tm)
    xs, hid, y = saved
    dt = xs.dtype
    # the combine's two gradients in the sorted domain: a row's incoming
    # gradient is its token's, so the weight's is a dot a row (mapped back
    # to choices by a gather of scalars) and y's is that row scaled
    dout_row = jnp.take(dout, route["tok"], axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
    dw_row = jnp.sum(dout_row * y.astype(jnp.float32), axis=-1)
    dweight = jnp.where(valid, dw_row[pos], 0.0).astype(weight.dtype)
    w_row = jnp.where(route["live"],
                      weight.reshape(-1)[route["pair"]].astype(jnp.float32),
                      0.0)
    dy = (dout_row * w_row[:, None]).astype(dt)
    act = _silu_mul(hid, f)
    da = gm.gmm(dy, w2, tg, na, name="moe_down_dx", tm=tm,
                transpose_rhs=True).astype(jnp.float32)
    dw2 = gm.tgmm(act, dy, tg, na, groups, name="moe_down_dw", tm=tm,
                  tk=f, tn=min(w2.shape[2], (1 << 20) // f))
    g, u = hid[:, :f].astype(jnp.float32), hid[:, f:].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    dhid = jnp.concatenate(
        [da * u * sig * (1.0 + g * (1.0 - sig)), da * g * sig],
        axis=1).astype(dt)
    dxs = gm.gmm(dhid, w13, tg, na, name="moe_up_dx", tm=tm,
                 transpose_rhs=True)
    dw13 = gm.tgmm(xs, dhid, tg, na, groups, name="moe_up_dw", tm=tm)
    if sizes:
        dx = _held_sum(dxs, route["order"], sizes, None, x.dtype)
    else:
        dx = _gather_sum(dxs, pos, valid).astype(x.dtype)
    return dx, dweight, dw13, dw2


#: rows of the fast buffer over the expected number of (token, held expert)
#: pairs. Under near-uniform routing the pairs stay within a few percent of
#: the expectation; twice it leaves the chunked path to real imbalance.
CAPACITY_FACTOR = 2.0

#: a prefix of the combine's prefix form has room for the expected number
#: of its tokens and this many standard deviations (or CAPACITY_FACTOR
#: times the expectation, if that is more)
PREFIX_SIGMAS = 6.0


def _prefix_sizes(tokens, top_k, groups, num_experts, tm):
    """Rows of ``_held_sum``'s ``top_k`` prefixes: for rank ``j`` the
    expected number of tokens with more than ``j`` of their ``top_k``
    distinct choices among the ``groups`` held of ``num_experts``
    (hypergeometric), with the margin above, in whole tiles, at most
    ``tokens``; 0 where no token can have so many."""
    pmf = [math.comb(groups, c) * math.comb(num_experts - groups, top_k - c)
           / math.comb(num_experts, top_k) for c in range(top_k + 1)]
    sizes = []
    for j in range(top_k):
        p = sum(pmf[j + 1:])
        mean = tokens * p
        rows = max(CAPACITY_FACTOR * mean,
                   mean + PREFIX_SIGMAS * math.sqrt(mean * (1.0 - p)))
        sizes.append(min(tokens, -(-int(math.ceil(rows)) // tm) * tm))
    return tuple(sizes)


def _plan(tokens, top_k, groups, num_experts, tm):
    """-> (tiles of the fast buffer, chunks of the second path or 0 when
    the fast buffer already holds every pair, the combine's prefix sizes or
    () for the k-slot form). The form is chosen from the shapes alone: the
    prefix form where a second path exists to take its overflow and it
    reads, its un-permute included, under three quarters of the k-slot
    form's ``top_k x tokens`` rows. Where every expert is held every slot
    is live, ``top_k`` rows a token is the floor of a gathered combine and
    the k-slot form reads no more."""
    pairs = tokens * top_k
    expected = pairs * groups / float(num_experts)
    rows = min(pairs, int(math.ceil(CAPACITY_FACTOR * expected)))
    rows = -(-rows // tm) * tm
    if rows >= pairs:
        return -(-pairs // tm) + groups, 0, ()
    chunks = next(c for c in range(-(-pairs // rows), tokens + 1)
                  if tokens % c == 0)
    sizes = _prefix_sizes(tokens, top_k, groups, num_experts, tm)
    if 4 * (sum(sizes) + tokens) > 3 * pairs:
        sizes = ()
    return rows // tm + groups, chunks, sizes


def _chunk_tiles(tokens, chunks, top_k, groups, tm):
    """Tiles of one chunk's buffer: room for all its pairs."""
    return -(-(tokens // chunks) * top_k // tm) + groups


def _chunked(a, chunks):
    return a.reshape((chunks, a.shape[0] // chunks) + a.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def moe_experts(x, weight, idx, w13, w2, expert_lo, num_experts, tm):
    """x [t, h], weight/idx [t, k] (the router's), w13 [g, h, 2f] (gate and
    up side by side), w2 [g, f, h] -> (out [t, h], stats float32 [5]:
    pairs on held experts, largest expert load over the mean load, dropped
    pairs, 1 if the fast buffer held them all, the rows the combine
    gathered over ``t x k``).

    One branch point chooses the step's path from its counts: the chunked
    path when the pairs overflow the fast buffer; else the fast buffer, its
    combine in the prefix form (``_held_sum``) when ``_plan`` chose it for
    these shapes and no prefix overflows, and in the k-slot form
    (``_gather_sum``) otherwise. The three give the same bits."""
    return _experts_fwd(x, weight, idx, w13, w2, expert_lo, num_experts,
                        tm)[0]


# the forward and the backward are jitted so that a program traces and
# lowers their arms once, not once a layer and pass: tracing the kernels
# was 30 s of laguna_pretrain_8k's 68 s of set-up on the chip's host, 44
# with a third arm, and is 21 so (PERF.md section 6, PR 36)
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _experts_fwd(x, weight, idx, w13, w2, expert_lo, num_experts, tm):
    t, k = idx.shape
    groups = w13.shape[0]
    tiles, chunks, sizes = _plan(t, k, groups, num_experts, tm)
    local = idx - expert_lo
    valid = jnp.logical_and(local >= 0, local < groups)
    route = _route(local, valid, groups, tm, tiles)
    counts = route["counts"].astype(jnp.float32)
    total = jnp.sum(counts)

    def fast(prefixes):
        out, saved = _set_fwd(x, weight, route, valid, w13, w2, tm,
                              sizes=prefixes)
        return out.astype(x.dtype), saved, route["dropped"]

    if not chunks:
        out, saved, dropped = fast(())
        path = jnp.ones((), jnp.int32)
    else:
        chunk_tiles = _chunk_tiles(t, chunks, k, groups, tm)

        def slow(_):
            def one(c):
                xc, wc, lc, vc = c
                rc = _route(lc, vc, groups, tm, chunk_tiles)
                return _set_fwd(xc, wc, rc, vc, w13, w2, tm)[0], \
                    rc["dropped"]
            out, dropped = jax.lax.map(
                one, tuple(_chunked(a, chunks)
                           for a in (x, weight, local, valid)))
            rows = tiles * tm
            f2 = w13.shape[2]
            empty = (jnp.zeros((rows, x.shape[1]), x.dtype),
                     jnp.zeros((rows, f2), x.dtype),
                     jnp.zeros((rows, x.shape[1]), x.dtype))
            return out.reshape(t, -1).astype(x.dtype), empty, \
                jnp.sum(dropped)

        # 0 the chunked path, 1 the fast buffer, 2 with the prefix form
        path = (total <= (tiles - groups) * tm).astype(jnp.int32)
        arms = [slow, lambda _: fast(())]
        if sizes:
            route["order"] = _held_order(valid, route["pos"], sizes)
            path = path * (1 + route["order"]["fits"].astype(jnp.int32))
            arms.append(lambda _: fast(sizes))
        out, saved, dropped = jax.lax.switch(path, arms, None)
    share = (sum(sizes) + t) / float(t * k) if sizes else 1.0
    stats = jnp.stack([total, jnp.max(counts) / jnp.maximum(
        total / groups, 1.0), dropped.astype(jnp.float32),
        (path > 0).astype(jnp.float32), jnp.where(path == 2, share, 1.0)])
    res = (x, weight, local, valid, w13, w2, route, saved, path)
    return (out, stats), res


def _experts_bwd(expert_lo, num_experts, tm, res, cts):
    dx, dweight, dw13, dw2 = _experts_grads(expert_lo, num_experts, tm, res,
                                            cts[0])
    return dx, dweight, np.zeros(res[2].shape, jax.dtypes.float0), dw13, dw2


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _experts_grads(expert_lo, num_experts, tm, res, dout):
    x, weight, local, valid, w13, w2, route, saved, path = res
    t, k = local.shape
    groups = w13.shape[0]
    _, chunks, sizes = _plan(t, k, groups, num_experts, tm)

    def fast(prefixes):
        return _set_bwd(x, weight, route, valid, w13, w2, tm, dout, saved,
                        prefixes)

    if not chunks:
        return fast(())
    chunk_tiles = _chunk_tiles(t, chunks, k, groups, tm)

    def slow(_):
        def one(carry, c):
            xc, wc, lc, vc, dc = c
            rc = _route(lc, vc, groups, tm, chunk_tiles)
            dx, dw, d13, d2 = _set_bwd(xc, wc, rc, vc, w13, w2, tm, dc, None)
            return (carry[0] + d13.astype(jnp.float32),
                    carry[1] + d2.astype(jnp.float32)), (dx, dw)
        zero = (jnp.zeros(w13.shape, jnp.float32),
                jnp.zeros(w2.shape, jnp.float32))
        (d13, d2), (dx, dw) = jax.lax.scan(
            one, zero, tuple(_chunked(a, chunks)
                             for a in (x, weight, local, valid, dout)))
        return (dx.reshape(x.shape), dw.reshape(weight.shape),
                d13.astype(w13.dtype), d2.astype(w2.dtype))

    arms = [slow, lambda _: fast(())]
    if sizes:
        arms.append(lambda _: fast(sizes))
    return jax.lax.switch(path, arms, None)


moe_experts.defvjp(_experts_fwd, _experts_bwd)


#: rows of a tile of the decode regime's buffer: bfloat16's sublane pack,
#: the least a grouped product's row tile can be
DECODE_TILE_M = 16


def moe_experts_decode_counts(x, weight, idx, w13, w2, live=None,
                              tm: int = DECODE_TILE_M, held=None):
    """The expert layer at a few rows (a decode step: one row a request),
    forward only. x [t, h], weight / idx [t, k] (``idx`` counted from the
    first held expert), w13 [e, h, 2f], w2 [e, f, h], ``live`` bool [t]
    (rows of empty slots route nowhere), ``held`` bool [t, k] (where a
    share of the experts is held: which choices fell among them; the
    others add nothing) -> (out float32 [t, h], the live rows every held
    expert got, int32 [e]).

    Where training has thousands of rows a call, tiles of 128 rows and is
    bound by compute, a step of 16 rows x 8 choices touches most of the
    experts with two or three rows each and is bound by the read of their
    weights. So the same sorted buffer is laid out in tiles of ``tm`` = 16
    rows, an expert no row chose owns no tile (its weights are not read:
    ``tile_layout(min_tiles=0)``), and the buffer has room for every pair
    whatever the routing (``t x k`` pairs can open at most ``min(t x k,
    e)`` tiles beyond ``t x k / tm``), so there is no second path. The two
    products are ``moe_up_dec`` / ``moe_down_dec`` in a device trace."""
    t, k = idx.shape
    groups = w13.shape[0]
    pairs = t * k
    tiles = -(-pairs // tm) + min(pairs, groups)
    valid = jnp.ones((t, k), bool) if live is None \
        else jnp.broadcast_to(live[:, None], (t, k))
    if held is not None:
        valid = jnp.logical_and(valid, held)
    route = _route(idx, valid, groups, tm, tiles, min_tiles=0)
    out, _ = _set_fwd(x, weight, route, valid, w13, w2, tm,
                      names=("moe_up_dec", "moe_down_dec"))
    return out, route["counts"].astype(jnp.int32)


def moe_experts_decode(x, weight, idx, w13, w2, live=None,
                       tm: int = DECODE_TILE_M):
    """:func:`moe_experts_decode_counts` -> (out float32 [t, h], experts
    touched int32 [])."""
    out, counts = moe_experts_decode_counts(x, weight, idx, w13, w2, live,
                                            tm)
    return out, jnp.sum(counts > 0).astype(jnp.int32)


@register("moe_experts", no_grad_slots=("TopkIdx",),
          nondiff_outputs=("Stats",))
def _moe_experts(ctx, ins, attrs):
    """X [.., h], TopkIdx / TopkWeight [.., k] (``moe_router``'s), WGateUp
    [held, h, 2f], WDown [held, f, h] -> Out [.., h]: the held experts'
    part of ``sum_e w_e FFN_e(x)`` (SwiGLU), and Stats (see
    :func:`moe_experts`). Attrs: ``expert_lo`` (the first held expert's
    id), ``num_experts`` (all the router chooses from), ``tile_m`` (rows
    of a tile; the kernels' 128 unless a toy size asks for less)."""
    x = ins["X"][0]
    idx, weight = ins["TopkIdx"][0], ins["TopkWeight"][0]
    w13, w2 = ins["WGateUp"][0], ins["WDown"][0]
    h, k = x.shape[-1], idx.shape[-1]
    out, stats = moe_experts(
        x.reshape(-1, h), weight.reshape(-1, k).astype(x.dtype),
        idx.reshape(-1, k), w13.astype(x.dtype), w2.astype(x.dtype),
        int(attrs.get("expert_lo", 0)), int(attrs["num_experts"]),
        int(attrs.get("tile_m", gm.TILE_M)))
    return {"Out": [out.reshape(x.shape)], "Stats": [stats]}
