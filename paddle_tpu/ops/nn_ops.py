"""Neural-net op lowerings: conv, pool, normalization, losses, embedding.

Analogs of reference kernels: conv_op/conv_cudnn_op.cu, pool_op,
batch_norm_op.cu, layer_norm_op.cu, softmax_op, softmax_with_cross_entropy_op,
dropout_op.cu, lookup_table_v2_op.cu (paddle/fluid/operators/). Convs and
matmuls map onto the MXU via lax.conv_general_dilated / dot_general; the
rest fuse into them under XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.program import convert_dtype
from .registry import GRAD_SLOT_SUFFIX, _generic_grad_lowering, register


# ---------------------------------------------------------------------------
# Convolution / pooling
# ---------------------------------------------------------------------------

def _conv_padding(paddings, ndim):
    if isinstance(paddings, str):
        return paddings.upper()  # SAME / VALID
    p = list(paddings)
    if len(p) == ndim:          # [ph, pw]
        return [(int(x), int(x)) for x in p]
    if len(p) == 2 * ndim:      # [ph0, ph1, pw0, pw1]
        return [(int(p[2 * i]), int(p[2 * i + 1])) for i in range(ndim)]
    raise ValueError(f"bad paddings {paddings}")


@register("conv2d", no_grad_slots=())
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    pad = _conv_padding(attrs.get("paddings", [0, 0]), 2)
    dil = [int(d) for d in attrs.get("dilations", [1, 1])]
    groups = int(attrs.get("groups", 1))
    fmt = attrs.get("data_format", "NCHW")
    # Filter layout is always OIHW in the reference regardless of
    # data_format (operators/conv_op.cc).
    if fmt in ("NCHW", "AnyLayout"):
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NCHW", "OIHW", "NCHW"))
    else:
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "OIHW", "NHWC"))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dil,
        dimension_numbers=dn, feature_group_count=groups)
    return {"Output": [out]}


@register("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    # channels = groups; reference separates this op, we share the lowering
    return _conv2d(ctx, ins, attrs)


def _transpose_pad(pad, kdims, dil):
    """jax.lax.conv_transpose pads the stride-dilated input directly and
    runs a VALID conv, so its padding relates to the paddle/torch
    conv-transpose padding p as  p_jax = dilation*(k-1) - p  per side
    (verified numerically vs torch; with k=3, p=1 the two coincide, which
    is how the old pass-through survived the original sweep). String
    paddings (SAME/VALID) pass through untouched — jax resolves those
    itself."""
    if isinstance(pad, str):
        return pad
    return [(d * (k - 1) - lo, d * (k - 1) - hi)
            for (lo, hi), k, d in zip(pad, kdims, dil)]


@register("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]  # w: [in, out/groups, kh, kw]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    pad = _conv_padding(attrs.get("paddings", [0, 0]), 2)
    dil = [int(d) for d in attrs.get("dilations", [1, 1])]
    groups = int(attrs.get("groups", 1))
    if groups != 1:
        raise NotImplementedError("grouped conv2d_transpose")
    pad = _transpose_pad(pad, w.shape[2:], dil)
    # paddle filter layout [in, out, kh, kw] -> [kh, kw, out, in]:
    # with transpose_kernel=True jax flips the spatial dims and swaps
    # I<->O internally, so the HWIO slots must carry (O=out, I=in)
    # pre-swap -> effective input channels match lhs (caught by the
    # numerical-grad sweep; the old (2,3,0,1) transpose put in/out
    # backwards and failed for in_ch != out_ch)
    out = jax.lax.conv_transpose(
        x, jnp.transpose(w, (2, 3, 1, 0)),
        strides=strides, padding=pad, rhs_dilation=dil,
        dimension_numbers=("NCHW", "HWIO", "NCHW"),
        transpose_kernel=True)
    return {"Output": [out]}


@register("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = [int(s) for s in attrs.get("strides", [1, 1, 1])]
    pad = _conv_padding(attrs.get("paddings", [0, 0, 0]), 3)
    dil = [int(d) for d in attrs.get("dilations", [1, 1, 1])]
    groups = int(attrs.get("groups", 1))
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCDHW", "OIDHW", "NCDHW"))
    out = jax.lax.conv_general_dilated(
        x, w, strides, pad, rhs_dilation=dil, dimension_numbers=dn,
        feature_group_count=groups)
    return {"Output": [out]}


@register("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    ptype = attrs.get("pooling_type", "max")
    ksize = [int(k) for k in attrs.get("ksize", [2, 2])]
    strides = [int(s) for s in attrs.get("strides", ksize)]
    paddings = attrs.get("paddings", [0, 0])
    global_pool = attrs.get("global_pooling", False)
    adaptive = attrs.get("adaptive", False)
    exclusive = attrs.get("exclusive", True)
    ceil_mode = attrs.get("ceil_mode", False)

    if adaptive:
        oh, ow = ksize
        if (x.shape[2] % oh == 0) and (x.shape[3] % ow == 0):
            kh, kw = x.shape[2] // oh, x.shape[3] // ow
            ksize, strides, paddings = [kh, kw], [kh, kw], [0, 0]
            global_pool = False
        else:
            raise NotImplementedError(
                "adaptive pool with non-divisible sizes")
    if global_pool:
        ksize = [x.shape[2], x.shape[3]]
        strides = ksize
        paddings = [0, 0]

    pad2 = _conv_padding(paddings, 2)
    if isinstance(pad2, str):
        raise NotImplementedError("string padding for pool2d")
    if ceil_mode:
        # pad extra on the high side so windows cover the input
        new_pad = []
        for i, (lo, hi) in enumerate(pad2):
            dim = x.shape[2 + i]
            rem = (dim + lo + hi - ksize[i]) % strides[i]
            extra = (strides[i] - rem) % strides[i]
            new_pad.append((lo, hi + extra))
        pad2 = new_pad
    window = (1, 1) + tuple(ksize)
    strides4 = (1, 1) + tuple(strides)
    pad4 = ((0, 0), (0, 0)) + tuple(pad2)

    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4, pad4)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4, pad4)
        if exclusive and any(p != (0, 0) for p in pad2):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           strides4, pad4)
            out = summed / counts
        else:
            out = summed / float(np.prod(ksize))
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# Softmax / losses
# ---------------------------------------------------------------------------

@register("softmax")
def _softmax(ctx, ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=axis)]}


@register("log_softmax")
def _log_softmax(ctx, ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.log_softmax(ins["X"][0], axis=axis)]}


def _hard_label_stats(logits, label, axis, ignore_index):
    """What the hard-label cross-entropy needs of its logits, forward and
    backward, from ONE read of them in the dtype they arrive in -> (z, lse,
    onehot, valid): the logits in the statistics' dtype (float32; float64
    for float64 logits), the log-sum-exp a row, the label's column and
    which rows count, the last three with ``axis`` kept as 1. No array of
    the logits' size is written: the convert, the exponent and the compare
    fuse into the reductions (or the products) that read them."""
    lbl = label if label.ndim == logits.ndim else jnp.expand_dims(label, axis)
    # Mask label == ignore_index for ANY value (reference kernel semantics;
    # conventional default is -100). Clamp so an out-of-range label picks
    # a class that exists.
    valid = lbl != ignore_index
    safe_lbl = jnp.clip(jnp.where(valid, lbl, 0), 0,
                        logits.shape[axis] - 1).astype(jnp.int32)
    z = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
    m = jnp.max(z, axis=axis, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(z - m), axis=axis, keepdims=True))
    column = jax.lax.broadcasted_iota(jnp.int32, z.shape, axis % z.ndim)
    return z, lse, column == safe_lbl, valid


@register("softmax_with_cross_entropy", no_grad_slots=("Label",),
          nondiff_outputs=("Softmax",))
def _softmax_with_cross_entropy(ctx, ins, attrs):
    """reference operators/softmax_with_cross_entropy_op.cu. Hard labels:
    one pass over the logits (``_hard_label_stats``), the loss in the
    statistics' dtype; ``Softmax`` is computed from the logits for the
    reference's contract and is dead code where nobody reads it."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1)
    if attrs.get("soft_label", False):
        logp = jax.nn.log_softmax(logits, axis=axis)
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
        return {"Softmax": [jnp.exp(logp)], "Loss": [loss]}
    with jax.named_scope("vocab_loss"):
        z, lse, onehot, valid = _hard_label_stats(
            logits, label, axis, attrs.get("ignore_index", -100))
        picked = jnp.sum(jnp.where(onehot, z, 0.0), axis=axis, keepdims=True)
        loss = jnp.where(valid, lse - picked, 0.0)
        softmax = jnp.exp(z - lse).astype(logits.dtype)
    return {"Softmax": [softmax], "Loss": [loss]}


@register("softmax_with_cross_entropy_grad")
def _softmax_with_cross_entropy_grad(ctx, ins, attrs):
    """``Logits@GRAD = g * (softmax - onehot)`` in the logits' dtype,
    rounded once; the log-sum-exp is the forward's expression again, so
    inside one program XLA computes it once. Nothing of the logits' size
    is written but the gradient itself (and that fuses into the products
    that read it). Soft labels keep the generic vjp."""
    if attrs.get("soft_label", False):
        return _generic_grad_lowering(ctx, "softmax_with_cross_entropy",
                                      ins, attrs)
    if ins.get("Softmax" + GRAD_SLOT_SUFFIX):
        raise NotImplementedError(
            "softmax_with_cross_entropy_grad takes a cotangent on Loss "
            "only: Softmax is a non-differentiable output of the op "
            "(differentiate softmax + cross_entropy where its gradient "
            "is wanted)")
    logits, label = ins["Logits"][0], ins["Label"][0]
    with jax.named_scope("vocab_loss"):
        z, lse, onehot, valid = _hard_label_stats(
            logits, label, attrs.get("axis", -1),
            attrs.get("ignore_index", -100))
        g = jnp.where(valid, ins["Loss" + GRAD_SLOT_SUFFIX][0], 0.0)
        d = g.astype(z.dtype) * (jnp.exp(z - lse) - onehot.astype(z.dtype))
    return {"Logits" + GRAD_SLOT_SUFFIX: [d.astype(logits.dtype)]}


@register("cross_entropy", no_grad_slots=("Label",))
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    soft_label = attrs.get("soft_label", False)
    eps = 1e-12
    if soft_label:
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, -1)
        picked = jnp.take_along_axis(
            x, jnp.expand_dims(lbl, -1).astype(jnp.int32), axis=-1)
        loss = -jnp.log(picked + eps)
    return {"Y": [loss]}


@register("sigmoid_cross_entropy_with_logits", no_grad_slots=("Label",))
def _sce_logits(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    ignore_index = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    mask = label != ignore_index
    loss = jnp.where(mask, loss, 0.0)
    if attrs.get("normalize", False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(x.dtype)), 1.0)
    return {"Out": [loss]}


@register("squared_l2_distance")
def _squared_l2_distance(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sub = x - y
    return {"sub_result": [sub],
            "Out": [jnp.sum(jnp.square(sub), axis=-1, keepdims=True)]}


@register("huber_loss", no_grad_slots=("Y",))
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Residual": [r], "Out": [loss]}


@register("smooth_l1_loss", no_grad_slots=("Y",))
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Diff": [d], "Out": [loss]}


@register("mse_loss", no_grad_slots=("Label",))
def _mse_loss(ctx, ins, attrs):
    x, label = ins["Input"][0], ins["Label"][0]
    return {"Out": [jnp.square(x - label)]}


@register("kldiv_loss", no_grad_slots=("Target",))
def _kldiv_loss(ctx, ins, attrs):
    x, tgt = ins["X"][0], ins["Target"][0]
    reduction = attrs.get("reduction", "mean")
    loss = tgt * (jnp.log(jnp.maximum(tgt, 1e-12)) - x)
    loss = jnp.where(tgt > 0, loss, 0.0)
    if reduction == "mean":
        return {"Loss": [jnp.mean(loss)]}
    if reduction == "sum":
        return {"Loss": [jnp.sum(loss)]}
    if reduction == "batchmean":
        return {"Loss": [jnp.sum(loss) / x.shape[0]]}
    return {"Loss": [loss]}


@register("label_smooth", no_grad_slots=("PriorDist",))
def _label_smooth(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    if ins.get("PriorDist"):
        prior = ins["PriorDist"][0]
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

@register("batch_norm", no_grad_slots=("Mean", "Variance"),
          nondiff_outputs=("MeanOut", "VarianceOut", "SavedMean",
                           "SavedVariance", "ReserveSpace"))
def _batch_norm(ctx, ins, attrs):
    """reference operators/batch_norm_op.cu. Running stats update is
    functional: MeanOut/VarianceOut rebind the state vars in the env."""
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    fmt = attrs.get("data_format", "NCHW")
    use_global = attrs.get("use_global_stats", False) or is_test

    if fmt == "NCHW":
        caxis = 1
    else:
        caxis = x.ndim - 1
    raxes = tuple(i for i in range(x.ndim) if i != caxis)
    bshape = [1] * x.ndim
    bshape[caxis] = x.shape[caxis]

    if use_global:
        m, v = mean, var
        mean_out, var_out = mean, var
        saved_m, saved_v = mean, var
    else:
        m = jnp.mean(x, axis=raxes)
        v = jnp.var(x, axis=raxes)
        mean_out = momentum * mean + (1 - momentum) * m
        var_out = momentum * var + (1 - momentum) * v
        saved_m, saved_v = m, jax.lax.rsqrt(v + eps)
    inv = jax.lax.rsqrt(v + eps)
    y = (x - m.reshape(bshape)) * inv.reshape(bshape) * \
        scale.reshape(bshape) + bias.reshape(bshape)
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_m], "SavedVariance": [saved_v]}


@register("layer_norm")
def _layer_norm(ctx, ins, attrs):
    """reference operators/layer_norm_op.cu; see also the pallas fused
    variant in paddle_tpu/ops/pallas/layer_norm.py."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    if (len(axes) == 1 and ins.get("Scale") and ins.get("Bias")
            and x.shape[-1] % 128 == 0):
        from .. import flags
        if flags.get_flag("use_pallas_layer_norm"):
            from .pallas.layer_norm import fused_layer_norm_with_stats
            y, m, v = fused_layer_norm_with_stats(
                x, ins["Scale"][0], ins["Bias"][0], eps)
            stat_shape = x.shape[:begin]
            return {"Y": [y], "Mean": [m.reshape(stat_shape)],
                    "Variance": [v.reshape(stat_shape)]}
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(v + eps)
    y = (x - m) * inv
    nshape = x.shape[begin:]
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(nshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(nshape)
    stat_shape = x.shape[:begin]
    return {"Y": [y], "Mean": [m.reshape(stat_shape)],
            "Variance": [v.reshape(stat_shape)]}


@register("group_norm")
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - m) * jax.lax.rsqrt(v + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    return {"Y": [y], "Mean": [m.reshape(n, groups)],
            "Variance": [v.reshape(n, groups)]}


@register("instance_norm")
def _instance_norm(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    y = (x - m) * jax.lax.rsqrt(v + eps)
    c = x.shape[1]
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    n_, c_ = x.shape[0], x.shape[1]
    return {"Y": [y], "SavedMean": [m.reshape(n_, c_)],
            "SavedVariance": [v.reshape(n_, c_)]}


@register("norm")
def _norm(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


# ---------------------------------------------------------------------------
# Dropout — custom grad via saved Mask (vjp would re-draw the mask)
# ---------------------------------------------------------------------------

@register("dropout", grad_drops_inputs=("X",), grad_needs_outputs=("Mask",),
          nondiff_outputs=("Mask",))
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False)
    impl = attrs.get("dropout_implementation", "upscale_in_train")
    if is_test or p == 0.0:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": [out], "Mask": [jnp.ones_like(x, dtype=jnp.uint8)]}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
    return {"Out": [out], "Mask": [keep.astype(jnp.uint8)]}


@register("dropout_grad")
def _dropout_grad(ctx, ins, attrs):
    g = ins["Out@GRAD"][0]
    mask = ins["Mask"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "upscale_in_train")
    if impl == "upscale_in_train":
        gx = jnp.where(mask > 0, g / (1.0 - p), 0.0).astype(g.dtype)
    else:
        gx = jnp.where(mask > 0, g, 0.0).astype(g.dtype)
    return {"X@GRAD": [gx]}


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

@register("lookup_table_v2", no_grad_slots=("Ids",))
def _lookup_table_v2(ctx, ins, attrs):
    """reference operators/lookup_table_v2_op.cu. Grad is vjp of take =
    scatter-add (XLA lowers to efficient TPU scatter); padding_idx rows
    receive no update by masking in the custom grad below."""
    w, ids = ins["W"][0], ins["Ids"][0]
    return {"Out": [jnp.take(w, ids, axis=0)]}


@register("lookup_table_v2_grad")
def _lookup_table_v2_grad(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    g = ins["Out@GRAD"][0]
    padding_idx = attrs.get("padding_idx", -1)
    gw = jnp.zeros_like(w)
    flat_ids = ids.reshape(-1)
    flat_g = g.reshape(-1, g.shape[-1]).astype(w.dtype)
    if padding_idx is not None and padding_idx >= 0:
        flat_g = jnp.where((flat_ids == padding_idx)[:, None], 0.0, flat_g)
    gw = gw.at[flat_ids].add(flat_g)
    return {"W@GRAD": [gw]}


@register("lookup_table", no_grad_slots=("Ids",))
def _lookup_table(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    if ids.ndim > 1 and ids.shape[-1] == 1:
        ids = ids.squeeze(-1)
    return {"Out": [jnp.take(w, ids, axis=0)]}


@register("embedding_bag", no_grad_slots=("Ids",))
def _embedding_bag(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    mode = attrs.get("mode", "sum")
    emb = jnp.take(w, ids, axis=0)
    if mode == "sum":
        return {"Out": [jnp.sum(emb, axis=1)]}
    return {"Out": [jnp.mean(emb, axis=1)]}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

@register("accuracy", not_differentiable=True)
def _accuracy(ctx, ins, attrs):
    """reference operators/metrics/accuracy_op: inputs Out(topk vals),
    Indices, Label."""
    indices, label = ins["Indices"][0], ins["Label"][0]
    if label.ndim == 2 and label.shape[1] == 1:
        label_c = label
    else:
        label_c = label.reshape(-1, 1)
    correct = jnp.any(indices == label_c, axis=1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = jnp.asarray(indices.shape[0], jnp.int32)
    acc = num_correct / indices.shape[0]
    return {"Accuracy": [acc.reshape(())],
            "Correct": [num_correct.astype(jnp.int32)],
            "Total": [total]}


@register("auc", not_differentiable=True)
def _auc(ctx, ins, attrs):
    """Streaming AUC (reference operators/metrics/auc_op): updates
    stat buckets functionally."""
    preds = ins["Predict"][0]
    label = ins["Label"][0].reshape(-1)
    stat_pos = ins["StatPos"][0]
    stat_neg = ins["StatNeg"][0]
    num_thresholds = attrs.get("num_thresholds", 4095)
    pos_prob = preds[:, 1] if preds.ndim == 2 and preds.shape[1] == 2 \
        else preds.reshape(-1)
    bucket = jnp.clip((pos_prob * num_thresholds).astype(jnp.int32),
                      0, num_thresholds)
    is_pos = (label > 0).astype(stat_pos.dtype)
    stat_pos = stat_pos.at[bucket].add(is_pos)
    stat_neg = stat_neg.at[bucket].add(1 - is_pos)
    # AUC from buckets (trapezoid over cumulative TP/FP, high→low threshold)
    tp = jnp.cumsum(stat_pos[::-1])
    fp = jnp.cumsum(stat_neg[::-1])
    tot_pos = tp[-1]
    tot_neg = fp[-1]
    tp_prev = jnp.concatenate([jnp.zeros(1, tp.dtype), tp[:-1]])
    fp_prev = jnp.concatenate([jnp.zeros(1, fp.dtype), fp[:-1]])
    area = jnp.sum((fp - fp_prev) * (tp + tp_prev) / 2.0)
    auc = jnp.where(tot_pos * tot_neg > 0, area / (tot_pos * tot_neg), 0.0)
    return {"AUC": [auc.astype(jnp.float64) if auc.dtype == jnp.float64 else auc.astype(jnp.float32)],
            "StatPosOut": [stat_pos], "StatNegOut": [stat_neg]}


def _interp(name, method):
    @register(name)
    def _lower(ctx, ins, attrs, _method=method):
        """reference operators/interpolate_op.cc — resize via jax.image
        (differentiable; vjp gives the adjoint resize)."""
        x = ins["X"][0]  # NCHW
        out_h = attrs.get("out_h", -1)
        out_w = attrs.get("out_w", -1)
        scale = attrs.get("scale", 0.0)
        if (out_h is None or out_h <= 0) and scale:
            out_h = int(x.shape[2] * scale)
            out_w = int(x.shape[3] * scale)
        shape = (x.shape[0], x.shape[1], int(out_h), int(out_w))
        return {"Out": [jax.image.resize(x, shape, method=_method)]}
    return _lower


_interp("nearest_interp_v2", "nearest")
_interp("bilinear_interp_v2", "linear")
_interp("bicubic_interp_v2", "cubic")
_interp("nearest_interp", "nearest")
_interp("bilinear_interp", "linear")
