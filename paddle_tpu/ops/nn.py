"""Neural-net ops: conv/pool/norm/dropout/embedding/softmax/losses/attention.

Parity surface: /root/reference/paddle/fluid/operators/ conv2d (conv_op.cc,
conv_cudnn_op.cu), pool2d, softmax, layer_norm_op.cu, batch_norm_op.cc,
dropout_op.cc, lookup_table_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, and the fused attention
(operators/fused/multihead_matmul_op.cu). Convs and matmuls lower to
lax.conv_general_dilated / dot_general for the MXU; batch_norm keeps
running-stat state functionally (MeanOut/VarianceOut) matching the reference
kernel contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .common import one


# ---------------------------------------------------------------------------
# convolution family
# ---------------------------------------------------------------------------
def _conv_nd(x, w, strides, paddings, dilations, groups, data_format="NCHW"):
    nd = x.ndim - 2
    if isinstance(paddings, int):
        paddings = [paddings] * nd
    if len(paddings) == nd:
        pads = [(p, p) for p in paddings]
    else:  # [before0, after0, before1, after1 ...]
        pads = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(nd)]
    if data_format in ("NCHW", "NCDHW"):
        dn_in = "NC" + "DHW"[-nd:]
        dn_out = dn_in
    else:
        dn_in = "N" + "DHW"[-nd:] + "C"
        dn_out = dn_in
    dn_kernel = "OI" + "DHW"[-nd:]
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        (dn_in, dn_kernel, dn_out))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pads,
        rhs_dilation=dilations, dimension_numbers=dn,
        feature_group_count=groups)


@register_op("conv2d", inputs=("Input", "Filter"), outputs=("Output",))
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    out = _conv_nd(x, w,
                   tuple(attrs.get("strides", [1, 1])),
                   attrs.get("paddings", [0, 0]),
                   tuple(attrs.get("dilations", [1, 1])),
                   attrs.get("groups", 1),
                   attrs.get("data_format", "NCHW"))
    return {"Output": [out]}


@register_op("depthwise_conv2d", inputs=("Input", "Filter"),
             outputs=("Output",))
def _depthwise_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    groups = attrs.get("groups", x.shape[1])
    out = _conv_nd(x, w, tuple(attrs.get("strides", [1, 1])),
                   attrs.get("paddings", [0, 0]),
                   tuple(attrs.get("dilations", [1, 1])), groups,
                   attrs.get("data_format", "NCHW"))
    return {"Output": [out]}


@register_op("conv3d", inputs=("Input", "Filter"), outputs=("Output",))
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    out = _conv_nd(x, w, tuple(attrs.get("strides", [1, 1, 1])),
                   attrs.get("paddings", [0, 0, 0]),
                   tuple(attrs.get("dilations", [1, 1, 1])),
                   attrs.get("groups", 1),
                   attrs.get("data_format", "NCDHW"))
    return {"Output": [out]}


@register_op("conv2d_transpose", inputs=("Input", "Filter"),
             outputs=("Output",))
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1]))
    paddings = attrs.get("paddings", [0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1]))
    # conv2d_transpose == conv backward-data (reference conv_transpose_op.h):
    # weight layout is [in_c, out_c, kh, kw]; lower via input dilation.
    if isinstance(paddings, int):
        paddings = [paddings] * 2
    pads = [(p, p) for p in paddings] if len(paddings) == 2 else \
        [(paddings[0], paddings[1]), (paddings[2], paddings[3])]
    wt = jnp.flip(jnp.swapaxes(w, 0, 1), axis=(2, 3))  # [out_c, in_c, kh, kw]
    dn = jax.lax.conv_dimension_numbers(x.shape, wt.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    out = jax.lax.conv_general_dilated(
        x, wt, window_strides=(1, 1),
        padding=[(d * (k - 1) - p0, d * (k - 1) - p1)
                 for (p0, p1), k, d in zip(pads, w.shape[2:], dilations)],
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=dn)
    return {"Output": [out]}


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------
def _pool(x, ksize, strides, paddings, pooling_type, ceil_mode, exclusive,
          adaptive, global_pooling, nd):
    if global_pooling or (adaptive and all(k == 1 for k in ksize)):
        axes = tuple(range(2, 2 + nd))
        if pooling_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        return jnp.mean(x, axis=axes, keepdims=True)
    if adaptive:
        # adaptive pooling to output size ksize
        out = x
        for i, osize in enumerate(ksize):
            axis = 2 + i
            insize = x.shape[axis]
            if insize % osize == 0:
                # divisible: reshape + reduce (cheapest)
                k = insize // osize
                shape = list(out.shape)
                shape[axis:axis + 1] = [osize, k]
                r = out.reshape(shape)
                out = (jnp.max(r, axis=axis + 1) if pooling_type == "max"
                       else jnp.mean(r, axis=axis + 1))
            elif pooling_type != "max":
                # non-divisible average: static bin-membership matrix
                # (adaptive_pool bins are [floor(j*I/O), ceil((j+1)*I/O))
                # like pool_op.h AdaptivePool) contracted on the MXU —
                # shapes stay static, no dynamic slicing
                w = np.zeros((osize, insize), np.float32)
                for j in range(osize):
                    lo = (j * insize) // osize
                    hi = -(-((j + 1) * insize) // osize)
                    w[j, lo:hi] = 1.0 / (hi - lo)
                out = jnp.moveaxis(
                    jnp.tensordot(out, jnp.asarray(w, out.dtype),
                                  axes=[[axis], [1]]), -1, axis)
            else:
                raise NotImplementedError(
                    "adaptive MAX pool needs divisible sizes on TPU "
                    "(static shapes; average pooling handles any size)")
        return out
    window = (1, 1) + tuple(ksize)
    strides_full = (1, 1) + tuple(strides)
    if isinstance(paddings, int):
        paddings = [paddings] * nd
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in paddings)
    if ceil_mode:
        new_pads = []
        for i, (lo, hi) in enumerate(pads):
            if i >= 2:
                dim = x.shape[i]
                k, s = window[i], strides_full[i]
                out_sz = -(-(dim + lo + hi - k) // s) + 1
                needed = (out_sz - 1) * s + k - dim - lo
                hi = max(hi, needed)
            new_pads.append((lo, hi))
        pads = tuple(new_pads)
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window,
                                     strides_full, pads)
    # avg pool
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full,
                                   pads)
    if exclusive:
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                       strides_full, pads)
        return summed / counts
    return summed / np.prod(ksize)


@register_op("pool2d", inputs=("X",))
def _pool2d(ctx, ins, attrs):
    return one(_pool(ins["X"][0], attrs.get("ksize", [2, 2]),
                     attrs.get("strides", [1, 1]), attrs.get("paddings", [0, 0]),
                     attrs.get("pooling_type", "max"),
                     attrs.get("ceil_mode", False),
                     attrs.get("exclusive", True),
                     attrs.get("adaptive", False),
                     attrs.get("global_pooling", False), 2))


@register_op("pool3d", inputs=("X",))
def _pool3d(ctx, ins, attrs):
    return one(_pool(ins["X"][0], attrs.get("ksize", [2, 2, 2]),
                     attrs.get("strides", [1, 1, 1]),
                     attrs.get("paddings", [0, 0, 0]),
                     attrs.get("pooling_type", "max"),
                     attrs.get("ceil_mode", False),
                     attrs.get("exclusive", True),
                     attrs.get("adaptive", False),
                     attrs.get("global_pooling", False), 3))


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------
@register_op("softmax", inputs=("X",))
def _softmax(ctx, ins, attrs):
    return one(jax.nn.softmax(ins["X"][0], axis=attrs.get("axis", -1)))


@register_op("log_softmax", inputs=("X",))
def _log_softmax(ctx, ins, attrs):
    return one(jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1)))


@register_op("cross_entropy", inputs=("X", "Label"),
             outputs=("Y",), non_diff_inputs=("Label",))
def _cross_entropy(ctx, ins, attrs):
    # operators/cross_entropy_op.cc: X is probabilities (post-softmax)
    x, label = ins["X"][0], ins["Label"][0]
    soft = attrs.get("soft_label", False)
    ignore = attrs.get("ignore_index", -100)
    eps = 1e-12
    if soft:
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim:
            lbl = jnp.squeeze(lbl, -1)
        picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32),
                                     axis=-1)
        loss = -jnp.log(picked + eps)
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    return {"Y": [loss]}


@register_op("cross_entropy2", inputs=("X", "Label"),
             outputs=("Y", "XShape", "MatchX"), non_diff_inputs=("Label",))
def _cross_entropy2(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    lbl = jnp.squeeze(label, -1) if label.ndim == x.ndim else label
    picked = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
    return {"Y": [-jnp.log(picked + 1e-12)],
            "XShape": [jnp.zeros((0,) + x.shape, x.dtype)],
            "MatchX": [picked]}


@register_op("softmax_with_cross_entropy", inputs=("Logits", "Label"),
             outputs=("Softmax", "Loss"), non_diff_inputs=("Label",))
def _softmax_with_ce(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1)
    soft_label = attrs.get("soft_label", False)
    ignore = attrs.get("ignore_index", -100)
    # logsumexp in fp32 even when AMP feeds bf16 logits (the reference
    # lists softmax_with_cross_entropy in the AMP black list for the
    # same reason)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    softmax = jnp.exp(logp)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logits.ndim:
            lbl = jnp.squeeze(lbl, axis)
        picked = jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32),
                                     axis=axis)
        loss = -picked
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    return {"Softmax": [softmax], "Loss": [loss]}


@register_op("sigmoid_cross_entropy_with_logits", inputs=("X", "Label"),
             non_diff_inputs=("Label",))
def _sigmoid_ce(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    mask = label != ignore
    loss = jnp.where(mask, loss, 0.0)
    if attrs.get("normalize", False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(x.dtype)), 1.0)
    return one(loss)


@register_op("bce_loss", inputs=("X", "Label"), non_diff_inputs=("Label",))
def _bce_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-12
    return one(-(label * jnp.log(x + eps) +
                 (1 - label) * jnp.log(1 - x + eps)))


@register_op("square_error_cost", inputs=("X", "Y"))
def _square_error_cost(ctx, ins, attrs):
    d = ins["X"][0] - ins["Y"][0]
    return one(d * d)


@register_op("smooth_l1_loss", inputs=("X", "Y", "InsideWeight",
                                       "OutsideWeight"),
             outputs=("Out", "Diff"))
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    sigma2 = sigma * sigma
    diff = x - y
    if "InsideWeight" in ins and ins["InsideWeight"]:
        diff = diff * ins["InsideWeight"][0]
    abs_diff = jnp.abs(diff)
    loss = jnp.where(abs_diff < 1.0 / sigma2,
                     0.5 * diff * diff * sigma2,
                     abs_diff - 0.5 / sigma2)
    if "OutsideWeight" in ins and ins["OutsideWeight"]:
        loss = loss * ins["OutsideWeight"][0]
    return {"Out": [jnp.sum(loss, axis=tuple(range(1, loss.ndim)),
                            keepdims=True).reshape(x.shape[0], 1)],
            "Diff": [diff]}


@register_op("huber_loss", inputs=("X", "Y"), outputs=("Out", "Residual"))
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r,
                     delta * (ar - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register_op("log_loss", inputs=("Predicted", "Labels"),
             outputs=("Loss",), non_diff_inputs=("Labels",))
def _log_loss(ctx, ins, attrs):
    p, l = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    return {"Loss": [-l * jnp.log(p + eps) -
                     (1 - l) * jnp.log(1 - p + eps)]}


@register_op("hinge_loss", inputs=("Logits", "Labels"),
             outputs=("Loss",), non_diff_inputs=("Labels",))
def _hinge_loss(ctx, ins, attrs):
    logits, labels = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": [jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0)]}


@register_op("rank_loss", inputs=("Left", "Right", "Label"),
             non_diff_inputs=("Label",))
def _rank_loss(ctx, ins, attrs):
    left, right, label = ins["Left"][0], ins["Right"][0], ins["Label"][0]
    d = left - right
    return one(jnp.log1p(jnp.exp(d)) - label * d)


@register_op("margin_rank_loss", inputs=("X1", "X2", "Label"),
             outputs=("Out", "Activated"), non_diff_inputs=("Label",))
def _margin_rank_loss(ctx, ins, attrs):
    x1, x2, label = ins["X1"][0], ins["X2"][0], ins["Label"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register_op("kldiv_loss", inputs=("X", "Target"),
             outputs=("Loss",), non_diff_inputs=("Target",))
def _kldiv_loss(ctx, ins, attrs):
    x, target = ins["X"][0], ins["Target"][0]
    reduction = attrs.get("reduction", "mean")
    loss = target * (jnp.where(target > 0, jnp.log(target), 0.0) - x)
    loss = jnp.where(target > 0, loss, 0.0)
    if reduction == "mean":
        return {"Loss": [jnp.mean(loss)]}
    if reduction == "sum":
        return {"Loss": [jnp.sum(loss)]}
    if reduction == "batchmean":
        return {"Loss": [jnp.sum(loss) / x.shape[0]]}
    return {"Loss": [loss]}


@register_op("nll_loss", inputs=("X", "Label", "Weight"),
             outputs=("Out", "Total_weight"), non_diff_inputs=("Label",))
def _nll_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    weight = ins.get("Weight", [None])[0] if ins.get("Weight") else None
    ignore = attrs.get("ignore_index", -100)
    reduction = attrs.get("reduction", "mean")
    picked = -jnp.take_along_axis(x, label[..., None].astype(jnp.int32),
                                  axis=1).squeeze(1)
    w = jnp.ones_like(picked) if weight is None else weight[label]
    w = jnp.where(label == ignore, 0.0, w)
    picked = picked * w
    total = jnp.sum(w)
    if reduction == "mean":
        return {"Out": [jnp.sum(picked) / jnp.maximum(total, 1e-12)],
                "Total_weight": [total]}
    if reduction == "sum":
        return {"Out": [jnp.sum(picked)], "Total_weight": [total]}
    return {"Out": [picked], "Total_weight": [total]}


@register_op("mse_loss", inputs=("X", "Y"))
def _mse_loss(ctx, ins, attrs):
    d = ins["X"][0] - ins["Y"][0]
    return one(jnp.mean(d * d))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
@register_op("layer_norm", inputs=("X", "Scale", "Bias"),
             outputs=("Y", "Mean", "Variance"))
def _layer_norm(ctx, ins, attrs):
    # operators/layer_norm_op.cu: normalize over trailing dims from
    # begin_norm_axis; outputs saved mean/var over the leading dims.
    # `layer_norm` on the device trace, as the Pallas kernel names its
    # own calls (telemetry.py's convention): one name for both forms
    with jax.named_scope("layer_norm"):
        x = ins["X"][0]
        eps = attrs.get("epsilon", 1e-5)
        axis = attrs.get("begin_norm_axis", 1)
        if (axis == x.ndim - 1 and ins.get("Scale") and ins.get("Bias")
                and jax.default_backend() == "tpu"):
            from ..kernels.layer_norm import layer_norm_with_stats
            y, mean, var = layer_norm_with_stats(
                x, ins["Scale"][0], ins["Bias"][0], eps)
            return {"Y": [y], "Mean": [mean], "Variance": [var]}
        red = tuple(range(axis, x.ndim))
        mean = jnp.mean(x, axis=red, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=red, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        # Scale/Bias are stored flat [prod(norm_dims)] (layer_norm_op.cc
        # contract); fold them back over the normalized region so a
        # begin_norm_axis < ndim-1 (multi-dim region) broadcasts correctly
        if ins.get("Scale"):
            y = y * ins["Scale"][0].reshape(x.shape[axis:])
        if ins.get("Bias"):
            y = y + ins["Bias"][0].reshape(x.shape[axis:])
        lead = int(np.prod(x.shape[:axis]))
        return {"Y": [y], "Mean": [mean.reshape(lead)],
                "Variance": [var.reshape(lead)]}


@register_op("batch_norm",
             inputs=("X", "Scale", "Bias", "Mean", "Variance"),
             outputs=("Y", "MeanOut", "VarianceOut", "SavedMean",
                      "SavedVariance"))
def _batch_norm(ctx, ins, attrs):
    # operators/batch_norm_op.cc contract: training mode computes batch
    # stats and updates running Mean/Variance with momentum; test mode uses
    # running stats. MeanOut/VarianceOut share buffers with Mean/Variance in
    # the reference — here they are functional state outputs the executor
    # writes back.
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean_in, var_in = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    use_global = attrs.get("use_global_stats", False) or is_test
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    red = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    # statistics always accumulate in fp32 (the reference kernel's
    # BatchNormParamType promotes fp16/bf16 stats the same way). The
    # normalize is FOLDED into a per-channel affine y = x*a + b with
    # a = scale*rsqrt(var+eps), b = bias - mean*a computed in fp32 on
    # [C]-sized vectors only — the full [N,C,H,W] activation is never
    # round-tripped through fp32, so under AMP the BN/relu/add chain
    # stays bf16-wide in HBM.
    if use_global:
        mean, var = mean_in, var_in
        a = scale.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
        b = bias.astype(jnp.float32) - mean * a
        y = (x * a.reshape(bshape).astype(x.dtype)
             + b.reshape(bshape).astype(x.dtype))
        return {"Y": [y], "MeanOut": [mean_in], "VarianceOut": [var_in],
                "SavedMean": [mean_in], "SavedVariance": [var_in]}
    # training mode: custom-vjp BN — the round-5 TPU trace showed 33%
    # of the ResNet-50 step inside reduce fusions, most of them the
    # autodiff backward of the stats composition; the canonical BN
    # backward needs exactly TWO reductions (sum dy, sum dy*xhat)
    y, mean, var = _bn_train(red, float(eps), x, scale, bias)
    mean_out = momentum * mean_in + (1 - momentum) * mean
    var_out = momentum * var_in + (1 - momentum) * var
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [mean], "SavedVariance": [var]}


def _bn_bshape(x, red):
    return [1 if i in red else x.shape[i] for i in range(x.ndim)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bn_train(red, eps, x, scale, bias):
    y, mean, var, _ = _bn_train_fwd_impl(red, eps, x, scale, bias)
    return y, mean, var


def _bn_train_fwd_impl(red, eps, x, scale, bias):
    xs = x.astype(jnp.float32)
    mean = jnp.mean(xs, axis=red)
    var = jnp.mean(jnp.square(xs), axis=red) - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    a = scale.astype(jnp.float32) * inv
    b = bias.astype(jnp.float32) - mean * a
    bshape = _bn_bshape(x, red)
    y = (x * a.reshape(bshape).astype(x.dtype)
         + b.reshape(bshape).astype(x.dtype))
    return y, mean, var, inv


def _bn_train_fwd(red, eps, x, scale, bias):
    # symbolic_zeros=True wraps each primal in a CustomVJPPrimal
    x, scale, bias = x.value, scale.value, bias.value
    y, mean, var, inv = _bn_train_fwd_impl(red, eps, x, scale, bias)
    return (y, mean, var), (x, scale, mean, inv)


def _bn_train_bwd(red, eps, residuals, cts):
    """Canonical two-reduction batch-norm backward (the closed form the
    reference's batch_norm_grad kernel implements,
    batch_norm_op.cc KernelBackward):
        dbias  = sum(dy);  dscale = sum(dy * xhat)
        dx     = (scale*inv/N) * (N*dy - dbias - xhat*dscale)
    plus the mean/var output paths — SymbolicZero on the training hot
    path (they only feed the non-differentiated running-stat update),
    so their full-shape terms are genuinely skipped, not left for XLA
    zero-folding. A consumer of SavedMean/SavedVariance still
    differentiates exactly."""
    from jax.custom_derivatives import SymbolicZero
    dy, dmean_ct, dvar_ct = cts
    x, scale, mean, inv = residuals
    bshape = _bn_bshape(x, red)
    n = 1
    for i in red:
        n *= x.shape[i]
    xs = x.astype(jnp.float32)
    xhat = (xs - mean.reshape(bshape)) * inv.reshape(bshape)
    if isinstance(dy, SymbolicZero):
        dx = jnp.zeros(x.shape, jnp.float32)
        dscale = jnp.zeros(scale.shape, jnp.float32)
        dbias = jnp.zeros(scale.shape, jnp.float32)
    else:
        g = dy.astype(jnp.float32)
        dbias = jnp.sum(g, axis=red)
        dscale = jnp.sum(g * xhat, axis=red)
        a = scale.astype(jnp.float32) * inv
        dx = (a / n).reshape(bshape) * (
            n * g - dbias.reshape(bshape)
            - xhat * dscale.reshape(bshape))
    # d mean/dx = 1/N; d var/dx = 2*(x-mean)/N
    if not isinstance(dmean_ct, SymbolicZero):
        dx = dx + (dmean_ct / n).reshape(bshape)
    if not isinstance(dvar_ct, SymbolicZero):
        dx = dx + dvar_ct.reshape(bshape) * (2.0 / n) * (
            xs - mean.reshape(bshape))
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            dbias.astype(scale.dtype))


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd, symbolic_zeros=True)


@register_op("instance_norm", inputs=("X", "Scale", "Bias"),
             outputs=("Y", "SavedMean", "SavedVariance"))
def _instance_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(shape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(shape)
    return {"Y": [y], "SavedMean": [mean.reshape(x.shape[0], x.shape[1])],
            "SavedVariance": [var.reshape(x.shape[0], x.shape[1])]}


@register_op("group_norm", inputs=("X", "Scale", "Bias"),
             outputs=("Y", "Mean", "Variance"))
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    xg = x.reshape((n, g, c // g) + spatial)
    red = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.var(xg, axis=red, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    shape = [1, c] + [1] * len(spatial)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(shape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(shape)
    return {"Y": [y], "Mean": [mean.reshape(n, g)],
            "Variance": [var.reshape(n, g)]}


@register_op("data_norm", inputs=("X", "BatchSize", "BatchSum",
                                  "BatchSquareSum"),
             outputs=("Y", "Means", "Scales"))
def _data_norm(ctx, ins, attrs):
    x = ins["X"][0]
    bsize, bsum, bsq = ins["BatchSize"][0], ins["BatchSum"][0], \
        ins["BatchSquareSum"][0]
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsq)
    return {"Y": [(x - means) * scales], "Means": [means],
            "Scales": [scales]}


@register_op("l2_normalize", inputs=("X",))
def _l2_normalize(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    return one(x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=axis, keepdims=True) + eps))


# ---------------------------------------------------------------------------
# dropout & embedding
# ---------------------------------------------------------------------------
def _keep_mask(key, keep_prob, shape):
    """Bernoulli keep-mask tuned for TPU: the hardware RNG emits 32
    random bits per word, but dropout only needs 8 bits of resolution —
    generating a quarter of the words and byte-splitting halves the
    measured mask cost vs threefry (2.5ms -> 1.25ms per [32,512,3072]
    bf16 on v5e). Threshold uses the byte grid, so keep_prob resolves to
    1/256 steps (the reference's fp32 uniform-compare has the same class
    of quantization at fp granularity)."""
    n = int(np.prod(shape)) if shape else 1
    if jax.default_backend() == "cpu" or n < 4096 or n % 4:
        return jax.random.bernoulli(key, keep_prob, shape)
    k4 = jnp.concatenate([key, key]).astype(jnp.uint32)
    _, bits = jax.lax.rng_bit_generator(
        k4, (n // 4,), dtype=jnp.uint32,
        algorithm=jax.lax.RandomAlgorithm.RNG_DEFAULT)
    u8 = jax.lax.bitcast_convert_type(bits, jnp.uint8).reshape(shape)
    # P(u8 < t) = t/256; t = round(keep_prob*256) is within 1/512 of the
    # requested rate
    return u8 < np.uint8(min(int(round(keep_prob * 256)), 255))


@register_op("dropout", inputs=("X",), outputs=("Out", "Mask"),
             is_random=True)
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            return {"Out": [x], "Mask": [jnp.ones_like(x)]}
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    if p <= 0.0:
        # p=0 must not burn RNG throughput (a dropout_prob=0 layer is a
        # common "disabled" config; generating a full mask of ones cost
        # more than the surrounding matmul)
        return {"Out": [x], "Mask": [jnp.ones_like(x)]}
    from ..flags import get_flag
    strategy = get_flag("FLAGS_dropout_storage", "xla")
    upscale = impl == "upscale_in_train"
    # NB: jnp.issubdtype, not dtype.kind == "f" — bfloat16's numpy kind
    # is 'V' (void), and AMP bf16 activations are the main beneficiary
    if strategy in ("u8", "seed") and jnp.issubdtype(x.dtype,
                                                     jnp.floating):
        key = ctx.rng()
        out, mask = _drop_custom(1.0 - p, upscale, strategy == "u8",
                                 x, key)
        return {"Out": [out], "Mask": [mask]}
    keep = _keep_mask(ctx.rng(), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if upscale:
        out = jnp.where(keep, x / max(1.0 - p, 1e-12), 0.0)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _drop_custom(keep_prob, upscale, store_u8, x, key):
    """Dropout whose backward residual is CHOSEN, not left to XLA's
    cost model: the round-5 B=64 OOM dump showed XLA materializing
    4 bytes/element (u32 full-shape buffers) for every keep decision —
    [B,512,3072] FFN masks alone were 4.6G. store_u8=True pins the
    residual to a uint8 mask (1 byte/elem); False stores only the PRNG
    KEY and regenerates the identical mask in the backward from the
    deterministic _keep_mask(key, ...) — zero mask bytes in HBM at the
    price of re-running the rbg in the bwd (the flash kernel's
    in-kernel dropout, kernels/flash_attention.py, is the same idea
    one level lower). Selected by FLAGS_dropout_storage."""
    out, mask, _ = _drop_fwd_impl(keep_prob, upscale, store_u8, x, key)
    return out, mask


def _drop_fwd_impl(keep_prob, upscale, store_u8, x, key):
    keep = _keep_mask(key, keep_prob, x.shape)
    if upscale:
        out = jnp.where(keep, x / max(keep_prob, 1e-12), 0.0)
    else:
        out = jnp.where(keep, x, 0.0)
    return out, keep.astype(x.dtype), keep


def _drop_custom_fwd(keep_prob, upscale, store_u8, x, key):
    out, mask, keep = _drop_fwd_impl(keep_prob, upscale, store_u8,
                                     x, key)
    res = keep.astype(jnp.uint8) if store_u8 else key
    return (out, mask), (res, x.shape)


def _drop_custom_bwd(keep_prob, upscale, store_u8, residuals, gs):
    g_out, _g_mask = gs  # the Mask output is fwd-only
    res, shape = residuals
    if store_u8:
        keep = res != 0
    else:
        keep = _keep_mask(res, keep_prob, shape)
    if upscale:
        dx = jnp.where(keep, g_out / max(keep_prob, 1e-12), 0.0)
    else:
        dx = jnp.where(keep, g_out, 0.0)
    import numpy as _np
    dkey = _np.zeros((2,), jax.dtypes.float0)  # uint32 key: zero-tangent
    return dx.astype(g_out.dtype), dkey


_drop_custom.defvjp(_drop_custom_fwd, _drop_custom_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _drop_custom_nomask(keep_prob, upscale, store_u8, x, key):
    """_drop_custom without the Mask output: for call sites that never
    consume it (attention probs dropout) — in EAGER execution the
    discarded full-size float Mask would otherwise materialize per
    layer (jit DCEs it, eager cannot)."""
    out, _, _ = _drop_fwd_impl(keep_prob, upscale, store_u8, x, key)
    return out


def _drop_nomask_fwd(keep_prob, upscale, store_u8, x, key):
    out, _, keep = _drop_fwd_impl(keep_prob, upscale, store_u8, x, key)
    res = keep.astype(jnp.uint8) if store_u8 else key
    return out, (res, x.shape)


def _drop_nomask_bwd(keep_prob, upscale, store_u8, residuals, g_out):
    dx, dkey = _drop_custom_bwd(keep_prob, upscale, store_u8,
                                residuals, (g_out, None))
    return dx, dkey


_drop_custom_nomask.defvjp(_drop_nomask_fwd, _drop_nomask_bwd)


def apply_probs_dropout(x, keep_prob, key):
    """Upscale-in-train dropout on a probability tensor, honoring
    FLAGS_dropout_storage — the ONE dispatch site shared by the dropout
    op and the composed-attention path (so strategy behavior cannot
    drift between them)."""
    from ..flags import get_flag
    strategy = get_flag("FLAGS_dropout_storage", "xla")
    if strategy in ("u8", "seed") and jnp.issubdtype(x.dtype,
                                                    jnp.floating):
        return _drop_custom_nomask(keep_prob, True, strategy == "u8",
                                   x, key)
    keep = _keep_mask(key, keep_prob, x.shape)
    return jnp.where(keep, x / max(keep_prob, 1e-12), 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_rows_onehot(vocab, w, ids):
    return jnp.take(w, ids, axis=0)


def _gather_rows_onehot_fwd(vocab, w, ids):
    # residuals must be jax types: a zero-size array carries w's dtype
    return jnp.take(w, ids, axis=0), (ids, jnp.zeros((0,), w.dtype))


def _gather_rows_onehot_bwd(vocab, res, g):
    """dW as chunked one-hot MATMULS instead of a scatter-add: the MXU
    eats [chunk, V] @ [chunk, H] contractions, while the TPU scatter
    path serializes through update cells (the round-3 open question on
    the BERT embedding backward; scripts/tpu_experiments.py measures
    both). Chunks of N keep the one-hot working set ~chunk*V*2B; the
    [V, H] fp32 accumulator rides the scan carry. Padding the tail
    chunk with id == V makes one_hot emit an all-zero row — no
    contribution, no masking.

    Contract note: ids must be in [0, V). The scatter path clips an
    out-of-range id to the edge row (XLA gather/scatter clip mode);
    here it contributes ZERO dW — both are garbage-in behaviors, but
    they differ, so invalid ids train differently per flag."""
    ids, w_proto = res
    V = vocab
    n = ids.shape[0]
    # size the one-hot block by its ACTUAL bytes (dtype-aware: fp32
    # grads double the block the old fixed 4096 budgeted) — ~256MB cap;
    # under AMP the one-hot rides bf16, the accumulator stays fp32
    itemsize = jnp.dtype(g.dtype).itemsize
    chunk = max(256, min(4096, (256 << 20) // max(V * itemsize, 1)))
    chunk = min(chunk, max(256, n))
    n_pad = (-n) % chunk
    ids_p = jnp.concatenate(
        [ids, jnp.full((n_pad,), V, ids.dtype)]) if n_pad else ids
    g_p = jnp.concatenate(
        [g, jnp.zeros((n_pad,) + g.shape[1:], g.dtype)]) if n_pad else g
    steps = ids_p.shape[0] // chunk

    def body(dw, i):
        sl_ids = jax.lax.dynamic_slice(ids_p, (i * chunk,), (chunk,))
        sl_g = jax.lax.dynamic_slice_in_dim(g_p, i * chunk, chunk, 0)
        oh = jax.nn.one_hot(sl_ids, V, dtype=sl_g.dtype)
        return dw + jax.lax.dot_general(
            oh, sl_g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), None

    dw, _ = jax.lax.scan(body, jnp.zeros((V,) + g.shape[1:], jnp.float32),
                         jnp.arange(steps))
    return (dw.astype(w_proto.dtype),
            jnp.zeros(ids.shape, jax.dtypes.float0))


_gather_rows_onehot.defvjp(_gather_rows_onehot_fwd, _gather_rows_onehot_bwd)


def _embedding_take(w, ids):
    """Row gather whose dW strategy is flag-selected at trace time:
    FLAGS_embedding_onehot_grad=True routes the backward through MXU
    one-hot matmuls; default is XLA's scatter-add."""
    from ..flags import get_flag
    if get_flag("FLAGS_embedding_onehot_grad", False):
        flat = ids.reshape(-1).astype(jnp.int32)
        out = _gather_rows_onehot(int(w.shape[0]), w, flat)
        return out.reshape(tuple(ids.shape) + (w.shape[-1],))
    return jnp.take(w, ids.astype(jnp.int32), axis=0)


@register_op("lookup_table", inputs=("W", "Ids"), non_diff_inputs=("Ids",))
def _lookup_table(ctx, ins, attrs):
    # operators/lookup_table_op.cc — Ids shaped [..., 1]; padding_idx rows
    # output zero. Sparse (SelectedRows) grads become XLA scatter-adds.
    w, ids = ins["W"][0], ins["Ids"][0]
    if ids.shape and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, -1)
    padding_idx = attrs.get("padding_idx", -1)
    out = _embedding_take(w, ids)
    if padding_idx != -1:
        pad = padding_idx if padding_idx >= 0 else w.shape[0] + padding_idx
        out = jnp.where((ids == pad)[..., None], 0.0, out)
    return one(out)


@register_op("lookup_table_v2", inputs=("W", "Ids"),
             non_diff_inputs=("Ids",))
def _lookup_table_v2(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    padding_idx = attrs.get("padding_idx", -1)
    out = _embedding_take(w, ids)
    if padding_idx != -1:
        pad = padding_idx if padding_idx >= 0 else w.shape[0] + padding_idx
        out = jnp.where((ids == pad)[..., None], 0.0, out)
    return one(out)


@register_op("embedding_bag_sum", inputs=("W", "Ids"),
             non_diff_inputs=("Ids",))
def _embedding_bag_sum(ctx, ins, attrs):
    # fused_embedding_seq_pool analog: lookup + sum-pool over a fixed axis
    w, ids = ins["W"][0], ins["Ids"][0]
    out = jnp.take(w, ids.astype(jnp.int32), axis=0)
    return one(jnp.sum(out, axis=1))


# ---------------------------------------------------------------------------
# attention (reference fused/multihead_matmul_op.cu) — composed form; the
# Pallas flash-attention kernel in paddle_tpu/kernels/flash_attention.py is
# substituted by layers.multihead_attention when enabled.
# ---------------------------------------------------------------------------
# multihead_matmul (packed-QKV signature of the reference's fused op)
# registers in ops/fused.py and routes to the Pallas flash-attention
# kernel.


@register_op("stack_lstm_unit", inputs=("X", "C"), outputs=("H", "COut"))
def _lstm_unit(ctx, ins, attrs):
    x, c_prev = ins["X"][0], ins["C"][0]
    i, f, o, j = jnp.split(x, 4, axis=-1)
    forget_bias = attrs.get("forget_bias", 0.0)
    c = c_prev * jax.nn.sigmoid(f + forget_bias) + \
        jax.nn.sigmoid(i) * jnp.tanh(j)
    h = jnp.tanh(c) * jax.nn.sigmoid(o)
    return {"H": [h], "COut": [c]}


# ---------------------------------------------------------------------------
# interpolation
# ---------------------------------------------------------------------------
def _interp(x, out_hw, method, align_corners):
    """NCHW resize with reference align_corners semantics
    (interpolate_op.h): align_corners maps output i -> i*(in-1)/(out-1);
    otherwise half-pixel centers (what jax.image.resize implements)."""
    n, c, h, w = x.shape
    oh, ow = out_hw
    if not align_corners:
        xt = jnp.transpose(x, (0, 2, 3, 1))
        out = jax.image.resize(xt, (n, oh, ow, c), method=method)
        return jnp.transpose(out, (0, 3, 1, 2))

    def src_coords(osize, isize):
        if osize == 1:
            return jnp.zeros((1,), jnp.float32)
        return jnp.arange(osize, dtype=jnp.float32) * (isize - 1) / (osize - 1)

    ys = src_coords(oh, h)
    xs = src_coords(ow, w)
    if method == "nearest":
        yi = jnp.round(ys).astype(jnp.int32)
        xi = jnp.round(xs).astype(jnp.int32)
        return x[:, :, yi][:, :, :, xi]
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    y1 = jnp.clip(y0 + 1, 0, h - 1)
    x1 = jnp.clip(x0 + 1, 0, w - 1)
    wy = (ys - y0)[None, None, :, None]
    wx = (xs - x0)[None, None, None, :]
    g = lambda yy, xx: x[:, :, yy][:, :, :, xx]
    top = g(y0, x0) * (1 - wx) + g(y0, x1) * wx
    bot = g(y1, x0) * (1 - wx) + g(y1, x1) * wx
    return top * (1 - wy) + bot * wy


@register_op("bilinear_interp", inputs=("X",))
def _bilinear_interp(ctx, ins, attrs):
    x = ins["X"][0]
    oh = attrs.get("out_h", -1)
    ow = attrs.get("out_w", -1)
    scale = attrs.get("scale", 0.0)
    if oh <= 0 and scale > 0:
        oh, ow = int(x.shape[2] * scale), int(x.shape[3] * scale)
    return one(_interp(x, (oh, ow), "bilinear",
                       attrs.get("align_corners", True)))


@register_op("nearest_interp", inputs=("X",))
def _nearest_interp(ctx, ins, attrs):
    x = ins["X"][0]
    oh = attrs.get("out_h", -1)
    ow = attrs.get("out_w", -1)
    scale = attrs.get("scale", 0.0)
    if oh <= 0 and scale > 0:
        oh, ow = int(x.shape[2] * scale), int(x.shape[3] * scale)
    return one(_interp(x, (oh, ow), "nearest",
                       attrs.get("align_corners", True)))


@register_op("grid_sampler", inputs=("X", "Grid"), outputs=("Output",))
def _grid_sampler(ctx, ins, attrs):
    x, grid = ins["X"][0], ins["Grid"][0]  # x: NCHW, grid: NHW2 in [-1,1]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1

    def pick(yy, xx):
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        batch = jnp.arange(n)[:, None, None]
        return x[batch, :, yy, xx]  # N,H,W,C

    wa = ((x1 - gx) * (y1 - gy))[..., None]
    wb = ((x1 - gx) * (gy - y0))[..., None]
    wc = ((gx - x0) * (y1 - gy))[..., None]
    wd = ((gx - x0) * (gy - y0))[..., None]
    out = wa * pick(y0, x0) + wb * pick(y1, x0) + \
        wc * pick(y0, x1) + wd * pick(y1, x1)
    return {"Output": [jnp.transpose(out, (0, 3, 1, 2))]}


@register_op("sync_batch_norm",
             inputs=("X", "Scale", "Bias", "Mean", "Variance"),
             outputs=("Y", "MeanOut", "VarianceOut", "SavedMean",
                      "SavedVariance"))
def _sync_batch_norm(ctx, ins, attrs):
    """Cross-replica batch norm (operators/sync_batch_norm_op.cu — the
    CUDA kernel ncclAllReduces sum(x) and sum(x^2) before normalizing).

    TPU-native design note: under GSPMD (CompiledProgram /
    with_data_parallel), the batch axis is sharded over the mesh and
    jnp.mean over it IS the global mean — XLA inserts the all-reduce,
    which is exactly the reference's NCCL collective. So the lowering is
    the batch_norm lowering; the semantic difference the reference needs
    a separate CUDA kernel for comes for free from the sharding
    propagation. (Inside shard_map, where means are shard-local, a
    lax.pmean wrapper would be needed — the framework's SPMD paths all
    go through GSPMD.)"""
    return _batch_norm(ctx, ins, attrs)


@register_op("conv3d_transpose", inputs=("Input", "Filter"),
             outputs=("Output",))
def _conv3d_transpose(ctx, ins, attrs):
    """conv3d backward-data (conv_transpose_op.cc, 3d path): weight
    [in_c, out_c, kd, kh, kw], lowered via lhs dilation like
    conv2d_transpose."""
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    paddings = attrs.get("paddings", [0, 0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1, 1]))
    if isinstance(paddings, int):
        paddings = [paddings] * 3
    pads = [(p, p) for p in paddings] if len(paddings) == 3 else \
        [(paddings[2 * i], paddings[2 * i + 1]) for i in range(3)]
    wt = jnp.flip(jnp.swapaxes(w, 0, 1), axis=(2, 3, 4))
    dn = jax.lax.conv_dimension_numbers(x.shape, wt.shape,
                                        ("NCDHW", "OIDHW", "NCDHW"))
    out = jax.lax.conv_general_dilated(
        x, wt, window_strides=(1, 1, 1),
        padding=[(d * (k - 1) - p0, d * (k - 1) - p1)
                 for (p0, p1), k, d in zip(pads, w.shape[2:], dilations)],
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=dn)
    return {"Output": [out]}


@register_op("sample_logits",
             inputs=("Logits", "Labels", "CustomizedSamples",
                     "CustomizedProbabilities"),
             outputs=("Samples", "Probabilities", "SampledLogits",
                      "SampledLabels"),
             is_random=True, non_diff_inputs=("Labels",
                                              "CustomizedSamples",
                                              "CustomizedProbabilities"))
def _sample_logits(ctx, ins, attrs):
    """Sampled-softmax helper (operators/sample_logits_op.cc): gather
    the NT true-label logits plus S sampled negatives per row, subtract
    log Q(y) (the log-uniform sampler's probability, math_function's
    LogUniformSampler), and mask accidental hits. SampledLabels are
    0..NT-1 (the true labels occupy the leading columns)."""
    logits = ins["Logits"][0]
    labels = ins["Labels"][0].astype(jnp.int64)
    n, k = logits.shape
    nt = labels.shape[1]
    s = int(attrs.get("num_samples", 5))
    if attrs.get("use_customized_samples", False):
        samples = ins["CustomizedSamples"][0].astype(jnp.int64)
        probs = ins["CustomizedProbabilities"][0]
    else:
        # log-uniform (Zipfian) sampling: P(c) = log(c+2)-log(c+1) /
        # log(K+1) — the reference's LogUniformSampler distribution
        u = jax.random.uniform(ctx.rng(), (n, s))
        neg = (jnp.exp(u * jnp.log(float(k + 1))) - 1.0) \
            .astype(jnp.int64).clip(0, k - 1)
        samples = jnp.concatenate([labels, neg], axis=1)
        probs = (jnp.log(samples.astype(jnp.float32) + 2.0)
                 - jnp.log(samples.astype(jnp.float32) + 1.0)) \
            / jnp.log(float(k + 1))
    sampled = jnp.take_along_axis(logits, samples.astype(jnp.int32),
                                  axis=1)
    sampled = sampled - jnp.log(probs + 1e-20)
    if attrs.get("remove_accidental_hits", True):
        # a negative column equal to any true label of its row is an
        # accidental hit: suppress it so softmax ignores the duplicate
        hit = (samples[:, None, :] == labels[:, :, None]).any(axis=1)
        col_is_neg = jnp.arange(samples.shape[1]) >= nt
        sampled = jnp.where(hit & col_is_neg[None, :],
                            sampled - 1e20, sampled)
    sampled_labels = jnp.tile(jnp.arange(nt, dtype=jnp.int64), (n, 1))
    return {"Samples": [samples], "Probabilities": [probs],
            "SampledLogits": [sampled], "SampledLabels": [sampled_labels]}


@register_op("hsigmoid", inputs=("X", "W", "Label", "Bias", "PathTable",
                                 "PathCode"),
             outputs=("Out", "PreOut"),
             non_diff_inputs=("Label", "PathTable", "PathCode"))
def _hsigmoid(ctx, ins, attrs):
    """Hierarchical sigmoid loss (operators/hierarchical_sigmoid_op.cc,
    math/matrix_bit_code.h SimpleCode): with the default complete
    binary tree over num_classes, label l's path node at depth d is
    ((l + C) >> (d+1)) - 1 and its code bit ((l + C) >> d) & 1; the
    loss sums softplus(preout) - code*preout over valid depths.
    Custom trees pass PathTable/PathCode (id -1 = stop)."""
    x = ins["X"][0]                       # [N, D]
    w = ins["W"][0]                       # [C-1, D]
    label = ins["Label"][0].reshape(-1).astype(jnp.int32)
    bias = ins["Bias"][0].reshape(-1) if ins.get("Bias") else None
    c = int(attrs.get("num_classes", w.shape[0] + 1))
    if ins.get("PathTable"):
        nodes = ins["PathTable"][0].astype(jnp.int32)   # [N, L]
        codes = ins["PathCode"][0].astype(jnp.int32)
        valid = nodes >= 0
        nodes = jnp.maximum(nodes, 0)
    else:
        depth = max(1, int(np.ceil(np.log2(max(c, 2)))))
        full = label + c                                 # [N]
        ds = jnp.arange(depth, dtype=jnp.int32)
        nodes = (full[:, None] >> (ds + 1)[None, :]) - 1  # [N, L]
        codes = (full[:, None] >> ds[None, :]) & 1
        valid = nodes >= 0
        # visit path root-to-leaf order irrelevant for the sum
        nodes = jnp.maximum(nodes, 0)
    pre = jnp.einsum("nd,nld->nl", x, w[nodes])          # [N, L]
    if bias is not None:
        pre = pre + bias[nodes]
    # softplus(pre) - code*pre, masked to the real path
    loss = jnp.where(valid,
                     jnp.logaddexp(0.0, pre) - codes * pre, 0.0)
    return {"Out": [loss.sum(axis=1, keepdims=True)],
            "PreOut": [pre]}


@register_op("inplace_abn",
             inputs=("X", "Scale", "Bias", "Mean", "Variance"),
             outputs=("Y", "MeanOut", "VarianceOut", "SavedMean",
                      "SavedVariance"))
def _inplace_abn(ctx, ins, attrs):
    """In-place activated batch norm (operators/inplace_abn_op.cc):
    batch_norm followed by the fused activation — in-placeness is an
    HBM trick XLA owns; semantics are bn+act."""
    outs = _batch_norm(ctx, ins, attrs)
    act = attrs.get("activation", "identity")
    y = outs["Y"][0]
    if act in ("leaky_relu", "leakyrelu"):
        alpha = attrs.get("alpha", 0.01)
        y = jnp.where(y >= 0, y, alpha * y)
    elif act == "elu":
        alpha = attrs.get("alpha", 1.0)
        y = jnp.where(y >= 0, y, alpha * (jnp.exp(y) - 1.0))
    elif act != "identity":
        y = getattr(jax.nn, act)(y)
    outs["Y"] = [y]
    return outs


@register_op("maxout", inputs=("X",))
def _maxout(ctx, ins, attrs):
    """maxout_op.cc: channel groups of `groups` reduced by max
    (NCHW: C -> C/groups)."""
    x = ins["X"][0]
    g = int(attrs["groups"])
    axis = int(attrs.get("axis", 1))
    if axis < 0:
        axis += x.ndim
    c = x.shape[axis]
    shape = list(x.shape)
    shape[axis:axis + 1] = [c // g, g]
    return one(jnp.max(x.reshape(shape), axis=axis + 1))


@register_op("add_position_encoding", inputs=("X",))
def _add_position_encoding(ctx, ins, attrs):
    """add_position_encoding_op.cc: x*alpha + sinusoid(pos)*beta,
    the transformer position table computed in-graph."""
    x = ins["X"][0]
    alpha = float(attrs.get("alpha", 1.0))
    beta = float(attrs.get("beta", 1.0))
    rank2 = x.ndim == 2  # LoD form [N, D]: one running sequence
    if rank2:
        x = x[None]
    B, T, D = x.shape
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    half = (D + 1) // 2
    div = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * -(np.log(10000.0) / max(half - 1, 1)))
    enc = jnp.concatenate([jnp.sin(pos * div), jnp.cos(pos * div)],
                          axis=1)[:, :D]  # odd D: trim the cos tail
    out = x * alpha + enc[None].astype(x.dtype) * beta
    return one(out[0] if rank2 else out)


@register_op("bilinear_tensor_product",
             inputs=("X", "Y", "Weight", "Bias"))
def _bilinear_tensor_product(ctx, ins, attrs):
    """bilinear_tensor_product_op.cc: out[:, k] = x @ W[k] @ y^T diag."""
    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]  # w [K, M, N]
    out = jnp.einsum("bm,kmn,bn->bk", x, w, y)
    if ins.get("Bias"):
        out = out + ins["Bias"][0]
    return one(out)


@register_op("similarity_focus", inputs=("X",), no_grad=True)
def _similarity_focus(ctx, ins, attrs):
    """similarity_focus_op.h: for each indexed channel slice, greedily
    select min(H, W) maxima with pairwise-distinct rows AND columns
    (the reference walks positions in descending order skipping used
    rows/cols); the union over indexes lights the mask across all
    channels. Static unrolled greedy — min(H, W) steps."""
    x = ins["X"][0]  # [B, C, H, W]
    axis = int(attrs.get("axis", 1))
    indexes = list(attrs.get("indexes", [0]))
    if axis != 1:
        raise NotImplementedError("similarity_focus: axis=1 (channel) "
                                  "only on TPU")
    sel = x[:, jnp.asarray(indexes, jnp.int32)]   # [B, I, H, W]
    B, I, H, W = sel.shape
    k = min(H, W)
    neg = jnp.asarray(-jnp.inf, sel.dtype)
    scores = sel
    picked = jnp.zeros((B, I, H, W), bool)
    row_used = jnp.zeros((B, I, H), bool)
    col_used = jnp.zeros((B, I, W), bool)
    for _ in range(k):
        masked = jnp.where(row_used[..., :, None]
                           | col_used[..., None, :], neg, scores)
        flat = masked.reshape(B, I, H * W)
        idx = jnp.argmax(flat, axis=2)
        r, c = idx // W, idx % W
        picked = picked | (
            (jnp.arange(H)[None, None, :, None] == r[..., None, None])
            & (jnp.arange(W)[None, None, None, :] == c[..., None, None]))
        row_used = row_used | jax.nn.one_hot(r, H, dtype=bool)
        col_used = col_used | jax.nn.one_hot(c, W, dtype=bool)
    mask2d = picked.any(axis=1)
    return one(jnp.broadcast_to(mask2d[:, None], x.shape)
               .astype(x.dtype))
