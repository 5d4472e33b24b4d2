"""Neural-network operators: conv, FC, norm, pooling, activation, softmax.

Reference: src/operator/nn/ (convolution.cc, fully_connected.cc:239-328,
batch_norm.cc, pooling.cc, activation.cc, softmax.cc, dropout.cc,
layer_norm.cc, lrn.cc, upsampling.cc, deconvolution.cc) plus the cuDNN
specializations under src/operator/nn/cudnn/.

TPU-first notes:
- Convolution/FullyConnected lower to ``lax.conv_general_dilated`` /
  ``dot_general`` → the MXU.  Layout stays NCHW at the API (reference
  default); XLA relayouts internally for the TPU (it prefers NHWC and
  does this transformation for free during layout assignment).
- BatchNorm is functional: returns (out, mean, var); running-stat
  updates are performed by the caller (gluon/nn/basic_layers.py) so the
  op stays pure/traceable.  Cross-device sync BN uses lax.pmean when
  running under shard_map (see parallel/).
- Dropout takes an explicit PRNG key input (op purity) — the NDArray
  layer threads keys from mxnet_tpu.random.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from .registry import register


def _tup(v, n):
    if v is None or v == ():
        return (1,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    t = tuple(int(x) for x in v)
    return t


def _conv_dn(nd):
    # (lhs, rhs, out) specs for 1/2/3-D NC* layouts
    spatial = "DHW"[-nd:] if nd <= 3 else None
    lhs = "NC" + spatial
    rhs = "OI" + spatial
    return lax.conv_dimension_numbers((0,) * (nd + 2), (0,) * (nd + 2), (lhs, rhs, lhs))


@register("Convolution", aliases=("conv",))
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                num_filter=1, num_group=1, no_bias=False, layout=None, cudnn_off=False,
                cudnn_tune=None, workspace=1024, **_):
    """N-D convolution (reference: src/operator/nn/convolution.cc).

    ``layout`` supports the reference's channel-first defaults (NCW/
    NCHW/NCDHW, weight OI+spatial) and the channel-last forms (NWC/
    NHWC/NDHWC) with the reference's OHWI weight convention
    (num_filter, *kernel, in_c/groups — conv-inl.h WeightShape for
    NHWC).  cudnn_*/workspace attrs are accepted for API parity
    and ignored — XLA picks the TPU algorithm.
    """
    nd = len(kernel)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad, nd) if pad else (0,) * nd
    channel_last = layout is not None and str(layout).endswith("C")
    if channel_last:
        spatial = "DHW"[-nd:]
        spec = ("N" + spatial + "C", "O" + spatial + "I",
                "N" + spatial + "C")
        dn = lax.conv_dimension_numbers((0,) * (nd + 2), (0,) * (nd + 2),
                                        spec)
        bias_shape = (1,) * (nd + 1) + (-1,)
    else:
        dn = _conv_dn(nd)
        bias_shape = (1, -1) + (1,) * nd
    if (channel_last and nd == 2 and _pallas_dw_enabled()
            and all(d == 1 for d in dilate)):
        # backward-filter via the Pallas kernel (pallas_conv.py) where
        # supported; forward and dX keep XLA's lowering bit-for-bit
        out = _nhwc_conv2d_pallas_dw(stride, pad, int(num_group))(
            data, weight)
    else:
        out = lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=int(num_group),
            preferred_element_type=None,
        )
    if bias is not None and not no_bias:
        out = out + bias.reshape(bias_shape)
    return out


def _pallas_dw_enabled():
    import os

    return os.environ.get("MXTPU_PALLAS_CONV_DW", "0") == "1"


@functools.lru_cache(maxsize=None)
def _nhwc_conv2d_pallas_dw(stride, pad, groups):
    """NHWC 2-D conv whose weight-gradient routes to the Pallas dW
    kernel (MXTPU_PALLAS_CONV_DW=1).  Forward and data-gradient are
    jax.vjp of the plain lax conv — identical lowerings to the default
    path — so only the measured backward-filter changes."""
    import jax

    from . import pallas_conv

    dn = lax.conv_dimension_numbers((0, 0, 0, 0), (0, 0, 0, 0),
                                    ("NHWC", "OHWI", "NHWC"))

    def raw(x, w):
        return lax.conv_general_dilated(
            x, w, window_strides=stride,
            padding=[(p, p) for p in pad],
            dimension_numbers=dn, feature_group_count=groups)

    @jax.custom_vjp
    def conv(x, w):
        return raw(x, w)

    def fwd(x, w):
        return raw(x, w), (x, w)

    def bwd(res, dy):
        x, w = res
        _, vjp_x = jax.vjp(lambda xx: raw(xx, w), x)
        (dx,) = vjp_x(dy)
        kernel = w.shape[1:3]
        if pallas_conv.supported(x.shape, dy.shape, kernel, stride, pad,
                                 (1, 1), groups,
                                 ebytes=x.dtype.itemsize):
            dw = pallas_conv.conv_dw_nhwc(x, dy, kernel,
                                          pad).astype(w.dtype)
        else:
            _, vjp_w = jax.vjp(lambda ww: raw(x, ww), w)
            (dw,) = vjp_w(dy)
        return dx, dw

    conv.defvjp(fwd, bwd)
    return conv


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                  adj=(), target_shape=(), num_filter=1, num_group=1, no_bias=True,
                  layout=None, **_):
    """Transposed convolution (reference: src/operator/nn/deconvolution.cc).

    Implemented as the gradient of convolution via lhs-dilation, which XLA
    maps back onto the MXU."""
    nd = len(kernel)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad, nd) if pad else (0,) * nd
    adj = _tup(adj, nd) if adj else (0,) * nd
    kernel = _tup(kernel, nd)
    # weight layout in MXNet deconv: (in_c, out_c/group, *kernel)
    dn = _conv_dn(nd)
    eff_k = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    padding = [(ek - 1 - p, ek - 1 - p + a) for ek, p, a in zip(eff_k, pad, adj)]
    # flip spatial dims + swap in/out channels → standard transposed conv
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if num_group == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        ic, ocg = w.shape[0], w.shape[1]
        w = w.reshape((int(num_group), ic // int(num_group), ocg) + w.shape[2:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((ocg * int(num_group), ic // int(num_group)) + w.shape[3:])
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    )
    if bias is not None:
        # a supplied bias wins over the no_bias flag: the reference's
        # default no_bias=True governs how many inputs it EXPECTS
        # (deconvolution-inl.h), not whether a provided bias is applied
        # — silently dropping a passed bias was a real bug (r3)
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("FullyConnected", aliases=("fc",))
def fully_connected(data, weight, bias=None, num_hidden=1, no_bias=False, flatten=True, **_):
    """reference: src/operator/nn/fully_connected.cc:239-328."""
    if flatten:
        x = data.reshape((data.shape[0], -1))
    else:
        x = data
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


@register("Activation")
def activation(data, act_type="relu", **_):
    """Elementwise activation (reference: src/operator/nn/activation.cc).

    ``act_type``: relu / sigmoid / tanh / softrelu (softplus) /
    softsign — each lowers to the matching jax.nn / jnp primitive."""
    f = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
    }[act_type]
    return f(data)


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334, **_):
    """Leaky-ReLU family (reference: src/operator/leaky_relu.cc):
    leaky / prelu (learned ``gamma``) / elu / selu / gelu / rrelu
    (eval-mode mean slope — training rrelu needs the Dropout key path)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data > 0, data, alpha * (jnp.exp(data) - 1.0))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        # eval-mode rrelu (mean slope); training rrelu needs RNG — use Dropout-style key path
        return jnp.where(data > 0, data, (lower_bound + upper_bound) / 2.0 * data)
    raise ValueError("unknown act_type %r" % act_type)


@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None, **_):
    """Softmax along ``axis`` (reference: src/operator/nn/softmax.cc)
    with optional ``temperature`` scaling and ``length``-masked
    variable-length rows (masked positions emit exact zeros)."""
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        steps = jnp.arange(x.shape[int(axis)])
        shape = [1] * x.ndim
        shape[int(axis)] = -1
        mask = steps.reshape(shape) < jnp.expand_dims(length, int(axis))
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=int(axis))
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=int(axis))


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None, **_):
    """Numerically-stable log(softmax(data)) along ``axis`` with
    optional ``temperature`` (reference: src/operator/nn/softmax.cc)."""
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=int(axis))


@register("softmin")
def softmin(data, axis=-1, **_):
    """softmax(-data): assigns the highest probability to the SMALLEST
    element along ``axis`` (reference: src/operator/nn/softmin.cc)."""
    return jax.nn.softmax(-data, axis=int(axis))


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance", **_):
    """Deprecated-in-reference softmax layer
    (src/operator/nn/softmax_activation.cc): mode='instance' flattens
    each sample, mode='channel' normalizes along axis 1."""
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@functools.lru_cache(maxsize=None)
def _softmax_output_core(grad_scale, ignore_label, multi_output, use_ignore,
                         normalization, smooth_alpha):
    """Build a custom-vjp softmax-output fn for a static config.

    The backward is the fused (softmax - onehot(label)) cross-entropy
    gradient of the reference (src/operator/softmax_output.cc), ignoring
    the incoming head cotangent — SoftmaxOutput *is* the loss layer.
    """
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def f(data, label):
        return jax.nn.softmax(data, axis=axis)

    def fwd(data, label):
        out = jax.nn.softmax(data, axis=axis)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        ncls = out.shape[axis]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, ncls, dtype=out.dtype, axis=axis)
        if smooth_alpha:
            onehot = (onehot * (1.0 - smooth_alpha)
                      + smooth_alpha / (ncls - 1) * (1.0 - onehot))
        grad = out - onehot
        if use_ignore:
            keep = (lab != int(ignore_label)).astype(out.dtype)
            grad = grad * jnp.expand_dims(keep, axis)
        scale = grad_scale
        if normalization == "batch":
            scale = scale / out.shape[0]
        elif normalization == "valid":
            if use_ignore:
                scale = scale / jnp.maximum(
                    jnp.sum((lab != int(ignore_label)).astype(out.dtype)), 1.0)
            else:
                scale = scale / float(_np.prod(lab.shape))
        grad = grad * scale
        return (grad.astype(out.dtype), jnp.zeros_like(label))

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0, multi_output=False,
                   use_ignore=False, preserve_shape=False, normalization="null",
                   out_grad=False, smooth_alpha=0.0, **_):
    """Softmax forward with fused cross-entropy backward
    (reference: src/operator/softmax_output.cc — the Module-API loss layer)."""
    f = _softmax_output_core(float(grad_scale), float(ignore_label),
                             bool(multi_output), bool(use_ignore),
                             str(normalization), float(smooth_alpha))
    return f(data, label)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label, **_):
    """Summed cross-entropy of softmax(data) against integer ``label``
    indices (reference: src/operator/loss_binary_op.cc)."""
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return jnp.sum(nll)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0, **_):
    """Identity forward with fused L2-loss backward ``pred - label``
    (reference: src/operator/regression_output.cc — Module-API head)."""
    return _regression_out(data, label, grad_scale, "linear")


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0, **_):
    """Identity forward with fused L1-loss backward ``sign(pred -
    label)`` (reference: src/operator/regression_output.cc)."""
    return _regression_out(data, label, grad_scale, "mae")


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0, **_):
    """sigmoid(data) forward with the fused cross-entropy backward
    ``pred - label`` (reference: src/operator/regression_output.cc)."""
    return _regression_out(data, label, grad_scale, "logistic")


@functools.lru_cache(maxsize=None)
def _regression_core(grad_scale, kind):
    @jax.custom_vjp
    def f(data, label):
        return jax.nn.sigmoid(data) if kind == "logistic" else data

    def fwd(data, label):
        out = jax.nn.sigmoid(data) if kind == "logistic" else data
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        lab = label.reshape(out.shape)
        num = out.shape[1] if out.ndim > 1 else 1
        if kind == "mae":
            grad = jnp.sign(out - lab)
        else:  # linear & logistic share (pred - label)
            grad = out - lab
        grad = grad * (grad_scale / num)
        # label cotangent must keep the primal label's shape
        return (grad.astype(out.dtype), jnp.zeros_like(label))

    f.defvjp(fwd, bwd)
    return f


def _regression_out(data, label, grad_scale, kind):
    return _regression_core(float(grad_scale), kind)(data, label)


# ---------------------------------------------------------------- norm layers


def _bn_nout(attrs):
    return 3 if attrs.get("output_mean_var") else 1


@register("BatchNorm", num_outputs=_bn_nout)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1,
               cudnn_off=False, axis_name=None, **_):
    """Functional BatchNorm (reference: src/operator/nn/batch_norm.cc).

    Returns out, or (out, batch_mean, batch_var) when ``output_mean_var``.
    The Gluon layer / executor updates moving stats outside (keeps the op
    pure → traceable); when ``use_global_stats`` (inference) the moving
    stats are used directly.
    """
    ax = int(axis) % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # axis_name: cross-device statistics under EXPLICIT parallelism
    # (shard_map/pmap) — the SyncBatchNorm contract (reference:
    # contrib/sync_batch_norm.cc).  Under GSPMD jit a batch-sharded input
    # already reduces globally without it.
    if use_global_stats:
        mean, var = moving_mean, moving_var
    elif data.dtype in (jnp.bfloat16, jnp.float16):
        # single-pass statistics: E[x] and E[x²] reduce in ONE fused HBM
        # sweep (two-pass (x-mean)² doubled the bandwidth of every BN —
        # the forward is HBM-bound).  fp32 accumulation gives ~2^16 more
        # mantissa than the bf16 inputs, so E[x²]-E[x]² cancellation is
        # bounded by the input's own precision; for fp32 inputs the
        # two-pass form below stays (cancellation would exceed it).
        xf = data.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        meansq = jnp.mean(jnp.square(xf), axis=red)
        if axis_name:
            mean = lax.pmean(mean, axis_name)
            meansq = lax.pmean(meansq, axis_name)
        var = jnp.maximum(meansq - jnp.square(mean), 0.0)
        mean = mean.astype(data.dtype)
        var = var.astype(data.dtype)
    else:
        mean = jnp.mean(data, axis=red)
        if axis_name:
            mean = lax.pmean(mean, axis_name)
        var = jnp.mean(jnp.square(data - _expand(mean, ax, data.ndim)),
                       axis=red)
        if axis_name:
            var = lax.pmean(var, axis_name)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    # scale/shift computed in fp32 (gamma/beta stay fp32 under mixed
    # precision) then applied in the DATA dtype so bf16 activations do
    # not get promoted back to fp32 downstream
    scale = (g.astype(jnp.float32) * inv).astype(data.dtype)
    shift = beta.astype(data.dtype)
    out = (data - _expand(mean.astype(data.dtype), ax, data.ndim)) * \
        _expand(scale, ax, data.ndim) + _expand(shift, ax, data.ndim)
    if output_mean_var:
        return out, mean, var
    return out


def _expand(v, axis, ndim):
    shape = [1] * ndim
    shape[axis] = -1
    return v.reshape(shape)


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **_):
    """Layer normalization over ``axis`` with learned ``gamma``/``beta``
    (reference: src/operator/nn/layer_norm.cc)."""
    ax = int(axis)
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.mean(jnp.square(data - mean), axis=ax, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3, **_):
    """Instance normalization: per-sample, per-channel statistics over
    the spatial axes (reference: src/operator/instance_norm.cc)."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(data - mean), axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance", **_):
    """Scale entries to unit L2 norm per instance/channel/spatial
    position (reference: src/operator/l2_normalization.cc)."""
    if mode == "instance":
        red = tuple(range(1, data.ndim))
    elif mode == "channel":
        red = (1,)
    else:  # spatial
        red = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / norm


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **_):
    """Local response norm across channels (reference: src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = int(nsize) // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    sq = jnp.pad(sq, pad)
    window = sum(
        lax.slice_in_dim(sq, i, i + data.shape[1], axis=1) for i in range(int(nsize))
    )
    return data / jnp.power(knorm + alpha / nsize * window, beta)


# ---------------------------------------------------------------- pooling


@register("Pooling")
def pooling(data, kernel=(), pool_type="max", stride=(), pad=(), global_pool=False,
            pooling_convention="valid", count_include_pad=True, cudnn_off=False,
            p_value=2, layout=None, **_):
    """reference: src/operator/nn/pooling.cc — max/avg/sum/lp pooling,
    'valid' (floor) vs 'full' (ceil) conventions, global pooling."""
    nd = data.ndim - 2
    channel_last = layout is not None and str(layout).endswith("C")
    spatial0 = 1 if channel_last else 2  # first spatial axis
    if global_pool:
        kernel = data.shape[spatial0:spatial0 + nd]
        stride = (1,) * nd
        pad = (0,) * nd
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd) if stride else (1,) * nd
    pad = _tup(pad, nd) if pad else (0,) * nd

    spatial_padding = []
    for i in range(nd):
        lo = hi = pad[i]
        if pooling_convention == "full":
            # ceil convention: possibly extra padding on the high side
            size = data.shape[spatial0 + i]
            out_sz = -(-(size + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - size - pad[i]
            hi = max(needed, pad[i])
        spatial_padding.append((lo, hi))
    if channel_last:
        padding = [(0, 0)] + spatial_padding + [(0, 0)]
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        padding = [(0, 0), (0, 0)] + spatial_padding
        window = (1, 1) + kernel
        strides = (1, 1) + stride

    if pool_type == "max":
        init = -jnp.inf
        out = lax.reduce_window(data, init, lax.max, window, strides, padding)
        return out
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad and pooling_convention != "full":
            denom = float(_np.prod(kernel))
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        # a ceil-convention window can land entirely in padding; its
        # count is 0 and 0/0 would poison the batch with NaN — emit 0
        return summed / jnp.maximum(counts, 1.0)
    if pool_type == "lp":
        p = float(p_value)
        powed = lax.reduce_window(jnp.power(jnp.abs(data), p), 0.0, lax.add,
                                  window, strides, padding)
        return jnp.power(powed, 1.0 / p)
    raise ValueError("unknown pool_type %r" % pool_type)


@register("ROIPooling")
def roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0, **_):
    """reference: src/operator/roi_pooling.cc — fixed-size output so it
    stays jittable (static shapes)."""
    ph, pw = int(pooled_size[0]), int(pooled_size[1])
    H, W = data.shape[2], data.shape[3]

    def pool_one(roi):
        bidx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        img = data[bidx]  # (C, H, W)

        ys = jnp.arange(H)
        xs = jnp.arange(W)

        def cell(i, j):
            hstart = y1 + (i * rh) // ph
            hend = y1 + ((i + 1) * rh + ph - 1) // ph
            wstart = x1 + (j * rw) // pw
            wend = x1 + ((j + 1) * rw + pw - 1) // pw
            m = ((ys[:, None] >= hstart) & (ys[:, None] < hend)
                 & (xs[None, :] >= wstart) & (xs[None, :] < wend))
            masked = jnp.where(m[None], img, -jnp.inf)
            v = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(v), v, 0.0)

        cells = jnp.stack([jnp.stack([cell(i, j) for j in range(pw)], -1)
                           for i in range(ph)], -2)  # (C, ph, pw)
        return cells

    return jax.vmap(pool_one)(rois)


# ---------------------------------------------------------------- dropout


@register("Dropout")
def dropout(key, data, p=0.5, mode="training", axes=(), cudnn_off=False, **_):
    """reference: src/operator/nn/dropout.cc.  ``key`` is an explicit PRNG
    key threaded by the NDArray layer (mxnet_tpu/random.py) so the op is
    pure; in 'always' mode or outside autograd training scope the caller
    passes key=None → identity."""
    if key is None or p <= 0.0:
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask


# ---------------------------------------------------------------- resize/upsample


@register("UpSampling")
def upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
               multi_input_mode="concat", workspace=512, **_):
    """Spatial upsampling (reference: src/operator/nn/upsampling.cc):
    'nearest' repeats pixels (multi-input concat supported), 'bilinear'
    uses jax.image.resize in place of the reference's deconv kernel."""
    data = args[0]
    s = int(scale)
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
        if len(args) > 1 and multi_input_mode == "concat":
            outs = [out]
            for a in args[1:]:
                ss = data.shape[2] * s // a.shape[2]
                outs.append(jnp.repeat(jnp.repeat(a, ss, axis=2), ss, axis=3))
            out = jnp.concatenate(outs, axis=1)
        return out
    # bilinear upsampling uses a deconv in the reference; use jax.image
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * s, w * s), method="bilinear")


@register("BilinearSampler")
def bilinear_sampler(data, grid, **_):
    """reference: src/operator/bilinear_sampler.cc (STN sampler)."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0

    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(img, yy, xx):
        yy = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xx = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        return img[:, yy, xx]

    def sample_one(img, y0_, x0_, wy_, wx_):
        v00 = gather(img, y0_, x0_)
        v01 = gather(img, y0_, x0_ + 1)
        v10 = gather(img, y0_ + 1, x0_)
        v11 = gather(img, y0_ + 1, x0_ + 1)
        return (v00 * (1 - wy_) * (1 - wx_) + v01 * (1 - wy_) * wx_
                + v10 * wy_ * (1 - wx_) + v11 * wy_ * wx_)

    return jax.vmap(sample_one)(data, y0, x0, wy, wx)


@register("GridGenerator")
def grid_generator(data, transform_type="affine", target_shape=(0, 0), **_):
    """Sampling-grid generation for the spatial transformer (reference:
    src/operator/grid_generator.cc): 'affine' expands 2x3 thetas onto a
    normalized (h, w) mesh, 'warp' converts a flow field to grid
    coordinates."""
    h, w = int(target_shape[0]), int(target_shape[1])
    if transform_type == "affine":
        theta = data.reshape((-1, 2, 3))
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()], axis=0)  # (3, h*w)
        out = jnp.einsum("nij,jk->nik", theta, base)  # (n, 2, h*w)
        return out.reshape((-1, 2, h, w))
    # warp type: data is (n, 2, h, w) flow
    n = data.shape[0]
    ys = jnp.arange(h, dtype=data.dtype)
    xs = jnp.arange(w, dtype=data.dtype)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    fx = (data[:, 0] + gx) * 2.0 / jnp.maximum(w - 1, 1) - 1.0
    fy = (data[:, 1] + gy) * 2.0 / jnp.maximum(h - 1, 1) - 1.0
    return jnp.stack([fx, fy], axis=1)


@register("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0), transform_type="affine",
                        sampler_type="bilinear", **_):
    """Spatial transformer network head (reference:
    src/operator/spatial_transformer.cc): affine grid from ``loc``
    thetas + bilinear sampling of ``data``."""
    grid = grid_generator(loc, transform_type="affine", target_shape=target_shape)
    return bilinear_sampler(data, grid)


@register("CTCLoss", aliases=("ctc_loss",))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False, blank_label="first", **_):
    """CTC loss (reference: src/operator/contrib/ctc_loss.cc, 3rdparty/ctc_include).

    data: (seq, batch, alphabet) activations (pre-softmax).
    Uses a lax.scan forward algorithm in log space.
    """
    # The reference op contracts its input list by the use_* flags
    # (ctc_loss.cc ListArguments): when only label_lengths is in use, it
    # is the THIRD input.  Positional callers (gluon CTCLoss passes
    # pred_lengths=None) therefore land it in the data_lengths slot.
    if use_label_lengths and not use_data_lengths and label_lengths is None:
        label_lengths, data_lengths = data_lengths, None
    seq_len, batch, alphabet = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else alphabet - 1
    lab = label.astype(jnp.int32)
    if blank_label == "last":
        pass  # labels already 0-based
    max_lab = lab.shape[1]
    if label_lengths is not None and use_label_lengths:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        # reference: 0 (or -1) padding marks end when blank is 'first'
        valid = (lab > 0) if blank == 0 else (lab >= 0)
        lab_len = jnp.sum(valid.astype(jnp.int32), axis=1)
    if data_lengths is not None and use_data_lengths:
        dat_len = data_lengths.astype(jnp.int32)
    else:
        dat_len = jnp.full((batch,), seq_len, dtype=jnp.int32)

    # extended label sequence with blanks: length 2L+1
    ext_len = 2 * max_lab + 1
    pos = jnp.arange(ext_len)
    ext = jnp.where(pos % 2 == 0, blank, lab[:, jnp.minimum(pos // 2, max_lab - 1)])
    neg_inf = jnp.asarray(-1e30, dtype=logp.dtype)

    alpha0 = jnp.full((batch, ext_len), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = ext[:, 1]
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(lab_len > 0, jnp.take_along_axis(logp[0], first_lab[:, None], 1)[:, 0], neg_inf))

    def step(alpha, t):
        lp = logp[t]  # (batch, alphabet)
        emit = jnp.take_along_axis(lp, ext, axis=1)  # (batch, ext_len)
        a_prev = alpha
        a_shift1 = jnp.concatenate([jnp.full((batch, 1), neg_inf), alpha[:, :-1]], 1)
        a_shift2 = jnp.concatenate([jnp.full((batch, 2), neg_inf), alpha[:, :-2]], 1)
        same = (ext == jnp.concatenate([jnp.full((batch, 2), -1, dtype=jnp.int32),
                                        ext[:, :-2]], 1))
        is_blank = ext == blank
        allow2 = ~(is_blank | same)
        cand = jnp.where(allow2, jnp.logaddexp(jnp.logaddexp(a_prev, a_shift1), a_shift2),
                         jnp.logaddexp(a_prev, a_shift1))
        new_alpha = cand + emit
        # freeze past data length
        active = (t < dat_len)[:, None]
        new_alpha = jnp.where(active, new_alpha, alpha)
        return new_alpha, None

    alphaT, _unused = lax.scan(step, alpha0, jnp.arange(1, seq_len))
    end1 = 2 * lab_len
    end2 = 2 * lab_len - 1
    p1 = jnp.take_along_axis(alphaT, end1[:, None], 1)[:, 0]
    p2 = jnp.where(lab_len > 0,
                   jnp.take_along_axis(alphaT, jnp.maximum(end2, 0)[:, None], 1)[:, 0],
                   neg_inf)
    return -jnp.logaddexp(p1, p2)
