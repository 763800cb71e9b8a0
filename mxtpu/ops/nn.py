"""Neural-network layer ops: conv/pool/BN/FC/activations/losses/sequence ops.

Parity with the reference's legacy OperatorProperty layer set (SURVEY.md §2.3,
src/operator/{convolution,pooling,batch_norm,fully_connected,activation,dropout,
softmax_output,leaky_relu,lrn,concat,slice_channel,pad,upsampling,instance_norm,
l2_normalization,sequence_*,regression_output,make_loss}-inl.h). TPU-native: each
lowers to a handful of XLA HLOs (conv_general_dilated, reduce_window, dot_general)
and the cuDNN wrapper layer (src/operator/cudnn_*) disappears into the compiler.
Loss-head ops (SoftmaxOutput etc.) use jax.custom_vjp to encode the reference
semantics that backward ignores incoming head gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from . import mixers
from .registry import Required, register

# ---------------------------------------------------------------- FullyConnected


def _fully_connected(a, data, weight, bias=None):
    if a.get("flatten", True):
        x = data.reshape(data.shape[0], -1)
    else:
        x = data  # apply along the last axis (Gluon Dense flatten=False)
    out = jnp.dot(x, weight.T)
    if bias is not None:
        out = out + bias
    return out


register("FullyConnected", _fully_connected,
         arg_names=lambda a: ["data", "weight"] if a.get("no_bias") else
         ["data", "weight", "bias"],
         attrs={"num_hidden": Required(int), "no_bias": False,
                "flatten": True})

# ---------------------------------------------------------------- Convolution

_CONV_DNUMS = {1: ("NCW", "OIW", "NCW"),
               2: ("NCHW", "OIHW", "NCHW"),
               3: ("NCDHW", "OIDHW", "NCDHW")}


def _tup(v, n, default):
    v = tuple(v) if v else ()
    if len(v) < n:
        v = v + (default,) * (n - len(v))
    return v[:n]


def _convolution(a, data, weight, bias=None):
    nd = len(a.kernel)
    stride = _tup(a.stride, nd, 1)
    dilate = _tup(a.dilate, nd, 1)
    pad = _tup(a.pad, nd, 0)
    dnums = _CONV_DNUMS[nd]
    channels_last = nd == 2 and a.get("layout") == "NHWC"
    if channels_last:
        # channels-last activations (the compile pipeline's `layout`
        # transform): the WEIGHT keeps its OIHW storage — only the
        # activation layout moves, so bind dicts/checkpoints are
        # untouched and the rewrite never transposes parameters
        dnums = ("NHWC", "OIHW", "NHWC")
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dnums,
        feature_group_count=int(a.num_group),
        preferred_element_type=None)
    if bias is not None:
        out = out + (bias if channels_last
                     else bias.reshape((1, -1) + (1,) * nd))
    return out


register("Convolution", _convolution,
         arg_names=lambda a: ["data", "weight"] if a.get("no_bias") else
         ["data", "weight", "bias"],
         attrs={"kernel": Required(tuple), "stride": (), "dilate": (), "pad": (),
                "num_filter": Required(int), "num_group": 1, "no_bias": False,
                "workspace": 1024, "cudnn_tune": None, "cudnn_off": False,
                "layout": None},
         aliases=("Convolution_v1",))


def _causal_conv1d(a, data, weight, bias=None):
    """Causal depthwise convolution over time, channels last: data
    (B, T, C), weight (C, K), y[t] = act(sum_i weight[:, i] * x[t - (K-1) + i]
    + bias), nothing before the row's start; with ``bias`` a third input
    (C,) is added; ``act_type`` is ``"none"`` or ``"silu"``. The short
    convolution of the linear-attention and state-space mixers (K = 4): K
    shifted multiply-adds that XLA fuses into one pass, where a grouped
    Convolution with one channel a group would go through the convolution
    emitter. Accumulates in float32, rounds once after the activation. The
    gradient is the operator's own (mixers.py `short_conv`): from dY and the
    data alone, one pass, nothing float32 of the data's size kept or
    written."""
    return mixers.short_conv(data, weight, bias, a.kernel,
                             a.get("act_type", "none"))


register("_contrib_CausalConv1D", _causal_conv1d,
         arg_names=lambda a: ["data", "weight", "bias"] if a.get("bias")
         else ["data", "weight"],
         attrs={"kernel": Required(int), "bias": False, "act_type": "none"},
         aliases=("causal_conv1d",))


def _deconvolution(a, data, weight, bias=None):
    """Transposed convolution as the explicit gradient-of-conv form:
    lhs_dilation=stride + spatially-flipped weight. Weight layout is the
    reference's (C_in, C_out/g, *k) (deconvolution-inl.h); verified
    element-for-element against torch.nn.functional.conv_transpose
    across channel/stride/pad/output_padding/group combinations
    (tests/test_operator_semantics.py)."""
    nd = len(a.kernel)
    k = tuple(int(x) for x in a.kernel)
    stride = _tup(a.stride, nd, 1)
    dilate = _tup(a.dilate, nd, 1)
    pad = _tup(a.pad, nd, 0)
    adj = _tup(a.adj, nd, 0)
    g = int(a.num_group)
    ke = tuple(dilate[i] * (k[i] - 1) + 1 for i in range(nd))  # effective
    if a.target_shape:
        tgt = _tup(a.target_shape, nd, 0)
        adj = tuple(
            tgt[i] - ((data.shape[2 + i] - 1) * stride[i]
                      - 2 * pad[i] + ke[i])
            for i in range(nd))
    ci = weight.shape[0]
    co = weight.shape[1] * g
    w = weight[(slice(None), slice(None)) + (slice(None, None, -1),) * nd]
    # (C_in, C_out/g, *k) -> blockwise (C_out, C_in/g, *k) so XLA's grouped
    # conv sees the standard O/I layout
    w = w.reshape((g, ci // g, co // g) + k)
    w = jnp.swapaxes(w, 1, 2).reshape((co, ci // g) + k)
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd,
        padding=[(ke[i] - 1 - pad[i], ke[i] - 1 - pad[i] + adj[i])
                 for i in range(nd)],
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=_CONV_DNUMS[nd],
        feature_group_count=g)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


register("Deconvolution", _deconvolution,
         arg_names=lambda a: ["data", "weight"] if a.get("no_bias", True) else
         ["data", "weight", "bias"],
         attrs={"kernel": Required(tuple), "stride": (), "dilate": (), "pad": (),
                "adj": (), "target_shape": (), "num_filter": Required(int),
                "num_group": 1, "no_bias": True, "workspace": 512,
                "cudnn_tune": None, "cudnn_off": False, "layout": None})

# ---------------------------------------------------------------- Pooling


def _pool_pads(in_shape, kernel, stride, pad, convention):
    """Per-dim (lo, hi) padding; 'full' (ceil) convention pads extra on the high side."""
    pads = []
    for x, k, s, p in zip(in_shape, kernel, stride, pad):
        if convention == "full":
            out = -(-(x + 2 * p - k) // s) + 1  # ceil
        else:
            out = (x + 2 * p - k) // s + 1
        needed = max((out - 1) * s + k - x - p, p)
        pads.append((p, needed))
    return pads


def _pooling(a, data):
    nd = data.ndim - 2
    channels_last = nd == 2 and a.get("layout") == "NHWC"
    spatial = data.shape[1:3] if channels_last else data.shape[2:]
    if a.global_pool:
        kernel = spatial
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = _tup(a.kernel, nd, 1)
        stride = _tup(a.stride, nd, 1)
        pad = _tup(a.pad, nd, 0)
    sp_pads = _pool_pads(spatial, kernel, stride, pad,
                         a.pooling_convention)
    if channels_last:
        pads = [(0, 0)] + sp_pads + [(0, 0)]
        dims = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
    else:
        pads = [(0, 0), (0, 0)] + sp_pads
        dims = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
    if a.pool_type == "max":
        # scalar init keeps XLA's reduce-window-max pattern (autodiff-able)
        return lax.reduce_window(data, -jnp.inf, lax.max, dims, strides, pads)
    s = lax.reduce_window(data, 0.0, lax.add, dims, strides, pads)
    if a.pool_type == "sum":
        return s
    # avg: divide by full window size (reference mshadow pool includes padding)
    denom = 1
    for k in kernel:
        denom *= k
    return s / jnp.asarray(denom, data.dtype)


register("Pooling", _pooling,
         attrs={"kernel": (), "pool_type": "max", "global_pool": False,
                "stride": (), "pad": (), "pooling_convention": "valid",
                "cudnn_off": False, "layout": None},
         aliases=("Pooling_v1",))

# ---------------------------------------------------------------- BatchNorm


def _batch_norm(a, data, gamma, beta, moving_mean, moving_var):
    ax = int(a.get("axis", 1))
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    g = jnp.ones_like(gamma) if a.fix_gamma else gamma
    if a.use_global_stats or not a.get("__is_train__", False):
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    else:
        # single-pass stats: sum and sum-of-squares fuse into ONE
        # multi-output reduction that reads the (bf16) activation once with
        # the f32 convert inlined. The two-pass jnp.var form needs the f32
        # activation twice, which makes XLA materialize a full f32 copy of
        # every conv output — ~2x the training step's HBM traffic.
        n = 1.0
        for i in red:
            n *= data.shape[i]
        s1 = jnp.sum(data, axis=red, dtype=jnp.float32)
        s2 = jnp.sum(jnp.square(data.astype(jnp.float32)), axis=red)
        mean32 = s1 / n
        var32 = jnp.maximum(s2 / n - jnp.square(mean32), 0.0)
        mean = mean32.astype(data.dtype)
        var = var32.astype(data.dtype)
        m = a.momentum
        new_mm = m * moving_mean + (1 - m) * lax.stop_gradient(mean)
        new_mv = m * moving_var + (1 - m) * lax.stop_gradient(var)
    inv = lax.rsqrt(var.astype(jnp.float32) + a.eps).astype(data.dtype)
    out = (data - mean.reshape(bshape)) * (g * inv).reshape(bshape) + beta.reshape(bshape)
    if a.output_mean_var:
        return out, mean, var, new_mm, new_mv
    return out, new_mm, new_mv


register("BatchNorm", _batch_norm,
         arg_names=["data", "gamma", "beta", "moving_mean", "moving_var"],
         aux_names=["moving_mean", "moving_var"],
         attrs={"eps": 1e-3, "momentum": 0.9, "fix_gamma": True,
                "use_global_stats": False, "output_mean_var": False, "axis": 1,
                "__is_train__": False},
         num_outputs=lambda a: 3 if a.output_mean_var else 1,
         aliases=("BatchNorm_v1",))


def _layer_norm(a, data, gamma, beta):
    """Normalize over one axis with learned scale/shift (the transformer
    family's workhorse; the reference gained nn.LayerNorm post-0.11 —
    src/operator/nn/layer_norm.cc in later MXNet, whose extra outputs are
    (mean, STD)). Statistics follow _batch_norm's traffic discipline: one
    multi-output sum/sum-of-squares reduction with f32 accumulation and
    the convert inlined, never a materialized f32 copy of the input."""
    ax = int(a.get("axis", -1)) % data.ndim
    n = data.shape[ax]
    s1 = jnp.sum(data, axis=ax, keepdims=True, dtype=jnp.float32)
    s2 = jnp.sum(jnp.square(data.astype(jnp.float32)), axis=ax,
                 keepdims=True)
    mean = s1 / n
    # clamp: the E[x^2]-E[x]^2 cancellation can go slightly negative
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + a.eps)
    bshape = tuple(data.shape[ax] if i == ax else 1
                   for i in range(data.ndim))
    out32 = (data.astype(jnp.float32) - mean) * inv \
        * gamma.astype(jnp.float32).reshape(bshape) \
        + beta.astype(jnp.float32).reshape(bshape)
    out = out32.astype(data.dtype)
    if a.output_mean_var:
        return (out,
                jnp.squeeze(mean, ax).astype(data.dtype),
                jnp.squeeze(jnp.sqrt(var + a.eps), ax).astype(data.dtype))
    return out


register("LayerNorm", _layer_norm,
         arg_names=["data", "gamma", "beta"],
         attrs={"eps": 1e-5, "axis": -1, "output_mean_var": False},
         num_outputs=lambda a: 3 if a.output_mean_var else 1)


def _rms_norm(a, data, gamma, gate=None):
    """gamma * x / sqrt(mean(x^2) + eps) over one axis (Zhang and Sennrich,
    arXiv:1910.07467), no mean and no shift. The reduction and the scaling
    run in float32 whatever the input's dtype; one fused pass, as
    _layer_norm. With ``gated`` a third input scales the result by
    silu(gate), the output gate of the linear-attention mixers; with
    ``gate_first`` as well, silu(gate) multiplies x before the mean square
    is taken, as the state-space mixers gate theirs. With ``groups`` > 1 the
    mean square is taken over each of that many equal groups of the axis
    (the last), gamma still one number a channel: the output norm of the
    state-space mixers. A gated norm over the last axis is the operator
    with its own gradient (mixers.py `gated_norm`): one pass forward, one
    backward from dOut, the data and the gate."""
    ax = int(a.axis) % data.ndim
    groups = int(a.get("groups", 1))
    gate_first = bool(a.get("gate_first"))
    if gate is not None and ax == data.ndim - 1:
        return mixers.gated_norm(data, gamma, gate, a.eps, groups, gate_first)
    x = data.astype(jnp.float32)
    if gate is not None and gate_first:
        x = x * jax.nn.silu(gate.astype(jnp.float32))
    if groups > 1:
        if ax != data.ndim - 1 or data.shape[ax] % groups:
            raise ValueError("RMSNorm: %d groups of axis %d of %r" % (
                groups, ax, data.shape))
        grouped = x.reshape(x.shape[:-1] + (groups, -1))
        ms = jnp.broadcast_to(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True),
            grouped.shape).reshape(x.shape)
    else:
        ms = jnp.mean(jnp.square(x), axis=ax, keepdims=True)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = x * lax.rsqrt(ms + a.eps) * gamma.astype(jnp.float32).reshape(bshape)
    if gate is not None and not gate_first:
        out = out * jax.nn.silu(gate.astype(jnp.float32))
    return out.astype(data.dtype)


register("RMSNorm", _rms_norm,
         arg_names=lambda a: ["data", "gamma", "gate"] if a.get("gated") else
         ["data", "gamma"],
         attrs={"eps": 1e-6, "axis": -1, "gated": False, "groups": 1,
                "gate_first": False})

# ---------------------------------------------------------------- activations


def _activation(a, x):
    t = a.act_type
    if t == "relu":
        return jnp.maximum(x, 0)
    if t == "sigmoid":
        return jax.nn.sigmoid(x)
    if t == "tanh":
        return jnp.tanh(x)
    if t == "softrelu":
        return jax.nn.softplus(x)
    if t == "silu":
        return jax.nn.silu(x)
    raise ValueError("unknown act_type %s" % t)


register("Activation", _activation, attrs={"act_type": Required(str)})


def _leaky_relu(a, x, gamma=None):
    t = a.act_type
    if t == "leaky":
        return jnp.where(x > 0, x, a.slope * x)
    if t == "elu":
        return jnp.where(x > 0, x, a.slope * (jnp.exp(x) - 1))
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x > 0, x, g * x)
    if t == "rrelu":
        slope = (a.lower_bound + a.upper_bound) / 2.0
        return jnp.where(x > 0, x, slope * x)
    raise ValueError("unknown act_type %s" % t)


register("LeakyReLU", _leaky_relu,
         arg_names=lambda a: ["data", "gamma"] if a.get("act_type") == "prelu"
         else ["data"],
         attrs={"act_type": "leaky", "slope": 0.25, "lower_bound": 0.125,
                "upper_bound": 0.334})

# ---------------------------------------------------------------- softmax family
register("softmax", lambda a, x: jax.nn.softmax(
    x / (a.temperature or 1.0), axis=int(a.axis)),
    attrs={"axis": -1, "temperature": None})
register("log_softmax", lambda a, x: jax.nn.log_softmax(
    x / (a.temperature or 1.0), axis=int(a.axis)),
    attrs={"axis": -1, "temperature": None})


def _softmax_activation(a, x):
    if a.mode == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


register("SoftmaxActivation", _softmax_activation, attrs={"mode": "instance"})


# -- SoftmaxOutput: forward = softmax(data); backward = (p - target) * scale,
#    ignoring head gradients (reference src/operator/softmax_output-inl.h).
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _softmax_output_core(a, data, label):
    return _softmax_fwd_only(a, data)


def _softmax_fwd_only(a, data):
    if a.multi_output:
        return jax.nn.softmax(data, axis=1)
    if data.ndim > 2 and not a.preserve_shape:
        return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(a, data, label):
    out = _softmax_fwd_only(a, data)
    return out, (out, label)


def _softmax_output_bwd(a, res, g):
    p, label = res
    axis = 1 if a.multi_output else p.ndim - 1
    if label.shape == p.shape:
        target = label
        valid = jnp.ones(label.shape[:1], p.dtype)
    else:
        idx = label.astype(jnp.int32)
        target = jax.nn.one_hot(idx, p.shape[axis], dtype=p.dtype, axis=axis)
        if a.use_ignore:
            mask = (idx != int(a.ignore_label)).astype(p.dtype)
            target = jnp.where(jnp.expand_dims(mask, axis).astype(bool), target, p)
            valid = mask
        else:
            valid = jnp.ones(idx.shape, p.dtype)
    grad = (p - target) * a.grad_scale
    if a.normalization == "batch":
        grad = grad / p.shape[0]
    elif a.normalization == "valid":
        grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
    return grad.astype(p.dtype), jnp.zeros_like(label)


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)

register("SoftmaxOutput", lambda a, data, label: _softmax_output_core(a, data, label),
         arg_names=["data", "label"],
         attrs={"grad_scale": 1.0, "ignore_label": -1.0, "multi_output": False,
                "use_ignore": False, "preserve_shape": False,
                "normalization": "null", "out_grad": False, "smooth_alpha": 0.0},
         loss_like=True, aliases=("Softmax",))


def _softmax_cross_entropy(a, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    idx = label.astype(jnp.int32)
    return -jnp.sum(jnp.take_along_axis(logp, idx[:, None], axis=-1))


register("softmax_cross_entropy", _softmax_cross_entropy,
         arg_names=["data", "label"], attrs={})

# ---------------------------------------------------------------- regression heads


def _regression(name, link, grad_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def core(a, data, label):
        return link(data)

    def fwd(a, data, label):
        out = link(data)
        return out, (out, label)

    def bwd(a, res, g):
        out, label = res
        # the reference reshapes label to the prediction's shape
        # (regression_output-inl.h), so (b,) labels pair with (b, 1) preds
        # without broadcasting into a (b, b) gradient
        lab = label.reshape(out.shape) if label.shape != out.shape else label
        grad = grad_fn(out, lab) * a.grad_scale
        return grad.astype(out.dtype), jnp.zeros_like(label)

    core.defvjp(fwd, bwd)
    register(name, lambda a, d, l: core(a, d, l), arg_names=["data", "label"],
             attrs={"grad_scale": 1.0}, loss_like=True)


_regression("LinearRegressionOutput", lambda x: x, lambda o, l: o - l)
_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l)
_regression("MAERegressionOutput", lambda x: x, lambda o, l: jnp.sign(o - l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _make_loss_core(a, data):
    return data


def _make_loss_fwd(a, data):
    return data, data.shape


def _make_loss_bwd(a, shape, g):
    scale = a.grad_scale
    if a.normalization == "batch":
        scale = scale / shape[0]
    elif a.normalization == "valid":
        scale = scale / max(1, int(_np.prod(shape)))
    return (jnp.full(shape, scale, jnp.float32),)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)
register("MakeLoss", lambda a, x: _make_loss_core(a, x),
         attrs={"grad_scale": 1.0, "valid_thresh": 0.0, "normalization": "null"},
         loss_like=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _svm_core(a, data, label):
    return data


def _svm_fwd(a, data, label):
    return data, (data, label)


def _svm_bwd(a, res, g):
    data, label = res
    idx = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(idx, data.shape[-1], dtype=data.dtype)
    if a.use_linear:
        viol = ((1 - onehot * 2) * data + a.margin > 0).astype(data.dtype)
        grad = viol * (1 - onehot * 2)
    else:
        dist = (1 - onehot * 2) * data + a.margin
        grad = 2 * jnp.maximum(dist, 0) * (1 - onehot * 2)
    return (grad * a.regularization_coefficient).astype(data.dtype), jnp.zeros_like(label)


_svm_core.defvjp(_svm_fwd, _svm_bwd)
register("SVMOutput", lambda a, d, l: _svm_core(a, d, l), arg_names=["data", "label"],
         attrs={"margin": 1.0, "regularization_coefficient": 1.0, "use_linear": False},
         loss_like=True)

# ---------------------------------------------------------------- Dropout


def _dropout(a, rng, x):
    if not a.get("__is_train__", False) or a.p <= 0:
        return x
    keep = 1.0 - a.p
    mask = jax.random.bernoulli(rng, keep, x.shape).astype(x.dtype) / keep
    return x * mask


register("Dropout", _dropout, attrs={"p": 0.5, "__is_train__": False},
         needs_rng=True)

# ---------------------------------------------------------------- normalization


def _lrn(a, x):
    n = int(a.nsize)
    sq = jnp.square(x)
    pad = [(0, 0), (n // 2, n // 2), (0, 0), (0, 0)][: x.ndim]
    while len(pad) < x.ndim:
        pad.append((0, 0))
    # literal init value: a traced init breaks reverse-mode autodiff of
    # reduce_window (same constraint as Pooling above)
    s = lax.reduce_window(sq, 0.0, lax.add,
                          (1, n) + (1,) * (x.ndim - 2), (1,) * x.ndim, pad)
    return x * jnp.power(a.knorm + (a.alpha / n) * s, -a.beta)


register("LRN", _lrn,
         attrs={"alpha": 1e-4, "beta": 0.75, "knorm": 2.0, "nsize": Required(int)})


def _instance_norm(a, x, gamma, beta):
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * lax.rsqrt(var + a.eps) * gamma.reshape(bshape) + beta.reshape(bshape)


register("InstanceNorm", _instance_norm, arg_names=["data", "gamma", "beta"],
         attrs={"eps": 1e-3})


def _l2_normalization(a, x):
    if a.mode == "last":
        # each vector along the last axis (a head's query or key), float32
        x32 = x.astype(jnp.float32)
        ss = jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(ss + a.eps)).astype(x.dtype)
    if a.mode == "channel":
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + a.eps)
    elif a.mode == "spatial":
        red = tuple(range(2, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=True) + a.eps)
    else:  # instance
        red = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=True) + a.eps)
    return x / norm


register("L2Normalization", _l2_normalization,
         attrs={"eps": 1e-10, "mode": "instance"})

# ---------------------------------------------------------------- concat / split
register("Concat", lambda a, *xs: jnp.concatenate(xs, axis=int(a.dim)),
         variadic="num_args", attrs={"num_args": Required(int), "dim": 1},
         aliases=("concat",))


def _slice_channel(a, x):
    ax = int(a.axis)
    parts = jnp.split(x, int(a.num_outputs), axis=ax)
    if a.squeeze_axis:
        parts = [jnp.squeeze(p, axis=ax) for p in parts]
    return tuple(parts)


register("SliceChannel", _slice_channel,
         attrs={"num_outputs": Required(int), "axis": 1, "squeeze_axis": False},
         num_outputs=lambda a: int(a.num_outputs), aliases=("split",))

# ---------------------------------------------------------------- pad / upsample


def _pad(a, x):
    pw = a.pad_width
    pairs = [(int(pw[2 * i]), int(pw[2 * i + 1])) for i in range(x.ndim)]
    if a.mode == "constant":
        return jnp.pad(x, pairs, constant_values=a.constant_value)
    mode = {"edge": "edge", "reflect": "reflect"}[a.mode]
    return jnp.pad(x, pairs, mode=mode)


register("Pad", _pad,
         attrs={"mode": Required(str), "pad_width": Required(tuple),
                "constant_value": 0.0},
         aliases=("pad",))


def _upsampling(a, *xs):
    s = int(a.scale)
    if a.sample_type == "nearest":
        outs = []
        target = None
        for x in xs:
            up = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
            if target is None:
                target = up.shape[2:]
            outs.append(up)
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=1)
    x = xs[0]
    new = (x.shape[0], x.shape[1], x.shape[2] * s, x.shape[3] * s)
    return jax.image.resize(x, new, method="bilinear")


register("UpSampling", _upsampling, variadic="num_args",
         attrs={"num_args": 1, "scale": Required(int), "sample_type": "nearest",
                "num_filter": 0, "multi_input_mode": "concat", "workspace": 512})


def _crop_op(a, *xs):
    x = xs[0]
    if len(xs) == 2:
        h, w = xs[1].shape[2], xs[1].shape[3]
    else:
        h, w = int(a.h_w[0]), int(a.h_w[1])
    if a.center_crop:
        y0 = (x.shape[2] - h) // 2
        x0 = (x.shape[3] - w) // 2
    else:
        y0, x0 = int(a.offset[0]), int(a.offset[1])
    return x[:, :, y0:y0 + h, x0:x0 + w]


register("Crop", _crop_op, variadic="num_args",
         attrs={"num_args": 1, "offset": (0, 0), "h_w": (0, 0),
                "center_crop": False})

# ---------------------------------------------------------------- sequence ops


def _seq_iota(data):
    # data layout (T, N, ...) -- axis 0 is time (reference sequence_*-inl.h)
    T = data.shape[0]
    shape = (T,) + (1,) * (data.ndim - 1)
    return jnp.arange(T).reshape(shape)


def _sequence_last(a, data, sequence_length=None):
    if not a.use_sequence_length or sequence_length is None:
        return data[-1]
    idx = (sequence_length.astype(jnp.int32) - 1)  # (N,)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)).astype(jnp.int32),
        axis=0)[0]


register("SequenceLast", _sequence_last,
         arg_names=lambda a: ["data", "sequence_length"]
         if a.get("use_sequence_length") else ["data"],
         attrs={"use_sequence_length": False})


def _sequence_mask(a, data, sequence_length=None):
    if not a.use_sequence_length or sequence_length is None:
        return data
    t = _seq_iota(data)
    lens = sequence_length.reshape((1, -1) + (1,) * (data.ndim - 2))
    return jnp.where(t < lens, data, jnp.asarray(a.value, data.dtype))


register("SequenceMask", _sequence_mask,
         arg_names=lambda a: ["data", "sequence_length"]
         if a.get("use_sequence_length") else ["data"],
         attrs={"use_sequence_length": False, "value": 0.0})


def _sequence_reverse(a, data, sequence_length=None):
    if not a.use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    t = _seq_iota(data)
    lens = sequence_length.reshape((1, -1) + (1,) * (data.ndim - 2)).astype(jnp.int32)
    src = jnp.where(t < lens, lens - 1 - t, t)
    return jnp.take_along_axis(data, jnp.broadcast_to(src, data.shape), axis=0)


register("SequenceReverse", _sequence_reverse,
         arg_names=lambda a: ["data", "sequence_length"]
         if a.get("use_sequence_length") else ["data"],
         attrs={"use_sequence_length": False})

# ---------------------------------------------------------------- misc
register("IdentityAttachKLSparseReg", lambda a, x: x,
         attrs={"sparseness_target": 0.1, "penalty": 0.001, "momentum": 0.9})

# ------------------------------------------------------- arg-shape inference
# fills parameter shapes from the data shape (see registry.OpDef.infer_args)
from .registry import get_op as _get_op  # noqa: E402


def _prod(xs):
    p = 1
    for x in xs:
        p *= x
    return p


def _fc_infer(a, shapes):
    data = shapes[0]
    d = data[-1] if not a.get("flatten", True) else _prod(data[1:])
    out = [data, (int(a.num_hidden), d)]
    if not a.no_bias:
        out.append((int(a.num_hidden),))
    return out


_get_op("FullyConnected").infer_args = _fc_infer


def _conv_infer(a, shapes):
    data = shapes[0]
    c = data[-1] if a.get("layout") == "NHWC" else data[1]
    w = (int(a.num_filter), c // int(a.num_group)) + tuple(a.kernel)
    out = [data, w]
    if not a.no_bias:
        out.append((int(a.num_filter),))
    return out


_get_op("Convolution").infer_args = _conv_infer


def _deconv_infer(a, shapes):
    data = shapes[0]
    c = data[1]
    w = (c, int(a.num_filter) // int(a.num_group)) + tuple(a.kernel)
    out = [data, w]
    if not a.no_bias:
        out.append((int(a.num_filter),))
    return out


_get_op("Deconvolution").infer_args = _deconv_infer


def _bn_infer(a, shapes):
    data = shapes[0]
    c = (data[int(a.get("axis", 1))],)
    return [data, c, c, c, c]


_get_op("BatchNorm").infer_args = _bn_infer


def _ln_infer(a, shapes):
    data = shapes[0]
    c = (data[int(a.get("axis", -1)) % len(data)],)
    return [data, c, c]


_get_op("LayerNorm").infer_args = _ln_infer


def _rms_infer(a, shapes):
    data = shapes[0]
    out = [data, (data[int(a.get("axis", -1)) % len(data)],)]
    if a.get("gated"):
        out.append(shapes[2] if shapes[2] is not None else data)
    return out


_get_op("RMSNorm").infer_args = _rms_infer


def _causal_conv_infer(a, shapes):
    c = shapes[0][-1]
    return [shapes[0], (c, int(a.kernel))] + ([(c,)] if a.get("bias") else [])


_get_op("_contrib_CausalConv1D").infer_args = _causal_conv_infer


def _in_infer(a, shapes):
    data = shapes[0]
    c = (data[1],)
    return [data, c, c]


_get_op("InstanceNorm").infer_args = _in_infer


def _emb_infer(a, shapes):
    return [shapes[0], (int(a.input_dim), int(a.output_dim))]


_get_op("Embedding").infer_args = _emb_infer


def _prelu_infer(a, shapes):
    data = shapes[0]
    if a.act_type == "prelu":
        return [data, (data[1],)]
    return [data]


_get_op("LeakyReLU").infer_args = _prelu_infer


def _label_like_batch(a, shapes):
    data = shapes[0]
    if a.get("multi_output"):
        lbl = (data[0],) + tuple(data[2:])
    else:
        lbl = (data[0],)
    return [data, shapes[1] if shapes[1] is not None else lbl]


_get_op("SoftmaxOutput").infer_args = _label_like_batch
_get_op("SVMOutput").infer_args = _label_like_batch


def _label_like_data(a, shapes):
    return [shapes[0], shapes[1] if shapes[1] is not None else shapes[0]]


for _n in ("LinearRegressionOutput", "LogisticRegressionOutput",
           "MAERegressionOutput"):
    _get_op(_n).infer_args = _label_like_data
