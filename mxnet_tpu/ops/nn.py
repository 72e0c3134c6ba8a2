"""Neural-network layer ops.

Reference parity: src/operator/nn/ (convolution, fully_connected, batch_norm,
pooling, activation, softmax, dropout, layer_norm, lrn, upsampling ...) and
the cuDNN/MIOpen wrapper family.  On TPU the vendor-library role is played by
XLA itself: conv/matmul lower onto the MXU (lax.conv_general_dilated /
dot_general), normalizations and activations fuse into neighbouring HLO.
Spatial ops default to MXNet's native NC[DHW] layouts; conv/pool also accept
the channel-last layouts (NWC/NHWC/NDHWC) the reference reserves for its
tensor-core paths — on TPU channel-last is the MXU-friendly tiling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, is_float_dtype
from .registry import register


def _pair(v, n):
    if v is None or v == ():
        return (0,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _safe_acc(data, weight):
    """fp16 safe accumulation: fp16 partial sums overflow at ~65504, so
    matmul/conv inputs are upcast to f32 (MXNET_SAFE_ACCUMULATION).  The
    upcast-inputs pattern (not preferred_element_type) keeps the transpose
    rules dtype-consistent under value_and_grad.  bf16 needs nothing: the
    MXU accumulates bf16 in f32 natively."""
    if np.dtype(data.dtype) == np.float16:
        return data.astype(jnp.float32), weight.astype(jnp.float32), True
    return data, weight, False


# ---------------------------------------------------------------------------
# dense / conv
# ---------------------------------------------------------------------------
@register("FullyConnected")
def fully_connected(data, weight, *bias, num_hidden=None, no_bias=False, flatten=True):
    """y = x W^T + b (reference: src/operator/nn/fully_connected-inl.h).

    Weight layout (num_hidden, input_dim), matching MXNet exactly.
    """
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    # No explicit preferred_element_type: an f32 output + astype breaks the
    # transpose rules under value_and_grad (the cotangent arrives f32
    # against bf16 saved operands — the BENCH_r02 failure mode).
    x, w, downcast = _safe_acc(x, weight)
    y = jax.lax.dot_general(
        x, w,
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
    )
    if downcast:
        y = y.astype(data.dtype)
    if not no_bias and bias:
        y = y + bias[0]
    return y


def _conv_dims(kernel):
    return len(kernel)


def _channels_last(layout):
    """True for MXNet channel-last layouts (NWC/NHWC/NDHWC).

    The reference supports these for cuDNN tensor-core paths
    (src/operator/nn/convolution.cu layout-specialized kernels); on TPU the
    channel-last path is the MXU-friendly tiling — XLA avoids the implicit
    layout conversions it inserts around NCHW convs.
    """
    return layout is not None and layout.endswith("C") and layout != "NC"


@register("Convolution")
def convolution(data, weight, *bias, kernel=(), stride=(), dilate=(), pad=(),
                num_filter=1, num_group=1, no_bias=False, workspace=1024,
                cudnn_tune=None, cudnn_off=False, layout=None):
    """N-d convolution (reference src/operator/nn/convolution-inl.h).

    Weight layout follows the data layout as in MXNet: OI<spatial> for
    NC-first (default), O<spatial>I for channel-last (NHWC family).
    cudnn_* attrs are accepted and ignored: algorithm selection is XLA's job.
    """
    n = _conv_dims(kernel)
    stride = _pair(stride or 1, n)
    dilate = _pair(dilate or 1, n)
    pad = _pair(pad, n)
    spatial = "DHW"[-n:]
    if _channels_last(layout):
        specs = ("N" + spatial + "C", "O" + spatial + "I", "N" + spatial + "C")
    else:
        specs = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    dn = jax.lax.conv_dimension_numbers(data.shape, weight.shape, specs)
    lhs, rhs, downcast = _safe_acc(data, weight)
    out = jax.lax.conv_general_dilated(
        lhs, rhs,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if downcast:
        out = out.astype(data.dtype)
    if not no_bias and bias:
        if _channels_last(layout):
            b = bias[0].reshape((1,) * (n + 1) + (-1,))
        else:
            b = bias[0].reshape((1, -1) + (1,) * n)
        out = out + b
    return out


@register("Deconvolution")
def deconvolution(data, weight, *bias, kernel=(), stride=(), dilate=(), pad=(),
                  adj=(), target_shape=(), num_filter=1, num_group=1,
                  no_bias=True, workspace=1024, cudnn_tune=None, cudnn_off=False,
                  layout=None):
    """Transposed convolution (reference src/operator/nn/deconvolution-inl.h).
    Weight layout (C_in, C_out/group, *kernel) as in MXNet."""
    n = _conv_dims(kernel)
    if target_shape:
        # MXNet derives pad from target_shape; silently ignoring it would
        # return a differently-padded tensor
        raise NotImplementedError(
            "Deconvolution target_shape is not supported; give pad/adj "
            "explicitly (out = (in-1)*s - 2p + d*(k-1) + 1 + adj)")
    stride = _pair(stride or 1, n)
    dilate = _pair(dilate or 1, n)
    pad = _pair(pad, n)
    adj = _pair(adj, n) if adj else (0,) * n
    spatial = "DHW"[-n:]
    lhs, rhs, downcast = _safe_acc(data, weight)
    # transposed conv = dilated conv with the SPATIALLY FLIPPED kernel
    # (conv_general_dilated correlates; the gradient-of-conv semantics
    # need the flip) ...
    if _channels_last(layout):
        sp_axes = tuple(range(1, 1 + n))  # weight (I, *k, O)
        specs = ("N" + spatial + "C", "I" + spatial + "O", "N" + spatial + "C")
    else:
        sp_axes = tuple(range(2, 2 + n))  # weight (I, O/g, *k)
        specs = ("NC" + spatial, "IO" + spatial, "NC" + spatial)
    rhs = jnp.flip(rhs, sp_axes)
    if num_group > 1:
        # ... and grouped weights regroup to what feature_group_count
        # expects: rhs (I/g, O_total, *k) where O-blocks line up with the
        # input-channel blocks.  (C_in, C_out/g, *k) ->
        # (g, C_in/g, C_out/g, *k) -> (C_in/g, g, C_out/g, *k) ->
        # (C_in/g, C_out, *k)
        if _channels_last(layout):
            # (C_in, *k, C_out/g): move I to front grouping similarly
            cin = rhs.shape[0]
            rhs = rhs.reshape((num_group, cin // num_group)
                              + rhs.shape[1:])
            rhs = jnp.moveaxis(rhs, 0, -2)  # (C_in/g, *k, g, C_out/g)
            rhs = rhs.reshape(rhs.shape[:-2]
                              + (num_group * rhs.shape[-1],))
        else:
            cin = rhs.shape[0]
            rhs = rhs.reshape((num_group, cin // num_group)
                              + rhs.shape[1:])
            rhs = jnp.swapaxes(rhs, 0, 1)  # (C_in/g, g, C_out/g, *k)
            rhs = rhs.reshape((cin // num_group,
                               num_group * rhs.shape[2]) + rhs.shape[3:])
    dn = jax.lax.conv_dimension_numbers(data.shape, rhs.shape, specs)
    # lhs_dilation implements the fractional stride; padding chosen so that
    # out = (in-1)*s - 2p + dilate*(k-1) + 1 + adj  (MXNet's formula)
    pads = []
    for i in range(n):
        k = dilate[i] * (kernel[i] - 1) + 1
        lo = k - 1 - pad[i]
        hi = k - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    out = jax.lax.conv_general_dilated(
        lhs, rhs,
        window_strides=(1,) * n,
        padding=pads,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if downcast:
        out = out.astype(data.dtype)
    if not no_bias and bias:
        if _channels_last(layout):
            out = out + bias[0].reshape((1,) * (n + 1) + (-1,))
        else:
            out = out + bias[0].reshape((1, -1) + (1,) * n)
    return out


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------
@register("Pooling")
def pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(),
            pad=(), pooling_convention="valid", count_include_pad=True,
            cudnn_off=False, p_value=2, layout=None):
    """Spatial pooling (reference src/operator/nn/pooling-inl.h).

    Channel-last layouts (NWC/NHWC/NDHWC) pool over the middle dims."""
    n = data.ndim - 2
    last = _channels_last(layout)
    sp0 = 1 if last else 2  # first spatial dim index
    if global_pool:
        kernel = data.shape[sp0:sp0 + n]
        stride = (1,) * n
        pad = (0,) * n
    kernel = _pair(kernel, n)
    stride = _pair(stride or 1, n)
    pad = _pair(pad, n)

    if last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad on the high side enough to cover the last window
        extra = []
        for i in range(n):
            in_i = data.shape[sp0 + i]
            out_i = int(np.ceil((in_i + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            need = (out_i - 1) * stride[i] + kernel[i] - in_i - pad[i]
            extra.append(max(need, pad[i]))
        sp_pads = tuple((pad[i], extra[i]) for i in range(n))
    else:
        sp_pads = tuple((p, p) for p in pad)
    if last:
        pads = ((0, 0),) + sp_pads + ((0, 0),)
    else:
        pads = ((0, 0), (0, 0)) + sp_pads

    # dtype-safe identities: bfloat16 (ml_dtypes) reports numpy kind 'V',
    # so go through jnp.issubdtype rather than dtype.kind (the BENCH_r02
    # crash).  The identities must be HOST numpy scalars — lax only
    # recognizes the max/add monoid (and thus differentiates the window
    # reduce) for literal init values, not traced jnp constants.
    dt = np.dtype(data.dtype)
    if pool_type == "max":
        if is_float_dtype(dt):
            init = np.array(-np.inf, dt)
        else:
            init = np.array(np.iinfo(dt).min, dt)
        return jax.lax.reduce_window(data, init, jax.lax.max, window, strides, pads)
    zero = np.zeros((), dt)
    if pool_type in ("avg", "sum"):
        summed = jax.lax.reduce_window(data, zero, jax.lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = float(np.prod(kernel))
            return summed / denom
        ones = jnp.ones_like(data)
        counts = jax.lax.reduce_window(ones, zero, jax.lax.add, window, strides, pads)
        return summed / counts
    if pool_type == "lp":
        powed = jax.lax.reduce_window(
            jnp.abs(data) ** p_value, zero, jax.lax.add, window, strides, pads
        )
        return powed ** (1.0 / p_value)
    raise MXNetError(f"pool_type {pool_type}")


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------
@register("Activation")
def activation(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise MXNetError(f"act_type {act_type}")


@register("LeakyReLU")
def leaky_relu(data, *gamma, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(data, approximate=True)
    if act_type == "prelu":
        g = gamma[0]
        shape = [1] * data.ndim
        if data.ndim > 1:
            shape[1] = g.size
        return jnp.where(data >= 0, data, g.reshape(shape) * data)
    if act_type == "rrelu":
        # deterministic mid-slope outside training (reference uses RNG in train)
        mid = (lower_bound + upper_bound) / 2
        return jnp.where(data >= 0, data, mid * data)
    raise MXNetError(f"act_type {act_type}")


# ---------------------------------------------------------------------------
# softmax family
# ---------------------------------------------------------------------------
@register("softmax")
def softmax(data, *length, axis=-1, temperature=None, dtype=None,
            use_length=False):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(data, axis=-1, temperature=None, dtype=None):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.softmax(-x, axis=axis)


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=logp.dtype)
    return jnp.sum(-logp * onehot)


_softmax_output_cache = {}


def _make_softmax_output(grad_scale, ignore_label, use_ignore, multi_output,
                         normalization, smooth_alpha):
    """Build a custom_vjp softmax-output closed over its (static) attrs.

    Legacy semantics: backward IGNORES the incoming cotangent and emits
    (p - onehot(label)) scaled — reference src/operator/nn/softmax_output-inl.h.
    """
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def fwd(data, label):
        return jax.nn.softmax(data, axis=axis)

    def f(data, label):
        out = jax.nn.softmax(data, axis=axis)
        return out, (out, label)

    def b(res, g):
        out, label = res
        k = out.shape[axis]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), k, dtype=out.dtype)
        if multi_output:
            onehot = jnp.moveaxis(onehot, -1, 1)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (k - 1) * (1 - onehot)
        grad = out - onehot
        if use_ignore:
            mask = (label != ignore_label).astype(out.dtype)
            if mask.ndim < grad.ndim:
                mask = jnp.expand_dims(mask, axis)
            grad = grad * mask
        scale = grad_scale
        if normalization == "valid" and use_ignore:
            valid = jnp.maximum(jnp.sum(label != ignore_label).astype(out.dtype), 1.0)
            scale = grad_scale / valid
        elif normalization == "batch":
            scale = grad_scale / out.shape[0]
        if is_float_dtype(label.dtype):  # incl. bfloat16 (numpy kind 'V')
            lab_ct = jnp.zeros_like(label)
        else:  # integer labels: jax requires a float0 cotangent
            lab_ct = np.zeros(label.shape, dtype=jax.dtypes.float0)
        return (grad * scale, lab_ct)

    fwd.defvjp(f, b)
    return fwd


@register("SoftmaxOutput")
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   use_ignore=False, multi_output=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    key = (grad_scale, ignore_label, use_ignore, multi_output, normalization,
           smooth_alpha)
    fn = _softmax_output_cache.get(key)
    if fn is None:
        fn = _make_softmax_output(*key)
        _softmax_output_cache[key] = fn
    return fn(data, label)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
@register("BatchNorm")
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, training=False):
    """BatchNorm (reference src/operator/nn/batch_norm-inl.h).

    Pure function: in training mode returns (out, batch_mean, batch_var) when
    output_mean_var so the caller (gluon.nn.BatchNorm) can update the moving
    aux states — the reference mutates aux in-op; we keep the op pure for XLA.
    `training` comes from autograd train-mode, threaded by the caller.
    """
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    axis = axis % data.ndim  # normalize negatives (axis=-1 for NHWC nets)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    red = tuple(i for i in range(data.ndim) if i != axis)
    if training and not use_global_stats:
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red)
        var = jnp.mean(jnp.square(x32 - mean.reshape(shape)), axis=red)
    else:
        mean, var = moving_mean, moving_var
    inv = jax.lax.rsqrt(var.reshape(shape) + eps).astype(data.dtype)
    out = (data - mean.reshape(shape).astype(data.dtype)) * inv * g.reshape(
        shape
    ).astype(data.dtype) + beta.reshape(shape).astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    if (not output_mean_var and axis in (-1, data.ndim - 1)
            and data.ndim >= 2):
        from . import pallas as _pk

        if _pk.enabled() and _pk.use_compiled():
            out = _pk.layer_norm(data.reshape(-1, data.shape[-1]), gamma,
                                 beta, eps=eps)
            return out.reshape(data.shape)
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=axis, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = ((x32 - mean) * inv).astype(data.dtype) * gamma.reshape(shape) + beta.reshape(shape)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register("_contrib_add_layer_norm")
def add_layer_norm(data, residual, gamma, beta, eps=1e-5):
    """Residual add + last-axis layer norm: LN(data + residual).  The
    pre-norm transformer block boundary as ONE op-class, so the
    fused_kernels pass can substitute the single-VMEM-pass Pallas kernel
    (ops/pallas/fused.add_layer_norm); this stock implementation is the
    bitwise-parity path when the pass is off."""
    x32 = data.astype(jnp.float32) + residual.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    out_dtype = jnp.result_type(data.dtype, residual.dtype)
    shape = [1] * data.ndim
    shape[-1] = data.shape[-1]
    return ((x32 - mean) * inv).astype(out_dtype) * gamma.reshape(
        shape) + beta.reshape(shape)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(data - mean), axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(shape) + beta.reshape(shape)


@register("GroupNorm")
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    n, c = data.shape[:2]
    spatial = data.shape[2:]
    x = data.reshape((n, num_groups, c // num_groups) + spatial)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=red, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.stack(
        [padded[:, i : i + data.shape[1]] for i in range(nsize)], axis=0
    ).sum(axis=0)
    return data / jnp.power(knorm + alpha / nsize * window, beta)


# ---------------------------------------------------------------------------
# dropout (RNG key threaded explicitly; see mxnet_tpu.random)
# ---------------------------------------------------------------------------
@register("Dropout", differentiable=True)
def dropout(data, key, p=0.5, mode="training", axes=(), training=False,
            cudnn_off=False):
    if not training or p <= 0.0:
        return data
    # `axes` = variational dropout: the mask is broadcast along those axes
    shape = [1 if i in axes else data.shape[i] for i in range(data.ndim)]
    keep = 1.0 - p
    # the barrier holds the mask as one value: without it XLA clones the
    # threefry generator into every fusion that reads the mask, the
    # backward's matmul fusions among them (PERF.md section 5)
    mask = jax.lax.optimization_barrier(
        jax.random.bernoulli(key, keep, tuple(shape)))
    return data * mask.astype(data.dtype) / keep


# ---------------------------------------------------------------------------
# resize
# ---------------------------------------------------------------------------
@register("UpSampling")
def upsampling(*inputs, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    data = inputs[0]
    n, c, h, w = data.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    else:
        out = jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")
    return out


@register("BilinearResize2D")
def bilinear_resize_2d(data, height=1, width=1, scale_height=None, scale_width=None,
                       mode="size"):
    n, c, h, w = data.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    return jax.image.resize(data, (n, c, height, width), method="bilinear")


# ---------------------------------------------------------------------------
# regression output heads (reference: src/operator/regression_output-inl.h)
# Legacy semantics like SoftmaxOutput: backward IGNORES the incoming
# cotangent and emits the analytic per-element residual * grad_scale.
# ---------------------------------------------------------------------------
def _make_regression_output(transform, residual, grad_scale):
    @jax.custom_vjp
    def fwd(data, label):
        return transform(data)

    def f(data, label):
        out = transform(data)
        return out, (out, label)

    def b(res, g):
        out, label = res
        return (residual(out, label) * grad_scale, jnp.zeros_like(label))

    fwd.defvjp(f, b)
    return fwd


_regression_cache = {}


def _regression_output(kind, data, label, grad_scale):
    key = (kind, grad_scale)
    fn = _regression_cache.get(key)
    if fn is None:
        transform = {"linear": lambda x: x,
                     "mae": lambda x: x,
                     "logistic": jax.nn.sigmoid}[kind]
        residual = {"linear": lambda o, l: o - l,
                    "mae": lambda o, l: jnp.sign(o - l),
                    "logistic": lambda o, l: o - l}[kind]
        fn = _make_regression_output(transform, residual, grad_scale)
        _regression_cache[key] = fn
    return fn(data, label.reshape(data.shape))


@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    return _regression_output("linear", data, label, grad_scale)


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    return _regression_output("mae", data, label, grad_scale)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    return _regression_output("logistic", data, label, grad_scale)


@register("MakeLoss")
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """Forward identity; backward seeds grad_scale as the gradient,
    normalized by batch size or by the count of elements above valid_thresh
    (reference: src/operator/make_loss-inl.h)."""
    @jax.custom_vjp
    def fwd(x):
        return x

    def f(x):
        return x, x

    def b(x, g):
        if normalization == "batch":
            denom = jnp.asarray(x.shape[0], x.dtype)
        elif normalization == "valid":
            denom = jnp.maximum(
                jnp.sum(x > valid_thresh).astype(x.dtype), 1.0)
        else:
            denom = jnp.asarray(1.0, x.dtype)
        return (jnp.full_like(g, grad_scale) / denom,)

    fwd.defvjp(f, b)
    return fwd(data)


# ---------------------------------------------------------------------------
# CTC loss (reference: src/operator/nn/ctc_loss.cc + gluon CTCLoss)
# ---------------------------------------------------------------------------
def _ctc_forward(logp, t_len, ext, s_valid, skip_ok):
    """Log-space CTC alpha recursion for ONE sequence.

    logp: (T, C) log-softmax scores; ext: (S,) extended label seq
    (blank-interleaved, S = 2*Lmax+1); s_valid: number of valid ext slots
    (2*label_len+1); skip_ok: (S,) whether the s-2 skip transition is legal.
    Returns the log-likelihood; differentiating this scan IS the standard
    CTC gradient.
    """
    NEG = -1e30
    S = ext.shape[0]
    alpha0 = jnp.full((S,), NEG)
    alpha0 = alpha0.at[0].set(logp[0, ext[0]])
    alpha0 = alpha0.at[1].set(jnp.where(s_valid > 1, logp[0, ext[1]], NEG))

    def step(alpha, lp_t):
        a1 = jnp.concatenate([jnp.full((1,), NEG), alpha[:-1]])
        a2 = jnp.concatenate([jnp.full((2,), NEG), alpha[:-2]])
        a2 = jnp.where(skip_ok, a2, NEG)
        m = jnp.maximum(alpha, jnp.maximum(a1, a2))
        tot = m + jnp.log(jnp.exp(alpha - m) + jnp.exp(a1 - m)
                          + jnp.exp(a2 - m))
        new = tot + lp_t[ext]
        return new, new

    _, alphas = jax.lax.scan(step, alpha0, logp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas])  # (T, S)
    final = alphas[t_len - 1]
    last = final[s_valid - 1]
    # empty label (s_valid == 1): only the all-blank path exists — do not
    # logsumexp final[0] with itself
    prev = jnp.where(s_valid > 1, final[jnp.maximum(s_valid - 2, 0)], NEG)
    m = jnp.maximum(last, prev)
    return m + jnp.log(jnp.exp(last - m) + jnp.exp(prev - m))


@register("ctc_loss")
def ctc_loss(data, label, *lengths, use_data_lengths=False,
             use_label_lengths=False, blank_label="first"):
    """Connectionist Temporal Classification loss.

    data: (T, N, C) activations (softmax applied internally, reference
    semantics); label: (N, Lmax) class ids, values < 0 are padding.
    Optional data_lengths/label_lengths NDArrays follow positionally when
    the corresponding use_* flag is set.  blank_label 'first' -> blank id
    0 (labels use 1..C-1); 'last' -> blank id C-1 (labels use 0..C-2).
    Returns per-example loss (N,).
    """
    T, N, C = data.shape
    blank = 0 if blank_label == "first" else C - 1
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)

    li = 0
    if use_data_lengths:
        t_lens = lengths[li].astype(jnp.int32)
        li += 1
    else:
        t_lens = jnp.full((N,), T, jnp.int32)
    lab = label.astype(jnp.int32)
    if use_label_lengths:
        l_lens = lengths[li].astype(jnp.int32)
    else:
        # padding convention (reference ctc_loss doc): blank_label='first'
        # reserves id 0 for blank AND uses 0 as label padding (real labels
        # are 1..C-1); 'last' uses -1 padding (labels 0..C-2)
        if blank_label == "first":
            l_lens = (lab > 0).sum(axis=1).astype(jnp.int32)
        else:
            l_lens = (lab >= 0).sum(axis=1).astype(jnp.int32)
    lab = jnp.maximum(lab, 0)

    Lmax = lab.shape[1]
    blanks = jnp.full((N, Lmax), blank, jnp.int32)
    ext = jnp.stack([blanks, lab], axis=2).reshape(N, 2 * Lmax)
    ext = jnp.concatenate([ext, blanks[:, :1]], axis=1)  # (N, 2Lmax+1)
    skip_ok = jnp.concatenate(
        [jnp.zeros((N, 2), bool),
         (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2])], axis=1)
    s_valid = 2 * l_lens + 1
    ll = jax.vmap(_ctc_forward, in_axes=(1, 0, 0, 0, 0))(
        logp, t_lens, ext, s_valid, skip_ok)
    return (-ll).astype(data.dtype)


# ---------------------------------------------------------------------------
# legacy spatial utility ops (reference src/operator/pad.cc, crop.cc,
# nn/im2col.h, nn/moments.cc, svm_output.cc)
# ---------------------------------------------------------------------------
@register("Pad")
def pad_op(data, mode="constant", pad_width=(), constant_value=0.0):
    """Pad (reference src/operator/pad.cc): pad_width is the flat
    (before, after) pair per axis, mxnet convention."""
    pw = [(int(pad_width[2 * i]), int(pad_width[2 * i + 1]))
          for i in range(data.ndim)]
    if mode == "constant":
        return jnp.pad(data, pw, constant_values=jnp.asarray(
            constant_value, data.dtype))
    return jnp.pad(data, pw, mode={"edge": "edge", "reflect": "reflect"}[mode])


@register("Crop")
def crop_op(*inputs, offset=(0, 0), h_w=(0, 0), num_args=1,
            center_crop=False):
    """Crop data (B,C,H,W) to h_w, or to the spatial size of a second
    reference input (reference src/operator/crop.cc)."""
    data = inputs[0]
    if num_args == 2 and len(inputs) > 1:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = int(h_w[0]), int(h_w[1])
    H, W = data.shape[2], data.shape[3]
    if center_crop:
        y0, x0 = (H - th) // 2, (W - tw) // 2
    else:
        y0, x0 = int(offset[0]), int(offset[1])
    return data[:, :, y0:y0 + th, x0:x0 + tw]


@register("moments")
def moments(data, axes=None, keepdims=False):
    """Mean and variance over axes (reference src/operator/nn/moments.cc)."""
    ax = tuple(int(a) for a in axes) if axes is not None else None
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=ax, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=ax, keepdims=keepdims)
    if not keepdims:
        mean = jnp.squeeze(mean, axis=ax)
    return mean.astype(data.dtype), var.astype(data.dtype)


_svm_output_cache = {}


def _make_svm_output(margin, reg_coef, use_linear):
    """Legacy output-op semantics like SoftmaxOutput: forward is identity,
    backward ignores the cotangent and emits the hinge-loss gradient
    (reference src/operator/svm_output-inl.h)."""

    @jax.custom_vjp
    def fwd(data, label):
        return data

    def f(data, label):
        return data, (data, label)

    def b(res, g):
        data, label = res
        x32 = data.astype(jnp.float32)
        k = data.shape[-1]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, k, dtype=jnp.float32)
        scores_y = jnp.sum(x32 * onehot, axis=-1, keepdims=True)
        viol = margin - scores_y + x32  # (..., k); at y: margin exactly
        if use_linear:  # L1-SVM: +-reg on violating classes
            mask = ((viol > 0) & (onehot == 0)).astype(jnp.float32)
            grad = reg_coef * mask
        else:  # L2-SVM: gradient proportional to the violation
            mask = ((viol > 0) & (onehot == 0)).astype(jnp.float32)
            grad = 2.0 * reg_coef * viol * mask
        grad = grad - onehot * jnp.sum(grad, axis=-1, keepdims=True)
        if is_float_dtype(label.dtype):
            lab_ct = jnp.zeros_like(label)
        else:
            lab_ct = np.zeros(label.shape, dtype=jax.dtypes.float0)
        return (grad.astype(data.dtype), lab_ct)

    fwd.defvjp(f, b)
    return fwd


@register("SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    key = (float(margin), float(regularization_coefficient), bool(use_linear))
    fn = _svm_output_cache.get(key)
    if fn is None:
        fn = _make_svm_output(*key)
        _svm_output_cache[key] = fn
    return fn(data, label)


@register("im2col")
def im2col(data, kernel=(), stride=(), dilate=(), pad=()):
    """Sliding-window unfold: (B,C,*sp) -> (B, C*prod(kernel), L)
    (reference src/operator/nn/im2col.h).  Feature order is channel-major
    then kernel-position, matching the reference."""
    n = len(kernel)
    stride = _pair(stride or 1, n)
    dilate = _pair(dilate or 1, n)
    pad = _pair(pad or 0, n)
    patches = jax.lax.conv_general_dilated_patches(
        data, filter_shape=tuple(int(k) for k in kernel),
        window_strides=tuple(int(s) for s in stride),
        padding=[(int(p), int(p)) for p in pad],
        rhs_dilation=tuple(int(d) for d in dilate))
    B = data.shape[0]
    return patches.reshape(B, patches.shape[1], -1)


@register("col2im")
def col2im(data, output_size=(), kernel=(), stride=(), dilate=(), pad=()):
    """Inverse of im2col: overlapping patches scatter-add back into
    (B, C, *output_size) (reference src/operator/nn/im2col.h col2im)."""
    n = len(kernel)
    stride = _pair(stride or 1, n)
    dilate = _pair(dilate or 1, n)
    pad = _pair(pad or 0, n)
    kernel = tuple(int(k) for k in kernel)
    out_sp = tuple(int(s) for s in output_size)
    B = data.shape[0]
    C = data.shape[1] // int(np.prod(kernel))
    padded_sp = tuple(out_sp[i] + 2 * int(pad[i]) for i in range(n))
    o_sp = tuple(
        (padded_sp[i] - (dilate[i] * (kernel[i] - 1) + 1)) // stride[i] + 1
        for i in range(n))
    cols = data.reshape((B, C) + kernel + o_sp)
    out = jnp.zeros((B, C) + padded_sp, jnp.float32)
    for kidx in np.ndindex(*kernel):
        sl = tuple(
            slice(kidx[i] * dilate[i],
                  kidx[i] * dilate[i] + o_sp[i] * stride[i], stride[i])
            for i in range(n))
        out = out.at[(slice(None), slice(None)) + sl].add(
            cols[(slice(None), slice(None)) + kidx].astype(jnp.float32))
    crop = tuple(slice(int(pad[i]), int(pad[i]) + out_sp[i])
                 for i in range(n))
    return out[(slice(None), slice(None)) + crop].astype(data.dtype)


@register("RNN")
def rnn_op(data, parameters, state, *state_cell, state_size=0, num_layers=1,
           bidirectional=False, mode="lstm", p=0.0, state_outputs=False,
           projection_size=None, use_sequence_length=False, lstm_state_clip_min=None,
           lstm_state_clip_max=None, lstm_state_clip_nan=False):
    """Fused RNN with the reference's packed flat parameter vector
    (reference src/operator/rnn.cc: weights layer-major i2h/h2h first,
    then all biases — the cuDNN/MIOpen packing).  Unpacks the vector and
    delegates to rnn_ops._fused_rnn.  Dropout between layers is
    inference-ignored here (the stateless op has no RNG key input);
    gluon.rnn layers use _fused_rnn with an explicit key for training.
    """
    from .rnn_ops import _fused_rnn, rnn_packed_layout

    if use_sequence_length:
        raise MXNetError("RNN: use_sequence_length is not supported; mask "
                         "outputs with SequenceMask instead")
    if (lstm_state_clip_min is not None or lstm_state_clip_max is not None
            or projection_size is not None):
        raise MXNetError("RNN: lstm_state_clip_* / projection_size are not "
                         "supported")

    H = int(state_size)
    dirs = 2 if bidirectional else 1
    flat = parameters
    entries, _ = rnn_packed_layout(mode, data.shape[2], H, num_layers,
                                   bidirectional)
    by_key = {(l, d, g, k): (off, shp) for l, d, g, k, off, shp in entries}

    def take(key):
        off, shp = by_key[key]
        return jax.lax.dynamic_slice_in_dim(
            flat, off, int(np.prod(shp))).reshape(shp)

    weights = []
    for layer in range(num_layers):
        for d in range(dirs):
            weights.extend([take((layer, d, "i2h", "weight")),
                            take((layer, d, "h2h", "weight")),
                            take((layer, d, "i2h", "bias")),
                            take((layer, d, "h2h", "bias"))])
    cell = state_cell[0] if mode == "lstm" else jnp.zeros_like(state)
    outs = _fused_rnn(data, None, state, cell, *weights, mode=mode,
                      state_size=H, num_layers=num_layers,
                      bidirectional=bidirectional, p=0.0, training=False)
    if not state_outputs:
        return outs[0] if isinstance(outs, tuple) else outs
    return outs
