"""Plain float32 layers for the configurations' references.

Straightforward ``jax.numpy`` / ``jax.lax``: no kernels, no registry ops,
nothing imported from ``mxnet_tpu``.  A reference is a function
``forward(p, x, arch, train, dropout_masks)`` over these layers; ``p`` is a
:class:`Params`, which hands each layer the system's own parameter values in
the order the system lists them and refuses a name or a shape the layer does
not expect, so a reference cannot silently read the wrong tensor.  The same
walk, run on shapes alone, counts the multiply-accumulates a sample needs
(:func:`macs_per_sample`): the architecture is written down once.

References compute on the host's CPU device where jax has one (true
float32, a small program that compiles in seconds, and nothing of the
reference's ever sits in the chip's memory), else on the default device;
either way under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matrix multiplication otherwise runs in bfloat16 passes.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


class Params:
    """The system's parameters as ``(name, value)`` pairs in its own order.

    ``Params(None)`` is the abstract form: every ``take`` returns zeros of
    the expected shape (staged, never allocated, under ``jax.eval_shape``).
    ``macs`` accumulates multiply-accumulates per sample as layers run.
    """

    def __init__(self, named):
        self._named = None if named is None else list(named)
        self._next = 0
        self.macs = 0

    def take(self, suffix, shape):
        if self._named is None:
            return jnp.zeros(shape, jnp.float32)
        if self._next >= len(self._named):
            raise ValueError("reference wants %s%r after the system's last "
                             "parameter" % (suffix, tuple(shape)))
        name, value = self._named[self._next]
        self._next += 1
        if not name.endswith(suffix) or tuple(value.shape) != tuple(shape):
            raise ValueError(
                "reference expects a parameter *%s of shape %r here, the "
                "system's next one is %s %r"
                % (suffix, tuple(shape), name, tuple(value.shape)))
        return value

    def finish(self):
        if self._named is not None and self._next != len(self._named):
            raise ValueError("reference used %d of the system's %d parameters"
                             % (self._next, len(self._named)))


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def _channel_axis(layout):
    return 3 if layout == "NHWC" else 1


def conv2d(p, x, channels, kernel, stride=1, pad=0, bias=False,
           layout="NCHW"):
    """Cross-correlation; weight ``OHWI`` for NHWC, ``OIHW`` for NCHW (the
    system's storage)."""
    kh, kw = _pair(kernel)
    cin = x.shape[_channel_axis(layout)]
    if layout == "NHWC":
        w = p.take("weight", (channels, kh, kw, cin))
        spec = ("NHWC", "OHWI", "NHWC")
    else:
        w = p.take("weight", (channels, cin, kh, kw))
        spec = ("NCHW", "OIHW", "NCHW")
    ph, pw = _pair(pad)
    out = lax.conv_general_dilated(
        x, w, window_strides=_pair(stride), padding=[(ph, ph), (pw, pw)],
        dimension_numbers=spec)
    p.macs += (out.size // out.shape[0]) * kh * kw * cin
    if bias:
        b = p.take("bias", (channels,))
        out = out + (b if layout == "NHWC" else b[None, :, None, None])
    return out


def batch_norm(p, x, train, eps=1e-5, layout="NCHW"):
    """Batch statistics (biased variance) when training, the running ones
    otherwise.  The running statistics' update is not part of the loss."""
    axis = _channel_axis(layout)
    c = x.shape[axis]
    gamma = p.take("gamma", (c,))
    beta = p.take("beta", (c,))
    mean = p.take("running_mean", (c,))
    var = p.take("running_var", (c,))
    shape = [1] * x.ndim
    shape[axis] = c
    if train:
        red = tuple(i for i in range(x.ndim) if i != axis)
        mean = jnp.mean(x, axis=red)
        var = jnp.mean(jnp.square(x - mean.reshape(shape)), axis=red)
    inv = gamma / jnp.sqrt(var + eps)
    return (x - mean.reshape(shape)) * inv.reshape(shape) \
        + beta.reshape(shape)


def relu(x):
    return jnp.maximum(x, 0.0)


def _window(kernel, stride, pad, layout):
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    ph, pw = _pair(pad)
    if layout == "NHWC":
        return ((1, kh, kw, 1), (1, sh, sw, 1),
                [(0, 0), (ph, ph), (pw, pw), (0, 0)])
    return ((1, 1, kh, kw), (1, 1, sh, sw),
            [(0, 0), (0, 0), (ph, ph), (pw, pw)])


def max_pool(x, kernel, stride, pad=0, layout="NCHW"):
    """Floor ("valid") output size; padding never wins a maximum."""
    window, strides, padding = _window(kernel, stride, pad, layout)
    return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, padding)


def avg_pool(x, kernel, stride, pad=0, layout="NCHW"):
    """Floor output size; padded zeros count in the mean, as in the
    reference framework's default (``count_include_pad``)."""
    window, strides, padding = _window(kernel, stride, pad, layout)
    kh, kw = _pair(kernel)
    return lax.reduce_window(x, 0.0, lax.add, window, strides, padding) \
        / float(kh * kw)


def global_avg_pool(x, layout="NCHW"):
    return jnp.mean(x, axis=(1, 2) if layout == "NHWC" else (2, 3))


def dense(p, x, units):
    w = p.take("weight", (units, x.shape[1]))
    b = p.take("bias", (units,))
    p.macs += units * x.shape[1]
    return x @ w.T + b


def softmax_cross_entropy(logits, labels):
    """Mean over the batch of ``-log softmax(logits)[label]``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                 axis=1)
    return -jnp.mean(picked)


def macs_per_sample(forward, arch, input_shape):
    """Multiply-accumulates of one forward pass of one sample, counted from
    shapes alone (convolutions and dense layers; nothing runs)."""
    p = Params(None)
    jax.eval_shape(
        lambda x: forward(p, x, arch, False),
        jax.ShapeDtypeStruct((1,) + tuple(input_shape), jnp.float32))
    return p.macs


def train_flops_per_sample(forward, arch, input_shape):
    """Operations one training sample requires: the forward pass and, in
    the backward pass, one product for the input gradient and one for the
    weight gradient of every convolution and dense layer: 3 x 2 x MACs.
    Recomputation is not counted; neither are the elementwise layers."""
    return 6 * macs_per_sample(forward, arch, input_shape)


def outputs(forward, arch, variants, y, dropout_masks=()):
    """What the system is compared with, for each ``(named_params, x)`` of
    ``variants`` (the same names and shapes in each): inference logits on
    ``x``, and the training loss on ``(x, y)`` with its gradient for every
    parameter.  One program, whose only constants are the architecture's:
    weights, inputs, labels and masks are arguments, so that a later run
    with another seed finds it in the compile cache.

    -> [(logits, loss, {name: gradient})], float32."""
    names = [n for n, _ in variants[0][0]]

    def run(vals, x, train, masks):
        p = Params(zip(names, vals))
        logits = forward(p, x, arch, train, masks)
        p.finish()
        return logits

    @jax.jit
    def both(vals, x, labels, masks):
        loss, grads = jax.value_and_grad(
            lambda v: softmax_cross_entropy(run(v, x, True, masks), labels))(
            vals)
        return run(vals, x, False, masks), loss, grads

    try:
        where = jax.devices("cpu")[0]
    except RuntimeError:        # jax was started without its CPU backend
        where = jax.devices()[0]

    def put(value, dtype):
        return jax.device_put(np.asarray(value, dtype), where)

    labels = put(y, np.int32)
    masks = [put(m, np.float32) for m in dropout_masks]
    out = []
    with jax.default_matmul_precision("highest"):
        for named_params, x in variants:
            vals = [put(v, np.float32) for _, v in named_params]
            logits, loss, grads = both(vals, put(x, np.float32), labels,
                                       masks)
            out.append((logits, loss, dict(zip(names, grads))))
    return out
