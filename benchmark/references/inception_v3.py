"""Inception v3 (Szegedy et al. 2015, arXiv:1512.00567), plain reference.

``arch``: ``classes``, ``layout`` ("NCHW": the served model has no other),
``dropout``.  The widths are the paper's and are written out below, module
by module, as the reference framework's Gluon ``inception_v3`` has them:
every convolution is conv (no bias) -> batch norm (eps 1e-3) -> relu; no
auxiliary classifier; 3x3 average pools count their padding; the 8x8
average pool before the classifier fixes the input at 299x299; dropout
sits between that pool and the dense layer.

``dropout_masks``: one array per dropout layer in call order, already
scaled (0 or 1/keep), used when training; the harness takes them from the
system's own dropout so both sides drop the same units.
"""

import jax.numpy as jnp

import plain_layers
from plain_layers import (avg_pool, batch_norm, conv2d, dense, max_pool,
                          relu, train_flops_per_sample)

LAYOUT = "NCHW"


def _conv(p, x, train, channels, kernel, stride=1, pad=0):
    x = conv2d(p, x, channels, kernel, stride, pad, layout=LAYOUT)
    return relu(batch_norm(p, x, train, eps=1e-3, layout=LAYOUT))


def _chain(p, x, train, *settings):
    for s in settings:
        x = _conv(p, x, train, *s)
    return x


def _cat(*xs):
    return jnp.concatenate(xs, axis=1)


def _a(p, x, train, pool_features):
    return _cat(
        _chain(p, x, train, (64, 1)),
        _chain(p, x, train, (48, 1), (64, 5, 1, 2)),
        _chain(p, x, train, (64, 1), (96, 3, 1, 1), (96, 3, 1, 1)),
        _chain(p, avg_pool(x, 3, 1, 1), train, (pool_features, 1)))


def _b(p, x, train):
    return _cat(
        _chain(p, x, train, (384, 3, 2)),
        _chain(p, x, train, (64, 1), (96, 3, 1, 1), (96, 3, 2)),
        max_pool(x, 3, 2))


def _c(p, x, train, c7):
    return _cat(
        _chain(p, x, train, (192, 1)),
        _chain(p, x, train, (c7, 1), (c7, (1, 7), 1, (0, 3)),
               (192, (7, 1), 1, (3, 0))),
        _chain(p, x, train, (c7, 1), (c7, (7, 1), 1, (3, 0)),
               (c7, (1, 7), 1, (0, 3)), (c7, (7, 1), 1, (3, 0)),
               (192, (1, 7), 1, (0, 3))),
        _chain(p, avg_pool(x, 3, 1, 1), train, (192, 1)))


def _d(p, x, train):
    return _cat(
        _chain(p, x, train, (192, 1), (320, 3, 2)),
        _chain(p, x, train, (192, 1), (192, (1, 7), 1, (0, 3)),
               (192, (7, 1), 1, (3, 0)), (192, 3, 2)),
        max_pool(x, 3, 2))


def _split(p, y, train):
    return _cat(_chain(p, y, train, (384, (1, 3), 1, (0, 1))),
                _chain(p, y, train, (384, (3, 1), 1, (1, 0))))


def _e(p, x, train):
    return _cat(
        _chain(p, x, train, (320, 1)),
        _split(p, _chain(p, x, train, (384, 1)), train),
        _split(p, _chain(p, x, train, (448, 1), (384, 3, 1, 1)), train),
        _chain(p, avg_pool(x, 3, 1, 1), train, (192, 1)))


def forward(p, x, arch, train, dropout_masks=()):
    x = _chain(p, x, train, (32, 3, 2), (32, 3), (64, 3, 1, 1))
    x = max_pool(x, 3, 2)
    x = _chain(p, x, train, (80, 1), (192, 3))
    x = max_pool(x, 3, 2)
    for pool_features in (32, 64, 64):
        x = _a(p, x, train, pool_features)
    x = _b(p, x, train)
    for c7 in (128, 160, 160, 192):
        x = _c(p, x, train, c7)
    x = _d(p, x, train)
    x = _e(p, x, train)
    x = _e(p, x, train)
    x = avg_pool(x, 8, 8)
    if train and arch["dropout"] > 0:
        x = x * dropout_masks[0]
    return dense(p, x.reshape(x.shape[0], -1), arch["classes"])


def dropout_shapes(arch, batch):
    return [(batch, 2048, 1, 1)] if arch["dropout"] > 0 else []


def outputs(arch, variants, y, dropout_masks=()):
    """[(inference logits, training loss, {name: gradient})] for each
    ``(named_params, x)`` of ``variants``, on the system's own parameter
    values: what the system is compared with."""
    return plain_layers.outputs(forward, arch, variants, y, dropout_masks)


def flops_per_sample(arch, input_shape):
    return train_flops_per_sample(forward, arch, input_shape)
