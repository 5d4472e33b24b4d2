"""ResNet v1 (He et al. 2015, arXiv:1512.03385), plain reference.

``arch``: ``block`` ("bottleneck" or "basic"), ``layers`` per stage,
``channels`` (stem, then each stage's output), ``classes``, ``layout``.

Departures from the paper, all the served model's own (the reference
framework's Gluon ``resnet50_v1``): the stride of a bottleneck sits on its
first 1x1 convolution, and the 1x1 convolutions inside a bottleneck carry a
bias (the 3x3 ones and the shortcut's do not).
"""

import plain_layers
from plain_layers import (batch_norm, conv2d, dense, global_avg_pool,
                          max_pool, relu, train_flops_per_sample)


def _bottleneck(p, x, channels, stride, downsample, train, layout):
    mid = channels // 4
    y = conv2d(p, x, mid, 1, stride, 0, bias=True, layout=layout)
    y = relu(batch_norm(p, y, train, layout=layout))
    y = conv2d(p, y, mid, 3, 1, 1, layout=layout)
    y = relu(batch_norm(p, y, train, layout=layout))
    y = conv2d(p, y, channels, 1, 1, 0, bias=True, layout=layout)
    y = batch_norm(p, y, train, layout=layout)
    if downsample:
        x = conv2d(p, x, channels, 1, stride, 0, layout=layout)
        x = batch_norm(p, x, train, layout=layout)
    return relu(y + x)


def _basic(p, x, channels, stride, downsample, train, layout):
    y = conv2d(p, x, channels, 3, stride, 1, layout=layout)
    y = relu(batch_norm(p, y, train, layout=layout))
    y = conv2d(p, y, channels, 3, 1, 1, layout=layout)
    y = batch_norm(p, y, train, layout=layout)
    if downsample:
        x = conv2d(p, x, channels, 1, stride, 0, layout=layout)
        x = batch_norm(p, x, train, layout=layout)
    return relu(y + x)


def forward(p, x, arch, train, dropout_masks=()):
    layout = arch["layout"]
    block = {"bottleneck": _bottleneck, "basic": _basic}[arch["block"]]
    channels = arch["channels"]
    x = conv2d(p, x, channels[0], 7, 2, 3, layout=layout)
    x = relu(batch_norm(p, x, train, layout=layout))
    x = max_pool(x, 3, 2, 1, layout=layout)
    for stage, depth in enumerate(arch["layers"]):
        out = channels[stage + 1]
        for i in range(depth):
            stride = 2 if (i == 0 and stage > 0) else 1
            x = block(p, x, out, stride,
                      i == 0 and out != channels[stage], train, layout)
    x = global_avg_pool(x, layout=layout)
    return dense(p, x, arch["classes"])


def dropout_shapes(arch, batch):
    return []


def outputs(arch, variants, y, dropout_masks=()):
    """[(inference logits, training loss, {name: gradient})] for each
    ``(named_params, x)`` of ``variants``, on the system's own parameter
    values: what the system is compared with."""
    return plain_layers.outputs(forward, arch, variants, y, dropout_masks)


def flops_per_sample(arch, input_shape):
    return train_flops_per_sample(forward, arch, input_shape)
