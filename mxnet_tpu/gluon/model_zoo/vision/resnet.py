"""ResNet v1/v2 (reference: python/mxnet/gluon/model_zoo/vision/resnet.py).

By-spec reproduction notice: the network topology (block kinds, layer
counts, channel widths, stride/downsample placement) and the parameter
naming scheme are reproduced from the papers ("Deep Residual Learning
for Image Recognition" / "Identity Mappings in Deep Residual Networks")
and the reference's Gluon module, because both the architecture and the
param names ARE the compatibility contract — checkpoints written by the
reference must load here (tests/test_backwards_compat.py).  Structural
similarity to the reference file is therefore expected; the compute
underneath is this repo's own (lax convs on the MXU, XLA
conv+bn+relu fusion under ``hybridize()``).

TPU layout option (beyond reference parity): every constructor takes
``layout="NCHW"|"NHWC"``.  NCHW (default) keeps the reference's exact
param shapes (OIHW conv weights) for checkpoint interop; NHWC stores
OHWI weights and expects NHWC input — the channel-last layout maps
directly onto the MXU tiling with fewer HBM relayout bytes (measured
~7% faster on an isolated conv tower on another toolchain, not
re-measured; the benchmark's ResNet-50 cells run NHWC).

ResNet-50 v1 is the flagship benchmark model (BASELINE.md: ResNet-50
ImageNet img/s).
"""

from __future__ import annotations

from ...block import HybridBlock
from ...nn import (BatchNorm, Conv2D, Dense, GlobalAvgPool2D, HybridSequential,
                   MaxPool2D, Activation)

__all__ = ["ResNetV1", "ResNetV2", "BasicBlockV1", "BasicBlockV2",
           "BottleneckV1", "BottleneckV2", "resnet18_v1", "resnet34_v1",
           "resnet50_v1", "resnet101_v1", "resnet152_v1", "resnet18_v2",
           "resnet34_v2", "resnet50_v2", "resnet101_v2", "resnet152_v2",
           "get_resnet"]


def _conv3x3(channels, stride, in_channels, layout="NCHW"):
    return Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                  use_bias=False, in_channels=in_channels, layout=layout)


def _bn_axis(layout):
    return 3 if layout == "NHWC" else 1


class BasicBlockV1(HybridBlock):
    """18/34-layer residual block, v1 (post-activation)."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.body = HybridSequential(prefix="")
        self.body.add(_conv3x3(channels, stride, in_channels, layout))
        self.body.add(BatchNorm(axis=ax))
        self.body.add(Activation("relu"))
        self.body.add(_conv3x3(channels, 1, channels, layout))
        self.body.add(BatchNorm(axis=ax))
        if downsample:
            self.downsample = HybridSequential(prefix="")
            self.downsample.add(Conv2D(channels, kernel_size=1, strides=stride,
                                       use_bias=False, in_channels=in_channels,
                                       layout=layout))
            self.downsample.add(BatchNorm(axis=ax))
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return F.Activation(residual + x, act_type="relu")


class BottleneckV1(HybridBlock):
    """50/101/152-layer bottleneck block, v1."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.body = HybridSequential(prefix="")
        self.body.add(Conv2D(channels // 4, kernel_size=1, strides=stride,
                             layout=layout))
        self.body.add(BatchNorm(axis=ax))
        self.body.add(Activation("relu"))
        self.body.add(_conv3x3(channels // 4, 1, channels // 4, layout))
        self.body.add(BatchNorm(axis=ax))
        self.body.add(Activation("relu"))
        self.body.add(Conv2D(channels, kernel_size=1, strides=1, layout=layout))
        self.body.add(BatchNorm(axis=ax))
        if downsample:
            self.downsample = HybridSequential(prefix="")
            self.downsample.add(Conv2D(channels, kernel_size=1, strides=stride,
                                       use_bias=False, in_channels=in_channels,
                                       layout=layout))
            self.downsample.add(BatchNorm(axis=ax))
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return F.Activation(x + residual, act_type="relu")


class BasicBlockV2(HybridBlock):
    """18/34-layer residual block, v2 (pre-activation)."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.bn1 = BatchNorm(axis=ax)
        self.conv1 = _conv3x3(channels, stride, in_channels, layout)
        self.bn2 = BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels, 1, channels, layout)
        if downsample:
            self.downsample = Conv2D(channels, 1, stride, use_bias=False,
                                     in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        x = self.bn1(x)
        x = F.Activation(x, act_type="relu")
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x)
        x = F.Activation(x, act_type="relu")
        x = self.conv2(x)
        return x + residual


class BottleneckV2(HybridBlock):
    """50/101/152-layer bottleneck block, v2."""

    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.bn1 = BatchNorm(axis=ax)
        self.conv1 = Conv2D(channels // 4, kernel_size=1, strides=1,
                            use_bias=False, layout=layout)
        self.bn2 = BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels // 4, stride, channels // 4, layout)
        self.bn3 = BatchNorm(axis=ax)
        self.conv3 = Conv2D(channels, kernel_size=1, strides=1, use_bias=False,
                            layout=layout)
        if downsample:
            self.downsample = Conv2D(channels, 1, stride, use_bias=False,
                                     in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        x = self.bn1(x)
        x = F.Activation(x, act_type="relu")
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x)
        x = F.Activation(x, act_type="relu")
        x = self.conv2(x)
        x = self.bn3(x)
        x = F.Activation(x, act_type="relu")
        x = self.conv3(x)
        return x + residual


class _S2DStem(HybridBlock):
    """The 7×7/s2 stem conv, computed via space-to-depth (TPU MXU
    optimization, opt-in): the C=3 input leaves MXU lanes ~empty, so
    the stem's backward-filter runs at <10% MXU (r4 trace, other
    toolchain, not re-measured).
    Rearranging 2×2 input blocks into channels (C: 3→12, spatial /2)
    and the 7×7 kernel into an equivalent 4×4 one computes the SAME
    function with 4× the lane occupancy.

    Derivation: out(o) = Σ_k w[k]·x[2o+k], k∈[-3,3].  Front-pad the
    kernel to 8 so K' = k+4 ∈ [1,7]; then K' = 2t+dy factors exactly
    into a (4,2) reshape — tap t∈[0,4) of a stride-1 conv over the
    s2d grid, block row dy — with the s2d input padded (2,1).  The
    parameter keeps the reference (O,7,7,I) shape, so checkpoints
    swap between stems freely; the rearrangement happens in the
    traced forward (a few KB, fused away by XLA).
    """

    def __init__(self, channels, in_channels=3, **kwargs):
        super().__init__(**kwargs)
        self._channels = channels
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(channels, 7, 7, in_channels))

    def hybrid_forward(self, F, x, weight):
        if not hasattr(x, "shape"):  # symbolic trace: Symbol has no shape
            raise NotImplementedError(
                "stem_s2d runs on the hybrid/ndarray path (GluonTrainStep, "
                "hybridize); for export/SymbolBlock build the model with "
                "stem_s2d=False — the parameter shapes are identical, so "
                "the same checkpoint loads either way")
        c_in = self.weight.shape[3]
        # kernel: (O,7,7,I) -> front-pad spatial to 8 -> (O,4,2,4,2,I)
        # -> (O,4,4,2,2,I) -> (O,4,4,4I) with channel order (dy,dx,c)
        w = F.pad(weight, mode="constant",
                  pad_width=(0, 0, 1, 0, 1, 0, 0, 0))
        w = w.reshape((self._channels, 4, 2, 4, 2, c_in))
        w = w.transpose((0, 1, 3, 2, 4, 5))
        w = w.reshape((self._channels, 4, 4, 4 * c_in))
        # input: NHWC (B,H,W,C) -> (B,H/2,W/2,4C), same (dy,dx,c) order
        b, h, ww_, c = x.shape
        if h % 2 or ww_ % 2:
            raise ValueError(
                "stem_s2d needs even spatial dims, got %dx%d — pad the "
                "input or use the standard stem (same checkpoint loads)"
                % (h, ww_))
        xs = x.reshape((b, h // 2, 2, ww_ // 2, 2, c))
        xs = xs.transpose((0, 1, 3, 2, 4, 5))
        xs = xs.reshape((b, h // 2, ww_ // 2, 4 * c))
        # asymmetric (2,1) padding in s2d space = the original pad 3
        xs = F.pad(xs, mode="constant",
                   pad_width=(0, 0, 2, 1, 2, 1, 0, 0))
        return F.Convolution(xs, w, no_bias=True, kernel=(4, 4),
                             stride=(1, 1), pad=(0, 0),
                             num_filter=self._channels, layout="NHWC")


class ResNetV1(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW", stem_s2d=False, **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        self._layout = layout
        if stem_s2d and layout != "NHWC":
            raise ValueError("stem_s2d requires layout='NHWC'")
        if stem_s2d and thumbnail:
            raise ValueError("stem_s2d applies to the 7x7/s2 stem; "
                             "thumbnail models have a 3x3/s1 stem")
        with self.name_scope():
            self.features = HybridSequential(prefix="")
            if thumbnail:
                self.features.add(_conv3x3(channels[0], 1, 0, layout))
            else:
                if stem_s2d:
                    self.features.add(_S2DStem(channels[0],
                                               prefix="conv0_"))
                else:
                    self.features.add(Conv2D(channels[0], 7, 2, 3,
                                             use_bias=False,
                                             layout=layout))
                self.features.add(BatchNorm(axis=_bn_axis(layout)))
                self.features.add(Activation("relu"))
                self.features.add(MaxPool2D(3, 2, 1, layout=layout))
            for i, num_layer in enumerate(layers):
                stride = 1 if i == 0 else 2
                self.features.add(self._make_layer(
                    block, num_layer, channels[i + 1], stride, i + 1,
                    in_channels=channels[i], layout=layout))
            self.features.add(GlobalAvgPool2D(layout=layout))
            self.output = Dense(classes, in_units=channels[-1])

    def _make_layer(self, block, layers, channels, stride, stage_index,
                    in_channels=0, layout="NCHW"):
        layer = HybridSequential(prefix="stage%d_" % stage_index)
        with layer.name_scope():
            layer.add(block(channels, stride, channels != in_channels,
                            in_channels=in_channels, layout=layout, prefix=""))
            for _ in range(layers - 1):
                layer.add(block(channels, 1, False, in_channels=channels,
                                layout=layout, prefix=""))
        return layer

    def hybrid_forward(self, F, x):
        x = self.features(x)
        x = self.output(x)
        return x


class ResNetV2(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        self._layout = layout
        ax = _bn_axis(layout)
        with self.name_scope():
            self.features = HybridSequential(prefix="")
            self.features.add(BatchNorm(axis=ax, scale=False, center=False))
            if thumbnail:
                self.features.add(_conv3x3(channels[0], 1, 0, layout))
            else:
                self.features.add(Conv2D(channels[0], 7, 2, 3, use_bias=False,
                                         layout=layout))
                self.features.add(BatchNorm(axis=ax))
                self.features.add(Activation("relu"))
                self.features.add(MaxPool2D(3, 2, 1, layout=layout))
            in_channels = channels[0]
            for i, num_layer in enumerate(layers):
                stride = 1 if i == 0 else 2
                self.features.add(self._make_layer(
                    block, num_layer, channels[i + 1], stride, i + 1,
                    in_channels=in_channels, layout=layout))
                in_channels = channels[i + 1]
            self.features.add(BatchNorm(axis=ax))
            self.features.add(Activation("relu"))
            self.features.add(GlobalAvgPool2D(layout=layout))
            self.output = Dense(classes, in_units=in_channels)

    _make_layer = ResNetV1._make_layer

    def hybrid_forward(self, F, x):
        x = self.features(x)
        x = self.output(x)
        return x


resnet_spec = {
    18: ("basic_block", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
    34: ("basic_block", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
    50: ("bottle_neck", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
    101: ("bottle_neck", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
    152: ("bottle_neck", [3, 8, 36, 3], [64, 256, 512, 1024, 2048]),
}
resnet_net_versions = [ResNetV1, ResNetV2]
resnet_block_versions = [
    {"basic_block": BasicBlockV1, "bottle_neck": BottleneckV1},
    {"basic_block": BasicBlockV2, "bottle_neck": BottleneckV2},
]


def get_resnet(version, num_layers, pretrained=False, ctx=None, root=None,
               **kwargs):
    assert num_layers in resnet_spec
    assert version in (1, 2)
    block_type, layers, channels = resnet_spec[num_layers]
    resnet_class = resnet_net_versions[version - 1]
    block_class = resnet_block_versions[version - 1][block_type]
    net = resnet_class(block_class, layers, channels, **kwargs)
    if pretrained:
        raise RuntimeError("pretrained weights unavailable: no network egress")
    return net


def resnet18_v1(**kwargs):
    return get_resnet(1, 18, **kwargs)


def resnet34_v1(**kwargs):
    return get_resnet(1, 34, **kwargs)


def resnet50_v1(**kwargs):
    return get_resnet(1, 50, **kwargs)


def resnet101_v1(**kwargs):
    return get_resnet(1, 101, **kwargs)


def resnet152_v1(**kwargs):
    return get_resnet(1, 152, **kwargs)


def resnet18_v2(**kwargs):
    return get_resnet(2, 18, **kwargs)


def resnet34_v2(**kwargs):
    return get_resnet(2, 34, **kwargs)


def resnet50_v2(**kwargs):
    return get_resnet(2, 50, **kwargs)


def resnet101_v2(**kwargs):
    return get_resnet(2, 101, **kwargs)


def resnet152_v2(**kwargs):
    return get_resnet(2, 152, **kwargs)
