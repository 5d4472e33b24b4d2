"""Model zoo tests (modeled on reference tests/python/unittest/
test_gluon_model_zoo.py) — small inputs, eager and hybridized."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.test_utils import assert_almost_equal


@pytest.mark.parametrize("name", ["resnet18_v1", "resnet18_v2",
                                  "mobilenet0.25", "mobilenetv2_0.25",
                                  "squeezenet1.1"])
def test_models_forward(name):
    net = vision.get_model(name, classes=10)
    net.initialize()
    x = mx.nd.array(np.random.rand(1, 3, 224, 224).astype("float32"))
    out = net(x)
    assert out.shape == (1, 10)


def test_resnet18_hybrid_parity():
    net = vision.get_model("resnet18_v1", classes=7)
    net.initialize()
    x = mx.nd.array(np.random.rand(2, 3, 64, 64).astype("float32"))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    assert_almost_equal(eager, hybrid, rtol=1e-3, atol=1e-4)


def test_get_model_unknown():
    with pytest.raises(ValueError):
        vision.get_model("not_a_model")


def test_resnet50_structure():
    net = vision.resnet50_v1(classes=13)
    net.initialize()
    x = mx.nd.array(np.random.rand(1, 3, 32, 32).astype("float32"))
    out = net(x)
    assert out.shape == (1, 13)
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    # ~25.6M params at 1000 classes; at 13 classes fc shrinks
    assert 23_000_000 < n_params < 26_000_000


def test_resnet_nhwc_matches_nchw():
    """layout="NHWC" (TPU-fast channel-last option) computes the same
    function as the reference-layout NCHW net once conv weights are
    relaid OIHW->OHWI."""
    for ctor in (vision.resnet18_v1, vision.resnet18_v2):
        a = ctor(classes=5)
        b = ctor(classes=5, layout="NHWC")
        a.initialize()
        b.initialize()
        x = mx.nd.array(np.random.rand(2, 3, 32, 32).astype("float32"))
        x_cl = mx.nd.array(x.asnumpy().transpose(0, 2, 3, 1))
        a(x)
        b(x_cl)  # resolve deferred shapes
        pa, pb = a.collect_params(), b.collect_params()
        for ka, kb in zip(sorted(pa.keys()), sorted(pb.keys())):
            w = pa[ka].data().asnumpy()
            tgt = tuple(pb[kb].data().shape)
            if w.ndim == 4 and w.shape != tgt:
                w = w.transpose(0, 2, 3, 1)  # OIHW -> OHWI
            assert w.shape == tgt, (ka, kb, w.shape, tgt)
            pb[kb].set_data(mx.nd.array(w))
        assert_almost_equal(a(x).asnumpy(), b(x_cl).asnumpy(),
                            rtol=1e-3, atol=1e-4)


def test_pooling_layer_honors_nhwc():
    """Gluon pooling layers pass layout through to the op (a dropped
    layout here silently pools the wrong axes)."""
    from mxnet_tpu.gluon import nn

    x = np.random.rand(2, 8, 8, 4).astype("float32")
    pool = nn.MaxPool2D(2, 2, layout="NHWC")
    pool.initialize()
    out = pool(mx.nd.array(x))
    assert out.shape == (2, 4, 4, 4)
    ref = x.reshape(2, 4, 2, 4, 2, 4).max(axis=(2, 4))
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-6, atol=1e-6)
    gap = nn.GlobalAvgPool2D(layout="NHWC")
    gap.initialize()
    out = gap(mx.nd.array(x))
    assert out.shape == (2, 1, 1, 4)
    assert_almost_equal(out.asnumpy().reshape(2, 4), x.mean(axis=(1, 2)),
                        rtol=1e-5, atol=1e-6)


def test_inception_bn_forward_and_param_count():
    """Inception-BN (r4: the sixth network of the reference's published
    perf matrix, symbols/inception-bn.py).  11.3M params at 1000
    classes pins the topology constants."""
    net = vision.get_model("inception_bn", classes=10)
    net.initialize()
    out = net(mx.nd.array(np.random.rand(1, 3, 224, 224).astype("float32")))
    assert out.shape == (1, 10)
    full = vision.inception_bn()
    full.initialize()
    full(mx.nd.zeros((1, 3, 224, 224)))
    n = sum(int(np.prod(p.shape))
            for p in full.collect_params().values())
    assert abs(n - 11_315_272) < 1000, n


def test_inception_bn_nhwc_matches_nchw():
    a = vision.inception_bn(classes=5)
    b = vision.inception_bn(classes=5, layout="NHWC")
    a.initialize()
    b.initialize()
    x = mx.nd.array(np.random.rand(1, 3, 224, 224).astype("float32"))
    x_cl = mx.nd.array(x.asnumpy().transpose(0, 2, 3, 1))
    a(x)
    b(x_cl)
    pa, pb = a.collect_params(), b.collect_params()
    for ka, kb in zip(sorted(pa.keys()), sorted(pb.keys())):
        w = pa[ka].data().asnumpy()
        tgt = tuple(pb[kb].data().shape)
        if w.ndim == 4 and w.shape != tgt:
            w = w.transpose(0, 2, 3, 1)  # OIHW -> OHWI
        assert w.shape == tgt, (ka, kb, w.shape, tgt)
        pb[kb].set_data(mx.nd.array(w))
    assert_almost_equal(a(x).asnumpy(), b(x_cl).asnumpy(),
                        rtol=1e-3, atol=1e-4)


def test_resnet_s2d_stem_matches_standard():
    """stem_s2d=True (space-to-depth stem, TPU MXU option) computes
    the SAME function as the 7x7/s2 conv with identical param shapes,
    so checkpoints swap between stems freely.  Measured perf-neutral
    at model scale on v5e (r4, other toolchain, not re-measured: the
    stem dW is byte-bound, not lane-bound) — kept as the standard TPU option with the
    equivalence pinned here."""
    rng = np.random.RandomState(0)
    a = vision.resnet18_v1(classes=5, layout="NHWC")
    b = vision.resnet18_v1(classes=5, layout="NHWC", stem_s2d=True)
    a.initialize()
    b.initialize()
    x = mx.nd.array(rng.rand(1, 224, 224, 3).astype(np.float32))
    a(x)
    b(x)
    pa, pb = a.collect_params(), b.collect_params()
    for na, nb in zip(sorted(pa.keys()), sorted(pb.keys())):
        w = pa[na].data()
        assert tuple(w.shape) == tuple(pb[nb].data().shape), (na, nb)
        pb[nb].set_data(w)
    assert_almost_equal(a(x).asnumpy(), b(x).asnumpy(), rtol=1e-3,
                        atol=1e-4)


def test_resnet_s2d_stem_validates():
    import pytest as _pytest

    with _pytest.raises(ValueError, match="NHWC"):
        vision.resnet18_v1(classes=5, stem_s2d=True)  # NCHW default
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BasicBlockV1,
                                                         ResNetV1)
    with _pytest.raises(ValueError, match="thumbnail"):
        ResNetV1(BasicBlockV1, [2, 2], [16, 16, 32], classes=5,
                 thumbnail=True, layout="NHWC", stem_s2d=True)
    net = vision.resnet18_v1(classes=5, layout="NHWC", stem_s2d=True)
    net.initialize()
    with _pytest.raises(ValueError, match="even"):
        net(mx.nd.zeros((1, 223, 223, 3)))
