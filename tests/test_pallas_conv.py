"""Pallas conv backward-filter kernel (ops/pallas_conv.py): numerical
equivalence against XLA's own lowering, shape gating, and the
MXTPU_PALLAS_CONV_DW integration through the Gluon training step.

The kernel has never been timed (tools/bench_conv_dw.py is the tool;
ROADMAP.md Speed #2); these tests pin CORRECTNESS on the CPU
interpreter so the kernel can never drift from the XLA oracle
unnoticed, and `python chip_smoke.py` compiles it with Mosaic.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_conv import conv_dw_nhwc, conv_dw_xla, supported

CASES = [
    # (N,H,W,I), kernel, pad, O — ResNet's stride-1 conv zoo, scaled down
    ((4, 8, 8, 16), (3, 3), (1, 1), 32),
    ((4, 8, 8, 16), (1, 1), (0, 0), 32),
    ((4, 7, 7, 8), (3, 3), (1, 1), 16),   # width off the sublane tile
    ((2, 8, 8, 8), (7, 7), (3, 3), 16),
]


@pytest.mark.parametrize("xs,k,p,o", CASES)
@pytest.mark.parametrize("form", ["pertap", "im2col"])
def test_dw_matches_xla_oracle(xs, k, p, o, form):
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    n, h, w, _i = xs
    oh = h + 2 * p[0] - k[0] + 1
    ow = w + 2 * p[1] - k[1] + 1
    x = jnp.asarray(rs.rand(*xs).astype(np.float32))
    dy = jnp.asarray(rs.rand(n, oh, ow, o).astype(np.float32))
    want = conv_dw_xla(x, dy, k, (1, 1), p)
    got = conv_dw_nhwc(x, dy, k, p, interpret=True, formulation=form)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_supported_gating():
    assert supported((4, 8, 8, 16), (4, 8, 8, 32), (3, 3), (1, 1), (1, 1),
                     (1, 1), 1)
    # groups, dilation, stem channels, and shape mismatches fall back
    assert not supported((4, 8, 8, 16), (4, 8, 8, 32), (3, 3), (1, 1),
                         (1, 1), (1, 1), 2)
    # so does any stride: Mosaic refuses strided loads of 16-bit data
    assert not supported((4, 9, 9, 16), (4, 5, 5, 32), (3, 3), (2, 2),
                         (1, 1), (1, 1), 1)
    assert not supported((4, 8, 8, 16), (4, 8, 8, 32), (3, 3), (1, 1),
                         (1, 1), (2, 2), 1)
    assert not supported((4, 224, 224, 3), (4, 112, 112, 64), (7, 7),
                         (2, 2), (3, 3), (1, 1), 1)
    assert not supported((4, 8, 8, 16), (4, 5, 5, 32), (3, 3), (1, 1),
                         (1, 1), (1, 1), 1)


def _train_one_step(monkeypatch, flag):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.gluon_step import GluonTrainStep
    from mxnet_tpu.parallel.mesh import create_mesh
    import mxnet_tpu.ops.nn as ops_nn

    monkeypatch.setenv("MXTPU_PALLAS_CONV_DW", "1" if flag else "0")
    ops_nn._nhwc_conv2d_pallas_dw.cache_clear()

    np.random.seed(3)
    mx.random.seed(3)
    import jax

    mesh = create_mesh({"dp": 1}, devices=jax.devices("cpu")[:1])
    net = nn.HybridSequential(prefix="pcnet_")
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1, layout="NHWC", in_channels=8))
        net.add(nn.Activation("relu"))
        net.add(nn.Conv2D(16, 1, layout="NHWC", in_channels=8))
        net.add(nn.GlobalAvgPool2D(layout="NHWC"))
        net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net(mx.nd.zeros((1, 6, 6, 8), ctx=mx.cpu()))
    step = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, lr=0.1)
    rs = np.random.RandomState(0)
    x = rs.rand(4, 6, 6, 8).astype(np.float32)
    y = rs.randint(0, 3, (4,)).astype(np.int32)
    x, y = step.put_batch(x, y)
    loss = float(np.asarray(step(x, y)))
    vals = [np.asarray(v) for v in step.train_vals]
    return loss, vals


def test_flagged_training_step_matches_default(monkeypatch):
    """One full train step with the Pallas dW path must produce the same
    loss and updated weights as XLA's lowering (fp32, CPU interpret)."""
    loss_off, vals_off = _train_one_step(monkeypatch, False)
    loss_on, vals_on = _train_one_step(monkeypatch, True)
    assert np.isclose(loss_on, loss_off, rtol=1e-5)
    for a, b in zip(vals_on, vals_off):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_block_images_bounds_ragged_row_groups():
    """Widths off the 8-sublane tile cap the image block by the number
    of (image, row) groups Mosaic must relayout (compile cost measured
    on the chip: 448 groups took the machine down); aligned widths are
    bounded by VMEM alone."""
    from mxnet_tpu.ops.pallas_conv import (_MAX_RAGGED_ROW_GROUPS,
                                           _block_images)

    assert _block_images(128, 1, 0, 14, 14) * 14 <= _MAX_RAGGED_ROW_GROUPS
    assert _block_images(128, 1, 0, 14, 14) == 2
    assert _block_images(128, 1, 0, 7, 7) == 4
    assert _block_images(128, 1, 0, 28, 28) == 1
    assert _block_images(128, 1, 0, 56, 56) == 128
