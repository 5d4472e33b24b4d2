"""Shared device-benchmark harness for the perf tools.

One implementation of "build a GluonTrainStep on the single-chip mesh
with a synthetic device-resident batch" so bench_train_matrix.py and
profile_step.py measure exactly the computation bench.py gates — a
methodology change lands in one place and every published number stays
comparable.
"""

import numpy as np

# inception_v3 ends in a fixed AvgPool2D(8): its canonical (and only
# valid) input is 299x299.  Everything else in the zoo trains at 224.
NETWORK_HW = {"inception_v3": 299}


def build_train_step(network, batch, hw=None, dtype="bfloat16",
                     layout="NHWC", classes=1000, lr=0.1, momentum=0.9,
                     wd=1e-4):
    """-> (step, x, y, layout, hw): a compiled-on-first-call
    GluonTrainStep over {'dp': 1} with a device-resident synthetic
    batch.  Falls back to NCHW for nets without a layout option."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.gluon_step import GluonTrainStep
    from mxnet_tpu.parallel.mesh import create_mesh

    hw = hw or NETWORK_HW.get(network, 224)
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    ctor = getattr(vision, network)
    try:
        net = ctor(classes=classes, layout=layout)
    except TypeError as e:
        # only the "no layout option" signature error falls back to
        # NCHW (alexnet etc.); any other TypeError from a
        # layout-supporting constructor must surface, not be silently
        # rebuilt and mislabeled as NCHW
        if "layout" not in str(e):
            raise
        net = ctor(classes=classes)
        layout = "NCHW"
    ctx = mx.current_context()
    # probe at FULL size: flatten-tailed nets (alexnet, vgg) resolve
    # their Dense in_units from the probe's spatial dims, and
    # inception_v3's fixed AvgPool2D(8) rejects small inputs — only
    # global-pool nets tolerate a small probe, so don't special-case
    probe = (1, 3, hw, hw) if layout == "NCHW" else (1, hw, hw, 3)
    with ctx:
        net.initialize(ctx=ctx)
        net(mx.nd.zeros(probe, ctx=ctx))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    step = GluonTrainStep(net, loss, mesh=mesh, lr=lr, momentum=momentum,
                          wd=wd, compute_dtype=dtype)
    rng = np.random.RandomState(0)
    shape = (batch, 3, hw, hw) if layout == "NCHW" else (batch, hw, hw, 3)
    x = rng.rand(*shape).astype(np.float32)
    y = rng.randint(0, classes, (batch,)).astype(np.int32)
    x, y = step.put_batch(x, y)
    return step, x, y, layout, hw
