#!/usr/bin/env python
"""Data-parallel ResNet training over a device mesh (reference:
example/distributed_training — Gluon ResNet with kvstore/horovod;
BASELINE.json config 5: kvstore='nccl' -> 'tpu').

TPU-native shape: ONE jitted SPMD train step over a jax.sharding.Mesh —
the batch is sharded over the 'dp' axis, GSPMD inserts the gradient
all-reduce over ICI, and the optimizer update runs in-graph (the analog
of the reference's push/pull + server-side optimizer, SURVEY §3.4).

Run single-host multi-device as-is (all local devices), or test without
TPUs: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import argparse
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh


def main(argv=None):
    import jax

    parser = argparse.ArgumentParser(description="data-parallel resnet")
    parser.add_argument("--network", type=str, default="resnet50_v1")
    parser.add_argument("--batch-size", type=int, default=128,
                        help="GLOBAL batch (split across the dp mesh)")
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--dtype", type=str, default="bfloat16")
    parser.add_argument("--num-devices", type=int, default=0,
                        help="0 = all devices")
    args = parser.parse_args(argv)

    devices = jax.devices()
    if args.num_devices:
        devices = devices[:args.num_devices]
    n = len(devices)
    assert args.batch_size % n == 0, "global batch must divide the mesh"
    mesh = create_mesh({"dp": n}, devices=devices)
    print("mesh: %d devices (%s)" % (n, devices[0].platform))

    shape = tuple(int(x) for x in args.image_shape.split(","))
    net = getattr(vision, args.network)(classes=args.num_classes)
    ctx = mx.current_context()
    with ctx:
        net.initialize(ctx=ctx)
        net(mx.nd.zeros((1,) + shape, ctx=ctx))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    step = GluonTrainStep(net, loss, mesh=mesh, lr=args.lr, momentum=0.9,
                          wd=1e-4,
                          compute_dtype=None if args.dtype == "float32"
                          else args.dtype)

    rng = np.random.RandomState(0)
    x = rng.rand(args.batch_size, *shape).astype(np.float32)
    y = rng.randint(0, args.num_classes, (args.batch_size,)).astype(np.int32)
    x, y = step.put_batch(x, y)

    l = None
    for _ in range(3):  # compile + warmup
        l = step(x, y)
    first = float(np.asarray(l))

    t0 = time.perf_counter()
    for _ in range(args.steps):
        l = step(x, y)
    last = float(np.asarray(l))
    dt = time.perf_counter() - t0
    ips = args.steps * args.batch_size / dt
    print("loss %.4f -> %.4f | %.1f img/s global (%.1f per device)"
          % (first, last, ips, ips / n))
    # memorizing a fixed batch: loss must drop if grads flow end-to-end
    assert last < first, (first, last)
    return ips


if __name__ == "__main__":
    main()
