#!/usr/bin/env python
"""Inference throughput across the model zoo (reference:
example/image-classification/benchmark_score.py — the script behind
docs/faq/perf.md's img/s tables).

Per (network, batch) it jits one forward and reports img/s.
"""

import argparse
import time

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo import vision


def score(network, batch_size, image_shape=(3, 224, 224), num_batches=20,
          dtype="float32"):
    ctx = mx.current_context()
    net = getattr(vision, network)(classes=1000)
    # init + deferred-shape resolution on CPU: the eager per-op path
    # pays one device compile PER OP; only the staged whole-graph
    # computation should touch the accelerator
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((1,) + tuple(image_shape), ctx=mx.cpu()))
    net.collect_params().reset_ctx(ctx)
    net.hybridize()
    data = mx.nd.random.uniform(shape=(batch_size,) + tuple(image_shape),
                                ctx=ctx)
    if dtype in ("float16", "bfloat16"):
        net.cast(dtype)
        data = data.astype(dtype)
    net(data).wait_to_read()  # warmup (jit compile)
    tic = time.time()
    for _ in range(num_batches):
        out = net(data)
    out.wait_to_read()
    return num_batches * batch_size / (time.time() - tic)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--networks", type=str,
                        default="alexnet,vgg16,resnet50_v1,inception_v3")
    parser.add_argument("--batch-sizes", type=str, default="1,32,128")
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--num-batches", type=int, default=20)
    parser.add_argument("--dtype", type=str, default="float32")
    args = parser.parse_args(argv)
    shape = tuple(int(x) for x in args.image_shape.split(","))
    results = []
    for net in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            ips = score(net, bs, shape, args.num_batches, args.dtype)
            print("network: %s, batch: %d, image/sec: %.1f"
                  % (net, bs, ips))
            results.append((net, bs, ips))
    return results


if __name__ == "__main__":
    main()
