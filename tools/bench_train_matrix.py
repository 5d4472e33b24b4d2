#!/usr/bin/env python
"""Training throughput for any model-zoo network, device-only.

Fills the training half of the reference's published perf matrix
(docs/faq/perf.md:219-236: V100 training img/s for Alexnet,
Inception-v3, ResNet-50 via train_imagenet.py).  The whole train step
(fwd+bwd+SGD momentum+BN stats) is GluonTrainStep's one jitted
computation; ``--chain`` steps are chained into a single dispatch
(GluonTrainStep.make_chained), so host dispatch is paid once per chain
— the same methodology as bench.py's device metric.

Image size is chosen per network (tools/bench_common.NETWORK_HW:
inception_v3 trains at its canonical 299, everything else at 224), so
one invocation reproduces the whole published matrix; --image-shape
overrides it for every network when set.

Usage: python tools/bench_train_matrix.py [--networks a,b,c]
       [--batches 64,128] [--chain 30] [--image-shape 3,299,299]
       [--dtype bfloat16] [--layout NHWC]
Prints one JSON line per (network, batch).
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from bench_common import build_train_step  # noqa: E402


def measure(network, batch, chain, hw, dtype, layout, reps=3):
    from mxnet_tpu import random as mxrandom

    step, x, y, layout, hw = build_train_step(
        network, batch, hw=hw, dtype=dtype, layout=layout)
    chained = step.make_chained(chain)
    key = mxrandom.next_key()
    chained(x, y, key).block_until_ready()  # compile + warm
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        chained(x, y, key).block_until_ready()
        rates.append(chain * batch / (time.perf_counter() - t0))
    return statistics.median(rates), layout, hw


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--networks", default="alexnet,inception_v3,resnet50_v1")
    p.add_argument("--batches", default="64,128")
    p.add_argument("--chain", type=int, default=30)
    p.add_argument("--image-shape", default=None,
                   help="override the per-network default (e.g. 3,299,299)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--layout", default="NHWC")
    args = p.parse_args(argv)
    hw = int(args.image_shape.split(",")[-1]) if args.image_shape else None
    results = []
    for net in args.networks.split(","):
        for bs in (int(b) for b in args.batches.split(",")):
            img_s, layout, used_hw = measure(net, bs, args.chain, hw,
                                             args.dtype, args.layout)
            rec = {"metric": "%s training img/s (bs=%d, %dx%d, %s, %s, "
                             "device-only %d-chain)"
                             % (net, bs, used_hw, used_hw, args.dtype,
                                layout, args.chain),
                   "value": round(img_s, 1), "unit": "img/s"}
            print(json.dumps(rec))
            results.append(rec)
    return results


if __name__ == "__main__":
    main()
