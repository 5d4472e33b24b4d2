#!/usr/bin/env python
"""Pure device latency vs host-dispatched latency (VERDICT r1 weak 3).

Separates the per-call host overhead from true device time by
running K chained forwards inside ONE jitted computation
(``lax.fori_loop``; the output feeds back into the next input so XLA
cannot elide iterations), then comparing with the one-call-per-step
host loop.

Usage: python tools/bench_device_latency.py [--network resnet50_v1]
       [--batch 1] [--inner 50] [--dtype float32]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet50_v1")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--inner", default="50",
                   help="chain depth, or comma list for a least-squares fit")
    p.add_argument("--outer", type=int, default=20)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--dtype", default="float32")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import _StagingScope
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.parameter import param_override
    from mxnet_tpu.ndarray import NDArray

    net = getattr(vision, args.network)()
    ctx = mx.current_context()
    with ctx:
        net.initialize(ctx=ctx)
        if args.dtype != "float32":
            net.cast(args.dtype)
        net(mx.nd.zeros((1, 3, 224, 224), ctx=ctx,
                        dtype=args.dtype))
    params = list(net.collect_params().values())
    pvals = tuple(p.data().data_jax for p in params)

    def forward(pvals, x):
        override = {p: NDArray(v) for p, v in zip(params, pvals)}
        with param_override(override), _StagingScope():
            out = net(NDArray(x))
        return out.data_jax

    x = jnp.asarray(np.random.RandomState(0)
                    .rand(args.batch, 3, 224, 224)
                    .astype(args.dtype if args.dtype != "float32"
                            else np.float32))

    def _wait(arr):
        arr.block_until_ready()

    # --- host-dispatched: one call per forward
    jf = jax.jit(forward)
    _wait(jf(pvals, x))
    t0 = time.perf_counter()
    for _ in range(args.outer):
        out = jf(pvals, x)
    _wait(out)
    host_ms = (time.perf_counter() - t0) / args.outer * 1000

    # --- device-only: K chained forwards in one computation.  Two
    # properties make the chain elision-proof (r4 hardening): (1) every
    # iteration's output feeds a scalar accumulator that is RETURNED
    # and fetched, so no forward is dead code; (2) the input is rolled
    # one pixel per iteration, so the forward is not loop-invariant and
    # cannot be hoisted out and computed once.  The earlier `x + 0*out`
    # trick kept the forwards live only if the compiler declined two
    # legal rewrites — this version does not rely on the compiler's
    # restraint.
    def make_chained(inner):
        @jax.jit
        def chained(pvals, x):
            def body(_, carry):
                xc, acc = carry
                out = forward(pvals, xc)
                acc = acc + jnp.mean(out).astype(jnp.float32)
                return (jnp.roll(xc, 1, axis=-1), acc)
            _, acc = lax.fori_loop(
                0, inner, body, (x, jnp.zeros((), jnp.float32)))
            return acc
        return chained

    depths = [int(d) for d in str(args.inner).split(",")]
    walls = []
    for inner in depths:
        chained = make_chained(inner)
        _wait(chained(pvals, x))  # compile + warm
        best = None
        for _ in range(args.reps):
            t0 = time.perf_counter()
            _wait(chained(pvals, x))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        walls.append(best)

    rec = {
        "network": args.network, "batch": args.batch, "dtype": args.dtype,
        "host_dispatched_ms_per_forward": round(host_ms, 3),
        "host_img_s": round(args.batch / host_ms * 1000, 1),
        "depths": depths,
        "wall_ms": [round(w * 1000, 2) for w in walls],
    }
    if len(depths) >= 2:
        # least-squares fit wall = overhead + t_fwd * depth: the
        # multi-depth fit divides the per-call host noise by the depth
        # span, so bs=1 resolves to ~us
        t_fwd, overhead = np.polyfit(depths, walls, 1)
        rec["device_ms_per_forward"] = round(t_fwd * 1000, 4)
        rec["fit_overhead_ms"] = round(overhead * 1000, 2)
        rec["device_img_s"] = round(args.batch / (t_fwd * 1000) * 1000, 1)
        # the deepest single chain is also a hard upper bound on t_fwd
        # (index by max depth: --inner need not be sorted ascending)
        deepest = depths.index(max(depths))
        rec["upper_bound_ms"] = round(
            walls[deepest] / depths[deepest] * 1000, 4)
    else:
        dev_ms = walls[0] / depths[0] * 1000
        rec["device_ms_per_forward"] = round(dev_ms, 3)
        rec["device_img_s"] = round(args.batch / dev_ms * 1000, 1)
    rec["per_call_overhead_ms"] = round(
        host_ms - rec["device_ms_per_forward"], 3)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
