#!/usr/bin/env python
"""Collective-bandwidth micro-benchmark (reference: tools/bandwidth/
measure.py — measured kvstore push/pull GB/s across devices).

TPU-native: times an all-reduce (psum) of a large buffer over the device
mesh — the operation gradients ride during data-parallel training — and
reports algorithmic bandwidth per chip.
"""

import argparse
import time

import numpy as np


def measure(size_mb=64, iters=10, dtype="float32"):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    elems = int(size_mb * (1 << 20) / np.dtype(dtype).itemsize)
    x = jnp.ones((n, elems), dtype=dtype)

    @jax.jit
    def allreduce(x):
        return shard_map(lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
                         in_specs=P("dp", None), out_specs=P("dp", None))(x)

    allreduce(x).block_until_ready()  # compile + warmup
    tic = time.time()
    for _ in range(iters):
        out = allreduce(x)
    out.block_until_ready()
    dt = (time.time() - tic) / iters
    # ring all-reduce moves 2*(n-1)/n of the buffer per chip
    bytes_moved = 2 * (n - 1) / max(n, 1) * elems * np.dtype(dtype).itemsize
    return {"devices": n, "size_mb": size_mb, "time_s": dt,
            "gbps_per_chip": bytes_moved / dt / 1e9}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=float, default=64)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--dtype", type=str, default="float32")
    args = parser.parse_args(argv)
    r = measure(args.size_mb, args.iters, args.dtype)
    print("devices=%d size=%.0fMB time=%.4fs bandwidth=%.2f GB/s/chip"
          % (r["devices"], r["size_mb"], r["time_s"], r["gbps_per_chip"]))
    return r


if __name__ == "__main__":
    main()
