#!/usr/bin/env python
"""Flash-attention benchmark on the live chip: Pallas kernel vs the
XLA-fused reference attention, fwd and fwd+bwd, across sequence
lengths.  Beyond-parity evidence (the reference has no
fused attention; its transformer path materializes the full (seq, seq)
score matrix via interleaved_matmul_selfatt_*).

Device-only timing: K iterations chained inside one jit (output fed
back) so per-call dispatch overhead is excluded, same methodology as
bench_device_latency.py.
"""

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.attention import flash_attention, mha_reference


def bench(fn, args, iters):
    """Device-only: chain `iters` calls inside ONE jit (host dispatch
    is paid once), feeding the output back into q so iterations cannot
    be elided; ``block_until_ready`` is the completion barrier."""
    q0 = args[0]

    @jax.jit
    def chained(q, *rest):
        def body(_, q):
            out = fn(q, *rest)
            if isinstance(out, tuple):
                out = out[0]
            return (out.astype(q.dtype) * 1e-6 + q).astype(q.dtype)
        return jax.lax.fori_loop(0, iters, body, q)

    def run():
        chained(q0, *args[1:]).block_until_ready()

    run()                                              # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best / iters


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--seqs", type=str, default="1024,2048,4096")
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--causal", action="store_true")
    p.add_argument("--dtype", default="bfloat16")
    args = p.parse_args(argv)

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rows = []
    for seq in (int(s) for s in args.seqs.split(",")):
        shape = (args.batch, args.heads, seq, args.head_dim)
        q, k, v = (jnp.asarray(rng.randn(*shape), dt) for _ in range(3))

        # fwd FLOPs: 2 matmuls of (seq x d) @ (d x seq) and (seq x seq) @ (seq x d)
        flops = 4.0 * args.batch * args.heads * seq * seq * args.head_dim
        if args.causal:
            flops /= 2

        def fwd_flash(q, k, v):
            return flash_attention(q, k, v, causal=args.causal)

        def fwd_ref(q, k, v):
            return mha_reference(q, k, v, causal=args.causal)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=args.causal).sum()

        def loss_ref(q, k, v):
            return mha_reference(q, k, v, causal=args.causal).sum()

        t_flash = bench(fwd_flash, (q, k, v), args.iters)
        t_ref = bench(fwd_ref, (q, k, v), args.iters)
        g_flash = bench(jax.grad(loss_flash, argnums=(0, 1, 2)),
                        (q, k, v), args.iters)
        g_ref = bench(jax.grad(loss_ref, argnums=(0, 1, 2)),
                      (q, k, v), args.iters)
        rows.append((seq, t_flash, t_ref, g_flash, g_ref, flops))
        print("seq %5d | fwd: flash %7.3f ms (%.1f TFLOP/s)  xla %7.3f ms"
              " | fwd+bwd: flash %7.3f ms  xla %7.3f ms | speedup "
              "fwd %.2fx bwd %.2fx"
              % (seq, t_flash * 1e3, flops / t_flash / 1e12,
                 t_ref * 1e3, g_flash * 1e3, g_ref * 1e3,
                 t_ref / t_flash, g_ref / g_flash))
    return rows


if __name__ == "__main__":
    main()
