#!/usr/bin/env python
"""Flash-attention kernels on the live chip, one at a time: the forward,
dq and dk/dv Pallas kernels of ``mxnet_tpu/ops/attention.py`` across
sequence lengths and block pairs, in ms and in TFLOP/s of the operations
attention requires (``benchmark/harness/attention_cost.py``'s count: with
``--causal`` half the square, per pair of positions and head 2 d + 2 dv
forward, 4 d + 2 dv for dq, 4 d + 4 dv for dk/dv: 640 / 1,024 / 1,280 at
latent attention's 192 / 128).  ``--xla`` adds the unfused reference,
forward and forward + backward (it materialises the (seq, seq) scores).

The block table of a language-model cell is one command (latent attention's
heads; grouped-query heads of 64 with ``--heads 32 --kv-heads 8 --head-dim
64 --seqs 8192``; a sliding window with ``--window 1024``, the operations
then the band's, ``S W - W (W - 1) / 2`` pairs a head):

    python tools/bench_attention.py --batch 2 --heads 32 --head-dim 192 \\
        --v-head-dim 128 --seqs 4096 --causal \\
        --blocks 256x512,512x512,256x256,512x1024,1024x512

Device-only timing: K iterations chained inside one jit, one element of
the result fed back into q, so per-call dispatch is excluded and no
iteration can be hoisted or elided.
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

from mxnet_tpu.ops import attention as att


def bench(fn, args, iters):
    """Seconds a call of ``fn(*args)``: ``iters`` calls chained inside
    ONE jit (host dispatch is paid once); an element of each result lands
    in the first argument, in place, so the calls depend on one another;
    ``block_until_ready`` is the completion barrier."""
    @jax.jit
    def chained(first, *rest):
        def body(_, first):
            out = jax.tree_util.tree_leaves(fn(first, *rest))[0]
            origin = (0,) * first.ndim
            return first.at[origin].add(
                (out[(0,) * out.ndim] * 1e-6).astype(first.dtype))
        return jax.lax.fori_loop(0, iters, body, first)

    def run():
        chained(*args).block_until_ready()

    run()                                              # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best / iters


def kernel_times(q, k, v, g, causal, block_q, block_k, iters, window=None):
    """{"fwd", "dq", "dkv"}: seconds a call of each kernel."""
    how = (1.0 / np.sqrt(q.shape[-1]), causal, window, block_q, block_k,
           att.pallas_interpret())
    out, lse = att._fwd_pallas(q, k, v, *how)
    operands = att._bwd_operands(q, k, v, out, lse, g)
    return {
        "fwd": bench(lambda q, k, v: att._fwd_pallas(q, k, v, *how),
                     (q, k, v), iters),
        "dq": bench(lambda *ops: att._dq_pallas(ops, *how), operands, iters),
        "dkv": bench(lambda *ops: att._dkv_pallas(ops, *how), operands,
                     iters),
    }


def main(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="heads of k and v (grouped-query; default: --heads)")
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--v-head-dim", type=int, default=None,
                   help="head size of v and the output (default: --head-dim)")
    p.add_argument("--seqs", type=str, default="1024,2048,4096")
    p.add_argument("--blocks", type=str, default=None,
                   help="block pairs QxK, comma separated (default: the "
                        "pair flash_attention takes)")
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--causal", action="store_true")
    p.add_argument("--window", type=int, default=None,
                   help="sliding window (with --causal): a query sees that "
                        "many keys, itself the last")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--xla", action="store_true",
                   help="time the unfused reference too")
    args = p.parse_args(argv)

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    d = args.head_dim
    dv = args.v_head_dim or d
    blocks = args.blocks and [tuple(int(n) for n in pair.split("x"))
                              for pair in args.blocks.split(",")]
    per_pair = {"fwd": 2 * d + 2 * dv, "dq": 4 * d + 2 * dv,
                "dkv": 4 * d + 4 * dv}
    rows = []
    for seq in (int(s) for s in args.seqs.split(",")):
        kv_heads = args.kv_heads or args.heads
        q = jnp.asarray(rng.randn(args.batch, args.heads, seq, d), dt)
        k = jnp.asarray(rng.randn(args.batch, kv_heads, seq, d), dt)
        v = jnp.asarray(rng.randn(args.batch, kv_heads, seq, dv), dt)
        g = jnp.asarray(rng.randn(args.batch, args.heads, seq, dv), dt)
        pairs = args.batch * args.heads * seq * seq / (2.0 if args.causal
                                                       else 1.0)
        window = args.window if args.window and args.window < seq else None
        if window:
            pairs = args.batch * args.heads * (
                seq * window - window * (window - 1) / 2.0)
        for block_q, block_k in blocks or att._block_choices(q, v)[:1]:
            block_q, block_k = min(block_q, seq), min(block_k, seq)
            t = kernel_times(q, k, v, g, args.causal, block_q, block_k,
                             args.iters, window)
            rows.append((seq, block_q, block_k, t))
            print("seq %5d blocks %4d/%-4d | " % (seq, block_q, block_k)
                  + "  ".join("%s %7.3f ms (%5.1f TFLOP/s)"
                              % (name, t[name] * 1e3,
                                 pairs * per_pair[name] / t[name] / 1e12)
                              for name in ("fwd", "dq", "dkv"))
                  + "  | sum %7.3f ms" % (sum(t.values()) * 1e3), flush=True)
        if args.xla:
            def ref(q, k, v):
                return att.mha_reference(q, k, v, causal=args.causal)

            def ref_grads(q, k, v, g):
                return jax.vjp(ref, q, k, v)[1](g)

            t_fwd = bench(ref, (q, k, v), args.iters)
            t_all = bench(ref_grads, (q, k, v, g), args.iters)
            print("seq %5d xla reference | fwd %7.3f ms  fwd+bwd %7.3f ms"
                  % (seq, t_fwd * 1e3, t_all * 1e3), flush=True)
    return rows


if __name__ == "__main__":
    main()
