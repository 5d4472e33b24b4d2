"""Per-shape benchmark: Pallas conv-dW kernel vs XLA's backward-filter
lowering (ROADMAP.md Speed #2; never run to a recorded result yet).

Method:
each measurement chains `depth` dW computations inside ONE jit via
lax.fori_loop, rolls the input every iteration (defeats LICM), and
accumulates a reduced scalar.  Per-iteration time comes from the
difference of two depths, cancelling the single dispatch overhead.

Shapes: the stride-1 convs of ResNet-50 NHWC bs=128 (the kernel does
not take strided convs: Mosaic refuses strided loads of 16-bit data).
Output: one markdown table; the result decides MXTPU_PALLAS_CONV_DW
(default or deleted, ROADMAP.md Design #2).

Usage: python tools/bench_conv_dw.py [--batch 128] [--depths 8,24]
       [--out table.md] [--shapes all|3x3|1x1]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, (H, W, I), kernel, pad, O) at the bench batch size
RESNET50_SHAPES = [
    ("c2.3x3.64",    (56, 56, 64),   (3, 3), (1, 1), 64),
    ("c3.3x3.128",   (28, 28, 128),  (3, 3), (1, 1), 128),
    ("c4.3x3.256",   (14, 14, 256),  (3, 3), (1, 1), 256),
    ("c5.3x3.512",   (7, 7, 512),    (3, 3), (1, 1), 512),
    ("c2.1x1.64-256", (56, 56, 64),  (1, 1), (0, 0), 256),
    ("c2.1x1.256-64", (56, 56, 256), (1, 1), (0, 0), 64),
    ("c4.1x1.1024-256", (14, 14, 1024), (1, 1), (0, 0), 256),
]


def _flops(batch, oh, ow, kernel, ci, co):
    return 2.0 * batch * oh * ow * kernel[0] * kernel[1] * ci * co


def bench_impl(fn, x, dy, depths, reps=3):
    """Median per-iteration seconds via chained depths (see module
    docstring).  fn(x, dy) -> dW."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chained(depth):
        @jax.jit
        def run(x, dy):
            def body(i, carry):
                acc, xv = carry
                xv = jnp.roll(xv, 1, axis=1)  # new bytes every iteration
                dw = fn(xv, dy)
                return acc + jnp.sum(dw).astype(jnp.float32), xv

            acc, _ = lax.fori_loop(0, depth, body,
                                   (jnp.float32(0.0), x))
            return acc

        return run

    d1, d2 = depths
    f1, f2 = chained(d1), chained(d2)
    f1(x, dy).block_until_ready()  # compile+warm
    f2(x, dy).block_until_ready()
    t1s, t2s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        f1(x, dy).block_until_ready()
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        f2(x, dy).block_until_ready()
        t2s.append(time.perf_counter() - t0)
    t1 = sorted(t1s)[len(t1s) // 2]
    t2 = sorted(t2s)[len(t2s) // 2]
    return (t2 - t1) / (d2 - d1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--depths", default="8,24")
    ap.add_argument("--shapes", default="all")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--formulations", default="auto")
    ap.add_argument("--out", default=None,
                    help="also write the markdown table to this file")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_conv import conv_dw_nhwc, conv_dw_xla

    depths = tuple(int(d) for d in args.depths.split(","))
    dtype = jnp.dtype(args.dtype)
    rs = np.random.RandomState(0)

    rows = []
    lines = ["| shape | impl | ms/iter | TFLOP/s | vs XLA |",
             "|---|---|---|---|---|"]

    def emit(line):
        print(line, flush=True)
        lines.append(line)
    for (name, (h, w, ci), kernel, pad, co) in RESNET50_SHAPES:
        if args.shapes != "all" and args.shapes not in name:
            continue
        oh = h + 2 * pad[0] - kernel[0] + 1
        ow = w + 2 * pad[1] - kernel[1] + 1
        x = jnp.asarray(rs.rand(args.batch, h, w, ci), dtype)
        dy = jnp.asarray(rs.rand(args.batch, oh, ow, co), dtype)
        fl = _flops(args.batch, oh, ow, kernel, ci, co)

        t_xla = bench_impl(
            lambda xv, dyv: conv_dw_xla(xv, dyv, kernel, (1, 1), pad),
            x, dy, depths)
        emit("| %s | xla | %.3f | %.2f | 1.00x |"
             % (name, t_xla * 1e3, fl / t_xla / 1e12))
        forms = (["pertap", "im2col"] if args.formulations == "both"
                 else [None])
        for form in forms:
            label = "pallas" if form is None else "pallas-" + form
            try:
                t_pal = bench_impl(
                    lambda xv, dyv: conv_dw_nhwc(xv, dyv, kernel, pad,
                                                 formulation=form),
                    x, dy, depths)
                emit("| %s | %s | %.3f | %.2f | %.2fx |"
                     % (name, label, t_pal * 1e3, fl / t_pal / 1e12,
                        t_xla / t_pal))
                rows.append((name, label, t_xla, t_pal))
            except Exception as e:
                emit("| %s | %s | FAILED: %s | | |"
                     % (name, label, str(e)[:80]))
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return rows


if __name__ == "__main__":
    main()
