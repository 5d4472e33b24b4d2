"""Pallas TPU kernel for the convolution backward-filter (dW) pass.

Why this exists (r4 trace, other toolchain, not re-measured): in the
flagship ResNet-50 step, XLA's backward-filter lowering ran the conv-dW
fusion family at 160–500 GB/s — neither compute- nor byte-bound — for
~9 ms of the 48 ms step.  The kernel itself has never been timed
(ROADMAP.md Speed #2).  The dW contraction is really a batched matmul:
for every filter tap (r, s) of a stride-1 conv,

    dW[r, s, i, o] = sum_{n, y, x} Xp[n, y + r, x + s, i] * dY[n, y, x, o]

so the TPU-native formulation tiles images through VMEM and issues one
(I × R̂) @ (R̂ × O) MXU contraction per tap per image-block, with the
f32 accumulator resident in VMEM across the sequential image grid
(the flash-attention pattern, attention.py).

Layouts: data NHWC, weight OHWI — the bench model's channel-last
layout (ops/nn.py convolution, layout="NHWC").  Reference analog: the
cuDNN wgrad algos behind src/operator/nn/convolution.cc; here the
kernel IS the algorithm choice.

Two formulations, selected per shape:
* per-tap (kh·kw matmuls of M=I): best when I >= 128 fills the MXU;
* im2col (one matmul of M=kh·kw·I): pays a VMEM concat to raise M for
  narrow layers (I < 128, e.g. ResNet conv2_x I=64 → M=576).

Stride 1 only: a strided conv needs strided loads from the VMEM block,
and Mosaic (jax 0.9.0 / libtpu 0.0.34, v5e) refuses them for 16-bit
data ("not implemented: Strided load with non 32-bit data") — the only
data the bf16 training step has.  Strided convs keep XLA's lowering.

`conv_dw_nhwc` is the public entry; `supported()` reports whether a
shape/config routes to the kernel (else callers fall back to XLA's
lowering).  Integration behind MXTPU_PALLAS_CONV_DW in ops/nn.py.
`python chip_smoke.py` compiles it with Mosaic at ResNet-50's shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

from ..util import pallas_interpret

_VMEM_BUDGET = 12 * 1024 * 1024  # leave headroom under the ~16 MiB/core
# Flattening an (nb, oh, ow, C) block to (nb*oh*ow, C) rows relayouts
# every (image, row) group whose width is not a multiple of the
# 8-sublane tile, and Mosaic's compile time and host memory grow much
# faster than linearly in the number of such groups.  Measured on v5e
# (jax 0.9.0, 14x14 C=256 per-tap, bf16): 28 groups compile in 14 s;
# 112 groups in 95 s with +4 GB of host memory; 448 groups ran past
# 4 min and 18 GB and took the machine down.  Stay at the first.
_MAX_RAGGED_ROW_GROUPS = 32


def supported(x_shape, dy_shape, kernel, stride, pad, dilate, groups,
              ebytes=2):
    """True when conv_dw_nhwc handles this configuration (including the
    VMEM fit of a single-image block — callers fall back to XLA's
    lowering otherwise, so an oversized shape must never reach
    pallas_call)."""
    if groups != 1:
        return False
    if any(d != 1 for d in dilate):
        return False
    if len(kernel) != 2 or tuple(stride) != (1, 1):
        return False
    if x_shape[-1] < 8:
        # the stem's I=3 pads the lane dim 128/3x in VMEM; its dW is
        # byte-bound anyway (r4 space-to-depth experiment) — XLA
        return False
    n, h, w, _c = dy_shape
    # output spatial must match the conv arithmetic exactly
    hp = x_shape[1] + 2 * pad[0]
    wp = x_shape[2] + 2 * pad[1]
    if hp - kernel[0] + 1 != h or wp - kernel[1] + 1 != w:
        return False
    per_image, out_bytes = _sizing(
        (hp, wp, x_shape[-1]), (h, w, dy_shape[-1]), kernel,
        # the auto formulation choice (conv_dw_nhwc) mirrors this
        "im2col" if x_shape[-1] < 128 else "pertap", ebytes)
    return per_image + out_bytes <= _VMEM_BUDGET


def _pad_to(v, m):
    return -(-int(v) // m) * m


def _sizing(xp_hwc, dy_hwc, kernel, formulation, ebytes):
    """(per-image VMEM bytes, accumulator bytes) with TPU vreg padding:
    the minor dim tiles to 128 lanes, the second-minor to 8 sublanes —
    a C=64 operand costs 2x its logical bytes in VMEM."""
    hp, wp, ci = xp_hwc
    oh, ow, co = dy_hwc
    kh, kw = kernel
    per_image = (hp * _pad_to(wp, 8) * _pad_to(ci, 128) +
                 oh * _pad_to(ow, 8) * _pad_to(co, 128)) * ebytes
    if formulation == "im2col":
        per_image += (oh * _pad_to(ow, 8) *
                      _pad_to(kh * kw * ci, 128) * ebytes)
    out_bytes = kh * kw * _pad_to(ci, 8) * _pad_to(co, 128) * 4
    return per_image, out_bytes


def _block_images(n, per_image_bytes, out_bytes, oh, ow):
    """Largest power-of-two image-block fitting the VMEM budget and,
    for widths off the sublane tile, the compile-cost bound above."""
    nb = 1
    while (nb * 2 <= n and n % (nb * 2) == 0 and
           (nb * 2) * per_image_bytes + out_bytes <= _VMEM_BUDGET and
           (ow % 8 == 0 or (nb * 2) * oh <= _MAX_RAGGED_ROW_GROUPS)):
        nb *= 2
    return nb


def _dw_kernel_pertap(x_ref, dy_ref, out_ref, *, kh, kw, oh, ow):
    """One image-block step: kh*kw MXU contractions accumulated into the
    full (kh, kw, I, O) output, which stays VMEM-resident across the
    sequential image grid."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    dy = dy_ref[:]
    dyf = dy.reshape(-1, dy.shape[-1])  # (nb*oh*ow, O)
    for r in range(kh):
        for s in range(kw):
            xs = x_ref[:, r:r + oh, s:s + ow, :]
            xsf = xs.reshape(-1, xs.shape[-1])  # (nb*oh*ow, I)
            acc = lax.dot_general(
                xsf, dyf, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (I, O)
            out_ref[r, s] += acc


def _dw_kernel_im2col(x_ref, dy_ref, out_ref, *, kh, kw, oh, ow):
    """One image-block step: a single (kh*kw*I × R̂) @ (R̂ × O)
    contraction — the concat buys MXU rows for narrow-channel layers."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    dy = dy_ref[:]
    dyf = dy.reshape(-1, dy.shape[-1])
    taps = []
    for r in range(kh):
        for s in range(kw):
            taps.append(x_ref[:, r:r + oh, s:s + ow, :])
    xcat = jnp.concatenate(taps, axis=-1)          # (nb, oh, ow, kh*kw*I)
    xsf = xcat.reshape(-1, xcat.shape[-1])
    acc = lax.dot_general(xsf, dyf, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    out_ref[:] += acc                              # (kh*kw*I, O)


@functools.partial(jax.jit,
                   static_argnames=("kernel", "pad", "interpret",
                                    "formulation"))
def conv_dw_nhwc(x, dy, kernel, pad=(0, 0), interpret=False,
                 formulation=None):
    """Backward-filter for a stride-1 NHWC conv with OHWI weights.

    x: (N, H, W, I) forward input; dy: (N, OH, OW, O) output cotangent.
    Returns dW with shape (O, kh, kw, I) in fp32 (the caller casts to
    the weight dtype — matching XLA's fp32 conv accumulation).
    formulation: None (auto), 'pertap', or 'im2col'.
    """
    kh, kw = kernel
    n, _h, _w, ci = x.shape
    _, oh, ow, co = dy.shape
    interpret = interpret or pallas_interpret()
    xp = jnp.pad(x, ((0, 0), (pad[0], pad[0]), (pad[1], pad[1]), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]

    if formulation is None:
        # narrow-channel layers waste MXU rows per tap; buy rows with
        # the im2col concat
        formulation = "im2col" if ci < 128 else "pertap"

    per_image, out_bytes = _sizing((hp, wp, ci), (oh, ow, co), kernel,
                                   formulation, x.dtype.itemsize)
    nb = _block_images(n, per_image, out_bytes, oh, ow)

    if formulation == "im2col":
        kern = functools.partial(_dw_kernel_im2col, kh=kh, kw=kw,
                                 oh=oh, ow=ow)
        out_shape = jax.ShapeDtypeStruct((kh * kw * ci, co), jnp.float32)
        out_spec = pl.BlockSpec((kh * kw * ci, co), lambda g: (0, 0))
    else:
        kern = functools.partial(_dw_kernel_pertap, kh=kh, kw=kw,
                                 oh=oh, ow=ow)
        out_shape = jax.ShapeDtypeStruct((kh, kw, ci, co), jnp.float32)
        out_spec = pl.BlockSpec((kh, kw, ci, co), lambda g: (0, 0, 0, 0))

    dw = pl.pallas_call(
        kern,
        grid=(n // nb,),
        in_specs=[
            pl.BlockSpec((nb, hp, wp, ci), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((nb, oh, ow, co), lambda g: (g, 0, 0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(xp, dy)

    dw = dw.reshape(kh, kw, ci, co)
    return jnp.transpose(dw, (3, 0, 1, 2))  # OHWI


def conv_dw_xla(x, dy, kernel, stride=(1, 1), pad=(0, 0)):
    """XLA's own backward-filter lowering for an NHWC/OHWI conv — the
    baseline the Pallas kernel must beat (tools/bench_conv_dw.py) and
    the numerical oracle for its tests and for chip_smoke.py."""
    dn = lax.conv_dimension_numbers(
        x.shape, (dy.shape[-1], kernel[0], kernel[1], x.shape[-1]),
        ("NHWC", "OHWI", "NHWC"))

    def fwd(w):
        return lax.conv_general_dilated(
            x, w, window_strides=stride,
            padding=[(pad[0], pad[0]), (pad[1], pad[1])],
            dimension_numbers=dn)

    w0 = jnp.zeros((dy.shape[-1], kernel[0], kernel[1], x.shape[-1]),
                   x.dtype)
    _, vjp = jax.vjp(fwd, w0)
    (dw,) = vjp(dy)
    return dw
