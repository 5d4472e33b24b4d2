"""Operators of current decoder language models (docs/LLM_OPS.md).

RMS norm, rotary position embedding (adjacent pairs or halves, its
frequencies and amplitude data of the layer: ``rotary_frequencies`` has
yarn's), the gated-SiLU feed-forward, the projections of latent attention
and of grouped-query attention with a norm on every head (the rotation on
all of a head's lanes or its first ones) and the per-head output gate of
gated attention, a gated short
causal convolution over the sequence, the router (sigmoid scores with a
selection bias, DeepSeek-V3, arXiv:2412.19437, or softmax scores, with the
balancing term of Switch Transformer, arXiv:2101.03961) and the
held-experts layer of a mixture of experts, a linear head fused with
its cross-entropy over token chunks, and the coefficients and mixes of
hyper-connections over a residual stream of several streams.  The reference
framework has none of them (its transformer helpers are
``src/operator/contrib/transformer.cc``).

The expert layer is told which experts it holds: the router scores all
of the model's experts, selection and normalisation run over all of them,
and the layer computes the part of the routed sum that its own experts
give (what expert parallelism asks of one chip; on one chip without the
exchange).  No pair routed to a held expert is dropped at any imbalance,
and the work follows the rows actually routed: the pairs are sorted by
expert into row tiles, and loops whose trip counts follow the tiles in use
gather a step's rows (one tile, or up to four of one expert in the
backward pass), run their expert and scatter the result.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import xray as _xray
from ..util import pallas_interpret
from .registry import OP_INPUT_NAMES, register

__all__ = ["rms_norm", "rope", "rotary_frequencies", "gated_silu", "mla_qkv", "mla_out", "gqa_qkv",
           "gqa_out", "head_gate", "gated_short_conv", "moe_route",
           "moe_experts", "linear_cross_entropy", "expert_tiles",
           "hc_coefficients", "hc_pre_mix", "hc_post_mix"]

# rows of one expert tile: what an expert's rows are padded to.  A step of
# the loops costs its expert's three weights read (twice in the backward
# pass) and, in the backward pass, their three float32 gradients read, added
# to and written, whatever its rows: at 256 rows that fixed part was two
# thirds of a tile's time on a v5e, and the step time followed the routed
# pairs at 0.72 us a pair (PERF.md, PR 28); 512 halves it.
DEFAULT_EXPERT_TILE = 512
# tiles of one expert that a step of the backward loops takes while the
# expert has that many left (a big step); what is left of it goes one tile a
# step, so an expert with fewer tiles costs what it did and padding stays a
# tile's.  At 512 rows a weight gradient's write is bound by its slice's
# bytes (0.107 ms for 0.057 ms of product at LFM2's widths), at 4 x 512 by
# its product; 2 leaves the writes at twice the bytes, 8 needs experts of
# 4,096 rows (PERF.md, PR 35).
EXPERT_TILES_A_STEP = 4
# tiles of a big step whose rows go through the expert together (a run):
# what is computed row by row (the recomputed forward, dh, dx) keeps its
# float32 intermediates in the v5e's fast memory at 2 x 512 rows of LFM2's
# width and not at 4 x 512; the weight gradients contract all of the big
# step's rows whatever the run (PERF.md, PR 35).
EXPERT_TILES_A_RUN = 2
DEFAULT_LOSS_CHUNK = 1024
LANES = 128


@register("_contrib_rms_norm", aliases=("rms_norm",))
def rms_norm(data, gamma, eps=1e-6, **_):
    """Root-mean-square normalisation over the last axis with a learned
    scale: ``x * rsqrt(mean(x^2) + eps) * gamma`` (Zhang & Sennrich 2019,
    arXiv:1910.07467); statistics in float32 whatever the input's type."""
    x = data.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale * gamma.astype(jnp.float32)).astype(data.dtype)


def rotary_frequencies(dim, rope_theta=10000.0, rope_type="default",
                       factor=1.0, original_max_position_embeddings=None,
                       beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                       **_):
    """A layer's rotary scaling as data: ``(inv_freq, amplitude)``, the
    ``dim / 2`` angles a position advances each pair by (a tuple of floats,
    computed in float64) and what ``cos`` and ``sin`` are multiplied by.
    The arguments carry the names of a ``config.json``'s rope parameters.

    ``"default"``: ``theta^(-2i/dim)``, amplitude 1.  ``"yarn"`` (Peng et
    al., arXiv:2309.00071): a pair that turns more than ``beta_fast`` times
    over the ``original_max_position_embeddings`` keeps its frequency, one
    that turns less than ``beta_slow`` times has it divided by ``factor``,
    the pairs between are blended linearly over their index: with ``c(r) =
    dim ln(original / (2 pi r)) / (2 ln theta)``, ``low = max(floor(c(
    beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)), dim - 1)``,
    ``ramp_i = clip((i - low) / (high - low), 0, 1)``: ``inv_freq_i = theta^(-2i/dim) (ramp_i / factor + 1 -
    ramp_i)``.  The amplitude is ``attention_factor``, by default ``0.1 ln
    factor + 1``."""
    f64 = _np.float64  # mxlint: disable=dtype-default -- host table
    inv = float(rope_theta) ** (-_np.arange(0, dim, 2, dtype=f64) / dim)
    if rope_type == "default":
        return tuple(float(v) for v in inv), 1.0
    if rope_type != "yarn":
        raise ValueError("rotary_frequencies: rope_type %r is not "
                         "'default' or 'yarn'" % (rope_type,))

    def pair_that_turns(times):
        return dim * math.log(original_max_position_embeddings
                              / (times * 2 * math.pi)) \
            / (2 * math.log(rope_theta))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = _np.clip((_np.arange(dim // 2, dtype=f64) - low) / (high - low),
                    0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return tuple(float(v) for v in inv), float(attention_factor)


def _rotary_tables(seq, dim, theta, halves=False, inv_freq=None,
                   amplitude=1.0):
    """``cos``, ``sin`` of ``p * theta^(-2i/dim)`` for positions ``p <
    seq``, each on both lanes of its pair, the sine negative on a pair's
    first lane: ``(seq, dim)`` float32 constants from a float64 host table
    (float32 angles at position 4096 and theta 3.2e7 are wrong in the
    fourth digit).  A pair is the adjacent lanes ``(2i, 2i + 1)``, or with
    ``halves`` the lanes ``(i, i + dim / 2)``.  ``inv_freq`` (``dim / 2``
    values) takes the place of ``theta``'s frequencies and both tables are
    multiplied by ``amplitude``: a layer's rotary scaling
    (``rotary_frequencies``)."""
    f64 = _np.float64  # mxlint: disable=dtype-default -- host table, cast below
    if inv_freq is None:
        inv = float(theta) ** (-_np.arange(0, dim, 2, dtype=f64) / dim)
    else:
        inv = _np.asarray(inv_freq, dtype=f64)
        if inv.shape != (dim // 2,):
            raise ValueError("rotary tables: %d frequencies for %d lanes"
                             % (inv.size, dim))
    angle = _np.arange(seq, dtype=f64)[:, None] * inv[None, :]
    if halves:
        cos = _np.tile(_np.cos(angle), 2)
        sin = _np.tile(_np.sin(angle), 2)
        sin[:, :dim // 2] *= -1
    else:
        cos = _np.repeat(_np.cos(angle), 2, axis=-1)
        sin = _np.repeat(_np.sin(angle), 2, axis=-1)
        sin[:, 0::2] *= -1
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def _swap_pairs(x, halves=False):
    """``x[..., 2i] <-> x[..., 2i + 1]`` (with ``halves``: ``x[..., i] <->
    x[..., i + dim / 2]``) in float32, as a product with a
    0 / 1 permutation: one term a result, so it is exact (float32
    operands take the MXU's exact passes).  On a v5e XLA makes one fusion
    of it and the arithmetic around it; lanes taken by stride become
    gathers (scatters in the gradient), and rolled by one, four sliced
    float32 copies (PERF.md, PR 31).  The permutation is at most 128
    wide: a wider last axis is cut into equal parts (halves wider than
    that are two slices put back the other way round)."""
    dim = x.shape[-1]
    if halves and dim > 128:
        return jnp.concatenate([x[..., dim // 2:], x[..., :dim // 2]],
                               axis=-1).astype(jnp.float32)
    width = dim if halves else next(
        w for w in range(min(dim, 128), 1, -1)
        if dim % w == 0 and w % 2 == 0)
    lane = _np.arange(width, dtype=_np.int32)
    perm = _np.zeros((width, width), dtype=_np.float32)
    perm[lane, (lane + width // 2) % width if halves else lane ^ 1] = 1
    parts = x.reshape(x.shape[:-1] + (dim // width, width))
    out = lax.dot_general(
        parts, jnp.asarray(perm, x.dtype), (((parts.ndim - 1,), (0,)),
                                            ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    return out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rotary(x, cos, sin, start, halves=False):
    """Lanes ``start:`` of ``x (..., seq, dim)`` rotated pair by pair by
    the tables of ``_rotary_tables`` (made with the same ``halves``),
    float32 arithmetic, the lanes before ``start`` left as they are:
    written in place of ``x`` where the compiler may."""
    tail = x[..., start:]
    out = (tail.astype(jnp.float32) * cos
           + _swap_pairs(tail, halves) * sin).astype(x.dtype)
    if not start:
        return out
    return lax.dynamic_update_slice_in_dim(x, out, start, axis=x.ndim - 1)


def _rotary_fwd(x, cos, sin, start, halves):
    return _rotary(x, cos, sin, start, halves), (cos, sin)


def _rotary_bwd(start, halves, tables, g):
    # a rotation's transpose is the rotation back; the lanes before
    # ``start`` pass through
    cos, sin = tables
    return _rotary(g, cos, -sin, start, halves), None, None


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


@register("_contrib_rope", aliases=("rope",))
def rope(data, theta=10000.0, halves=False, inv_freq=None, amplitude=1.0,
         **_):
    """Rotary position embedding (Su et al., arXiv:2104.09864) over
    ``(..., seq, dim)``: position ``p`` rotates the adjacent pair ``(2i,
    2i+1)`` by ``p * theta^(-2i/dim)`` (``rope_interleave``), or with
    ``halves`` the pair ``(i, i + dim/2)`` ("rotate half"):
    ``x * cos + swap_pairs(x) * sin`` in float32, the angles constants
    computed in float64 when the op is traced; its gradient is the
    rotation back.  ``inv_freq`` and ``amplitude``: a layer's rotary
    scaling in place of ``theta`` (``rotary_frequencies``); with an
    amplitude the op is a rotation times that number."""
    halves = bool(halves)
    cos, sin = _rotary_tables(data.shape[-2], data.shape[-1], theta, halves,
                              inv_freq, float(amplitude))
    return _rotary(data, cos, sin, 0, halves)


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                           preferred_element_type=jnp.float32)


@register("_contrib_gated_silu", aliases=("gated_silu",))
def gated_silu(data, gate_weight, up_weight, down_weight, **_):
    """Gated-SiLU feed-forward ``W_down(silu(W_gate x) * W_up x)`` (Shazeer
    2020, arXiv:2002.05202), no biases; weights ``(out, in)`` like
    ``FullyConnected``'s, float32 accumulation.  Forward and backward run
    under the scope ``ffn.gated``; the backward pass is written by hand
    (``_gated_silu_bwd``), its six products take their operands in the
    data's type."""
    with _xray.scope("ffn.gated"):
        return _gated_silu(data, gate_weight, up_weight, down_weight)


# --------------------------------------------------- latent attention


def _row_major(x):
    """``x`` held with its last axis minor.  A Pallas kernel takes its
    operands so, and left to itself the v5e's compiler lays a projection's
    result out sequence-minor (a head size of 192 fills a lane tile and a
    half) and copies it on the way to the kernel; its cotangent is held
    the same way."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _by_head(x, weight):
    """``(B, S, in)`` times a head's rows of ``weight (heads, d, in)`` ->
    ``(B, heads, S, d)``, the product's own result: no ``(B, S, heads *
    d)`` stands to be transposed."""
    return _row_major(jnp.einsum("bsr,hdr->bhsd", x, weight))


@register("_contrib_mla_qkv", num_outputs=3, aliases=("mla_qkv",))
def mla_qkv(data, qa_weight, qb_weight, kva_weight, kvb_weight,
            qnorm_weight, kvnorm_weight, num_heads=1, theta=10000.0,
            eps=1e-6, inv_freq=None, amplitude=1.0, **_):
    """The projections of multi-head latent attention in its training
    form (DeepSeek-V2, arXiv:2405.04434), from the block's input ``(B, S,
    units)`` to what ``flash_attention`` reads: ``q``, ``k`` ``(B, heads,
    S, nope + rope)`` and ``v`` ``(B, heads, S, v)``.

    The weights have the shapes of the published checkpoints:
    ``qa_weight (q_rank, units)``, ``qb_weight (heads * (nope + rope),
    q_rank)``, ``kva_weight (kv_rank + rope, units)``, ``kvb_weight (heads
    * (nope + v), kv_rank)`` and the two latents' norm scales, from which
    the sizes are read.  A head's rows are taken from the weights, not
    from a product's result: every product writes ``(B, heads, S, d)``
    itself, ``v`` is a product and not a slice of ``kv``, ``q``'s rotary
    lanes are rotated in place of the product's result, and one pass
    writes ``k`` from its ``nope`` part and the one rotary key that all
    heads share (the pass that reads ``dk`` sums that key's gradient over
    the heads).  ``inv_freq`` / ``amplitude``: the layer's rotary scaling
    in place of ``theta``, as in :func:`rope`."""
    heads = int(num_heads)
    rank = kvnorm_weight.shape[0]
    rot = kva_weight.shape[0] - rank
    nope = qb_weight.shape[0] // heads - rot
    with _xray.scope("mla.proj"):
        cos, sin = _rotary_tables(data.shape[-2], rot, theta, False,
                                  inv_freq, float(amplitude))
        c_q = rms_norm(jnp.matmul(data, qa_weight.T), qnorm_weight, eps=eps)
        q = _by_head(c_q, qb_weight.reshape(heads, nope + rot, -1))
        q = _rotary(q, cos, sin, nope, False)
        kva = jnp.matmul(data, kva_weight.T)
        c_kv = rms_norm(kva[..., :rank], kvnorm_weight, eps=eps)
        k_rot = _rotary(kva[..., rank:], cos, sin, 0, False)
        kvb = kvb_weight.reshape(heads, -1, rank)
        k_nope = _by_head(c_kv, kvb[:, :nope])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rot[:, None],
                                      k_nope.shape[:-1] + (rot,))], axis=-1)
        v = _by_head(c_kv, kvb[:, nope:])
        return q, k, v


def _heads_out(data, weight):
    """``data (B, heads, S, v)`` contracted over (head, value) with
    ``weight (units, heads * v)`` -> ``(B, S, units)``; no transposed copy
    of ``data``, and its gradient is written ``(B, heads, S, v)``."""
    _, heads, _, width = data.shape
    return jnp.einsum("bhsv,uhv->bsu", _row_major(data),
                      weight.reshape(-1, heads, width))


@register("_contrib_mla_out", aliases=("mla_out",))
def mla_out(data, weight, **_):
    """Latent attention's output projection from the kernel's own result
    (``_heads_out``), under the scope ``mla.proj``."""
    with _xray.scope("mla.proj"):
        return _heads_out(data, weight)


# ------------------------------------ grouped-query attention, head norms


def _first_lanes_rotated(x, cos, sin):
    """Lanes ``0 .. r - 1`` of ``x (..., S, d)`` rotated by halves (pairs
    ``(i, i + r / 2)``) by tables of ``r`` lanes, the lanes from ``r`` on
    passed through: transformers' partial rotary."""
    r = cos.shape[-1]
    return lax.dynamic_update_slice_in_dim(
        x, _rotary(x[..., :r], cos, sin, 0, True), 0, axis=x.ndim - 1)


@register("_contrib_gqa_qkv", num_outputs=3, aliases=("gqa_qkv",))
def gqa_qkv(data, q_weight, k_weight, v_weight, qnorm_weight, knorm_weight,
            theta=10000.0, eps=1e-6, inv_freq=None, amplitude=1.0,
            rotary_dim=None, **_):
    """The projections of grouped-query attention with a norm on every
    head (Ainslie et al., arXiv:2305.13245; the head norms of Dehghani et
    al., arXiv:2302.05442), from the block's input ``(B, S, units)`` to
    what ``flash_attention`` reads: ``q (B, heads, S, d)``, ``k`` and ``v``
    ``(B, kv_heads, S, d)``, the key heads not repeated.

    ``q_weight (heads * d, units)``, ``k_weight`` and ``v_weight
    (kv_heads * d, units)``; ``qnorm_weight`` and ``knorm_weight`` ``(d,)``:
    every head of ``q`` and of ``k`` is RMS-normalised over its ``d`` values
    with the one learned scale, then rotated by halves (pairs ``(i, i + d /
    2)``).  The head size is read from the norms' scales, the head counts
    from the weights.  Every product writes ``(B, heads, S, d)`` itself.
    ``inv_freq`` / ``amplitude``: the layer's rotary scaling in place of
    ``theta``, as in :func:`rope`.  ``rotary_dim`` (``r < d``; a config's
    ``partial_rotary_factor`` times ``d``): only the first ``r`` lanes of a
    head are rotated, pairs ``(i, i + r / 2)``, by ``r / 2`` frequencies
    and the amplitude; the other lanes pass through unscaled."""
    d = qnorm_weight.shape[0]
    partial = rotary_dim is not None and int(rotary_dim) < d
    with _xray.scope("gqa.proj"):
        cos, sin = _rotary_tables(data.shape[-2],
                                  int(rotary_dim) if partial else d, theta,
                                  True, inv_freq, float(amplitude))
        q, k, v = (_by_head(data, w.reshape(-1, d, w.shape[-1]))
                   for w in (q_weight, k_weight, v_weight))
        if partial:
            return (_first_lanes_rotated(rms_norm(q, qnorm_weight, eps=eps),
                                         cos, sin),
                    _first_lanes_rotated(rms_norm(k, knorm_weight, eps=eps),
                                         cos, sin), v)
        q = _rotary(rms_norm(q, qnorm_weight, eps=eps), cos, sin, 0, True)
        k = _rotary(rms_norm(k, knorm_weight, eps=eps), cos, sin, 0, True)
        return q, k, v


@register("_contrib_gqa_out", aliases=("gqa_out",))
def gqa_out(data, weight, **_):
    """Grouped-query attention's output projection from the kernel's own
    result (``_heads_out``), under the scope ``gqa.proj``."""
    with _xray.scope("gqa.proj"):
        return _heads_out(data, weight)


@register("_contrib_head_gate", aliases=("head_gate",))
def head_gate(data, gate_data, gate_weight, **_):
    """The per-head output gate of gated attention (Qiu et al.,
    arXiv:2505.06708, its headwise form): ``data (B, heads, S, d)``, the
    attention kernel's own result, times ``sigmoid(gate_data W_g^T)``, one
    scalar a head and position, ``gate_data (B, S, units)`` the block's
    normed input and ``gate_weight (heads, units)``.  The product writes
    ``(B, heads, S)`` itself; sigmoid and multiply in float32, the result in
    ``data``'s dtype, under the scope ``gqa.gate``."""
    with _xray.scope("gqa.gate"):
        logits = jnp.einsum("bsu,hu->bhs", gate_data, gate_weight,
                            preferred_element_type=jnp.float32)
        return (data.astype(jnp.float32)
                * jax.nn.sigmoid(logits)[..., None]).astype(data.dtype)


# --------------------------------------------- gated short convolution


def _behind(u, length):
    """``[u_{t - (length - 1) + j} for j < length]``: ``u (B, S, C)`` shifted
    along the sequence, zero before a row's first position.  Channels stay
    on the lanes: a tap is a shift along the sequence."""
    seq = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (length - 1, 0), (0, 0)))
    return [padded[:, j:j + seq] for j in range(length)]


def _gates(bcx):
    f32 = jnp.float32
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return b.astype(f32), c.astype(f32), x.astype(f32)


@jax.custom_vjp
def _gated_conv(bcx, weight):
    """``c * conv(b * x)``, ``conv_t = sum_j weight[:, j] * u_{t - (L - 1)
    + j}``: ``bcx (B, S, 3 C)``, ``weight (C, L)``."""
    b, c, x = _gates(bcx)
    taps = weight.T.astype(jnp.float32)
    conv = sum(k * u for k, u in zip(taps, _behind(b * x, len(taps))))
    return (c * conv).astype(bcx.dtype)


def _gated_conv_fwd(bcx, weight):
    return _gated_conv(bcx, weight), (bcx, weight)


def _gated_conv_bwd(res, g):
    """Nothing but the op's inputs is kept: the gate's product and the
    convolution are three multiply-adds a value, run again here.  The
    convolution's transpose is the same taps looking forward."""
    bcx, weight = res
    b, c, x = _gates(bcx)
    taps = weight.T.astype(jnp.float32)
    length, seq = taps.shape[0], bcx.shape[1]
    behind = _behind(b * x, length)
    g = g.astype(jnp.float32)
    dconv = g * c
    ahead = jnp.pad(dconv, ((0, 0), (0, length - 1), (0, 0)))
    du = sum(taps[j] * ahead[:, length - 1 - j:length - 1 - j + seq]
             for j in range(length))
    dtaps = jnp.stack([jnp.sum(dconv * u, axis=(0, 1)) for u in behind])
    conv = sum(k * u for k, u in zip(taps, behind))
    dbcx = jnp.concatenate([du * x, g * conv, du * b], axis=-1)
    return dbcx.astype(bcx.dtype), dtaps.T.astype(weight.dtype)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register("_contrib_gated_short_conv", aliases=("gated_short_conv",))
def gated_short_conv(data, weight, **_):
    """Gated short causal convolution over the sequence (the operator of
    LIV-style hybrid models' convolution layers; Hasani et al.,
    arXiv:2410.03137 has the family): ``data (B, S, 3 C)`` holds ``[b; c;
    x]`` side by side on the last axis, ``weight (C, L)`` a depthwise
    kernel of ``L`` taps; ``u = b * x``; ``conv_t = sum_j weight[:, j] *
    u_{t - (L - 1) + j}`` with ``u`` zero before a row's first position (a
    row is one document); result ``c * conv``, ``(B, S, C)``.

    Channels stay on the last axis (the lanes) throughout, a tap is a
    shift along the sequence: no ``(B, C, S)`` copy.  float32 arithmetic
    whatever the input's type.  The backward pass keeps the two inputs
    only and writes the gradient of ``data`` in one piece."""
    with _xray.scope("shortconv.conv"):
        return _gated_conv(data, weight)


def _flag(value):
    """An on / off attribute, which a symbol hands over as a string."""
    return str(value).lower() in ("true", "1")


@register("_contrib_moe_route", aliases=("moe_route",),
          num_outputs=lambda attrs: 3 if _flag(attrs.get("balance")) else 2)
def moe_route(data, router_weight, router_bias=None, k=8, scale=1.0,
              eps=1e-20, scoring="sigmoid", balance=False, **_):
    """Top-``k`` routing over float32 scores ``s`` of ``x W_g``:
    ``scoring`` ``"sigmoid"`` (``noaux_tc`` with one group) or
    ``"softmax"`` over all the experts.  The ``k`` largest of ``s`` are
    selected, of ``s + router_bias`` where a selection bias is given; a
    selected expert weighs ``s / (sum of the selected s + eps) * scale``:
    the bias selects, it does not weigh.  -> (expert ids ``(..., k)``
    int32, weights ``(..., k)`` float32).  ``router_weight``: ``(experts,
    in)``.

    ``balance``: a third result, the router's term of the auxiliary
    balancing loss (Switch Transformer, arXiv:2101.03961, as the
    softmax-routed families train with it): ``experts x sum_e f_e P_e``,
    ``f_e`` the share of the ``tokens x k`` pairs that chose expert ``e``
    (a count: no gradient), ``P_e`` the mean of ``s_e`` over the tokens; a
    float32 scalar, 1 when every expert is chosen and scored alike."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError("moe_route: scoring %r is not 'sigmoid' or "
                         "'softmax'" % (scoring,))
    with _xray.scope("moe.route"):
        logits = _dot(data.astype(jnp.float32),
                      router_weight.astype(jnp.float32),
                      ((data.ndim - 1,), (1,)))
        s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        chosen_by = s if router_bias is None else s + lax.stop_gradient(
            router_bias.astype(jnp.float32))
        _, ids = lax.top_k(chosen_by, int(k))
        picked = jnp.take_along_axis(s, ids, axis=-1)
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                            + float(eps)) * scale
    if not _flag(balance):
        return ids.astype(jnp.int32), weights
    with _xray.scope("moe.aux"):
        experts = s.shape[-1]
        # a compare and a sum, fused; a scatter-add of ones costs the
        # v5e 0.24 us a pair (PERF.md, PR 33)
        chosen = jnp.sum(ids.reshape(-1)[:, None] == jnp.arange(experts),
                         axis=0, dtype=jnp.float32)
        share = lax.stop_gradient(chosen / ids.size)
        mean_score = jnp.mean(s.reshape(-1, experts), axis=0)
        return (ids.astype(jnp.int32), weights,
                experts * jnp.sum(share * mean_score))


# ------------------------------------------------------ the held experts


def expert_tiles(ids, first, held, tile):
    """Sort the (token, choice) pairs that chose a held expert into row
    tiles, each tile one expert's: every held expert's rows start at a
    multiple of ``tile``.  ``ids``: (tokens, k) expert ids over all of the
    model's experts; held are ``first .. first + held - 1``.

    -> (``row_pair`` (capacity,) int32: the flat pair index of every row,
    ``tokens * k`` where the row is padding; ``tile_expert`` (capacity /
    tile,) int32; ``n_tiles`` int32 scalar: the tiles in use; ``counts``
    (held,) int32: pairs per held expert).  The capacity covers every
    pair on held experts (nothing is dropped); only index arrays have
    that size."""
    pairs = ids.size
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    padded = (counts + tile - 1) // tile * tile
    ends = jnp.cumsum(padded)
    start_sorted = jnp.cumsum(counts) - counts
    start_tiled = ends - padded
    n_slots = -(-(pairs + held * (tile - 1)) // tile)
    capacity = n_slots * tile
    sorted_key = key[order]
    clipped = jnp.minimum(sorted_key, held - 1)
    dest = jnp.where(
        sorted_key < held,
        start_tiled[clipped] + jnp.arange(pairs) - start_sorted[clipped],
        capacity)
    row_pair = jnp.full((capacity,), pairs, jnp.int32).at[dest].set(
        order, mode="drop")
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(n_slots) * tile, side="right"), held - 1)
    return (row_pair, tile_expert.astype(jnp.int32),
            (ends[-1] // tile).astype(jnp.int32), counts)


def _expert_steps(counts, tile, slots):
    """The loops' steps from the pairs per held expert (``expert_tiles``'
    ``counts``; ``slots``: its capacity in tiles): with ``most`` =
    ``EXPERT_TILES_A_STEP`` an expert with ``n`` tiles gives ``n // most``
    *big steps* of ``most`` consecutive tiles, all its own, and ``n %
    most`` single tiles.  -> (``big`` int32: the
    first tile of every big step; how many there are; ``single`` int32: the
    tile of every single step; how many there are).  Every tile in use is
    in one step; past their counts the lists hold no step."""
    most = EXPERT_TILES_A_STEP
    held = counts.shape[0]
    tiles = (counts + tile - 1) // tile
    first = jnp.cumsum(tiles) - tiles
    big = tiles // most

    def listed(per_expert, size):
        """Step ``i`` of ``size``: whose it is, and which of its own."""
        ends = jnp.cumsum(per_expert)
        i = jnp.arange(size, dtype=jnp.int32)
        e = jnp.minimum(jnp.searchsorted(ends, i, side="right"), held - 1)
        return e, i - (ends - per_expert)[e], ends[-1].astype(jnp.int32)

    e, j, n_big = listed(big, slots // most)
    big_first = first[e] + most * j
    e, j, n_single = listed(tiles - most * big,
                            min(slots, held * (most - 1)))
    single = first[e] + most * big[e] + j
    return (big_first.astype(jnp.int32), n_big, single.astype(jnp.int32),
            n_single)


def _tile_rows(t, row_pair, weights_flat, k, tile, tiles=1):
    """The rows of the ``tiles`` tiles from tile ``t`` on (one expert's),
    the real ones first: how many are real, the token
    each reads (padding: token 0), where each adds into a ``(tokens, ...)``
    and into the flat ``(tokens * k,)`` sum (padding: past the end, so it
    adds nothing: :func:`_add_rows`), and the routing weights (padding:
    0)."""
    with _xray.scope("moe.dispatch"):
        pairs = lax.dynamic_slice(row_pair, (t * tile,), (tiles * tile,))
        n = weights_flat.shape[0]
        real = pairs < n
        safe = jnp.where(real, pairs, 0)
        gate = jnp.where(real, jnp.take(weights_flat, safe), 0.0)
        tok = safe // k
        return (jnp.sum(real, dtype=jnp.int32), tok,
                jnp.where(real, tok, n // k), pairs, gate)


def _kernel_adds_rows(units, tile):
    """Whether the loops add a tile's rows with ``_add_rows_kernel``: on an
    accelerator, where a row is whole lane tiles, a tile's rows whole
    sublane tiles, and they fit the kernel's VMEM twice (8 MB at 512 x
    2,048, of the 32 it asks for).  Elsewhere XLA's scatter-add does (on
    the CPU platform always: the kernel would run through the
    interpreter)."""
    return not pallas_interpret() and units % LANES == 0 \
        and tile % 8 == 0 and tile * units <= 2 ** 21


def _zeros_to_add_rows_into(tokens, units, tile):
    """The loops' float32 ``(tokens, units)`` sum.  For the kernel it is
    held ``(tokens, units / 128, 128)``: a token is then whole ``(8, 128)``
    tiles of the array, 8 KB in one piece at 2,048 units, which a copy can
    address; a row of the 2-D array is one sublane of 16 tiles, and Mosaic
    copies no slice that is not aligned to the tiling."""
    if _kernel_adds_rows(units, tile):
        return jnp.zeros((tokens, units // LANES, LANES), jnp.float32)
    return jnp.zeros((tokens, units), jnp.float32)


def _add_rows_kernel(at_ref, n_ref, _, rows_hbm, total_hbm, held, rows,
                     sem_in, sem_rows, sem_out):
    """``total[at[i]] += rows[i]`` for the tile's first ``n`` rows, the sum
    left in HBM and updated in place (``total_hbm`` is the aliased result):
    the rows of ``total`` are copied into ``held`` one by one, all of them
    in flight at once, and the tile's ``rows`` in one piece; added; and
    copied back the same way.  Every real row of a tile is another row of
    ``total`` (``moe_experts``' precondition), so no copy waits for
    another.  What bounds it on a v5e is issuing the 2 x ``n`` copies, not
    their bytes: starting the next rows' reads under the add and the
    writes (chunks of 16 to 256 rows) changed nothing (PERF.md, PR 33)."""
    def every_real_row(fn):
        def body(i, carry):
            fn(i)
            return carry

        lax.fori_loop(0, n_ref[0], body, 0)

    def copy_in(i):
        return pltpu.make_async_copy(total_hbm.at[at_ref[i]], held.at[i],
                                     sem_in)

    def copy_out(i):
        return pltpu.make_async_copy(held.at[i], total_hbm.at[at_ref[i]],
                                     sem_out)

    copy_rows = pltpu.make_async_copy(rows_hbm, rows, sem_rows)
    copy_rows.start()
    every_real_row(lambda i: copy_in(i).start())
    copy_rows.wait()
    every_real_row(lambda i: copy_in(i).wait())
    held[...] = held[...] + rows[...].reshape(held.shape)
    every_real_row(lambda i: copy_out(i).start())
    every_real_row(lambda i: copy_out(i).wait())


def _add_rows(total, n, at, rows, tile=None):
    """``total[at[i]] += rows[i]`` for one step's rows, in place: ``total``
    is a loop's carry.  The first ``n`` rows are real and no two of them
    name the same row of ``total``; the others name a row past its end and
    add nothing.  A 3-D ``total`` (``_zeros_to_add_rows_into``) takes the
    kernel, which keeps all the rows' copies in flight; XLA's scatter-add,
    which may assume nothing about the rows, finishes one row's read, add
    and write before it starts the next (PERF.md, PR 33).  A big step's
    rows are added ``tile`` at a time, on every platform."""
    tile = tile or rows.shape[0]
    if rows.shape[0] > tile:
        for j in range(rows.shape[0] // tile):
            rows_j = slice(j * tile, (j + 1) * tile)
            total = _add_rows(total, jnp.clip(n - j * tile, 0, tile),
                              at[rows_j], rows[rows_j])
        return total
    with _xray.scope("moe.combine"):
        if total.ndim < 3:
            return total.at[at].add(rows, mode="drop")
        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            _add_rows_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,), in_specs=[in_hbm, in_hbm],
                out_specs=in_hbm,
                scratch_shapes=[
                    pltpu.VMEM((tile,) + total.shape[1:], jnp.float32),
                    pltpu.VMEM(rows.shape, jnp.float32)]
                + [pltpu.SemaphoreType.DMA(())] * 3),
            out_shape=jax.ShapeDtypeStruct(total.shape, total.dtype),
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=32 * 1024 * 1024),
            name="moe_add_rows", interpret=pallas_interpret(),
        )(at, n.reshape(1), total, rows)


def _summed_rows(total, like):
    """A loop's finished sum as ``(tokens, units)`` of ``like``'s type.
    The kernel's 3-D sum is brought back by a kernel too, one pass over
    it: XLA converts first and then moves the result twice."""
    with _xray.scope("moe.combine"):
        tokens = total.shape[0]
        block = math.gcd(tokens, 256)
        if total.ndim < 3 or block % 8:
            return total.reshape(like.shape).astype(like.dtype)

        def kernel(total_ref, out_ref):
            out_ref[...] = total_ref[...].reshape(out_ref.shape).astype(
                out_ref.dtype)

        return pl.pallas_call(
            kernel, grid=(tokens // block,),
            in_specs=[pl.BlockSpec((block,) + total.shape[1:],
                                   lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((block, like.shape[1]), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(like.shape, like.dtype),
            name="moe_summed_rows", interpret=pallas_interpret())(total)


def _expert_forward(xt, wg, wu, wd):
    g = _dot(xt, wg, ((1,), (0,)))
    u = _dot(xt, wu, ((1,), (0,)))
    h = (jax.nn.silu(g) * u).astype(xt.dtype)
    return g, u, h, _dot(h, wd, ((1,), (0,)))


@jax.custom_vjp
def _gated_silu(x, wg, wu, wd):
    return _gated_silu_fwd(x, wg, wu, wd)[0]


def _gated_silu_fwd(x, wg, wu, wd):
    last = (x.ndim - 1,)
    g = _dot(x, wg, (last, (1,)))
    u = _dot(x, wu, (last, (1,)))
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return _dot(h, wd, (last, (1,))).astype(x.dtype), (x, wg, wu, wd, g, u)


def _gated_silu_bwd(res, dy):
    """``dh = dy W_down`` in float32, then ``dg`` and ``du`` from it and
    float32 ``g`` and ``u``, each rounded once to the data's type before
    their products, as ``_routed_sum_bwd`` does: the products of ``dx``,
    ``W_gate``'s and ``W_up``'s gradients take operands in that type and
    sum in float32, like the forward pass's.  Left to autodiff, ``dg`` and
    ``du`` stayed float32 and each of those four products' fusions formed
    them again from ``g``, ``u`` and ``dh`` on every pass over its result
    (a v5e ran the backward pass's six products at 50-59 % of its peak, the
    forward's three at 91-95 %).  ``W_down``'s gradient reads ``h``, which
    XLA forms inside that product from ``g`` and ``u``; barriers that kept
    it a pass of its own cost a step's temporaries up to 1.9 GB (PERF.md,
    Findings).  In float32 the roundings are none and the result is
    autodiff's, up to the order of the sums."""
    x, wg, wu, wd, g, u = res
    last = (x.ndim - 1,)
    rows = tuple(range(x.ndim - 1))
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    dh = _dot(dy, wd, (last, (0,)))
    sig = jax.nn.sigmoid(g)
    dg = (dh * u * sig * (1 + g * (1 - sig))).astype(x.dtype)
    du = (dh * g * sig).astype(x.dtype)
    dx = _dot(dg, wg, (last, (0,))) + _dot(du, wu, (last, (0,)))
    return (dx.astype(x.dtype), _dot(dg, x, (rows, rows)).astype(wg.dtype),
            _dot(du, x, (rows, rows)).astype(wu.dtype),
            _dot(dy, h, (rows, rows)).astype(wd.dtype))


_gated_silu.defvjp(_gated_silu_fwd, _gated_silu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _routed_sum(x, weights, wg, wu, wd, row_pair, tile_expert, n_tiles,
                counts, k, tile):
    return _routed_sum_fwd(x, weights, wg, wu, wd, row_pair, tile_expert,
                           n_tiles, counts, k, tile)[0]


def _routed_sum_fwd(x, weights, wg, wu, wd, row_pair, tile_expert, n_tiles,
                    counts, k, tile):
    """The forward pass tile by tile.  It takes no big steps: a tile's
    three products already run near the MXU's rate, and with them the
    step program's temporaries packed 130 MB worse (PERF.md, PR 35)."""
    flat = weights.reshape(-1)

    def body(carry):
        t, out = carry
        n, tok, at, _, gate = _tile_rows(t, row_pair, flat, k, tile)
        e = tile_expert[t]
        with _xray.scope("moe.dispatch"):
            xt = jnp.take(x, tok, axis=0)
        with _xray.scope("moe.experts"):
            yt = _expert_forward(xt, wg[e], wu[e], wd[e])[3]
        with _xray.scope("moe.combine"):
            yt = gate[:, None] * yt
        return t + 1, _add_rows(out, n, at, yt)

    _, out = lax.while_loop(
        lambda c: c[0] < n_tiles, body,
        (jnp.int32(0), _zeros_to_add_rows_into(*x.shape, tile)))
    return _summed_rows(out, x), (x, weights, wg, wu, wd, row_pair,
                                  tile_expert, counts)


def _routed_sum_bwd(k, tile, res, dy):
    """The backward pass step by step (``_expert_steps``), the rows'
    forward recomputed: nothing but the layer's input is kept between the
    passes.  Two loops carry the same sums: over the big steps, then over
    the single tiles.  A big step sends its rows through the expert in
    runs of ``EXPERT_TILES_A_RUN`` tiles (what a single step does to one:
    forward again, ``dh``, the rows' share of ``dx`` and of the routing
    weights' gradient added ``tile`` rows at a time) and keeps, for all of
    its ``EXPERT_TILES_A_STEP`` tiles, only the five operands of the three
    weight gradients in the data's type (``xt``, ``dg``, ``du``, ``h``,
    ``dyt``: 39 MB at LFM2's widths); each weight gradient is then one
    product that contracts all the rows and one read, add and write of the
    expert's float32 ``(in, width)`` slice of the carried sum.  Nothing is
    kept from one step to the next but the carried sums."""
    x, weights, wg, wu, wd, row_pair, tile_expert, counts = res
    flat = weights.reshape(-1)
    f32 = jnp.float32
    with _xray.scope("moe.dispatch"):
        big, n_big, single, n_single = _expert_steps(
            counts, tile, tile_expert.shape[0])

    def rows_backward(t, e, tiles, dx, dflat):
        """What goes row by row for ``tiles`` tiles from ``t`` on: their
        share of ``dx`` and ``dflat`` added, and the operands of the three
        weight gradients."""
        n, tok, at, pairs, gate = _tile_rows(t, row_pair, flat, k, tile,
                                             tiles)
        with _xray.scope("moe.dispatch"):
            xt = jnp.take(x, tok, axis=0)
            dyt = jnp.take(dy, tok, axis=0)
        with _xray.scope("moe.experts"):
            g, u, h, yt = _expert_forward(xt, wg[e], wu[e], wd[e])
            dgate = jnp.sum(dyt.astype(f32) * yt, axis=-1)
            dyt = (gate[:, None] * dyt.astype(f32)).astype(x.dtype)
            dh = _dot(dyt, wd[e], ((1,), (1,)))
            sig = jax.nn.sigmoid(g)
            dg = (dh * u * sig * (1 + g * (1 - sig))).astype(x.dtype)
            du = (dh * g * sig).astype(x.dtype)
            dxt = _dot(dg, wg[e], ((1,), (1,))) + _dot(du, wu[e],
                                                       ((1,), (1,)))
        return (_add_rows(dx, n, at, dxt, tile),
                _add_rows(dflat, n, pairs, dgate, tile), (xt, dg, du, h, dyt))

    def looped(sums, firsts, n, tiles):
        """``sums`` after the ``n`` steps of ``tiles`` tiles that start at
        the tiles ``firsts``."""
        run = min(tiles, EXPERT_TILES_A_RUN)
        assert tiles % run == 0, (tiles, run)

        def step(carry):
            i, (dx, dflat, dwg, dwu, dwd) = carry
            t = firsts[i]
            e = tile_expert[t]
            kept = []
            for first in range(0, tiles, run):
                dx, dflat, operands = rows_backward(t + first, e, run, dx,
                                                    dflat)
                kept.append(operands)
            xt, dg, du, h, dyt = (jnp.concatenate(a) for a in zip(*kept))
            with _xray.scope("moe.experts"), _xray.scope("moe.wgrad"):
                dwg = dwg.at[e].add(_dot(xt, dg, ((0,), (0,))))
                dwu = dwu.at[e].add(_dot(xt, du, ((0,), (0,))))
                dwd = dwd.at[e].add(_dot(h, dyt, ((0,), (0,))))
            return i + 1, (dx, dflat, dwg, dwu, dwd)

        if not firsts.size:     # fewer rows than such a step takes
            return sums
        return lax.while_loop(lambda c: c[0] < n, step,
                              (jnp.int32(0), sums))[1]

    sums = (_zeros_to_add_rows_into(*x.shape, tile),
            jnp.zeros(flat.shape, f32), jnp.zeros(wg.shape, f32),
            jnp.zeros(wu.shape, f32), jnp.zeros(wd.shape, f32))
    sums = looped(sums, big, n_big, EXPERT_TILES_A_STEP)
    dx, dflat, dwg, dwu, dwd = looped(sums, single, n_single, 1)

    def no_gradient(a):
        return _np.zeros(a.shape, dtype=jax.dtypes.float0)

    return (_summed_rows(dx, x), dflat.reshape(weights.shape).astype(
        weights.dtype), dwg.astype(wg.dtype), dwu.astype(wu.dtype),
        dwd.astype(wd.dtype), no_gradient(row_pair),
        no_gradient(tile_expert), no_gradient(counts[0]),   # n_tiles
        no_gradient(counts))


_routed_sum.defvjp(_routed_sum_fwd, _routed_sum_bwd)


@register("_contrib_moe_experts", num_outputs=3, aliases=("moe_experts",))
def moe_experts(data, expert_ids, expert_weights, gate_weight, up_weight,
                down_weight, first_expert=0, tile=DEFAULT_EXPERT_TILE, **_):
    """The held experts' part of a routed sum: ``y[n] = sum over the
    choices j of token n whose expert is held of weights[n, j] *
    E_ids[n, j](x[n])``, every expert a gated-SiLU feed-forward.

    ``data`` (tokens, in); ``expert_ids`` / ``expert_weights`` (tokens, k)
    over all of the model's experts (``moe_route``'s results); the held
    experts' weights stacked ``(held, in, width)``, ``(held, in, width)``,
    ``(held, width, in)`` for expert ids ``first_expert ..``.  Choices of
    absent experts add nothing.  No pair is dropped at any imbalance; the
    loop runs over the row tiles in use (``expert_tiles``), so the work
    follows the pairs actually routed here.  -> (y, pairs routed to held
    experts, largest held expert's pairs over their mean), the two
    counters float32 scalars.

    **Precondition: a token's ``k`` ids are distinct** (``moe_route``'s
    are, being a ``top_k``'s).  A tile's real rows are one expert's pairs
    in flat pair order (``expert_tiles`` sorts stably), so with distinct
    ids no two of them are the same token, and on an accelerator the loops
    add a tile into the float32 sum with a kernel that keeps many rows'
    read-add-write in flight at once (:func:`_add_rows`).  With an id
    repeated within a token two rows of a tile meet and one addend is
    lost; XLA's scatter-add, the CPU platform's path, adds them one after
    the other, so only a chip shows it.  Nothing checks the ids (they are
    traced values).  The kernel cannot sit in a program that GSPMD
    partitions over several chips (as ``flash_attention``'s cannot)."""
    held = gate_weight.shape[0]
    k = expert_ids.shape[-1]
    expert_ids = expert_ids.astype(jnp.int32)   # ids may arrive as floats
    with _xray.scope("moe.dispatch"):
        row_pair, tile_expert, n_tiles, counts = expert_tiles(
            expert_ids, int(first_expert), held, int(tile))
        routed = jnp.sum(counts).astype(jnp.float32)
        load = jnp.max(counts).astype(jnp.float32) * held \
            / jnp.maximum(routed, 1.0)
    y = _routed_sum(data, expert_weights, gate_weight, up_weight,
                    down_weight, row_pair, tile_expert, n_tiles, counts,
                    int(k), int(tile))
    return y, routed, load


# ------------------------------------------------- head fused with its loss


def _head_rows(h, weight, y):
    """A chunk's float32 logits, their log-sum-exp and its loss rows (0
    where the label is negative)."""
    logits = _dot(h, weight, ((1,), (1,)))
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(y, 0)[:, None], axis=-1)[:, 0]
    return logits, logz, jnp.where(y >= 0, logz - picked, 0.0)


def _head_dlogits(h, weight, y, g=None):
    """A chunk's loss rows and ``dlogits = g (softmax - onehot(y))``, 0
    where the label is negative, ``g`` a row's cotangent (1 where it is
    None), cast to the operands' dtype for the products that take it."""
    logits, logz, loss = _head_rows(h, weight, y)
    d = jnp.exp(logits - logz[:, None]) - (
        lax.broadcasted_iota(jnp.int32, logits.shape, 1) == y[:, None])
    if g is not None:
        d = d * g[:, None]
    return loss, jnp.where((y >= 0)[:, None], d, 0.0).astype(h.dtype)


def _head_scan(data, weight, label, chunk, g=None):
    """Over the chunks of rows, ``dW`` summed in the weight's dtype, as the
    transpose of a loop over checkpointed chunks sums it (a float32 carry
    made Mellum2's step hold 227 MB more temporaries: PERF.md, PR 38).
    -> (loss rows, dh, dW): three products a chunk; with ``g``, dW alone:
    two."""
    rows, width = data.shape
    n = rows // chunk
    xs = (data.reshape(n, chunk, width), label.reshape(n, chunk))
    if g is not None:
        xs += (g.reshape(n, chunk),)

    def body(dw, args):
        h = args[0]
        loss, d = _head_dlogits(h, weight, *args[1:])
        dw = dw + _dot(d, h, ((0,), (0,))).astype(dw.dtype)
        if g is not None:
            return dw, ()
        return dw, (loss, _dot(d, weight, ((1,), (0,))).astype(h.dtype))

    dw, rest = lax.scan(body, jnp.zeros_like(weight), xs)
    if g is not None:
        return dw
    loss, dh = rest
    return loss.reshape(rows), dh.reshape(rows, width), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_head_loss(data, weight, label, chunk):
    """Not differentiated: a chunk's logits and its loss rows, one product
    a chunk, nothing kept."""
    rows = data.shape[0]
    with _xray.scope("lm_head"):
        out = lax.map(lambda a: _head_rows(a[0], weight, a[1])[2],
                      (data.reshape(rows // chunk, chunk, -1),
                       label.reshape(rows // chunk, chunk)))
    return out.reshape(rows)


def _fused_head_loss_fwd(data, weight, label, chunk):
    """The gradient for a cotangent of 1 on every row, formed while a
    chunk's logits are in hand: three products a chunk, none left for the
    backward pass."""
    with _xray.scope("lm_head"):
        loss, dh, dw = _head_scan(data, weight, label, chunk)
    return loss, (dh, dw, data, weight, label)


def _fused_head_loss_bwd(chunk, res, g):
    """``dh`` is the kept one times a row's cotangent, whatever it is.
    ``dW``: where ``g`` is one value over the labelled rows (what a mean of
    the rows, or a row's sum times a constant, sends back), the kept one
    times it, no product; else a chunk's logits again and the ``dW``
    product, under ``lm_head.recompute``.  Decided on the device; exact
    either way."""
    dh_unit, dw_unit, data, weight, label = res
    with _xray.scope("lm_head"):
        g = g.astype(jnp.float32)
        labelled = label >= 0
        g0 = g[jnp.argmax(labelled)]
        same = jnp.all(jnp.where(labelled, g == g0, True))
        dh = (dh_unit * jnp.where(labelled, g, 0.0)[:, None]).astype(
            data.dtype)

        def again():
            with _xray.scope("lm_head.recompute"):
                return _head_scan(data, weight, label, chunk, g)

        dw = lax.cond(same, lambda: (dw_unit * g0).astype(weight.dtype),
                      again)
    return dh, dw, None


_fused_head_loss.defvjp(_fused_head_loss_fwd, _fused_head_loss_bwd)


@register("_contrib_linear_cross_entropy",
          aliases=("linear_cross_entropy",))
def linear_cross_entropy(data, weight, label, chunk=DEFAULT_LOSS_CHUNK, **_):
    """``-log softmax(data @ weight.T)[label]`` per row, the head's product
    fused with its loss over chunks of rows so that the float32 logits
    never stand whole.  Differentiated, the forward pass forms the gradient
    while a chunk's logits are in hand (``dlogits = softmax - onehot``,
    three products a chunk); the backward pass scales it by the cotangent,
    and recomputes a chunk's logits, for ``dW`` alone, only where that is
    not one value over the labelled rows.  ``data`` (rows, in), ``weight``
    (classes, in), ``label`` (rows,) integer; a negative label gives 0 (the
    row is left out).  Fewer rows than ``chunk`` take one chunk; otherwise
    the chunk is the largest common divisor of the two.  -> (rows,)
    float32."""
    rows = data.shape[0]
    chunk = rows if rows <= int(chunk) else math.gcd(rows, int(chunk))
    return _fused_head_loss(data, weight, label.astype(jnp.int32), chunk)


# --------------------------------------------------- hyper-connections
# The residual stream widened to n streams (Hyper-Connections, Zhu et al.,
# arXiv:2409.19606) whose mixing matrix is kept doubly stochastic (mHC,
# manifold-constrained hyper-connections, DeepSeek-AI 2025).  The stream of
# a token is held as one row of ``n x C`` lanes, stream ``i`` at lanes ``i C
# .. (i + 1) C - 1``: ``(B, S, n C)``, whose lanes a v5e tiles without
# padding and whose streams are lane-aligned slices (held ``(B, S, n, C)``,
# the compiler copies the stream into another layout on the way into the
# mixes).  The coefficients hold the token axes last, ``(n, B, S)`` and
# ``(n, n, B, S)``: the tokens lie on the lanes, and Sinkhorn's sums over
# the n x n matrix are sums of whole arrays.


def _sinkhorn(m, iters, eps):
    """``m (n, n, ...)`` positive, rows (axis 1 summed) then columns (axis
    0 summed) divided by their sums plus ``eps``, ``iters`` times: a loop
    of a fixed trip count, which autodiff turns into a scan."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=0, keepdims=True) + eps)

    return lax.fori_loop(0, int(iters), one, m)


@register("_contrib_hc_coefficients", num_outputs=3,
          aliases=("hc_coefficients",))
def hc_coefficients(data, phi, bias, alpha, streams=4, iters=20, eps=1e-6,
                    clamp_min=-30.0, clamp_max=30.0, **_):
    """The coefficients of one sublayer's hyper-connection, from the widened
    stream ``data (B, S, n C)``: with ``x`` a token's ``n C`` lanes, ``r = x
    / sqrt(mean(x^2) + eps)``, ``[p | q | R] = [alpha_0 (r phi)_pre |
    alpha_1 (r phi)_post | alpha_2 (r phi)_res] + bias`` (``phi (n C, 2n +
    n^2)``, ``bias (2n + n^2,)``, ``alpha (3,)``), ``h_pre = sigmoid(p)``,
    ``h_post = 2 sigmoid(q)`` and ``H_res = Sinkhorn_iters(exp(clamp(R)))``,
    ``R`` read row-major as ``n x n`` (``H_res[i, j]`` carries stream ``j``
    into stream ``i``), rows normalised before columns, ``eps`` in every
    denominator.  -> ``h_pre``, ``h_post (n, B, S)``, ``H_res (n, n, B,
    S)``, float32, the token axes last.  ``r phi`` is ``(x phi) / sqrt(...)``:
    the product takes ``x`` and ``phi`` in the stream's dtype and
    accumulates in float32; the norm's scale, the sigmoids and Sinkhorn (a
    loop of a fixed trip count) are float32.  Differentiated by autodiff.
    Under the scope ``hc.coef``."""
    n, f32 = int(streams), jnp.float32
    with _xray.scope("hc.coef"):
        proj = lax.dot_general(data, phi.astype(data.dtype),
                               (((data.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=f32)
        x = data.astype(f32)
        inv = lax.rsqrt(jnp.mean(x * x, axis=-1) + eps)
        a = alpha.astype(f32)
        scale = jnp.concatenate([jnp.broadcast_to(a[0], (n,)),
                                 jnp.broadcast_to(a[1], (n,)),
                                 jnp.broadcast_to(a[2], (n * n,))])
        z = jnp.moveaxis(proj, -1, 0) * inv
        z = z * scale.reshape((-1,) + (1,) * inv.ndim) \
            + bias.astype(f32).reshape((-1,) + (1,) * inv.ndim)
        h_pre = jax.nn.sigmoid(z[:n])
        h_post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
        res = jnp.exp(jnp.clip(z[2 * n:], clamp_min, clamp_max))
        h_res = _sinkhorn(res.reshape((n, n) + inv.shape), iters, eps)
        return h_pre, h_post, h_res


def _streams(data, n):
    """The ``n`` lane-aligned streams of ``data (..., n C)``."""
    c = data.shape[-1] // n
    return [data[..., i * c:(i + 1) * c] for i in range(n)]


def _sums_over_lanes(a, b):
    """``sum_c a[..., c] b[..., c]`` in float32: ``(...)``."""
    return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32), axis=-1)


@jax.custom_vjp
def _pre_mix(data, h_pre):
    total = sum(w[..., None] * x.astype(jnp.float32) for w, x in
                zip(h_pre, _streams(data, h_pre.shape[0])))
    return total.astype(data.dtype)


def _pre_mix_fwd(data, h_pre):
    return _pre_mix(data, h_pre), (data, h_pre)


def _pre_mix_bwd(res, g):
    data, h_pre = res
    g32 = g.astype(jnp.float32)
    d_data = jnp.concatenate([w[..., None] * g32 for w in h_pre], axis=-1)
    d_h = jnp.stack([_sums_over_lanes(g, x)
                     for x in _streams(data, h_pre.shape[0])])
    return d_data.astype(data.dtype), d_h


_pre_mix.defvjp(_pre_mix_fwd, _pre_mix_bwd)


@register("_contrib_hc_pre_mix", aliases=("hc_pre_mix",))
def hc_pre_mix(data, h_pre, **_):
    """A sublayer's input from the widened stream: ``u = sum_i h_pre[i]
    X_i``, ``data (B, S, n C)``, ``h_pre (n, B, S)`` -> ``(B, S, C)`` in
    ``data``'s dtype, float32 arithmetic.  The backward pass is written by
    hand: it keeps ``data`` as it came and forms the float32 lanes inside
    the passes that read them, so no float32 copy of the stream is saved
    or written.  Under the scope ``hc.mix``."""
    with _xray.scope("hc.mix"):
        return _pre_mix(data, h_pre)


@jax.custom_vjp
def _post_mix(data, h_res, h_post, update):
    n = h_post.shape[0]
    xs = [x.astype(jnp.float32) for x in _streams(data, n)]
    y = update.astype(jnp.float32)
    out = [sum(h_res[i, j][..., None] * xs[j] for j in range(n))
           + h_post[i][..., None] * y for i in range(n)]
    return jnp.concatenate(out, axis=-1).astype(data.dtype)


def _post_mix_fwd(data, h_res, h_post, update):
    return _post_mix(data, h_res, h_post, update), \
        (data, h_res, h_post, update)


def _post_mix_bwd(res, g):
    data, h_res, h_post, update = res
    n = h_post.shape[0]
    gs, xs = _streams(g, n), _streams(data, n)
    g32 = [a.astype(jnp.float32) for a in gs]
    d_data = jnp.concatenate(
        [sum(h_res[i, j][..., None] * g32[i] for i in range(n))
         for j in range(n)], axis=-1)
    d_update = sum(h_post[i][..., None] * g32[i] for i in range(n))
    d_res = jnp.stack([jnp.stack([_sums_over_lanes(gs[i], xs[j])
                                  for j in range(n)]) for i in range(n)])
    d_post = jnp.stack([_sums_over_lanes(a, update) for a in gs])
    return (d_data.astype(data.dtype), d_res, d_post,
            d_update.astype(update.dtype))


_post_mix.defvjp(_post_mix_fwd, _post_mix_bwd)


@register("_contrib_hc_post_mix", aliases=("hc_post_mix",))
def hc_post_mix(data, h_res, h_post, update, **_):
    """The widened stream after a sublayer: ``X'_i = sum_j H_res[i, j] X_j
    + h_post[i] y``, ``data (B, S, n C)``, ``h_res (n, n, B, S)``, ``h_post
    (n, B, S)``, the sublayer's result ``update (B, S, C)`` -> ``(B, S, n
    C)`` in ``data``'s dtype, float32 arithmetic.  The backward pass is
    written by hand, as ``hc_pre_mix``'s: the cotangent and the stream are
    read in their dtype and widened inside each pass (``dX_j = sum_i
    H_res[i, j] dX'_i``, ``dy = sum_i h_post[i] dX'_i``, ``dH_res[i, j] =
    dX'_i . X_j``, ``dh_post[i] = dX'_i . y``).  Under the scope
    ``hc.mix``."""
    with _xray.scope("hc.mix"):
        return _post_mix(data, h_res, h_post, update)


OP_INPUT_NAMES.update({
    "_contrib_rms_norm": ("data", "gamma"),
    "_contrib_rope": ("data",),
    "_contrib_gated_silu": ("data", "gate_weight", "up_weight",
                            "down_weight"),
    "_contrib_mla_qkv": ("data", "qa_weight", "qb_weight", "kva_weight",
                         "kvb_weight", "qnorm_weight", "kvnorm_weight"),
    "_contrib_mla_out": ("data", "weight"),
    "_contrib_gqa_qkv": ("data", "q_weight", "k_weight", "v_weight",
                         "qnorm_weight", "knorm_weight"),
    "_contrib_gqa_out": ("data", "weight"),
    "_contrib_head_gate": ("data", "gate_data", "gate_weight"),
    "_contrib_gated_short_conv": ("data", "weight"),
    "_contrib_moe_route": ("data", "router_weight", "router_bias"),
    "_contrib_moe_experts": ("data", "expert_ids", "expert_weights",
                             "gate_weight", "up_weight", "down_weight"),
    "_contrib_linear_cross_entropy": ("data", "weight", "label"),
    "_contrib_hc_coefficients": ("data", "phi", "bias", "alpha"),
    "_contrib_hc_pre_mix": ("data", "h_pre"),
    "_contrib_hc_post_mix": ("data", "h_res", "h_post", "update"),
})
