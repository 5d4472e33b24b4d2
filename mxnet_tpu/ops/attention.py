"""Fused multi-head attention — Pallas TPU flash-attention kernels.

The reference framework has no fused attention; its transformer helpers
(`src/operator/contrib/transformer.cc`: interleaved_matmul_selfatt_qk /
valatt, div_sqrt_dim) materialise the full (seq, seq) score matrix in
HBM.  On TPU that is HBM-bandwidth-bound; the TPU-native design is a
flash-attention kernel that tiles Q/K/V through VMEM, keeps the online
softmax statistics in VMEM scratch across the (sequential) K-block grid
steps, and feeds the MXU with (block_k x d) @ (d x block_q) matmuls whose
operands are in the dtype the inputs arrive in (bfloat16 inputs are never
cast up; every product accumulates in float32, and the scores, the
softmax statistics and the accumulators are float32).  With ``causal``
the kernels do the causal work only: a grid step above the diagonal
fetches nothing and computes nothing, and a block on the diagonal is
computed in tiles below it.  With a ``window`` (sliding-window attention:
query ``i`` sees the keys ``i - window < j <= i``) the band has a second
edge: the grid holds only as many key blocks a query block as the band
can touch, and a block that the trailing edge crosses is computed in
tiles inside it.

Layout: (batch, heads, seq, head_dim) throughout.  Grouped-query heads:
``k`` and ``v`` may have ``heads // group`` heads; query head ``h`` reads
key head ``h // group`` through the kernels' index maps (no copy of a key
head is made in HBM), and the dk/dv kernel sums over a group's query heads
in its accumulators.

Public entry points
-------------------
flash_attention(q, k, v, causal=..., window=..., sm_scale=...)  — custom_vjp
fused op
registered ops: ``_contrib_flash_attention`` plus the reference transformer
helper ops (``_contrib_div_sqrt_dim``, interleaved matmul family).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError
from ..util import pallas_interpret
from .registry import register

# Blocks, measured on v5e (tools/bench_attention.py, PR 29; PERF.md has the
# table): causal, bfloat16, 2 x 32 heads x 4,096, head sizes 192 / 128,
# forward + dq + dk/dv in ms:
#     1,024 / 1,024  3.61 + 4.37 + 4.98 = 12.96
#     1,024 / 512    4.32 + 5.07 + 5.97 = 15.36
#       512 / 512    4.40 + 5.40 + 5.59 = 15.39
#       512 / 1,024  4.48 + 5.27 + 5.95 = 15.69
#       256 / 512    5.93 + 7.56 + 6.76 = 20.25
#       256 / 256    7.81 + 10.91 + 8.37 = 27.09
# A grid step that runs costs a pass over the query block's statistics and
# accumulator whatever its key block's width, and a skipped one still its
# ~0.35 us, so the fewest steps win although a block on the diagonal is
# half masked; cutting that block into tiles of _TRIANGLE_TILE took
# 1,024 / 1,024 from 14.68 to 12.96 (128: 13.46, 512: 13.40).  At
# 2 x 8 x 2,048, d = 64 causal and d = 128 full, 1,024 / 1,024 also wins
# (0.64 against 1.01 ms, 1.04 against 1.66).  Mosaic's 16 MiB of scoped
# VMEM takes 1,024 / 1,024 up to _WIDE_ROW_BYTES of a q row and a v row
# together (bfloat16 256 / 256, float32 128 / 128); past that (float32
# 192 / 128, bfloat16 512 / 512) it does not compile, and the blocks are
# 256 / 512 (r3's measurement with float32 tiles: 2.9x the forward of
# 128 / 128 at seq 4096, d = 64).
# Grouped-query heads of 128 (PR 34): 1 x 32 query heads on 4 key heads x
# 16,384, 128 / 128, bfloat16, causal and with a window of 1,024 (the grid
# then holds the band's blocks only), forward + dq + dk/dv in ms:
#                     causal                          window 1,024
#     1,024 / 1,024  19.50 + 19.37 + 24.65 = 63.52   3.77 + 3.28 + 3.99 = 11.04
#     1,024 / 512    21.35 + 21.45 + 27.35 = 70.15   4.91 + 4.95 + 6.29 = 16.15
#       512 / 1,024  21.45 + 21.61 + 27.33 = 70.40   5.05 + 5.08 + 6.22 = 16.34
#       512 / 512    23.73 + 24.97 + 29.71 = 78.41   4.72 + 4.10 + 4.74 = 13.56
#       256 / 512                                    6.98 + 5.66 + 7.08 = 19.72
#       256 / 256                                    7.74 + 6.54 + 8.33 = 22.61
# The window's band is 1/8.26 of the causal half and costs 1/5.8 of its time:
# both blocks a query block runs are half masked (10 of 16 tiles computed).
_WIDE_BLOCKS = (1024, 1024)
_NARROW_BLOCKS = (256, 512)
_WIDE_ROW_BYTES = 1024
# Float32 operands at jax's ``highest`` matmul precision (a benchmark cell's
# check) are multiplied in six bfloat16 passes over split copies of a tile:
# at 1,024 / 1,024 the kernels then need 19-22 MiB of scoped VMEM (head
# sizes 64 / 64 and 128 / 128, compiled for a v5e), so 4-byte operands ask
# for more than the default 16.
_FLOAT32_VMEM_BYTES = 32 * 2 ** 20
_TRIANGLE_TILE = 256
_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# reference (unfused) implementation — the oracle, and the CPU platform's path
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal=False, sm_scale=None, window=None):
    """Unfused attention: softmax(q k^T * scale) v, fp32 accumulation.
    ``window`` (with ``causal``): query ``i`` sees the keys ``i - window < j
    <= i``."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if sm_scale is None else sm_scale
    group = _group(q, k)
    if group > 1:       # the oracle repeats the key heads; the kernels do not
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (qlen, klen), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (qlen, klen), 1)
        hidden = col > row
        if window is not None:
            hidden = hidden | (row - col >= window)
        s = jnp.where(hidden, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _scoped_vmem(operand):
    """``compiler_params`` of a kernel over ``operand``'s dtype: Mosaic's
    default but for 4-byte operands (``_FLOAT32_VMEM_BYTES``)."""
    if operand.dtype.itemsize < 4:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_FLOAT32_VMEM_BYTES)


def _group(q, k):
    """Query heads per key head of ``q (b, h, ..)`` and ``k (b, hk, ..)``."""
    heads, kv_heads = q.shape[1], k.shape[1]
    if heads % kv_heads:
        raise MXNetError("flash_attention: %d query heads are not a multiple "
                         "of %d key heads" % (heads, kv_heads))
    return heads // kv_heads


def _kv_head(z, group):
    """The row of ``k (b * hk, ..)`` that row ``z`` of ``q (b * h, ..)``
    reads: ``h = hk * group``, so ``b h + head`` maps to ``b hk + head //
    group``."""
    return z if group == 1 else z // group


# ---------------------------------------------------------------------------
# the causal rule, and a window's, over blocks
# ---------------------------------------------------------------------------
#
# Masking is top-left aligned: key ``c`` is seen by query ``r`` when
# ``c <= r``, whatever the two lengths, and with a ``window`` only when
# ``r - c < window`` as well: the band between the diagonal and the
# window's trailing edge.  Over blocks (query block ``i`` of ``block_q``
# rows, key block ``j`` of ``block_k`` columns) a grid step either runs or
# is skipped whole, and a step that is skipped must cost nothing: its index
# map names the block the neighbouring step that runs has in VMEM already,
# and Pallas issues no copy for an unchanged block index.  Without a window
# the inner grid axis counts all the blocks of the other sequence; with one
# it counts from the first block the band reaches (``_first_key_block``,
# ``_first_query_block``) and is only as long as the band is wide in blocks
# (``_band_blocks``), so what lies behind the window is not in the grid at
# all.  The functions take program ids or plain integers.

def _least(a, b):
    """``min``, of program ids or of plain integers (which stay plain:
    ``_band_blocks`` counts while a kernel is being traced)."""
    plain = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if plain else jnp.minimum(a, b)


def _most(a, b):
    plain = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if plain else jnp.maximum(a, b)


def _runs(i, j, block_q, block_k, window=None):
    """Block (i, j) holds a pair the rule lets through."""
    run = j * block_k <= i * block_q + block_q - 1
    if window is not None:
        run = run & (j * block_k + block_k - 1 >= i * block_q - (window - 1))
    return run


def _crosses_an_edge(i, j, block_q, block_k, window=None):
    """Block (i, j) holds a pair the rule masks (a block wholly inside the
    band needs no mask): the diagonal crosses it, or the window's trailing
    edge does."""
    masked = j * block_k + block_k - 1 > i * block_q
    if window is not None:
        masked = masked | (i * block_q + block_q - 1 - j * block_k >= window)
    return masked


def _first_key_block(i, block_q, block_k, window):
    """The first key block that query block ``i`` runs."""
    if window is None:
        return 0
    return _most(i * block_q - (window - 1), 0) // block_k


def _last_key_block(i, block_q, block_k, num_k):
    return _least((i * block_q + block_q - 1) // block_k, num_k - 1)


def _first_query_block(j, block_q, block_k, num_q):
    """The first query block that key block ``j`` runs (with fewer queries
    than keys, none may: then the last)."""
    return _least((j * block_k) // block_q, num_q - 1)


def _last_query_block(j, block_q, block_k, num_q, window):
    if window is None:
        return num_q - 1
    return _least((j * block_k + block_k - 1 + window - 1) // block_q,
                  num_q - 1)


def _band_blocks(block_q, block_k, num_q, num_k, window):
    """(key blocks a query block runs at most, query blocks a key block
    runs at most): the two inner grid axes' lengths under a window."""
    def most(first, last, n):
        return max(last(x) - first(x) + 1 for x in range(n))

    return (most(lambda i: _first_key_block(i, block_q, block_k, window),
                 lambda i: _last_key_block(i, block_q, block_k, num_k),
                 num_q),
            most(lambda j: _first_query_block(j, block_q, block_k, num_q),
                 lambda j: _last_query_block(j, block_q, block_k, num_q,
                                             window), num_k))


def _kv_block(i, j, block_q, block_k, num_k, window=None):
    """The key / value block grid step (i, j) of the forward and dq kernels
    names (key blocks innermost; ``j`` counts from the first block of the
    band): its own while it runs, after that the last one that ran."""
    last = _last_key_block(i, block_q, block_k, num_k)
    if window is not None:
        j = j + _first_key_block(i, block_q, block_k, window)
    return _least(j, last)


def _q_block(i, j, block_q, block_k, num_q, window=None):
    """The query block grid step (j, i) of the dk/dv kernel names (query
    blocks innermost): its own once it runs, before that the first one
    that will; under a window ``i`` counts from that one, and after the
    last that runs the step names the last."""
    first = _first_query_block(j, block_q, block_k, num_q)
    if window is None:
        return _most(i, first)
    return _least(first + i, _last_query_block(j, block_q, block_k, num_q,
                                               window))


def _cuts_tiles(block_q, block_k):
    """A masked step is cut into ``_TRIANGLE_TILE``s where the blocks are
    equal (an edge then meets a block at an offset known when the kernel is
    traced) and hold several tiles."""
    t = _TRIANGLE_TILE
    return block_q == block_k and block_q % t == 0 and block_q != t


def _masked_offsets(block_q, block_k, window):
    """The ``i - j`` of the steps that run masked, where the tiles of such a
    step depend on it (``_cuts_tiles``): 0 for the diagonal, and the one or
    two offsets at which the window's trailing edge crosses a block."""
    if not _cuts_tiles(block_q, block_k) or window is None:
        return [0]
    return [d for d in range(0, (window + block_q - 2) // block_q + 1)
            if d == 0 or d * block_q + block_q - 1 >= window]


def _band_steps(step, causal, window, i, j, block_q, block_k, inside=True):
    """Run ``step(offset)`` for grid step (i, j): always and unmasked
    (``offset`` None) without ``causal``; with it, not at all outside the
    band, with the mask in the blocks an edge crosses (``offset``: ``i - j``
    where the step's tiles depend on it, else 0), without it between the
    edges.  ``inside``: the step's block indices lie in the grid's arrays
    (a window's inner axis may count past the last block)."""
    if not causal:
        step(None)
        return
    run = _runs(i, j, block_q, block_k, window)
    if inside is not True:
        run = run & inside
    masked = _crosses_an_edge(i, j, block_q, block_k, window)
    offsets = _masked_offsets(block_q, block_k, window)
    if len(offsets) == 1:
        pl.when(jnp.logical_and(run, masked))(lambda: step(offsets[0]))
    else:
        for d in offsets:
            pl.when(jnp.logical_and(run, i - j == d))(
                functools.partial(step, d))
    pl.when(jnp.logical_and(run, jnp.logical_not(masked)))(
        lambda: step(None))


def _step_tiles(offset, i, j, block_q, block_k, whole, window=None):
    """What grid step (i, j) computes, as [(query slice, key slice, mask
    origin)] within its blocks; the origin is the (query, key) position the
    mask counts from, None for no mask.  An unmasked step (``offset`` None)
    is one tile.  So is a masked one, but where the blocks are equal and
    hold several ``_TRIANGLE_TILE``s (``_cuts_tiles``; ``offset`` is then
    the step's ``i - j``) it is cut so that what lies outside the band is
    not computed: strips of queries, each with the keys between the
    window's edge for its first query and the diagonal for its last, when
    the keys are taken ``whole`` (forward and dq: one update of a query's
    statistics and accumulator), strips of keys with the queries that see
    them when the queries are (dk/dv)."""
    everything = slice(None)
    if offset is None:
        return [(everything, everything, None)]
    t = _TRIANGLE_TILE
    if not _cuts_tiles(block_q, block_k):
        return [(everything, everything, (i * block_q, j * block_k))]
    # query r and key c of the blocks, counted within them, are r + ahead - c
    # apart; visible when that is in 0 .. reach - 1
    ahead = offset * block_q
    reach = block_q + ahead + 1 if window is None else window
    tiles = []
    for n in range(0, block_q, t):
        if whole == "keys":
            first = max(n + ahead - reach + 1, 0) // t * t
            last = min(n + t + ahead, block_k)
            tile = (slice(n, n + t), slice(first, last), (n + ahead, first))
        else:
            first = max(n - ahead, 0)
            last = min(-(-(n + t - 1 + reach - ahead) // t) * t, block_q)
            tile = (slice(first, last), slice(n, n + t), (first + ahead, n))
        if first < last:
            tiles.append(tile)
    return tiles


def _mask(s, origin, window=None):
    """Transposed scores ``s`` (keys down, queries across, from query and
    key ``origin``): keys after their query, and with a ``window`` keys that
    far behind it or further, to -inf."""
    if origin is None:
        return s
    row = origin[0] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    col = origin[1] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    hidden = col > row
    if window is not None:
        hidden = hidden | (row - col >= window)
    return jnp.where(hidden, _NEG_INF, s)


_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _dot(a, b, dims):
    """A product on the MXU: operands in the dtype they arrive in, float32
    accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
#
# All three kernels form the scores transposed, (block_k, block_q) = k @ q^T:
# what belongs to a query (running maximum and sum, lse, delta) is then a
# row over 128 lanes, 1/16 of the registers a column takes, reduced and
# broadcast along sublanes, and the products into dk and dv need no
# transposed operand.  The forward and dq kernels accumulate transposed,
# (head, block_q), and transpose once per query block.

def _key_step(qi, block_q, block_k, num_k, window):
    """(this grid step's place on the inner axis of the forward and dq
    kernels, the key block it stands for, whether that block exists): the
    axis counts key blocks, under a window from the band's first."""
    at = pl.program_id(2)
    if window is None:
        return at, at, True
    kj = at + _first_key_block(qi, block_q, block_k, window)
    return at, kj, kj < num_k


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal, window,
                block_q, block_k, num_k, steps):
    """Grid = (batch*heads, num_q, steps); K is the innermost (sequential)
    axis so the VMEM scratch (acc, m, l) carries across K steps."""
    qi = pl.program_id(1)
    at, kj, inside = _key_step(qi, block_q, block_k, num_k, window)

    @pl.when(at == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def step(offset):
        for qs, ks, origin in _step_tiles(offset, qi, kj, block_q, block_k,
                                          "keys", window):
            s = _dot(k_ref[0, ks, :], q_ref[0, qs, :], _NT) * sm_scale
            s = _mask(s, origin, window)             # (keys, queries)
            m_prev = m_ref[:, qs]                    # (1, queries)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, qs] = (l_ref[:, qs] * corr
                            + jnp.sum(p, axis=0, keepdims=True))
            vb = v_ref[0, ks, :]                     # (keys, dv)
            acc_ref[:, qs] = (acc_ref[:, qs] * corr
                              + _dot(vb, p.astype(vb.dtype), _TN))
            m_ref[:, qs] = m_new

    _band_steps(step, causal, window, qi, kj, block_q, block_k, inside)

    @pl.when(at == steps - 1)
    def _():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        o_ref[0] = (acc_ref[:] / l).T.astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _kv_spec(block_k, width, causal, block_q, num_k, group, window):
    """Key / value blocks of the forward and dq kernels' grid (its rows are
    query heads)."""
    def index(z, i, j):
        if causal:
            j = _kv_block(i, j, block_q, block_k, num_k, window)
        return (_kv_head(z, group), j, 0)
    return pl.BlockSpec((1, block_k, width), index)


def _inner_steps(causal, window, block_q, block_k, num_q, num_k):
    """(key blocks on the forward and dq kernels' inner grid axis, query
    blocks on the dk/dv kernel's): all of them, or with a window as many
    as the band is wide."""
    if not causal or window is None:
        return num_k, num_q
    return _band_blocks(block_q, block_k, num_q, num_k, window)


def _fwd_pallas(q, k, v, sm_scale, causal, window, block_q, block_k,
                interpret):
    """out (b, h, sq, dv) and lse (bh, 1, sq): a row a head, because a
    column, (bh, sq, 1), is padded to 128 lanes in HBM (134 MB a layer at
    64 x 4,096, kept from the forward to the backward pass)."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh, group = b * h, _group(q, k)
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh // group, sk, d)
    vr = v.reshape(bh // group, sk, dv)
    num_q = sq // block_q
    num_k = sk // block_k
    steps = _inner_steps(causal, window, block_q, block_k, num_q, num_k)[0]

    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k=num_k, steps=steps)
    out, lse = pl.pallas_call(
        kern,
        grid=(bh, num_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda z, i, j: (z, i, 0)),
            _kv_spec(block_k, d, causal, block_q, num_k, group, window),
            _kv_spec(block_k, dv, causal, block_q, num_k, group, window),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda z, i, j: (z, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
        ],
        compiler_params=_scoped_vmem(q),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, dv), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, sm_scale, causal, window, block_q, block_k,
                   num_k, steps):
    """Grid = (bh, num_q, steps): accumulate dq over K blocks, transposed
    like the scores: (d, queries) += k^T @ ds^T."""
    qi = pl.program_id(1)
    at, kj, inside = _key_step(qi, block_q, block_k, num_k, window)

    @pl.when(at == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(offset):
        for qs, ks, origin in _step_tiles(offset, qi, kj, block_q, block_k,
                                          "keys", window):
            kb = k_ref[0, ks, :]
            s = _dot(kb, q_ref[0, qs, :], _NT) * sm_scale
            p = jnp.exp(_mask(s, origin, window) - lse_ref[0, :, qs])
            dp = _dot(v_ref[0, ks, :], do_ref[0, qs, :], _NT)
            ds = p * (dp - delta_ref[0, :, qs]) * sm_scale
            acc_ref[:, qs] += _dot(kb, ds.astype(kb.dtype), _TN)

    _band_steps(step, causal, window, qi, kj, block_q, block_k, inside)

    @pl.when(at == steps - 1)
    def _():
        dq_ref[0] = acc_ref[:].T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                    window, block_q, block_k, num_q, steps, group):
    """Grid = (b * key heads, num_k, group * steps): accumulate dk/dv over
    the Q blocks of every query head of the key head's group, head after
    head; ``steps`` query blocks a head, all of them or under a window the
    band's, counted from its first."""
    kj = pl.program_id(1)
    step_id = pl.program_id(2)
    qi = step_id if group == 1 else step_id % steps
    inside = True
    if window is not None:
        qi = qi + _first_query_block(kj, block_q, block_k, num_q)
        inside = qi < num_q

    @pl.when(step_id == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(offset):
        for qs, ks, origin in _step_tiles(offset, qi, kj, block_q, block_k,
                                          "queries", window):
            q = q_ref[0, qs, :]
            do = do_ref[0, qs, :]
            s = _dot(k_ref[0, ks, :], q, _NT) * sm_scale
            p = jnp.exp(_mask(s, origin, window) - lse_ref[0, :, qs])
            dv_acc[ks, :] += _dot(p.astype(do.dtype), do, _NN)
            dp = _dot(v_ref[0, ks, :], do, _NT)
            ds = p * (dp - delta_ref[0, :, qs]) * sm_scale
            dk_acc[ks, :] += _dot(ds.astype(q.dtype), q, _NN)

    _band_steps(step, causal, window, qi, kj, block_q, block_k, inside)

    @pl.when(step_id == group * steps - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_operands(q, k, v, o, lse, do):
    """The two backward kernels' operands: (bh, seq, head) each (``k`` and
    ``v`` with their own, fewer, heads), lse and delta a row a query head,
    (bh, 1, sq)."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh, bhk = b * h, b * k.shape[1]
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, sq)
    return (q.reshape(bh, sq, d), k.reshape(bhk, sk, d),
            v.reshape(bhk, sk, dv), do.reshape(bh, sq, dv), lse, delta)


def _dq_pallas(operands, sm_scale, causal, window, block_q, block_k,
               interpret):
    qr, _, vr = operands[:3]
    bh, sq, d = qr.shape
    sk, dv = vr.shape[1:]
    num_q, num_k, group = sq // block_q, sk // block_k, bh // vr.shape[0]
    steps = _inner_steps(causal, window, block_q, block_k, num_q, num_k)[0]
    rows = pl.BlockSpec((1, 1, block_q), lambda z, i, j: (z, 0, i))
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          num_k=num_k, steps=steps),
        grid=(bh, num_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda z, i, j: (z, i, 0)),
            _kv_spec(block_k, d, causal, block_q, num_k, group, window),
            _kv_spec(block_k, dv, causal, block_q, num_k, group, window),
            pl.BlockSpec((1, block_q, dv), lambda z, i, j: (z, i, 0)),
            rows, rows,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda z, i, j: (z, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), qr.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        compiler_params=_scoped_vmem(qr),
        interpret=interpret,
    )(*operands)


def _dkv_pallas(operands, sm_scale, causal, window, block_q, block_k,
                interpret):
    qr, kr, vr = operands[:3]
    bh, sq, d = qr.shape
    bhk, sk, dv = vr.shape
    num_q, group = sq // block_q, bh // bhk
    steps = _inner_steps(causal, window, block_q, block_k, num_q,
                         sk // block_k)[1]

    # grid step (z, j, t): key head z, key block j, and t counts the query
    # blocks of the group's heads, head after head
    def q_head(z, t):
        return z if group == 1 else z * group + t // steps

    def q_block(t, j):
        i = t if group == 1 else t % steps
        return _q_block(i, j, block_q, block_k, num_q, window) if causal \
            else i

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda z, j, t: (q_head(z, t), q_block(t, j), 0))

    rows = pl.BlockSpec((1, 1, block_q),
                        lambda z, j, t: (q_head(z, t), 0, q_block(t, j)))
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          num_q=num_q, steps=steps, group=group),
        grid=(bhk, sk // block_k, group * steps),
        in_specs=[
            q_spec(d),
            pl.BlockSpec((1, block_k, d), lambda z, j, i: (z, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda z, j, i: (z, j, 0)),
            q_spec(dv),
            rows, rows,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda z, j, i: (z, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda z, j, i: (z, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, sk, d), kr.dtype),
            jax.ShapeDtypeStruct((bhk, sk, dv), vr.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_scoped_vmem(qr),
        interpret=interpret,
    )(*operands)


def _bwd_pallas(q, k, v, o, lse, do, sm_scale, causal, window,
                block_q, block_k, interpret):
    operands = _bwd_operands(q, k, v, o, lse, do)
    how = (sm_scale, causal, window, block_q, block_k, interpret)
    dq = _dq_pallas(operands, *how)
    dk, dv = _dkv_pallas(operands, *how)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# ---------------------------------------------------------------------------
# public fused op (custom_vjp)
# ---------------------------------------------------------------------------

def _tiles(q, k, block_q, block_k):
    """The kernel's grid assumes the blocks tile both sequences exactly
    (a ragged seq would leave trailing rows unwritten)."""
    return q.shape[2] % block_q == 0 and k.shape[2] % block_k == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, window, block_q, block_k, interpret):
    out, _ = _fwd_pallas(q, k, v, sm_scale, causal, window, block_q,
                         block_k, interpret)
    return out


# names under which a block that recomputes its forward in the backward
# pass (``jax.checkpoint``) may keep the forward kernel's results, so that
# the kernel runs once: ``save_only_these_names(*FLASH_RESIDUALS)``
FLASH_RESIDUALS = ("flash_attention_out", "flash_attention_lse")


def _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
               interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd_pallas(q, k, v, sm_scale, causal, window, block_q,
                           block_k, interpret)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(sm_scale, causal, window, block_q, block_k, interpret, res,
               g):
    q, k, v, out, lse = res
    return _bwd_pallas(q, k, v, out, lse, g, sm_scale, causal, window,
                       block_q, block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _block_choices(q, v):
    """The block pairs to try for these operands, best first (the
    measurements stand at the constants)."""
    row_bytes = (q.shape[-1] + v.shape[-1]) * q.dtype.itemsize
    wide = [_WIDE_BLOCKS] if row_bytes <= _WIDE_ROW_BYTES else []
    return wide + [_NARROW_BLOCKS, (128, 128)]


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=None, block_k=None, interpret=False,
                    window=None):
    """Fused attention over (batch, heads, seq, head_dim) arrays.  ``v``
    (and so the output) may have another head size than ``q`` and ``k``
    (latent attention trains with 192 for the scores and 128 for the
    values); ``sm_scale`` defaults to the scores' head size.  ``k`` and
    ``v`` may have fewer heads than ``q`` (grouped-query attention: ``heads
    // group`` of them, query head ``h`` reads key head ``h // group``);
    the kernels read a key head once per query head from where it lies,
    and ``dk`` / ``dv`` come back with the key heads' shape, summed over
    each group inside the kernel.  ``window`` (an integer, with ``causal``,
    self-attention only): query ``i`` sees the keys ``i - window < j <=
    i``; the kernels fetch and compute nothing behind the window (the
    section on the rule over blocks), and a window that reaches back to
    the first key from every query is plain causal attention.

    The products run in the inputs' dtype with float32 accumulation: for
    bfloat16 inputs the only roundings beyond the reference's are those
    of the probabilities and of ``ds`` to bfloat16 before their second
    product (``mha_reference`` rounds the probabilities the same way);
    float32 inputs are multiplied as float32 operands at jax's matmul
    precision (on the MXU the default is one bfloat16 pass, ``highest``
    is exact).  ``block_q`` / ``block_k``
    default to the measured choice for the operands (``_block_choices``);
    a sequence shorter than a block is one block, whatever its length.

    On an accelerator this is always the Pallas flash kernel: a longer
    sequence that no block pair tiles raises instead of materialising
    seq x seq scores.  The CPU platform computes the XLA reference;
    ``interpret=True`` runs the kernel through the Pallas interpreter
    there (the test suite's path).

    Inside a program GSPMD partitions over several chips (a step over a
    dp/tp mesh) XLA refuses the bare kernel — "Mosaic kernels cannot be
    automatically partitioned" — and jax 0.9.0 / libtpu 0.0.34 cannot
    take a ``custom_partitioning`` rule either ("Custom emitter for
    CustomSPMDPartitioning not found"): there the call must sit inside
    a ``shard_map``, as parallel/ring_attention.py's does.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        window = int(window)
        if not causal or window < 1 or q.shape[2] != k.shape[2]:
            raise MXNetError(
                "flash_attention: a window (%r) takes causal=True and as "
                "many queries as keys (%d, %d)"
                % (window, q.shape[2], k.shape[2]))
        if window >= k.shape[2]:
            window = None
    on_cpu = pallas_interpret()
    if not on_cpu or interpret:
        # prefer the fast measured blocks, but step down to 128/128 for
        # sequences they don't divide
        if block_q is None or block_k is None:
            choices = _block_choices(q, v)
        else:
            choices = [(block_q, block_k), (128, 128)]
        for cq, ck in choices:
            bq = min(cq, q.shape[2])
            bk = min(ck, k.shape[2])
            if _tiles(q, k, bq, bk):
                return _flash(q, k, v, sm_scale, causal, window, bq, bk,
                              on_cpu)
        if not on_cpu:
            raise MXNetError(
                "flash_attention: sequence lengths (q %d, k %d) are not "
                "tiled by blocks %s; pad the sequence to a multiple of 128"
                % (q.shape[2], k.shape[2],
                   " or ".join("(%d, %d)" % pair for pair in choices)))
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                         window=window)


# ---------------------------------------------------------------------------
# registered ops (reference: src/operator/contrib/transformer.cc)
# ---------------------------------------------------------------------------

@register("_contrib_flash_attention", aliases=("flash_attention",))
def flash_attention_op(query, key, value, causal=False, sm_scale=None,
                       window=None, **_):
    """Fused scaled-dot-product attention over (B, H, T, D) q/k/v —
    the registry face of :func:`flash_attention` (tiled online-softmax
    kernel; ``causal`` masks the upper triangle, ``window`` what lies that
    far behind a query or further, ``sm_scale`` defaults to 1/sqrt(D))."""
    return flash_attention(query, key, value, causal=bool(causal),
                           sm_scale=sm_scale, window=window)


@register("_contrib_div_sqrt_dim", aliases=("div_sqrt_dim",))
def div_sqrt_dim(data, **_):
    """data / sqrt(last_dim) (src/operator/contrib/transformer.cc)."""
    return data / math.sqrt(data.shape[-1])


@register("_contrib_interleaved_matmul_selfatt_qk",
          aliases=("interleaved_matmul_selfatt_qk",))
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1, **_):
    """Scores from interleaved qkv (seq, batch, 3*proj) layout.

    Reference computes q k^T from the packed projection
    (src/operator/contrib/transformer.cc interleaved_matmul_selfatt_qk).
    Output: (batch*heads, seq, seq).
    """
    s, b, p3 = queries_keys_values.shape
    proj = p3 // 3
    d = proj // heads
    x = queries_keys_values.reshape(s, b, heads, 3, d)
    q = x[:, :, :, 0, :]
    k = x[:, :, :, 1, :]
    # (b*h, s, d) @ (b*h, d, s)
    qt = q.transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    kt = k.transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    return jnp.einsum("zqd,zkd->zqk", qt, kt,
                      preferred_element_type=jnp.float32).astype(
                          queries_keys_values.dtype)


@register("_contrib_interleaved_matmul_selfatt_valatt",
          aliases=("interleaved_matmul_selfatt_valatt",))
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                      heads=1, **_):
    """attention @ values back to (seq, batch, proj) layout."""
    s, b, p3 = queries_keys_values.shape
    proj = p3 // 3
    d = proj // heads
    x = queries_keys_values.reshape(s, b, heads, 3, d)
    v = x[:, :, :, 2, :].transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    out = jnp.einsum("zqk,zkd->zqd", attention.astype(jnp.float32),
                     v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, heads, s, d).transpose(2, 0, 1, 3).reshape(
        s, b, proj).astype(queries_keys_values.dtype)
