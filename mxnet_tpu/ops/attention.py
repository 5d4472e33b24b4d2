"""Fused multi-head attention — Pallas TPU flash-attention kernels.

The reference framework has no fused attention; its transformer helpers
(`src/operator/contrib/transformer.cc`: interleaved_matmul_selfatt_qk /
valatt, div_sqrt_dim) materialise the full (seq, seq) score matrix in
HBM.  On TPU that is HBM-bandwidth-bound; the TPU-native design is a
flash-attention kernel that tiles Q/K/V through VMEM, keeps the online
softmax statistics in VMEM scratch across the (sequential) K-block grid
steps, and feeds the MXU with (block_q x d) @ (d x block_k) matmuls.

Layout: (batch, heads, seq, head_dim) throughout.

Public entry points
-------------------
flash_attention(q, k, v, causal=..., sm_scale=...)  — custom_vjp fused op
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

# Measured on v5e (tools/bench_attention.py, r3): 256/512 blocks run
# the fwd kernel ~2.9x faster than 128/128 (6.1 -> 17.6 TFLOP/s at
# seq 4096, d=64) — larger K blocks amortize the online-softmax
# rescale and keep the MXU busy despite the narrow d=64 operand.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# reference (unfused) implementation — the oracle, and the CPU platform's path
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal=False, sm_scale=None):
    """Unfused attention: softmax(q k^T * scale) v, fp32 accumulation."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if sm_scale is None else sm_scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (qlen, klen), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (qlen, klen), 1)
        s = jnp.where(col > row, _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, causal,
                block_q, block_k, num_k):
    """Grid = (batch*heads, num_q, num_k); K is the innermost (sequential)
    axis so the VMEM scratch (acc, m, l) carries across K steps."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal
    run = True
    if causal:
        run = kj * block_k <= qi * block_q + block_q - 1

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)            # (block_q, d)
        kb = k_ref[0].astype(jnp.float32)           # (block_k, d)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col > row, _NEG_INF, s)

        m_prev = m_ref[:, 0:1]                       # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (bq, bk)
        corr = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        vb = v_ref[0].astype(jnp.float32)            # (block_k, d)
        pv = jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == num_k - 1)
    def _():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse rides as (bh, sq, 1): a (block_q, 1) block keeps the TPU
        # (8, 128)-tiling rule satisfied (last dim == full array dim)
        lse_ref[0] = m_ref[:, 0:1] + jnp.log(l)


def _fwd_pallas(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, dv)
    num_q = sq // block_q
    num_k = sk // block_k

    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k)
    out, lse = pl.pallas_call(
        kern,
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda z, i, j: (z, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda z, i, j: (z, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda z, i, j: (z, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, dv), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, sm_scale, causal, block_q, block_k, num_k):
    """Grid = (bh, num_q, num_k): accumulate dq over K blocks."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = kj * block_k <= qi * block_q + block_q - 1

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (bq, 1)
        delta = delta_ref[0]                         # (bq, 1)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col > row, _NEG_INF, s)
        p = jnp.exp(s - lse)                         # softmax probs
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        acc_ref[:] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_k - 1)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, sm_scale, causal, block_q, block_k, num_q):
    """Grid = (bh, num_k, num_q): accumulate dk/dv over Q blocks."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = qi * block_q + block_q - 1 >= kj * block_k

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                             # (bq, 1)
        delta = delta_ref[0]                         # (bq, 1)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(col > row, _NEG_INF, s)
        p = jnp.exp(s - lse)                         # (bq, bk)
        # dv += p^T @ do
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale             # (bq, bk)
        # dk += ds^T @ q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, sm_scale, causal,
                block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh = b * h
    qr, kr = (x.reshape(bh, -1, d) for x in (q, k))
    vr = v.reshape(bh, sk, dv)
    dor = do.reshape(bh, sq, dv)
    lser = lse.reshape(bh, sq, 1)
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, sq, 1)
    num_q = sq // block_q
    num_k = sk // block_k

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k=num_k),
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda z, i, j: (z, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda z, i, j: (z, j, 0)),
            pl.BlockSpec((1, block_q, dv), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda z, i, j: (z, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda z, i, j: (z, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda z, i, j: (z, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q),
        grid=(bh, num_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda z, j, i: (z, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda z, j, i: (z, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda z, j, i: (z, j, 0)),
            pl.BlockSpec((1, block_q, dv), lambda z, j, i: (z, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda z, j, i: (z, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda z, j, i: (z, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda z, j, i: (z, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda z, j, i: (z, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# ---------------------------------------------------------------------------
# public fused op (custom_vjp)
# ---------------------------------------------------------------------------

def _tiles(q, k, block_q, block_k):
    """The kernel's grid assumes the blocks tile both sequences exactly
    (a ragged seq would leave trailing rows unwritten)."""
    return q.shape[2] % block_q == 0 and k.shape[2] % block_k == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _fwd_pallas(q, k, v, sm_scale, causal, block_q, block_k,
                         interpret)
    return out


# names under which a block that recomputes its forward in the backward
# pass (``jax.checkpoint``) may keep the forward kernel's results, so that
# the kernel runs once: ``save_only_these_names(*FLASH_RESIDUALS)``
FLASH_RESIDUALS = ("flash_attention_out", "flash_attention_lse")


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd_pallas(q, k, v, sm_scale, causal, block_q, block_k,
                           interpret)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _bwd_pallas(q, k, v, out, lse, g, sm_scale, causal,
                       block_q, block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """Fused attention over (batch, heads, seq, head_dim) arrays.  ``v``
    (and so the output) may have another head size than ``q`` and ``k``
    (latent attention trains with 192 for the scores and 128 for the
    values); ``sm_scale`` defaults to the scores' head size.

    On an accelerator this is always the Pallas flash kernel: a sequence
    the blocks cannot tile raises instead of materialising seq x seq
    scores.  The CPU platform computes the XLA reference;
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
    on_cpu = pallas_interpret()
    if not on_cpu or interpret:
        # prefer the fast measured blocks, but step down to 128/128 for
        # sequences they don't divide
        for cq, ck in ((block_q, block_k), (128, 128)):
            bq = min(cq, q.shape[2])
            bk = min(ck, k.shape[2])
            if _tiles(q, k, bq, bk):
                return _flash(q, k, v, sm_scale, causal, bq, bk, on_cpu)
        if not on_cpu:
            raise MXNetError(
                "flash_attention: sequence lengths (q %d, k %d) are not "
                "tiled by blocks (%d, %d) or (128, 128); pad the "
                "sequence to a multiple of 128"
                % (q.shape[2], k.shape[2], block_q, block_k))
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# registered ops (reference: src/operator/contrib/transformer.cc)
# ---------------------------------------------------------------------------

@register("_contrib_flash_attention", aliases=("flash_attention",))
def flash_attention_op(query, key, value, causal=False, sm_scale=None, **_):
    """Fused scaled-dot-product attention over (B, H, T, D) q/k/v —
    the registry face of :func:`flash_attention` (tiled online-softmax
    kernel; ``causal`` masks the upper triangle, ``sm_scale`` defaults
    to 1/sqrt(D))."""
    return flash_attention(query, key, value, causal=bool(causal),
                           sm_scale=sm_scale)


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
