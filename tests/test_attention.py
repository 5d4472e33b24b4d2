"""Flash-attention kernel tests (pallas interpret mode on CPU).

Mirrors the reference op-test style (tests/python/unittest/test_operator.py):
forward vs an unfused numpy/jnp reference, gradients vs jax.grad of the
reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import attention as att


def _rand(shape, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    b, h, s, d = 2, 3, 256, 64
    q, k, v = (_rand((b, h, s, d), seed=i) for i in range(3))
    ref = att.mha_reference(q, k, v, causal=causal)
    out = att.flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    b, h, s, d = 1, 2, 128, 32
    q, k, v = (_rand((b, h, s, d), seed=10 + i) for i in range(3))

    def loss_flash(q, k, v):
        o = att.flash_attention(q, k, v, causal=causal, interpret=True,
                                block_q=64, block_k=64)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = att.mha_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_flash_rectangular_kv():
    # cross-attention: klen != qlen
    b, h, sq, sk, d = 1, 2, 128, 256, 32
    q = _rand((b, h, sq, d), seed=1)
    k = _rand((b, h, sk, d), seed=2)
    v = _rand((b, h, sk, d), seed=3)
    ref = att.mha_reference(q, k, v)
    out = att.flash_attention(q, k, v, interpret=True,
                              block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fallback_path_off_tpu():
    # ragged seq → falls back to the XLA reference path (still correct)
    b, h, s, d = 1, 1, 100, 16
    q, k, v = (_rand((b, h, s, d), seed=20 + i) for i in range(3))
    out = att.flash_attention(q, k, v, causal=True)
    ref = att.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_registered_contrib_ops():
    import mxnet_tpu as mx

    # flash attention through the op registry / nd namespace
    q = mx.nd.random.normal(shape=(1, 2, 64, 16))
    k = mx.nd.random.normal(shape=(1, 2, 64, 16))
    v = mx.nd.random.normal(shape=(1, 2, 64, 16))
    out = mx.nd.contrib.flash_attention(q, k, v)
    assert out.shape == (1, 2, 64, 16)

    # div_sqrt_dim
    x = mx.nd.ones((2, 16))
    y = mx.nd.contrib.div_sqrt_dim(x)
    np.testing.assert_allclose(y.asnumpy(), np.ones((2, 16)) / 4.0,
                               rtol=1e-6)


def test_interleaved_matmul_selfatt():
    s, b, heads, d = 8, 2, 2, 4
    proj = heads * d
    qkv = _rand((s, b, 3 * proj), seed=5)
    from mxnet_tpu.ops.registry import apply_op
    scores = apply_op("_contrib_interleaved_matmul_selfatt_qk", qkv,
                      heads=heads)
    assert scores.shape == (b * heads, s, s)
    attn = jax.nn.softmax(scores, axis=-1)
    out = apply_op("_contrib_interleaved_matmul_selfatt_valatt",
                   qkv, attn, heads=heads)
    assert out.shape == (s, b, proj)
    # numpy check of qk
    x = np.asarray(qkv).reshape(s, b, heads, 3, d)
    q = x[:, :, :, 0, :].transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    kk = x[:, :, :, 1, :].transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    want = np.einsum("zqd,zkd->zqk", q, kk)
    np.testing.assert_allclose(np.asarray(scores), want, rtol=1e-4, atol=1e-4)


def test_flash_interpret_ragged_seq_falls_back_correctly():
    """ADVICE r3: interpret mode must apply the same divisibility check
    as hardware — a ragged seq (300 with 256/512 default blocks) would
    otherwise leave trailing output rows unwritten.  The public entry
    must produce correct values for ANY seq length."""
    b, h, s, d = 1, 2, 300, 32
    q, k, v = (_rand((b, h, s, d), seed=20 + i) for i in range(3))
    ref = att.mha_reference(q, k, v)
    out = att.flash_attention(q, k, v, interpret=True)  # default blocks
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # and the tiling check itself refuses ragged shapes
    assert not att._tiles(q, k, 256, 512)
    assert not att._tiles(q, k, 128, 128)


def test_flash_ragged_seq_raises_off_cpu(monkeypatch):
    """On an accelerator a sequence the blocks cannot tile is an error,
    never a silent drop to materialised seq x seq scores."""
    from mxnet_tpu.base import MXNetError

    monkeypatch.setattr(att, "pallas_interpret", lambda: False)
    q, k, v = (_rand((1, 1, 300, 32), seed=i) for i in range(3))
    with pytest.raises(MXNetError, match="not tiled"):
        att.flash_attention(q, k, v)

