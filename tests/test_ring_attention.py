"""Ring / Ulysses sequence-parallel attention on an 8-device CPU mesh.

Distributed semantics tested with XLA virtual host devices (conftest sets
--xla_force_host_platform_device_count=8), the analog of the reference's
local `launch.py -n N` distributed tests (SURVEY.md §4).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from mxnet_tpu.ops.attention import mha_reference
from mxnet_tpu.parallel.mesh import create_mesh
from mxnet_tpu.parallel.ring_attention import ring_attention, ulysses_attention


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs)


def _rand(shape, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


def _sp_mesh(n=8):
    return create_mesh({"sp": n}, devices=jax.devices()[:n])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = _sp_mesh()
    b, h, s, d = 2, 4, 8 * 16, 32
    q, k, v = (_rand((b, h, s, d), seed=i) for i in range(3))

    spec = P(None, None, "sp", None)
    f = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = jax.jit(f)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_grads():
    mesh = _sp_mesh()
    b, h, s, d = 1, 2, 8 * 8, 16
    q, k, v = (_rand((b, h, s, d), seed=10 + i) for i in range(3))
    spec = P(None, None, "sp", None)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.tanh(ring(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.tanh(mha_reference(q, k, v, causal=True)))

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    mesh = _sp_mesh()
    b, h, s, d = 2, 8, 8 * 16, 32                  # heads divisible by sp=8
    q, k, v = (_rand((b, h, s, d), seed=20 + i) for i in range(3))
    spec = P(None, None, "sp", None)
    f = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = jax.jit(f)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_long_sequence_sharded_memory():
    # 8k sequence over 8 devices: each device only ever sees 1k-long
    # K/V shards; this would OOM-scale quadratically if unsharded
    mesh = _sp_mesh()
    b, h, s, d = 1, 1, 8 * 1024, 8
    q, k, v = (_rand((b, h, s, d), seed=30 + i) for i in range(3))
    spec = P(None, None, "sp", None)
    f = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh, in_specs=(spec, spec, spec), out_specs=spec))
    out = f(q, k, v)
    assert out.shape == (b, h, s, d)
    assert bool(jnp.all(jnp.isfinite(out)))
