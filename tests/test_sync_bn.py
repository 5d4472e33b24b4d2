"""SyncBatchNorm tests (reference: tests/python/.../test_contrib_operator
sync BN cases + the §2.3 checklist item)."""

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.ops.registry import apply_op


def test_sync_bn_matches_bn_single_device():
    rng = np.random.RandomState(0)
    x = rng.rand(4, 3, 5, 5).astype(np.float32)
    g = np.ones(3, np.float32)
    b = np.zeros(3, np.float32)
    mm = np.zeros(3, np.float32)
    mv = np.ones(3, np.float32)
    a = np.asarray(apply_op("BatchNorm", x, g, b, mm, mv, fix_gamma=False))
    s = np.asarray(apply_op("_contrib_SyncBatchNorm", x, g, b, mm, mv,
                            fix_gamma=False))
    assert np.allclose(a, s, atol=2e-3)


def test_sync_bn_global_stats_under_shard_map():
    """Under shard_map over a dp axis, SyncBatchNorm with axis_name must
    normalize with GLOBAL batch statistics (the reference's cross-GPU
    barrier semantics)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.ops.contrib import sync_batch_norm

    devs = jax.devices()
    if len(devs) < 2:
        import pytest
        pytest.skip("needs multi-device (run under the 8-dev CPU conftest)")
    n = len(devs)
    rng = np.random.RandomState(1)
    x = rng.rand(2 * n, 3, 4, 4).astype(np.float32)
    g = np.ones(3, np.float32)
    b = np.zeros(3, np.float32)
    mm = np.zeros(3, np.float32)
    mv = np.ones(3, np.float32)
    mesh = Mesh(np.array(devs), ("dp",))

    def local(xs):
        return sync_batch_norm(xs, g, b, mm, mv, fix_gamma=False,
                               axis_name="dp")

    out = jax.jit(shard_map(local, mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp")))(x)
    want = np.asarray(apply_op("BatchNorm", x, g, b, mm, mv,
                               fix_gamma=False))
    assert np.allclose(np.asarray(out), want, atol=2e-3), \
        np.abs(np.asarray(out) - want).max()
