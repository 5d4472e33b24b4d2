"""Collective structure of the compiled sharded programs (VERDICT r3
task #5): beyond "loss went down on the 8-dev mesh", assert the things
that must hold for the 256-chip north star and CAN be validated without
hardware — the compiled HLO contains the collectives each parallelism
inserts (all-reduce for dp grad sync and tp partial sums,
collective-permute for the pp ring and sp ring attention), and sharded
parameters actually occupy 1/factor of their bytes per device.

Wider-than-8 meshes are validated by re-running the driver's own
``__graft_entry__.dryrun_multichip`` in a re-exec'd interpreter with 16
(and, in the large tier, 32) virtual devices — all six phases,
including the 3-axis dp×tp×pp composition.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LARGE = os.environ.get("MXTPU_TEST_LARGE") == "1"

D = 16


def _step_hlo(step, x, y):
    """Optimized (post-SPMD-partitioning) HLO of the compiled step."""
    return step.program_for(x, y).as_text()


def _dense_net():
    net = nn.HybridSequential(prefix="csnet_")
    with net.name_scope():
        net.add(nn.Dense(D, activation="relu", in_units=D,
                         prefix="d1_"))
        net.add(nn.Dense(4, in_units=D, prefix="d2_"))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, D)))
    return net


@pytest.mark.parametrize("rule", [{"lr": 0.1}, {"optimizer": "adam"}],
                         ids=["fused_sgd", "optimizer"])
def test_dp_step_contains_gradient_allreduce(rule):
    """Data parallelism = GSPMD inserts an all-reduce for the gradient
    sync (the reference's KVStore push/pull, riding ICI here), whichever
    rule then updates the replicated parameters."""
    mesh = create_mesh({"dp": 8})
    net = _dense_net()
    if "optimizer" in rule:
        rule = {"optimizer": mx.optimizer.create(rule["optimizer"])}
    step = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, **rule)
    x, y = step.put_batch(np.random.rand(16, D).astype(np.float32),
                          np.zeros((16,), np.int32))
    hlo = _step_hlo(step, x, y)
    assert "all-reduce" in hlo
    # replicated params: every device holds the full array
    for p, v in zip(step.trainable, step.train_vals):
        shard = v.addressable_shards[0].data
        assert shard.size == v.size, p.name


def test_tp_step_shards_params_and_inserts_psum():
    """Column-parallel weight: per-device bytes shrink by exactly the
    tp factor; the row-parallel partial-sum all-reduce is in the HLO."""
    from jax.sharding import PartitionSpec as P

    mesh = create_mesh({"dp": 4, "tp": 2})
    net = _dense_net()

    def spec_fn(name, shape):
        if name.endswith("d1_weight"):
            return P("tp", None)   # column-parallel
        if name.endswith("d2_weight"):
            return P(None, "tp")   # row-parallel -> psum on the output
        return P()

    step = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, lr=0.1, param_spec_fn=spec_fn)
    x, y = step.put_batch(np.random.rand(8, D).astype(np.float32),
                          np.zeros((8,), np.int32))
    hlo = _step_hlo(step, x, y)
    assert "all-reduce" in hlo
    sharded = {p.name: v for p, v in zip(step.trainable, step.train_vals)
               if p.name.endswith("weight")}
    assert sharded
    for name, v in sharded.items():
        shard = v.addressable_shards[0].data
        assert shard.size * 2 == v.size, (name, shard.shape, v.shape)
    # and the optimizer state mirrors the parameter sharding
    for p, s in zip(step.trainable, step.opt_state):
        if p.name.endswith("weight"):
            assert s.addressable_shards[0].data.size * 2 == s.size, p.name


def test_ring_attention_compiles_to_collective_permute():
    """SP ring attention = ppermute ring over ICI, not all-gather: the
    compiled HLO must rotate KV with collective-permute and must NOT
    materialize the full sequence with an all-gather."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    b, h, s, d = 1, 2, 64, 8
    q = jnp.zeros((b, h, s, d), jnp.float32)
    fn = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None)))
    hlo = fn.lower(q, q, q).compile().as_text()
    assert "collective-permute" in hlo
    assert "all-gather" not in hlo


def test_pipeline_train_step_contains_ring():
    """A pp-sharded Gluon pipeline's whole compiled train step carries
    the GPipe collective-permute ring."""
    from mxnet_tpu.gluon.contrib.parallel import (PipelineBlock,
                                                  param_spec_fn_for)

    mesh = create_mesh({"pp": 4, "dp": 2})

    def make_stage(seed):
        np.random.seed(seed)
        s = nn.HybridSequential(prefix="")
        s.add(nn.Dense(D, activation="tanh", flatten=False, in_units=D))
        s.initialize(mx.init.Xavier())
        s(mx.nd.zeros((2, D)))
        return s

    pipe = PipelineBlock([make_stage(i) for i in range(4)],
                         n_microbatches=4).attach_mesh(mesh)
    net = nn.HybridSequential(prefix="ppnet_")
    with net.name_scope():
        head = nn.Dense(3, in_units=D)
    net.add(pipe)
    net.add(head)
    head.initialize(mx.init.Xavier())
    step = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, lr=0.1,
                          param_spec_fn=param_spec_fn_for(net))
    x, y = step.put_batch(np.random.rand(16, D).astype(np.float32),
                          np.zeros((16,), np.int32))
    hlo = _step_hlo(step, x, y)
    assert "collective-permute" in hlo
    # stacked stage params: each device holds 1/4 of the stage axis
    stage_vals = [v for p, v in zip(step.trainable, step.train_vals)
                  if p.name.startswith(pipe.prefix)]
    assert stage_vals
    for v in stage_vals:
        assert v.addressable_shards[0].data.size * 4 == v.size


def _run_dryrun(n):
    env = dict(os.environ)
    # asked for explicitly: the entry re-execs on a virtual n-device CPU
    # mesh only under JAX_PLATFORMS=cpu (anything else is an error)
    env["JAX_PLATFORMS"] = "cpu"
    # budget sized for a CONTENDED 1-core container (r5: the 16-dev run
    # took 560s when the suite shared the core with a second job)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         "dryrun", str(n)],
        capture_output=True, text=True, timeout=1500, env=env)


def test_dryrun_multichip_16_devices():
    """All six dryrun phases (dp, dp×tp, sp ring, pp, ep, dp×tp×pp) at
    16 virtual devices — the scale-up beyond the suite's 8."""
    r = _run_dryrun(16)
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "dryrun_multichip(16): dp loss" in out
    assert "dp(8) x tp(2)" in out
    assert "sp ring attention over 16 devices" in out
    assert "pp(8) GPipe" in out
    assert "ep(16) MoE" in out
    assert "dp(2) x tp(2) x pp(4)" in out


@pytest.mark.skipif(not LARGE, reason="set MXTPU_TEST_LARGE=1 (slow)")
def test_dryrun_multichip_32_devices():
    r = _run_dryrun(32)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "dp(2) x tp(2) x pp(8)" in r.stdout
