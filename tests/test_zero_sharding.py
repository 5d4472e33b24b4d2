"""PR 14: ZeRO-style weight-update sharding (parallel/gluon_step.py
``zero=True`` + compiled_step.ZeroCompiledStep).

Pins the acceptance criteria:

- dp-vs-ZeRO parity: the sharded step produces BIT-EXACT f32 losses,
  params, and per-step global grad norms vs the unsharded dp step for
  the compiled-step-safe optimizers (SGD momentum, Adam, RMSProp, plus
  the newly-flagged AdaGrad/AdaDelta) over 20 steps;
- state shrink: per-device param+optimizer-state bytes measured off the
  live shards clear 0.8×n at n=2 and n=8 in-process and n=64 in a
  subprocess (the tier-1 guard against a regression to replicated
  state), and the compiled HLO carries the param all-gather;
- the seam: ``trainer.compile(..., zero=True)`` routes to
  ZeroCompiledStep, guards reject unsafe configurations, and the
  observability substrate sees the
  sharded path (zero counters, compare() notes semantics, the
  zero-allgather-dominated doctor rule, metrics-timeline columns).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, health, optimizer as opt_mod
from mxnet_tpu import histogram, metrics_timeline, perfdoctor
from mxnet_tpu import runtime_stats, stepstats
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    runtime_stats.reset()
    metrics_timeline.disable()
    metrics_timeline.reset()
    yield
    health.disable()
    metrics_timeline.disable()
    metrics_timeline.reset()
    stepstats.disable()     # the timeline's enable() turned both on
    histogram.disable()
    runtime_stats.reset()


def _mlp(prefix, seed=42, feat=12, classes=4):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"),
                nn.Dense(10, activation="tanh"), nn.Dense(classes))
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((2, feat), ctx=mx.cpu()))
    return net


def _data(n=20, batch=16, feat=12, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    return ([rs.rand(batch, feat).astype(np.float32) for _ in range(n)],
            [rs.randint(0, classes, (batch,)).astype(np.int32)
             for _ in range(n)])


def _run(step, xs, ys):
    losses, gnorms = [], []
    for x, y in zip(xs, ys):
        losses.append(float(np.asarray(step(x, y))))
        gnorms.append(float(np.asarray(step.last_grad_norm)))
    return losses, gnorms


# --------------------------------------------------------------- parity


@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("adagrad", {"learning_rate": 0.05}),
    ("adadelta", {}),
])
def test_dp_vs_zero_bit_exact_20_steps(opt, kw):
    """Same model/data/seed: the unsharded dp step and the ZeRO step
    produce bit-identical f32 losses, global grad norms (the health
    trajectory), and final params over 20 steps — elementwise optimizer
    updates commute with the shard boundary, and the padded tail stays
    exactly zero."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data()
    mesh = create_mesh({"dp": 8})

    net_d = _mlp("zpar_")
    dp = GluonTrainStep(net_d, loss_fn, mesh=mesh,
                        optimizer=opt_mod.create(opt, **kw))
    ld, gd = _run(dp, xs, ys)

    net_z = _mlp("zpar_")
    zs = GluonTrainStep(net_z, loss_fn, mesh=mesh, zero=True,
                        optimizer=opt_mod.create(opt, **kw))
    lz, gz = _run(zs, xs, ys)

    assert ld == lz, "loss trajectories diverged for %s" % opt
    assert gd == gz, "grad-norm trajectories diverged for %s" % opt
    dp.sync_to_params()
    zs.sync_to_params()
    for pa, pb in zip(net_d.collect_params().values(),
                      net_z.collect_params().values()):
        assert np.array_equal(pa.data().asnumpy(), pb.data().asnumpy()), \
            "param %s diverged under %s" % (pa.name, opt)


def test_zero_sgd_momentum_fallback_bit_exact():
    """optimizer=None (the fused sgd-momentum closure) shards too and
    stays bit-exact vs its dp twin."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=10)
    mesh = create_mesh({"dp": 8})
    dp = GluonTrainStep(_mlp("zmom_"), loss_fn, mesh=mesh, lr=0.1,
                        momentum=0.9, wd=1e-4)
    zs = GluonTrainStep(_mlp("zmom_"), loss_fn, mesh=mesh, lr=0.1,
                        momentum=0.9, wd=1e-4, zero=True)
    ld, gd = _run(dp, xs, ys)
    lz, gz = _run(zs, xs, ys)
    assert ld == lz and gd == gz


# --------------------------------------------------- state-bytes shrink


def _measured_shrink(zs):
    per_dev = sum(int(v.addressable_shards[0].data.nbytes)
                  for v in zs.train_vals + zs.opt_state)
    repl = zs.zero_layout["replicated_param_bytes"]
    state_per_leaf = {
        i: [np.dtype(dt).itemsize for dt in dts]
        for i, dts in enumerate(zs.zero_layout["state_dtypes"])}
    repl_state = sum(m["size"] * b for i, m in
                     enumerate(zs.zero_layout["params"])
                     for b in state_per_leaf[i])
    return (repl + repl_state) / max(1, per_dev)


@pytest.mark.parametrize("n", [2, 8])
def test_state_bytes_shrink_in_process(n):
    """Measured per-device param+opt bytes shrink >= 0.8*n (padding is
    the only loss), and the optimizer state is BORN sharded — every
    state leaf's addressable shard is 1/n of its global shape."""
    import jax

    zs = GluonTrainStep(_mlp("zshr%d_" % n),
                        gluon.loss.SoftmaxCrossEntropyLoss(),
                        mesh=create_mesh({"dp": n}, devices=jax.devices()[:n]),
                        zero=True, optimizer=opt_mod.create("adam"))
    assert _measured_shrink(zs) >= 0.8 * n
    for v in zs.train_vals + zs.opt_state:
        assert int(v.shape[0]) % n == 0
        assert int(v.addressable_shards[0].data.shape[0]) \
            == int(v.shape[0]) // n


@pytest.mark.parametrize("rule", [{"optimizer": "adam"}, {"lr": 0.1}],
                         ids=["optimizer", "fused_sgd"])
def test_hlo_carries_allgather_and_sharded_update(rule):
    """The compiled post-SPMD HLO of the zero step contains the param
    all-gather (GSPMD's lowering of the replicated forward constraint)
    — the collective structure the SCALING_TABLE rows pin — whichever
    rule updates the shards."""
    if "optimizer" in rule:
        rule = {"optimizer": opt_mod.create(rule["optimizer"])}
    zs = GluonTrainStep(_mlp("zhlo_"), gluon.loss.SoftmaxCrossEntropyLoss(),
                        mesh=create_mesh({"dp": 8}), zero=True, **rule)
    hlo = zs.program_for(*zs.put_batch(
        np.zeros((8, 12), np.float32), np.zeros((8,), np.int32))).as_text()
    assert hlo.count(" all-gather(") + hlo.count(" all-gather-start(") >= 1
    # grad reduction present in some collective form (true
    # reduce-scatter on TPU; all-reduce+slice is the CPU lowering)
    assert sum(hlo.count(" %s(" % op) for op in (
        "reduce-scatter", "all-reduce", "all-reduce-start")) >= 1


@pytest.mark.parametrize("n", [64])
def test_state_bytes_shrink_subprocess(n):
    """The 0.8*n shrink holds at n=64 (subprocess with 64 virtual
    devices) — the tier-1 guard at a width the in-process mesh can't
    reach."""
    code = """
import json, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, optimizer as opt_mod
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

mx.random.seed(1)
net = nn.HybridSequential(prefix="z64_")
with net.name_scope():
    net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
net.initialize(ctx=mx.cpu())
net(mx.nd.zeros((2, 32), ctx=mx.cpu()))
zs = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    mesh=create_mesh({"dp": %d}), zero=True,
                    optimizer=opt_mod.create("adam"))
per_dev = sum(int(v.addressable_shards[0].data.nbytes)
              for v in zs.train_vals + zs.opt_state)
json.dump({"per_dev": per_dev,
           "repl": zs.zero_layout["replicated_param_bytes"],
           "n": zs.zero_layout["n"]}, sys.stdout)
""" % n
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % n
    env["PYTHONPATH"] = REPO
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout)
    assert out["n"] == n
    # params + 2 Adam moments replicated would be 3x repl; per-device
    # must be <= that / (0.8 n)
    assert out["repl"] * 3 / out["per_dev"] >= 0.8 * n


# ------------------------------------------------------- seam & guards


def test_trainer_compile_zero_routing():
    """``trainer.compile(zero=True)`` yields a ZeroCompiledStep; the
    default is the replicated CompiledStep."""
    from mxnet_tpu.compiled_step import CompiledStep, ZeroCompiledStep

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _mlp("zrt_")
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    assert isinstance(tr.compile(net, loss_fn, zero=True),
                      ZeroCompiledStep)
    assert isinstance(tr.compile(net, loss_fn), CompiledStep)


def test_zero_step_counters_timeline_and_health():
    """One sharded step feeds every surface: zero_* counters, the
    metrics-timeline per-window columns, and the health grad-norm
    scalar."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=3)
    net = _mlp("zobs_")
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.01})
    zs = tr.compile(net, loss_fn, zero=True)
    metrics_timeline.enable(interval=1)
    hm = health.enable(interval=1)
    for x, y in zip(xs, ys):
        zs.step(mx.nd.array(x), mx.nd.array(y))
    c = runtime_stats.snapshot()["counters"]
    assert c["zero_steps"] == 3
    assert c["zero_allgather_bytes"] > 0
    assert c["zero_reduce_bytes"] > 0
    samples = metrics_timeline.samples()
    assert any(s.get("zero_allgather_bytes") for s in samples)
    flight = health.snapshot()["flight"]
    assert flight and any(r["grad_norm"] is not None for r in flight)
    assert any(r["key"] == "grad_norm" for r in hm.records)


def test_zero_guards():
    """Unsafe configurations raise, not silently degrade: non-safe
    optimizer, param_spec_fn composition."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = create_mesh({"dp": 8})
    net = _mlp("zgrd_")
    with pytest.raises(MXNetError, match="param_spec_fn"):
        GluonTrainStep(net, loss_fn, mesh=mesh, zero=True,
                       param_spec_fn=lambda *a: None)
    with pytest.raises(MXNetError, match="not compiled-step safe"):
        GluonTrainStep(net, loss_fn, mesh=mesh, zero=True,
                       optimizer=opt_mod.create("lbsgd"))


def test_adagrad_adadelta_eager_vs_compiled_within_tolerance():
    """The two newly compiled_step_safe optimizers: eager Trainer loop
    and the (unsharded) whole-step program follow the same trajectory.
    Per-op dispatch and one fused program round differently (fusion,
    reduction order), so the match is to float32 tolerance over the 5
    steps, not bit for bit."""
    from mxnet_tpu import autograd

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=5, batch=8)
    for name, kw in (("adagrad", {"learning_rate": 0.05}),
                     ("adadelta", {})):
        net_e = _mlp("zsafe_%s_e_" % name)
        tr_e = gluon.Trainer(net_e.collect_params(), name, dict(kw))
        le = []
        for x, y in zip(xs, ys):
            xa, ya = mx.nd.array(x), mx.nd.array(y)
            with autograd.record():
                l = loss_fn(net_e(xa), ya)
            l.backward()
            tr_e.step(x.shape[0])
            le.append(float(l.mean().asscalar()))
        net_c = _mlp("zsafe_%s_c_" % name)
        tr_c = gluon.Trainer(net_c.collect_params(), name, dict(kw))
        cs = tr_c.compile(net_c, loss_fn)
        lc = [float(cs.step(mx.nd.array(x), mx.nd.array(y))
                    .mean().asscalar()) for x, y in zip(xs, ys)]
        np.testing.assert_allclose(le, lc, rtol=1e-5, err_msg=name)
        for pa, pb in zip(net_e.collect_params().values(),
                          net_c.collect_params().values()):
            np.testing.assert_allclose(
                pa.data().asnumpy(), pb.data().asnumpy(), rtol=1e-4,
                atol=1e-6, err_msg="%s %s" % (name, pa.name))


# -------------------------------------------------------- observability


def test_compare_zero_counters_notes_not_regression():
    """compare(): zero:* rows present on one side only are topology
    notes, never part of the verdict; present on BOTH sides they gate
    like any counter."""
    base = {"snapshot": {"counters": {"trainer_steps": 4},
                         "stepstats": {}, "totals": {}, "ops": {}}}
    zero = {"snapshot": {"counters": {
        "trainer_steps": 4, "zero_steps": 4,
        "zero_allgather_bytes": 4000000, "zero_reduce_bytes": 4000000},
        "stepstats": {}, "totals": {}, "ops": {}}}
    r = runtime_stats.compare(base, zero)
    assert r["verdict"] == "flat"
    assert {e["metric"] for e in r["notes"]} == {
        "zero:zero_allgather_bytes", "zero:zero_reduce_bytes"}
    assert all(e["side"] == "after-only" for e in r["notes"])
    worse = {"snapshot": {"counters": {
        "trainer_steps": 4, "zero_steps": 4,
        "zero_allgather_bytes": 8000000, "zero_reduce_bytes": 4000000},
        "stepstats": {}, "totals": {}, "ops": {}}}
    r2 = runtime_stats.compare(zero, worse)
    assert r2["verdict"] == "regression"
    assert any(e["metric"] == "zero:zero_allgather_bytes"
               for e in r2["regressions"])
    assert not r2["notes"]
    rendered = runtime_stats.render_compare(r)
    assert "sharding topology differs" in rendered


def test_doctor_zero_allgather_dominated_rule():
    """The doctor flags an all-gather-dominated zero run and stays
    silent when the gather is a small share of the step's traffic."""
    hot = {"snapshot": {
        "counters": {"zero_steps": 10, "zero_allgather_bytes": int(3e7),
                     "zero_reduce_bytes": int(3e7)},
        "stepstats": {}, "totals": {}, "ops": {},
        "costs": {"compiled_step": {"bytes_per_call": 4e6}}}}
    findings = perfdoctor.diagnose(dump=hot)
    f = [x for x in findings if x["rule"] == "zero-allgather-dominated"]
    assert f and "docs/ZERO.md" in f[0]["action"]
    cold = {"snapshot": {
        "counters": {"zero_steps": 10, "zero_allgather_bytes": int(1e6),
                     "zero_reduce_bytes": int(1e6)},
        "stepstats": {}, "totals": {}, "ops": {},
        "costs": {"compiled_step": {"bytes_per_call": 4e7}}}}
    assert not [x for x in perfdoctor.diagnose(dump=cold)
                if x["rule"] == "zero-allgather-dominated"]
