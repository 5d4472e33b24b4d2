"""PR 11: whole-step compilation (mxnet_tpu/compiled_step.py).

Pins the acceptance criteria:

- eager vs compiled parity: same model/data/seed gives BIT-EXACT f32
  losses and params over N steps for every compiled-step-safe fused
  optimizer (incl. Adam bias correction and a per-step lr scheduler),
  and pinned-tolerance parity for conv models (the fused program's
  XLA autodiff may reassociate conv-backward reductions);
- donation safety: the old param buffers are really donated (deleted)
  while the Parameters stay fully usable — eager reads, eager
  forwards, save/load, checkpoint save/resume mid-run (the pinned
  zero-copy snapshot) all keep working between compiled steps;
- shape changes build a NEW cache entry (a counted compiled_step
  jit-cache miss), never a per-step silent recompile;
- the observability substrate sees the compiled path end to end: the
  dedicated ``compiled_step`` stepstats phase, ~1 warm dispatch per
  step in the counters, coherent metrics-timeline windows, and the
  perf doctor's eager-dispatch-tax recommendation on eager dumps.
"""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (autograd, checkpoint, compiled_step, gluon,
                       histogram, metrics_timeline, perfdoctor,
                       runtime_stats, stepstats)
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    runtime_stats.reset()
    stepstats.disable()
    histogram.disable()
    metrics_timeline.disable()
    metrics_timeline.reset()
    yield
    checkpoint.disable()
    # disable() keeps the manager readable by design; later suites
    # assert a clean _GLOBAL (test_bench_gate overhead bound)
    checkpoint._GLOBAL.clear()
    metrics_timeline.disable()
    metrics_timeline.reset()
    runtime_stats.reset()
    stepstats.disable()
    histogram.disable()


def _make_mlp(seed=42, hybridize=False, dropout=0.0, batchnorm=False):
    mx.random.seed(seed)
    np.random.seed(seed)
    # fixed prefix: checkpoint manifests key params by name, and the
    # default prefix counter is process-global
    net = nn.HybridSequential(prefix="csnet_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        if batchnorm:
            net.add(nn.BatchNorm())
        if dropout:
            net.add(nn.Dropout(dropout))
        net.add(nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    if hybridize:
        net.hybridize()
    net(mx.nd.zeros((2, 8), ctx=mx.cpu()))
    return net


def _data(n=5, batch=8, feat=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    return ([rs.rand(batch, feat).astype(np.float32) for _ in range(n)],
            [rs.randint(0, classes, (batch,)).astype(np.int32)
             for _ in range(n)])


def _run_eager(net, trainer, loss_fn, xs, ys, batch=None):
    losses = []
    for x, y in zip(xs, ys):
        xa, ya = mx.nd.array(x), mx.nd.array(y)
        with autograd.record():
            l = loss_fn(net(xa), ya)
        l.backward()
        trainer.step(batch or x.shape[0])
        losses.append(float(l.mean().asscalar()))
    return losses


def _run_compiled(cs, xs, ys):
    return [float(cs.step(mx.nd.array(x), mx.nd.array(y))
                  .mean().asscalar()) for x, y in zip(xs, ys)]


def _assert_params_equal(net_a, net_b, exact=True, rtol=0.0):
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        a, b = pa.data().asnumpy(), pb.data().asnumpy()
        if exact:
            assert np.array_equal(a, b), \
                "param %s diverged (max %g)" % (pa.name,
                                                np.abs(a - b).max())
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, err_msg=pa.name)


# --------------------------------------------------------------- parity


@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
    ("adamax", {}),
    ("ftrl", {}),
])
def test_parity_bit_exact_f32(opt, kw):
    """Same model/data/seed: eager and compiled f32 losses AND params
    are bit-identical over 5 steps — the per-step scalars (Adam's
    host-double bias correction included) flow as traced inputs with
    the exact values the eager path uses."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data()
    net_e = _make_mlp()
    tr_e = gluon.Trainer(net_e.collect_params(), opt, dict(kw))
    le = _run_eager(net_e, tr_e, loss_fn, xs, ys)
    net_c = _make_mlp()
    tr_c = gluon.Trainer(net_c.collect_params(), opt, dict(kw))
    cs = tr_c.compile(net_c, loss_fn)
    lc = _run_compiled(cs, xs, ys)
    assert le == lc
    _assert_params_equal(net_e, net_c)


def test_parity_lr_scheduler_bit_exact():
    """A per-step scheduler lr is a traced input, not a baked constant:
    the compiled program follows the schedule without retracing and
    matches eager bit for bit."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=6)
    kw = {"learning_rate": 0.2, "momentum": 0.9,
          "lr_scheduler": mx.lr_scheduler.FactorScheduler(2, 0.5)}
    net_e = _make_mlp()
    tr_e = gluon.Trainer(net_e.collect_params(), "sgd", dict(
        kw, lr_scheduler=mx.lr_scheduler.FactorScheduler(2, 0.5)))
    le = _run_eager(net_e, tr_e, loss_fn, xs, ys)
    net_c = _make_mlp()
    tr_c = gluon.Trainer(net_c.collect_params(), "sgd", dict(
        kw, lr_scheduler=mx.lr_scheduler.FactorScheduler(2, 0.5)))
    cs = tr_c.compile(net_c, loss_fn)
    lc = _run_compiled(cs, xs, ys)
    assert le == lc
    _assert_params_equal(net_e, net_c)
    # the schedule never forced a rebuild: one program, many lr values
    assert len(cs._cache) == 1


def test_parity_hybridized_dropout_and_bn():
    """Dropout + BatchNorm vs the HYBRIDIZED eager path: both consume
    exactly one PRNG key per step (the CachedOp idiom), so the mask
    sequence — and therefore the whole trajectory — matches
    bit-exactly; BN running stats ride the aux-update channel."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=4)
    net_e = _make_mlp(hybridize=True, dropout=0.5, batchnorm=True)
    tr_e = gluon.Trainer(net_e.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    le = _run_eager(net_e, tr_e, loss_fn, xs, ys)
    net_c = _make_mlp(hybridize=True, dropout=0.5, batchnorm=True)
    tr_c = gluon.Trainer(net_c.collect_params(), "sgd",
                         {"learning_rate": 0.1})
    cs = tr_c.compile(net_c, loss_fn)
    lc = _run_compiled(cs, xs, ys)
    assert le == lc
    _assert_params_equal(net_e, net_c)  # includes BN running stats


def test_parity_conv_model_pinned_tolerance():
    """Conv models: the fused program's XLA autodiff may reassociate
    conv-backward reductions vs the per-op tape, so the contract is
    first-step-exact forward + pinned-tolerance trajectory."""
    def make_conv(seed=3):
        mx.random.seed(seed)
        np.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(8, 3, padding=1, layout="NHWC"))
            net.add(nn.BatchNorm())
            net.add(nn.GlobalAvgPool2D(layout="NHWC"))
            net.add(nn.Dense(4))
        net.initialize(ctx=mx.cpu())
        net(mx.nd.zeros((1, 8, 8, 3)))
        return net

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(1)
    xs = [rs.rand(4, 8, 8, 3).astype(np.float32) for _ in range(4)]
    ys = [rs.randint(0, 4, (4,)).astype(np.int32) for _ in range(4)]
    net_e = make_conv()
    tr_e = gluon.Trainer(net_e.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    le = _run_eager(net_e, tr_e, loss_fn, xs, ys)
    net_c = make_conv()
    tr_c = gluon.Trainer(net_c.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    cs = tr_c.compile(net_c, loss_fn)
    lc = _run_compiled(cs, xs, ys)
    np.testing.assert_allclose(le[0], lc[0], rtol=1e-6)
    np.testing.assert_allclose(le, lc, rtol=1e-3)
    _assert_params_equal(net_e, net_c, exact=False, rtol=1e-3)


# ------------------------------------------------------ donation safety


def test_donation_rebinds_and_interop():
    """The param buffers really are donated (old jax buffers deleted),
    yet the Parameter NDArrays keep working for everything downstream:
    eager reads, eager forwards between steps, save/load roundtrip."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=3)
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    cs = tr.compile(net, loss_fn)
    p = list(net.collect_params().values())[0]
    old_buf = p.data()._data
    old_state_buf = None
    cs.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    assert old_buf.is_deleted(), \
        "param input was not donated into the step program"
    # momentum state was donated and rebound too
    upd = tr._updaters[0]
    state_nd = upd.states[tr._param2idx[p.name]]
    old_state_buf = state_nd._data
    # params stay fully usable between steps
    w1 = p.data().asnumpy()
    out_eager = net(mx.nd.array(xs[1])).asnumpy()
    assert np.isfinite(out_eager).all()
    cs.step(mx.nd.array(xs[1]), mx.nd.array(ys[1]))
    assert old_state_buf.is_deleted()
    assert not np.array_equal(w1, p.data().asnumpy())
    # save/load through the normal Gluon API after compiled steps
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        f = os.path.join(d, "net.params")
        net.save_parameters(f)
        net2 = _make_mlp(seed=9)
        net2.load_parameters(f)
        _assert_params_equal(net, net2)


def test_checkpoint_save_resume_mid_run(tmp_path):
    """Auto-checkpointing every compiled step (interval=1) with the
    pinned zero-copy snapshot, then resume from the manifest mid-run:
    the resumed trajectory is bit-exact vs an uninterrupted run, and
    donation never corrupted a snapshot (zero checkpoint errors)."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=6)

    # uninterrupted 6-step compiled reference
    net_ref = _make_mlp()
    tr_ref = gluon.Trainer(net_ref.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
    cs_ref = tr_ref.compile(net_ref, loss_fn)
    ref_losses = _run_compiled(cs_ref, xs, ys)

    # run 1: 4 steps with auto-checkpoint at every step, then "crash"
    ckdir = str(tmp_path / "ck")
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    cs = tr.compile(net, loss_fn)
    checkpoint.enable(ckdir, interval=1)
    _run_compiled(cs, xs[:4], ys[:4])
    mgr = checkpoint.manager()
    mgr.wait()
    assert mgr.totals["errors"] == 0, mgr.last_error
    assert mgr.totals["saves"] >= 4
    checkpoint.disable()

    # run 2: fresh objects, resume, continue steps 5-6 compiled
    net2 = _make_mlp(seed=1)  # different init: must be overwritten
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
    checkpoint.enable(ckdir, interval=1)
    resumed_step = checkpoint.auto_resume(trainer=tr2, block=net2)
    assert resumed_step == 4
    cs2 = tr2.compile(net2, loss_fn)
    resumed = _run_compiled(cs2, xs[4:], ys[4:])
    assert resumed == ref_losses[4:]
    _assert_params_equal(net_ref, net2)


def test_manual_save_auto_pins_against_donation(tmp_path):
    """A MANUAL save_trainer between compiled steps (no explicit
    pin) must still survive the next step's donation: once any
    CompiledStep has stepped, by-reference captures pin automatically
    (compiled_step.donation_active)."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=4)
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    cs = tr.compile(net, loss_fn)
    cs.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    assert compiled_step.donation_active()
    mgr = checkpoint.CheckpointManager(str(tmp_path / "ck"))
    mgr.save_trainer(tr, step=1)  # async, by reference, NO pin arg
    want = {p.name: p.data().asnumpy()
            for p in net.collect_params().values()}
    # the very next step donates the captured buffers
    cs.step(mx.nd.array(xs[1]), mx.nd.array(ys[1]))
    assert mgr.wait(timeout=30)
    assert mgr.totals["errors"] == 0, mgr.last_error
    mgr.close()
    # the snapshot holds the step-1 values, not garbage
    net2 = _make_mlp(seed=2)
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
    mgr2 = checkpoint.CheckpointManager(str(tmp_path / "ck"))
    assert mgr2.restore(trainer=tr2, block=net2) is not None
    for p in net2.collect_params().values():
        np.testing.assert_array_equal(p.data().asnumpy(), want[p.name])


# ------------------------------------------------- cache & observability


def test_shape_change_new_entry_not_recompile_storm():
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=4)
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    cs = tr.compile(net, loss_fn)
    for x, y in zip(xs, ys):
        cs.step(mx.nd.array(x), mx.nd.array(y))
    assert len(cs._cache) == 1  # steady shape: ONE program
    # ... that XLA compiled ONCE: the fresh momentum state goes in
    # committed, like the state the program hands back, so the second
    # step reuses the first step's executable (on the chip a second
    # ResNet-50 compile cost ~35 s)
    (entry,) = cs._cache.values()
    assert entry.fn._cache_size() == 1
    cs.step(mx.nd.array(xs[0][:4]), mx.nd.array(ys[0][:4]))
    cs.step(mx.nd.array(xs[1][:4]), mx.nd.array(ys[1][:4]))
    assert len(cs._cache) == 2  # new batch shape: one NEW entry
    snap = runtime_stats.snapshot()
    row = snap["ops"]["compiled_step"]
    assert row["misses"] == 2
    assert row["hits"] == 4  # every other step reused a cached program
    assert row["compile_seconds"] > 0
    assert snap["counters"]["compiled_step_steps"] == 6
    # the cache-keyed build registered with the storm detector's
    # bookkeeping (visible evidence, no warning below threshold)
    assert snap["storms"]["compiled_step"]["compiles"] == 2


def test_stepstats_compiled_phase_and_timeline_coherence():
    """The dedicated ``compiled_step`` stepstats phase carries the warm
    call, per-op warm dispatches collapse to ~1/step, and the metrics
    timeline's windowed deltas stay coherent (compiled_steps=1,
    no misses) in the fused steady state."""
    stepstats.enable()
    metrics_timeline.enable()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=5)
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    cs = tr.compile(net, loss_fn)
    for x, y in zip(xs, ys):
        cs.step(mx.nd.array(x), mx.nd.array(y))
    ss = stepstats.snapshot()
    assert ss["steps"] == 4  # first boundary arms the clock
    assert "compiled_step" in ss["phases"]
    assert ss["phases"]["compiled_step"]["sum"] > 0
    a = stepstats.anatomy(ss)
    assert a["phases"]["compiled_step"]["share"] > 0
    # steady state: one compiled_step hit per step, nothing else warm
    snap = runtime_stats.snapshot()
    steps = snap["counters"]["compiled_step_steps"]
    assert snap["ops"]["compiled_step"]["hits"] == steps - 1
    samples = metrics_timeline.samples()
    assert len(samples) == 4
    for s in samples[1:]:  # first sample's window covers the build
        assert s.get("compiled_steps") == 1
        assert "misses" not in s and "compiles" not in s
        assert s["phases_ms"].get("compiled_step", 0) > 0


def test_trainer_step_histogram_and_span_parity():
    """CompiledStep.step emits the same trainer:step series the eager
    Trainer does, so cluster skew/straggler tooling keeps working."""
    histogram.enable()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=3)
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    cs = tr.compile(net, loss_fn)
    cs.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))  # build step
    warm_after_build = (histogram.snapshot().get("dispatch:warm")
                        or {}).get("count", 0)
    for x, y in zip(xs[1:], ys[1:]):
        cs.step(mx.nd.array(x), mx.nd.array(y))
    snap = histogram.snapshot()
    assert snap["trainer:step"]["count"] == 3
    # whole-step samples land in their OWN series, never dispatch:warm
    # (seconds-long step samples would wreck the per-op distribution):
    # the warm series stops growing once the program is built
    assert snap["compiled_step"]["count"] == 2  # warm calls only
    assert (snap.get("dispatch:warm") or {}).get("count", 0) == \
        warm_after_build


def test_cost_capture_into_diag_costs(monkeypatch):
    """With cost capture active the whole-step program's XLA
    cost/memory analysis lands in the snapshot's cost section like any
    per-op jit entry."""
    monkeypatch.setenv("MXNET_TPU_COST_ANALYSIS", "1")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    xs, ys = _data(n=2)
    net = _make_mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    cs = tr.compile(net, loss_fn)
    for x, y in zip(xs, ys):
        cs.step(mx.nd.array(x), mx.nd.array(y))
    costs = runtime_stats.snapshot()["costs"]
    assert "compiled_step" in costs
    rec = costs["compiled_step"]
    # >=: earlier FAILED tests' traceback frames can keep their
    # CompiledStep instances alive in the weak registry
    assert rec["cache_entries"] >= 1 and rec["analyzed"] >= 1
    assert rec.get("flops_per_call", 0) > 0


# ------------------------------------------------------- guard rails


def test_unsupported_configurations_raise():
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _make_mlp()
    # optimizer with a cross-step host recurrence
    tr = gluon.Trainer(net.collect_params(), "nadam", {})
    with pytest.raises(MXNetError, match="not compiled-step safe"):
        tr.compile(net, loss_fn)
    # server-side updates cannot be traced into a device program
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1},
                       update_on_kvstore=True)
    with pytest.raises(MXNetError, match="kvstore"):
        tr.compile(net, loss_fn)
    # a dist store passed as an OBJECT must hit the same guard as the
    # string form (silently skipping cross-process sync would diverge
    # the replicas)
    class _FakeDist:
        type = "dist_sync"
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=_FakeDist())
    with pytest.raises(MXNetError, match="dist kvstore"):
        tr.compile(net, loss_fn)
    # a trainer param outside the block would silently stop updating
    extra = gluon.Parameter("stray_weight", shape=(2,))
    extra.initialize(ctx=mx.cpu())
    tr = gluon.Trainer(list(net.collect_params().values()) + [extra],
                       "sgd", {"learning_rate": 0.1})
    with pytest.raises(MXNetError, match="stray_weight"):
        tr.compile(net, loss_fn)


# ------------------------------------------------------- perf doctor


def _eager_dump(dispatch_share=0.5, compile_share=0.1, steps=10,
                warm_hits=500, compiled_steps=0):
    counters = {"trainer_steps": steps}
    if compiled_steps:
        counters["compiled_step_steps"] = compiled_steps
    return {"snapshot": {
        "stepstats": {
            "enabled": True, "steps": steps,
            "wall": {"sum": 1.0, "mean": 0.1},
            "phases": {
                "dispatch_warm": {"sum": dispatch_share,
                                  "mean": dispatch_share / steps},
                "compile": {"sum": compile_share,
                            "mean": compile_share / steps},
            },
            "unattributed": {"sum": 0.0},
        },
        "totals": {"jit_cache_hits": warm_hits,
                   "dispatch_seconds": dispatch_share},
        "counters": counters,
    }}


def test_doctor_recommends_compiled_step_on_eager_dump():
    findings = perfdoctor.diagnose(dump=_eager_dump())
    tax = [f for f in findings if f["rule"] == "eager-dispatch-tax"]
    assert len(tax) == 1
    f = tax[0]
    assert f["severity"] == "warn"
    assert "trainer.compile" in f["action"]
    assert "whole-step compilation" in f["title"]
    # projected savings derive from the warm counters: 50 calls/step
    # over a 50% dispatch share projects ~49% of step time back
    assert "saving ~49%" in f["title"]
    assert any("50.0 dispatches/step" in ev for ev in f["evidence"])


def test_doctor_quiet_when_compiled_or_minor():
    # the run already uses the compiled path
    assert not [f for f in perfdoctor.diagnose(
        dump=_eager_dump(compiled_steps=10))
        if f["rule"] == "eager-dispatch-tax"]
    # dispatch share below the warn threshold
    assert not [f for f in perfdoctor.diagnose(
        dump=_eager_dump(dispatch_share=0.1, compile_share=0.02))
        if f["rule"] == "eager-dispatch-tax"]
    # already ~one dispatch per step: nothing to collapse
    assert not [f for f in perfdoctor.diagnose(
        dump=_eager_dump(warm_hits=10))
        if f["rule"] == "eager-dispatch-tax"]
