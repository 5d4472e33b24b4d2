"""PR 25: ``GluonTrainStep.__call__``'s host phases as
``profiler.boundary_span``s: on the jax profiler's clock whenever a
profiler session is on, in the chrome-trace recorder while that runs, and
nowhere otherwise.  A live CPU profile (python tracer off, as the benchmark
traces) of three steps of a two-layer network, in both layouts and with
both kinds of optimizer."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.harness import program_spans, trace  # noqa: E402

import mxnet_tpu as mx
from mxnet_tpu import gluon, optimizer as opt_mod, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

STEP, PUT, KEY, SCALARS, LAUNCH = (
    "mxtpu.step", "mxtpu.step.put_batch", "mxtpu.step.key",
    "mxtpu.step.scalars", "mxtpu.step.launch")


@pytest.fixture(autouse=True)
def _recorder_off():
    profiler.set_state("stop")
    profiler.dumps(reset=True)
    yield
    profiler.set_state("stop")
    profiler.dumps(reset=True)


def _step(prefix, zero, with_optimizer):
    import jax

    mx.random.seed(7)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((2, 12), ctx=mx.cpu()))
    kwargs = {"optimizer": opt_mod.create("adam", learning_rate=0.01)} \
        if with_optimizer else {"lr": 0.1, "momentum": 0.9}
    return GluonTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), zero=zero,
        mesh=create_mesh({"dp": 2}, devices=jax.devices()[:2]), **kwargs)


def _batch():
    rs = np.random.RandomState(0)
    return (rs.rand(8, 12).astype(np.float32),
            rs.randint(0, 4, (8,)).astype(np.int32))


def _profile(tmp_path, body):
    """The ``mxtpu.*`` events of plane /host:CPU while ``body`` ran, as the
    benchmark reads them: [(name, start, end, line, stats)] by start."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return program_spans.host_spans(trace.newest_xplane(str(tmp_path)))


@pytest.mark.parametrize("with_optimizer", [False, True],
                         ids=["fused_sgd", "optimizer"])
@pytest.mark.parametrize("zero", [False, True], ids=["dp", "zero"])
def test_three_steps_on_the_profilers_clock(tmp_path, zero, with_optimizer):
    step = _step("sp%d%d_" % (zero, with_optimizer), zero, with_optimizer)
    x, y = _batch()
    on_device = step.put_batch(x, y)

    def three_steps():
        step(x, y)                      # a host batch
        step(*on_device)                # device arrays: nothing to put
        float(np.asarray(step(x, y)))

    spans = _profile(tmp_path, three_steps)
    steps = [s for s in spans if s[0] == STEP]
    assert [s[4]["step_num"] for s in steps] == [0, 1, 2]
    expected = [KEY] + [SCALARS] * with_optimizer + [LAUNCH]
    for n, (_, start, end, line, _) in enumerate(steps):
        inside = [s for s in spans
                  if s[0] != STEP and start <= s[1] and s[2] <= end]
        # call 0 settles the orders the state is held in (PR 36's spans,
        # tests/test_setup_spans.py); no later call does
        settled = [s[0] for s in inside if s[0].startswith("mxtpu.setup.")]
        assert settled == (n == 0) * (
            ["mxtpu.setup.orders"] + ["mxtpu.setup.orders.learn"] * (not zero)
            + ["mxtpu.setup.orders.relay"] * bool(step._relaid))
        children = [s for s in inside if s[0].startswith(STEP + ".")]
        assert [c[0] for c in children] == \
            [PUT] * (n != 1) + expected
        # on the step's own line, one after the other
        assert {c[3] for c in children} == {line}
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
        launch = children[-1]
        assert launch[4] == {"leaves": step._leaves,
                             "relaid_leaves": step._relaid}
    assert len([s for s in spans if s[0].startswith(STEP)]) \
        == 3 * (1 + len(expected)) + 2
    # every array the launch flattens: parameters, optimizer state,
    # statistics, batch, labels, key and the optimizer's host scalars
    n_scalars = len(step._rule.slots)
    assert (n_scalars > 0) == with_optimizer
    assert step._leaves == len(step.train_vals) + len(step.opt_state) \
        + len(step.aux_vals) + 3 + n_scalars


def test_the_chrome_recorder_sees_the_same_names():
    step = _step("spchrome_", False, True)
    x, y = _batch()
    step(x, y)
    assert "mxtpu." not in profiler.dumps()
    profiler.set_state("run")
    step(x, y)
    float(np.asarray(step(*step.put_batch(x, y))))
    profiler.set_state("stop")
    table = profiler.dumps()
    calls = {line.split()[0]: int(line.split()[1])
             for line in table.splitlines()[1:]
             if line.startswith("mxtpu.")}
    assert calls == {STEP: 2, PUT: 1, KEY: 2, SCALARS: 2, LAUNCH: 2}
    events = [e for e in profiler._state["events"]
              if e["name"].startswith("mxtpu.")]
    assert {e["cat"] for e in events} == {"boundary"}
    assert [e["args"]["step_num"] for e in events
            if e["name"] == STEP] == [1, 2]
    assert {e["args"]["leaves"] for e in events
            if e["name"] == LAUNCH} == {step._leaves}


def test_with_nothing_running_a_step_records_no_event():
    step = _step("spoff_", False, False)
    x, y = _batch()
    float(np.asarray(step(x, y)))
    float(np.asarray(step(x, y)))
    assert profiler._state["events"] == []
    assert step._calls == 2


@pytest.mark.parametrize("step_num", [None, 3])
def test_boundary_span_is_a_jax_annotation_and_propagates_errors(step_num):
    import jax

    span = profiler.boundary_span("mxtpu.test", step_num=step_num, n=1)
    kind = jax.profiler.TraceAnnotation if step_num is None \
        else jax.profiler.StepTraceAnnotation
    assert isinstance(span, kind)
    profiler.set_state("run")
    with pytest.raises(KeyError):
        with profiler.boundary_span("mxtpu.test", step_num=step_num, n=1):
            raise KeyError("x")
    profiler.set_state("stop")
    event, = [e for e in profiler._state["events"]
              if e["name"] == "mxtpu.test"]
    assert event["ph"] == "X" and event["args"]["n"] == 1
    assert event["args"].get("step_num") == step_num
