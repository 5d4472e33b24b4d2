"""The program's spans as the benchmark reads them
(``benchmark/harness/program_spans.py``): device phases from ``op_name``
scopes, host phases from ``mxtpu.*`` annotations, on hand-made traces, on a
live CPU profile and on a recorded trace cut from a chip run of PR 25
(``fixtures/resnet50_train_bs128_spans_2steps.*``, cut by
``fixtures/cut_xplane_spans.py``)."""

import gzip
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import (hlo_cost, manifest,  # noqa: E402
                               program_spans, trace)

CELLS = ["resnet50_train_bs128", "inception3_train_bs128",
         "resnet50_train_dp4"]
NEW_METRICS = {
    "step.forward_ms": "step", "step.backward_ms": "step",
    "step.optimizer_ms": "step", "step.other_ms": "step",
    "entry.key_ms_per_step": "entry", "entry.launch_ms_per_step": "entry"}


# ------------------------------------------------------------ device phases

BLOCK = "resnetv10)/resnetv10/stage1/resnetv10_stage1/conv2d0/jit(<unknown>)"


@pytest.mark.parametrize("op_name, phase", [
    # a block's forward under jvp(...), as GluonTrainStep lowers it
    ("jit(step)/grad/jvp(" + BLOCK + "/conv_general_dilated", "forward"),
    # the same under transpose(jvp(...)): its backward
    ("jit(step)/grad/transpose(jvp(" + BLOCK + "/conv_general_dilated",
     "backward"),
    ("jit(step)/grad/jvp(loss)/softmaxcrossentropyloss0/jit(<unknown>)/"
     "jit(log_softmax)/exp", "forward"),
    ("jit(step)/grad/transpose(jvp(loss))/softmaxcrossentropyloss0/"
     "jit(<unknown>)/jit(log_softmax)/mul", "backward"),
    # a scope wrapped once more by pjit(...)
    ("jit(step)/grad/pjit(jvp(hybridsequential0))/dense0/dot_general",
     "forward"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/optimizer/jit(call)/sqrt", "optimizer"),
    # the ZeRO regions are phases of their own: not one of the three
    ("jit(step)/grad/jvp(zero_allgather)/sharding_constraint", "other"),
    ("jit(step)/zero_reduce_scatter/sharding_constraint", "other"),
    ("jit(step)/zero_gradnorm/reduce_sum", "other"),
    ("", "other"),
    (None, "other"),
    # bare primitives: the gradient norm, the casts around the blocks
    ("jit(step)/reduce_sum", "other"),
    ("jit(step)/grad/transpose(jvp())/convert_element_type", "other"),
    # another program's instructions: jax's own while/body is no scope of
    # the program's, and nothing of the key's lies inside ``grad``
    ("jit(_threefry_fold_in)/while/body/closed_call/add", "other"),
    ("jit(_threefry_seed)/convert_element_type", "other"),
    # a parameter's name, as prefetch copies carry it
    ("train_vals[140]", "other"),
])
def test_phase_of_real_op_names(op_name, phase):
    assert program_spans.phase_of(op_name) == phase


STEP_MODULE = """HloModule jit_step, is_scheduled=true

%fc.fwd (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  ROOT %m.1 = f32[256]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/grad/jvp(net)/dense0/mul"}
}

%fc.bwd (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %r.1 = f32[256]{0} maximum(%p0, %p0), metadata={op_name="jit(step)/grad/jvp(net)/relu0/max"}
  %g.1 = f32[256]{0} multiply(%r.1, %p0), metadata={op_name="jit(step)/grad/transpose(jvp(net))/relu0/mul"}
  ROOT %u.1 = f32[256]{0} subtract(%p0, %g.1), metadata={op_name="jit(step)/optimizer/sub"}
}

%fc.opt (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  ROOT %s.1 = f32[256]{0} subtract(%p0, %p0), metadata={op_name="jit(step)/optimizer/sub"}
}

ENTRY %main (a: f32[256]) -> f32[256] {
  %a = f32[256]{0} parameter(0)
  %fusion.fwd = f32[256]{0} fusion(%a), kind=kLoop, calls=%fc.fwd
  %fusion.bwd = f32[256]{0} fusion(%fusion.fwd), kind=kLoop, calls=%fc.bwd
  %copy.1 = f32[256]{0} copy(%a), metadata={op_name="train_vals[0]"}
  ROOT %fusion.opt = f32[256]{0} fusion(%a), kind=kLoop, calls=%fc.opt
}
"""


def _text(name, opcode="fusion", calls=None):
    return "%%%s = f32[256]{0} %s(f32[256]{0} %%a)%s" % (
        name, opcode, ", kind=kLoop, calls=%" + calls if calls else "")


def _trace(ops, spans=()):
    dev = {"ops": [trace.Op(s, e, t) for s, e, t in ops], "async": [],
           "modules": []}
    return trace.Trace({0: dev}, list(spans))


def _step_trace():
    """Two steps of 100 ns: forward 20, backward 40 (a fusion that starts
    with a forward instruction the compiler duplicated into it and ends in
    the update, as on the chip: ``hlo_cost`` names it by the first), a copy
    of 5, a 7 ns wait in an ``async-done`` (a container to ``leaf_ops``),
    optimizer 10, and 18 idle."""
    ops = []
    for base in (0, 100):
        ops += [(base, base + 20, _text("fusion.fwd", calls="fc.fwd")),
                (base + 20, base + 60, _text("fusion.bwd", calls="fc.bwd")),
                (base + 60, base + 65, _text("copy.1", "copy")),
                (base + 65, base + 72, _text("slice-done.9", "async-done")),
                (base + 72, base + 82, _text("fusion.opt", calls="fc.opt"))]
    return _trace(ops, [(trace.WINDOW_SPAN, 0, 200)])


def test_device_phases_conserve_the_first_chips_busy_time():
    t, modules = _step_trace(), [hlo_cost.Module(STEP_MODULE)]
    assert program_spans.phase_of(
        modules[0].instructions["fusion.bwd"][1]) == "forward"
    seconds = program_spans.device_phase_s(t, modules)
    assert seconds == {
        "forward": pytest.approx(40e-9), "backward": pytest.approx(80e-9),
        "optimizer": pytest.approx(20e-9),
        # the copy carries a parameter's name, the wait is no leaf: both
        # are time of the step that no scope covers
        "other": pytest.approx((5 + 7) * 2e-9)}
    busy_s, _ = trace.busy_and_window_s(t)
    assert sum(seconds.values()) == pytest.approx(busy_s) == \
        pytest.approx(164e-9)
    obs = {"trace": t, "modules": modules, "tail": {"steps": 2}}
    per_step = {p: program_spans.device_phase_ms_per_step(obs, p)
                for p in program_spans.PHASES}
    assert per_step == {
        "forward": pytest.approx(20e-6), "backward": pytest.approx(40e-6),
        "optimizer": pytest.approx(10e-6), "other": pytest.approx(12e-6)}


def test_without_the_modules_text_no_phase_is_reported():
    """All of the time would read as ``other``: a program whose
    instructions carry no scope is not reported as 0 ms of forward."""
    obs = {"trace": _step_trace(), "modules": [], "tail": {"steps": 2}}
    for phase in program_spans.PHASES:
        assert program_spans.device_phase_ms_per_step(obs, phase) is None


# -------------------------------------------------------------- host phases

# (name, start, end, line, stats): two steps on the main thread, one span
# of another thread that overlaps them in time
SPANS = sorted([
    ("mxtpu.step", 100, 200, "python#0", {"step_num": 0}),
    ("mxtpu.step.key", 110, 140, "python#0", {}),
    ("mxtpu.step.launch", 150, 190, "python#0", {"leaves": 431}),
    ("mxtpu.step", 300, 420, "python#0", {"step_num": 1}),
    ("mxtpu.step.put_batch", 300, 330, "python#0", {}),
    ("mxtpu.step.key", 330, 350, "python#0", {}),
    ("mxtpu.step.launch", 360, 420, "python#0", {"leaves": 431}),
    ("mxtpu.step.key", 120, 400, "worker#1", {}),
], key=lambda s: (s[1], -s[2]))


def test_parents_self_time_and_per_step_means_of_host_spans():
    names = [s[0].rsplit(".", 1)[-1] + "@" + s[3] for s in SPANS]
    parent = program_spans.parents(SPANS)
    assert [(names[i], p if p is None else names[p])
            for i, p in enumerate(parent)] == [
        ("step@python#0", None), ("key@python#0", "step@python#0"),
        ("key@worker#1", None), ("launch@python#0", "step@python#0"),
        ("step@python#0", None), ("put_batch@python#0", "step@python#0"),
        ("key@python#0", "step@python#0"),
        ("launch@python#0", "step@python#0")]
    own = dict(zip([(n, s[1]) for n, s in zip(names, SPANS)],
                   program_spans.self_ns(SPANS)))
    assert own[("step@python#0", 100)] == 100 - 30 - 40
    assert own[("step@python#0", 300)] == 120 - 30 - 20 - 60
    assert own[("key@worker#1", 120)] == 280
    main = [s for s in SPANS if s[3] == "python#0"]
    per_step = program_spans.span_ms_per_step
    assert per_step(main, "mxtpu.step.key", (0, 500), 2) == \
        pytest.approx((30 + 20) / 1e6 / 2)
    assert per_step(main, "mxtpu.step.launch", (0, 500), 2) == \
        pytest.approx((40 + 60) / 1e6 / 2)
    # only what lies inside the window counts
    assert per_step(main, "mxtpu.step.launch", (0, 250), 1) == \
        pytest.approx(40 / 1e6)
    assert per_step(main, "mxtpu.step.scalars", (0, 500), 2) is None
    assert per_step([], "mxtpu.step.key", (0, 500), 2) is None


def test_idle_gaps_go_to_the_innermost_program_span():
    t = _trace(
        ops=[(0, 105, _text("fusion.1")), (135, 155, _text("fusion.1")),
             (195, 250, _text("fusion.1")), (290, 500, _text("fusion.1"))],
        spans=[(trace.WINDOW_SPAN, 0, 500)])
    main = [s for s in SPANS if s[3] == "python#0"]
    found = program_spans.idle_by_span(t, main)
    # 105-135: the step covers all of it and its key 25 of the 30, so the
    # inner span takes it; 155-195: launch covers 35 of 40; 250-290: the
    # host is between two steps
    assert found == [["mxtpu.step.launch", pytest.approx(40e-9)],
                     ["outside_step", pytest.approx(40e-9)],
                     ["mxtpu.step.key", pytest.approx(30e-9)]]
    # a gap of which no child covers more than half is the step's own
    own = program_spans.idle_by_span(
        _trace(ops=[(0, 125, _text("fusion.1")),
                    (165, 500, _text("fusion.1"))],
               spans=[(trace.WINDOW_SPAN, 0, 500)]), main)
    assert own == [["mxtpu.step", pytest.approx(40e-9)]]
    assert program_spans.idle_by_span(trace.Trace({}, []), main) == []


def test_host_spans_of_a_live_profile_and_the_search_for_the_runs_own(
        tmp_path, monkeypatch):
    """``run.py`` writes its trace under ``<tmp>/benchmark_run_*/trace`` and
    hands the readers neither the path nor the program's spans: with no
    path, the newest such trace is read."""
    import jax

    def profile(trace_dir, step_num):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("dispatch"):
                with jax.profiler.StepTraceAnnotation("mxtpu.step",
                                                      step_num=step_num):
                    with jax.profiler.TraceAnnotation("mxtpu.step.launch",
                                                      leaves=7):
                        pass
        finally:
            jax.profiler.stop_trace()
        return trace.newest_xplane(str(trace_dir))

    monkeypatch.setattr(program_spans.tempfile, "tempdir", str(tmp_path))
    assert program_spans.host_spans() == []        # no run left a trace
    old = profile(tmp_path / "benchmark_run_old" / "trace", 4)
    new = profile(tmp_path / "benchmark_run_new" / "trace", 5)
    os.utime(old, (1, 1))
    spans = program_spans.host_spans()
    assert spans == program_spans.host_spans(new)
    assert [s[0] for s in spans] == ["mxtpu.step", "mxtpu.step.launch"]
    step, launch = spans
    assert step[4]["step_num"] == 5 and launch[4] == {"leaves": 7}
    assert step[3] == launch[3]                    # one thread's line
    assert step[1] <= launch[1] <= launch[2] <= step[2]
    assert program_spans.parents(spans) == [None, 0]
    assert program_spans.host_spans(old)[0][4]["step_num"] == 4
    # the benchmark's own span is not the program's
    assert program_spans.host_spans(new, prefix="dispatch")[0][0] == \
        "dispatch"


# ----------------------------------------------------- readers and manifest


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
@pytest.mark.parametrize("observed", ["no_trace", "no_device_plane"])
def test_a_reader_finds_nothing_to_read_without_a_chips_trace(
        metric, observed, tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans.tempfile, "tempdir", str(tmp_path))
    reader = manifest.Manifest(REPO).cell(CELLS[0]).reader(metric)
    recorded = None if observed == "no_trace" else trace.Trace({}, [])
    obs = {"trace": recorded, "modules": [], "tail": None,
           "window": {"dispatch_s": [0.004]}}
    assert reader.read(obs) is None
    obs["tail"] = {"steps": 10}
    assert reader.read(obs) is None


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_metrics_resolve_and_list_the_three_cells(metric):
    m = manifest.Manifest(REPO)
    entry = m.named("per_layer", metric)
    assert entry["workloads"] == CELLS
    assert entry["layer"] == NEW_METRICS[metric]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("ms", "lower", "device_trace",
                                "train_samples_per_s")
    for cell in CELLS:
        assert entry in m.cell(cell).per_layer
        assert callable(m.cell(cell).reader(metric).read)


# ----------------------------------------------------------- recorded trace

FIXTURE = "resnet50_train_bs128_spans_2steps"


@pytest.fixture()
def recorded(tmp_path):
    """(path, Trace, modules): two steps of resnet50_train_bs128 on the v5e
    (PR 25), the last of one group and the first of the next, with the
    optimized ``jit_step`` module of the same run."""
    path = str(tmp_path / (FIXTURE + ".xplane.pb"))
    with gzip.open(os.path.join(HERE, "fixtures",
                                FIXTURE + ".xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(HERE, "fixtures",
                                FIXTURE + ".jit_step.hlo.txt.gz"),
                   "rt") as f:
        modules = [hlo_cost.Module(f.read())]
    spans = {"dispatch", "loss_fetch", trace.WINDOW_SPAN}
    return path, trace.Trace.from_xplane(path, spans), modules


def test_recorded_device_phases_and_their_conservation(recorded):
    _, t, modules = recorded
    seconds = program_spans.device_phase_s(t, modules)
    # per step: forward 13.75, backward 29.76, optimizer 0.05 (the update
    # is fused into the weight gradients), other 4.40 ms
    assert seconds == {
        "forward": pytest.approx(0.027509799),
        "backward": pytest.approx(0.059518428),
        "optimizer": pytest.approx(0.000095147),
        "other": pytest.approx(0.008797383)}
    busy_s, window_s = trace.busy_and_window_s(t)
    assert busy_s == pytest.approx(0.095920757)
    assert sum(seconds.values()) == pytest.approx(busy_s)
    # the instructions of their own alone fall 2 % short: the waits in
    # ``async-done`` are containers to ``leaf_ops``
    leaf_s = sum(op.end - op.start for op in trace.leaf_ops(t)) / 1e9
    assert leaf_s == pytest.approx(0.093957032)
    assert busy_s - leaf_s > 0.02 * busy_s
    obs = {"trace": t, "modules": modules, "tail": {"steps": 2}}
    assert program_spans.device_phase_ms_per_step(obs, "backward") == \
        pytest.approx(29.759214)


def test_recorded_heaviest_instructions_are_backward(recorded):
    """What the ledger's cut names could not say: the three heaviest are
    backward convolutions; the stage-1 batch-norm fusions after them run in
    the backward pass too, though ``hlo_cost`` names them by the forward
    instruction duplicated into them."""
    _, t, modules = recorded
    by_name = {}
    for op in trace.leaf_ops(t):
        entry = by_name.setdefault(op.name, [0, op])
        entry[0] += op.end - op.start
    ranked = sorted(by_name.values(), key=lambda e: -e[0])[:6]
    named = [(op.name, program_spans._instruction_phase(op, modules),
              program_spans.phase_of(modules[0].instructions[op.name][1]))
             for _, op in ranked]
    assert named == [
        ("fusion.1677", "backward", "backward"),
        ("fusion.1675", "backward", "backward"),
        ("fusion.1676", "backward", "backward"),
        ("fusion.1911", "backward", "forward"),
        ("fusion.1921", "backward", "forward"),
        ("fusion.1934", "backward", "forward")]
    assert [round(ns / 2e6, 2) for ns, _ in ranked] == \
        [1.45, 1.21, 1.17, 0.93, 0.91, 0.91]
    for _, op in ranked[:3]:
        assert modules[0].instructions[op.name][1].endswith(
            "conv_general_dilated")


def test_recorded_host_spans_run_ahead_of_the_chip(recorded):
    """While the chip runs these two steps the host dispatches eight: it
    is seven steps ahead, and the eighth blocks inside its key."""
    path, t, _ = recorded
    spans = program_spans.host_spans(path)
    steps = [s for s in spans if s[0] == "mxtpu.step"]
    assert [s[4]["step_num"] for s in steps] == list(range(231, 239))
    assert {s[3] for s in spans} == {steps[0][3]}          # one line
    launches = [s for s in spans if s[0] == "mxtpu.step.launch"]
    assert {s[4]["leaves"] for s in launches} == {495}
    parent = program_spans.parents(spans)
    for i, span in enumerate(spans):
        assert (parent[i] is None) == (span[0] == "mxtpu.step")
        if parent[i] is not None:
            assert spans[parent[i]][0] == "mxtpu.step"
    # the step's own time: 39 us of the first step's 6.05 ms
    assert program_spans.self_ns(spans)[0] == pytest.approx(39081)
    keys = [round((s[2] - s[1]) / 1e6, 1) for s in spans
            if s[0] == "mxtpu.step.key"]
    assert keys == [2.4, 2.0, 1.8, 1.7, 1.6, 1.6, 1.5, 23.0]
    # each key and launch lies inside one of the benchmark's dispatch spans
    dispatches = [s for s in t.spans if s[0] == "dispatch"]
    for _, start, end, _, _ in spans:
        assert any(d[1] <= start and end <= d[2] for d in dispatches)
    # inside the (cut) window: seven launches, eight keys of which the
    # blocked one ends after it
    window = t.window()
    assert program_spans.span_ms_per_step(
        spans, "mxtpu.step.launch", window, 2) == pytest.approx(7.2132195)
    assert program_spans.span_ms_per_step(
        spans, "mxtpu.step.key", window, 2) == pytest.approx(6.306623)
    # the chip idles 3.4 ms while the host draws the next group's first key
    # and 2.4 ms before that, while the benchmark fetches the loss
    assert program_spans.idle_by_span(t, spans) == [
        ["mxtpu.step.key", pytest.approx(0.003367175)],
        ["outside_step", pytest.approx(0.002422794)],
        ["mxtpu.step.launch", pytest.approx(2.965e-06)],
        ["mxtpu.step", pytest.approx(1.8e-08)]]
    assert sum(s for _, s in program_spans.idle_by_span(t, spans)) == \
        pytest.approx(sum(s for _, s in trace.idle_gaps(t)))
