"""``setup_s`` by phase (``benchmark/harness/setup_phases.py``): the
partition on hand-made records, the readers on a program without the lists,
on this one, and the eight entries of ``BENCHMARK.json`` that arrived with
them."""

import collections
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import device, manifest, setup_phases  # noqa: E402

Span = collections.namedtuple("Span",
                              "name start_ns end_ns parent thread stats")
Built = collections.namedtuple(
    "Built", "kind fun_name start_ns end_ns thread span retrieval_s")

SETUP_METRICS = {
    "setup.trace_lower_s": ("trace_lower", "s"),
    "setup.compile_s": ("compile", "s"),
    "setup.cache_load_s": ("cache_load", "s"),
    "setup.programs_compiled": ("programs_compiled", "count"),
    "setup.state_s": ("state", "s"),
    "setup.warmup_s": ("warmup", "s"),
    "setup.other_s": ("other", "s")}
LANGUAGE_CELLS = ["joyai_flash_train_seq4k", "lfm2_moe_train_seq8k",
                  "mellum2_moe_train_seq16k"]


def span(name, start, end, parent=None, **stats):
    return Span(name, start, end, parent, 1, stats)


def built(kind, start, end, name="jit(step)", under=None, read_s=None):
    return Built(kind, name, start, end, 1, under, read_s)


# a process of 1,000 ns: a check step first called at 300, the timed step at
# 600, set-up's end at 1,000
SPANS = [
    span("mxtpu.setup.place", 100, 160),
    span("mxtpu.setup.orders.learn", 320, 380, "mxtpu.setup.orders",
         source="compiled"),
    span("mxtpu.setup.orders", 310, 400, "mxtpu.step"),
    span("mxtpu.step.launch", 400, 480, "mxtpu.step", leaves=5),
    span("mxtpu.step", 300, 500, step_num=0),
    span("mxtpu.setup.place", 520, 560),
    span("mxtpu.setup.orders.relay", 640, 660, "mxtpu.setup.orders",
         relaid_leaves=2),
    span("mxtpu.setup.orders", 610, 670, "mxtpu.step"),
    span("mxtpu.step.launch", 670, 800, "mxtpu.step", leaves=5),
    span("mxtpu.step", 600, 820, step_num=0),
    # a step first called in the window: after set-up's end, not read
    span("mxtpu.step", 1100, 1200, step_num=0),
]
LOG = [
    built("trace", 20, 40, "forward"),
    built("lower", 40, 50, "jit(forward)"),
    built("compile", 50, 90, "jit(forward)"),
    # an eager program inside a state span: the log wins
    built("cache_load", 120, 130, "jit(copy)", "mxtpu.setup.place", 0.004),
    # the program that is only read, inside .learn; a compile that an
    # eager constant of its trace asked for lies inside the trace
    built("trace", 330, 360, "asked", "mxtpu.setup.orders.learn"),
    built("compile", 340, 350, "jit(iota)", "mxtpu.setup.orders.learn"),
    built("lower", 360, 365, "jit(asked)", "mxtpu.setup.orders.learn"),
    built("compile", 365, 375, "jit(asked)", "mxtpu.setup.orders.learn"),
    # two traces that overlap count once (jax lets the inner one leave no
    # record; an overlap that got through is still one stretch of time)
    built("trace", 410, 440, "step", "mxtpu.step.launch"),
    built("trace", 420, 430, "inner", "mxtpu.step.launch"),
    built("compile", 440, 470, "jit(step)", "mxtpu.step.launch"),
    built("trace", 680, 700, "step", "mxtpu.step.launch"),
    built("lower", 700, 710, "jit(step)", "mxtpu.step.launch"),
    built("cache_load", 710, 790, "jit(step)", "mxtpu.step.launch", 0.04),
    # built in the window: not set-up's
    built("compile", 1010, 1020, "jit(late)"),
]


def test_every_nanosecond_goes_to_one_bucket_innermost_first():
    got = setup_phases.partition(SPANS, LOG, 0, 1000)
    assert got == {
        # 20 + 10, asked 30 - 10 (the compile inside it) + 5, 30 (the
        # overlapping pair once), 20 + 10
        "trace_lower": 30 + 25 + 30 + 30,
        "compile": 40 + 10 + 10 + 30,
        "cache_load": 10 + 80,
        # place 60 - 10 and 40; the check's orders 90 - (30 + 5 + 10) in
        # .learn; the timed step's orders 60 (its .relay inside it)
        "state": 50 + 40 + 45 + 60,
        # 600 .. 1,000 less the orders' 60 and the log's 110
        "warmup": 400 - 60 - 110,
        "other": 1000 - 115 - 90 - 90 - 195 - 230}
    assert sum(got.values()) == 1000
    assert all(isinstance(v, int) for v in got.values())
    assert setup_phases.built_as(LOG, "compile", 1000) == 4
    assert setup_phases.built_as(LOG, "cache_load", 1000) == 2
    assert setup_phases.warmup_start_ns(SPANS, 1000) == 600


@pytest.mark.parametrize("start, end", [
    (0, 1000), (35, 777), (0, 345), (415, 425), (900, 2000), (0, 5000)])
def test_the_partition_conserves_any_stretch(start, end):
    got = setup_phases.partition(SPANS, LOG, start, end)
    assert sum(got.values()) == end - start
    assert min(got.values()) >= 0


def test_without_a_second_step_warm_up_starts_at_the_only_one():
    assert setup_phases.warmup_start_ns(SPANS, 550) == 300
    got = setup_phases.partition(SPANS, LOG, 0, 550)
    # 300 .. 550 less the check's orders (90) and launch's log (60), and
    # the second place, which ends after 550, is not read
    assert got["warmup"] == 250 - 90 - 60
    assert setup_phases.warmup_start_ns(SPANS, 250) is None
    nothing = setup_phases.partition([], [], 0, 1000)
    assert nothing == dict.fromkeys(setup_phases.BUCKETS, 0) | {
        "other": 1000}


def test_the_table_names_the_programs_longest_first():
    second = 10 ** 9
    log = [built("compile", 3 * second, 45 * second, "jit(step)",
                 "mxtpu.step.launch"),
           built("cache_load", 50 * second, 52 * second, "jit(asked)",
                 "mxtpu.setup.orders.learn", 1.25),
           built("trace", 1 * second, 1 * second + 1000, "tiny"),
           built("trace", 2 * second, 2 * second + 3000, "tiny"),
           built("compile", 70 * second, 80 * second, "jit(late)")]
    lines = setup_phases.table(log, 0, 60 * second,
                               {"kept_spans": 0, "compile_log": 7})
    assert "3 records" not in lines[0] and "4 records" in lines[0]
    assert "'compile_log': 7" in lines[0]
    assert lines[2].split() == ["42.000", "compile", "3.00", "jit(step)",
                                "mxtpu.step.launch"]
    assert lines[3].split()[:5] == ["2.000", "cache_load", "50.00",
                                    "jit(asked)", "mxtpu.setup.orders.learn"]
    assert lines[3].endswith("(read in 1.250 s)")
    assert lines[4].split()[:2] == ["0.000", "trace"] \
        and "2 records under" in lines[4]
    assert len(lines) == 5 and "jit(late)" not in "\n".join(lines)


def obs_now():
    return {"values": {"setup_s": device.process_age_s()}}


@pytest.mark.parametrize("metric", sorted(SETUP_METRICS))
def test_on_a_program_without_the_lists_a_reader_finds_nothing(
        metric, monkeypatch, capsys):
    from mxnet_tpu import profiler

    monkeypatch.delattr(profiler, "kept_spans")
    reader = manifest.Manifest(REPO).cell(LANGUAGE_CELLS[0]).reader(metric)
    assert reader.read(obs_now()) is None
    assert capsys.readouterr().out == ""


def test_the_seven_read_one_run_once_and_sum_to_setup_s(capsys, request):
    """On this program: whatever the test process has built so far plus one
    step of its own, read as a run's set-up that ends now."""
    import numpy as np

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_step_spans import _batch, _step

    # the step below has to compile, not load: other tests of this
    # directory turn a persistent compile cache on for the process
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    request.addfinalizer(compilation_cache.reset_cache)
    request.addfinalizer(lambda: jax.config.update(
        "jax_enable_compilation_cache", was))
    step = _step("phases_", False, True)
    for _ in range(2):
        float(np.asarray(step(*_batch())))
    obs = obs_now()
    cell = manifest.Manifest(REPO).cell("resnet50_train_dp4")
    got = {name: cell.reader(name).read(obs) for name in SETUP_METRICS}
    out = capsys.readouterr().out
    assert out.count("compile log:") == out.count("set-up by phase:") == 1
    assert "jit(step)" in out and "mxtpu.step.launch" in out
    seconds = [v for name, v in got.items()
               if name != "setup.programs_compiled"]
    assert sum(seconds) == pytest.approx(obs["values"]["setup_s"], abs=1e-6)
    assert min(seconds) >= 0
    # the step of this test compiled (no compile cache here), traced and
    # lowered, was placed, and ran twice after its call 0 began
    assert got["setup.programs_compiled"] >= 2
    for name in ("setup.trace_lower_s", "setup.compile_s", "setup.state_s",
                 "setup.warmup_s", "setup.other_s"):
        assert got[name] > 0, name
    # another run's observations are read anew
    assert cell.reader("setup.warmup_s").read(obs_now()) \
        > got["setup.warmup_s"]
    assert capsys.readouterr().out.count("compile log:") == 1


@pytest.mark.parametrize("metric", sorted(SETUP_METRICS))
def test_the_setup_metrics_resolve_in_every_cell(metric):
    m = manifest.Manifest(REPO)
    entry = m.named("per_layer", metric)
    assert "workloads" not in entry
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (SETUP_METRICS[metric][1], "lower",
                                "program_counter", "setup", "setup_s")
    for workload in m.data["workloads"]:
        cell = m.cell(workload["name"])
        assert entry in cell.per_layer
        assert callable(cell.reader(metric).read)


def test_the_scalars_span_has_its_reader_in_the_language_cells(monkeypatch):
    m = manifest.Manifest(REPO)
    entry = m.named("per_layer", "entry.scalars_ms_per_step")
    twin = m.named("per_layer", "entry.key_ms_per_step")
    assert entry["workloads"] == LANGUAGE_CELLS
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} \
        == {k: twin[k] for k in twin if k not in ("name", "workloads")}
    for workload in m.data["workloads"]:
        cell = m.cell(workload["name"])
        assert (entry in cell.per_layer) == (cell.name in LANGUAGE_CELLS)
    # PR 25's reader, given the span's name
    from benchmark.harness import program_spans

    asked = []
    monkeypatch.setattr(program_spans, "host_span_ms_per_step",
                        lambda obs, name: asked.append(name) or 1.5)
    reader = m.cell(LANGUAGE_CELLS[0]).reader("entry.scalars_ms_per_step")
    assert reader.read({}) == 1.5 and asked == ["mxtpu.step.scalars"]


def test_the_manifest_gained_eight_entries_at_its_end():
    names = [e["name"] for e in manifest.Manifest(REPO).data["per_layer"]]
    assert names[-8:] == [
        "setup.trace_lower_s", "setup.compile_s", "setup.cache_load_s",
        "setup.programs_compiled", "setup.state_s", "setup.warmup_s",
        "setup.other_s", "entry.scalars_ms_per_step"]
