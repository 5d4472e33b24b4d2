"""``moe.wgrad_ms_per_step`` (PR 35): the routed sum's weight-gradient
products as the benchmark reads them, the scope ``moe.wgrad`` inside
``moe.experts``: on a hand-made trace, in the manifest, and on a program
that lacks the scope (the parent)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, trace  # noqa: E402

METRIC = "moe.wgrad_ms_per_step"
CELLS = {"joyai_flash_train_seq4k": "moe.routed_ms_per_step",
         "lfm2_moe_train_seq8k": "moe.lfm2_routed_ms_per_step",
         "mellum2_moe_train_seq16k": "moe.mellum_routed_ms_per_step"}
LOOP = "jit(step)/grad/transpose(jvp(l1))/moe/jit(<unknown>)/while/body/"


def _obs(cell, scopes_us, steps=1):
    """One traced tail: an instruction of ``us`` microseconds under each
    ``op_name`` path."""
    ops, names, at = [], {}, 0
    for i, (path, us) in enumerate(scopes_us):
        name = "fusion.%d" % i
        ops.append(trace.Op(
            at, at + us * 1000, "%%%s = f32[8,2048,1792]{2,1,0} fusion("
            "f32[8,2048,1792]{2,1,0} %%a), kind=kOutput, calls=%%fc%d"
            % (name, i)))
        names[name] = (0, LOOP + path + "/dot_general")
        at += us * 1000
    recorded = trace.Trace({0: {"ops": ops, "async": [], "modules": []}},
                           [(trace.WINDOW_SPAN, 0, 1_000_000)])
    return {"cell": cell, "trace": recorded, "chips": 1,
            "modules": [types.SimpleNamespace(instructions=names)],
            "tail": {"steps": steps, "counters": {}}}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_metric_is_the_three_language_cells(name):
    m = manifest.Manifest(REPO)
    entry = m.named("per_layer", METRIC)
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "moe",
                     "moves": "train_samples_per_s",
                     "workloads": sorted(CELLS)}
    assert entry in m.cell(name).per_layer
    assert m.named("per_layer", CELLS[name])["layer"] == "moe"
    for cnn in ("resnet50_train_bs128", "inception3_train_bs128",
                "resnet50_train_dp4"):
        assert entry not in m.cell(cnn).per_layer


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_weight_gradients_are_read_inside_the_experts(name):
    """Three big steps' writes of 80 us, two single tiles' of 45, among
    the experts' other products: ``moe.wgrad`` reads its own, and the
    routed path's metric still reads all of ``moe.experts``."""
    cell = manifest.Manifest(REPO).cell(name)
    obs = _obs(cell, [("moe.experts", 300), ("moe.experts/moe.wgrad", 80),
                      ("moe.experts/moe.wgrad", 80),
                      ("moe.experts/moe.wgrad", 80),
                      ("moe.experts/moe.wgrad", 45),
                      ("moe.experts/moe.wgrad", 45), ("moe.combine", 20),
                      ("moe.dispatch", 7), ("lm_head", 40)], steps=2)
    assert cell.reader(METRIC).read(obs) == pytest.approx(330 / 2 / 1e3)
    assert cell.reader(CELLS[name]).read(obs) == pytest.approx(
        (300 + 330 + 20 + 7) / 2 / 1e3)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_program_without_the_scope_reads_nothing(name):
    """The parent's step has ``moe.experts`` and no ``moe.wgrad``; a CPU
    run has no device trace: None, nothing raised."""
    cell = manifest.Manifest(REPO).cell(name)
    parent = _obs(cell, [("moe.experts", 300), ("moe.combine", 20)])
    assert cell.reader(METRIC).read(parent) is None
    assert cell.reader(CELLS[name]).read(parent) == pytest.approx(320 / 1e3)
    for empty in (dict(parent, trace=None),
                  dict(parent, trace=trace.Trace({}, []))):
        assert cell.reader(METRIC).read(empty) is None
