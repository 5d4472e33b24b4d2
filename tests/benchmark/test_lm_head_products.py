"""``lm_head.products_per_step`` (PR 38): the product instructions a step
under the scope ``lm_head`` as the benchmark reads them, on a hand-made
trace: products under the scope only, over the tail's steps, 0 where the
scope holds no product, None without a device trace."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, trace  # noqa: E402

METRIC = "lm_head.products_per_step"
CELLS = ("joyai_flash_train_seq4k", "lfm2_moe_train_seq8k",
         "mellum2_moe_train_seq16k")
STEP = "jit(step)/grad/"
LOOP = "/while/body/closed_call/"


def _obs(cell, ops_of_a_step, steps=1):
    """A traced tail of ``steps`` steps, each the instructions
    ``(op_name, operations)`` of ``ops_of_a_step``, 10 us each."""
    ops, names, at = [], {}, 0
    for _ in range(steps):
        for i, (op_name, flops) in enumerate(ops_of_a_step):
            name = "fusion.%d" % i
            ops.append(trace.Op(
                at, at + 10_000, "%%%s = f32[1024,2304]{1,0} fusion("
                "bf16[1024,2304]{1,0} %%a), kind=kOutput, calls=%%fc%d"
                % (name, i)))
            names[name] = (flops, op_name)
            at += 10_000
    recorded = trace.Trace({0: {"ops": ops, "async": [], "modules": []}},
                           [(trace.WINDOW_SPAN, 0, at)])
    return {"cell": cell, "trace": recorded, "chips": 1,
            "modules": [types.SimpleNamespace(instructions=names)],
            "tail": {"steps": steps, "counters": {}}}


def _head(kind, chunks=16):
    """A step's instructions under ``lm_head``: ``parent`` four products a
    chunk (forward, the logits again, two gradients), ``change`` three,
    each beside a product-less softmax fusion; the fast backward path's
    two scaling fusions."""
    fwd = STEP + "jvp(loss)/lm_head" + LOOP
    again = STEP + "jvp(loss)/lm_head/while/body/"
    bwd = STEP + "transpose(jvp(loss))/lm_head" + LOOP
    product = 2e9
    a_chunk = [(fwd + "dot_general", product), (fwd + "exp", 0)]
    if kind == "parent":
        a_chunk += [(again + "dot_general", product),
                    (bwd + "dot_general", product),
                    (bwd + "dot_general", product), (bwd + "mul", 0)]
    else:
        a_chunk += [(fwd + "dot_general", product)] * 2
    ops = a_chunk * chunks
    if kind == "change":
        ops += [(STEP + "transpose(jvp(loss))/lm_head/mul", 0)] * 2
    return ops


# the rest of a step: products and passes under other scopes, and a
# scope whose name only begins like the head's
OTHER = [(STEP + "jvp(model)/gqa.proj/dot_general", 5e9),
         (STEP + "transpose(jvp(model))/moe.experts/dot_general", 5e9),
         (STEP + "jvp(model)/lm_headless/dot_general", 5e9),
         (STEP + "jvp(model)/rms_norm/mul", 0), ("", 0)]


def test_the_metric_is_the_three_language_cells():
    m = manifest.Manifest(REPO)
    entry = m.named("per_layer", METRIC)
    assert entry == {"name": METRIC, "unit": "count", "better": "lower",
                     "source": "device_trace", "layer": "lm_head",
                     "moves": "train_samples_per_s",
                     "workloads": list(CELLS)}
    assert m.named("per_layer", "lm_head.ms_per_step")["layer"] == "lm_head"
    for name in CELLS:
        assert entry in m.cell(name).per_layer
    for cnn in ("resnet50_train_bs128", "inception3_train_bs128",
                "resnet50_train_dp4"):
        assert entry not in m.cell(cnn).per_layer


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("kind, a_step", [("parent", 64), ("change", 48)])
@pytest.mark.parametrize("steps", [1, 3])
def test_products_under_the_head_only_over_the_steps(name, kind, a_step,
                                                     steps):
    """16 chunks a step: 64 products on the parent, 48 on the change,
    whatever else the step holds and however many steps the tail has."""
    cell = manifest.Manifest(REPO).cell(name)
    obs = _obs(cell, OTHER + _head(kind) + OTHER, steps=steps)
    assert cell.reader(METRIC).read(obs) == pytest.approx(a_step)


@pytest.mark.parametrize("name", CELLS)
def test_a_head_without_products_reads_zero_and_no_trace_none(name):
    """A step with instructions under ``lm_head`` and no product among them
    reads 0; one without the scope, or without a device trace (the CPU),
    reads None, nothing raised."""
    cell = manifest.Manifest(REPO).cell(name)
    reader = cell.reader(METRIC)
    passes = _obs(cell, OTHER + [(STEP + "jvp(loss)/lm_head/exp", 0),
                                 (STEP + "transpose(jvp(loss))/lm_head/mul",
                                  0)], steps=2)
    assert reader.read(passes) == 0
    assert reader.read(_obs(cell, OTHER)) is None
    for empty in (dict(passes, trace=None),
                  dict(passes, trace=trace.Trace({}, []))):
        assert reader.read(empty) is None
