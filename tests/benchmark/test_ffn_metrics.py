"""``ffn.ms_per_step`` and ``kernels.ffn_roofline``: the gated-SiLU
feed-forward under the scope ``ffn.gated`` as the benchmark reads it, on a
hand-made trace: its time over the steps, its share of the roofline (a
product whose fusion forms an operand again on every pass reads lower),
in the manifest, and on a program that lacks the scope (the parent)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import manifest, trace  # noqa: E402

CELLS = ("joyai_flash_train_seq4k", "lfm2_moe_train_seq8k",
         "laguna_moe_train_seq4k")
METRICS = {"ffn.ms_per_step": ("ms", "lower", "ffn"),
           "kernels.ffn_roofline": ("%", "higher", "kernels")}
STEP = "jit(step)/grad/"
DENSE = STEP + "jvp(l0)/ffn/ffn.gated/"
DENSE_BWD = STEP + "transpose(jvp(l0))/ffn/ffn.gated/"
SHARED = STEP + "transpose(jvp(l1))/moe.shared/ffn.gated/"
# one product of the dense layer at LFM2's widths: 2 x 16,384 x 2,048 x
# 7,168 operations, 2.446 ms at the bfloat16 peak; operands and result
# small beside it in bytes
PRODUCT = 2 * 16384 * 2048 * 7168
OPERANDS = "bf16[16384,2048]{1,0} %a, bf16[7168,2048]{1,0} %b"
RESULT = "bf16[16384,7168]{1,0}"


def _obs(cell, ops_of_a_step, steps=1):
    """A traced tail of ``steps`` steps, each the instructions ``(op_name,
    operations, microseconds[, (operands, result)])`` of ``ops_of_a_step``
    (the dense layer's product's types where none are given)."""
    ops, names, at = [], {}, 0
    for _ in range(steps):
        for i, (op_name, flops, us, *shapes) in enumerate(ops_of_a_step):
            operands, result = shapes[0] if shapes else (OPERANDS, RESULT)
            name = "fusion.%d" % i
            ops.append(trace.Op(at, at + int(us * 1000), "%%%s = %s fusion("
                                "%s), kind=kOutput, calls=%%fc%d"
                                % (name, result, operands, i)))
            names[name] = (flops, op_name)
            at += int(us * 1000)
    recorded = trace.Trace({0: {"ops": ops, "async": [], "modules": []}},
                           [(trace.WINDOW_SPAN, 0, at)])
    return {"cell": cell, "trace": recorded, "chips": 1,
            "modules": [types.SimpleNamespace(instructions=names)],
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "tail": {"steps": steps, "counters": {}}}


# the rest of a step: products under other scopes, one of them a shared
# expert's product outside the feed-forward's scope, and a scope whose name
# only begins like it
OTHER = [(STEP + "jvp(l1)/moe.experts/dot_general", PRODUCT, 5000.0),
         (STEP + "transpose(jvp(l1))/moe.shared/mul", 0, 300.0),
         (STEP + "jvp(l2)/ffn.gatedness/dot_general", PRODUCT, 5000.0),
         ("", 0, 80.0)]
AT_PEAK_US = PRODUCT / 197e12 * 1e6
# an instruction's least HBM bytes (operands and result once) over 819 GB/s
BYTES_US = 2 * (16384 * 2048 + 7168 * 2048 + 16384 * 7168) / 819e3
SHARED_TYPES = ("bf16[16384,2048]{1,0} %a, bf16[448,2048]{1,0} %b",
                "bf16[16384,448]{1,0}")
SHARED_BYTES_US = 2 * (16384 * 2048 + 448 * 2048 + 16384 * 448) / 819e3


def _layer(share):
    """The layer's nine products a step (three forward, six backward: a
    forward product at 95 % of peak, a backward one at ``share``), an
    elementwise pass at 90 % of the bandwidth, and a shared expert's
    product (448 wide) at 95 %."""
    forward = [(DENSE + "dot_general", PRODUCT, AT_PEAK_US / 0.95)] * 3
    backward = [(DENSE_BWD + "dot_general", PRODUCT, AT_PEAK_US / share)] * 6
    return forward + backward + [
        (DENSE_BWD + "mul", 0, BYTES_US / 0.9),
        (SHARED + "dot_general", PRODUCT // 16, AT_PEAK_US / 16 / 0.95,
         SHARED_TYPES)]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metrics_are_the_three_language_cells(metric):
    m = manifest.Manifest(REPO)
    entry = m.named("per_layer", metric)
    unit, better, layer = METRICS[metric]
    assert entry == {"name": metric, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "train_samples_per_s", "workloads": list(CELLS)}
    for name in CELLS:
        assert entry in m.cell(name).per_layer
    for other in ("resnet50_train_bs128", "inception3_train_bs128",
                  "resnet50_train_dp4", "mellum2_moe_train_seq16k"):
        assert entry not in m.cell(other).per_layer
    assert m.named("per_layer", "kernels.xla_ops_roofline")["layer"] \
        == "kernels"


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("steps", [1, 3])
def test_the_time_under_the_scope_over_the_steps(name, steps):
    """Dense and shared, forward and backward, and nothing of the scopes
    beside it: the layer's instructions' microseconds over the steps."""
    cell = manifest.Manifest(REPO).cell(name)
    layer = _layer(0.55)
    obs = _obs(cell, OTHER + layer, steps=steps)
    want = sum(us for _, _, us, *_ in layer) / 1e3
    assert cell.reader("ffn.ms_per_step").read(obs) == pytest.approx(want)


@pytest.mark.parametrize("name", CELLS)
def test_a_product_that_forms_its_operand_per_pass_reads_lower(name):
    """The same operations in more time: backward products at 55 % of
    peak (an operand formed again on every pass over the result) read
    lower than at 90 %; the share is the bounds' sum over the times' sum,
    an instruction's bound the larger of operations over the peak and
    its least bytes over the bandwidth, whole."""
    cell = manifest.Manifest(REPO).cell(name)
    reader = cell.reader("kernels.ffn_roofline")
    shares = {}
    for share in (0.55, 0.9, 1.0):
        layer = [(op_name, flops, int(us * 1000) / 1000, *shapes)
                 for op_name, flops, us, *shapes in _layer(share)]
        bound = sum(max(flops / 197e6, SHARED_BYTES_US if shapes else BYTES_US)
                    for _, flops, _, *shapes in layer)
        want = 100.0 * bound / sum(us for _, _, us, *_ in layer)
        shares[share] = reader.read(_obs(cell, OTHER + layer, steps=2))
        assert shares[share] == pytest.approx(want)
    assert shares[0.55] < 70 < shares[0.9] < shares[1.0] < 100.0


@pytest.mark.parametrize("name", CELLS)
def test_bytes_that_the_time_could_not_move_read_above_100(name):
    """An instruction whose least bytes would take the peak bandwidth longer
    than its measured time (a byte count too high, or a time that leaves out
    part of the work) is not cut to its time: the share reads above 100 %,
    where the benchmark refuses it, and does not hide the fault."""
    cell = manifest.Manifest(REPO).cell(name)
    fast = [(DENSE_BWD + "mul", 0, BYTES_US / 4)]
    share = cell.reader("kernels.ffn_roofline").read(
        _obs(cell, OTHER + fast))
    assert share == pytest.approx(400.0, rel=1e-3)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_program_without_the_scope_reads_nothing(name, metric):
    """The parent's step has the layer under its block's scope ``ffn`` and
    no ``ffn.gated``; a CPU run has no device trace: None, nothing
    raised."""
    cell = manifest.Manifest(REPO).cell(name)
    reader = cell.reader(metric)
    parent = _obs(cell, OTHER + [(STEP + "jvp(l0)/ffn/dot_general", PRODUCT,
                                  3000.0)])
    assert reader.read(parent) is None
    for empty in (dict(parent, trace=None),
                  dict(parent, trace=trace.Trace({}, [])),
                  dict(parent, tail=None)):
        assert reader.read(empty) is None
