"""The reduction from a profiler trace to device numbers, checked on small
recorded traces cut from real chip traces of PR 22 (``fixtures/*.xplane.pb.gz``,
cut by ``fixtures/cut_xplane.py``) and on hand-made intervals."""

import gzip
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import device, hlo_cost, trace  # noqa: E402

SPANS = {"dispatch", "loss_fetch", trace.WINDOW_SPAN}


def recorded(name, tmp_path):
    path = str(tmp_path / name)
    with gzip.open(os.path.join(HERE, "fixtures", name + ".gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.Trace.from_xplane(path, SPANS)


# ---------------------------------------------------------------- intervals


def test_merge_clip_subtract_and_gaps():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (7, 7), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert trace.total(merged) == 7
    assert trace.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 9)]) == \
        [(0, 2), (3, 5), (9, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert trace.gaps(merged, 0, 10) == [(3, 5), (9, 10)]


def _trace(ops, spans, async_ops=()):
    dev = {"ops": [trace.Op(s, e, t) for s, e, t in ops],
           "async": [trace.Op(s, e, t) for s, e, t in async_ops],
           "modules": []}
    return trace.Trace({0: dev}, spans)


FUSION = "%fusion.1 = f32[256]{0} fusion(f32[256]{0} %p), kind=kLoop, " \
         "calls=%fc"
ALL_REDUCE_DONE = "%all-reduce-done.1 = f32[256]{0} all-reduce-done(" \
                  "f32[256]{0} %all-reduce-start.1)"
ALL_REDUCE_SPAN = "%all-reduce-start.1 = f32[256]{0} all-reduce-start(" \
                  "f32[256]{0} %g), replica_groups={{0,1,2,3}}"


def test_busy_idle_and_gap_names_on_hand_made_intervals():
    t = _trace(
        ops=[(10, 40, FUSION), (30, 50, FUSION), (75, 92, FUSION)],
        spans=[(trace.WINDOW_SPAN, 0, 100), ("dispatch", 0, 12),
               ("loss_fetch", 52, 69), ("dispatch", 70, 100)])
    busy_s, window_s = trace.busy_and_window_s(t)
    assert busy_s == pytest.approx(57e-9) and window_s == \
        pytest.approx(100e-9)
    # gaps: 0-10 and 92-100 under dispatch; 50-75, of which loss_fetch
    # covers 17 and the next dispatch 5: the whole gap goes to loss_fetch
    assert trace.idle_gaps(t) == [["loss_fetch", pytest.approx(25e-9)],
                                  ["dispatch", pytest.approx(18e-9)]]


def test_exposed_collective_time_is_what_no_compute_covers():
    t = _trace(
        ops=[(0, 50, FUSION), (50, 60, ALL_REDUCE_DONE), (60, 100, FUSION)],
        async_ops=[(20, 60, ALL_REDUCE_SPAN)],
        spans=[(trace.WINDOW_SPAN, 0, 100)])
    in_flight, exposed = trace.collectives(t)
    assert in_flight == pytest.approx(40e-9)     # 20-60
    assert exposed == pytest.approx(10e-9)       # 50-60: only the wait ran
    assert trace.collectives(_trace([(0, 5, FUSION)], [])) is None


# ------------------------------------------------------- operations, bytes


CONV_MODULE = """HloModule jit_step, is_scheduled=true

%fused_computation.7 (p0: bf16[128,56,56,64], p1: bf16[64,3,3,64]) -> bf16[128,56,56,64] {
  %p0 = bf16[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[64,3,3,64]{3,0,2,1:T(8,128)(2,1)} parameter(1)
  ROOT %conv.1 = bf16[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} convolution(%p0, %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_o01i->b01f, metadata={op_name="jit(step)/jvp(net)/stage1/conv2d1/conv_general_dilated"}
}

ENTRY %main (a: bf16[128,56,56,64], w: bf16[64,3,3,64]) -> bf16[128,56,56,64] {
  %a = bf16[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %w = bf16[64,3,3,64]{3,0,2,1:T(8,128)(2,1)S(1)} parameter(1)
  ROOT %fusion.7 = bf16[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} fusion(%a, %w), kind=kOutput, calls=%fused_computation.7
}
"""
FUSION_7 = "%fusion.7 = bf16[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} fusion(" \
           "bf16[128,56,56,64]{3,0,2,1:T(8,128)(2,1)} %a, " \
           "bf16[64,3,3,64]{3,0,2,1:T(8,128)(2,1)S(1)} %w), kind=kOutput, " \
           "calls=%fused_computation.7"


def test_operations_and_bytes_of_a_convolution_fusion():
    module = hlo_cost.Module(CONV_MODULE)
    flops, op_name = module.instructions["fusion.7"]
    # 3x3 taps that land on padding are not counted: per spatial dimension
    # 56*3 - 2 valid (output, tap) pairs
    assert flops == 2 * 128 * 64 * 64 * (56 * 3 - 2) ** 2
    assert op_name.endswith("stage1/conv2d1/conv_general_dilated")
    # the weight sits in VMEM (S(1)): its prefetch paid for it
    assert hlo_cost.min_hbm_bytes(FUSION_7) == 2 * 2 * 128 * 56 * 56 * 64
    name, opcode, _ = hlo_cost.split_instruction(FUSION_7)
    assert (name, opcode) == ("fusion.7", "fusion")


def test_a_convolution_costs_the_same_however_it_is_phrased():
    """XLA phrases some 1x1 convolutions as a 56x56 window over padding,
    and a strided convolution's input gradient as a dilated one.  Padding
    and holes are not operations."""
    plain = hlo_cost.convolution_flops(
        [128, 56, 56, 64], [128, 56, 56, 64], [64, 1, 1, 64],
        "window={size=1x1}, dim_labels=b01f_o01i->b01f")
    # as compiled for ResNet-50's stage1 conv2d0 (PR 22): the weight is the
    # "input", the activation the 56x56 "kernel", nearly all of it padding
    padded = hlo_cost.convolution_flops(
        [128, 56, 56, 64], [64, 1, 1, 64], [128, 56, 56, 64],
        "window={size=56x56 pad=55_55x55_55 rhs_reversal=1x1}, "
        "dim_labels=b01f_o01i->f01b")
    assert plain == padded == 2 * 128 * 56 * 56 * 64 * 64
    forward = hlo_cost.convolution_flops(
        [8, 28, 28, 32], [8, 56, 56, 16], [32, 1, 1, 16],
        "window={size=1x1 stride=2x2}, dim_labels=b01f_o01i->b01f")
    input_gradient = hlo_cost.convolution_flops(
        [8, 56, 56, 16], [8, 28, 28, 32], [16, 1, 1, 32],
        "window={size=1x1 pad=0_1x0_1 lhs_dilate=2x2}, "
        "dim_labels=b01f_o01i->b01f")
    assert forward == input_gradient == 2 * 8 * 28 * 28 * 32 * 16


def test_roofline_arithmetic_and_stable_names():
    peaks = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    module = hlo_cost.Module(CONV_MODULE)
    flops = module.instructions["fusion.7"][0]
    nbytes = hlo_cost.min_hbm_bytes(FUSION_7)
    t_flops, t_bytes = flops / 100e12, nbytes / 1e12
    assert t_flops > t_bytes            # this one is compute-bound
    measured = 2 * t_flops
    wait = "%copy-done.3 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, " \
           "f32[8]{0}, u32[]{:S(2)}) %copy-start.3)"
    t = _trace(ops=[(0, measured * 1e9, FUSION_7),
                    (measured * 1e9, measured * 1.25e9, wait)],
               spans=[])
    found = trace.roofline(t, [module], peaks)
    # the wait counts in the time and has no bound
    assert found["time_s"] == pytest.approx(measured * 1.25)
    assert found["share"] == pytest.approx(t_flops / (measured * 1.25))
    assert found["flops_bound_s"] == pytest.approx(t_flops)
    assert found["bytes_bound_s"] == 0
    names = trace.device_ops(t, [module])
    assert names[0][0] == "fusion jvp(net)/stage1/conv2d1/" \
                          "conv_general_dilated"
    assert names[0][1] == pytest.approx(measured)
    # without the module's text the name falls back to opcode and type
    assert trace.device_ops(t, [])[0][0] == "fusion bf16[128,56,56,64]"


def test_peaks_table_names_its_source_and_has_no_default():
    peaks = device.load_peaks(REPO)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com/tpu/docs/v5e" in peaks["TPU v5 lite"]["source"]
    assert "cpu" not in peaks


# ----------------------------------------------------------- recorded trace


def test_recorded_one_chip_trace(tmp_path):
    """Two steps of resnet50_train_bs128 on the v5e (PR 22), the last of one
    group and the first of the next, so the cut holds one loss fetch."""
    t = recorded("resnet50_train_bs128_2steps.xplane.pb", tmp_path)
    assert sorted(t.devices) == [0]
    steps = [m for m in t.devices[0]["modules"]
             if m[2].startswith("jit_step(")]
    assert [round((end - start) / 1e6, 1) for start, end, _ in steps] == \
        [48.0, 48.0]
    busy_s, window_s = trace.busy_and_window_s(t)
    assert window_s == pytest.approx(0.100222512)
    assert busy_s == pytest.approx(0.095941537)
    # the chip waits 3.0 ms while the host fetches the loss and 1.2 ms for
    # the next group's first dispatch; nothing is left unnamed
    assert trace.idle_gaps(t) == [
        ["loss_fetch", pytest.approx(0.003033423)],
        ["dispatch", pytest.approx(0.001247552)]]
    assert sum(s for _, s in trace.idle_gaps(t)) == \
        pytest.approx(window_s - busy_s)
    assert trace.collectives(t) is None          # one chip: none
    peaks = device.load_peaks(REPO)["TPU v5 lite"]
    # without the module's text no instruction has operations: the bound is
    # bytes alone, 56.3 ms of the 94.0 ms the instructions took
    found = trace.roofline(t, [], peaks)
    assert found["flops_bound_s"] == 0
    assert found["bytes_bound_s"] == pytest.approx(0.0562667592)
    assert found["share"] == pytest.approx(0.59873349)
    top = trace.device_ops(t, [])
    assert len(top) == 10 and top[0][0] == "fusion bf16[128,56,56,256]"
    assert top[0][1] == pytest.approx(0.006418416)
    opcodes = {op.opcode for op in t.devices[0]["ops"]}
    assert {"fusion", "copy-start", "copy-done", "async-start",
            "async-done", "select-and-scatter"} <= opcodes


def test_recorded_four_chip_trace(tmp_path):
    """One step of resnet50_train_dp4 on four v5e chips (PR 22).  GSPMD's
    all-reduces over ``dp`` are synchronous instructions on this toolchain:
    101 a step (batch-norm statistics forward and backward, and the
    gradients), and while one runs nothing else does on that chip, so all
    of their time is exposed."""
    t = recorded("resnet50_train_dp4_1step.xplane.pb", tmp_path)
    assert sorted(t.devices) == [0, 1, 2, 3]
    for dev in t.devices.values():
        reduces = [op for op in dev["ops"] if op.opcode == "all-reduce"]
        assert len(reduces) == 101
        assert hlo_cost.is_collective(reduces[0].opcode)
    busy_s, window_s = trace.busy_and_window_s(t)
    assert window_s == pytest.approx(0.048866048)
    assert busy_s == pytest.approx(0.04853957525)   # mean of the four
    per_chip = [trace.total(iv) / 1e9 for iv in trace.busy(t).values()]
    assert max(per_chip) - min(per_chip) < 2e-4
    in_flight, exposed = trace.collectives(t)
    assert in_flight == pytest.approx(0.0012584335)
    assert exposed == pytest.approx(in_flight)
