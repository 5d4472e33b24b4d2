"""The ``laguna_s_2_1_ep32`` cell's benchmark files on the CPU: the six
per-layer readers on a hand-made trace (the attention kernels told apart by
the query heads of their layer type, read from
``num_attention_heads_per_layer``), the flops function against the
reference's own layer walk and the issue's hand count, and one whole run of
the cell at a tiny size; the spread tool's record of a window."""

import copy
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import (device, gqa_attention_cost,  # noqa: E402
                               manifest, swa_attention_cost, trace)

CELL = "laguna_moe_train_seq4k"
METRICS = ["attention.laguna_ms_per_step", "attention.head_gate_ms_per_step",
           "kernels.laguna_swa_attention_roofline",
           "kernels.laguna_full_attention_roofline",
           "moe.laguna_routed_ms_per_step", "moe.laguna_max_expert_load_ratio"]

# the real file's layer pattern, at a size for the CPU: window layers of 9
# query heads on one key head and full ones of 6 (the cell's groups), a
# window of 8 on rows of 64, half of a full layer's 16 lanes rotated by yarn
# over 8 lanes, half the positions past yarn's original 32
TINY = dict(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, shared_expert_intermediate_size=24,
    router_outputs=16, held_experts=[4, 4], num_experts_per_tok=3,
    num_attention_heads=6, num_attention_heads_per_layer=[6, 9, 9, 9, 6],
    num_key_value_heads=1, head_dim=16, sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": 32, "beta_fast": 2,
            "beta_slow": 0.25, "attention_factor": 0.1 * np.log(4.0) + 1.0,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                              "partial_rotary_factor": 1}})


def tiny(config):
    """The cell's configuration at a size for the CPU: every key of the
    real file, the sizes replaced, the layers and the compared names as
    they are."""
    config = copy.deepcopy(config)
    config["architecture"].update(copy.deepcopy(TINY))
    config["factory_kwargs"].update(weight_std=0.3)
    config["input"]["shape"] = [64]
    config.update(check_seq_len=64, check_batch=2, check_candidates=24)
    config["training"]["lr"] = 1e-3
    return config


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


def test_the_cell_reports_its_six_metrics_and_no_other_cells(cell):
    m = cell.manifest
    reported = {metric["name"] for metric in cell.per_layer}
    for name in METRICS:
        entry = m.named("per_layer", name)
        assert entry["workloads"] == [CELL], name
        assert entry["moves"] == "train_samples_per_s"
        assert name in reported
    assert {"step.device_ms", "step.mfu", "device.idle_share",
            "setup.compile_s"} <= reported
    assert not reported & {"kernels.swa_attention_roofline",
                           "moe.lfm2_max_expert_load_ratio",
                           "attention.swa_gqa_ms_per_step"}
    assert cell.chips == 1 and cell.traffic["global_batch"] == 1
    assert cell.traffic["entry"] == "gluon_gated_window_lm_train_step"
    assert 1 <= cell.traffic["warmup_groups"] <= 10
    assert len(m.named("workloads", CELL)["why"]) <= 200


def _call(name, operands, results):
    return "%%%s = %s custom-call(%s), custom_call_target=" \
        "\"tpu_custom_call\"" % (name, results, ", ".join(
            "%s %%a%d" % (t, i) for i, t in enumerate(operands)))


def _kernels(heads):
    q, k, rows = ("bf16[%d,4096,128]" % heads, "bf16[8,4096,128]",
                  "f32[%d,1,4096]" % heads)
    return {"forward": ([q, k, k], "(%s, %s)" % (q, rows)),
            "dq": ([q, k, k, q, rows, rows], q),
            "dkv": ([q, k, k, q, rows, rows], "(%s, %s)" % (k, k))}


@pytest.fixture()
def observed(cell):
    """One step of 1,000 us: a window layer's three kernels (72 query
    heads, 10 us each) under ``swa.attention``, a full layer's (48 heads,
    40 each) under ``gqa.attention`` and, beside them, a kernel of the
    other head count under each scope (it must not be priced), the
    projections, the gate, the routed path's four scopes, the shared
    expert, the head."""
    ops, names, at = [], {}, 0

    def add(name, text, us, scope):
        nonlocal at
        ops.append(trace.Op(at, at + us * 1000, text))
        names[name] = (0, "jit(step)/jit(main)/transpose(jvp(l1))/%s/op"
                       % scope)
        at += us * 1000

    for scope, heads, us in (("swa.attention", 72, 10),
                             ("gqa.attention", 48, 40)):
        for kind, (operands, results) in _kernels(heads).items():
            name = "%s.%s" % (kind, scope[:3])
            add(name, _call(name, operands, results), us, scope)
    stray = _kernels(48)["forward"]
    add("stray.swa", _call("stray.swa", *stray), 7, "swa.attention")
    for i, (scope, us) in enumerate([
            ("gqa.proj", 70), ("gqa.gate", 3), ("moe.route", 9),
            ("moe.dispatch", 6), ("moe.experts", 50), ("moe.combine", 11),
            ("moe.shared", 8), ("lm_head", 40)]):
        name = "fusion.%d" % i
        add(name, "%%%s = f32[256]{0} fusion(f32[256]{0} %%a), kind=kLoop, "
            "calls=%%fc%d" % (name, i), us, scope)
    recorded = trace.Trace({0: {"ops": ops, "async": [], "modules": []}},
                           [(trace.WINDOW_SPAN, 0, 1_000_000)])
    return {"cell": cell, "trace": recorded, "chips": 1,
            "modules": [types.SimpleNamespace(instructions=names)],
            "peaks": device.load_peaks(REPO)["TPU v5 lite"],
            "tail": {"steps": 1, "counters": {
                "l1_moe_max_load": 1.02, "l4_moe_max_load": 1.31,
                "l4_moe_held_pairs": 1280.0, "l1_moe_router_bias": -0.01}}}


def test_the_readers_find_their_scopes_on_a_hand_made_trace(cell, observed):
    def read(metric):
        return cell.reader(metric).read(observed)

    # the projections, the gate, both layer types' kernels and the stray one
    assert read("attention.laguna_ms_per_step") == pytest.approx(
        (70 + 3 + 30 + 120 + 7) / 1e3)
    assert read("attention.head_gate_ms_per_step") == pytest.approx(3 / 1e3)
    assert read("moe.laguna_routed_ms_per_step") == pytest.approx(76 / 1e3)
    assert read("moe.laguna_max_expert_load_ratio") == 1.31
    arch = cell.config["architecture"]
    peaks = observed["peaks"]
    for metric, shapes, cost, us in (
            ("kernels.laguna_swa_attention_roofline",
             dict(swa_attention_cost.work(arch, 1, 4096, 512), heads=72),
             swa_attention_cost.kernel_cost, 30),
            ("kernels.laguna_full_attention_roofline",
             dict(swa_attention_cost.work(arch, 1, 4096), heads=48),
             gqa_attention_cost.kernel_cost, 120)):
        bound = sum(max(flops / peaks["bf16_flops_per_s"],
                        least / peaks["hbm_bytes_per_s"])
                    for flops, least in (cost(kind, 2, shapes)
                                         for kind in _kernels(72)))
        assert read(metric) == pytest.approx(100 * bound / (us * 1e-6))


def test_the_rooflines_take_each_layer_types_own_head_count(cell, observed):
    """Priced with ``num_attention_heads`` (48) the window layers' kernels
    would not be found; with 72 everywhere the full layers' would not."""
    arch = cell.config["architecture"]
    assert arch["num_attention_heads"] == 48
    assert arch["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    kernels = [op.text for op in observed["trace"].devices[0]["ops"]
               if "custom-call" in op.text]
    for heads, window, found in ((72, 512, 3), (48, None, 4)):
        shapes = dict(swa_attention_cost.work(arch, 1, 4096, window),
                      heads=heads)
        kinds = [swa_attention_cost.kernel_kind(k, shapes) for k in kernels]
        assert sum(kind is not None for kind in kinds) == found
    # the window layers' kernels are asked for 72 heads x the band's pairs
    shapes = dict(swa_attention_cost.work(arch, 1, 4096, 512), heads=72)
    pairs = 4096 * 512 - 512 * 511 / 2
    assert swa_attention_cost.kernel_cost("forward", 2, shapes)[0] \
        == 72 * pairs * 4 * 128


def test_without_a_device_trace_the_readers_find_nothing(cell, observed):
    """The parent's program, or a CPU: nothing to read, nothing raised."""
    for empty in (dict(observed, trace=None),
                  dict(observed, trace=trace.Trace({}, []))):
        for metric in METRICS[:-1]:
            assert cell.reader(metric).read(empty) is None
    assert cell.reader(METRICS[-1]).read(dict(observed, tail=None)) is None
    # a program without the gate's scope: its reader finds nothing
    names = observed["modules"][0].instructions
    for name, (cost, op_name) in list(names.items()):
        names[name] = (cost, op_name.replace("gqa.gate", "gqa.proj"))
    assert cell.reader("attention.head_gate_ms_per_step").read(
        observed) is None


def _dot_macs(jaxpr):
    """Multiply-adds of every ``dot_general`` of a jaxpr, nested ones too."""
    macs = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            contract = eqn.params["dimension_numbers"][0][0]
            lhs = eqn.invars[0].aval.shape
            macs += int(np.prod(eqn.outvars[0].aval.shape)) * int(
                np.prod([lhs[i] for i in contract]))
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", None)
            if inner is not None:
                macs += _dot_macs(getattr(inner, "jaxpr", inner))
    return macs


def test_the_flops_function_is_the_references_own_layer_walk(cell):
    """The products the reference's forward makes at a tiny size, counted
    from its jaxpr, are what the formula gives for the same walk (every
    held expert on every token, attention's whole square); the cell's
    number is the issue's hand count: 561 M multiply-adds a token, of which
    the gated attention layers ~357 and the three 72-head window layers
    ~218."""
    import jax

    from mxnet_tpu.gluon.nn import LayerTypesMoELM

    reference = cell.reference()
    arch = tiny(cell.config)["architecture"]
    net = LayerTypesMoELM(**arch)
    cut = len(net.prefix)
    shapes = {n[cut:]: jax.ShapeDtypeStruct(p.shape, np.float32)
              for n, p in net.collect_params().items()}
    rows, seq = 2, 16
    jaxpr = jax.make_jaxpr(lambda p, x: reference.forward(p, x, arch))(
        shapes, jax.ShapeDtypeStruct((rows, seq), np.float32))
    walked = 2.0 * _dot_macs(jaxpr.jaxpr)
    formula = reference.forward_flops_per_token(
        arch, seq, pairs=arch["held_experts"][1], whole_square=True)
    assert walked == pytest.approx(formula * rows * seq, rel=1e-9)

    real, seq = cell.config["architecture"], 4096
    kv = 2 * 3072 * 1024
    full = 2 * 3072 * 48 * 128 + kv + 3072 * 48 + 48 * 256 * (seq + 1) / 2
    window = 2 * 3072 * 72 * 128 + kv + 3072 * 72 \
        + 72 * 256 * (512 - 512 * 511 / 2 / seq)
    routed = 3072 * 256 + 3 * 3072 * 1024 * (10 * 8 / 256) \
        + 3 * 3072 * 1024
    macs = 2 * full + 3 * window + 3 * 3072 * 12288 + 4 * routed \
        + 3072 * 12544
    per_token = reference.forward_flops_per_token(real, seq)
    assert per_token == pytest.approx(2.0 * macs, rel=1e-12)
    assert macs == pytest.approx(559.2e6, rel=1e-3)
    assert (2 * full + 3 * window) / macs == pytest.approx(0.634, abs=0.005)
    assert 3 * window == pytest.approx(216.0e6, rel=1e-3)
    assert reference.flops_per_sample(real, cell.config["input"]["shape"]) \
        == pytest.approx(3 * seq * per_token)


# --------------------------------------------------------- one whole run


def cpu_gate(chips, root):
    import jax

    return jax.devices()[:chips], device.load_peaks(root)["TPU v5 lite"]


def tiny_checkout(cell, root):
    """The cell's benchmark files under ``root``, the configuration at
    ``tiny`` size and one warm-up group."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    entry = cell.manifest.named("configs", cell.config_name)
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump(tiny(cell.config), f)
    with open(cell.manifest.find("traffic", cell.traffic_name, ".json")
              .replace(REPO, root), "w") as f:
        json.dump(dict(cell.traffic, warmup_groups=1), f)


def test_one_whole_run_of_the_cell_at_a_tiny_size(cell, tmp_path, capsys):
    """``run.main`` through the cell's own files, the configuration's sizes
    replaced and one warm-up group: a result line, correct, with the
    program counter's metric; the device-trace readers find no device plane
    on a CPU and leave their metrics out."""
    root = str(tmp_path)
    tiny_checkout(cell, root)
    assert run.main(["--workload", CELL, "--seed", "3900000321",
                     "--seconds", "0.5", "--trace", "1"],
                    gate=cpu_gate, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, lines[-40:]
    metrics = result["metrics"]
    assert metrics["entry.compiles_in_window"]["value"] == 0
    assert metrics["moe.laguna_max_expert_load_ratio"]["value"] >= 1.0
    assert 0 < metrics["step.mfu"]["value"] < 100
    for name in METRICS[:-1]:
        assert name not in metrics
    assert sum("warm-up group" in line for line in lines) == 1
    assert sum(line.endswith("ok") and ("swa_timed." in line
                                        or "dense_prefix." in line)
               for line in lines) == 11
    assert sum("check after_step." in line and line.endswith("ok")
               for line in lines) == 4
    assert any("M parameters" in line for line in lines)


# ------------------------------------------------- the spread tool's record


def test_the_spread_tool_records_every_dispatch_and_fetch(cell, tmp_path,
                                                          capsys):
    """``tools/cell_spread.py`` on the cell at a tiny size: a seed's line
    holds every dispatch's and every fetch's time of the window, which add
    up to its groups' times, and the window's garbage collections."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import cell_spread

    root = str(tmp_path)
    tiny_checkout(cell, root)
    assert cell_spread.main([CELL, "3900000331", "--seconds", "0.3"],
                            gate=cpu_gate, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    (seed,) = [json.loads(line[5:]) for line in lines
               if line.startswith("SEED ")]
    assert seed["ok"] and len(seed["group_s"]) * 10 == seed["steps"]
    assert len(seed["dispatch_s"]) == seed["steps"]
    assert len(seed["fetch_s"]) == len(seed["group_s"])
    assert seed["group_s"] == pytest.approx(
        [sum(seed["dispatch_s"][i * 10:(i + 1) * 10]) + f
         for i, f in enumerate(seed["fetch_s"])])
    assert all(len(c) == 3 and c[2] >= 0 for c in seed["collections"])


def test_the_spread_tool_records_the_collections_inside_its_block():
    """``cell_spread.Collections``: a full collection inside the block is
    recorded with its generation, start and length; none after it."""
    import gc

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import cell_spread

    with cell_spread.Collections() as seen:
        gc.collect()
    gc.collect()
    full = [c for c in seen.seen if c[0] == 2]
    assert len(full) == 1 and full[0][1] >= 0 and full[0][2] >= 0
    assert cell_spread.Collections not in map(type, gc.callbacks)
