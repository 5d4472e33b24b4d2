"""The ``xing4_0_29b_a4b_ep8`` cell's benchmark files on the CPU: the two
readers of the hyper-connections (``hc.ms_per_step`` and
``kernels.hc_roofline``) on a hand-made trace, ``harness/hc_cost.py``'s
bytes against a count by hand at one small shape, the flops function
against the reference's own layer walk; and the tiny model, trained
through ``GluonTrainStep`` with Adam by the cell's own entry, against the
plain reference through ``check.against_reference`` (logits, loss, the
gradients of phi, b, alpha and an expert, the timed rows, the bias's
move), where wrong mixes and the reference in bfloat16 do not pass."""

import copy
import os
import re
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import (check, device, hc_cost,  # noqa: E402
                               manifest, trace)

CELL = "xing_hc_train_seq4k"
METRICS = ["hc.ms_per_step", "kernels.hc_roofline"]

# the real file's kinds of layer at a size for the CPU: the dense layer and
# one routed layer (the other three are the same code), 4 streams, yarn
# past its original 16 positions on rows of 32
TINY = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=2, intermediate_size=48,
    moe_intermediate_size=16, router_outputs=16, held_experts=[4, 4],
    num_experts_per_tok=3, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    rope_scaling={"type": "yarn", "factor": 4,
                  "original_max_position_embeddings": 16, "beta_fast": 4,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})


def tiny(config):
    """The cell's configuration at a size for the CPU: every key of the
    real file, the sizes and the depth replaced, the streams as they are;
    the compared names of the real file's first routed layer stand for
    those of the others."""
    config = copy.deepcopy(config)
    config["architecture"].update(copy.deepcopy(TINY))
    config["factory_kwargs"].update(weight_std=0.3)
    config["input"]["shape"] = [32]
    config.update(check_seq_len=32, check_batch=2, check_candidates=24)
    config["check_gradients"] = [
        re.sub(r"^(after_step\.)?l[2-4]_", r"\1l1_", n)
        for n in config["check_gradients"]]
    config["check_gradients"] = sorted(set(config["check_gradients"]),
                                       key=config["check_gradients"].index)
    config["training"]["lr"] = 1e-3
    return config


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


def test_the_cell_reports_its_two_metrics_and_no_other_cell_does(cell):
    m = cell.manifest
    reported = {metric["name"] for metric in cell.per_layer}
    for name in METRICS:
        entry = m.named("per_layer", name)
        assert entry["workloads"] == [CELL], name
        assert entry["moves"] == "train_samples_per_s"
        assert entry["source"] == "device_trace"
        assert name in reported
    assert m.named("per_layer", "hc.ms_per_step")["layer"] == "hc"
    assert m.named("per_layer", "kernels.hc_roofline")["layer"] == "kernels"
    assert {"step.device_ms", "step.mfu", "device.idle_share",
            "setup.compile_s"} <= reported
    assert not reported & {"kernels.mla_attention_roofline",
                           "attention.mla_ms_per_step"}
    assert cell.chips == 1 and cell.traffic["global_batch"] == 1
    assert cell.traffic["entry"] == "gluon_hc_lm_train_step"
    assert cell.traffic["warmup_groups"] == 3
    assert cell.traffic["compute_dtype"] == "bfloat16"
    assert cell.config["input"]["shape"] == [4096]
    assert len(m.named("workloads", CELL)["why"]) <= 200
    assert m.data["workloads"][-1]["name"] == CELL
    assert [e["name"] for e in m.data["per_layer"][-2:]] == METRICS


def test_the_file_keeps_the_published_widths_and_holds_759_M(cell):
    """Every width as published (hidden 3,584, MLA 768 / 512 / 32 x (128 +
    64) / 128, dense 9,216, experts and the shared expert 1,024, 64 router
    outputs and 4 a token, 4 streams, 20 Sinkhorn iterations); ``reduced``
    names the depth, the experts held, the vocabulary, the leading dense
    layers and the MTP module, whose published values stand under
    ``published``; the factory's model (shapes only, nothing drawn) holds
    759.3 M parameters beside its counters."""
    from mxnet_tpu.gluon.nn import MLAMoELM

    cfg, entry = cell.config, cell.manifest.named("configs",
                                                  cell.config_name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "n_routed_experts", "vocab_size", "num_hidden_layers",
        "first_k_dense_replace", "num_nextn_predict_layers"])
    assert cfg["published"] == {
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "num_nextn_predict_layers": 1}
    arch = cfg["architecture"]
    for key, value in arch.items():
        if key in cfg and key not in cfg["reduced"]:
            assert value == cfg[key], key
    assert (arch["hidden_size"], arch["q_lora_rank"], arch["kv_lora_rank"],
            arch["num_attention_heads"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"],
            arch["intermediate_size"], arch["moe_intermediate_size"],
            arch["router_outputs"], arch["num_experts_per_tok"],
            arch["hc_mult"], arch["hc_sinkhorn_iters"]) == (
        3584, 768, 512, 32, 128, 64, 128, 9216, 1024, 64, 4, 4, 20)
    assert arch["held_experts"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert arch["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert "8 chips share each layer" in cfg["deployment"]
    net = MLAMoELM(**arch)
    cut = len(net.prefix)
    shapes = {n[cut:]: p.shape for n, p in net.collect_params().items()}
    assert shapes["l0_hc_attn_phi"] == (4 * 3584, 24)
    assert shapes["l1_moe_experts_up_weight"] == (8, 3584, 1024)
    assert shapes["l1_moe_router_weight"] == (64, 3584)
    assert not any(n.startswith("mtp_") for n in shapes)
    held = sum(int(np.prod(s)) for n, s in shapes.items()
               if not n.endswith(("held_pairs", "max_load", "router_bias")))
    assert "%.1f M" % (held / 1e6) == "759.3 M"


# ------------------------------------------------------------- hc_cost


def test_the_least_bytes_are_the_hand_count_at_one_small_shape():
    """n = 2 streams of C = 8 lanes, 3 layers (6 sublayers), 2 rows of 5
    tokens, bfloat16.  A sublayer, a token, in elements: forward X read
    (16), u written (8); X read (16), y read (8), X' written (16) = 64;
    backward dX' (16), X (16), y (8) read, dy (8) written; du (8), X
    (16), dX' (16) read, dX (16) written = 104.  phi is 16 x 8, read twice
    and its gradient written once."""
    arch = {"hc_mult": 2, "hidden_size": 8, "num_hidden_layers": 3}
    assert hc_cost.stream_elements_per_token(arch) == 64 + 104
    by_hand = 6 * 2 * (10 * (64 + 104) + 3 * 16 * 8)
    assert hc_cost.least_bytes(arch, 2, 5, 2) == by_hand
    assert hc_cost.least_bytes(arch, 2, 5, 4) == 2 * by_hand
    assert hc_cost.itemsize("bfloat16") == 2


def test_the_cells_least_bytes_by_hand(cell):
    """At 1 x 4,096 tokens, 4 streams of 3,584 and 10 sublayers: 37 x
    3,584 elements a token and sublayer, 10.86 GB of streams and 20.6 MB
    of phi: 13.3 ms at the v5e's 819 GB/s."""
    arch = cell.config["architecture"]
    least = hc_cost.least_bytes(arch, 1, 4096, 2)
    assert least == 10 * 2 * (4096 * 37 * 3584 + 3 * 14336 * 24)
    peaks = device.load_peaks(REPO)["TPU v5 lite"]
    assert least / peaks["hbm_bytes_per_s"] == pytest.approx(13.29e-3,
                                                             rel=1e-3)


# ------------------------------------------------------------- readers


@pytest.fixture()
def observed(cell):
    """One step of 1,000 us on a hand-made trace: the coefficients 30 us
    and the mixes 90 us under their scopes, beside them the attention, the
    experts and the head, and one instruction whose op_name only contains
    ``hc`` in a longer component (a block named ``hc_attn``: not the
    scope)."""
    ops, names, at = [], {}, 0
    for i, (scope, us) in enumerate([
            ("hc.coef", 30), ("hc.mix", 50), ("mla.proj", 200),
            ("mla.attention", 300), ("hc.mix", 40), ("moe.experts", 100),
            ("hc_attn", 7), ("lm_head", 50)]):
        name = "fusion.%d" % i
        ops.append(trace.Op(at, at + us * 1000,
                            "%%%s = f32[256]{0} fusion(f32[256]{0} %%a), "
                            "kind=kLoop, calls=%%fc%d" % (name, i)))
        names[name] = (0, "jit(step)/jit(main)/transpose(jvp(l1))/%s/op"
                       % scope)
        at += us * 1000
    recorded = trace.Trace({0: {"ops": ops, "async": [], "modules": []}},
                           [(trace.WINDOW_SPAN, 0, 1_000_000)])
    return {"cell": cell, "trace": recorded, "chips": 1,
            "modules": [types.SimpleNamespace(instructions=names)],
            "peaks": device.load_peaks(REPO)["TPU v5 lite"],
            "tail": {"steps": 1, "counters": {}}}


def test_the_readers_find_their_scopes_on_a_hand_made_trace(cell, observed):
    assert cell.reader("hc.ms_per_step").read(observed) == pytest.approx(
        (30 + 50 + 40) / 1e3)
    least = hc_cost.least_bytes(cell.config["architecture"], 1, 4096, 2)
    want = 100.0 * least / observed["peaks"]["hbm_bytes_per_s"] / 120e-6
    assert cell.reader("kernels.hc_roofline").read(observed) \
        == pytest.approx(want)
    # two steps in the tail: per step half the time, twice the share
    two = dict(observed, tail={"steps": 2, "counters": {}})
    assert cell.reader("hc.ms_per_step").read(two) == pytest.approx(0.06)
    assert cell.reader("kernels.hc_roofline").read(two) \
        == pytest.approx(2 * want)


def test_without_the_scopes_or_a_trace_the_readers_find_nothing(cell,
                                                               observed):
    """The parent's program (no hyper-connections) or a CPU: nothing to
    read, nothing raised."""
    for empty in (dict(observed, trace=None),
                  dict(observed, trace=trace.Trace({}, []))):
        for metric in METRICS:
            assert cell.reader(metric).read(empty) is None
    names = observed["modules"][0].instructions
    for name, (cost, op_name) in list(names.items()):
        names[name] = (cost, op_name.replace("hc.", "mla."))
    for metric in METRICS:
        assert cell.reader(metric).read(observed) is None


# ------------------------------------------------------------- flops


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
    from its jaxpr (the two mixes are products there), are what the
    formula gives for the same walk; the cell's number is the hand count,
    the hyper-connections' 10 x 415,744 multiply-adds a token among it."""
    from mxnet_tpu.gluon.nn import MLAMoELM

    reference = cell.reference()
    arch = tiny(cell.config)["architecture"]
    net = MLAMoELM(**arch)
    cut = len(net.prefix)
    shapes = {n[cut:]: jax.ShapeDtypeStruct(p.shape, np.float32)
              for n, p in net.collect_params().items()}
    rows, seq = 2, 16
    jaxpr = jax.make_jaxpr(lambda p, x: reference.forward(p, x, arch))(
        shapes, jax.ShapeDtypeStruct((rows, seq), np.float32))
    walked = 2.0 * _dot_macs(jaxpr.jaxpr)
    formula = reference.forward_flops_per_token(
        arch, seq, pairs=arch["held_experts"][1], square_share=1)
    assert walked == pytest.approx(formula * rows * seq, rel=1e-9)

    real, seq = cell.config["architecture"], 4096
    hc = 14336 * 24 + 14336 + 16 * 3584
    assert reference.hc_macs_per_token(real) == hc == 415744
    attn = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 3584 + 32 * 320 * seq / 2
    routed = 3584 * 64 + 3 * 3584 * 1024 * (1 + 4 * 8 / 64)
    macs = 5 * (attn + 2 * hc) + 3 * 3584 * 9216 + 4 * routed \
        + 3584 * 16384
    per_token = reference.forward_flops_per_token(real, seq)
    assert per_token == pytest.approx(2.0 * macs, rel=1e-12)
    assert reference.flops_per_sample(real, cell.config["input"]["shape"]) \
        == pytest.approx(3 * seq * per_token)


# ------------------------------------------------- system against reference


class Lines(list):
    def __call__(self, message):
        self.append(message)


@pytest.fixture(scope="module")
def session_and_system(cell):
    small = copy.copy(cell)
    small.config = tiny(cell.config)
    reference = cell.reference()
    ctx = run.Context(small, seed=4200000777, devices=jax.devices()[:1])
    ctx.say = Lines()
    session = small.entry().build(ctx)
    return small, reference, session, session.system_outputs(reference)


def test_system_agrees_with_its_plain_reference(session_and_system):
    """The tiny model through the cell's entry: ``GluonTrainStep`` with
    Adam, in float32; logits, loss, the gradients of an expert, phi, b and
    alpha on the check rows, the timed rows (embedding, expansion, layer 0
    whole with both hyper-connections, collapse, final norm, head) and two
    routed layer's state after the step, each within its limit."""
    small, reference, session, system = session_and_system
    assert any("candidate rows rejected" in line for line in session.ctx.say)
    assert system["logits"].shape == (2, 32, 97)
    grads = system["gradients"]
    assert grads["dense_prefix.hidden"].shape == (1, 32, 32)
    assert grads["l1_hc_attn_phi"].shape == (4 * 32, 24)
    assert grads["dense_prefix.l0_hc_attn_bias"].shape == (24,)
    assert grads["l1_hc_attn_alpha"].shape == (3,)
    lines = Lines()
    assert check.against_reference(reference, small.config, system, lines), \
        "\n".join(lines)
    assert len(lines) == 2 + len(small.config["check_gradients"])


def _transposed_mixing(reference, monkeypatch):
    plain = reference.sinkhorn
    monkeypatch.setattr(reference, "sinkhorn", lambda m, arch: jnp.swapaxes(
        plain(m, arch), -1, -2))


def _h_post_without_its_factor_of_2(reference, monkeypatch):
    plain = reference.coefficients

    def coefficients(p, pre, X, arch):
        h_pre, h_post, h_res = plain(p, pre, X, arch)
        return h_pre, h_post / 2, h_res

    monkeypatch.setattr(reference, "coefficients", coefficients)


WRONG = [_transposed_mixing, _h_post_without_its_factor_of_2]


@pytest.mark.parametrize("wrong", WRONG, ids=[f.__name__[1:] for f in WRONG])
def test_a_wrong_mix_fails_the_check(wrong, session_and_system, monkeypatch):
    """The comparison is symmetric: a reference that mixes the streams by
    ``H_res`` transposed or drops ``h_post``'s factor of 2 stands for a
    system that does, against the same limits."""
    small, reference, _, system = session_and_system
    wrong(reference, monkeypatch)
    lines = Lines()
    assert not check.against_reference(reference, small.config, system,
                                       lines)
    assert any(line.endswith("FAIL") for line in lines)


def test_the_reference_computed_in_bfloat16_fails_every_floor(
        session_and_system):
    """The reference in the nearest precision below the stated one, handed
    to the comparison as if a system had computed it: logits, loss, the
    hyper-connections' gradients and the timed rows each fall outside their
    limit."""
    small, reference, _, system = session_and_system
    logits, loss, grads = reference.outputs(
        small.config["architecture"], [(system["params"], system["x"])],
        system["y"], dtype="bfloat16")[0]
    lower = dict(system, logits=np.asarray(logits, np.float32),
                 loss=float(loss),
                 gradients={n: np.asarray(g, np.float32)
                            for n, g in grads.items()})
    lines = Lines()
    assert not check.against_reference(reference, small.config, lower, lines)
    failed = " ".join(line.split()[1] for line in lines
                      if line.endswith("FAIL"))
    for kind in ("logits", "loss", "l1_hc_attn_phi", "l1_hc_ffn_bias",
                 "dense_prefix.hidden", "dense_prefix.l0_hc_attn_phi"):
        assert kind in failed, (kind, lines)
