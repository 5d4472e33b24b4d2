"""The ``joyai_llm_flash_ep16`` configuration and its cell on the CPU: the
file keeps the published widths, the flops function counts what the
reference's own layer walk multiplies, the system agrees with the reference
through the cell's entry and ``check.against_reference`` at a tiny size
(and four wrong computations do not), and one whole run prints a result."""

import copy
import json
import os
import shutil
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import check, device, manifest  # noqa: E402

CELL = "joyai_flash_train_seq4k"

# the catalog row's ``config`` (its source: the cell's ``source`` URL)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}

TINY = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=2,
    first_k_dense_replace=1, intermediate_size=48, moe_intermediate_size=16,
    num_experts_per_tok=4, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    router_outputs=16, held_experts=[4, 8])


def tiny(config):
    """The cell's configuration at a size for the CPU: every key of the
    real file, the sizes replaced."""
    config = copy.deepcopy(config)
    config["architecture"].update(TINY)
    config["factory_kwargs"].update(weight_std=0.3)
    config["input"]["shape"] = [16]
    config.update(check_seq_len=16, check_batch=2, check_candidates=16,
                  check_gradients=["l1_attn_qb_weight",
                                   "l1_moe_experts_up_weight",
                                   "embed_weight"] + [
                      n for n in config["check_gradients"]
                      if n.startswith("dense_prefix.")])
    config["training"]["lr"] = 1e-3
    return config


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    cfg, entry = cell.config, cell.manifest.named("configs",
                                                  cell.config_name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # the share: 16 of 256 experts, an eighth of the vocabulary, the
    # leading dense layer and four routed layers
    assert cfg["n_routed_experts"] * 16 == PUBLISHED["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_hidden_layers"] == cfg["first_k_dense_replace"] + 4
    arch = cfg["architecture"]
    for key, value in arch.items():
        if key in cfg:
            assert value == cfg[key], key
    assert not set(cfg["factory_kwargs"]) & set(arch)
    assert arch["router_outputs"] == PUBLISHED["n_routed_experts"]
    assert arch["held_experts"] == [0, cfg["n_routed_experts"]]
    assert arch["mtp_loss_weight"] == cfg["training"]["mtp_loss_weight"]
    assert cfg["input"]["shape"] == [4096]
    assert cell.traffic["global_batch"] == 2 and cell.chips == 1


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
    from its jaxpr, are what the formula gives for the same walk (the dense
    mask runs every held expert on every token, attention the whole
    square); the cell's number differs in those two terms only, and is the
    issue's 0.88 GFLOP a token."""
    import jax

    reference = cell.reference()
    arch = tiny(cell.config)["architecture"]
    rows, seq = 2, 16
    shapes = {
        n: jax.ShapeDtypeStruct(s, np.float32)
        for n, s in _shapes(arch).items()}
    jaxpr = jax.make_jaxpr(lambda p, x: reference.forward(p, x, arch))(
        shapes, jax.ShapeDtypeStruct((rows, seq), np.float32))
    walked = 2.0 * _dot_macs(jaxpr.jaxpr)
    formula = reference.forward_flops_per_token(
        arch, seq, pairs=arch["held_experts"][1], square_share=1.0)
    assert walked == pytest.approx(formula * rows * seq, rel=1e-9)

    real = cell.config["architecture"]
    per_token = reference.forward_flops_per_token(real, 4096)
    assert per_token == pytest.approx(0.881e9, rel=1e-3)
    assert reference.flops_per_sample(real, cell.config["input"]["shape"]) \
        == pytest.approx(3 * 4096 * per_token)
    # the held experts' share of it: half a pair a token
    with_experts = reference.forward_flops_per_token(real, 4096, pairs=0.5)
    assert with_experts == per_token


def _shapes(arch):
    """{parameter name: shape}, as the program names them."""
    from mxnet_tpu.gluon.nn import MLAMoELM

    net = MLAMoELM(**{k: v for k, v in arch.items()
                      if k != "mtp_loss_weight"})
    cut = len(net.prefix)
    return {n[cut:]: p.shape for n, p in net.collect_params().items()}


# ------------------------------------------------- system against reference


class Lines(list):
    def __call__(self, message):
        self.append(message)


@pytest.fixture(scope="module")
def session_and_system(cell):
    import jax

    small = copy.copy(cell)
    small.config = tiny(cell.config)
    reference = cell.reference()
    ctx = run.Context(small, seed=2400000123, devices=jax.devices()[:1])
    ctx.say = Lines()
    session = small.entry().build(ctx)
    return small, reference, session, session.system_outputs(reference)


def test_system_agrees_with_its_plain_reference(session_and_system):
    small, reference, session, system = session_and_system
    said = session.ctx.say
    assert any("candidate rows rejected" in line for line in said)
    assert system["x"].shape == (2, 16) and system["logits"].shape == (
        2, 2, 16, 97)
    # the timed step's shape: the traffic's rows of the input's length
    assert system["y"].shape == (2, 16)
    assert system["gradients"]["dense_prefix.hidden"].shape == (2, 16, 32)
    lines = Lines()
    assert check.against_reference(reference, small.config, system, lines), \
        "\n".join(lines)
    # logits, loss, three gradients; the dense prefix's stream and four
    # gradients
    assert len(lines) == 10


def _without_the_mtp_term(reference, config, monkeypatch):
    config["architecture"]["mtp_loss_weight"] = 0.0


def _weighing_by_biased_scores(reference, config, monkeypatch):
    import jax
    import jax.numpy as jnp

    def route(p, pre, x, arch):
        k = arch["num_experts_per_tok"]
        s = jax.nn.sigmoid(x @ p[pre + "router_weight"].T) \
            + p[pre + "router_bias"]
        picked, ids = jax.lax.top_k(s, k)
        return ids, picked / jnp.sum(picked, axis=-1, keepdims=True) \
            * arch["routed_scaling_factor"], picked[..., 0]

    monkeypatch.setattr(reference, "route", route)


def _normalising_over_the_held_only(reference, config, monkeypatch):
    import jax.numpy as jnp

    plain = reference.route

    def route(p, pre, x, arch):
        ids, weights, margin = plain(p, pre, x, arch)
        first, held = arch["held_experts"]
        here = (ids >= first) & (ids < first + held)
        kept = jnp.where(here, weights, 0.0)
        return ids, kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20) \
            * arch["routed_scaling_factor"], margin

    monkeypatch.setattr(reference, "route", route)


def _a_dense_prefix_whose_loss_skips_a_quarter(reference, config,
                                               monkeypatch):
    """Only the comparison at the timed shape sees it: a loss over chunks
    that loses one."""
    plain = reference.cross_entropy

    def cross_entropy(logits, labels, valid):
        if logits.shape[0] == 1:    # the dense prefix runs row by row
            valid = valid * 3 // 4
        return plain(logits, labels, valid)

    monkeypatch.setattr(reference, "cross_entropy", cross_entropy)


WRONG = [_without_the_mtp_term, _weighing_by_biased_scores,
         _normalising_over_the_held_only,
         _a_dense_prefix_whose_loss_skips_a_quarter]


@pytest.mark.parametrize("wrong", WRONG, ids=[f.__name__[1:] for f in WRONG])
def test_a_wrong_computation_fails_the_check(wrong, session_and_system,
                                             monkeypatch):
    """The comparison is symmetric: a reference that drops the MTP term,
    weighs by the biased scores or normalises over the held experts only
    stands for a system that does, against the same limits."""
    small, reference, _, system = session_and_system
    config = copy.deepcopy(small.config)
    wrong(reference, config, monkeypatch)
    lines = Lines()
    assert not check.against_reference(reference, config, system, lines)
    assert any(line.endswith("FAIL") for line in lines)


def test_the_check_computed_in_bfloat16_fails(session_and_system):
    """The system's side in the nearest precision below the stated one."""
    small, reference, session, system = session_and_system
    make = session._make_step
    session._make_step = lambda dtype: make("bfloat16")
    try:
        lower = session.system_outputs(reference)
    finally:
        session._make_step = make
    np.testing.assert_array_equal(lower["x"], system["x"])
    lines = Lines()
    assert not check.against_reference(reference, small.config, lower, lines)
    failed = [line for line in lines if line.endswith("FAIL")]
    assert failed and not any("logits" in line for line in failed)


def test_one_adam_update_is_the_references(session_and_system):
    """One step of the cell's path from zero state moves every weight by
    Adam's rule on the reference's gradient: ``g' = g + wd W``, ``W -= lr
    sqrt(1 - b2) / (1 - b1) * m / (sqrt(v) + eps)``."""
    small, reference, session, system = session_and_system
    train = small.config["training"]
    step = session._make_step(None)
    import jax

    with jax.default_matmul_precision("highest"):
        step(system["x"], system["x"])
    cut = len(session.net.prefix)
    after = {p.name[cut:]: np.asarray(v)
             for p, v in zip(step.trainable, step.train_vals)}
    ref = reference.outputs(small.config["architecture"],
                            [(system["params"], system["x"])],
                            system["y"])[0][2]
    before = dict(system["params"])
    b1, b2 = train["beta1"], train["beta2"]
    for name in small.config["check_gradients"][:3]:
        g = np.asarray(ref[name]) + train["wd"] * before[name]
        m, v = (1 - b1) * g, (1 - b2) * g * g
        want = before[name] - train["lr"] * np.sqrt(1 - b2) / (1 - b1) \
            * m / (np.sqrt(v) + train["epsilon"])
        moved = np.abs(want - before[name]).max()
        assert np.abs(after[name] - want).max() < 2e-3 * moved, name


# --------------------------------------------------------- one whole run


def cpu_gate(chips, root):
    import jax

    return jax.devices()[:chips], device.load_peaks(root)["TPU v5 lite"]


def test_one_whole_run_of_the_cell_at_a_tiny_size(cell, tmp_path, capsys):
    """``run.main`` through the cell's own files, the configuration's sizes
    replaced: a result line, correct, with the program counter's metric;
    the device-trace readers find no device plane on a CPU and leave their
    metrics out."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    entry = cell.manifest.named("configs", cell.config_name)
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump(tiny(cell.config), f)
    assert run.main(["--workload", CELL, "--seed", "2400000321",
                     "--seconds", "0.5", "--trace", "1"],
                    gate=cpu_gate, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, lines[-12:]
    metrics = result["metrics"]
    assert metrics["entry.compiles_in_window"]["value"] == 0
    assert metrics["moe.max_expert_load_ratio"]["value"] >= 1.0
    assert 0 < metrics["step.mfu"]["value"] < 100
    for name in ("kernels.mla_attention_roofline", "attention.mla_ms_per_step",
                 "moe.routed_ms_per_step", "lm_head.ms_per_step"):
        assert name not in metrics
    assert any("candidate rows rejected" in line for line in lines)
