"""The ``lfm2_8b_a1b_ep4`` configuration and its cell on the CPU: the file
keeps the published widths, the flops function counts what the reference's
own layer walk multiplies, the system agrees with the reference through the
cell's entry and ``check.against_reference`` at a tiny size (and wrong
computations, and the reference in bfloat16, do not), the four shares of a
routed layer add up to the uncut layer, the new kernels' cost functions
count from the shapes of the work, the warm-up is the timed step and nothing
else, and one whole run prints a result."""

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
from benchmark.harness import (check, device, gqa_attention_cost,  # noqa: E402
                               manifest)

CELL = "lfm2_moe_train_seq8k"

_PERIOD = ["conv", "conv", "conv", "full_attention"]
# the catalog row's ``config`` (its source: the cell's ``source`` URL)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention"] + _PERIOD * 4
    + ["conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}

TINY = dict(
    vocab_size=97, hidden_size=32,
    layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
    intermediate_size=48, moe_intermediate_size=16, num_experts_per_tok=2,
    num_attention_heads=4, num_key_value_heads=2, router_outputs=8,
    held_experts=[2, 4])


def tiny(config):
    """The cell's configuration at a size for the CPU: every key of the
    real file, the sizes replaced."""
    config = copy.deepcopy(config)
    config["architecture"].update(TINY)
    config["factory_kwargs"].update(weight_std=0.3)
    config["input"]["shape"] = [16]
    config.update(check_seq_len=16, check_batch=2, check_candidates=16,
                  check_gradients=["l1_attn_k_weight", "l2_conv_in_weight",
                                   "l2_moe_experts_up_weight",
                                   "embed_weight"] + [
                      n for n in config["check_gradients"] if "." in n
                      and not n.startswith(("after_step.l3", "after_step.l4"))])
    config["training"]["lr"] = 1e-3
    return config


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    cfg, entry = cell.config, cell.manifest.named("configs",
                                                  cell.config_name)
    assert len(PUBLISHED["layer_types"]) == 24
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert entry["source"] in cfg["source"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # the share: 8 of 32 experts, a quarter of the vocabulary, published
    # layers 1-5: one leading dense layer and one whole period
    assert cfg["num_experts"] * 4 == PUBLISHED["num_experts"]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6] \
        == ["conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) \
        == cfg["num_dense_layers"] + 4
    assert "4 chips share each layer" in cfg["deployment"]
    arch = cfg["architecture"]
    for key, value in arch.items():
        if key in cfg:
            assert value == cfg[key], key
    assert not set(cfg["factory_kwargs"]) & set(arch)
    assert arch["router_outputs"] == PUBLISHED["num_experts"]
    assert arch["held_experts"] == [0, cfg["num_experts"]]
    assert arch["route_epsilon"] == 1e-6
    assert len(cfg["assumed"]) >= 9
    assert cfg["input"]["shape"] == [8192]
    assert cell.traffic["global_batch"] == 2 and cell.chips == 1
    assert cell.traffic["entry"] == "gluon_next_token_train_step"


def _net(arch, **kwargs):
    from mxnet_tpu.gluon.nn import ConvAttentionMoELM

    return ConvAttentionMoELM(**dict(arch, **kwargs))


def _shapes(arch):
    """{parameter name: shape}, as the program names them."""
    net = _net(arch)
    cut = len(net.prefix)
    return {n[cut:]: p.shape for n, p in net.collect_params().items()}


def test_the_built_model_holds_the_parameters_the_file_states(cell):
    shapes = _shapes(cell.config["architecture"])
    assert "head_weight" not in shapes          # tied: one parameter
    held = sum(int(np.prod(s)) for n, s in shapes.items()
               if not n.endswith(("held_pairs", "max_load")))
    by_hand = 16384 * 2048 \
        + (4 * 2048 * 2048 + 2048 * 3 + 3 * 2048 * 7168) \
        + (2 * 2048 * 2048 + 2 * 512 * 2048 + 2 * 64) \
        + 3 * (4 * 2048 * 2048 + 2048 * 3) \
        + 4 * (32 * 2048 + 32 + 8 * 3 * 2048 * 1792) \
        + 5 * 2 * 2048 + 2048
    assert held == by_hand
    assert "%.1f M" % (held / 1e6) in cell.config["deployment"]


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
    issue's hand count: 0.433 GFLOP a token."""
    import jax

    reference = cell.reference()
    arch = tiny(cell.config)["architecture"]
    rows, seq = 2, 16
    shapes = {n: jax.ShapeDtypeStruct(s, np.float32)
              for n, s in _shapes(arch).items()}
    jaxpr = jax.make_jaxpr(lambda p, x: reference.forward(p, x, arch))(
        shapes, jax.ShapeDtypeStruct((rows, seq), np.float32))
    walked = 2.0 * _dot_macs(jaxpr.jaxpr)
    formula = reference.forward_flops_per_token(
        arch, seq, pairs=arch["held_experts"][1], square_share=1.0)
    assert walked == pytest.approx(formula * rows * seq, rel=1e-9)

    real = cell.config["architecture"]
    # by hand, multiply-adds a token: a convolution layer's two products,
    # the attention layer's four and the causal half of its square, the
    # dense feed-forward, a router with one held pair a token, the head
    conv, dense = 4 * 2048 * 2048, 3 * 2048 * 7168
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 32 * 128 * 4096
    routed = 2048 * 32 + 3 * 2048 * 1792 * (4 * 8 / 32)
    macs = (conv + dense) + (attn + routed) + 3 * (conv + routed) \
        + 2048 * 16384
    per_token = reference.forward_flops_per_token(real, 8192)
    assert per_token == 2.0 * macs == pytest.approx(0.4327e9, rel=1e-3)
    assert reference.flops_per_sample(real, cell.config["input"]["shape"]) \
        == pytest.approx(3 * 8192 * per_token)


def test_the_kernels_cost_comes_from_the_shapes_of_the_work(cell):
    """Operations: the causal half per query head; bytes: q-sized arrays
    per query head, k-sized per key head.  A kernel handed repeated keys
    is the same work, so the same bound: a lower share, not a higher one."""
    shapes = gqa_attention_cost.work(cell.config["architecture"], 2, 8192)
    assert shapes == {"rows": 2, "seq": 8192, "heads": 32, "kv_heads": 8,
                      "d": 64}
    pairs = 2 * 32 * 8192 * 8192 / 2
    q_bytes, k_bytes = 2 * 32 * 8192 * 64 * 2, 2 * 8 * 8192 * 64 * 2
    row_bytes = 2 * 32 * 8192 * 4
    want = {"forward": (pairs * 256, 2 * q_bytes + 2 * k_bytes + row_bytes),
            "dq": (pairs * 384, 3 * q_bytes + 2 * k_bytes + 2 * row_bytes),
            "dkv": (pairs * 512, 2 * q_bytes + 4 * k_bytes + 2 * row_bytes)}
    for kind, cost in want.items():
        assert gqa_attention_cost.kernel_cost(kind, 2, shapes) == cost

    def call(operands, results):
        return "%%k = %s custom-call(%s), custom_call_target=" \
            "\"tpu_custom_call\"" % (results, ", ".join(
                "%s %%a%d" % (t, i) for i, t in enumerate(operands)))

    q, k, rows = "bf16[64,8192,64]", "bf16[16,8192,64]", "f32[64,1,8192]"
    assert gqa_attention_cost.kernel_kind(
        call([q, k, k], "(%s, %s)" % (q, rows)), shapes) == ("forward", 2)
    assert gqa_attention_cost.kernel_kind(
        call([q, k, k, q, rows, rows], q), shapes) == ("dq", 2)
    assert gqa_attention_cost.kernel_kind(
        call([q, k, k, q, rows, rows], "(%s, %s)" % (k, k)),
        shapes) == ("dkv", 2)
    # keys repeated in HBM: recognised by its q, charged the same work
    assert gqa_attention_cost.kernel_kind(
        call([q, q, q], "(%s, %s)" % (q, rows)), shapes) == ("forward", 2)
    # another model's kernel, another instruction
    assert gqa_attention_cost.kernel_kind(
        call(["bf16[64,4096,192]"] * 3, q), shapes) is None
    assert gqa_attention_cost.kernel_kind(
        "%f = bf16[64,8192,64] fusion(bf16[64,8192,64] %a)", shapes) is None


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
    ctx = run.Context(small, seed=3200000123, devices=jax.devices()[:1])
    ctx.say = Lines()
    session = small.entry().build(ctx)
    return small, reference, session, session.system_outputs(reference)


def test_system_agrees_with_its_plain_reference(session_and_system):
    small, reference, session, system = session_and_system
    said = session.ctx.say
    assert any("candidate rows rejected" in line for line in said)
    assert system["x"].shape == (2, 16)
    assert system["logits"].shape == (2, 16, 97)
    # the timed step's shape: the traffic's rows of the input's length
    assert system["y"].shape == (2, 16)
    assert system["gradients"]["dense_prefix.hidden"].shape == (2, 16, 32)
    assert system["gradients"]["gqa_timed.out"].shape == (2, 16, 32)
    assert system["gradients"]["gqa_timed.l1_attn_k_weight"].shape == (16, 32)
    lines = Lines()
    assert check.against_reference(reference, small.config, system, lines), \
        "\n".join(lines)
    # logits, loss, four gradients; the dense prefix's stream and three
    # gradients; the attention layer's output and three gradients; two
    # routed layers' bias moves and held pairs after the step
    assert len(lines) == 18
    moves = system["gradients"]["after_step.l2_moe_router_bias"]
    assert moves.shape == (8,) and set(np.round(moves, 3)) <= {-1.0, 0.0, 1.0}
    assert np.abs(moves).sum() >= 4


def _rotating_adjacent_pairs(reference, config, monkeypatch):
    import jax.numpy as jnp

    def rope(x, theta):
        seq, d = x.shape[-2:]
        inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
        cos, sin = jnp.asarray(np.cos(angle), x.dtype), \
            jnp.asarray(np.sin(angle), x.dtype)
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                         axis=-1).reshape(x.shape)

    monkeypatch.setattr(reference, "rope", rope)


def _reading_the_key_head_of_another_group(reference, config, monkeypatch):
    """Query head ``h`` on key head ``h % kv_heads``: the keys tiled where
    they should be repeated."""
    import jax.numpy as jnp

    plain = reference._qkv

    def qkv(p, pre, x, arch):
        q, k, v = plain(p, pre, x, arch)
        group = arch["num_attention_heads"] // arch["num_key_value_heads"]
        return (q,) + tuple(jnp.concatenate([a[:, ::group]] * group, axis=1)
                            for a in (k, v))

    monkeypatch.setattr(reference, "_qkv", qkv)


def _a_convolution_that_looks_ahead(reference, config, monkeypatch):
    import jax.numpy as jnp

    def short_conv(p, pre, x, arch):
        taps, seq = arch["conv_L_cache"], x.shape[1]
        b, c, xt = jnp.split(x @ p[pre + "in_weight"].T, 3, axis=-1)
        u = jnp.pad(b * xt, ((0, 0), (0, taps - 1), (0, 0)))
        kernel = p[pre + "conv_weight"]
        conv = sum(kernel[:, j] * u[:, j:j + seq] for j in range(taps))
        return (c * conv) @ p[pre + "out_weight"].T

    monkeypatch.setattr(reference, "short_conv", short_conv)


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


def _a_head_of_its_own(reference, config, monkeypatch):
    """An untied head shows in the embedding's gradient: the head's part
    of the sum is missing."""
    import jax
    import jax.numpy as jnp

    def loss(p, x, tokens, arch):
        h, _ = reference.hidden_states(p, reference._ids(x), arch)
        logits = reference.rms_norm(h, p["norm_weight"], arch["norm_eps"]) \
            @ jax.lax.stop_gradient(p["embed_weight"]).T
        return reference.cross_entropy(logits, jnp.roll(tokens, -1, axis=1),
                                       tokens.shape[1] - 1)

    monkeypatch.setattr(reference, "loss", loss)


def _a_rule_that_feeds_the_popular_experts(reference, config, monkeypatch):
    """The balancing rule with its sign the wrong way round."""
    plain = reference.after_step

    def after_step(p, tokens, arch):
        return {n: -v if n.endswith("router_bias") else v
                for n, v in plain(p, tokens, arch).items()}

    monkeypatch.setattr(reference, "after_step", after_step)


def _a_rule_at_twice_the_rate(reference, config, monkeypatch):
    plain = reference.after_step

    def after_step(p, tokens, arch):
        return {n: 2 * v if n.endswith("router_bias") else v
                for n, v in plain(p, tokens, arch).items()}

    monkeypatch.setattr(reference, "after_step", after_step)


def _pairs_counted_on_other_experts(reference, config, monkeypatch):
    """The held experts taken to start at id 0 where they start at 2."""
    plain = reference.after_step

    def after_step(p, tokens, arch):
        return plain(p, tokens, dict(
            arch, held_experts=[0, arch["held_experts"][1]]))

    monkeypatch.setattr(reference, "after_step", after_step)


WRONG = [_rotating_adjacent_pairs, _reading_the_key_head_of_another_group,
         _a_convolution_that_looks_ahead, _weighing_by_biased_scores,
         _a_head_of_its_own, _a_rule_that_feeds_the_popular_experts,
         _a_rule_at_twice_the_rate, _pairs_counted_on_other_experts]


@pytest.mark.parametrize("wrong", WRONG, ids=[f.__name__[1:] for f in WRONG])
def test_a_wrong_computation_fails_the_check(wrong, session_and_system,
                                             monkeypatch):
    """The comparison is symmetric: a reference that rotates other pairs,
    reads another group's keys, convolves forward in time, weighs by the
    biased scores or unties the head stands for a system that does,
    against the same limits; so does a balancing rule with the wrong sign
    or rate, or a count of other experts' pairs."""
    small, reference, _, system = session_and_system
    config = copy.deepcopy(small.config)
    wrong(reference, config, monkeypatch)
    lines = Lines()
    assert not check.against_reference(reference, config, system, lines)
    assert any(line.endswith("FAIL") for line in lines)


def test_the_reference_computed_in_bfloat16_fails_every_floor(
        session_and_system):
    """The reference in the nearest precision below the stated one, handed
    to the comparison as if a system had computed it: logits, loss and
    gradients each fall outside their limit."""
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
    for kind in ("logits", "loss", "embed_weight", "dense_prefix.hidden",
                 "gqa_timed.out", "gqa_timed.l1_attn_k_weight"):
        assert kind in failed, (kind, lines)


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
    assert any(line.endswith("FAIL") for line in lines)


def test_one_adam_update_is_the_references(session_and_system):
    """One step of the cell's path from zero state moves every weight by
    Adam's rule on the reference's gradient: ``g' = g + wd W``, ``W -= lr
    sqrt(1 - b2) / (1 - b1) * m / (sqrt(v) + eps)``; the tied weight, which
    the embedding and the head both read, once."""
    small, reference, session, system = session_and_system
    train = small.config["training"]
    step = session._make_step(None)
    import jax

    with jax.default_matmul_precision("highest"):
        step(system["x"], system["x"])
    cut = len(session.net.prefix)
    names = [p.name[cut:] for p in step.trainable]
    assert names.count("embed_weight") == 1 and "head_weight" not in names
    after = dict(zip(names, (np.asarray(v) for v in step.train_vals)))
    ref = reference.outputs(small.config["architecture"],
                            [(system["params"], system["x"])],
                            system["y"])[0][2]
    before = dict(system["params"])
    b1, b2 = train["beta1"], train["beta2"]
    for name in small.config["check_gradients"][:4]:
        g = np.asarray(ref[name]) + train["wd"] * before[name]
        m, v = (1 - b1) * g, (1 - b2) * g * g
        want = before[name] - train["lr"] * np.sqrt(1 - b2) / (1 - b1) \
            * m / (np.sqrt(v) + train["epsilon"])
        moved = np.abs(want - before[name]).max()
        assert np.abs(after[name] - want).max() < 2e-3 * moved, name


def test_the_warm_up_is_the_timed_step_and_nothing_else(session_and_system):
    """``warm_up`` runs the step program once, a group, and the traffic's
    ``warmup_groups`` groups, and every routed layer's selection bias has
    moved from its draw by that many times the rule's rate at most, in
    whole rates: nothing but the step's own rule touched it."""
    small, _, session, system = session_and_system
    traffic = small.traffic
    steps = 1 + (1 + traffic["warmup_groups"]) * traffic["steps_per_fetch"]
    assert traffic["warmup_groups"] >= 3
    dispatched, dispatch = [], session.dispatch
    session.dispatch = lambda: (dispatched.append(1), dispatch())[1]
    try:
        session.warm_up()
    finally:
        del session.dispatch
    assert len(dispatched) == steps
    rate = small.config["architecture"]["bias_update_rate"]
    drawn, cut = dict(system["params"]), len(session.net.prefix)
    moved = 0
    for p, v in zip(session.step.aux, session.step.aux_vals):
        if p.name.endswith("router_bias"):
            rates = (np.asarray(v) - drawn[p.name[cut:]]) / rate
            assert np.abs(rates - np.round(rates)).max() < 1e-2
            assert np.abs(rates).max() <= steps
            moved += int(np.abs(np.round(rates)).sum())
    assert moved > 0
    assert any("after the warm-up" in line for line in session.ctx.say)


def test_the_four_shares_of_a_routed_layer_sum_to_the_uncut_layer():
    """Four chips hold experts 0-7, 8-15, 16-23 and 24-31 of 32.  Their
    routed outputs (there is no shared expert to count once) add up to the
    uncut layer's, which is the reference's with all 32 held."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.nn import RoutedExperts

    reference = manifest.load_module(os.path.join(
        REPO, "benchmark", "references", "lfm2_moe.py"))

    def layer(held, prefix):
        mx.random.seed(5)
        blk = RoutedExperts(32, 16, 32, 4, held_experts=held, weight_std=0.3,
                            shared=False, route_epsilon=1e-6, prefix=prefix)
        blk.initialize(ctx=mx.cpu())
        return blk

    whole = layer((0, 32), "whole_")
    full = {n[len("whole_"):]: p.data().asnumpy()
            for n, p in whole.collect_params().items()}
    assert not any("shared" in n for n in full)
    x = mx.nd.array(np.random.RandomState(2).randn(2, 16, 32)
                    .astype(np.float32))
    total = 0
    for first in (0, 8, 16, 24):
        share = layer((first, 8), "share%d_" % first)
        for name, p in share.collect_params().items():
            value = full[name[len(share.prefix):]]
            if "experts_" in name:
                value = value[first:first + 8]
            p.set_data(mx.nd.array(value))
        total = total + share(x).asnumpy()
    arch = dict(num_experts_per_tok=4, routed_scaling_factor=1.0,
                route_epsilon=1e-6, held_experts=[0, 32])
    with jax.default_matmul_precision("highest"):
        uncut = whole(x).asnumpy()
        want = np.asarray(reference.moe(
            {"m_" + n: jnp.asarray(v) for n, v in full.items()}, "m_",
            jnp.asarray(x.asnumpy()), arch, []))
    scale = np.abs(want).max()
    assert np.abs(uncut - want).max() < 2e-5 * scale
    assert np.abs(total - want).max() < 2e-5 * scale


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
    # 97 ids are learnt by heart within the real warm-up's 71 steps
    with open(cell.manifest.find("traffic", cell.traffic_name, ".json")
              .replace(REPO, root), "w") as f:
        json.dump(dict(cell.traffic, warmup_groups=1), f)
    assert run.main(["--workload", CELL, "--seed", "3200000321",
                     "--seconds", "0.5", "--trace", "1"],
                    gate=cpu_gate, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, lines[-16:]
    metrics = result["metrics"]
    assert metrics["entry.compiles_in_window"]["value"] == 0
    assert metrics["moe.lfm2_max_expert_load_ratio"]["value"] >= 1.0
    assert 0 < metrics["step.mfu"]["value"] < 100
    for name in ("kernels.gqa_attention_roofline", "shortconv.ms_per_step",
                 "attention.gqa_ms_per_step", "moe.lfm2_routed_ms_per_step",
                 "moe.routed_ms_per_step", "moe.max_expert_load_ratio"):
        assert name not in metrics
    assert any("candidate rows rejected" in line for line in lines)
    assert any("after the warm-up" in line for line in lines)
    assert sum("groups, s (dispatches + fetch)" in line
               for line in lines) == 2            # the window and the tail
    assert sum("check after_step." in line and line.endswith("ok")
               for line in lines) == 4
    assert any("507.8 M" in line or "M parameters" in line for line in lines)
