"""Laguna-S-2.1's layers on the CPU against the plain reference
``benchmark/references/laguna_moe.py``, on seeded random weights at a small
size: the configuration file keeps the published widths; the per-head gate
(``llm.head_gate``) and the partial rotation (``gqa_qkv(rotary_dim=)``)
agree with the reference and with transformers' convention; the flash
kernels take 9 and 6 query heads a key head under a window narrower than a
block; four shares of a routed layer with its shared expert counted once
add up to the uncut layer; and the whole tiny model, trained through
``GluonTrainStep`` with Adam by the cell's own entry, agrees with the
reference through ``check.against_reference`` (logits, loss, the named
gradients, the bias's move), where wrong computations and the reference in
bfloat16 do not."""

import copy
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from benchmark.harness import check, manifest  # noqa: E402

CELL = "laguna_moe_train_seq4k"
_here = manifest.load_module(os.path.join(REPO, "tests", "benchmark",
                                          "test_laguna_metrics.py"))
tiny = _here.tiny


def _reference():
    return manifest.load_module(os.path.join(
        REPO, "benchmark", "references", "laguna_moe.py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


# ---------------------------------------------------------- the file


_PERIOD = ["full_attention"] + ["sliding_attention"] * 3


def test_the_file_keeps_every_published_width_and_states_the_share(cell):
    """Every width as published; ``reduced`` names depth, the experts held,
    the vocabulary and the per-layer lists, whose published values stand
    under ``published``; the share is 8 of 256 experts, 12,544 of 100,352
    ids and layers 0-4."""
    cfg, entry = cell.config, cell.manifest.named("configs",
                                                  cell.config_name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "num_experts", "vocab_size", "num_hidden_layers", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer", "gating_types"])
    assert entry["source"] in cfg["source"]
    widths = {"hidden_size": 3072, "head_dim": 128, "num_attention_heads": 48,
              "num_key_value_heads": 8, "intermediate_size": 12288,
              "moe_intermediate_size": 1024,
              "shared_expert_intermediate_size": 1024,
              "num_experts_per_tok": 10, "sliding_window": 512,
              "moe_routed_scaling_factor": 2.5, "mlp_only_layers": [0],
              "gating": "per-head", "tie_word_embeddings": False}
    for key, value in widths.items():
        assert cfg[key] == value, key
    pub = cfg["published"]
    assert pub["num_experts"] == 256 and pub["vocab_size"] == 100352
    assert pub["num_hidden_layers"] == len(pub["layer_types"]) == 48
    assert pub["layer_types"] == _PERIOD * 12
    assert pub["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert pub["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert pub["gating_types"] == ["per_head"] * 48
    for key in cfg["reduced"]:
        if isinstance(pub[key], list):
            assert cfg[key] == pub[key][:5], key
    assert cfg["num_experts"] * 32 == pub["num_experts"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["num_hidden_layers"] == 5
    rope = cfg["rope_parameters"]
    assert rope["full_attention"]["partial_rotary_factor"] == 0.5
    assert rope["full_attention"]["attention_factor"] == pytest.approx(
        1.4852030263919618)
    assert "32 chips share each layer" in cfg["deployment"]
    arch = cfg["architecture"]
    for key, value in arch.items():
        if key in cfg:
            assert value == cfg[key], key
    assert arch["router_outputs"] == pub["num_experts"]
    assert arch["held_experts"] == [0, cfg["num_experts"]]
    assert arch["routed_scaling_factor"] == cfg["moe_routed_scaling_factor"]
    assert arch["gating_types"] == cfg["gating_types"]
    assert arch["scoring_func"] == "sigmoid" and arch["bias_update_rate"]
    assert arch["norm_eps"] == cfg["rms_norm_eps"]
    assert arch["tie_embedding"] == cfg["tie_word_embeddings"]
    assert cfg["input"]["shape"] == [4096]
    assert len(cfg["assumed"]) >= 10
    assert any("sigmoid" in a and "bias" in a for a in cfg["assumed"])
    assert any(a.startswith("no gate on the shared expert")
               for a in cfg["assumed"])
    assert any("RMS-normalised per head" in a for a in cfg["assumed"])


def test_the_built_model_holds_811_M_parameters(cell):
    """The factory's model at the file's sizes (shapes only, nothing
    drawn): the issue's table to the parameter."""
    from mxnet_tpu.gluon.nn import LayerTypesMoELM

    net = LayerTypesMoELM(**cell.config["architecture"])
    cut = len(net.prefix)
    shapes = {n[cut:]: p.shape for n, p in net.collect_params().items()}
    assert shapes["l0_attn_q_weight"] == (48 * 128, 3072)
    assert shapes["l1_attn_q_weight"] == (72 * 128, 3072)
    assert shapes["l1_attn_gate_weight"] == (72, 3072)
    assert shapes["l4_attn_gate_weight"] == (48, 3072)
    assert shapes["l0_ffn_down_weight"] == (3072, 12288)
    assert shapes["l1_moe_shared_gate_weight"] == (1024, 3072)
    assert shapes["l1_moe_experts_up_weight"] == (8, 3072, 1024)
    assert shapes["l1_moe_router_bias"] == (256,)
    assert not any(n.startswith("l0_moe") for n in shapes)
    counters = ("held_pairs", "max_load", "router_bias")
    held = sum(int(np.prod(s)) for n, s in shapes.items()
               if not n.endswith(counters))
    kv = 2 * 1024 * 3072
    full = 2 * 48 * 128 * 3072 + kv + 48 * 3072 + 2 * 128
    window = 2 * 72 * 128 * 3072 + kv + 72 * 3072 + 2 * 128
    routed = 256 * 3072 + 9 * 3 * 1024 * 3072
    by_hand = (full + 3 * 3072 * 12288) + 3 * (window + routed) \
        + (full + routed) + 5 * 2 * 3072 + 2 * 12544 * 3072 + 3072
    assert held == by_hand
    assert "%.1f M" % (held / 1e6) == "811.0 M"
    assert "811.0 M" in cell.config["deployment"]


# --------------------------------------------------------- the operators


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


def test_the_per_head_gate_and_its_gradient():
    """``llm.head_gate``: every head's result times ``sigmoid(x W_g)`` of
    its own row of ``W_g``, value and gradients against the reference's
    gate on the same operands."""
    from mxnet_tpu.ops import llm

    reference = _reference()
    o, x, w = _rand((2, 9, 5, 4), 1), _rand((2, 5, 8), 2), _rand((9, 8), 3)
    g = _rand((2, 9, 5, 4), 4)

    def mine(o, x, w):
        return jnp.sum(llm.head_gate(o, x, w) * g)

    def plain(o, x, w):
        return jnp.sum(o * reference.head_gate({"gate_weight": w}, "", x)
                       * g)

    with jax.default_matmul_precision("highest"):
        got = llm.head_gate(o, x, w)
        want = o * (1.0 / (1.0 + np.exp(-np.einsum("bsu,hu->bhs", x, w))))[
            ..., None]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.grad(mine, argnums=(0, 1, 2))(o, x, w),
                        jax.grad(plain, argnums=(0, 1, 2))(o, x, w)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # bfloat16 in, bfloat16 out; the sigmoid and the product in float32
    assert llm.head_gate(o.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
                         w.astype(jnp.bfloat16)).dtype == jnp.bfloat16


def _yarn():
    return {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": 32, "beta_fast": 2,
            "beta_slow": 0.25, "attention_factor": 1.3,
            "partial_rotary_factor": 0.5}


def test_partial_rotary_follows_transformers_convention():
    """``gqa_qkv(rotary_dim=8)`` on heads of 16: lanes 8-15 are the normed
    heads untouched and unscaled, lanes 0-7 rotated by halves (pairs (i, i
    + 4)) by yarn's frequencies over 8 lanes (``inv_freq = theta^(-2i/8)``
    blended) times the amplitude; gradients are the reference's."""
    from mxnet_tpu.ops import llm

    reference = _reference()
    arch = {"head_dim": 16, "rope_parameters": {"full_attention": _yarn()}}
    inv_freq, amplitude = llm.rotary_frequencies(8, **_yarn())
    assert (inv_freq, amplitude) == (tuple(reference.rotary(
        arch, "full_attention")[0]), 1.3)
    # the fastest pair keeps theta^0, the slowest is divided by the factor
    assert inv_freq[0] == 1.0
    assert inv_freq[-1] == pytest.approx(10000.0 ** (-6 / 8) / 4)
    x = _rand((1, 64, 32), 5)
    wq, wk, wv = _rand((32, 32), 6), _rand((16, 32), 7), _rand((16, 32), 8)
    qn, kn = 1 + 0.1 * _rand((16,), 9), 1 + 0.1 * _rand((16,), 10)
    how = dict(inv_freq=inv_freq, amplitude=amplitude, rotary_dim=8)

    def split(w, n):
        return jnp.einsum("bsu,hdu->bhsd", x, w.reshape(n, 16, 32))

    with jax.default_matmul_precision("highest"):
        q, k, v = llm.gqa_qkv(x, wq, wk, wv, qn, kn, **how)
        normed = llm.rms_norm(split(wq, 2), qn)
        np.testing.assert_array_equal(q[..., 8:], normed[..., 8:])
        np.testing.assert_allclose(
            q, reference.rope(normed, arch, "full_attention"), rtol=1e-5,
            atol=1e-5)
        # the amplitude scales the rotated lanes: at position 0 they are
        # the normed lanes times 1.3
        np.testing.assert_allclose(q[:, :, 0, :8], 1.3 * normed[:, :, 0, :8],
                                   rtol=1e-6)
        np.testing.assert_allclose(k, reference.rope(
            llm.rms_norm(split(wk, 1), kn), arch, "full_attention"),
            rtol=1e-5, atol=1e-5)

        g = _rand((1, 2, 64, 16), 11)

        def mine(wq):
            return jnp.sum(llm.gqa_qkv(x, wq, wk, wv, qn, kn, **how)[0] * g)

        def plain(wq):
            return jnp.sum(reference.rope(llm.rms_norm(split(wq, 2), qn),
                                          arch, "full_attention") * g)

        np.testing.assert_allclose(jax.grad(mine)(wq), jax.grad(plain)(wq),
                                   rtol=1e-4, atol=1e-4)
    # a rotation of every lane (rotary_dim = d, or none) is the plain path
    full = llm.gqa_qkv(x, wq, wk, wv, qn, kn, rotary_dim=16)[0]
    np.testing.assert_array_equal(full, llm.gqa_qkv(x, wq, wk, wv, qn,
                                                    kn)[0])


@pytest.mark.parametrize("group,window", [(9, 200), (6, 256), (9, 100)])
def test_the_flash_kernels_at_groups_of_9_and_6_under_a_narrow_window(
        group, window):
    """The three kernels through the interpreter at 9 and 6 query heads a
    key head (the cell's window and full layers), a window narrower than
    the blocks of 512 (the cell: 512 under blocks of 1,024, so a query
    block's two key blocks are both cut into tiles), against
    ``mha_reference``: forward and all gradients."""
    from mxnet_tpu.ops import attention as att

    q, g = (_rand((1, group, 1024, 32), 70 + i) for i in range(2))
    k, v = (_rand((1, 1, 1024, 32), 72 + i) for i in range(2))
    assert att._masked_offsets(512, 512, window) == [0, 1]

    def with_grads(fn, **how):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, causal=True,
                                              window=window, **how), q, k, v)
        return (out,) + vjp(g)

    got = with_grads(att.flash_attention, interpret=True, block_q=512,
                     block_k=512)
    with jax.default_matmul_precision("highest"):
        want = with_grads(att.mha_reference)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, w in zip(got[1:], want[1:]):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)


def test_four_shares_of_a_routed_layer_with_its_shared_expert_sum_to_it():
    """Four chips hold experts 0-3, 4-7, 8-11 and 12-15 of 16, routed by
    sigmoid scores with a selection bias, each with the replicated shared
    expert: their routed parts, the shared expert counted once, add up to
    the uncut layer, which is the reference's with all 16 held."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.nn import RoutedExperts

    reference = _reference()

    def layer(held, prefix):
        mx.random.seed(5)
        blk = RoutedExperts(32, 16, 16, 3, held_experts=held, weight_std=0.3,
                            shared=24, route_epsilon=1e-20,
                            routed_scaling_factor=2.5, prefix=prefix)
        blk.initialize(ctx=mx.cpu())
        return blk

    whole = layer((0, 16), "whole_")
    full = {n[len("whole_"):]: p.data().asnumpy()
            for n, p in whole.collect_params().items()}
    assert full["shared_gate_weight"].shape == (24, 32)
    x = mx.nd.array(np.random.RandomState(2).randn(2, 32, 32)
                    .astype(np.float32))
    total = 0
    for first in (0, 4, 8, 12):
        share = layer((first, 4), "share%d_" % first)
        for name, p in share.collect_params().items():
            value = full[name[len(share.prefix):]]
            if "experts_" in name:
                value = value[first:first + 4]
            p.set_data(mx.nd.array(value))
        total = total + share(x).asnumpy() - share.shared(
            mx.nd.reshape(x, shape=(-1, 32))).asnumpy().reshape(2, 32, 32)
    arch = dict(num_experts_per_tok=3, route_epsilon=1e-20, router_outputs=16,
                held_experts=[0, 16], routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        uncut = whole(x).asnumpy()
        shared = whole.shared(mx.nd.reshape(x, shape=(-1, 32))).asnumpy()
        want = np.asarray(reference.moe(
            {"m_" + n: jnp.asarray(v) for n, v in full.items()}, "m_",
            jnp.asarray(x.asnumpy()), arch, []))
    total = total + shared.reshape(2, 32, 32)
    scale = np.abs(want).max()
    assert np.abs(uncut - want).max() < 2e-5 * scale
    assert np.abs(total - want).max() < 2e-5 * scale


# ------------------------------------------------- system against reference


class Lines(list):
    def __call__(self, message):
        self.append(message)


@pytest.fixture(scope="module")
def session_and_system(cell):
    small = copy.copy(cell)
    small.config = tiny(cell.config)
    reference = cell.reference()
    ctx = run.Context(small, seed=3900000123, devices=jax.devices()[:1])
    ctx.say = Lines()
    session = small.entry().build(ctx)
    return small, reference, session, session.system_outputs(reference)


def test_system_agrees_with_its_plain_reference(session_and_system):
    """The tiny model through the cell's entry: ``GluonTrainStep`` with
    Adam, in float32; logits, loss, six gradients of the check rows, the
    timed rows (layer 0 whole and a window layer alone) and the two routed
    layers' state after the step, each within its limit."""
    small, reference, session, system = session_and_system
    assert any("candidate rows rejected" in line for line in session.ctx.say)
    assert system["x"].shape == (2, 64)
    assert system["logits"].shape == (2, 64, 97)
    assert system["y"].shape == (1, 64)
    grads = system["gradients"]
    assert grads["dense_prefix.hidden"].shape == (1, 64, 32)
    assert grads["swa_timed.out"].shape == (1, 64, 32)
    assert grads["swa_timed.l1_attn_gate_weight"].shape == (9, 32)
    assert grads["dense_prefix.l0_attn_gate_weight"].shape == (6, 32)
    assert grads["l1_moe_shared_down_weight"].shape == (32, 24)
    moved = grads["after_step.l1_moe_router_bias"]
    assert moved.shape == (16,)
    np.testing.assert_allclose(moved, np.rint(moved), atol=1e-3)
    assert set(np.rint(moved).tolist()) <= {-1.0, 0.0, 1.0}
    lines = Lines()
    assert check.against_reference(reference, small.config, system, lines), \
        "\n".join(lines)
    assert len(lines) == 2 + len(small.config["check_gradients"])


def _no_gate(reference, config, monkeypatch):
    monkeypatch.setattr(reference, "head_gate",
                        lambda p, pre, x: jnp.ones((1, 1, 1, 1)))


def _one_gate_for_every_head(reference, config, monkeypatch):
    plain = reference.head_gate
    monkeypatch.setattr(reference, "head_gate", lambda p, pre, x: jnp.mean(
        plain(p, pre, x), axis=1, keepdims=True))


def _every_lane_rotated_in_the_full_layers(reference, config, monkeypatch):
    config["architecture"]["rope_parameters"]["full_attention"][
        "partial_rotary_factor"] = 1


def _the_last_lanes_rotated(reference, config, monkeypatch):
    """The pass-through lanes first, as ``_rotary``'s ``start`` has it."""
    plain = reference.rope

    def rope(x, arch, kind):
        lanes = reference.rotary_dim(arch, kind)
        d = x.shape[-1]
        flipped = jnp.concatenate([x[..., d - lanes:], x[..., :d - lanes]],
                                  axis=-1)
        out = plain(flipped, arch, kind)
        return jnp.concatenate([out[..., lanes:], out[..., :lanes]], axis=-1)

    monkeypatch.setattr(reference, "rope", rope)


def _the_amplitude_on_every_lane(reference, config, monkeypatch):
    plain = reference.rope

    def rope(x, arch, kind):
        amplitude = reference.rotary(arch, kind)[1]
        lanes = reference.rotary_dim(arch, kind)
        out = plain(x, arch, kind)
        return jnp.concatenate([out[..., :lanes], amplitude
                                * out[..., lanes:]], axis=-1)

    monkeypatch.setattr(reference, "rope", rope)


def _no_shared_expert(reference, config, monkeypatch):
    monkeypatch.setattr(reference, "moe", reference.lfm2.moe)


def _a_window_one_key_wider(reference, config, monkeypatch):
    config["architecture"]["sliding_window"] += 1


def _reading_the_key_head_of_another_group(reference, config, monkeypatch):
    """Two key heads where the layer has one: a group of 9 read as two
    groups, the second from a key head moved by one position."""
    plain = reference._qkv

    def qkv(p, pre, x, arch, i):
        q, k, v = plain(p, pre, x, arch, i)
        half = q.shape[1] // 2
        return q, k, jnp.concatenate(
            [v[:, :half], jnp.roll(v[:, half:], 1, axis=2)], axis=1)

    monkeypatch.setattr(reference, "_qkv", qkv)


WRONG = [_no_gate, _one_gate_for_every_head,
         _every_lane_rotated_in_the_full_layers, _the_last_lanes_rotated,
         _the_amplitude_on_every_lane, _no_shared_expert,
         _a_window_one_key_wider, _reading_the_key_head_of_another_group]


@pytest.mark.parametrize("wrong", WRONG, ids=[f.__name__[1:] for f in WRONG])
def test_a_wrong_computation_fails_the_check(wrong, session_and_system,
                                             monkeypatch):
    """The comparison is symmetric: a reference without the gate or with
    one gate for all heads, rotating every lane or the last ones, scaling
    the lanes it passes through, without the shared expert, with a window
    one key wider or reading another key head stands for a system that
    does, against the same limits."""
    small, reference, _, system = session_and_system
    config = copy.deepcopy(small.config)
    wrong(reference, config, monkeypatch)
    lines = Lines()
    assert not check.against_reference(reference, config, system, lines)
    assert any(line.endswith("FAIL") for line in lines)


def test_the_reference_computed_in_bfloat16_fails_every_floor(
        session_and_system):
    """The reference in the nearest precision below the stated one, handed
    to the comparison as if a system had computed it: logits, loss, the
    gradients and the timed rows each fall outside their limit."""
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
    for kind in ("logits", "loss", "l2_attn_gate_weight",
                 "l3_moe_router_weight", "dense_prefix.hidden",
                 "dense_prefix.l0_attn_gate_weight", "swa_timed.out",
                 "swa_timed.l1_attn_gate_weight"):
        assert kind in failed, (kind, lines)


def test_the_step_trains_the_bias_by_its_rule_and_warms_up(
        session_and_system):
    """The step's state beside the weights: every routed layer's selection
    bias and two counters, no gradient for them; the warm-up runs the
    traffic's groups and says the loads after each."""
    small, _, session, _ = session_and_system
    traffic = small.traffic
    session.ctx.traffic = dict(traffic, warmup_groups=2)
    try:
        session.warm_up()
    finally:
        session.ctx.traffic = traffic
    aux = [p.name for p in session.step.aux]
    assert len(aux) == 4 * 3
    assert sum(n.endswith("router_bias") for n in aux) == 4
    assert not any("router_bias" in p.name for p in session.step.trainable)
    said = [line for line in session.ctx.say if "warm-up group" in line]
    assert len(said) == 2
    assert session.read_counters()["l4_moe_max_load"] >= 1.0
