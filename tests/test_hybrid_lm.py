"""The short-convolution / grouped-query attention blocks of
``gluon/nn/hybrid_lm.py`` and the one decoder block and model classes of
``gluon/nn/mla_moe.py`` they are built from, against the plain reference of
the benchmark (``benchmark/references/lfm2_moe.py``), at a tiny size on the
CPU; and a tied weight through ``GluonTrainStep``: one leaf, two uses."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer
from mxnet_tpu.gluon.nn import (ConvAttentionMoELM, DecoderBlock, GatedFFN,
                                GQAttention, MLAttention, NextTokenLoss,
                                RoutedExperts, ShortConv)
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "references"))

import lfm2_moe as reference  # noqa: E402

ARCH = dict(
    vocab_size=61, hidden_size=32,
    layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
    intermediate_size=48, moe_intermediate_size=16, router_outputs=8,
    held_experts=[2, 4], num_experts_per_tok=2, routed_scaling_factor=1.0,
    route_epsilon=1e-6, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, rope_theta=1e6, norm_eps=1e-5)


def build(seed=3, **changed):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = ConvAttentionMoELM(weight_std=0.3, **dict(ARCH, **changed))
    net.initialize(ctx=mx.cpu())
    return net


def named(net):
    cut = len(net.prefix)
    return {n[cut:]: np.asarray(p.data().data_jax)
            for n, p in net.collect_params().items()}


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, 61, (2, 16)).astype(np.int32)


def _close(got, want, tol=2e-5):
    want = np.asarray(want)
    assert np.shape(got) == want.shape
    assert np.abs(np.asarray(got) - want).max() <= tol * np.abs(want).max()


def _hidden(seed):
    return np.random.RandomState(seed).randn(2, 16, 32).astype(np.float32)


PIECES = {
    "short_conv": (0, lambda blk: blk.mixer, lambda p, x: reference.short_conv(
        p, "l0_conv_", x, ARCH)),
    "attention": (1, lambda blk: blk.mixer, lambda p, x: reference.attention(
        p, "l1_attn_", x, ARCH)),
    "attention_in_blocks": (1, lambda blk: blk.mixer,
                            lambda p, x: reference.attention_in_blocks(
                                p, "l1_attn_", x, ARCH, 4)),
    "experts_without_a_shared_one": (
        2, lambda blk: blk.ffn,
        lambda p, x: reference.moe(p, "l2_moe_", x, ARCH, [])),
}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_piece_against_the_reference(net, piece):
    layer, part, plain = PIECES[piece]
    x = _hidden(layer)
    p = {n: jnp.asarray(v) for n, v in named(net).items()}
    with jax.default_matmul_precision("highest"):
        _close(part(net.blocks[layer])(mx.nd.array(x)).asnumpy(),
               plain(p, jnp.asarray(x)))


def test_the_model_is_the_references(net, tokens):
    """Logits through the tied head, and the layers in the order
    ``layer_types`` gives; one parameter serves embedding and head."""
    names = named(net)
    assert "head_weight" not in names and net.head.weight is net.embed.weight
    assert [type(b.mixer).__name__ for b in net.blocks] \
        == ["ShortConv", "GQAttention", "ShortConv"]
    assert [type(b.ffn).__name__ for b in net.blocks] \
        == ["GatedFFN", "RoutedExperts", "RoutedExperts"]
    assert not any("shared" in n for n in names)
    with jax.default_matmul_precision("highest"):
        got = net.head(net(mx.nd.array(tokens, dtype="int32"))).asnumpy()
        want = reference.forward(
            {n: jnp.asarray(v) for n, v in names.items()},
            jnp.asarray(tokens, jnp.float32), ARCH)
    _close(got, want)


def test_one_block_class_takes_any_mixer_and_feed_forward():
    """``DecoderBlock`` over latent attention with a dense feed-forward and
    over a short convolution with routed experts: the same class, the
    parameters named by what it was given."""
    def mla():
        return MLAttention(32, num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                           qk_nope_head_dim=8, qk_rope_head_dim=4,
                           v_head_dim=8, prefix="attn_")

    one = DecoderBlock(32, mla, lambda: GatedFFN(32, 48, prefix="ffn_"),
                       prefix="a_")
    two = DecoderBlock(
        32, lambda: ShortConv(32, prefix="conv_"),
        lambda: RoutedExperts(32, 16, 8, 2, shared=False, prefix="moe_"),
        prefix="b_")
    assert {"a_ln1_weight", "a_attn_qa_weight", "a_ffn_down_weight"} \
        <= set(one.collect_params().keys())
    assert {"b_ln2_weight", "b_conv_in_weight", "b_moe_router_bias"} \
        <= set(two.collect_params().keys())
    for blk in (one, two):
        blk.initialize(ctx=mx.cpu())
        assert blk(mx.nd.array(_hidden(4))).shape == (2, 16, 32)
    assert GQAttention(32, 4, 2, prefix="g_").k_weight.shape == (16, 32)


def _reference_gradients(params, tokens):
    trained = {n: jnp.asarray(v) for n, v in params.items()
               if not reference.not_trained(n)}
    rest = {n: jnp.asarray(v) for n, v in params.items()
            if reference.not_trained(n)}
    x = jnp.asarray(tokens, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jax.grad(lambda t: reference.loss(
            dict(t, **rest), x, jnp.asarray(tokens), ARCH))(trained)


def test_a_tied_weight_is_one_leaf_whose_gradient_is_the_sum(tokens):
    """Through ``GluonTrainStep``: the embedding's and the head's use of the
    one parameter both reach its gradient (the reference's sum; the
    embedding's use alone is another number), the step holds it once, and
    one Adam step from zero state moves it once, by Adam's rule on that
    sum: ``m = (1 - b1) g``, ``W -= lr sqrt(1 - b2) / (1 - b1) m / (sqrt(v)
    + eps)``."""
    net = build(seed=11)
    before = named(net)
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    lr, b1, b2, eps = 1e-3, 0.9, 0.95, 1e-8
    step = GluonTrainStep(
        net, NextTokenLoss(net.head), mesh=mesh,
        optimizer=optimizer.Adam(learning_rate=lr, beta1=b1, beta2=b2,
                                 epsilon=eps, wd=0.0))
    cut = len(net.prefix)
    names = [p.name[cut:] for p in step.trainable]
    assert names.count("embed_weight") == 1 and "head_weight" not in names
    assert len(step.train_vals) == len(names)
    with jax.default_matmul_precision("highest"):
        step(tokens, tokens)
    at = names.index("embed_weight")
    g = np.asarray(step.opt_state[2 * at]) / (1 - b1)      # first moment
    want = _reference_gradients(before, tokens)["embed_weight"]
    _close(g, want, tol=1e-4)

    # the embedding's use alone: the head reads a constant copy
    with jax.default_matmul_precision("highest"):
        p = {n: jnp.asarray(v) for n, v in before.items()}
        x = jnp.asarray(tokens, jnp.float32)

        def embedding_only(w):
            h, _ = reference.hidden_states(dict(p, embed_weight=w),
                                           jnp.asarray(tokens), ARCH)
            logits = reference.rms_norm(h, p["norm_weight"], 1e-5) \
                @ p["embed_weight"].T
            return reference.cross_entropy(
                logits, jnp.roll(jnp.asarray(tokens), -1, axis=1), 15)

        part = np.asarray(jax.grad(embedding_only)(p["embed_weight"]))
    assert np.abs(part - np.asarray(want)).max() \
        > 0.1 * np.abs(np.asarray(want)).max()
    del x

    moved = np.asarray(step.train_vals[at]) - before["embed_weight"]
    m, v = (1 - b1) * g, (1 - b2) * g * g
    rule = -lr * np.sqrt(1 - b2) / (1 - b1) * m / (np.sqrt(v) + eps)
    touched = np.abs(g) > 1e-6 * np.abs(g).max()
    assert np.abs(moved - rule)[touched].max() < 2e-3 * np.abs(rule).max()


def test_the_step_recomputes_the_blocks_and_keeps_the_counters(tokens):
    """The whole model through ``GluonTrainStep`` in bfloat16: the loss
    falls, and every routed layer's counters leave the step with its
    state: pairs on the held experts out of tokens x k, the busiest held
    expert over their mean."""
    net = build(seed=12)
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = GluonTrainStep(
        net, NextTokenLoss(net.head), mesh=mesh, compute_dtype="bfloat16",
        optimizer=optimizer.Adam(learning_rate=1e-2))
    losses = [float(step(tokens, tokens)) for _ in range(4)]
    assert losses[-1] < losses[0]
    cut = len(net.prefix)
    aux = {p.name[cut:]: float(np.asarray(v)[0])
           for p, v in zip(step.aux, step.aux_vals)
           if p.name.endswith(("held_pairs", "max_load"))}
    assert sorted(aux) == ["l1_moe_held_pairs", "l1_moe_max_load",
                           "l2_moe_held_pairs", "l2_moe_max_load"]
    for layer in ("l1", "l2"):
        assert 0 < aux[layer + "_moe_held_pairs"] <= tokens.size * 2
        assert 1.0 <= aux[layer + "_moe_max_load"] <= 4.0


def _loads(layer, x):
    """Choices per expert of ``layer``'s router on ``x``, from the op."""
    ids, _ = mx.nd.contrib.moe_route(
        x, layer.router_weight.data(), layer.router_bias.data(), k=2)
    return np.bincount(ids.asnumpy().ravel().astype(np.int64), minlength=8)


def test_the_balancing_rule_moves_the_selection_bias_and_levels_the_loads():
    """``RoutedExperts(bias_update_rate=)``: a training forward ends with
    ``bias -= rate * sign(choices - their mean)`` over all of the router's
    experts (held or not); repeated on one batch the loads level; at rate 0
    (the default) and outside training the bias stays what it was."""
    mx.random.seed(21)
    x = mx.nd.array(np.random.RandomState(21).normal(size=(256, 16)))
    kwargs = dict(units=16, hidden_size=8, num_experts=8, experts_per_token=2,
                  held_experts=(2, 4), weight_std=0.5, shared=False)
    layer = RoutedExperts(bias_update_rate=0.01, prefix="moved_", **kwargs)
    fixed = RoutedExperts(prefix="fixed_", **kwargs)
    for each in (layer, fixed):
        each.initialize(ctx=mx.cpu())
    drawn = fixed.router_bias.data().asnumpy()
    with mx.autograd.record():
        fixed(x)
    layer(x)                                    # not training: no update
    assert np.array_equal(fixed.router_bias.data().asnumpy(), drawn)
    before, loads = layer.router_bias.data().asnumpy(), _loads(layer, x)
    assert loads.std() > 0.15 * loads.mean()
    with mx.autograd.record():
        layer(x)
    np.testing.assert_allclose(
        layer.router_bias.data().asnumpy(),
        before - 0.01 * np.sign(loads - loads.mean()), atol=1e-7)
    for _ in range(60):
        with mx.autograd.record():
            layer(x)
    assert _loads(layer, x).std() < 0.08 * loads.mean()
