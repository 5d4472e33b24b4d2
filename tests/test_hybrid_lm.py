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
from mxnet_tpu.gluon.nn import (LayerTypesMoELM, DecoderBlock, GatedFFN,
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
    net = LayerTypesMoELM(weight_std=0.3, **dict(ARCH, **changed))
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


# ------------------- windows, rotary scaling, a side term for the loss (PR 34)


def test_the_old_name_builds_the_same_class():
    from mxnet_tpu.gluon.nn import ConvAttentionMoELM
    assert ConvAttentionMoELM is LayerTypesMoELM


WINDOWED = dict(
    ARCH, layer_types=["sliding_attention", "full_attention"],
    num_dense_layers=0, head_dim=16, sliding_window=4, tie_embedding=False,
    scoring_func="softmax", router_aux_loss_coef=0.01, route_epsilon=0.0,
    rope_parameters={
        "full_attention": dict(rope_type="yarn", rope_theta=1e4, factor=4,
                               original_max_position_embeddings=8,
                               beta_fast=2, beta_slow=0.25),
        "sliding_attention": dict(rope_type="default", rope_theta=1e4)})


def test_layer_types_give_windows_and_rotary_scaling_to_their_layers():
    """``"sliding_attention"`` builds a :class:`GQAttention` with the
    model's window and its type's rotary parameters; a window layer's
    output at a position does not move with a token further back than the
    window, a full layer's does; ``head_dim`` is the layers' own."""
    net = build(seed=4, **WINDOWED)
    window, full = (blk.mixer for blk in net.blocks)
    assert window._window == 4 and full._window is None
    assert window.q_weight.shape == (4 * 16, 32)
    assert window._rotary["amplitude"] == 1.0
    assert full._rotary["amplitude"] == pytest.approx(0.1 * np.log(4) + 1)
    assert full._rotary["inv_freq"] != window._rotary["inv_freq"]
    assert not any("router_bias" in n or "ffn_" in n for n in named(net))
    x = _hidden(5)
    moved = x.copy()
    moved[:, 3] += 1.0                      # position 3: seen up to 6
    for layer, reach in ((window, 7), (full, 16)):
        a = layer(mx.nd.array(x)).asnumpy()
        b = layer(mx.nd.array(moved)).asnumpy()
        changed = np.abs(a - b).max(axis=(0, 2)) > 1e-6
        assert list(np.flatnonzero(changed)) == list(range(3, reach))


class _SideTermFFN(mx.gluon.HybridBlock):
    """A feed-forward that hands the loss a term of its own: any block
    may."""

    def __init__(self, units, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, units),
                init=mx.initializer.Normal(0.3))

    def hybrid_forward(self, F, x, weight):
        y = F.FullyConnected(x, weight, None, no_bias=True,
                             num_hidden=weight.shape[0], flatten=False)
        if not mx.autograd.is_training():
            return y
        return y, 0.5 * F.mean(F.square(y))


def test_a_blocks_side_term_reaches_the_loss_through_the_recomputation():
    """A feed-forward's ``(y, term)`` leaves its block, recomputed in the
    backward pass, as a value of the step program; the model sums the
    blocks' terms, ``NextTokenLoss`` adds the sum to every row, and the
    gradient of the term arrives: against ``jax.grad`` of the same
    computation written out."""
    from mxnet_tpu.gluon.nn import DecoderLM

    mx.random.seed(9)
    np.random.seed(9)
    blocks = [(lambda: GQAttention(32, 4, 2, weight_std=0.3, prefix="attn_"),
               lambda: _SideTermFFN(32, prefix="ffn_"))] * 2
    net = DecoderLM(61, 32, blocks, weight_std=0.3, prefix="side_")
    net.initialize(ctx=mx.cpu())
    tokens = np.random.RandomState(1).randint(0, 61, (2, 16)).astype(np.int32)
    assert not isinstance(net(mx.nd.array(tokens, dtype="int32")), tuple)
    with mx.autograd.train_mode():
        hidden, side = net(mx.nd.array(tokens, dtype="int32"))
    assert hidden.shape == (2, 16, 32) and side.shape == ()
    step = GluonTrainStep(
        net, NextTokenLoss(net.head),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]),
        optimizer=optimizer.Adam(learning_rate=1e-3, beta1=0.9))
    params = named(net)
    with jax.default_matmul_precision("highest"):
        loss = float(step(tokens, tokens))
    cut = len(net.prefix)
    names = [p.name[cut:] for p in step.trainable]
    moment = dict(zip(names, step.opt_state[0::2]))

    ref = sys.modules["lfm2_moe"]
    arch = dict(ARCH, layer_types=["full_attention"] * 2, norm_eps=1e-6,
                rope_theta=1e4)

    def by_hand(p):
        h, side = p["embed_weight"][tokens], 0.0
        for i in range(2):
            pre = "l%d_" % i
            h = h + ref.attention(p, pre + "attn_", ref.rms_norm(
                h, p[pre + "ln1_weight"], 1e-6), arch)
            y = ref.rms_norm(h, p[pre + "ln2_weight"], 1e-6) \
                @ p[pre + "ffn_weight"].T
            h, side = h + y, side + 0.5 * jnp.mean(jnp.square(y))
        logits = ref.rms_norm(h, p["norm_weight"], 1e-6) @ p["head_weight"].T
        return ref.cross_entropy(logits, jnp.roll(tokens, -1, axis=1),
                                 15) + side, side

    with jax.default_matmul_precision("highest"):
        (want, side_value), grads = jax.value_and_grad(
            by_hand, has_aux=True)({n: jnp.asarray(v)
                                    for n, v in params.items()})
    assert float(side_value) > 0.05
    assert loss == pytest.approx(float(want), rel=1e-5)
    for name in ("l0_ffn_weight", "l1_ffn_weight", "l0_attn_q_weight",
                 "embed_weight"):
        _close(np.asarray(moment[name]) / 0.1, grads[name], 1e-4)
    # and the term is in those gradients: without it they differ
    bare = jax.grad(lambda p: by_hand(p)[0] - by_hand(p)[1])(
        {n: jnp.asarray(v) for n, v in params.items()})
    assert np.abs(np.asarray(bare["l1_ffn_weight"])
                  - np.asarray(grads["l1_ffn_weight"])).max() \
        > 0.05 * np.abs(np.asarray(grads["l1_ffn_weight"])).max()


def test_a_softmax_router_trains_under_its_balancing_loss(tokens):
    """The windowed model through ``GluonTrainStep``: no ``router_bias``
    leaf in the step's state, ``balance_term`` beside the two counters, the
    fetched loss above the bare cross-entropy by the weighted terms."""
    net = build(seed=14, **WINDOWED)
    step = GluonTrainStep(
        net, NextTokenLoss(net.head),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]),
        optimizer=optimizer.Adam(learning_rate=1e-3))
    hidden = net(mx.nd.array(tokens, dtype="int32"))
    bare = float(NextTokenLoss(net.head)(
        hidden, mx.nd.array(tokens, dtype="int32")).mean().asnumpy())
    loss = float(step(tokens, tokens))
    cut = len(net.prefix)
    aux = {p.name[cut:]: float(np.asarray(v)[0])
           for p, v in zip(step.aux, step.aux_vals)}
    assert sorted(aux) == sorted(
        "l%d_moe_%s" % (i, n) for i in (0, 1)
        for n in ("held_pairs", "max_load", "balance_term"))
    assert not any("router_bias" in p.name for p in step.trainable)
    terms = aux["l0_moe_balance_term"] + aux["l1_moe_balance_term"]
    assert 2.0 <= terms < 4.0
    assert loss - bare == pytest.approx(0.01 * terms, rel=2e-3)
    with pytest.raises(ValueError, match="selection bias"):
        RoutedExperts(32, 16, 8, 2, scoring="softmax", bias_update_rate=0.1)


def test_a_router_that_its_balancing_term_alone_trains(tokens):
    """``router_trained_by="balance"``: after one step of Adam with weight
    decay a router's first moment is the balancing terms' gradient and
    nothing else (no gradient of the task loss through the routing weights,
    no decay: ``GluonTrainStep`` honours the parameter's ``wd_mult``), and
    every other weight's is the whole loss's gradient plus the decay;
    against ``jax.grad`` of the benchmark's plain reference."""
    import mellum2_moe

    net = build(seed=15, **dict(WINDOWED, router_trained_by="balance"))
    assert [p.wd_mult for n, p in net.collect_params().items()
            if n.endswith("router_weight")] == [0.0, 0.0]
    step = GluonTrainStep(
        net, NextTokenLoss(net.head),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]),
        optimizer=optimizer.Adam(learning_rate=1e-3, beta1=0.9, wd=0.1))
    params = {n: jnp.asarray(v) for n, v in named(net).items()
              if not mellum2_moe.not_trained(n)}
    with jax.default_matmul_precision("highest"):
        step(tokens, tokens)
    cut = len(net.prefix)
    moment = dict(zip([p.name[cut:] for p in step.trainable],
                      step.opt_state[0::2]))
    arch = dict(WINDOWED, router_trained_by="balance")
    arch["rope_parameters"] = dict(
        arch["rope_parameters"], full_attention=dict(
            arch["rope_parameters"]["full_attention"],
            attention_factor=0.1 * np.log(4) + 1))
    ids = jnp.asarray(tokens)

    def terms(p):
        return mellum2_moe.balancing_loss(
            mellum2_moe.hidden_states(p, ids, arch)[1], arch)

    with jax.default_matmul_precision("highest"):
        whole = jax.grad(lambda p: mellum2_moe.loss(
            p, ids.astype(jnp.float32), ids, arch))(params)
        alone = jax.grad(terms)(params)
        trained_by_the_loss = jax.grad(lambda p: mellum2_moe.loss(
            p, ids.astype(jnp.float32), ids,
            dict(arch, router_trained_by="loss")))(params)
    for name in ("l0_moe_router_weight", "l1_moe_router_weight"):
        got = np.asarray(moment[name]) / 0.1
        _close(got, whole[name], 1e-4)
        # the last router's gradient is its own term's; the first also
        # carries the second's, through the stream
        assert np.abs(np.asarray(whole[name])).max() > 0
        assert np.abs(np.asarray(trained_by_the_loss[name]) - got).max() \
            > 10 * np.abs(got).max()
    _close(np.asarray(moment["l1_moe_router_weight"]) / 0.1,
           alone["l1_moe_router_weight"], 1e-4)
    for name in ("l0_moe_experts_up_weight", "l1_attn_q_weight",
                 "embed_weight"):
        _close(np.asarray(moment[name]) / 0.1 - 0.1 * np.asarray(params[name]),
               whole[name], 1e-4)
    for how in (dict(router_trained_by="balance"),
                dict(router_trained_by="task", balance_loss_weight=0.01)):
        with pytest.raises(ValueError, match="router_trained_by"):
            RoutedExperts(32, 16, 8, 2, scoring="softmax", **how)
