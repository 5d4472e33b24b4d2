"""The latent-attention / mixture-of-experts blocks of
``gluon/nn/mla_moe.py`` against the plain reference of the benchmark
(``benchmark/references/joyai_llm_flash.py``), at a tiny size on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.nn import (MLAMoELM, MLAttention, MultiTokenLoss,
                                RoutedExperts)
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "references"))

import joyai_llm_flash as reference  # noqa: E402

ARCH = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=2,
    first_k_dense_replace=1, intermediate_size=48, moe_intermediate_size=16,
    router_outputs=16, num_experts_per_tok=4, num_attention_heads=2,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, held_experts=[4, 8], routed_scaling_factor=2.5,
    rope_theta=32e6, rms_norm_eps=1e-6)
REF_ARCH = dict(ARCH, mtp_loss_weight=0.3)


def build(seed=3, **changed):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = MLAMoELM(weight_std=0.3, **dict(ARCH, **changed))
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
    return np.random.RandomState(0).randint(0, 97, (2, 16)).astype(np.int32)


def _close(got, want, tol=2e-5):
    want = np.asarray(want)
    assert np.abs(np.asarray(got) - want).max() <= tol * np.abs(want).max()


def _hidden(seed=1):
    return np.random.RandomState(seed).randn(2, 16, 32).astype(np.float32)


def _mla(net, p, tokens):
    x = _hidden()
    return (net.blocks[1].mixer(mx.nd.array(x)).asnumpy(),
            reference.mla(p, "l1_attn_", jnp.asarray(x), REF_ARCH))


def _moe(net, p, tokens):
    x = _hidden()
    return (net.blocks[1].ffn(mx.nd.array(x)).asnumpy(),
            reference.moe(p, "l1_moe_", jnp.asarray(x), REF_ARCH, []))


def _dense_block(net, p, tokens):
    x = _hidden()
    return (net.blocks[0](mx.nd.array(x)).asnumpy(),
            reference.block(p, "l0_", jnp.asarray(x), REF_ARCH, True, []))


def _routed_block(net, p, tokens):
    x = _hidden()
    return (net.mtp_blk(mx.nd.array(x)).asnumpy(),
            reference.block(p, "mtp_blk_", jnp.asarray(x), REF_ARCH, False,
                            []))


def _both_heads(net, p, tokens):
    main, mtp = net(mx.nd.array(tokens, dtype="int32"))
    got = np.stack([net.head(main).asnumpy(), net.head(mtp).asnumpy()])
    return got, reference.forward(p, tokens.astype(np.float32), REF_ARCH)


def _mtp_loss(net, p, tokens):
    ids = mx.nd.array(tokens, dtype="int32")
    rows = MultiTokenLoss(net.head, 0.3)(net(ids), ids).asnumpy()
    assert rows.shape == (2,)
    return rows.mean(), reference.loss(p, tokens.astype(np.float32),
                                       jnp.asarray(tokens), REF_ARCH)


PIECES = [_mla, _moe, _dense_block, _routed_block, _both_heads, _mtp_loss]


@pytest.mark.parametrize("piece", PIECES, ids=[f.__name__[1:] for f in PIECES])
def test_piece_against_the_reference(piece, net, tokens):
    with jax.default_matmul_precision("highest"):
        got, want = piece(net, named(net), tokens)
    _close(got, want)


# ------------------------------- latent attention as it was before PR 31

MLA_SIZES = dict(units=32, num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                 rope_theta=32e6)
# the parameters of a layer saved by PR 28, by structural name
MLA_STATE = {"qa_weight": (24, 32), "qb_weight": (2 * (8 + 4), 24),
             "kva_weight": (16 + 4, 32), "kvb_weight": (2 * (8 + 8), 16),
             "o_weight": (32, 2 * 8), "qnorm_weight": (24,),
             "kvnorm_weight": (16,)}


def _strided_rope(x, theta):
    seq, dim = x.shape[-2:]
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(seq)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _mla_as_it_was(p, x, heads=2, nope=8, rot=4, vd=8, rank=16, theta=32e6):
    """The formulation ``MLAttention`` had up to PR 30, kept as the plain
    reference: one product per projection, heads split by reshape and
    transpose of its result, ``v`` sliced from ``kv``, ``q`` and ``k``
    concatenated per head, the rotary key broadcast to the heads."""
    from mxnet_tpu.ops.attention import mha_reference
    from mxnet_tpu.ops.llm import rms_norm

    def by_head(d, size):
        return d.reshape(d.shape[0], d.shape[1], heads, size).transpose(
            0, 2, 1, 3)

    c_q = rms_norm(x @ p["qa_weight"].T, p["qnorm_weight"])
    q = by_head(c_q @ p["qb_weight"].T, nope + rot)
    kva = x @ p["kva_weight"].T
    c_kv = rms_norm(kva[..., :rank], p["kvnorm_weight"])
    k_r = _strided_rope(kva[..., rank:], theta)
    kv = by_head(c_kv @ p["kvb_weight"].T, nope + vd)
    q = jnp.concatenate([q[..., :nope], _strided_rope(q[..., nope:], theta)],
                        -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None], kv.shape[:3] + (rot,))], -1)
    o = mha_reference(q, k, kv[..., nope:], causal=True,
                      sm_scale=(nope + rot) ** -0.5)
    o = o.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], heads * vd)
    return o @ p["o_weight"].T


def _mla_layer(tmp_path):
    """A layer whose parameters come from a file with PR 28's names and
    shapes."""
    rs = np.random.RandomState(11)
    state = {name: (rs.randn(*shape) * 0.3 + (len(shape) == 1)).astype(
        np.float32) for name, shape in MLA_STATE.items()}
    mx.nd.save(str(tmp_path / "pr28.params"),
               {name: mx.nd.array(value) for name, value in state.items()})
    layer = MLAttention(prefix="attn_", **MLA_SIZES)
    layer.load_parameters(str(tmp_path / "pr28.params"), ctx=mx.cpu())
    return layer, state


MLA_COMPARED = ["output", "input"] + sorted(MLA_STATE)


@pytest.fixture(scope="module")
def mla_both(tmp_path_factory):
    """{name: (got, want)}: the layer's output, its input's gradient and
    its seven parameters' gradients, today's and as it was."""
    layer, state = _mla_layer(tmp_path_factory.mktemp("mla"))
    assert {n[len(layer.prefix):]: p.shape
            for n, p in layer.collect_params().items()} == MLA_STATE
    x = _hidden(4)
    g = np.random.RandomState(12).randn(2, 16, 32).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        data = mx.nd.array(x)
        data.attach_grad()
        with mx.autograd.record():
            out = layer(data)
        out.backward(mx.nd.array(g))
        want_out, vjp = jax.vjp(_mla_as_it_was, {
            n: jnp.asarray(v) for n, v in state.items()}, jnp.asarray(x))
        want_params, want_x = vjp(jnp.asarray(g))
    both = {"output": (out.asnumpy(), want_out),
            "input": (data.grad.asnumpy(), want_x)}
    for name, param in layer.collect_params().items():
        name = name[len(layer.prefix):]
        both[name] = (param.grad().asnumpy(), want_params[name])
    return both


@pytest.mark.parametrize("name", MLA_COMPARED)
def test_latent_attention_is_what_it_was_before_pr_31(mla_both, name):
    """Heads split on the weights, the rotation by a permutation, ``q``
    and ``k`` assembled once: the same function of the same parameters
    (``ops/llm.py::mla_qkv``, ``mla_out``), to float32 rounding."""
    got, want = mla_both[name]
    assert got.shape == want.shape
    _close(got, want, tol=1e-6)


def test_the_shares_of_a_layer_sum_to_the_uncut_layer():
    """Two chips hold 8 experts each of 16.  Their results, with the shared
    expert that both compute counted once, add up to the uncut layer, which
    is the reference's with all 16 held."""
    def layer(held, prefix):
        mx.random.seed(5)
        blk = RoutedExperts(32, 16, 16, 4, held_experts=held,
                            routed_scaling_factor=2.5, weight_std=0.3,
                            prefix=prefix)
        blk.initialize(ctx=mx.cpu())
        return blk

    whole = layer((0, 16), "whole_")
    full = {n[len("whole_"):]: p for n, p in whole.collect_params().items()}
    x = mx.nd.array(_hidden(2))
    total = 0
    for first in (0, 8):
        share = layer((first, 8), "share%d_" % first)
        for name, p in share.collect_params().items():
            value = full[name[len(share.prefix):]].data().asnumpy()
            if "experts_" in name:
                value = value[first:first + 8]
            p.set_data(mx.nd.array(value))
        total = total + share(x).asnumpy()
    shared = whole.shared(x).asnumpy()
    with jax.default_matmul_precision("highest"):
        uncut = whole(x).asnumpy()
        p = {"m_" + n: v.data().asnumpy() for n, v in full.items()}
        want = reference.moe(p, "m_", jnp.asarray(x.asnumpy()),
                             dict(REF_ARCH, held_experts=[0, 16]), [])
    _close(uncut, want)
    _close(total - shared, uncut)


def _one_sgd_step_gradients(net, tokens, weight=0.3):
    """{name: gradient} from one plain-SGD ``GluonTrainStep`` step at lr 1:
    ``g = W0 - W1``."""
    before = named(net)
    step = GluonTrainStep(
        net, MultiTokenLoss(net.head, weight),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]), lr=1.0,
        momentum=0.0, wd=0.0)
    with jax.default_matmul_precision("highest"):
        loss = float(step(tokens, tokens))
    cut = len(net.prefix)
    after = {p.name[cut:]: np.asarray(v)
             for p, v in zip(step.trainable, step.train_vals)}
    aux = {p.name[cut:]: np.asarray(v)
           for p, v in zip(step.aux, step.aux_vals)}
    return loss, {n: before[n] - v for n, v in after.items()}, aux


@jax.jit
def _reference_loss_gradients(trained, rest, x, tokens, weight):
    return jax.grad(lambda t: reference.loss(
        dict(t, **rest), x, tokens, dict(REF_ARCH, mtp_loss_weight=weight)))(
        trained)


def _reference_gradients(p, tokens, weight):
    trained = {n: jnp.asarray(v) for n, v in p.items()
               if not reference.not_trained(n)}
    rest = {n: jnp.asarray(v) for n, v in p.items()
            if reference.not_trained(n)}
    with jax.default_matmul_precision("highest"):
        return _reference_loss_gradients(
            trained, rest, tokens.astype(np.float32), jnp.asarray(tokens),
            weight)


def test_every_gradient_through_the_step_and_both_losses_reach_the_shared(
        tokens):
    """Every parameter's gradient through ``GluonTrainStep`` (every block's
    forward recomputed) agrees with the reference's; the shared embedding
    and head receive the gradients of both losses (without the MTP term
    theirs differ)."""
    model = build()
    p = named(model)
    loss, got, aux = _one_sgd_step_gradients(model, tokens)
    want = _reference_gradients(p, tokens, 0.3)
    assert sorted(got) == sorted(want)
    for name in want:
        _close(got[name], want[name], tol=2e-4)
    main_only = _reference_gradients(p, tokens, 0.0)
    for name in ("embed_weight", "head_weight"):
        apart = np.abs(np.asarray(want[name] - main_only[name])).max()
        assert apart > 0.05 * np.abs(np.asarray(want[name])).max()
    # the module's own weights see the MTP term alone
    assert np.abs(np.asarray(main_only["mtp_proj_weight"])).max() == 0
    assert np.abs(got["mtp_proj_weight"]).max() > 0
    _counters_left_the_step_with_its_state(p, aux, tokens)


def _counters_left_the_step_with_its_state(p, aux, tokens):
    """The routed layer's counters, updated inside a recomputed block, are
    what the reference's routing counts."""
    ids, _, _ = reference.route(
        {k: jnp.asarray(v) for k, v in p.items()}, "l1_moe_",
        reference.rms_norm(
            jnp.asarray(_block_input(p, tokens)), p["l1_ln2_weight"], 1e-6),
        REF_ARCH)
    counts = np.array([(np.asarray(ids) == e).sum() for e in range(4, 12)])
    assert aux["l1_moe_held_pairs"][0] == counts.sum()
    assert aux["l1_moe_max_load"][0] == pytest.approx(
        counts.max() / counts.mean(), rel=1e-5)
    # the bias is state that the step holds fixed
    np.testing.assert_array_equal(aux["l1_moe_router_bias"],
                                  p["l1_moe_router_bias"])


def _block_input(p, tokens):
    """What block 1's feed-forward norm sees: the reference's walk up to
    there."""
    p = {k: jnp.asarray(v) for k, v in p.items()}
    h = p["embed_weight"][tokens]
    h = reference.block(p, "l0_", h, REF_ARCH, True, [])
    return h + reference.mla(
        p, "l1_attn_", reference.rms_norm(h, p["l1_ln1_weight"], 1e-6),
        REF_ARCH)


def test_bfloat16_step_with_integer_tokens_trains(tokens):
    """``compute_dtype`` casts weights and floating inputs; token ids stay
    integers.  The loss falls on the fixed batch."""
    from mxnet_tpu import optimizer

    model = build()
    step = GluonTrainStep(
        model, MultiTokenLoss(model.head, 0.3),
        mesh=create_mesh({"dp": 1}, devices=jax.devices()[:1]),
        compute_dtype="bfloat16",
        optimizer=optimizer.Adam(learning_rate=1e-2, beta1=0.9, beta2=0.95,
                                 wd=0.1))
    losses = [float(step(tokens, tokens)) for _ in range(4)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert all(v.dtype == np.float32 for v in step.train_vals)


def test_a_gluon_loss_block_works_on_the_logits(net, tokens):
    """``net.head`` gives logits any Gluon loss takes: the fused loss's main
    term is SoftmaxCrossEntropyLoss on them."""
    ids = mx.nd.array(tokens, dtype="int32")
    main, mtp = net(ids)
    fused = MultiTokenLoss(net.head, 0.0)((main, mtp), ids).asnumpy()
    logits = net.head(main).asnumpy()[:, :-1]
    plain = gluon.loss.SoftmaxCrossEntropyLoss()(
        mx.nd.array(logits.reshape(-1, 97)),
        mx.nd.array(tokens[:, 1:].reshape(-1))).asnumpy()
    np.testing.assert_allclose(fused, plain.reshape(2, 15).mean(axis=1),
                               rtol=1e-5)
