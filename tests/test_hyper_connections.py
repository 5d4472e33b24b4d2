"""Hyper-connections (a residual stream of n = 4 streams mixed by
Sinkhorn-normalised matrices) and Xing4.0's yarn latent attention on the
CPU, against the plain reference ``benchmark/references/xing_moe.py`` on
seeded random weights at a small size: Sinkhorn's matrix, the coefficients
and both mixes with their gradients; the yarn tables and softmax scale;
eight shares of a routed layer with its hyper-connection add up to the
uncut layer; one stream is the program before hyper-connections, bit for
bit; a widened stream refuses an MTP module.  The whole tiny model through
the cell's entry against the reference is
``tests/benchmark/test_xing_metrics.py``'s.

Tolerances.  Float32 at highest matmul precision on both sides: values
agree to a few float32 ulps of their scale (rtol 1e-5 is 80 ulps; the
same computation in bfloat16 is off by ~1e-2)."""

import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

CELL = "xing_hc_train_seq4k"
_here = manifest.load_module(os.path.join(REPO, "tests", "benchmark",
                                          "test_xing_metrics.py"))
tiny = _here.tiny
N, C = 4, 32


def _reference():
    return manifest.load_module(os.path.join(
        REPO, "benchmark", "references", "xing_moe.py"))


@pytest.fixture(scope="module")
def cell():
    return manifest.Manifest(REPO).cell(CELL)


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(scale * np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


def _arch(**kw):
    return dict(hc_mult=N, hc_sinkhorn_iters=20, hc_eps=1e-6,
                mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, **kw)


def _coefficient_operands(seed=0):
    """A widened stream (2, 8, n C) and one hyper-connection's parameters,
    its bias drawn as the model draws it (diagonal favoured), alpha large
    enough that the stream's part moves every coefficient."""
    from mxnet_tpu.gluon.nn.mla_moe import HC_BIAS_STD, HC_DIAGONAL

    m = 2 * N + N * N
    centre = np.concatenate([np.full(N, -np.log(N - 1.0)), np.zeros(N),
                             HC_DIAGONAL * np.eye(N).reshape(-1)])
    bias = jnp.asarray(centre, jnp.float32) + _rand((m,), seed + 1,
                                                    HC_BIAS_STD)
    return (_rand((2, 8, N * C), seed), _rand((N * C, m), seed + 2, 0.05),
            bias, jnp.asarray([0.5, 0.4, 0.3], jnp.float32))


def _as_reference(x, phi, bias, alpha):
    p = {"hc_phi": phi, "hc_bias": bias, "hc_alpha": alpha}
    return p, x.reshape(x.shape[:2] + (N, C))


# ------------------------------------------------------------- Sinkhorn


def test_sinkhorns_matrix_is_the_references_and_doubly_stochastic():
    """``H_res`` against the reference's on the same operands, within
    float32 rounding (rtol 1e-5); the columns, normalised last, sum to 1
    within 1e-6 plus float32's rounding of a sum of four (each is S / (S +
    hc_eps), hc_eps = 1e-6 below 1 at S = 1); the rows' deviation after 20
    iterations is printed; the diagonal is favoured and no row is
    uniform."""
    from mxnet_tpu.ops import llm

    reference = _reference()
    x, phi, bias, alpha = _coefficient_operands()
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = llm.hc_coefficients(x, phi, bias, alpha)
        p, X = _as_reference(x, phi, bias, alpha)
        want = reference.coefficients(p, "hc_", X, _arch())
    assert h_res.shape == (N, N, 2, 8) and h_pre.shape == (N, 2, 8)
    assert h_res.dtype == h_pre.dtype == jnp.float32
    mine = np.moveaxis(np.asarray(h_res), (0, 1), (2, 3))
    np.testing.assert_allclose(mine, want[2], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.moveaxis(np.asarray(h_pre), 0, -1),
                               want[0], rtol=1e-5)
    np.testing.assert_allclose(np.moveaxis(np.asarray(h_post), 0, -1),
                               want[1], rtol=1e-5)
    columns = mine.sum(axis=-2)
    rows = mine.sum(axis=-1)
    assert np.abs(columns - 1).max() <= 1e-6 + 4 * 2.0 ** -24
    print("rows' largest deviation from 1 after 20 iterations: %.3g"
          % np.abs(rows - 1).max())
    assert np.abs(rows - 1).max() < 1e-3
    diagonal = np.diagonal(mine, axis1=-2, axis2=-1)
    assert diagonal.min() > 0.3 and (mine.max(axis=-1) - mine.min(axis=-1)
                                     ).min() > 0.1


def test_the_clamp_holds_at_30():
    """Logits of the mixing matrix beyond +-30 are read at +-30: the same
    matrix as at exactly +-30, finite where exp(40) would lose the rest of
    its row to rounding."""
    from mxnet_tpu.ops import llm

    x, phi, _, alpha = _coefficient_operands(3)
    zero = jnp.zeros_like(alpha)
    wild = np.zeros(2 * N + N * N, np.float32)
    wild[2 * N:] = np.where(np.eye(N).reshape(-1) > 0, 40.0, -45.0)
    edge = np.clip(wild, -30, 30)
    got = llm.hc_coefficients(x, phi, jnp.asarray(wild), zero)[2]
    at = llm.hc_coefficients(x, phi, jnp.asarray(edge), zero)[2]
    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, at)
    reference = _reference()
    p, X = _as_reference(x, phi, jnp.asarray(wild), zero)
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(got), (0, 1), (2, 3)),
        reference.coefficients(p, "hc_", X, _arch())[2], rtol=1e-5,
        atol=1e-7)


def test_the_coefficients_and_mixes_and_their_gradients_are_the_references():
    """One sublayer wrapped in its hyper-connection, ``X' = H_res X +
    h_post f(h_pre . X)`` with ``f = tanh``: the program's three operators
    against the reference's ``connected``, the value and the gradients of
    the stream, phi, b and alpha (rtol 1e-5 of each gradient's scale)."""
    from mxnet_tpu.ops import llm

    reference = _reference()
    x, phi, bias, alpha = _coefficient_operands(5)
    g = _rand((2, 8, N, C), 6)

    def mine(x, phi, bias, alpha):
        h_pre, h_post, h_res = llm.hc_coefficients(x, phi, bias, alpha)
        y = jnp.tanh(llm.hc_pre_mix(x, h_pre))
        out = llm.hc_post_mix(x, h_res, h_post, y)
        return jnp.sum(out.reshape(g.shape) * g)

    def plain(x, phi, bias, alpha):
        p, X = _as_reference(x, phi, bias, alpha)
        return jnp.sum(reference.connected(p, "hc_", X, _arch(), jnp.tanh)
                       * g)

    with jax.default_matmul_precision("highest"):
        a, b = (jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))(
            x, phi, bias, alpha) for f in (mine, plain))
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5)
    for got, want in zip(a[1], b[1]):
        scale = float(np.abs(want).max())
        assert scale > 0
        assert float(np.abs(np.asarray(got) - want).max()) < 1e-5 * scale


def test_the_mixes_keep_the_streams_dtype_and_lanes():
    """bfloat16 in, bfloat16 out, the coefficients float32; the pre mix of
    one-hot ``h_pre`` is that stream's lanes, the post mix of the identity
    and ``h_post`` 0 the stream itself."""
    from mxnet_tpu.ops import llm

    x = _rand((2, 8, N * C), 7).astype(jnp.bfloat16)
    h = jnp.zeros((N, 2, 8), jnp.float32).at[2].set(1.0)
    assert llm.hc_pre_mix(x, h).dtype == jnp.bfloat16
    np.testing.assert_array_equal(llm.hc_pre_mix(x, h), x[..., 2 * C:3 * C])
    eye = jnp.broadcast_to(jnp.eye(N)[..., None, None], (N, N, 2, 8))
    out = llm.hc_post_mix(x, eye, jnp.zeros((N, 2, 8)),
                          jnp.ones((2, 8, C), jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(out, x)
    _, phi, bias, alpha = _coefficient_operands()
    assert all(c.dtype == jnp.float32 for c in llm.hc_coefficients(
        x, phi.astype(jnp.bfloat16), bias, alpha))


# ------------------------------------------------------------- yarn MLA


def test_yarn_tables_and_softmax_scale_are_deepseeks(cell):
    """``deepseek_yarn`` at the cell's numbers (64 rotary lanes, factor 64
    from 4,096, beta 32 / 1, mscale = mscale_all_dim = 1): the reference's
    frequencies to float64's rounding, amplitude 1 and the softmax scale 192^-1/2 x
    (0.1 ln 64 + 1)^2 = 192^-1/2 x 2.0047; at a tiny size latent attention
    with yarn past its original length is the reference's, and an
    amplitude or a scale left out would not be."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.nn import MLAttention
    from mxnet_tpu.gluon.nn.mla_moe import deepseek_yarn

    reference = _reference()
    arch = cell.config["architecture"]
    inv_freq, amplitude, softmax = deepseek_yarn(
        64, arch["rope_theta"], arch["rope_scaling"])
    want, want_amplitude = reference.rotary(arch)
    np.testing.assert_allclose(inv_freq, want, rtol=1e-15)
    assert amplitude == want_amplitude == 1.0
    assert softmax == pytest.approx((0.1 * np.log(64) + 1) ** 2, rel=1e-12)
    assert softmax == pytest.approx(2.0047, abs=1e-4)
    assert 192 ** -0.5 * softmax == pytest.approx(
        reference.softmax_scale(arch), rel=1e-12)
    # the fastest pair keeps theta^0, the slowest is divided by 64
    assert inv_freq[0] == 1.0
    assert inv_freq[-1] == pytest.approx(1e4 ** (-62 / 64) / 64)

    small = dict(_here.TINY, rms_norm_eps=1e-6, rope_theta=10000.0)
    small["rope_scaling"] = dict(small["rope_scaling"], mscale=2)
    mx.random.seed(11)
    layer = MLAttention(32, num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                        rope_scaling=small["rope_scaling"], weight_std=0.3,
                        prefix="attn_")
    layer.initialize(ctx=mx.cpu())
    assert layer._rotary["amplitude"] == pytest.approx(
        (0.2 * np.log(4) + 1) / (0.1 * np.log(4) + 1))
    x = _rand((2, 32, 32), 12)
    p = {n: p.data().data_jax for n, p in layer.collect_params().items()}
    with jax.default_matmul_precision("highest"):
        got = layer(mx.nd.array(np.asarray(x))).asnumpy()
        want = reference.mla(p, "attn_", x, small)
        unscaled = reference.mla(p, "attn_", x, dict(
            small, rope_scaling=dict(small["rope_scaling"],
                                     mscale_all_dim=0)))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 1e-5 * scale
    assert np.abs(got - unscaled).max() > 1e-3 * scale


# ------------------------------------------------------- the routed layer


def test_eight_shares_of_a_widened_routed_layer_sum_to_the_uncut_layer():
    """A routed layer in a 4-stream residual, 16 experts top-3 with a
    selection bias and a shared expert, wrapped in its hyper-connection,
    over eight chips holding 2 experts each: every share's output is
    ``H_res X + h_post (shared + its experts' part)``, so the eight
    outputs, less seven times the shared expert's and ``H_res X``'s (each
    counted once), are the uncut reference layer."""
    from mxnet_tpu.gluon.block import staged_call
    from mxnet_tpu.gluon.nn.mla_moe import RoutedExperts
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.ops import llm

    reference = _reference()
    arch = _arch(num_experts_per_tok=3, router_outputs=16,
                 held_experts=[0, 16], routed_scaling_factor=2.0)
    X, phi, bias, alpha = _coefficient_operands(13)
    p = {"hc_phi": phi, "hc_bias": bias, "hc_alpha": alpha}

    def routed(held):
        return RoutedExperts(C, 16, 16, 3, held_experts=held,
                             routed_scaling_factor=2.0, prefix="moe_")

    shapes = {n: v.shape for n, v in routed((0, 16)).collect_params().items()}
    full = {n: _rand(shape, 20 + i, 0.01 if n.endswith("router_bias")
                     else 0.3) for i, (n, shape) in enumerate(shapes.items())}
    p.update(full)

    def part(first):
        """``u``'s routed part of the share holding ``first``, ``first +
        1``, with the shared expert, the uncut layer's values handed in."""
        layer = routed((first, 2))
        params = list(layer.collect_params().values())
        values = [full[v.name][first:first + 2] if "experts_" in v.name
                  else full[v.name] for v in params]

        def forward(values, u):
            override = {q: NDArray(v) for q, v in zip(params, values)}
            return staged_call(layer, override, None, (NDArray(u),),
                               train=False)[0]._data

        return jax.jit(forward)(values, u)

    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = llm.hc_coefficients(X, phi, bias, alpha)
        u = llm.hc_pre_mix(X, h_pre)
        shared = llm.gated_silu(u, full["moe_shared_gate_weight"],
                                full["moe_shared_up_weight"],
                                full["moe_shared_down_weight"])
        total = sum(llm.hc_post_mix(X, h_res, h_post, part(first))
                    for first in range(0, 16, 2)) \
            - 7 * llm.hc_post_mix(X, h_res, h_post, shared)
        want = reference.connected(
            p, "hc_", X.reshape(2, 8, N, C), arch,
            lambda u: reference.moe(p, "moe_", u, arch, []))
    want = np.asarray(want).reshape(2, 8, N * C)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(total) - want).max() < 2e-5 * scale


# ------------------------------------------------------ one stream, MTP

# sha256 (16 hex digits) of the jaxpr of a tiny one-stream MLAMoELM's
# staged loss and gradient (below), taken on the program as it was before
# it knew hyper-connections: one stream is that program, not a
# neighbour of it.  A change meant to move the one-stream path updates it.
ONE_STREAM_JAXPR = "847060f89aa44d3a"


def test_one_stream_is_the_program_before_hyper_connections_bit_for_bit():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import staged_call
    from mxnet_tpu.gluon.nn import MLAMoELM, MultiTokenLoss
    from mxnet_tpu.ndarray import NDArray

    mx.random.seed(7)
    net = MLAMoELM(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        first_k_dense_replace=1, intermediate_size=48,
        moe_intermediate_size=16, router_outputs=16, num_experts_per_tok=3,
        num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        held_experts=[4, 4], routed_scaling_factor=2.5)
    net.initialize(ctx=mx.cpu())
    params = list(net.collect_params().values())
    assert not any("_hc_" in p.name for p in params)
    loss = MultiTokenLoss(net.head)

    def value(values, ids):
        override = {p: NDArray(v) for p, v in zip(params, values)}
        out, _ = staged_call(lambda t: mx.nd.mean(loss(net(t), t)), override,
                             None, (NDArray(ids),))
        return out._data

    ids = np.random.RandomState(0).randint(0, 97, (2, 16)).astype(np.int32)
    text = str(jax.make_jaxpr(jax.value_and_grad(value))(
        [p.data().data_jax for p in params], ids))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == ONE_STREAM_JAXPR


def test_a_widened_stream_with_an_mtp_module_raises(cell):
    from mxnet_tpu.gluon.nn import MLAMoELM

    arch = dict(tiny(cell.config)["architecture"])
    with pytest.raises(ValueError, match="multi-token prediction"):
        MLAMoELM(**dict(arch, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="0 or 1"):
        MLAMoELM(**dict(arch, hc_mult=1, num_nextn_predict_layers=2))
    net = MLAMoELM(**dict(arch, hc_mult=1, num_nextn_predict_layers=1))
    assert any(n.endswith("mtp_proj_weight") for n in net.collect_params())
