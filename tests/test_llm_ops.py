"""The operators of ``ops/llm.py`` against numpy, with gradients, and the
flash-attention kernels with a value head size that differs from the
scores' (interpret mode).  Docs: docs/LLM_OPS.md."""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import llm
from mxnet_tpu.ops.attention import flash_attention, mha_reference


def _rs(seed=0):
    return np.random.RandomState(seed)


def _f(a):
    return jnp.asarray(np.asarray(a, np.float32))


# ------------------------------------------------- numpy forms of each op


def np_rms_norm(x, gamma, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma


def np_rope(x, theta):
    seq, dim = x.shape[-2:]
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(seq)[:, None] * inv[None, :]
    cos, sin = np.cos(angle), np.sin(angle)
    out = np.empty_like(x, dtype=np.float64)
    a, b = x[..., 0::2], x[..., 1::2]
    out[..., 0::2], out[..., 1::2] = a * cos - b * sin, a * sin + b * cos
    return out


def np_rope_halves(x, theta):
    """Pairs ``(i, i + dim / 2)``: ``x cos + rotate_half(x) sin``."""
    seq, dim = x.shape[-2:]
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(seq)[:, None] * inv[None, :]
    cos, sin = np.tile(np.cos(angle), 2), np.tile(np.sin(angle), 2)
    turned = np.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + turned * sin


def np_gated_short_conv(bcx, weight):
    """``c * conv(b * x)``, the convolution causal over axis 1."""
    b, c, x = np.split(bcx, 3, axis=-1)
    taps, seq = weight.shape[1], bcx.shape[1]
    u = np.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    return c * sum(weight[:, j] * u[:, j:j + seq] for j in range(taps))


def np_gqa_qkv(x, wq, wk, wv, gq, gk, theta, eps):
    """q, k and v as one array, heads side by side on axis 1."""
    d = gq.shape[0]

    def heads(w):
        return (x @ w.T).reshape(x.shape[0], x.shape[1], -1, d) \
            .transpose(0, 2, 1, 3)

    q = np_rope_halves(np_rms_norm(heads(wq), gq, eps), theta)
    k = np_rope_halves(np_rms_norm(heads(wk), gk, eps), theta)
    return np.concatenate([q, k, heads(wv)], axis=1)


def np_silu(x):
    return x / (1 + np.exp(-x))


def np_gated_silu(x, gate, up, down):
    return (np_silu(x @ gate.T) * (x @ up.T)) @ down.T


def np_route(x, w, b, k, scale, eps=1e-20):
    s = 1 / (1 + np.exp(-(x @ w.T)))
    ids = np.argsort(-(s + b), axis=-1, kind="stable")[:, :k]
    picked = np.take_along_axis(s, ids, -1)
    return ids, picked / (picked.sum(-1, keepdims=True) + eps) * scale


def np_experts(x, ids, weights, wg, wu, wd, first):
    y = np.zeros_like(x)
    for j in range(wg.shape[0]):        # a dense mask, expert by expert
        w = np.where(ids == first + j, weights, 0).sum(-1)
        y += w[:, None] * ((np_silu(x @ wg[j]) * (x @ wu[j])) @ wd[j])
    return y


def np_linear_ce(h, w, label):
    logits = h @ w.T
    logz = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    picked = np.take_along_axis(logits, np.maximum(label, 0)[:, None], 1)[:, 0]
    return np.where(label >= 0, logz - picked, 0.0)


def _moe_inputs(tokens=37, hidden=8, width=6, experts=12, held=4):
    rs = _rs(1)
    return dict(
        x=rs.randn(tokens, hidden), router=rs.randn(experts, hidden),
        bias=rs.randn(experts) * 0.1,
        wg=rs.randn(held, hidden, width), wu=rs.randn(held, hidden, width),
        wd=rs.randn(held, width, hidden))


CASES = {}


def case(fn):
    CASES[fn.__name__] = fn
    return fn


@case
def rms_norm():
    rs = _rs()
    args = (rs.randn(3, 5, 8), rs.rand(8) + 0.5)
    return (lambda x, g: llm.rms_norm(x, g, eps=1e-6),
            lambda x, g: np_rms_norm(x, g), args, (0, 1))


@case
def rope_four_axes():
    x = _rs().randn(2, 3, 7, 8)
    return (lambda x: llm.rope(x, theta=32e6),
            lambda x: np_rope(x, 32e6), (x,), (0,))


@case
def rope_three_axes():
    x = _rs().randn(2, 7, 8)
    return (lambda x: llm.rope(x, theta=1e4),
            lambda x: np_rope(x, 1e4), (x,), (0,))


@case
def rope_of_one_pair():
    x = _rs().randn(3, 5, 2)
    return (lambda x: llm.rope(x, theta=1e4),
            lambda x: np_rope(x, 1e4), (x,), (0,))


@case
def rope_at_position_4095_with_a_head_axis():
    x = _rs().randn(1, 2, 4096, 64)
    return (lambda x: llm.rope(x, theta=3.2e7),
            lambda x: np_rope(x, 3.2e7), (x,), (0,))


@case
def rope_at_position_4095_without_a_head_axis():
    x = _rs().randn(1, 4096, 128)
    return (lambda x: llm.rope(x, theta=1e4),
            lambda x: np_rope(x, 1e4), (x,), (0,))


@case
def rope_wider_than_a_lane_tile():
    x = _rs().randn(2, 5, 384)          # three permutations of 128
    return (lambda x: llm.rope(x, theta=1e4),
            lambda x: np_rope(x, 1e4), (x,), (0,))


@case
def rope_of_a_width_no_tile_divides():
    x = _rs().randn(2, 5, 200)          # two permutations of 100
    return (lambda x: llm.rope(x, theta=3.2e7),
            lambda x: np_rope(x, 3.2e7), (x,), (0,))


@case
def rope_by_halves_with_a_head_axis():
    x = _rs().randn(2, 3, 7, 64)
    return (lambda x: llm.rope(x, theta=1e6, halves=True),
            lambda x: np_rope_halves(x, 1e6), (x,), (0,))


@case
def rope_by_halves_wider_than_a_lane_tile():
    x = _rs().randn(2, 5, 256)          # two slices swapped, no product
    return (lambda x: llm.rope(x, theta=1e4, halves=True),
            lambda x: np_rope_halves(x, 1e4), (x,), (0,))


@case
def rope_by_halves_at_position_8191():
    x = _rs().randn(1, 8192, 64)
    return (lambda x: llm.rope(x, theta=1e6, halves=True),
            lambda x: np_rope_halves(x, 1e6), (x,), (0,))


@case
def gated_short_conv():
    rs = _rs()
    return (llm.gated_short_conv, np_gated_short_conv,
            (rs.randn(2, 9, 24), rs.randn(8, 3)), (0, 1))


@case
def gated_short_conv_of_four_taps_on_a_short_row():
    rs = _rs()
    return (llm.gated_short_conv, np_gated_short_conv,
            (rs.randn(3, 2, 12), rs.randn(4, 4)), (0, 1))


@case
def gqa_qkv():
    rs = _rs()
    args = (rs.randn(2, 5, 12), rs.randn(32, 12), rs.randn(16, 12),
            rs.randn(16, 12), rs.rand(8) + 0.5, rs.rand(8) + 0.5)

    def op(*a):
        return jnp.concatenate(llm.gqa_qkv(*a, theta=1e6, eps=1e-5), axis=1)

    return (op, lambda *a: np_gqa_qkv(*a, 1e6, 1e-5), args,
            (0, 1, 2, 3, 4, 5))


@case
def gqa_out():
    rs = _rs()
    return (llm.gqa_out,
            lambda o, w: o.transpose(0, 2, 1, 3).reshape(2, 5, 32) @ w.T,
            (rs.randn(2, 4, 5, 8), rs.randn(12, 32)), (0, 1))


@case
def gated_silu():
    rs = _rs()
    args = (rs.randn(2, 5, 8), rs.randn(12, 8), rs.randn(12, 8),
            rs.randn(8, 12))
    return llm.gated_silu, np_gated_silu, args, (0, 1, 2, 3)


@case
def moe_route_weights():
    m = _moe_inputs()
    return (lambda x, w: llm.moe_route(x, w, _f(m["bias"]), k=3,
                                       scale=2.5)[1],
            lambda x, w: np_route(x, w, m["bias"], 3, 2.5)[1],
            (m["x"], m["router"]), (0, 1))


@case
def moe_route_weights_with_its_epsilon():
    m = _moe_inputs()
    return (lambda x, w: llm.moe_route(x, w, _f(m["bias"]), k=3, scale=1.0,
                                       eps=0.25)[1],
            lambda x, w: np_route(x, w, m["bias"], 3, 1.0, eps=0.25)[1],
            (m["x"], m["router"]), (0, 1))


def _moe_experts_case(tokens, tile):
    m = _moe_inputs(tokens=tokens)
    ids, weights = np_route(m["x"], m["router"], m["bias"], 3, 2.5)

    def op(x, weights, wg, wu, wd):
        return llm.moe_experts(x, jnp.asarray(ids, jnp.int32), weights, wg,
                               wu, wd, first_expert=4, tile=tile)[0]

    return (op, lambda x, w, wg, wu, wd: np_experts(x, ids, w, wg, wu, wd, 4),
            (m["x"], weights, m["wg"], m["wu"], m["wd"]), (0, 1, 2, 3, 4))


@case
def moe_experts():
    return _moe_experts_case(tokens=37, tile=4)


@case
def moe_experts_with_tiles_that_are_mostly_padding():
    """6 tokens, 18 pairs over 12 experts: a tile of 64 rows holds one or
    two real rows, the rest add nothing anywhere."""
    return _moe_experts_case(tokens=6, tile=64)


@case
def moe_experts_with_loads_that_cross_a_tile():
    """~9 pairs a held expert against tiles of 8: a full tile, then one
    that is nearly all padding."""
    return _moe_experts_case(tokens=37, tile=8)


LOADS = {    # tiles on the three held experts, rows beyond them
    "no_rows": ((0, 0, 0), (0, 0, 0)),
    "one_row": ((0, 0, 0), (1, 0, 0)),
    "exactly_four_tiles": ((0, 4, 0), (0, 0, 0)),
    "four_tiles_and_a_row": ((0, 4, 0), (0, 1, 0)),
    "nine_tiles": ((1, 0, 8), (2, 0, 3)),
    "one_held_expert": ((0, 0, 6), (0, 0, 3)),
    "an_expert_absent": ((5, 0, 2), (0, 0, 1)),
    "four_and_five_tiles": ((4, 3, 4), (0, 3, 1)),
}


def _loaded_ids(load, tile, first=4, experts=8):
    """(tokens, 2) ids: a token's first choice is a held expert (``first``
    on) as ``LOADS[load]`` counts them, or expert 0, which is absent, for
    a few more; its second choice is absent."""
    tiles, more = LOADS[load]
    counts = [t * tile + m for t, m in zip(tiles, more)]
    chosen = np.concatenate([np.full(c, first + j) for j, c in
                             enumerate(counts)] + [np.zeros(3, np.int64)])
    chosen = _rs(11).permutation(chosen)
    return np.stack([chosen, np.full(len(chosen), experts - 1)], -1), counts


def _loaded_args(ids):
    """x, routing weights and three held experts' weights for those ids."""
    rs = _rs(7)
    return (rs.randn(len(ids), 8), rs.rand(len(ids), 2), rs.randn(3, 8, 6),
            rs.randn(3, 8, 6), rs.randn(3, 6, 8))


def _loaded_case(load, tile):
    ids, _ = _loaded_ids(load, tile)
    x, weights, wg, wu, wd = _loaded_args(ids)

    def op(x, weights, wg, wu, wd):
        return llm.moe_experts(x, jnp.asarray(ids, jnp.int32), weights, wg,
                               wu, wd, first_expert=4, tile=tile)[0]

    return (op, lambda x, w, wg, wu, wd: np_experts(x, ids, w, wg, wu, wd, 4),
            (x, weights, wg, wu, wd), (0, 1, 2, 3, 4))


for _load in LOADS:
    for _tile in (4, 8):
        CASES["moe_experts_with_%s_of_%d_rows" % (_load, _tile)] = \
            functools.partial(_loaded_case, _load, _tile)


@case
def linear_cross_entropy():
    rs = _rs()
    label = rs.randint(-1, 11, (16,))
    return (lambda h, w: llm.linear_cross_entropy(h, w, jnp.asarray(label),
                                                  chunk=4),
            lambda h, w: np_linear_ce(h, w, label),
            (rs.randn(16, 8), rs.randn(11, 8)), (0, 1))


@pytest.mark.parametrize("name", sorted(CASES))
def test_op_against_numpy_with_gradients(name):
    """Forward against the numpy form in float64; the gradient of a fixed
    random projection of the output against central differences of the
    numpy form."""
    op, numpy_form, args, wrt = CASES[name]()
    got = np.asarray(op(*[_f(a) for a in args]))
    want = numpy_form(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    proj = _rs(9).randn(*want.shape)
    grads = jax.grad(lambda *a: jnp.sum(op(*a) * _f(proj)), argnums=wrt)(
        *[_f(a) for a in args])
    rs = _rs(5)
    for i, g in zip(wrt, grads):
        direction = rs.randn(*args[i].shape)
        h = 1e-5
        plus = [a + h * direction if j == i else a
                for j, a in enumerate(args)]
        minus = [a - h * direction if j == i else a
                 for j, a in enumerate(args)]
        numeric = ((numpy_form(*plus) - numpy_form(*minus)) * proj).sum() \
            / (2 * h)
        assert np.sum(np.asarray(g) * direction) == pytest.approx(
            numeric, rel=2e-3, abs=2e-4), (name, i)


@pytest.mark.parametrize("shape,theta", [((2, 3, 16, 2), 1e4),
                                         ((1, 2, 4096, 64), 3.2e7),
                                         ((1, 4096, 128), 1e4)],
                         ids=["one_pair", "heads_64", "no_heads_128"])
def test_rope_of_bfloat16_is_the_float32_result_cast(shape, theta):
    """The arithmetic is float32 whatever the input's type, and the pair's
    partner is fetched exactly: bfloat16 in gives the float32 result's
    cast, to an ulp; traced or eager."""
    x = jnp.asarray(_rs(4).randn(*shape), jnp.bfloat16)
    want = llm.rope(x.astype(jnp.float32), theta=theta)
    np.testing.assert_allclose(want, np_rope(np.asarray(x, np.float64), theta),
                               rtol=2e-5, atol=2e-5)
    want = np.asarray(want.astype(jnp.bfloat16), np.float32)
    # an ulp of the result; where the two terms cancel, the float32
    # rounding of the terms (a fused multiply-add or not)
    ulp = np.abs(want) * 2.0 ** -7 + np.abs(np.asarray(x, np.float32)).max() \
        * 2.0 ** -21
    for op in (llm.rope, jax.jit(llm.rope, static_argnames="theta")):
        got = op(x, theta=theta)
        assert got.dtype == jnp.bfloat16
        assert (np.abs(np.asarray(got, np.float32) - want) <= ulp).all()


def test_gated_short_conv_is_a_depthwise_causal_convolution():
    """Against ``lax.conv_general_dilated`` over ``(batch, channels, seq)``
    with ``L - 1`` zeros before the row (the published module's form),
    forward and both gradients; bfloat16 in gives the float32 result's
    cast; and the lowered op never holds channels anywhere but last."""
    from jax import lax

    rs = _rs(8)
    bcx, weight = _f(rs.randn(2, 33, 3 * 16)), _f(rs.randn(16, 3))
    g = _f(rs.randn(2, 33, 16))

    def plain(bcx, weight):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        conv = lax.conv_general_dilated(
            (b * x).transpose(0, 2, 1), weight[:, None, :], (1,), [(2, 0)],
            feature_group_count=16, dimension_numbers=("NCH", "OIH", "NCH"))
        return c * conv.transpose(0, 2, 1)

    np.testing.assert_allclose(llm.gated_short_conv(bcx, weight),
                               plain(bcx, weight), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(llm.gated_short_conv(*a) * g),
                   (0, 1))(bcx, weight)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), (0, 1))(bcx, weight)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)
    low = llm.gated_short_conv(bcx.astype(jnp.bfloat16), weight)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(low, np.float32),
        np.asarray(plain(bcx.astype(jnp.bfloat16).astype(jnp.float32),
                         weight).astype(jnp.bfloat16), np.float32),
        rtol=1e-2, atol=1e-2)
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(llm.gated_short_conv(*a) * g), (0, 1))).lower(
        bcx, weight).as_text()
    assert "stablehlo.convolution" not in text
    assert "tensor<2x16x" not in text and "tensor<2x48x" not in text


def test_the_gradient_of_rope_is_the_rotation_back():
    """No gather forward, no scatter backward: the lowered gradient is
    products, selects and elementwise arithmetic."""
    x = _f(_rs(6).randn(2, 3, 9, 8))
    back = jax.grad(lambda x, g: jnp.sum(llm.rope(x, theta=1e4) * g))
    g = _f(_rs(7).randn(2, 3, 9, 8))
    np.testing.assert_allclose(
        llm.rope(back(x, g), theta=1e4), g, rtol=1e-5, atol=1e-5)
    text = jax.jit(back).lower(x, g).as_text()
    assert "gather" not in text and "scatter" not in text


def test_the_ops_are_in_the_contrib_namespaces():
    x = mx.nd.array(_rs().randn(2, 5, 8).astype(np.float32))
    out = mx.nd.contrib.rms_norm(x, mx.nd.ones((8,)))
    np.testing.assert_allclose(
        out.asnumpy(), np_rms_norm(x.asnumpy(), 1.0), rtol=1e-5)
    for name in ("rope", "gated_silu", "mla_qkv", "mla_out", "gqa_qkv",
                 "gqa_out", "gated_short_conv", "moe_route", "moe_experts",
                 "linear_cross_entropy"):
        assert hasattr(mx.nd.contrib, name) and hasattr(mx.sym.contrib, name)


# ------------------------------------------------------- the expert layer


def _layer(m, first, held, ids, weights, tile=4):
    sl = slice(first, first + held)
    return np.asarray(llm.moe_experts(
        _f(m["x"]), jnp.asarray(ids, jnp.int32), _f(weights),
        _f(m["wg"][sl]), _f(m["wu"][sl]), _f(m["wd"][sl]),
        first_expert=first, tile=tile)[0])


def test_the_shares_of_a_layer_sum_to_the_uncut_layer():
    """Three chips hold 4 experts each of 12: their parts of the routed sum
    add up to what one chip holding all 12 gives, which is the numpy form
    of the whole layer.  (What every chip computes alike, the shared
    expert, is outside the op and counted once: tests/test_mla_moe.py.)"""
    m = _moe_inputs(held=12)
    ids, weights = np_route(m["x"], m["router"], m["bias"], 3, 2.5)
    whole = _layer(m, 0, 12, ids, weights)
    np.testing.assert_allclose(
        whole, np_experts(m["x"], ids, weights, m["wg"], m["wu"], m["wd"], 0),
        rtol=1e-4, atol=1e-4)
    shares = sum(_layer(m, first, 4, ids, weights) for first in (0, 4, 8))
    np.testing.assert_allclose(shares, whole, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tile", [4, 16, 64])
def test_nothing_is_dropped_with_every_token_on_one_held_expert(tile):
    m = _moe_inputs(tokens=50, held=12)
    ids = np.tile(np.array([[6, 1, 11]]), (50, 1))     # 6 is held below
    weights = _rs(3).rand(50, 3)
    got = _layer(m, 4, 4, ids, weights, tile=tile)
    want = np_experts(m["x"], ids, weights, m["wg"][4:8], m["wu"][4:8],
                      m["wd"][4:8], 4)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    _, _, n_tiles, counts = llm.expert_tiles(jnp.asarray(ids, jnp.int32), 4,
                                             4, tile)
    assert list(np.asarray(counts)) == [0, 0, 50, 0]
    assert int(n_tiles) == -(-50 // tile)


def test_the_loop_runs_over_the_tiles_in_use_not_over_the_capacity():
    """The trip count follows the pairs routed here: sum over the held
    experts of ceil(pairs / tile), against a capacity that covers every
    pair on one expert."""
    m = _moe_inputs(tokens=200)
    ids, _ = np_route(m["x"], m["router"], m["bias"], 3, 2.5)
    row_pair, tile_expert, n_tiles, counts = llm.expert_tiles(
        jnp.asarray(ids, jnp.int32), 4, 4, 8)
    counts = np.asarray(counts)
    assert list(counts) == [(ids == 4 + j).sum() for j in range(4)]
    assert int(n_tiles) == sum(-(-c // 8) for c in counts)
    assert len(tile_expert) * 8 == len(row_pair) >= ids.size
    assert int(n_tiles) < len(tile_expert) / 2
    # every pair on a held expert has one row, in its expert's tiles
    rows = np.asarray(row_pair)
    real = rows[rows < ids.size]
    assert sorted(real) == sorted(np.flatnonzero(
        (ids.reshape(-1) >= 4) & (ids.reshape(-1) < 8)))
    for t in range(int(n_tiles)):
        pairs = rows[t * 8:(t + 1) * 8]
        pairs = pairs[pairs < ids.size]
        assert (ids.reshape(-1)[pairs] == 4 + int(tile_expert[t])).all()


@pytest.mark.parametrize("tile", [4, 8])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_steps_take_every_tile_in_use_once(load, tile):
    """``_expert_steps``: an expert with ``n`` tiles gives ``n // 4`` big
    steps, each four consecutive tiles of its own, and ``n % 4`` single
    tiles; together they are the tiles in use, each once."""
    ids, counts = _loaded_ids(load, tile)
    row_pair, tile_expert, n_tiles, got_counts = llm.expert_tiles(
        jnp.asarray(ids, jnp.int32), 4, 3, tile)
    assert list(np.asarray(got_counts)) == counts
    slots = len(tile_expert)
    big, n_big, single, n_single = (np.asarray(a) for a in llm._expert_steps(
        got_counts, tile, slots))
    most = llm.EXPERT_TILES_A_STEP
    tiles = [-(-c // tile) for c in counts]
    assert int(n_big) == sum(n // most for n in tiles)
    assert int(n_single) == sum(n % most for n in tiles)
    assert most * int(n_big) + int(n_single) == int(n_tiles)
    taken = []
    for t in big[:int(n_big)]:
        step = list(range(t, t + most))
        assert len(set(np.asarray(tile_expert)[step])) == 1     # one expert's
        taken += step
    taken += list(single[:int(n_single)])
    assert sorted(taken) == list(range(int(n_tiles)))           # each once
    # a big step's rows: real ones first, one expert's, only its last tile
    # may end in padding
    rows = np.asarray(row_pair)
    for t in big[:int(n_big)]:
        pairs = rows[t * tile:(t + most) * tile]
        real = pairs < ids.size
        assert real[:(most - 1) * tile].all()
        assert not real[np.argmin(real):].any() or real.all()
        assert (ids.reshape(-1)[pairs[real]]
                == 4 + int(tile_expert[t])).all()


def test_both_loops_run_over_the_steps_in_use_not_over_the_capacity():
    """The lists have the capacity's size (every pair on one expert), the
    trip counts what the loads give: 200 tokens x 3 choices over 12
    experts leave ~50 pairs on each of the four held: 6-7 tiles of 8, one
    big step and two or three single tiles an expert."""
    m = _moe_inputs(tokens=200)
    ids, _ = np_route(m["x"], m["router"], m["bias"], 3, 2.5)
    _, tile_expert, n_tiles, counts = llm.expert_tiles(
        jnp.asarray(ids, jnp.int32), 4, 4, 8)
    slots = len(tile_expert)
    big, n_big, single, n_single = llm._expert_steps(counts, 8, slots)
    tiles = [-(-c // 8) for c in np.asarray(counts)]
    assert (int(n_big), int(n_single)) == (sum(n // 4 for n in tiles),
                                           sum(n % 4 for n in tiles))
    assert 4 * int(n_big) + int(n_single) == int(n_tiles) < slots / 2
    assert len(big) == slots // 4 and len(single) == min(slots, 4 * 3)
    assert int(n_big) < len(big) / 2 and 0 < int(n_single) < len(single)
    # fewer slots than a big step takes: no big loop at all
    big, n_big, single, n_single = llm._expert_steps(
        jnp.asarray([2, 0, 1, 0], jnp.int32), 8, 3)
    assert len(big) == 0 and int(n_single) == 2 and list(
        np.asarray(single)[:2]) == [0, 1]


def _value_and_gradients(ids, args, tile):
    proj = _f(_rs(9).randn(*args[0].shape))

    def op(*a):
        return llm.moe_experts(a[0], jnp.asarray(ids, jnp.int32), *a[1:],
                               first_expert=4, tile=tile)[0]

    return (op(*args),) + jax.grad(
        lambda *a: jnp.sum(op(*a) * proj), argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("tile", [4, 8])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_big_steps_give_what_single_tiles_give(monkeypatch, load, tile):
    """The op's value and its five gradients with big steps against the
    same loads taken one tile a step: a weight gradient's rows are summed
    inside one float32 product in place of four partial sums, so to
    rounding."""
    ids, _ = _loaded_ids(load, tile)
    args = [_f(a) for a in _loaded_args(ids)]
    got = _value_and_gradients(ids, args, tile)
    monkeypatch.setattr(llm, "EXPERT_TILES_A_STEP", 1)
    for g, w in zip(got, _value_and_gradients(ids, args, tile)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


def _equations(jaxpr, name):
    """Every equation of that primitive, the loops' bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, name)


def test_a_big_step_adds_its_rows_a_tile_at_a_time():
    """On the CPU platform the loops add through XLA's scatter-add; a big
    step's rows go ``tile`` at a time, as on a chip, where
    ``_kernel_adds_rows`` goes by the tile: no scatter-add of the backward
    pass takes more rows, and a big step's four are there for ``dx`` and
    for the routing weights' gradient."""
    tile = 8
    ids, _ = _loaded_ids("four_and_five_tiles", tile)
    args = [_f(a) for a in _loaded_args(ids)]
    jaxpr = jax.make_jaxpr(
        lambda *a: _value_and_gradients(ids, a, tile))(*args).jaxpr
    added = [eqn.invars[1].aval.shape for eqn in
             _equations(jaxpr, "scatter-add")]
    rows = [shape[0] for shape in added if len(shape) == 2]
    # forward (once in the value, once under the gradient): one a tile;
    # backward: a single tile's two and a big step's four times two
    assert rows == [tile] * len(rows)
    assert len(rows) == 2 * 1 + 2 + 2 * llm.EXPERT_TILES_A_STEP
    # and a step of either kind writes each weight gradient once
    assert len([shape for shape in added if shape == (1,)]) == 3 + 3
    assert len(list(_equations(jaxpr, "while"))) == 2 + 2


def _routing(name, tokens=50):
    """(ids, weights) over 12 experts, 3 a token; experts 4..7 are held."""
    m = _moe_inputs(tokens=tokens, held=12)
    if name == "one_held_expert":
        return np.tile(np.array([[6, 1, 11]]), (tokens, 1)), \
            _rs(3).rand(tokens, 3)
    bias = m["bias"].copy()
    if name == "an_idle_held_expert":
        bias[5] = -10.0                         # never among the largest
    ids, weights = llm.moe_route(_f(m["x"]), _f(m["router"]), _f(bias), k=3,
                                 scale=2.5)
    return np.asarray(ids), np.asarray(weights)


ROUTINGS = pytest.mark.parametrize(
    "routing", ["random", "one_held_expert", "an_idle_held_expert"])


@ROUTINGS
@pytest.mark.parametrize("tile", [4, 16, 64])
def test_what_the_combine_relies_on_is_true(routing, tile):
    """``_add_rows_kernel`` keeps many rows' read-add-write in flight at
    once, which is right only while no two of them name the same row of
    the sum (a CPU run cannot tell: XLA's scatter-add, the path there,
    adds rows that meet one after the other).  In every tile in use the
    real rows come first, ``n`` of them, their tokens strictly increase,
    so no two meet, and the padding rows name a row past the end; the same
    holds for the flat pair indices the routing weights' gradient is
    scattered with."""
    ids, weights = _routing(routing)
    tokens, k = ids.shape
    row_pair, tile_expert, n_tiles, counts = llm.expert_tiles(
        jnp.asarray(ids, jnp.int32), 4, 4, tile)
    if routing == "an_idle_held_expert":
        assert np.asarray(counts)[1] == 0 and np.asarray(counts).sum() > 0
    assert int(n_tiles) == sum(-(-c // tile) for c in np.asarray(counts))
    flat = _f(weights).reshape(-1)
    seen = []
    for t in range(int(n_tiles)):
        n, tok, at, pairs, gate = (np.asarray(a) for a in llm._tile_rows(
            t, row_pair, flat, k, tile))
        real = np.arange(tile) < n
        assert 0 < n <= tile
        for index, size in ((at, tokens), (pairs, tokens * k)):
            assert (np.diff(index[real]) > 0).all()     # no two rows meet
            assert (index[real] >= 0).all() and (index[real] < size).all()
            assert (index[~real] >= size).all()         # dropped
        np.testing.assert_array_equal(at[real], pairs[real] // k)
        np.testing.assert_array_equal(tok[real], at[real])
        assert (tok[~real] == 0).all() and (gate[~real] == 0).all()
        assert (ids.reshape(-1)[pairs[real]] == 4 + int(tile_expert[t])).all()
        seen.extend(pairs[real])
    held = (ids.reshape(-1) >= 4) & (ids.reshape(-1) < 8)
    assert sorted(seen) == list(np.flatnonzero(held))   # every pair, once


@pytest.mark.parametrize("tokens,tile", [(40, 8), (8, 64), (37, 16),
                                         (200, 8)],
                         ids=["loads_cross_a_tile", "mostly_padding",
                              "tokens_no_block_divides",
                              "big_steps_and_single_tiles"])
def test_the_kernel_adds_what_the_scatter_adds(monkeypatch, tokens, tile):
    """The accelerator's path through the interpreter (``_add_rows_kernel``
    on a ``(tokens, units / 128, 128)`` sum; the CPU platform otherwise
    takes XLA's scatter-add): the op's value and its five gradients are
    the scatter path's to rounding, with tiles that fill, tiles the padding
    cuts short, tiles that are nearly all padding, and big steps, which
    call the kernel once a tile of theirs (200 tokens: 6-7 tiles of 8 an
    expert, one big step and two or three single tiles);
    the finished sum comes back through ``_summed_rows``' kernel, or,
    where no block of 8 tokens divides it, through XLA's reshape."""
    m = _moe_inputs(tokens=tokens, hidden=256, width=8)
    ids, weights = np_route(m["x"], m["router"], m["bias"], 3, 2.5)
    args = [_f(a) for a in (m["x"], weights, m["wg"], m["wu"], m["wd"])]
    want = _value_and_gradients(ids, args, tile)
    monkeypatch.setattr(llm, "_kernel_adds_rows", lambda units, tile: True)
    lowered = jax.jit(lambda x: llm.moe_experts(
        x, jnp.asarray(ids, jnp.int32), *args[1:], first_expert=4,
        tile=tile)[0]).lower(args[0]).as_text()
    assert "x2x128xf32" in lowered              # the sum, three axes
    for got, w in zip(_value_and_gradients(ids, args, tile), want):
        np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-4)


def _distinct_within_a_token(ids):
    ids = np.sort(np.asarray(ids), axis=-1)
    return bool((np.diff(ids, axis=-1) != 0).all())


@ROUTINGS
def test_a_token_chooses_an_expert_at_most_once(routing):
    """``moe_experts``' precondition (its docstring): ``moe_route``'s ids
    meet it, being a ``top_k``'s, also where scores tie; so do the ids
    every test of this file (the ones tests/test_op_sweep.py names for
    ``_contrib_moe_experts``) hands the op."""
    assert _distinct_within_a_token(_routing(routing)[0])
    tied, _ = llm.moe_route(jnp.zeros((5, 8)), jnp.zeros((12, 8)),
                            jnp.zeros(12), k=3)
    assert _distinct_within_a_token(tied)
    m = _moe_inputs()
    assert _distinct_within_a_token(
        np_route(m["x"], m["router"], m["bias"], 3, 2.5)[0])
    assert not _distinct_within_a_token([[1, 4, 1]])


def test_the_bias_changes_the_selection_and_not_the_weights():
    m = _moe_inputs()
    x, w = _f(m["x"]), _f(m["router"])
    ids0, w0 = llm.moe_route(x, w, jnp.zeros(12), k=3, scale=2.5)
    bias = np.zeros(12, np.float32)
    bias[5] = 10.0                              # expert 5 is always chosen
    ids1, w1 = llm.moe_route(x, w, _f(bias), k=3, scale=2.5)
    assert (np.asarray(ids1) == 5).any(axis=1).all()
    assert not (np.asarray(ids0) == 5).any(axis=1).all()
    s = 1 / (1 + np.exp(-(m["x"] @ m["router"].T)))
    picked = np.take_along_axis(s, np.asarray(ids1), -1)
    np.testing.assert_allclose(
        np.asarray(w1), picked / picked.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 2.5, rtol=1e-5)
    # where the bias changed nothing of the selection, nor did the weights
    same = (np.asarray(ids0) == np.asarray(ids1)).all(axis=1)
    np.testing.assert_array_equal(np.asarray(w0)[same], np.asarray(w1)[same])


# ------------------------------------- flash attention, v of another size


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d,dv", [(48, 32), (32, 64)],
                         ids=["v_smaller", "v_larger"])
def test_flash_attention_with_another_value_head_size(causal, d, dv):
    rs = _rs(2)
    q, k = _f(rs.randn(1, 2, 128, d)), _f(rs.randn(1, 2, 128, d))
    v = _f(rs.randn(1, 2, 128, dv))

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64,
                               block_k=64, interpret=True)

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=causal)

    out = kernel(q, k, v)
    assert out.shape == (1, 2, 128, dv)
    np.testing.assert_allclose(out, plain(q, k, v), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(kernel(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_flash_attention_at_the_timed_blocks_and_head_sizes():
    """Latent attention's head sizes (192 for the scores, 128 for the
    values) at the blocks float32 takes (256 / 512: the cell's check runs
    them), causal, over four query and two key blocks: unequal blocks, a
    diagonal that crosses a key block, key blocks skipped above it;
    forward, dq and dkv."""
    from mxnet_tpu.ops.attention import _block_choices

    rs = _rs(3)
    q, k = _f(rs.randn(1, 1, 1024, 192)), _f(rs.randn(1, 1, 1024, 192))
    v = _f(rs.randn(1, 1, 1024, 128))
    assert _block_choices(q, v)[0] == (256, 512)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=True)

    with jax.default_matmul_precision("highest"):
        out = kernel(q, k, v)
        np.testing.assert_allclose(out, plain(q, k, v), rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(kernel(*a))),
                       (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))),
                        (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


# ------------------------- rotary scaling as data, a softmax router (PR 34)


def _yarn_closed_form(dim, theta, factor, original, fast, slow):
    """ISSUE 34's closed form, written again here."""
    i = np.arange(dim // 2, dtype=np.float64)
    extrap = theta ** (-2 * i / dim)

    def c(r):
        return dim * np.log(original / (2 * np.pi * r)) / (2 * np.log(theta))

    low, high = max(np.floor(c(fast)), 0), min(np.ceil(c(slow)), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return extrap / factor * ramp + extrap * (1 - ramp), (low, high)


def test_yarns_table_is_the_closed_form_past_the_original_length():
    """The published full layers' numbers: the ramp runs over the pairs 18
    .. 35 of 64, and at 8 positions past 8,192 ``cos`` and ``sin`` are the
    closed form's times the amplitude, on both lanes of a pair."""
    how = dict(rope_type="yarn", rope_theta=500000, factor=16,
               original_max_position_embeddings=8192, beta_fast=32,
               beta_slow=1, attention_factor=1.2772588722239782)
    inv, amplitude = llm.rotary_frequencies(128, **how)
    want, (low, high) = _yarn_closed_form(128, 5e5, 16, 8192, 32, 1)
    assert (low, high) == (18, 35)
    np.testing.assert_allclose(inv, want, rtol=1e-14)
    assert inv[:19] == tuple(5e5 ** (-2 * np.arange(19) / 128))
    np.testing.assert_allclose(inv[35:], 5e5 ** (-2 * np.arange(35, 64) / 128)
                               / 16, rtol=1e-14)
    assert amplitude == pytest.approx(0.1 * np.log(16) + 1, rel=1e-15)
    assert llm.rotary_frequencies(128, **dict(how, attention_factor=None))[1] \
        == pytest.approx(amplitude, rel=1e-15)
    positions = 8192 + np.array([1, 2, 3, 5, 8, 13, 21, 34])
    cos, sin = llm._rotary_tables(8192 + 35, 128, None, True, inv, amplitude)
    angle = positions[:, None] * want[None, :]
    for half in (slice(0, 64), slice(64, 128)):
        np.testing.assert_allclose(np.asarray(cos)[positions, half],
                                   amplitude * np.cos(angle), atol=2e-7)
    np.testing.assert_allclose(np.asarray(sin)[positions, :64],
                               -amplitude * np.sin(angle), atol=2e-7)
    np.testing.assert_allclose(np.asarray(sin)[positions, 64:],
                               amplitude * np.sin(angle), atol=2e-7)
    # the default type is theta's frequencies, and theta alone builds them
    plain, one = llm.rotary_frequencies(128, rope_type="default",
                                        rope_theta=500000)
    assert one == 1.0
    for a, b in zip(llm._rotary_tables(64, 128, 500000, True),
                    llm._rotary_tables(64, 128, None, True, plain, one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="rope_type"):
        llm.rotary_frequencies(128, rope_type="linear")
    with pytest.raises(ValueError, match="frequencies"):
        llm._rotary_tables(8, 128, None, True, inv[:5])


def test_rope_and_gqa_qkv_take_the_layers_frequencies_and_amplitude():
    """``rope(inv_freq=, amplitude=)`` is the rotation by those angles
    times the amplitude, its gradient the rotation back times the same;
    ``gqa_qkv`` hands them to the same table builder."""
    rng = np.random.RandomState(3)
    x = _f(rng.randn(2, 40, 16))
    inv, amplitude = llm.rotary_frequencies(
        16, rope_type="yarn", rope_theta=1e4, factor=4,
        original_max_position_embeddings=32, beta_fast=2, beta_slow=0.25)
    angle = np.arange(40)[:, None] * np.asarray(inv)[None, :]
    a, b = np.asarray(x)[..., :8], np.asarray(x)[..., 8:]
    want = amplitude * np.concatenate(
        [a * np.cos(angle) - b * np.sin(angle),
         b * np.cos(angle) + a * np.sin(angle)], axis=-1)
    got = llm.rope(x, halves=True, inv_freq=inv, amplitude=amplitude)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert np.abs(np.asarray(llm.rope(x, theta=1e4, halves=True))
                  - want).max() > 0.1
    g = _f(rng.randn(2, 40, 16))
    back = jax.vjp(lambda x: llm.rope(x, halves=True, inv_freq=inv,
                                      amplitude=amplitude), x)[1](g)[0]
    ga, gb = np.asarray(g)[..., :8], np.asarray(g)[..., 8:]
    np.testing.assert_allclose(back, amplitude * np.concatenate(
        [ga * np.cos(angle) + gb * np.sin(angle),
         gb * np.cos(angle) - ga * np.sin(angle)], axis=-1),
        rtol=2e-5, atol=2e-6)
    w = [_f(0.3 * rng.randn(n, 16)) for n in (32, 16, 16)]
    ones = jnp.ones(16)
    q, k, _ = llm.gqa_qkv(x, *w, ones, ones, inv_freq=inv,
                          amplitude=amplitude)
    q0, k0, _ = llm.gqa_qkv(x, *w, ones, ones, theta=1e4)
    np.testing.assert_allclose(q[:, :, 0], amplitude * q0[:, :, 0],
                               rtol=1e-5, atol=1e-6)   # position 0: no angle
    assert np.abs(np.asarray(k) - amplitude * np.asarray(k0)).max() > 0.05


def _softmax_route(x, w, k):
    """Plain: probabilities over all experts, the k largest, normalised;
    the balancing term experts x sum_e f_e P_e."""
    experts = w.shape[0]
    p = jax.nn.softmax(x @ w.T, axis=-1)
    picked, ids = jax.lax.top_k(p, k)
    f = jnp.mean(jax.nn.one_hot(ids.reshape(-1), experts), axis=0)
    term = experts * jnp.sum(jax.lax.stop_gradient(f) * jnp.mean(p, axis=0))
    return ids, picked / jnp.sum(picked, axis=-1, keepdims=True), term


def test_a_softmax_router_and_its_balancing_term():
    """``moe_route(scoring="softmax")`` without a bias: ids, weights and,
    with ``balance``, the term's value and its gradient (through ``P``
    alone: ``f`` is a count) against the plain computation."""
    rng = np.random.RandomState(11)
    x, w = _f(rng.randn(48, 16)), _f(0.5 * rng.randn(12, 16))
    ids, weights, term = llm.moe_route(x, w, k=3, eps=0.0, scoring="softmax",
                                       balance=True)
    want_ids, want_weights, want_term = _softmax_route(x, w, 3)
    np.testing.assert_array_equal(ids, want_ids)
    assert ids.dtype == jnp.int32 and term.shape == ()
    np.testing.assert_allclose(weights, want_weights, rtol=1e-6)
    np.testing.assert_allclose(np.sum(weights, axis=-1), 1.0, rtol=1e-6)
    assert float(term) == pytest.approx(float(want_term), rel=1e-6)
    assert 1.0 < float(term) < 2.0          # 1 when the experts are level
    two = llm.moe_route(x, w, k=3, eps=0.0, scoring="softmax")
    assert len(two) == 2
    np.testing.assert_array_equal(two[0], ids)

    def value(route):
        def f(x, w):
            _, weights, term = route(x, w)
            return term + jnp.sum(weights[:, 0])
        return jax.grad(f, argnums=(0, 1))(x, w)

    got = value(lambda x, w: llm.moe_route(x, w, k=3, eps=0.0,
                                           scoring="softmax", balance=True))
    want = value(lambda x, w: _softmax_route(x, w, 3))
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 1e-3
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    # the term alone moves the router: popular experts' rows go down
    alone = jax.grad(lambda w: llm.moe_route(
        x, w, k=3, eps=0.0, scoring="softmax", balance=True)[2])(w)
    assert np.abs(np.asarray(alone)).max() > 1e-4
    # level experts: every expert chosen and scored alike gives 1
    level = llm.moe_route(jnp.tile(jnp.eye(12), (4, 1)), 5 * jnp.eye(12),
                          k=1, scoring="softmax", balance=True)[2]
    assert float(level) == pytest.approx(1.0, rel=1e-6)
    # a sigmoid router's term uses its scores; an unknown scoring raises
    assert len(llm.moe_route(x, w, jnp.zeros(12), k=3, balance=True)) == 3
    with pytest.raises(ValueError, match="scoring"):
        llm.moe_route(x, w, k=3, scoring="tanh")
    op = mx.nd.contrib.moe_route(mx.nd.array(np.asarray(x)),
                                 mx.nd.array(np.asarray(w)), None, k=3,
                                 eps=0.0, scoring="softmax", balance=True)
    assert len(op) == 3
    assert float(op[2].asnumpy()) == pytest.approx(float(want_term), rel=1e-6)


# ------------------------------- the head fused with its loss, PR 38


def _recomputing_linear_ce(data, weight, label, chunk=llm.DEFAULT_LOSS_CHUNK):
    """The op before PR 38, kept as a reference: a chunk's logits under
    ``jax.checkpoint``, recomputed in the backward pass (four products a
    chunk under ``value_and_grad``)."""
    rows = data.shape[0]
    chunk = rows if rows <= chunk else math.gcd(rows, chunk)

    @jax.checkpoint
    def one(args):
        h, y = args
        logits = jax.lax.dot_general(h, weight, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(y >= 0, logz - picked, 0.0)

    return jax.lax.map(one, (data.reshape(rows // chunk, chunk, -1),
                             label.reshape(rows // chunk, chunk))
                       ).reshape(rows)


def np_linear_ce_grads(h, w, label, g):
    """``dh``, ``dW`` of ``sum(g * rows)``: ``dlogits = g (softmax -
    onehot)``, 0 where the label is negative."""
    logits = h @ w.T
    p = np.exp(logits - logits.max(-1, keepdims=True))
    d = p / p.sum(-1, keepdims=True)
    d[np.arange(len(label)), np.maximum(label, 0)] -= 1.0
    d *= np.where(label >= 0, g, 0.0)[:, None]
    return d @ w, d.T @ h


def _next_token_rows(batch, seq, classes, ahead=1, seed=3):
    """``_head_loss``'s labels: token ``i + ahead`` labels position ``i``,
    a row's last ``ahead`` positions -1; some more -1 besides."""
    rs = _rs(seed)
    tokens = rs.randint(0, classes, (batch, seq))
    labels = np.concatenate([tokens[:, ahead:],
                             np.full((batch, ahead), -1)], axis=1)
    labels[rs.rand(batch, seq) < 0.2] = -1
    return labels.reshape(-1)


def _step_loss(op, h, w, label, batch, ahead=1, scale=1.0):
    """What ``GluonTrainStep`` differentiates: the mean over rows of
    ``_head_loss``'s per-row value, a row's sum times a constant."""
    seq = h.shape[0] // batch
    rows = op(h, w, jnp.asarray(label))
    return jnp.mean(jnp.sum(rows.reshape(batch, seq), axis=1)
                    * (scale / (seq - ahead)))


# (batch, seq, chunk): 40 rows in chunks of 8 (16 does not divide them),
# 48 in three of 16, 12 in one
SHAPES = [(2, 20, 16), (3, 16, 16), (1, 12, 16)]


@pytest.mark.parametrize("batch,seq,chunk", SHAPES)
def test_the_gradient_under_the_steps_cotangent(batch, seq, chunk):
    """float32, the step's own cotangent (one value on every row), labels
    of -1 present, against the numpy form."""
    rs = _rs(4)
    h, w = rs.randn(batch * seq, 8), rs.randn(24, 8)
    label = _next_token_rows(batch, seq, 24)
    assert (label < 0).any() and (label >= 0).any()
    op = functools.partial(llm.linear_cross_entropy, chunk=chunk)
    value, (dh, dw) = jax.value_and_grad(
        lambda h, w: _step_loss(op, h, w, label, batch),
        argnums=(0, 1))(_f(h), _f(w))
    g = np.full(batch * seq, 1.0 / (batch * (seq - 1)))
    assert float(value) == pytest.approx(
        float(np.sum(np_linear_ce(h, w, label) * g)), rel=1e-5)
    want_dh, want_dw = np_linear_ce_grads(h, w, label, g)
    np.testing.assert_allclose(dh, want_dh, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-7)
    assert dh.dtype == dw.dtype == jnp.float32


@pytest.mark.parametrize("batch,seq,chunk", SHAPES)
def test_the_gradient_in_bfloat16_is_the_recomputing_ops(batch, seq, chunk):
    """bfloat16 operands: the gradient formed in the forward pass against
    the parent's formulation, within bfloat16's rounding (the two round
    ``dlogits`` and the products' results at other points)."""
    rs = _rs(6)
    h = jnp.asarray(rs.randn(batch * seq, 16), jnp.bfloat16)
    w = jnp.asarray(0.5 * rs.randn(40, 16), jnp.bfloat16)
    label = _next_token_rows(batch, seq, 40)

    def grads(op):
        return jax.value_and_grad(
            lambda h, w: _step_loss(functools.partial(op, chunk=chunk),
                                    h, w, label, batch, scale=0.3),
            argnums=(0, 1))(h, w)

    (value, got), (want_value, want) = (grads(llm.linear_cross_entropy),
                                        grads(_recomputing_linear_ce))
    assert float(value) == pytest.approx(float(want_value), rel=1e-6)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=2 ** -6,
                                   atol=2 ** -7 * np.abs(b).max())


def test_one_weight_in_two_calls_sums_their_gradients():
    """``MultiTokenLoss`` applies one head to two streams, a tied head is
    the embedding too: every path's gradient of the weight adds up."""
    rs = _rs(8)
    batch, seq, classes = 2, 12, 20
    w, h = _f(rs.randn(classes, 8)), _f(rs.randn(batch * seq, 8))
    tokens = jnp.asarray(rs.randint(0, classes, batch * seq))
    main, ahead = (_next_token_rows(batch, seq, classes, a, seed=a)
                   for a in (1, 2))

    def loss(op, w, h):
        embedded = w[tokens] + h                 # the tied embedding
        return _step_loss(op, embedded, w, main, batch) \
            + _step_loss(op, h, w, ahead, batch, ahead=2, scale=0.3)

    for chunk in (8, 24):
        got = jax.grad(lambda w, h: loss(functools.partial(
            llm.linear_cross_entropy, chunk=chunk), w, h), (0, 1))(w, h)
        want = jax.grad(lambda w, h: loss(functools.partial(
            _recomputing_linear_ce, chunk=chunk), w, h), (0, 1))(w, h)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("cotangent", ["one_value", "zero",
                                       "other_on_unlabelled_rows",
                                       "a_value_a_row"])
def test_every_cotangent_gives_the_numpy_gradient(cotangent):
    """The backward pass scales the kept gradient where the cotangent is
    one value over the labelled rows (whatever it is on the others) and
    recomputes where it is not: exact either way."""
    rs = _rs(10)
    h, w = rs.randn(32, 8), rs.randn(12, 8)
    label = _next_token_rows(4, 8, 12)
    g = {"one_value": np.full(32, 0.37), "zero": np.zeros(32),
         "other_on_unlabelled_rows": np.where(label >= 0, 0.37, 5.0),
         "a_value_a_row": rs.randn(32)}[cotangent]
    rows, vjp = jax.vjp(lambda h, w: llm.linear_cross_entropy(
        h, w, jnp.asarray(label), chunk=8), _f(h), _f(w))
    np.testing.assert_allclose(rows, np_linear_ce(h, w, label), rtol=2e-5,
                               atol=2e-5)
    dh, dw = vjp(_f(g))
    want_dh, want_dw = np_linear_ce_grads(h, w, label, g)
    np.testing.assert_allclose(dh, want_dh, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(dw, want_dw, rtol=2e-5, atol=1e-6)


def _products(text):
    """The ``dot`` instructions of an optimized HLO module."""
    return [line for line in text.splitlines()
            if re.search(r"= \S+ dot\(", line)]


def test_the_forward_alone_holds_one_product_a_chunk():
    """Not differentiated (evaluation, the imperative op): a loop over the
    chunks whose body holds one product, and nothing else kept."""
    rs = _rs(12)
    h, w = _f(rs.randn(40, 8)), _f(rs.randn(24, 8))
    label = jnp.asarray(_next_token_rows(2, 20, 24))
    lowered = jax.jit(lambda h, w: llm.linear_cross_entropy(
        h, w, label, chunk=16)).lower(h, w)
    text = lowered.as_text()
    assert text.count("stablehlo.dot_general") == 1
    assert text.count("stablehlo.while") == 1
    assert len(_products(lowered.compile().as_text())) == 1


def test_the_steps_gradient_compiles_without_the_recomputation():
    """Under the step's cotangent the compiled gradient holds three
    products (the logits, ``dh``, ``dW``), all under ``lm_head``, none
    under ``lm_head.recompute``, and no conditional: XLA sees the
    cotangent is one value.  Under a cotangent that differs between rows
    the recomputation is there, for ``dW`` alone: the logits and one
    product (``dh`` is the kept one times the cotangent, exact for any)."""
    rs = _rs(14)
    h, w = _f(rs.randn(40, 8)), _f(rs.randn(24, 8))
    label = _next_token_rows(2, 20, 24)
    op = functools.partial(llm.linear_cross_entropy, chunk=16)
    step = jax.jit(jax.grad(lambda h, w: _step_loss(op, h, w, label, 2),
                            argnums=(0, 1))).lower(h, w).compile().as_text()
    products = _products(step)
    assert len(products) == 3
    assert all("lm_head" in p and "lm_head.recompute" not in p
               for p in products)
    assert "conditional(" not in step
    other = jax.jit(jax.grad(lambda h, w, g: jnp.sum(op(h, w, jnp.asarray(
        label)) * g), argnums=(0, 1))).lower(
            h, w, _f(rs.randn(40))).compile().as_text()
    assert "conditional(" in other
    assert len([p for p in _products(other)
                if "lm_head.recompute" in p]) == 2


# --------------------- the gated-SiLU feed-forward's backward pass


def _plain_gated_silu(x, wg, wu, wd):
    """The formula left to autodiff: what ``gated_silu`` was before its
    backward pass was written by hand."""
    last = (x.ndim - 1,)
    g = llm._dot(x, wg, (last, (1,)))
    u = llm._dot(x, wu, (last, (1,)))
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return llm._dot(h, wd, (last, (1,))).astype(x.dtype)


def _gated_silu_args(rows, units=16, hidden=24, seed=15):
    rs = _rs(seed)
    return (rs.randn(*rows, units), rs.randn(hidden, units) * 0.3,
            rs.randn(hidden, units) * 0.3, rs.randn(units, hidden) * 0.3,
            rs.randn(*rows, units))


@pytest.mark.parametrize("rows", [(2, 5), (7,)], ids=["batch", "no_batch"])
def test_the_gated_silu_backward_in_float32_is_autodiffs(rows):
    """In float32 the rounding to the data's type is none: the gradients of
    ``x`` and of the three weights are autodiff's of the plain formula, up
    to the order of the sums."""
    x, wg, wu, wd, dy = (_f(a) for a in _gated_silu_args(rows))
    with jax.default_matmul_precision("highest"):
        got = jax.vjp(llm.gated_silu, x, wg, wu, wd)[1](dy)
        want = jax.vjp(_plain_gated_silu, x, wg, wu, wd)[1](dy)
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-5)


def _bf16(a):
    """``a`` rounded to bfloat16, as float64."""
    return np.asarray(jnp.asarray(np.asarray(a, np.float32), jnp.bfloat16),
                      np.float64)


@pytest.mark.parametrize("rows", [(3, 16), (40,)], ids=["batch", "no_batch"])
def test_the_gated_silu_backward_in_bfloat16_rounds_dg_du_and_h(rows):
    """bfloat16 in: every product takes operands in bfloat16 and sums in
    float32, ``dh`` stays float32; ``dg``, ``du`` and ``h`` are each rounded
    to bfloat16 once, before their products (numpy, float64 between the
    roundings); the two halves of ``dx`` are summed before one rounding.
    Results in bfloat16: at least 99 % of them the reference's to the
    bit (left to autodiff, ``dg`` and ``du`` unrounded, about half), and
    every one within two of its ulps of the largest value."""
    x, wg, wu, wd, dy = (_bf16(a) for a in _gated_silu_args(
        rows, units=32, hidden=48))
    got = jax.vjp(llm.gated_silu, *(jnp.asarray(a, jnp.bfloat16)
                                     for a in (x, wg, wu, wd)))[1](
        jnp.asarray(dy, jnp.bfloat16))
    g, u = x @ wg.T, x @ wu.T
    sig = 1 / (1 + np.exp(-g))
    h = _bf16(g * sig * u)
    dh = dy @ wd
    dg, du = _bf16(dh * u * sig * (1 + g * (1 - sig))), _bf16(dh * g * sig)
    flat = (-1, x.shape[-1])
    want = (dg @ wg + du @ wu, dg.reshape(-1, 48).T @ x.reshape(flat),
            du.reshape(-1, 48).T @ x.reshape(flat),
            dy.reshape(flat).T @ h.reshape(-1, 48))
    for a, w in zip(got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == w.shape
        a = np.asarray(a, np.float64)
        assert np.mean(a == _bf16(w)) >= 0.99
        assert np.abs(a - _bf16(w)).max() <= 2 * 2.0 ** -8 * np.abs(w).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_gated_ffn_recomputed_gives_the_gradients_it_gives_outside(dtype):
    """``GatedFFN`` in a ``recomputed`` block (``jax.checkpoint``, as every
    decoder block of the language cells is staged): its forward runs again
    in the backward pass and the hand-written backward pass takes what
    that run forms; the gradients of the input and of the three weights
    are the ones the block gives outside the recomputation."""
    from mxnet_tpu.gluon.block import recomputed, staged_call
    from mxnet_tpu.gluon.nn import GatedFFN
    from mxnet_tpu.ndarray import NDArray

    ffn = GatedFFN(16, 24, weight_std=0.3, prefix="recomputed_ffn_")
    ffn.initialize(ctx=mx.cpu())
    params = list(ffn.collect_params().values())
    rs = _rs(16)
    cast = jnp.dtype(dtype)
    x = jnp.asarray(rs.randn(2, 5, 16), cast)
    dy = jnp.asarray(rs.randn(2, 5, 16), jnp.float32)
    values = [p.data().data_jax.astype(cast) for p in params]

    def loss(again, x, values):
        def block(h):
            return recomputed(ffn, h) if again else ffn(h)

        out, _ = staged_call(block, {p: NDArray(v)
                                     for p, v in zip(params, values)},
                             None, [NDArray(x)])
        return jnp.sum(out._data.astype(jnp.float32) * dy)

    grads = {again: jax.jit(jax.grad(functools.partial(loss, again),
                                     argnums=(0, 1)))(x, values)
             for again in (False, True)}
    assert " remat2[" in str(jax.make_jaxpr(
        jax.grad(functools.partial(loss, True)))(x, values))
    tol = 1e-6 if dtype == "float32" else 1e-2
    for a, w in zip(jax.tree.leaves(grads[True]),
                    jax.tree.leaves(grads[False])):
        assert a.dtype == cast
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)
