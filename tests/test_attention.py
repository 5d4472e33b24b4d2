"""Flash-attention kernel tests (pallas interpret mode on CPU).

Mirrors the reference op-test style (tests/python/unittest/test_operator.py):
forward vs an unfused numpy/jnp reference, gradients vs jax.grad of the
reference.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import attention as att


def _rand(shape, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


# (query heads, key heads): equal, and grouped-query with four a key head
HEADS = pytest.mark.parametrize("h,hk", [(3, 3), (4, 1)],
                                ids=["equal_heads", "group_of_4"])


@HEADS
@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal, h, hk):
    b, s, d = 2, 256, 64
    q = _rand((b, h, s, d), seed=0)
    k, v = (_rand((b, hk, s, d), seed=i) for i in (1, 2))
    ref = att.mha_reference(q, k, v, causal=causal)
    out = att.flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h,hk,d", [(2, 2, 32), (8, 2, 64)],
                         ids=["equal_heads", "two_groups_of_4"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal, h, hk, d):
    """Grouped-query: ``mha_reference`` repeats the key heads and autodiff
    sums their gradients over a group; the dk/dv kernel sums in its
    accumulators and returns the key heads' shape."""
    b, s = 1, 128
    q = _rand((b, h, s, d), seed=10)
    k, v = (_rand((b, hk, s, d), seed=10 + i) for i in (1, 2))

    def loss_flash(q, k, v):
        o = att.flash_attention(q, k, v, causal=causal, interpret=True,
                                block_q=64, block_k=64)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = att.mha_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_flash_reads_grouped_key_heads_where_they_lie():
    """No copy of a key head is made for its group's query heads: the three
    kernels take ``k`` and ``v`` with the key heads' rows (the index maps
    find a query head's key head), dk and dv leave with them, and heads
    that do not divide raise."""
    q, g = (_rand((2, 8, 256, 64), seed=i) for i in (0, 3))
    k, v = (_rand((2, 2, 256, 64), seed=i) for i in (1, 2))
    jaxpr = jax.make_jaxpr(functools.partial(
        _with_grads, att.flash_attention, True, interpret=True,
        block_q=128, block_k=128))(q, k, v, g).jaxpr
    kernels = _equations(jaxpr, "pallas_call")
    assert len(kernels) == 3
    for kernel in kernels:
        shapes = [x.aval.shape for x in kernel.invars]
        assert shapes[:3] == [(16, 256, 64), (4, 256, 64), (4, 256, 64)]
    assert [x.aval.shape for x in kernels[2].outvars] == [(4, 256, 64)] * 2
    with pytest.raises(mx.MXNetError, match="not a multiple"):
        att.flash_attention(q[:, :3], k, v, interpret=True)


def test_flash_rectangular_kv():
    # cross-attention: klen != qlen
    b, h, sq, sk, d = 1, 2, 128, 256, 32
    q = _rand((b, h, sq, d), seed=1)
    k = _rand((b, h, sk, d), seed=2)
    v = _rand((b, h, sk, d), seed=3)
    ref = att.mha_reference(q, k, v)
    out = att.flash_attention(q, k, v, interpret=True,
                              block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fallback_path_off_tpu():
    # ragged seq → falls back to the XLA reference path (still correct)
    b, h, s, d = 1, 1, 100, 16
    q, k, v = (_rand((b, h, s, d), seed=20 + i) for i in range(3))
    out = att.flash_attention(q, k, v, causal=True)
    ref = att.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_registered_contrib_ops():
    import mxnet_tpu as mx

    # flash attention through the op registry / nd namespace
    q = mx.nd.random.normal(shape=(1, 2, 64, 16))
    k = mx.nd.random.normal(shape=(1, 2, 64, 16))
    v = mx.nd.random.normal(shape=(1, 2, 64, 16))
    out = mx.nd.contrib.flash_attention(q, k, v)
    assert out.shape == (1, 2, 64, 16)

    # div_sqrt_dim
    x = mx.nd.ones((2, 16))
    y = mx.nd.contrib.div_sqrt_dim(x)
    np.testing.assert_allclose(y.asnumpy(), np.ones((2, 16)) / 4.0,
                               rtol=1e-6)


def test_interleaved_matmul_selfatt():
    s, b, heads, d = 8, 2, 2, 4
    proj = heads * d
    qkv = _rand((s, b, 3 * proj), seed=5)
    from mxnet_tpu.ops.registry import apply_op
    scores = apply_op("_contrib_interleaved_matmul_selfatt_qk", qkv,
                      heads=heads)
    assert scores.shape == (b * heads, s, s)
    attn = jax.nn.softmax(scores, axis=-1)
    out = apply_op("_contrib_interleaved_matmul_selfatt_valatt",
                   qkv, attn, heads=heads)
    assert out.shape == (s, b, proj)
    # numpy check of qk
    x = np.asarray(qkv).reshape(s, b, heads, 3, d)
    q = x[:, :, :, 0, :].transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    kk = x[:, :, :, 1, :].transpose(1, 2, 0, 3).reshape(b * heads, s, d)
    want = np.einsum("zqd,zkd->zqk", q, kk)
    np.testing.assert_allclose(np.asarray(scores), want, rtol=1e-4, atol=1e-4)


def test_flash_interpret_ragged_seq_falls_back_correctly():
    """ADVICE r3: interpret mode must apply the same divisibility check
    as hardware — a ragged seq the blocks do not tile would otherwise
    leave trailing output rows unwritten.  The public entry must produce
    correct values for ANY seq length: 300 is shorter than a block and
    runs as one block, 1100 is tiled by no block pair and falls back."""
    for s in (300, 1100):
        q, k, v = (_rand((1, 2, s, 32), seed=20 + i) for i in range(3))
        ref = att.mha_reference(q, k, v)
        out = att.flash_attention(q, k, v, interpret=True)  # chosen blocks
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # and the tiling check itself refuses ragged shapes
        assert not att._tiles(q, k, 256, 512)
        assert not att._tiles(q, k, 128, 128)
    assert not att._tiles(q, k, 1024, 1024)


def test_flash_ragged_seq_raises_off_cpu(monkeypatch):
    """On an accelerator a sequence the blocks cannot tile is an error,
    never a silent drop to materialised seq x seq scores (a sequence
    shorter than a block is one block, whatever its length)."""
    from mxnet_tpu.base import MXNetError

    monkeypatch.setattr(att, "pallas_interpret", lambda: False)
    q, k, v = (_rand((1, 1, 1100, 32), seed=i) for i in range(3))
    with pytest.raises(MXNetError, match="not tiled"):
        att.flash_attention(q, k, v)



# ------------------------------------------------ 16-bit operands (PR 29)


def _with_grads(attn, causal, q, k, v, g, **how):
    out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, causal=causal, **how),
                       q, k, v)
    return (out,) + vjp(g)


def _oracle(causal, *args):
    """out, dq, dk, dv of the unfused reference in float32 at highest
    matmul precision, on the arguments as they are rounded."""
    with jax.default_matmul_precision("highest"):
        return _with_grads(att.mha_reference, causal,
                           *(a.astype(jnp.float32) for a in args))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d,dv", [(64, 64), (192, 128)])
def test_flash_bfloat16_against_the_float32_oracle(causal, d, dv):
    """bfloat16 inputs go into the products as they are; what is lost is
    the rounding of the bfloat16 results (measured 2-4e-3 of the largest
    value, in the interpreter and on the chip: PERF.md, PR 29)."""
    s = 1024
    q, k = (_rand((1, 2, s, d), seed=40 + i).astype(jnp.bfloat16)
            for i in range(2))
    v, g = (_rand((1, 2, s, dv), seed=42 + i).astype(jnp.bfloat16)
            for i in range(2))
    got = _with_grads(att.flash_attention, causal, q, k, v, g, interpret=True)
    for name, a, w in zip(("out", "dq", "dk", "dv"), got,
                          _oracle(causal, q, k, v, g)):
        assert a.dtype == jnp.bfloat16 and a.shape == w.shape
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - w))
                    / jnp.max(jnp.abs(w)))
        assert err < 1e-2, (name, err)


def _equations(jaxpr, name):
    """Every equation called ``name`` in a jaxpr and the jaxprs under it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_equations(sub, name))
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_flash_products_take_their_operands_dtype(dtype):
    """The three kernels' products run in the dtype the inputs arrive in
    and accumulate in float32: no tile is cast up on its way to the MXU."""
    q, k = (_rand((1, 1, 512, 64), seed=i).astype(dtype) for i in range(2))
    v, g = (_rand((1, 1, 512, 32), seed=2 + i).astype(dtype)
            for i in range(2))
    jaxpr = jax.make_jaxpr(functools.partial(
        _with_grads, att.flash_attention, True, interpret=True,
        block_q=256, block_k=256))(q, k, v, g).jaxpr
    kernels = _equations(jaxpr, "pallas_call")
    assert len(kernels) == 3
    for kernel, products in zip(kernels, (2, 3, 4)):
        dots = _equations(kernel.params["jaxpr"], "dot_general")
        # the masked and the unmasked branch hold the products once each
        assert len(dots) == 2 * products
        for dot in dots:
            assert [x.aval.dtype for x in dot.invars] == [dtype, dtype]
            assert dot.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("blocks", [(1024, 1024), (256, 512), (128, 128)],
                         ids=lambda b: "%dx%d" % b)
@pytest.mark.parametrize("sq,sk", [(512, 1024), (1024, 512)])
def test_flash_causal_rectangular_at_the_blocks_it_chooses(sq, sk, blocks):
    """Top-left-aligned causal masking with more keys than queries and
    with fewer, at every block pair ``_block_choices`` can give."""
    d, dv = 48, 32
    q = _rand((1, 2, sq, d), seed=50)
    k, v = _rand((1, 2, sk, d), seed=51), _rand((1, 2, sk, dv), seed=52)
    g = _rand((1, 2, sq, dv), seed=53)
    assert blocks in att._block_choices(q.astype(jnp.bfloat16), v)
    got = _with_grads(att.flash_attention, True, q, k, v, g, interpret=True,
                      block_q=blocks[0], block_k=blocks[1])
    with jax.default_matmul_precision("highest"):
        want = _with_grads(att.mha_reference, True, q, k, v, g)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)


def test_flash_blocks_follow_the_operands_row_bytes():
    """1,024 / 1,024 where Mosaic's scoped VMEM takes it (PERF.md, PR 29),
    the 256 / 512 of the float32 kernels past that."""
    def first(d, dv, dtype):
        q = jax.ShapeDtypeStruct((1, 1, 4096, d), dtype)
        v = jax.ShapeDtypeStruct((1, 1, 4096, dv), dtype)
        return att._block_choices(q, v)[0]

    assert first(192, 128, jnp.bfloat16) == (1024, 1024)
    assert first(256, 256, jnp.bfloat16) == (1024, 1024)
    assert first(128, 128, jnp.float32) == (1024, 1024)
    assert first(192, 128, jnp.float32) == (256, 512)
    assert first(512, 512, jnp.bfloat16) == (256, 512)


# -------------------------------------------- the causal rule over blocks


@pytest.mark.parametrize("sq,sk", [(4096, 4096), (2048, 4096), (4096, 2048)],
                         ids=lambda n: str(n))
@pytest.mark.parametrize("block_q,block_k",
                         [(256, 512), (512, 512), (128, 128), (512, 256)])
def test_causal_index_maps_against_brute_force(block_q, block_k, sq, sk):
    """A step that runs names its own block; a skipped step names the block
    its neighbour that runs has fetched (the step before it in the forward
    and dq grids, the step after it in the dk/dv grid), so the blocks
    fetched in a row are as many as the steps that run."""
    num_q, num_k = sq // block_q, sk // block_k
    rows, cols = np.arange(sq)[:, None], np.arange(sk)[None, :]
    seen = (cols <= rows).reshape(num_q, block_q, num_k, block_k)
    runs = seen.any(axis=(1, 3))                      # brute force
    crosses = runs & ~seen.all(axis=(1, 3))
    for i in range(num_q):
        for j in range(num_k):
            assert bool(att._runs(i, j, block_q, block_k)) == runs[i, j]
            if runs[i, j]:
                assert bool(att._crosses_an_edge(
                    i, j, block_q, block_k)) == crosses[i, j]

    assert runs[:, 0].all()                 # key block 0 runs for every i
    named = np.array([[int(att._kv_block(i, j, block_q, block_k, num_k))
                       for j in range(num_k)] for i in range(num_q)])
    own = np.broadcast_to(np.arange(num_k), named.shape)
    assert (named[runs] == own[runs]).all()
    before = np.roll(named, 1, axis=1)
    assert (named[~runs] == before[~runs]).all()
    fetched = 1 + (np.diff(named, axis=1) != 0).sum(axis=1)
    assert (fetched == runs.sum(axis=1)).all()

    named = np.array([[int(att._q_block(i, j, block_q, block_k, num_q))
                       for j in range(num_k)] for i in range(num_q)])
    own = np.broadcast_to(np.arange(num_q)[:, None], named.shape)
    assert (named[runs] == own[runs]).all()
    assert ((0 <= named) & (named < num_q)).all()
    after = np.roll(named, -1, axis=0)
    skipped = ~runs
    skipped[-1] = False                     # the last step has none after
    assert (named[skipped] == after[skipped]).all()
    fetched = 1 + (np.diff(named, axis=0) != 0).sum(axis=0)
    assert (fetched == np.maximum(runs.sum(axis=0), 1)).all()


@pytest.mark.parametrize("whole", ["keys", "queries"])
@pytest.mark.parametrize("block", [1024, 512, 256, 384])
def test_the_tiles_of_a_diagonal_block_cover_its_lower_half(block, whole):
    """What ``_step_tiles`` leaves out of a block on the diagonal is masked
    anyway, and no pair is computed twice."""
    count = np.zeros((block, block), int)             # (query, key)
    kept = np.zeros((block, block), bool)
    for qs, ks, (row0, col0) in att._step_tiles(0, 3, 3, block, block,
                                                whole):
        count[qs, ks] += 1
        rows = np.arange(block)[qs][:, None] - np.arange(block)[qs][0] + row0
        cols = np.arange(block)[ks][None, :] - np.arange(block)[ks][0] + col0
        kept[qs, ks] |= cols <= rows
    lower = np.tril(np.ones((block, block), bool))
    assert (kept == lower).all() and count.max() == 1
    if block % att._TRIANGLE_TILE == 0 and block > att._TRIANGLE_TILE:
        n = block // att._TRIANGLE_TILE
        assert count.sum() == block * block * (n + 1) // (2 * n)
    assert att._step_tiles(None, 3, 2, block, block, whole) == [
        (slice(None), slice(None), None)]


def test_flash_diagonal_blocks_cut_into_tiles_match_the_reference():
    """Equal blocks of two tiles a side over a 2 x 2 grid, float32: the
    blocks on the diagonal are computed as strips."""
    q, k = (_rand((1, 2, 1024, 48), seed=60 + i) for i in range(2))
    v, g = (_rand((1, 2, 1024, 32), seed=62 + i) for i in range(2))
    assert 512 == 2 * att._TRIANGLE_TILE
    got = _with_grads(att.flash_attention, True, q, k, v, g, interpret=True,
                      block_q=512, block_k=512)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(att.mha_reference, True, q, k, v, g)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- a sliding window (PR 34)


def _band(sq, sk, window):
    rows, cols = np.arange(sq)[:, None], np.arange(sk)[None, :]
    return (cols <= rows) & (rows - cols < window)


# (sequence, window, (block_q, block_k), query heads a key head): a window
# below, equal to, not a multiple of and above a block; equal blocks that
# are cut into tiles (512 = two tiles a side) and blocks that are not
WINDOWED = [(512, 64, (128, 128), 1), (512, 128, (128, 128), 2),
            (512, 200, (128, 128), 4), (512, 300, (128, 256), 1),
            (768, 256, (256, 128), 2), (1024, 512, (512, 512), 1),
            (1024, 300, (512, 512), 2), (1024, 700, (512, 512), 1),
            (2048, 1024, (512, 512), 1)]


@pytest.mark.parametrize("seq,window,blocks,group", WINDOWED,
                         ids=lambda v: str(v).replace(" ", ""))
def test_flash_with_a_window_matches_the_reference(seq, window, blocks,
                                                   group):
    """The three kernels through the interpreter against
    ``mha_reference(window=)``: forward and all gradients."""
    heads = 2 * group
    q, g = (_rand((1, heads, seq, 32), seed=70 + i) for i in range(2))
    k, v = (_rand((1, 2, seq, 32), seed=72 + i) for i in range(2))
    got = _with_grads(att.flash_attention, True, q, k, v, g, interpret=True,
                      block_q=blocks[0], block_k=blocks[1], window=window)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(att.mha_reference, True, q, k, v, g,
                           window=window)
        causal = att.mha_reference(q, k, v, causal=True)
    assert np.abs(np.asarray(want[0]) - np.asarray(causal)).max() > 1e-2
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [256, 300])
def test_a_window_that_reaches_the_first_key_is_causal_to_the_last_bit(
        window):
    q, g = (_rand((1, 4, 256, 32), seed=80 + i) for i in range(2))
    k, v = (_rand((1, 2, 256, 32), seed=82 + i) for i in range(2))
    how = dict(interpret=True, block_q=128, block_k=128)
    with_window = _with_grads(att.flash_attention, True, q, k, v, g,
                              window=window, **how)
    causal = _with_grads(att.flash_attention, True, q, k, v, g, **how)
    for a, b in zip(with_window, causal):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the CPU platform's path takes the window too
    np.testing.assert_array_equal(
        np.asarray(att.flash_attention(q, k, v, causal=True, window=window)),
        np.asarray(att.mha_reference(q, k, v, causal=True)))


def test_a_window_takes_causal_self_attention():
    q, k = _rand((1, 2, 256, 32)), _rand((1, 2, 128, 32))
    with pytest.raises(mx.base.MXNetError, match="window"):
        att.flash_attention(q, q, q, causal=False, window=64)
    with pytest.raises(mx.base.MXNetError, match="window"):
        att.flash_attention(q, k, k, causal=True, window=64)


@pytest.mark.parametrize("window", [1, 100, 256, 384, 512, 1000, 1024, 3000])
@pytest.mark.parametrize("block_q,block_k",
                         [(256, 512), (512, 512), (128, 128), (512, 256),
                          (1024, 1024)])
def test_window_index_maps_against_brute_force(block_q, block_k, window):
    """The block rule's counts against a brute-force count: the steps that
    run and the steps that mask are the blocks the band touches and the
    blocks an edge crosses; the inner grid axes are as long as the most
    blocks a row or column of the band runs; every step that runs names its
    own block, the others a block already fetched, so the blocks fetched
    are the blocks that run."""
    seq = 4096
    num = seq // block_q, seq // block_k
    num_q, num_k = num
    seen = _band(seq, seq, window).reshape(num_q, block_q, num_k, block_k)
    runs = seen.any(axis=(1, 3))
    crosses = runs & ~seen.all(axis=(1, 3))
    ran = masked = 0
    for i in range(num_q):
        for j in range(num_k):
            run = bool(att._runs(i, j, block_q, block_k, window))
            assert run == runs[i, j]
            ran += run
            if run:
                mask = bool(att._crosses_an_edge(i, j, block_q, block_k,
                                                 window))
                assert mask == crosses[i, j]
                masked += mask
    assert (ran, masked) == (runs.sum(), crosses.sum())
    key_steps, query_steps = att._band_blocks(block_q, block_k, num_q, num_k,
                                              window)
    assert key_steps == runs.sum(axis=1).max()
    assert query_steps == runs.sum(axis=0).max()
    assert key_steps <= (block_q + window - 2) // block_k + 2

    # forward and dq: grid step (i, at) stands for key block first + at
    for i in range(num_q):
        first = int(att._first_key_block(i, block_q, block_k, window))
        assert first == np.flatnonzero(runs[i])[0]
        named = [int(att._kv_block(i, at, block_q, block_k, num_k, window))
                 for at in range(key_steps)]
        own = [first + at for at in range(key_steps)]
        for at in range(key_steps):
            inside = own[at] < num_k and runs[i, own[at]]
            assert inside == bool(
                own[at] < num_k and att._runs(i, own[at], block_q, block_k,
                                              window))
            assert named[at] == (own[at] if inside else named[at - 1])
        assert len(set(named)) == runs[i].sum()
    # dk/dv: grid step (j, at) stands for query block first + at
    for j in range(num_k):
        first = int(att._first_query_block(j, block_q, block_k, num_q))
        assert first == np.flatnonzero(runs[:, j])[0]
        named = [int(att._q_block(at, j, block_q, block_k, num_q, window))
                 for at in range(query_steps)]
        for at in range(query_steps):
            inside = first + at < num_q and runs[first + at, j]
            assert named[at] == (first + at if inside else named[at - 1])
        assert len(set(named)) == runs[:, j].sum()


@pytest.mark.parametrize("whole", ["keys", "queries"])
@pytest.mark.parametrize("block,window", [
    (1024, 1024), (512, 1024), (512, 300), (512, 700), (1024, 100),
    (1024, 1500), (256, 300), (384, 500)])
def test_the_tiles_of_a_block_an_edge_crosses_cover_the_band(block, window,
                                                             whole):
    """What ``_step_tiles`` leaves out of a masked block is masked anyway,
    no pair is computed twice, and every masked offset is one of
    ``_masked_offsets`` (where the tiles depend on it)."""
    seq = 4 * block + (block if window > 2 * block else 0)
    num = seq // block
    band = _band(seq, seq, window).reshape(num, block, num, block)
    offsets = att._masked_offsets(block, block, window)
    for i in range(num):
        for j in range(num):
            part = band[i, :, j, :]
            if not part.any() or part.all():
                continue
            offset = i - j if att._cuts_tiles(block, block) else 0
            assert offset in offsets
            count = np.zeros((block, block), int)
            kept = np.zeros((block, block), bool)
            for qs, ks, (row0, col0) in att._step_tiles(
                    offset, i, j, block, block, whole, window):
                count[qs, ks] += 1
                rows = np.arange(block)[qs][:, None] \
                    - np.arange(block)[qs][0] + row0
                cols = np.arange(block)[ks][None, :] \
                    - np.arange(block)[ks][0] + col0
                kept[qs, ks] |= (cols <= rows) & (rows - cols < window)
            assert (kept == part).all() and count.max() == 1
    if (block, window) == (1024, 1024):
        # both blocks of a query block half masked: 10 tiles of 16 each
        assert offsets == [0, 1]
        for offset in offsets:
            tiles = att._step_tiles(offset, 5, 5 - offset, block, block,
                                    whole, window)
            assert sum(len(range(*qs.indices(block)))
                       * len(range(*ks.indices(block)))
                       for qs, ks, _ in tiles) == 10 * 256 * 256


def test_a_window_layers_grid_holds_the_band_only():
    """At the cell's shape (16,384, window 1,024, blocks 1,024 / 1,024) the
    three kernels' inner grid axes are 2 long where causal attention has
    16: what lies behind the window is not in the grid."""
    grids = {}

    def shapes(q, window):
        return jax.make_jaxpr(lambda q: jax.vjp(
            lambda q: att._flash(q, q, q, 1.0, True, window, 1024, 1024,
                                 False), q)[1](q))(q)

    q = jax.ShapeDtypeStruct((1, 2, 16384, 128), jnp.bfloat16)
    for window in (None, 1024):
        grids[window] = sorted(
            eqn.params["grid_mapping"].grid
            for eqn in _equations(shapes(q, window).jaxpr, "pallas_call"))
    assert grids[None] == [(2, 16, 16)] * 3
    assert grids[1024] == [(2, 16, 2)] * 3
