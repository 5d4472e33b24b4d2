"""Targeted tests for the thin spots the r5 coverage run surfaced
(COVERAGE.md): modules whose only exercise was inside subprocesses or
nothing at all.  Each test asserts observable behavior, not just
imports — the point is to pin the contracts, not inflate the number.
"""

import numpy as np
import pytest

import mxnet_tpu as mx


def test_split_input_slice_workloads():
    """executor_manager._split_input_slice: proportional slicing with
    remainder on the last device; degenerate workloads rejected
    (reference: python/mxnet/executor_manager.py)."""
    from mxnet_tpu.executor_manager import _split_input_slice

    s = _split_input_slice(10, [1, 1])
    assert s == [slice(0, 5), slice(5, 10)]
    # round(2.5)=2 (banker's), shortfall lands on the LAST device —
    # the reference's exact remainder rule
    s = _split_input_slice(10, [2, 1, 1])
    assert [sl.stop - sl.start for sl in s] == [5, 2, 3]
    assert s[-1].stop == 10
    with pytest.raises(ValueError, match="Invalid workload"):
        _split_input_slice(4, [0, 0])
    with pytest.raises(ValueError, match="empty"):
        _split_input_slice(2, [1, 1, 1, 1])


def test_rtc_cuda_module_errors_pallas_module_runs():
    """rtc: CudaModule is a loud N/A on TPU; PallasModule compiles and
    launches a real Pallas kernel (interpret on CPU)."""
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError, match="PallasModule"):
        mx.rtc.CudaModule("__global__ void axpy() {}")

    import jax

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def out_shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    mod = mx.rtc.PallasModule(kern, out_shape)
    launcher = mod.get_kernel()
    x = mx.nd.array(np.arange(8, dtype=np.float32))
    out = launcher([x])
    np.testing.assert_allclose(out.asnumpy(), np.arange(8) * 2.0)


def test_transformer_encoder_trains_in_process():
    """gluon.nn.transformer: encoder stack forward + one backward step
    in-process (previously exercised only in the dryrun subprocess)."""
    from mxnet_tpu import autograd as ag
    from mxnet_tpu.gluon.nn.transformer import (MultiHeadAttention,
                                                TransformerEncoder)

    mx.random.seed(0)
    enc = TransformerEncoder(units=16, hidden_size=32, num_heads=4,
                             num_layers=2)
    enc.initialize(ctx=mx.cpu())
    x = mx.nd.array(np.random.RandomState(0).rand(2, 6, 16)
                    .astype(np.float32))
    out = enc(x)
    assert out.shape == (2, 6, 16)

    # causal masking: position t of a causal MHA must not change when
    # future positions change
    mha = MultiHeadAttention(units=16, num_heads=4, causal=True)
    mha.initialize(ctx=mx.cpu())
    a = mx.nd.array(np.random.RandomState(1).rand(1, 5, 16)
                    .astype(np.float32))
    b = a.asnumpy().copy()
    b[:, 3:] = 0.0
    outa = mha(a).asnumpy()
    outb = mha(mx.nd.array(b)).asnumpy()
    np.testing.assert_allclose(outa[:, :3], outb[:, :3], rtol=2e-5,
                               atol=2e-6)

    params = list(enc.collect_params().values())
    with ag.record():
        loss = (enc(x) ** 2).sum()
    loss.backward()
    assert any(float(np.abs(p.grad().asnumpy()).sum()) > 0 for p in params)


def test_symbol_random_builds_sampling_graph():
    """mx.sym.random: symbolic sampler nodes bind and execute."""
    s = mx.sym.random.uniform(low=0.0, high=1.0, shape=(3, 4))
    exe = s.bind(mx.cpu(), {})
    out = exe.forward(is_train=True)[0].asnumpy()
    assert out.shape == (3, 4)
    assert (out >= 0).all() and (out <= 1).all()
    n = mx.sym.random.normal(loc=2.0, scale=0.0, shape=(5,))
    val = n.bind(mx.cpu(), {}).forward()[0].asnumpy()
    np.testing.assert_allclose(val, 2.0, atol=1e-6)


def test_inception_v3_forward_and_structure():
    """model_zoo inception_v3 (17.5% covered): forward shape, param
    count vs the reference topology, aux head handling."""
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.inception_v3(classes=7)
    net.initialize(ctx=mx.cpu())
    # inception v3 needs >= 75x75 spatial; keep it small for 1 core
    out = net(mx.nd.zeros((1, 3, 96, 96), ctx=mx.cpu()))
    assert out.shape == (1, 7)
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    assert n_params > 2e7  # inception-v3 scale, not a stub


def test_vgg_and_densenet_small_variants_forward():
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    vgg = vision.vgg11(classes=5)
    vgg.initialize(ctx=mx.cpu())
    assert vgg(mx.nd.zeros((1, 3, 32, 32), ctx=mx.cpu())).shape == (1, 5)

    dn = vision.densenet121(classes=5)
    dn.initialize(ctx=mx.cpu())
    assert dn(mx.nd.zeros((1, 3, 32, 32), ctx=mx.cpu())).shape == (1, 5)


def test_conv_rnn_cell_step_and_unroll():
    """gluon.contrib Conv RNN cells in-process: single step state
    shapes and a short unroll."""
    from mxnet_tpu.gluon.contrib.rnn import Conv2DLSTMCell

    mx.random.seed(0)
    cell = Conv2DLSTMCell(input_shape=(4, 8, 8), hidden_channels=6,
                          i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    cell.initialize(ctx=mx.cpu())
    x = mx.nd.array(np.random.RandomState(0).rand(2, 4, 8, 8)
                    .astype(np.float32))
    states = cell.begin_state(batch_size=2)
    out, new_states = cell(x, states)
    assert out.shape == (2, 6, 8, 8)
    assert len(new_states) == 2
    seq = mx.nd.array(np.random.RandomState(1).rand(2, 3, 4, 8, 8)
                      .astype(np.float32))
    outs, _ = cell.unroll(3, seq, layout="NTC", merge_outputs=False)
    assert len(outs) == 3 and outs[0].shape == (2, 6, 8, 8)


def test_tp_transformer_rules_in_process():
    """parallel.tp rules (previously dryrun-subprocess-only): column/
    row/vocab sharding by name, size-1 axes dropped, first match wins."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.tp import make_param_spec_fn, spec_for

    mesh = create_mesh({"dp": 4, "tp": 2})
    fn = make_param_spec_fn(mesh=mesh)
    # trailing Nones are trimmed; column-parallel = dim 0 over tp
    assert fn("enc_attn_qkv_weight", (12, 4)) == P("tp")
    assert fn("enc_attn_proj_weight", (4, 12)) == P(None, "tp")
    assert fn("enc_ffn1_weight", (32, 4)) == P("tp")
    assert fn("enc_norm_gamma", (4,)) == P()
    # a tp=1 mesh degrades every rule to replicated
    mesh1 = create_mesh({"dp": 8})
    fn1 = make_param_spec_fn(mesh=mesh1)
    assert fn1("enc_attn_qkv_weight", (12, 4)) == P()
    # meshless spec_for returns the raw rule; odd dims drop the axis
    assert spec_for("x_qkv_weight", (8, 4)) == P("tp", None)
    assert spec_for("x_qkv_weight", (9, 4), mesh=mesh) == P()


def test_kvstore_server_init_server_role_gate(monkeypatch):
    """kvstore_server.init_server: False for workers (user code
    continues); True + serves for DMLC_ROLE=server (drive a quick
    round-trip against it from this process)."""
    import threading

    from mxnet_tpu import kvstore_server
    from mxnet_tpu.kvstore.ps import PSClient

    monkeypatch.setenv("DMLC_ROLE", "worker")
    assert kvstore_server.init_server() is False

    monkeypatch.setenv("DMLC_ROLE", "server")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("MXTPU_PS_PORTS", "29517")
    t = threading.Thread(target=kvstore_server.init_server, daemon=True)
    t.start()
    # PSClient does not read DMLC_ROLE, so the env stays 'server' until
    # monkeypatch unwinds — flipping it here would race the thread's
    # own role read (r5 review finding)
    c = PSClient(connect_timeout=20)
    c.init("k", np.zeros((2,), np.float32))
    assert c.pull("k").shape == (2,)
    c.stop_servers()
    t.join(timeout=20)
    assert not t.is_alive()


def test_lstmp_and_variational_dropout_cells():
    """contrib rnn extras in-process: LSTMP projects states to
    projection_size; VariationalDropoutCell reuses ONE mask across
    time steps (the defining property)."""
    from mxnet_tpu import autograd as ag
    from mxnet_tpu.gluon.contrib.rnn import (LSTMPCell,
                                             VariationalDropoutCell)
    from mxnet_tpu.gluon.rnn import LSTMCell

    mx.random.seed(0)
    cell = LSTMPCell(hidden_size=8, projection_size=3)
    cell.initialize(ctx=mx.cpu())
    x = mx.nd.array(np.random.RandomState(0).rand(2, 4).astype(np.float32))
    out, states = cell(x, cell.begin_state(batch_size=2))
    assert out.shape == (2, 3)          # projected
    assert states[0].shape == (2, 3)    # h projected
    assert states[1].shape == (2, 8)    # c full

    base = LSTMCell(hidden_size=6, input_size=4)
    vd = VariationalDropoutCell(base, drop_inputs=0.5)
    vd.initialize(ctx=mx.cpu())
    seq = mx.nd.array(np.random.RandomState(1).rand(2, 5, 4)
                      .astype(np.float32))
    with ag.record(train_mode=True):
        outs, _ = vd.unroll(5, seq, layout="NTC", merge_outputs=True)
    assert outs.shape == (2, 5, 6)
    assert np.isfinite(outs.asnumpy()).all()


def test_activation_blocks_forward():
    """gluon.nn activation blocks: values match their definitions."""
    from mxnet_tpu.gluon import nn

    x = mx.nd.array([-2.0, -0.5, 0.0, 1.5])
    leaky = nn.LeakyReLU(0.1)
    leaky.initialize()
    np.testing.assert_allclose(
        leaky(x).asnumpy(), np.where(x.asnumpy() > 0, x.asnumpy(),
                                     0.1 * x.asnumpy()), rtol=1e-6)
    assert "LeakyReLU" in repr(leaky)

    elu = nn.ELU(alpha=1.0)
    elu.initialize()
    xn = x.asnumpy()
    np.testing.assert_allclose(
        elu(x).asnumpy(), np.where(xn > 0, xn, np.expm1(xn)), rtol=1e-5,
        atol=1e-6)

    mx.random.seed(0)
    prelu = nn.PReLU()
    prelu.initialize()
    out = prelu(x).asnumpy()
    alpha = list(prelu.collect_params().values())[0].data().asnumpy()
    np.testing.assert_allclose(out, np.where(xn > 0, xn, alpha * xn),
                               rtol=1e-5)

    selu = nn.SELU()
    selu.initialize()
    assert np.isfinite(selu(x).asnumpy()).all()

    sw = nn.Swish()
    sw.initialize()
    np.testing.assert_allclose(
        sw(x).asnumpy(), xn / (1 + np.exp(-xn)), rtol=1e-5, atol=1e-6)


def test_explicit_mixed_initializer_still_works():
    """r5 review regression: Mixed/Load define only __call__ (no
    _init_weight); an explicit init=Mixed must keep working alongside
    the PReLU-style param-level-init routing."""
    from mxnet_tpu.gluon import nn

    net = nn.Dense(4, in_units=3)
    net.initialize(mx.init.Mixed([".*weight", ".*"],
                                 [mx.init.Constant(3.0),
                                  mx.init.Zero()]), force_reinit=True)
    w, b = [p.data().asnumpy() for p in net.collect_params().values()]
    np.testing.assert_allclose(w, 3.0)
    np.testing.assert_allclose(b, 0.0)
