"""PR 14: sharded (ZeRO) checkpointing — ``save_zero`` /
``restore_zero`` / ``auto_resume(zero_step=)``.

Pins the durability contract:

- same-layout resume is BIT-EXACT: a fresh process/step restored from
  the sharded checkpoint continues with bit-identical losses (device
  shards + the host optimizer hyper-state both ride the checkpoint —
  Adam's update count drives bias correction);
- a SIGKILL mid-save (before the rank-0 manifest rename) leaves only a
  staging dir: the next manager prunes it, ``latest()`` still returns
  the previous valid checkpoint, and resume from it is bit-exact;
- layout-change resume: a run saved at dp=8 restores onto dp=4 (shards
  rebuilt, re-padded, re-placed) and continues numerically equivalent
  (allclose — the dp reduction tree differs, so not bit-exact);
- corruption in any shard file is caught by the manifest hashes:
  ``latest()`` quarantines the checkpoint like any other corrupt one;
- ``auto_resume(zero_step=)`` over a NON-sharded newest checkpoint
  warns and restores nothing rather than mixing formats.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, gluon, optimizer as opt_mod, runtime_stats
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    runtime_stats.reset()
    checkpoint.disable()
    yield
    checkpoint.disable()
    runtime_stats.reset()


def _mlp(prefix, seed=7, feat=12, classes=4):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(classes))
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((2, feat), ctx=mx.cpu()))
    return net


def _zstep(prefix, n=8, seed=7):
    import jax

    mesh = create_mesh({"dp": n}, devices=jax.devices()[:n])
    return GluonTrainStep(_mlp(prefix, seed=seed),
                          gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
                          zero=True, optimizer=opt_mod.create(
                              "adam", learning_rate=0.01))


def _data(n=8, batch=8, feat=12, classes=4, seed=3):
    rs = np.random.RandomState(seed)
    return ([rs.rand(batch, feat).astype(np.float32) for _ in range(n)],
            [rs.randint(0, classes, (batch,)).astype(np.int32)
             for _ in range(n)])


def _run(step, xs, ys):
    return [float(np.asarray(step(x, y))) for x, y in zip(xs, ys)]


# ------------------------------------------------------------- resume


def test_same_layout_resume_bit_exact(tmp_path):
    """save_zero at step 4, restore into a FRESH step (same prefix →
    same param names): the three continued losses match the
    uninterrupted run bit for bit — proof the host optimizer
    hyper-state (Adam's t) rides the checkpoint with the shards."""
    xs, ys = _data()
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=5,
                                       async_write=False)
    zs = _zstep("zck_")
    _run(zs, xs[:4], ys[:4])
    path = zs.save_zero(4, mgr=mgr)
    assert os.path.isdir(path)
    assert mgr.verify(path)
    baseline = _run(zs, xs[4:7], ys[4:7])

    zs2 = _zstep("zck_", seed=99)   # different init — restore must win
    step = zs2.restore_zero(mgr.latest(), mgr=mgr)
    assert step == 4
    assert _run(zs2, xs[4:7], ys[4:7]) == baseline


def test_layout_change_resume_allclose(tmp_path):
    """A checkpoint saved at dp=8 restores onto a dp=4 mesh: shards are
    rebuilt into full vectors, re-padded and re-placed.  The continued
    trajectory is numerically equivalent (the dp-8 and dp-4 grad
    reduction trees round differently, so allclose, not equality)."""
    xs, ys = _data()
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=5,
                                       async_write=False)
    zs = _zstep("zlay_", n=8)
    _run(zs, xs[:4], ys[:4])
    zs.save_zero(4, mgr=mgr)
    baseline = _run(zs, xs[4:7], ys[4:7])

    zs4 = _zstep("zlay_", n=4, seed=99)
    assert zs4.restore_zero(mgr.latest(), mgr=mgr) == 4
    cont = _run(zs4, xs[4:7], ys[4:7])
    assert np.allclose(cont, baseline, rtol=1e-5)


def test_sigkill_mid_save_falls_back_bit_exact(tmp_path):
    """Child process: commits a valid sharded checkpoint at step 2,
    then dies by SIGKILL inside the NEXT save_zero before the manifest
    rename (``_fsync_dir`` on the staging dir is the last call before
    commit).  A second process over the same directory prunes the
    staging leftovers, auto-resumes from step 2 and reproduces the
    uninterrupted continuation bit for bit."""
    code = """
import json, os, signal, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import checkpoint, gluon, optimizer as opt_mod
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.gluon_step import GluonTrainStep
from mxnet_tpu.parallel.mesh import create_mesh

mode, ckdir = sys.argv[1], sys.argv[2]

mx.random.seed(7); np.random.seed(7)
net = nn.HybridSequential(prefix="zkill_")
with net.name_scope():
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
net.initialize(ctx=mx.cpu())
net(mx.nd.zeros((2, 12), ctx=mx.cpu()))
zs = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    mesh=create_mesh({"dp": 8}), zero=True,
                    optimizer=opt_mod.create("adam", learning_rate=0.01))
rs = np.random.RandomState(3)
xs = [rs.rand(8, 12).astype(np.float32) for _ in range(7)]
ys = [rs.randint(0, 4, (8,)).astype(np.int32) for _ in range(7)]
checkpoint.enable(ckdir, interval=0, async_write=False)
mgr = checkpoint.manager()

if mode == "crash":
    for x, y in zip(xs[:2], ys[:2]):
        zs(x, y)
    zs.save_zero(2, mgr=mgr)
    for x, y in zip(xs[2:4], ys[2:4]):
        zs(x, y)
    real = checkpoint._fsync_dir
    def boom(path):
        if path.endswith(".tmp-shared"):
            os.kill(os.getpid(), signal.SIGKILL)
        real(path)
    checkpoint._fsync_dir = boom
    zs.save_zero(4, mgr=mgr)        # never returns
    print("UNREACHABLE")
elif mode == "baseline":
    for x, y in zip(xs[:2], ys[:2]):
        zs(x, y)
    out = [float(np.asarray(zs(x, y))) for x, y in zip(xs[2:5], ys[2:5])]
    json.dump(out, sys.stdout)
else:  # resume
    zs(xs[6], ys[6])                # diverge before restore
    step = checkpoint.auto_resume(zero_step=zs)
    assert step == 2, step
    out = [float(np.asarray(zs(x, y))) for x, y in zip(xs[2:5], ys[2:5])]
    json.dump(out, sys.stdout)
"""
    import json

    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO

    def child(mode):
        return subprocess.run(
            [sys.executable, "-c", code, mode, str(tmp_path)],
            env=env, capture_output=True, text=True, timeout=300)

    r = child("crash")
    assert r.returncode == -9, (r.returncode, r.stderr[-2000:])
    assert "UNREACHABLE" not in r.stdout
    leftovers = [d for d in os.listdir(tmp_path) if ".tmp-" in d]
    assert leftovers, "SIGKILL should leave the staging dir behind"

    rb = child("baseline")
    assert rb.returncode == 0, rb.stderr[-2000:]
    rr = child("resume")
    assert rr.returncode == 0, rr.stderr[-2000:]
    assert json.loads(rr.stdout) == json.loads(rb.stdout)
    # the resume child's manager init pruned the dead staging dir
    assert not [d for d in os.listdir(tmp_path) if ".tmp-" in d]


# ------------------------------------------------- corruption & guards


def test_shard_corruption_quarantined(tmp_path):
    """Shard files are hashed into the manifest: flipping bytes in one
    makes latest() quarantine the whole checkpoint."""
    xs, ys = _data(n=2)
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=5,
                                       async_write=False)
    zs = _zstep("zcor_")
    _run(zs, xs, ys)
    path = zs.save_zero(2, mgr=mgr)
    shard = os.path.join(path, "zero-shard-00003-of-00008.pkl")
    with open(shard, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff")
    mgr2 = checkpoint.CheckpointManager(str(tmp_path), keep=5,
                                        async_write=False)
    assert mgr2.latest() is None
    assert mgr2.totals["corrupt_skipped"] >= 1


def test_auto_resume_plain_checkpoint_warns_none(tmp_path):
    """auto_resume(zero_step=) over a newest checkpoint in the
    replicated format restores nothing (no silent format mixing)."""
    net = _mlp("zpl_")
    mgr = checkpoint.enable(str(tmp_path), interval=0, async_write=False)
    mgr.save(3, {p.name: p.data() for p in net.collect_params().values()})
    mgr.wait()
    zs = _zstep("zpl2_")
    assert checkpoint.auto_resume(zero_step=zs) is None


def test_restore_zero_guards(tmp_path):
    """Wrong-format manifests and optimizer-family changes raise."""
    from mxnet_tpu.base import MXNetError

    xs, ys = _data(n=2)
    mgr = checkpoint.CheckpointManager(str(tmp_path), keep=5,
                                       async_write=False)
    zs = _zstep("zgd_")
    _run(zs, xs, ys)
    zs.save_zero(2, mgr=mgr)
    manifest = mgr.latest()

    import jax

    mesh = create_mesh({"dp": 8}, devices=jax.devices()[:8])
    zsgd = GluonTrainStep(
        _mlp("zgd2_"), gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
        zero=True, optimizer=opt_mod.create("sgd", learning_rate=0.1,
                                            momentum=0.9))
    with pytest.raises(MXNetError, match="state structure changed"):
        zsgd.restore_zero(manifest, mgr=mgr)
