"""Native runtime tests: engine dependency semantics (mirrors reference
tests/cpp/engine/threaded_engine_test.cc and
tests/python/unittest/test_engine.py), RecordIO roundtrip + sharding, and
the prefetching pipeline (reference: test_io.py ImageRecordIter tests)."""

import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _native, engine as eng
from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack, pack_img, unpack


needs_native = pytest.mark.skipif(not _native.available(),
                                  reason="libmxtpu not built")


@needs_native
def test_engine_basic_ordering():
    e = eng.ThreadedEngine(n_workers=4, io_workers=2)
    v = e.new_variable()
    out = []
    # 50 sequential writers on one var must run in push order.
    for i in range(50):
        e.push(lambda i=i: out.append(i), mutable_vars=[v])
    e.wait_for_var(v)
    assert out == list(range(50))


@needs_native
def test_engine_readers_share_writers_exclusive():
    e = eng.ThreadedEngine(n_workers=8, io_workers=1)
    v = e.new_variable()
    state = {"x": 0}
    concurrent = {"now": 0, "max": 0}
    lock = threading.Lock()

    def read():
        with lock:
            concurrent["now"] += 1
            concurrent["max"] = max(concurrent["max"], concurrent["now"])
        time.sleep(0.002)
        with lock:
            concurrent["now"] -= 1

    def write():
        x = state["x"]
        time.sleep(0.001)
        state["x"] = x + 1

    e.push(write, mutable_vars=[v])
    for _ in range(8):
        e.push(read, const_vars=[v])
    e.push(write, mutable_vars=[v])
    for _ in range(8):
        e.push(read, const_vars=[v])
    e.wait_all()
    assert state["x"] == 2            # writes exclusive, never raced
    assert concurrent["max"] >= 2     # reads actually overlapped


@needs_native
def test_engine_error_propagates_to_wait():
    e = eng.ThreadedEngine(n_workers=2, io_workers=1)
    v = e.new_variable()

    def boom():
        raise ValueError("boom")

    e.push(boom, mutable_vars=[v])
    with pytest.raises(RuntimeError):
        e.wait_for_var(v)


@needs_native
def test_engine_cross_var_dependency():
    e = eng.ThreadedEngine(n_workers=4, io_workers=1)
    a, b = e.new_variable(), e.new_variable()
    log = []
    e.push(lambda: (time.sleep(0.01), log.append("w_a"))[-1], mutable_vars=[a])
    # reads a, writes b: must run after w_a
    e.push(lambda: log.append("a->b"), const_vars=[a], mutable_vars=[b])
    e.push(lambda: log.append("w_b"), mutable_vars=[b])
    e.wait_for_var(b)
    assert log == ["w_a", "a->b", "w_b"]


@needs_native
def test_engine_error_cleared_by_clean_write():
    e = eng.ThreadedEngine(n_workers=2, io_workers=1)
    v = e.new_variable()
    e.push(lambda: (_ for _ in ()).throw(ValueError("boom")),
           mutable_vars=[v])
    with pytest.raises(RuntimeError):
        e.wait_for_var(v)
    e.push(lambda: None, mutable_vars=[v])
    e.wait_for_var(v)  # clean write cleared the stale error


@needs_native
def test_engine_unknown_var_raises_cleanly():
    e = eng.ThreadedEngine(n_workers=2, io_workers=1)
    v = e.new_variable()
    with pytest.raises(RuntimeError):
        e.push(lambda: None, const_vars=[v], mutable_vars=[10**9])
    # engine must not be wedged: v's read share was rolled back
    e.push(lambda: None, mutable_vars=[v])
    e.wait_for_var(v)
    e.wait_all()


@needs_native
def test_engine_async_op_on_complete():
    e = eng.ThreadedEngine(n_workers=2, io_workers=1)
    v = e.new_variable()
    got = {}

    def start(op_id):
        # initiate out-of-band completion from another thread
        def finish():
            time.sleep(0.01)
            got["done"] = True
            e.on_complete(op_id)
        threading.Thread(target=finish, daemon=True).start()

    e.push(start, mutable_vars=[v], prop=eng.ASYNC)
    after = []
    e.push(lambda: after.append(got.get("done")), const_vars=[v])
    e.wait_all()
    assert after == [True]  # dependent op waited for on_complete


@needs_native
def test_engine_error_includes_traceback():
    e = eng.ThreadedEngine(n_workers=2, io_workers=1)
    v = e.new_variable()

    def boom():
        raise ValueError("very specific message")

    e.push(boom, mutable_vars=[v])
    with pytest.raises(RuntimeError, match="very specific message"):
        e.wait_for_var(v)


def _write_raw_rec(path, n, shape=(3, 8, 8), label_width=1, seed=0):
    """RecordIO file of IRHeader-packed raw float32 tensors."""
    rng = np.random.RandomState(seed)
    rec = MXRecordIO(path, "w")
    samples, labels = [], []
    for i in range(n):
        arr = rng.rand(*shape).astype(np.float32)
        lab = float(i % 7)
        rec.write(pack(IRHeader(0, lab, i, 0), arr.tobytes()))
        samples.append(arr)
        labels.append(lab)
    rec.close()
    return np.stack(samples), np.asarray(labels, dtype=np.float32)


@needs_native
def test_native_recordio_reader_matches_python(tmp_path):
    import ctypes
    path = str(tmp_path / "x.rec")
    samples, _ = _write_raw_rec(path, 33)
    lib = _native.get_lib()
    h = ctypes.c_void_p()
    _native.check_call(lib.MXTPURecordReaderCreate(path.encode(), 1 << 16,
                                                   0, 1, ctypes.byref(h)))
    got = 0
    while True:
        data = ctypes.POINTER(ctypes.c_uint8)()
        size = ctypes.c_uint32()
        _native.check_call(lib.MXTPURecordReaderNext(
            h, ctypes.byref(data), ctypes.byref(size)))
        if not data:
            break
        payload = ctypes.string_at(data, size.value)
        header, body = unpack(payload)
        arr = np.frombuffer(body, dtype=np.float32).reshape(3, 8, 8)
        assert np.array_equal(arr, samples[got])
        got += 1
    assert got == 33
    _native.check_call(lib.MXTPURecordReaderFree(h))


@needs_native
def test_native_recordio_sharding_covers_all(tmp_path):
    import ctypes
    path = str(tmp_path / "x.rec")
    _write_raw_rec(path, 101)
    lib = _native.get_lib()
    ids = []
    for part in range(4):
        h = ctypes.c_void_p()
        _native.check_call(lib.MXTPURecordReaderCreate(
            path.encode(), 1 << 14, part, 4, ctypes.byref(h)))
        while True:
            data = ctypes.POINTER(ctypes.c_uint8)()
            size = ctypes.c_uint32()
            _native.check_call(lib.MXTPURecordReaderNext(
                h, ctypes.byref(data), ctypes.byref(size)))
            if not data:
                break
            header, _ = unpack(ctypes.string_at(data, size.value))
            ids.append(header.id)
        _native.check_call(lib.MXTPURecordReaderFree(h))
    # Every record in exactly one shard.
    assert sorted(ids) == list(range(101))


@needs_native
def test_native_pipeline_raw_batches(tmp_path):
    """Built-in C++ raw decoder: values and order must match the file."""
    import ctypes
    path = str(tmp_path / "x.rec")
    samples, labels = _write_raw_rec(path, 40, shape=(2, 4, 4))
    lib = _native.get_lib()
    h = ctypes.c_void_p()
    nullcb = _native.DECODE_FN()
    _native.check_call(lib.MXTPUPipelineCreate(
        path.encode(), 1 << 16, 0, 1, 8, 2 * 4 * 4 * 4, 1, 0, 0, 2, 0, 1,
        nullcb, None, ctypes.byref(h)))
    seen = 0
    for _epoch in range(2):
        while True:
            data_p = ctypes.POINTER(ctypes.c_uint8)()
            label_p = ctypes.POINTER(ctypes.c_float)()
            count = ctypes.c_int()
            _native.check_call(lib.MXTPUPipelineNext(
                h, ctypes.byref(data_p), ctypes.byref(label_p),
                ctypes.byref(count)))
            if count.value < 0:
                break
            n = count.value
            flat = np.ctypeslib.as_array(data_p, (8 * 2 * 4 * 4 * 4,))
            batch = flat.view(np.float32).reshape(8, 2, 4, 4)[:n].copy()
            labs = np.ctypeslib.as_array(label_p, (8,))[:n].copy()
            start = seen % 40
            assert np.allclose(batch, samples[start:start + n])
            assert np.allclose(labs, labels[start:start + n])
            seen += n
            _native.check_call(lib.MXTPUPipelineRelease(h, data_p, label_p))
        assert seen % 40 == 0
        _native.check_call(lib.MXTPUPipelineReset(h))
    assert seen == 80
    _native.check_call(lib.MXTPUPipelineFree(h))


@needs_native
def test_image_record_iter_native_path(tmp_path):
    """End-to-end ImageRecordIter on the native pipeline with image decode
    via the Python callback."""
    path = str(tmp_path / "img.rec")
    rng = np.random.RandomState(3)
    rec = MXRecordIO(path, "w")
    imgs = []
    for i in range(20):
        img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img))
        imgs.append(img)
    rec.close()

    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=5, shuffle=False,
                               preprocess_threads=2, use_native=True)
    assert it._pipe is not None, "native pipeline should have been selected"
    labels = []
    nb = 0
    for batch in it:
        assert batch.data[0].shape == (5, 3, 8, 8)
        labels.extend(batch.label[0].asnumpy().astype(int).tolist())
        nb += 1
    assert nb == 4
    assert labels == list(range(20))
    # second epoch after reset
    it.reset()
    nb2 = sum(1 for _ in it)
    assert nb2 == 4


def _tiny_img_rec(path, n, hw=6):
    rng = np.random.RandomState(5)
    rec = MXRecordIO(path, "w")
    for i in range(n):
        img = (rng.rand(hw, hw, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img))
    rec.close()


@needs_native
def test_image_record_iter_partial_batch_native_vs_fallback(tmp_path):
    """Both paths keep the final partial batch, padded with REAL wrapped
    records (round_batch semantics) and pad set so score() can trim."""
    path = str(tmp_path / "img.rec")
    _tiny_img_rec(path, 10)
    outs = {}
    for native in (True, False):
        it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 6, 6),
                                   batch_size=4, shuffle=False,
                                   use_native=native)
        assert (it._pipe is not None) == native
        batches = list(it)
        assert [b.pad for b in batches] == [0, 0, 2]
        # padded tail wraps to the first records (labels 0, 1): fit()
        # trains on real samples, never fabricated zeros
        last_labels = batches[-1].label[0].asnumpy().astype(int).tolist()
        assert last_labels == [8, 9, 0, 1]
        outs[native] = np.concatenate(
            [b.label[0].asnumpy() for b in batches])
    assert np.allclose(outs[True], outs[False])


@needs_native
def test_native_shuffle_differs_across_epochs(tmp_path):
    path = str(tmp_path / "img.rec")
    _tiny_img_rec(path, 24)
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 6, 6),
                               batch_size=24, shuffle=True, seed=3,
                               shuffle_buffer=24, use_native=True)
    e1 = next(iter(it)).label[0].asnumpy().tolist()
    it.reset()
    e2 = next(iter(it)).label[0].asnumpy().tolist()
    assert sorted(e1) == sorted(e2) == list(range(24))
    assert e1 != e2  # epoch reseed


@needs_native
def test_image_record_iter_native_shuffle_covers_epoch(tmp_path):
    path = str(tmp_path / "img.rec")
    rng = np.random.RandomState(5)
    rec = MXRecordIO(path, "w")
    for i in range(30):
        img = (rng.rand(6, 6, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 6, 6),
                               batch_size=6, shuffle=True, seed=7,
                               preprocess_threads=2, use_native=True)
    labels = []
    for batch in it:
        labels.extend(batch.label[0].asnumpy().astype(int).tolist())
    assert sorted(labels) == list(range(30))
    assert labels != list(range(30))  # actually shuffled


@needs_native
def test_engine_stress_cpp(tmp_path):
    """Compile and run the C++ engine stress test (reference:
    tests/cpp/engine/threaded_engine_test.cc — FIFO ordering, read
    sharing/write exclusivity under load, error propagation)."""
    import shutil
    import subprocess

    if shutil.which("g++") is None:
        pytest.skip("no C++ compiler")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "tests", "native_c", "test_engine_stress.cc")
    so_dir = os.path.join(repo, "mxnet_tpu", "native")
    exe = str(tmp_path / "engine_stress")
    cc = subprocess.run(
        ["g++", "-std=c++17", "-O2", "-o", exe, src, "-L" + so_dir,
         "-lmxtpu", "-Wl,-rpath," + so_dir, "-pthread"],
        capture_output=True, text=True)
    assert cc.returncode == 0, cc.stderr
    r = subprocess.run([exe], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all checks passed" in r.stdout


@needs_native
def test_c_abi_from_c(tmp_path):
    """Compile and run a plain-C consumer of the libmxtpu ABI (the FFI
    seam other language bindings use; reference: c_api.h consumers)."""
    import shutil
    import subprocess

    if shutil.which("gcc") is None:
        pytest.skip("no C compiler")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "tests", "native_c", "test_c_abi.c")
    so_dir = os.path.join(repo, "mxnet_tpu", "native")
    exe = str(tmp_path / "test_c_abi")
    cc = subprocess.run(
        ["gcc", "-O1", "-o", exe, src, "-L" + so_dir, "-lmxtpu",
         "-Wl,-rpath," + so_dir], capture_output=True, text=True)
    assert cc.returncode == 0, cc.stderr
    r = subprocess.run([exe, str(tmp_path / "c.rec")], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all checks passed" in r.stdout


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "py-fallback"])
def test_image_record_iter_raw_records(tmp_path, use_native):
    """raw_records=True routes to the C++ builtin DecodeRaw (no Python
    in the worker loop) — or the equivalent numpy path when the native
    lib is unavailable; values and labels must round-trip on both."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _native
    from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack

    if use_native and _native.get_lib() is None:
        pytest.skip("native lib not built")
    path = str(tmp_path / "raw.rec")
    rs = np.random.RandomState(0)
    samples = []
    rec = MXRecordIO(path, "w")
    for i in range(12):
        arr = rs.rand(2, 4, 4).astype(np.float32)
        samples.append((float(i % 5), arr))
        rec.write(pack(IRHeader(0, float(i % 5), i, 0), arr.tobytes()))
    rec.close()

    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(2, 4, 4),
                               batch_size=4, shuffle=False,
                               preprocess_threads=2, raw_records=True,
                               use_native=use_native)
    assert (it._pipe is not None) == use_native
    seen = 0
    for batch in it:
        data = batch.data[0].asnumpy()
        label = batch.label[0].asnumpy().ravel()
        for j in range(4):
            want_label, want_arr = samples[seen]
            np.testing.assert_allclose(data[j], want_arr, atol=0)
            assert label[j] == want_label
            seen += 1
    assert seen == 12


def test_raw_records_warns_on_dropped_augmentation(tmp_path):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack

    path = str(tmp_path / "raw2.rec")
    rec = MXRecordIO(path, "w")
    rec.write(pack(IRHeader(0, 0.0, 0, 0),
                   np.zeros((2, 4, 4), np.float32).tobytes()))
    rec.close()
    with pytest.warns(UserWarning, match="augmentation"):
        mx.io.ImageRecordIter(path_imgrec=path, data_shape=(2, 4, 4),
                              batch_size=1, rand_mirror=True,
                              raw_records=True, use_native=False)


def test_native_jpeg_pipeline_matches_python(tmp_path):
    """The in-worker C++ JPEG decoder (pipeline.cc DecodeJpeg) produces
    the same batches as the Python-callback path — labels exactly,
    pixels within decoder rounding (r3; decoding in the C++ workers is
    what lets the pipeline scale over cores without the GIL)."""
    pytest.importorskip("PIL")
    from mxnet_tpu.io.io import ImageRecordIter, _native_has_jpeg
    from mxnet_tpu.recordio import IRHeader, MXIndexedRecordIO, pack_img

    if not _native_has_jpeg():
        pytest.skip("libmxtpu built without libjpeg")
    rng = np.random.RandomState(0)
    rec = MXIndexedRecordIO(str(tmp_path / "j.idx"), str(tmp_path / "j.rec"),
                            "w")
    for i in range(24):
        img = (rng.rand(40, 40, 3) * 255).astype(np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i % 5), i, 0), img,
                                  quality=95))
    rec.close()
    nat = ImageRecordIter(str(tmp_path / "j.rec"), (3, 32, 32), batch_size=8,
                          mean_r=10.0, mean_g=20.0, mean_b=30.0)
    assert nat._pipe is not None and nat._pipe._cb is None, \
        "builtin JPEG path not selected"
    py = ImageRecordIter(str(tmp_path / "j.rec"), (3, 32, 32), batch_size=8,
                         mean_r=10.0, mean_g=20.0, mean_b=30.0,
                         use_native=False)
    n = 0
    for b_nat, b_py in zip(nat, py):
        np.testing.assert_array_equal(b_nat.label[0].asnumpy(),
                                      b_py.label[0].asnumpy())
        diff = np.abs(b_nat.data[0].asnumpy() - b_py.data[0].asnumpy())
        assert diff.max() <= 1.0, diff.max()  # IDCT rounding slack
        n += 1
    assert n == 3

    # pad case (image smaller than data_shape): the centered canvas and
    # its -mean padding must match the python _center_fit path exactly
    rec = MXIndexedRecordIO(str(tmp_path / "p.idx"), str(tmp_path / "p.rec"),
                            "w")
    for i in range(8):
        img = (rng.rand(24, 24, 3) * 255).astype(np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i), i, 0), img,
                                  quality=95))
    rec.close()
    natp = ImageRecordIter(str(tmp_path / "p.rec"), (3, 32, 32),
                           batch_size=8, mean_r=100.0, mean_g=50.0,
                           mean_b=25.0)
    pyp = ImageRecordIter(str(tmp_path / "p.rec"), (3, 32, 32), batch_size=8,
                          mean_r=100.0, mean_g=50.0, mean_b=25.0,
                          use_native=False)
    bn = next(iter(natp)).data[0].asnumpy()
    bp = next(iter(pyp)).data[0].asnumpy()
    assert np.abs(bn - bp).max() <= 1.0
    assert bn[0, 0, 0, 0] == -100.0 and bn[0, 1, 0, 0] == -50.0


def test_native_jpeg_mixed_records_fallback(tmp_path):
    """A mixed .rec (JPEG + PNG payloads) on the builtin JPEG path
    routes non-JPEG records through the Python fallback callback
    per-record instead of failing mid-epoch (r3 review)."""
    PIL = pytest.importorskip("PIL")
    from io import BytesIO

    from PIL import Image

    from mxnet_tpu.io.io import ImageRecordIter, _native_has_jpeg
    from mxnet_tpu.recordio import IRHeader, MXIndexedRecordIO, pack, pack_img

    if not _native_has_jpeg():
        pytest.skip("libmxtpu built without libjpeg")
    rng = np.random.RandomState(0)
    rec = MXIndexedRecordIO(str(tmp_path / "m.idx"), str(tmp_path / "m.rec"),
                            "w")
    imgs = []
    for i in range(8):
        img = (rng.rand(32, 32, 3) * 255).astype(np.uint8)
        imgs.append(img)
        if i % 2 == 0:
            rec.write_idx(i, pack_img(IRHeader(0, float(i), i, 0), img,
                                      quality=100))
        else:  # PNG payload in the same file
            buff = BytesIO()
            Image.fromarray(img).save(buff, format="PNG")
            rec.write_idx(i, pack(IRHeader(0, float(i), i, 0),
                                  buff.getvalue()))
    rec.close()
    it = ImageRecordIter(str(tmp_path / "m.rec"), (3, 32, 32), batch_size=8)
    assert it._pipe is not None and it._pipe._cb is None  # builtin selected
    batch = next(iter(it))
    labels = batch.label[0].asnumpy()
    np.testing.assert_array_equal(np.sort(labels), np.arange(8.0))
    data = batch.data[0].asnumpy()
    # PNG records are lossless: their pixels must match exactly
    for i in range(1, 8, 2):
        row = np.where(labels == i)[0][0]
        np.testing.assert_array_equal(
            data[row], imgs[i].astype(np.float32).transpose(2, 0, 1))
