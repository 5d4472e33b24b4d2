"""PR 3 memory & cost analytics: device-buffer tracker + per-executable
XLA cost analysis + diagnostic dump.

The tentpole's three pieces, pinned end to end:

- the weakref device-buffer tracker (``device_memory.py``): alloc /
  free / peak accounting through a real 20-step Gluon training loop,
  buffer-identity dedup, chrome-trace counter ("C") events, and
  ``reset()`` retaining no references (weak or strong);
- compile-time XLA cost capture (``ops/registry.py``): per-jit-cache-
  entry flops / bytes / output+temp footprint aggregated into
  ``runtime_stats.snapshot()["costs"]``, achieved GB/s / GFLOP/s via
  profiled dispatch wall-time, and the roofline ordering;
- the diagnostic dump: ``dump_diag`` atomic JSON, the SIGUSR1 handler,
  and the ``python -m mxnet_tpu.runtime_stats`` CLI exiting 0 with the
  new report sections on a fresh process (tier-1 satellite).

Cost capture only runs while telemetry is active (profiler on /
MXNET_TPU_DIAG / MXNET_TPU_COST_ANALYSIS=1), so tests that need cost
rows turn the profiler on before compiling their ops, and use
test-unique attr values to force first-call misses (the per-op jit
cache is process-global).
"""

import gc
import json
import os
import signal
import subprocess
import sys
import weakref

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, device_memory, gluon, profiler, runtime_stats
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracker():
    saved_config = dict(profiler._state["config"])
    device_memory.reset()
    device_memory.stop()
    runtime_stats.reset()
    yield
    profiler.set_state("stop")
    profiler._state["events"] = []
    profiler._state["config"] = saved_config
    device_memory.reset()
    device_memory.stop()
    runtime_stats.reset()


# ------------------------------------------------------- buffer tracker


def test_tracker_disabled_by_default_and_counts_nothing():
    assert not device_memory.is_enabled()
    mx.nd.ones((8, 8)) + 1.0
    snap = device_memory.snapshot()
    assert snap["totals"]["allocations"] == 0
    assert snap["per_op"] == {} and snap["per_dtype"] == {}


def test_alloc_free_peak_accounting():
    device_memory.start()
    x = mx.nd.ones((64, 64))  # 16 KiB fp32
    snap = device_memory.snapshot()
    assert snap["enabled"]
    assert snap["totals"]["live_bytes"] >= 64 * 64 * 4
    assert snap["totals"]["allocations"] >= 1
    assert "ones" in snap["per_op"]
    assert snap["per_op"]["ones"]["live_bytes"] >= 64 * 64 * 4

    y = (x + x) * 2.03271  # dispatch outputs get the creating op label
    snap = device_memory.snapshot()
    assert "broadcast_add" in snap["per_op"]
    assert "float32" in snap["per_dtype"]
    live_with_y = snap["totals"]["live_bytes"]
    assert snap["totals"]["peak_bytes"] >= live_with_y

    # the tracker must hold no strong reference: dropping the NDArray
    # frees the buffer, the finalizer decrements live accounting
    buf_ref = weakref.ref(y._data)
    del y
    gc.collect()
    assert buf_ref() is None, "tracker retained the buffer"
    snap = device_memory.snapshot()
    assert snap["totals"]["live_bytes"] < live_with_y
    assert snap["totals"]["frees"] >= 1
    assert snap["totals"]["freed_bytes"] >= 64 * 64 * 4
    del x


def test_views_of_one_buffer_count_once():
    device_memory.start()
    x = mx.nd.ones((32, 32))
    base = device_memory.snapshot()["totals"]
    x.detach()  # new NDArray over the SAME jax buffer
    after = device_memory.snapshot()["totals"]
    assert after["allocations"] == base["allocations"]
    assert after["live_bytes"] == base["live_bytes"]
    del x


def test_reset_releases_references_and_zeroes():
    device_memory.start()
    x = mx.nd.ones((32, 32))
    assert device_memory.snapshot()["totals"]["allocations"] >= 1
    device_memory.reset()
    snap = device_memory.snapshot()
    assert snap["totals"] == {"live_bytes": 0, "live_count": 0,
                              "peak_bytes": 0, "allocated_bytes": 0,
                              "allocations": 0, "freed_bytes": 0,
                              "frees": 0}
    assert snap["per_op"] == {} and snap["per_dtype"] == {}
    assert device_memory._live == {}
    # finalizers were detached: the buffer dies with its NDArray and
    # its (stale) death must not corrupt the zeroed accounting
    wr = weakref.ref(x._data)
    del x
    gc.collect()
    assert wr() is None
    assert device_memory.snapshot()["totals"]["live_bytes"] == 0


def test_twenty_step_gluon_loop_accounting_and_counter_events(tmp_path):
    """The acceptance loop: 20 Gluon steps with autograd — live/peak
    accounting plausible, per-op/per-dtype breakdowns populated, and
    the dumped chrome trace carries the memory-timeline counter
    events."""
    profiler.set_config(filename=str(tmp_path / "mem_trace.json"))
    profiler.set_state("run")
    device_memory.start()
    runtime_stats.reset()

    net = nn.Dense(4)
    net.initialize(ctx=mx.cpu())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    X = rs.rand(40, 6).astype(np.float32)
    Y = rs.randint(0, 4, (40,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=2)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    steps = 0
    for batch in it:
        with autograd.record():
            out = net(batch.data[0])
            L = loss_fn(out, batch.label[0])
        L.backward()
        trainer.step(2)
        steps += 1
    assert steps == 20
    path = profiler.dump(finished=True)

    mem = runtime_stats.snapshot()["memory"]
    assert mem["enabled"]
    t = mem["totals"]
    assert t["live_bytes"] > 0
    assert t["peak_bytes"] >= t["live_bytes"]
    assert t["allocations"] > t["live_count"]  # step temporaries died
    assert t["frees"] > 0
    assert "float32" in mem["per_dtype"]
    # dispatch outputs carry their creating op
    assert any(op in mem["per_op"]
               for op in ("FullyConnected", "sgd_update", "mean"))

    trace = json.load(open(path))["traceEvents"]
    cev = [e for e in trace if e.get("ph") == "C"
           and e["name"] == "device_memory"]
    assert cev, "no memory counter events in the chrome trace"
    assert all({"live_bytes", "peak_bytes"} <= set(e["args"]) for e in cev)
    peaks = [e["args"]["peak_bytes"] for e in cev]
    assert peaks == sorted(peaks), "peak counter must be monotonic"
    assert any(e["args"]["live_bytes"] > 0 for e in cev)


# --------------------------------------------------------- cost capture


def test_cost_capture_off_when_telemetry_off():
    assert not profiler.is_running()
    from mxnet_tpu.ops import registry

    if os.environ.get("MXNET_TPU_DIAG") \
            or os.environ.get("MXNET_TPU_COST_ANALYSIS") == "1":
        pytest.skip("telemetry env active in this run")
    assert not registry.cost_capture_active()
    # the registry is process-global (other tests may have analyzed
    # entries with the profiler on) — assert on the DELTA of a fresh
    # miss: a new cache entry appears, no new analysis does
    before = runtime_stats.snapshot()["costs"].get("clip", {})
    x = mx.nd.ones((8, 8))
    mx.nd.clip(x, -3.0271, 3.0271)  # unique attrs -> first-call miss
    after = runtime_stats.snapshot()["costs"]["clip"]
    assert after["cache_entries"] == before.get("cache_entries", 0) + 1
    assert after.get("analyzed", 0) == before.get("analyzed", 0)


def test_cost_capture_and_roofline_with_profiler_on():
    from mxnet_tpu.ndarray.ndarray import imperative_invoke

    profiler.set_state("run")
    runtime_stats.reset()
    x = mx.nd.ones((128, 128))
    # unique alpha -> a guaranteed fresh cache entry (and so a fresh
    # analysis) even when other suite tests already compiled the op
    for _ in range(4):
        y = imperative_invoke("linalg_gemm2", [x, x],
                              {"alpha": 1.031741})[0]
    y.wait_to_read()

    snap = runtime_stats.snapshot()
    cost = snap["costs"].get("linalg_gemm2")
    assert cost and cost["cache_entries"] >= 1
    if not cost.get("analyzed"):
        pytest.skip("backend exposes no cost/memory analysis")
    # a 128x128x128 matmul: ~2*128^3 flops in the cost model (the mean
    # over entries dilutes if other alphas were analyzed; stay loose)
    assert cost.get("flops_per_call", 0) >= 128 ** 3
    assert cost.get("bytes_per_call", 0) >= 2 * 128 * 128 * 4
    assert cost.get("output_bytes", 0) >= 128 * 128 * 4

    s = snap["ops"]["linalg_gemm2"]
    # cache-warm calls only: the miss's compile-dominated wall-time
    # must stay out of the achieved-rate denominator
    assert s["timed_calls"] == s["hits"] >= 3
    assert s["dispatch_seconds"] > 0

    # this process runs on "cpu", which the peaks table does not list:
    # rates yes, but no bound and no headroom — never a default chip
    row = next(r for r in runtime_stats.roofline(snap)
               if r["op"] == "linalg_gemm2")
    assert row["achieved_gbps"] > 0 and row["achieved_gflops"] > 0
    assert "bound_us" not in row and "headroom_us" not in row
    with pytest.raises(KeyError, match="no published peaks"):
        runtime_stats.device_peaks()
    # the same dump read against a chip from the table
    assert runtime_stats.device_peaks("TPU v5 lite") == {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    rows = runtime_stats.roofline(snap, device_kind="TPU v5 lite")
    row = next(r for r in rows if r["op"] == "linalg_gemm2")
    assert row["headroom_us"] == pytest.approx(
        row["us_per_call"] - row["bound_us"])
    # rows come sorted by headroom descending
    heads = [r["headroom_us"] for r in rows if "headroom_us" in r]
    assert heads == sorted(heads, reverse=True)

    report = runtime_stats.report()
    for section in ("XLA cost model", "Jit-cache footprint",
                    "Device memory"):
        assert section in report
    assert "linalg_gemm2" in report


def test_report_sections_present_on_empty_state():
    runtime_stats.reset()
    report = runtime_stats.report()
    for section in ("XLA cost model", "Jit-cache footprint",
                    "Device memory"):
        assert section in report


# ------------------------------------------------------ diagnostic dump


def test_dump_diag_atomic_and_loadable(tmp_path):
    profiler.set_state("run")
    x = mx.nd.ones((16, 16))
    mx.nd.clip(x, -4.0441, 4.0441)
    profiler.set_state("stop")
    p = runtime_stats.dump_diag(str(tmp_path / "diag.json"), top=5)
    assert os.path.exists(p)
    data = json.load(open(p))
    assert data["version"] == 1
    assert data["pid"] == os.getpid()
    assert "snapshot" in data and "roofline" in data
    assert "memory" in data["snapshot"] and "costs" in data["snapshot"]
    assert len(data["roofline"]) <= 5
    # no temp file left behind
    assert [f for f in os.listdir(tmp_path)] == ["diag.json"]


def test_sigusr1_handler_dumps(tmp_path):
    sig = getattr(signal, "SIGUSR1", None)
    if sig is None:
        pytest.skip("no SIGUSR1 on this platform")
    path = str(tmp_path / "sig_diag.json")
    old = signal.getsignal(sig)
    try:
        assert runtime_stats._install_diag_handler(path)
        os.kill(os.getpid(), sig)
        assert os.path.exists(path)
        data = json.load(open(path))
        assert data["pid"] == os.getpid()
    finally:
        signal.signal(sig, old)


def test_cli_renders_a_dump(tmp_path, capsys):
    p = runtime_stats.dump_diag(str(tmp_path / "cli_diag.json"))
    assert runtime_stats.main([p]) == 0
    out = capsys.readouterr().out
    for section in ("XLA cost model", "Jit-cache footprint",
                    "Device memory", "Recent storm keys"):
        assert section in out


def test_diag_timing_populates_rates_without_profiler(monkeypatch):
    """The flagship MXNET_TPU_DIAG-only workflow (no profiler) must
    still fill the roofline's rate columns: DIAG turns on cache-warm
    dispatch timing."""
    from mxnet_tpu.ndarray.ndarray import imperative_invoke

    assert not profiler.is_running()
    monkeypatch.setenv("MXNET_TPU_DIAG", "/tmp/unused_diag.json")
    monkeypatch.setattr(runtime_stats, "DIAG_TIMING", True)
    runtime_stats.reset()
    x = mx.nd.ones((64, 64))
    for _ in range(4):
        y = imperative_invoke("linalg_gemm2", [x, x],
                              {"alpha": 1.0598231})[0]
    y.wait_to_read()
    s = runtime_stats.snapshot()["ops"]["linalg_gemm2"]
    assert s["timed_calls"] == s["hits"] >= 3
    assert s["dispatch_seconds"] > 0
    assert profiler._state["events"] == [], \
        "DIAG timing must not allocate profiler events"
    row = next(r for r in runtime_stats.roofline()
               if r["op"] == "linalg_gemm2")
    assert row.get("achieved_gbps", 0) > 0


def test_cost_capture_env_toggles_at_runtime(monkeypatch):
    """The activation envs are read live, not frozen at import: =0
    vetoes everything, =1 or MXNET_TPU_DIAG enable without the
    profiler."""
    from mxnet_tpu.ops import registry

    assert not profiler.is_running()
    monkeypatch.delenv("MXNET_TPU_DIAG", raising=False)
    monkeypatch.setenv("MXNET_TPU_COST_ANALYSIS", "1")
    assert registry.cost_capture_active()
    monkeypatch.setenv("MXNET_TPU_COST_ANALYSIS", "0")
    monkeypatch.setenv("MXNET_TPU_DIAG", "/tmp/whatever.json")
    assert not registry.cost_capture_active()  # explicit 0 wins
    monkeypatch.delenv("MXNET_TPU_COST_ANALYSIS")
    assert registry.cost_capture_active()  # DIAG alone enables


def test_cli_reader_does_not_clobber_diag_dump(tmp_path):
    """A reader process inheriting MXNET_TPU_DIAG from the shell must
    not overwrite the dump it came to display with its own (empty)
    exit snapshot."""
    path = runtime_stats.dump_diag(str(tmp_path / "diag.json"))
    writer_pid = json.load(open(path))["pid"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_DIAG=path)
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "-m", "mxnet_tpu.runtime_stats",
                          path], cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert json.load(open(path))["pid"] == writer_pid, \
        "reader's atexit dump clobbered the training run's diag file"


def test_cli_fresh_process_exits_zero_with_sections():
    """Tier-1 satellite: `python -m mxnet_tpu.runtime_stats` on a fresh
    process prints the report (with the new sections) and exits 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "-m", "mxnet_tpu.runtime_stats"],
                         cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    for section in ("Op", "XLA cost model", "Jit-cache footprint",
                    "Device memory"):
        assert section in res.stdout
