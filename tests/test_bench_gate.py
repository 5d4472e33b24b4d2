"""The no-fallback accelerator context and device provisioning, plus the
pay-for-use bounds of every telemetry layer.

A path that names a chip and finds none fails; it never falls back to
the CPU.  (The benchmark's own refusal of anything but a TPU is pinned
in ``tests/benchmark/test_harness.py``.)
"""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_accelerator_context_without_a_chip_raises():
    """mx.tpu()/mx.gpu() name a chip: with none attached (this CPU
    platform), or an index past the device count, resolving the device
    raises — no fallback to the host, no modulo onto chip 0.
    current_context() stays a default: CPU here."""
    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError

    assert mx.current_context().device_type == "cpu"
    for ctx in (mx.tpu(), mx.gpu(0), mx.tpu(3)):
        with pytest.raises(MXNetError, match="accelerator"):
            ctx.jax_device
    with pytest.raises(MXNetError):
        mx.nd.ones((2,), ctx=mx.tpu())


def test_provision_devices_never_probes_in_a_child(monkeypatch):
    """__graft_entry__._provision_devices uses jax's own devices
    in-process.  Too few on the CPU platform that was asked for
    explicitly: the virtual-mesh re-exec (and nothing else) is spawned.
    Too few anywhere else: an error, never a quiet CPU mesh."""
    import subprocess

    import pytest

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(REPO, "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    monkeypatch.delenv("_MXTPU_DRYRUN_REEXEC", raising=False)
    spawned = []

    def fake_call(cmd, env=None):
        spawned.append((cmd, env))
        return 0

    monkeypatch.setattr(subprocess, "call", fake_call)
    monkeypatch.setattr(subprocess, "run", None)  # any probe would crash
    assert len(ge._provision_devices(8)) == 8 and not spawned
    assert ge._provision_devices(16) is None
    (cmd, env), = spawned
    assert cmd[-2:] == ["dryrun", "16"]
    assert "--xla_force_host_platform_device_count=16" in env["XLA_FLAGS"]
    assert env["_MXTPU_DRYRUN_REEXEC"] == "1"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="jax sees 8 cpu"):
        ge._provision_devices(16)
    assert len(spawned) == 1


def test_disabled_instrumentation_dispatch_overhead_bound():
    """PR 2 gate: telemetry must be pay-for-use.  With the profiler off
    and the jit cache hot, imperative dispatch must (a) allocate zero
    profiler events and (b) keep per-call host time within noise of the
    seed's dispatch path.  (b) is enforced as a generous absolute bound:
    the added guard is one dict read + two counter increments (~1µs),
    while the whole dispatch costs ~50-200µs on CI CPU — the bound only
    trips if always-on instrumentation grows real per-call work."""
    import time

    import mxnet_tpu as mx
    from mxnet_tpu import profiler, runtime_stats

    assert not profiler.is_running()
    x = mx.nd.ones((8, 8))
    for _ in range(3):
        mx.nd.clip(x, -2.03125, 2.03125)  # warm the jit cache
    n_events = len(profiler._state["events"])

    n_calls = 200
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            mx.nd.clip(x, -2.03125, 2.03125)
        best = min(best, (time.perf_counter() - t0) / n_calls)

    assert len(profiler._state["events"]) == n_events, \
        "disabled profiler must not allocate events on the hot path"
    assert best < 2e-3, \
        "cached dispatch with telemetry off took %.1fus/call" % (best * 1e6)
    # the always-on counter layer must have seen every call
    st = runtime_stats.snapshot()["ops"]["clip"]
    assert st["calls"] >= 5 * n_calls


def test_disabled_tracker_creation_overhead_bound():
    """PR 3 gate: the device-buffer tracker must be pay-for-use.  With
    tracking compiled in but OFF (the default), wrapping a buffer in an
    NDArray pays one dict read — pinned as a generous absolute bound on
    the constructor, and as zero accounting recorded."""
    import time

    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu import device_memory
    from mxnet_tpu.ndarray import NDArray

    if os.environ.get("MXNET_TPU_DIAG") \
            or os.environ.get("MXNET_TPU_MEMORY_TRACK") == "1":
        pytest.skip("memory-tracking env active in this run")
    assert not device_memory.is_enabled()
    base = device_memory.snapshot()["totals"]
    x = mx.nd.ones((8, 8))

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            NDArray(x._data)
        best = min(best, (time.perf_counter() - t0) / n_calls)
    # the raw constructor is ~1us of slot writes; 100us tolerates slow
    # shared CI while still catching any real per-wrap work
    assert best < 1e-4, \
        "NDArray wrap with tracker off took %.1fus" % (best * 1e6)
    assert device_memory.snapshot()["totals"] == base, \
        "disabled tracker must record nothing"


def test_disabled_health_observe_overhead_bound():
    """PR 5 gate: the numerics health layer must be pay-for-use.  With
    the monitor disabled (the default), feeding a tensor to
    ``health.observe`` — the hook every surface (trainer, executor,
    cached-graph outputs) calls — is ONE dict read: no kernel, no queue
    entry, no counter.  Pinned as a generous absolute bound plus
    zero-state assertions."""
    import time

    import mxnet_tpu as mx
    from mxnet_tpu import health, runtime_stats

    assert not health.is_enabled()
    x = mx.nd.ones((8, 8))
    kernels_before = dict(health._KERNELS)
    base_observed = runtime_stats.snapshot()["counters"].get(
        "health_observed", 0)

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            health.observe("bench", x)
        best = min(best, (time.perf_counter() - t0) / n_calls)
    # the guard is a module attr + dict read (~0.1us); 10us tolerates
    # slow shared CI while catching any real disabled-path work
    assert best < 1e-5, \
        "health.observe with monitor off took %.2fus" % (best * 1e6)
    assert dict(health._KERNELS) == kernels_before, \
        "disabled observe must not build stat kernels"
    assert runtime_stats.snapshot()["counters"].get(
        "health_observed", 0) == base_observed, \
        "disabled observe must record nothing"


def test_disabled_checkpoint_step_overhead_bound():
    """PR 6 gate: the checkpoint layer must be pay-for-use.  With the
    manager disabled (the default), the ``checkpoint.on_step`` hook
    ``gluon.Trainer.step`` calls every step is ONE dict read: no
    manager, no capture, no thread, no counter.  Pinned like the
    health/telemetry bounds above."""
    import time

    from mxnet_tpu import checkpoint, runtime_stats

    assert not checkpoint.is_enabled()
    assert checkpoint._GLOBAL == []
    base_saves = runtime_stats.snapshot()["counters"].get(
        "checkpoint_saves", 0)

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            checkpoint.on_step(None)
        best = min(best, (time.perf_counter() - t0) / n_calls)
    # the guard is a module attr + dict read (~0.1us); 10us tolerates
    # slow shared CI while catching any real disabled-path work
    assert best < 1e-5, \
        "checkpoint.on_step with manager off took %.2fus" % (best * 1e6)
    assert checkpoint._GLOBAL == [], \
        "disabled on_step must not create a manager"
    assert runtime_stats.snapshot()["counters"].get(
        "checkpoint_saves", 0) == base_saves, \
        "disabled on_step must record nothing"


def test_disabled_histogram_observe_overhead_bound():
    """PR 7 gate: latency histograms must be pay-for-use.  With
    collection disabled (the default), ``histogram.observe`` — the hook
    the kvstore RTT / io / checkpoint / trainer feeds call — is ONE
    dict read: no bucket math, no Histogram allocation.  The feeding
    sites additionally guard BEFORE taking timestamps, so the off path
    pays no clock reads either (asserted via zero recorded state)."""
    import time

    import pytest

    from mxnet_tpu import histogram, runtime_stats

    if os.environ.get("MXNET_TPU_HISTOGRAMS") == "1" \
            or os.environ.get("MXNET_TPU_DIAG") \
            or os.environ.get("MXNET_TPU_PROFILE"):
        pytest.skip("histogram collection active in this run")
    assert not histogram.is_enabled()

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            histogram.observe("bench", 0.001)
        best = min(best, (time.perf_counter() - t0) / n_calls)
    # the guard is one dict read (~0.1us); 10us tolerates slow shared
    # CI while catching any real disabled-path work
    assert best < 1e-5, \
        "histogram.observe with collection off took %.2fus" % (best * 1e6)
    assert histogram.snapshot() == {}, \
        "disabled observe must record nothing"
    assert "bench" not in runtime_stats.snapshot()["histograms"]


def test_serving_layer_costs_training_imports_nothing():
    """PR 12 gate: the serving subsystem must be pay-for-use.  (a) A
    training process never imports it — ``import mxnet_tpu`` leaves
    ``mxnet_tpu.serving`` out of sys.modules (runtime_stats reads the
    serving section via sys.modules, never an import), so an idle/
    absent server adds ZERO import cost to training.  (b) Importing the
    module is inert: no threads, no histogram enablement, no counters —
    costs start only when an InferenceServer is constructed."""
    import subprocess
    import sys as _sys
    import threading

    from conftest import hermetic_subprocess_env

    r = subprocess.run(
        [_sys.executable, "-c",
         "import mxnet_tpu, sys; "
         "assert 'mxnet_tpu.serving' not in sys.modules, "
         "'training imports pulled in the serving layer'"],
        capture_output=True, text=True, timeout=300,
        env=hermetic_subprocess_env(REPO))
    assert r.returncode == 0, r.stdout + r.stderr

    import importlib

    from mxnet_tpu import histogram, runtime_stats

    hist_was_on = histogram.is_enabled()
    threads_before = {t.name for t in threading.enumerate()}
    counters_before = dict(runtime_stats.snapshot()["counters"])
    importlib.import_module("mxnet_tpu.serving")
    assert histogram.is_enabled() == hist_was_on, \
        "importing serving must not flip histogram collection"
    new_threads = {t.name for t in threading.enumerate()} \
        - threads_before
    assert not any(n.startswith("mxtpu-serve") for n in new_threads), \
        "importing serving must not start threads"
    after = runtime_stats.snapshot()["counters"]
    assert not any(k.startswith("serve") for k in set(after)
                   - set(counters_before)), \
        "importing serving must not record counters"


def test_disabled_heartbeat_and_seq_stamp_overhead_bound(ps_server):
    """PR 9 gate: self-healing must be pay-for-use.  Without
    MXNET_TPU_KV_DEADLINE (the default) the client starts NO heartbeat
    thread and opens no probe sockets; the per-request exactly-once
    header (``PSClient._stamp``) is O(1) — one counter increment + one
    small dict — pinned like the other disabled-path bounds."""
    import threading
    import time

    import pytest

    from mxnet_tpu.kvstore.ps import PSClient

    if os.environ.get("MXNET_TPU_KV_DEADLINE"):
        pytest.skip("kvstore heartbeat active in this run")
    c = PSClient(connect_timeout=10)
    try:
        assert c._hb_thread is None, \
            "no deadline env must mean no heartbeat thread"
        assert not any(t.name == "mxtpu-kv-heartbeat"
                       for t in threading.enumerate())

        n_calls = 1000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n_calls):
                c._stamp()
            best = min(best, (time.perf_counter() - t0) / n_calls)
        # the stamp is one itertools.count next + a dict literal
        # (~0.2us); 10us tolerates slow shared CI while catching any
        # real per-request work creeping in
        assert best < 1e-5, \
            "per-request seq stamp took %.2fus" % (best * 1e6)
    finally:
        c.close()


def test_disabled_stepstats_overhead_bound():
    """PR 8 gate: step-time attribution must be pay-for-use.  With
    attribution disabled (the default), every feeding hook —
    ``stepstats.add`` (leaf phases), ``stepstats.end`` (container
    phases), ``stepstats.end_step`` (the Trainer boundary) — is ONE
    dict read: no timestamps, no window arithmetic, no Histogram
    allocation.  Feeding sites additionally guard BEFORE calling
    ``begin()``, so the off path pays no clock reads either (asserted
    via zero recorded state)."""
    import time

    import pytest

    from mxnet_tpu import stepstats

    if os.environ.get("MXNET_TPU_STEPSTATS") == "1" \
            or os.environ.get("MXNET_TPU_DIAG") \
            or os.environ.get("MXNET_TPU_PROFILE"):
        pytest.skip("step-time attribution active in this run")
    assert not stepstats.is_enabled()

    n_calls = 1000
    best = {"add": float("inf"), "end": float("inf"),
            "end_step": float("inf")}
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            stepstats.add("bench", 0.001)
        best["add"] = min(best["add"],
                          (time.perf_counter() - t0) / n_calls)
        t0 = time.perf_counter()
        for _ in range(n_calls):
            stepstats.end("bench", None)
        best["end"] = min(best["end"],
                          (time.perf_counter() - t0) / n_calls)
        t0 = time.perf_counter()
        for _ in range(n_calls):
            stepstats.end_step()
        best["end_step"] = min(best["end_step"],
                               (time.perf_counter() - t0) / n_calls)
    for name, b in best.items():
        # the guard is one dict read (~0.1us); 10us tolerates slow
        # shared CI while catching any real disabled-path work
        assert b < 1e-5, \
            "stepstats.%s with attribution off took %.2fus" % (
                name, b * 1e6)
    snap = stepstats.snapshot()
    assert snap["steps"] == 0, "disabled hooks must record nothing"
    assert "phases" not in snap


def test_disabled_metrics_timeline_overhead_bound():
    """PR 10 gate: the live metrics timeline must be pay-for-use.  With
    the timeline disabled (the default), ``metrics_timeline.on_step`` —
    the hook ``gluon.Trainer.step`` guards with one dict read — is
    itself ONE dict read: no clock, no sample dict, no counter deltas,
    no file write.  Pinned like the other disabled-path bounds."""
    import time

    import pytest

    from mxnet_tpu import metrics_timeline

    if os.environ.get("MXNET_TPU_METRICS") \
            or os.environ.get("MXNET_TPU_METRICS_PORT") \
            or os.environ.get("MXNET_TPU_DIAG") \
            or os.environ.get("MXNET_TPU_PROFILE"):
        pytest.skip("metrics timeline active in this run")
    assert not metrics_timeline.is_enabled()
    # baseline, not absolute zero: an earlier in-process timeline user
    # (the example, test_metrics_timeline) leaves a readable ring behind
    before = metrics_timeline.snapshot()

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            metrics_timeline.on_step(32)
        best = min(best, (time.perf_counter() - t0) / n_calls)
    # the guard is one dict read (~0.1us); 10us tolerates slow shared
    # CI while catching any real disabled-path work
    assert best < 1e-5, \
        "metrics_timeline.on_step with timeline off took %.2fus" \
        % (best * 1e6)
    after = metrics_timeline.snapshot()
    assert after["samples"] == before["samples"], \
        "disabled on_step must record nothing"
    assert after["step"] == before["step"]


def test_disabled_xray_annotation_overhead_bound():
    """PR 15 gate: fused-step x-ray annotation must be pay-for-use.
    With annotation disabled (``MXNET_TPU_XRAY=0``), ``xray.scope`` —
    the helper every fused-step tracer and ``Block.__call__`` route
    through — is ONE dict read returning a shared null context: no jax
    import, no named_scope allocation.  (HLO attribution itself runs
    only at the two compile sites, never per step.)  Pinned like the
    other disabled-path bounds."""
    import time

    import pytest

    from mxnet_tpu import xray

    if os.environ.get("MXNET_TPU_XRAY") == "1":
        pytest.skip("x-ray annotation force-enabled in this run")
    was_on = xray.is_enabled()
    xray.disable()
    try:
        n_calls = 1000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n_calls):
                xray.scope(xray.REGION_OPT)
            best = min(best, (time.perf_counter() - t0) / n_calls)
        # the guard is one dict read (~0.1us); 10us tolerates slow
        # shared CI while catching any real disabled-path work
        assert best < 1e-5, \
            "xray.scope with annotation off took %.2fus" % (best * 1e6)
        assert xray.scope("anything") is xray._NULL
    finally:
        if was_on:
            xray.enable()


def test_disabled_autopilot_overhead_bound():
    """PR 17 gate: the observability autopilot must be pay-for-use.
    With the reflex engine disabled (the default), ``autopilot.on_step``
    and ``autopilot.on_serve`` — the hooks at the ``Trainer.step`` tail
    and the serving accounting path — are ONE dict read each: no clock,
    no doctor rules, no ledger entry, no counter.  Pinned like the
    other disabled-path bounds."""
    import time

    import pytest

    from mxnet_tpu import autopilot, runtime_stats

    if os.environ.get("MXNET_TPU_AUTOPILOT"):
        pytest.skip("autopilot force-enabled in this run")
    assert not autopilot.is_enabled()
    before = autopilot.ledger_section()
    clock_before = autopilot._train_clock["n"]
    base_evals = runtime_stats.snapshot()["counters"].get(
        "autopilot_evals", 0)

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            autopilot.on_step(None)
            autopilot.on_serve(None)
        best = min(best, (time.perf_counter() - t0) / (2 * n_calls))
    # the guard is one dict read (~0.1us); 10us tolerates slow shared
    # CI while catching any real disabled-path work
    assert best < 1e-5, \
        "autopilot seam with engine off took %.2fus" % (best * 1e6)
    after = autopilot.ledger_section()
    assert after["entries"] == before["entries"], \
        "disabled seams must record nothing"
    assert after["counters"] == before["counters"]
    assert autopilot._train_clock["n"] == clock_before, \
        "disabled on_step must not even tick its clock"
    assert runtime_stats.snapshot()["counters"].get(
        "autopilot_evals", 0) == base_evals


def test_disabled_reqtrace_overhead_bound():
    """PR 20 gate: the request x-ray must be pay-for-use.  With tracing
    disabled (the default), every lifecycle feed — ``on_submit`` /
    ``on_submitted`` / ``on_join`` / ``on_exec`` / ``on_done`` — is ONE
    dict read: no id assignment, no record, no ring append, no profiler
    touch.  Pinned like the other disabled-path bounds."""
    import time

    import pytest

    from mxnet_tpu import reqtrace

    flag = os.environ.get("MXNET_TPU_REQTRACE")
    if flag and flag != "0":
        pytest.skip("request tracing force-enabled in this run")
    assert not reqtrace.is_enabled()
    before = reqtrace.snapshot()

    class _Req:
        pass

    req = _Req()
    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            reqtrace.on_submit(req, 0)
            reqtrace.on_submitted(req)
            reqtrace.on_done(req, "ok")
        best = min(best, (time.perf_counter() - t0) / (3 * n_calls))
    # the guard is one dict read (~0.1us); 10us tolerates slow shared
    # CI while catching any real disabled-path work
    assert best < 1e-5, \
        "reqtrace seam with tracing off took %.2fus" % (best * 1e6)
    assert not hasattr(req, "trace"), \
        "disabled on_submit must not touch the request"
    assert reqtrace.snapshot() == before, \
        "disabled seams must record nothing"


def test_disabled_slo_overhead_bound():
    """PR 20 gate: SLO accounting must be pay-for-use.  With no
    objective declared (the default), ``slo.on_request`` — one call per
    finished request on the serving path — is ONE dict read: no clock,
    no lock, no event append.  Pinned like the other disabled-path
    bounds."""
    import time

    import pytest

    from mxnet_tpu import slo

    if os.environ.get("MXNET_TPU_SLO"):
        pytest.skip("SLO objectives force-enabled in this run")
    assert not slo.is_enabled()

    n_calls = 1000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            slo.on_request(1.0, True)
        best = min(best, (time.perf_counter() - t0) / n_calls)
    # the guard is one dict read (~0.1us); 10us tolerates slow shared
    # CI while catching any real disabled-path work
    assert best < 1e-5, \
        "slo.on_request with no objective took %.2fus" % (best * 1e6)
    assert slo.snapshot() == {"enabled": False}, \
        "disabled accounting must record nothing"
