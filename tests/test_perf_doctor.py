"""PR 8: step-time attribution (stepstats), the perf doctor, and
dump-diff regression reports.

Pins the acceptance criteria:

- on a ~20-step Gluon loop the per-phase attribution sums to <= the
  step wall time with the remainder explicit;
- ``--doctor`` on an induced recompile-storm + delayed-io run names
  both bottlenecks, ranked correctly (compile share > data-wait share);
- ``--compare`` on two dumps with an injected slowdown flags exactly
  the regressed phase, and is quiet on identical dumps;
- the doctor/compare CLIs finish inside a wall-time budget and emit
  ``::error``/``::notice`` annotations under ``--format github``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (autograd, gluon, histogram, perfdoctor,
                       runtime_stats, stepstats)
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# per-loop offset for the attr-churn storm: the per-op jit cache is
# process-global, so each _train_loop(storm=True) needs attr values no
# earlier test already compiled
_STORM_SEQ = iter(range(0, 10 ** 6, 1000))


@pytest.fixture(autouse=True)
def _clean_stepstats():
    """Each test starts and ends with attribution off and no state."""
    was_on = stepstats.is_enabled()
    runtime_stats.reset()  # also resets stepstats + histograms
    stepstats.disable()
    histogram.disable()
    yield
    runtime_stats.reset()
    if was_on:
        stepstats.enable()
    else:
        stepstats.disable()
    histogram.disable()


def _train_loop(steps=20, delay_io=0.0, storm=False, batch=2):
    """The canonical ~20-step Gluon loop, optionally with a delayed
    iterator and a per-step attr-churned op (one fresh compile per
    step)."""
    net = nn.Dense(4)
    net.initialize(ctx=mx.cpu())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    X = rs.rand(steps * batch, 6).astype(np.float32)
    Y = rs.randint(0, 4, (steps * batch,)).astype(np.float32)

    class SlowIter(mx.io.NDArrayIter):
        def next(self):
            if delay_io:
                time.sleep(delay_io)
            return super().next()

    it = SlowIter(X, Y, batch_size=batch)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.ones((4, 4))
    base = 31337.0 + next(_STORM_SEQ)
    n = 0
    for b in it:
        with autograd.record():
            L = loss_fn(net(b.data[0]), b.label[0])
        L.backward()
        trainer.step(batch)
        if storm:
            # unique attr per step -> a fresh jit-cache key per step:
            # the canonical recompile storm
            mx.nd.clip(x, 0.0, base + n)
        n += 1
    return n


# ------------------------------------------------- step-time attribution


def test_attribution_sums_to_at_most_step_wall():
    """ACCEPTANCE: per-phase attribution sums to <= step wall, with the
    remainder explicit, on the 20-step Gluon loop."""
    stepstats.enable()
    steps = _train_loop(steps=20)
    assert steps == 20
    snap = stepstats.snapshot()
    # the first boundary only arms the clock: 19 full windows
    assert snap["steps"] == 19
    assert snap["overattributed"] == 0
    wall_sum = snap["wall"]["sum"]
    phase_sum = sum(h["sum"] for h in snap["phases"].values())
    assert phase_sum <= wall_sum + 1e-9
    # the remainder is explicit and closes the budget exactly
    assert snap["unattributed"]["sum"] == pytest.approx(
        wall_sum - phase_sum, rel=1e-6, abs=1e-9)
    # the big phases of this loop actually got attributed
    for phase in ("data_wait", "forward", "backward", "optimizer_update"):
        assert snap["phases"][phase]["sum"] > 0.0, phase
    # per-phase histograms carry one observation per closed window
    for phase, h in snap["phases"].items():
        assert h["count"] == snap["steps"], phase


def test_attribution_containers_are_exclusive():
    """A leaf feed inside a container window is counted once, under its
    own phase: the container records only its exclusive remainder."""
    stepstats.enable()
    stepstats.end_step()  # arm the boundary
    tok = stepstats.begin()
    time.sleep(0.01)
    stepstats.add("compile", 0.004)  # nested leaf attribution
    stepstats.end("kvstore", tok)
    stepstats.end_step()
    snap = stepstats.snapshot()
    assert snap["steps"] == 1
    kv = snap["phases"]["kvstore"]["sum"]
    comp = snap["phases"]["compile"]["sum"]
    assert comp == pytest.approx(0.004)
    # container wall was ~10ms+4ms-leaf... the leaf was *claimed* inside
    # the window, so the container holds window wall minus 4ms
    assert kv > 0.005
    assert kv + comp <= snap["wall"]["sum"] + 1e-9


def test_disabled_records_nothing_and_snapshot_is_stub():
    assert not stepstats.is_enabled()
    stepstats.add("compile", 1.0)
    stepstats.end("kvstore", stepstats.begin())
    stepstats.end_step()
    snap = stepstats.snapshot()
    assert snap["steps"] == 0
    assert "phases" not in snap


def test_enable_raises_dispatch_timing_and_disable_restores():
    assert not runtime_stats.DIAG_TIMING or os.environ.get(
        "MXNET_TPU_DIAG")
    stepstats.enable()
    assert runtime_stats.DIAG_TIMING
    stepstats.disable()
    assert runtime_stats.DIAG_TIMING == bool(
        os.environ.get("MXNET_TPU_DIAG"))


def test_report_and_diag_dump_carry_step_anatomy(tmp_path):
    stepstats.enable()
    _train_loop(steps=6)
    text = runtime_stats.report()
    assert "Step anatomy" in text
    assert "unattributed remainder" in text
    path = runtime_stats.dump_diag(str(tmp_path / "diag.json"))
    data = json.load(open(path))
    ss = data["snapshot"]["stepstats"]
    assert ss["steps"] == 5
    assert set(ss["phases"]) == set(stepstats.PHASES)


def test_device_anatomy_ms_explicit_remainder_and_overlap():
    a = stepstats.device_anatomy_ms(10.0, {"device_compute": 7.0,
                                           "hbm_prefetch": 1.0})
    assert a["unattributed_ms"] == pytest.approx(2.0)
    assert "overlap_ms" not in a
    # async phases can legitimately sum past the wall: surfaced, not
    # hidden — unattributed clamps to 0
    b = stepstats.device_anatomy_ms(10.0, {"device_compute": 9.0,
                                           "hbm_prefetch": 3.0})
    assert b["unattributed_ms"] == 0.0
    assert b["overlap_ms"] == pytest.approx(2.0)


# ------------------------------------------------------------ the doctor


def test_doctor_ranks_recompile_storm_above_delayed_io(
        tmp_path, monkeypatch):
    """ACCEPTANCE: an induced recompile-storm + delayed-io run names
    both bottlenecks, ranked correctly (a per-step XLA compile costs
    far more than the 6ms io delay).  The reporting threshold is
    lowered so a loaded CI box (slow compiles shrinking data_wait's
    share) cannot hide the second finding — the RANKING is the pin."""
    monkeypatch.setattr(perfdoctor, "SHARE_NOTICE", 0.02)
    stepstats.enable()
    _train_loop(steps=20, delay_io=0.006, storm=True)
    path = runtime_stats.dump_diag(str(tmp_path / "diag.json"))
    kind, dump = perfdoctor.classify(path)
    assert kind == "dump"
    findings = perfdoctor.diagnose(dump=dump)
    rules = [f["rule"] for f in findings]
    assert "recompile-storm" in rules
    storm = next(f for f in findings if f["rule"] == "recompile-storm")
    data = next(f for f in findings
                if f["rule"] == "step-anatomy"
                and f["anchor"] == "data_wait")
    # ranked correctly: compile share > data-wait share
    assert rules.index("recompile-storm") < findings.index(data)
    assert storm["score"] > data["score"]
    # evidence names the op and the action is concrete
    assert storm["anchor"] == "clip"
    assert "traced_attrs" in storm["action"]
    assert any("clip" in ev for ev in storm["evidence"])
    # scores are shares of step time: sane bounds
    for f in findings:
        assert 0.0 <= f["score"] <= 1.0


def test_doctor_quiet_on_healthy_run(tmp_path):
    stepstats.enable()
    _train_loop(steps=12)
    path = runtime_stats.dump_diag(str(tmp_path / "diag.json"))
    _kind, dump = perfdoctor.classify(path)
    findings = perfdoctor.diagnose(dump=dump)
    assert all(f["rule"] != "recompile-storm" for f in findings)
    assert all(f["anchor"] != "data_wait" for f in findings)


def test_doctor_idle_gaps_from_trace(tmp_path):
    """A trainer:step span whose interior no other span covers is an
    idle-gap finding naming the worst step."""
    trace = {"traceEvents": [
        # step 0: fully covered by a child span
        {"name": "trainer:step", "ph": "X", "ts": 0, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "trainer:update", "ph": "X", "ts": 0, "dur": 1000,
         "pid": 0, "tid": 1},
        # step 1: 80% uncovered
        {"name": "trainer:step", "ph": "X", "ts": 2000, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "trainer:update", "ph": "X", "ts": 2000, "dur": 200,
         "pid": 0, "tid": 1},
    ]}
    findings = perfdoctor.diagnose(trace=trace)
    assert findings and findings[0]["rule"] == "idle-gaps"
    f = findings[0]
    assert f["score"] == pytest.approx(0.4)  # 800us of 2000us
    assert f["anchor"] == "trainer:step"
    assert any("ts=2000" in ev for ev in f["evidence"])


def test_doctor_idle_gap_not_masked_by_other_ranks_track():
    """In a merged multi-rank trace, another pid's spans must not count
    as coverage for this rank's step."""
    trace = {"traceEvents": [
        {"name": "trainer:step", "ph": "X", "ts": 0, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "autograd:backward", "ph": "X", "ts": 0, "dur": 1000,
         "pid": 1, "tid": 1},
    ]}
    findings = perfdoctor.diagnose(trace=trace)
    assert findings and findings[0]["rule"] == "idle-gaps"
    assert findings[0]["score"] == pytest.approx(1.0)


def test_doctor_no_idle_gap_finding_when_covered():
    trace = {"traceEvents": [
        {"name": "trainer:step", "ph": "X", "ts": 0, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "autograd:backward", "ph": "X", "ts": 0, "dur": 990,
         "pid": 0, "tid": 1},
    ]}
    assert perfdoctor.diagnose(trace=trace) == []


def test_doctor_shard_straggler_from_histograms():
    """One PS shard's RTT p99 an outlier vs the others -> a finding
    naming the shard."""
    snap = {"histograms": {}, "counters": {}, "ops": {}, "totals": {}}
    h_fast = histogram.Histogram()
    h_slow = histogram.Histogram()
    for _ in range(64):
        h_fast.observe(0.001)
        h_slow.observe(0.050)
    snap["histograms"]["kv:push_rtt:shard0"] = h_fast.snapshot()
    snap["histograms"]["kv:push_rtt:shard1"] = h_fast.snapshot()
    snap["histograms"]["kv:push_rtt:shard2"] = h_slow.snapshot()
    findings = perfdoctor.diagnose(dump={"snapshot": snap})
    stragglers = [f for f in findings if f["rule"] == "kvstore-straggler"]
    assert len(stragglers) == 1
    assert stragglers[0]["anchor"] == "kv:push_rtt:shard2"
    assert "shard2" in stragglers[0]["title"]


def test_doctor_host_sync_finding():
    """Deliberate sync sinks that stop being cheap get flagged with
    the span name and a concrete knob."""
    dump = {"snapshot": {
        "counters": {"monitor_seconds": 0.5},
        "ops": {}, "totals": {},
        "stepstats": {
            "enabled": True, "steps": 10, "overattributed": 0,
            "wall": {"count": 10, "sum": 1.0, "min": 0.1, "max": 0.1,
                     "mean": 0.1, "p50": 0.1, "p90": 0.1, "p99": 0.1,
                     "buckets": {}},
            "phases": {}, "unattributed": {"count": 10, "sum": 0.0}}}}
    findings = perfdoctor.diagnose(dump=dump)
    sync = [f for f in findings if f["rule"] == "host-sync"]
    assert sync and sync[0]["anchor"] == "monitor:stat"
    assert sync[0]["score"] == pytest.approx(0.5)
    assert sync[0]["severity"] == "warn"


def test_doctor_github_annotations_escaped():
    findings = [{"rule": "x", "severity": "warn", "score": 0.5,
                 "title": "100% bad\nline", "anchor": "op",
                 "evidence": [], "action": "fix: a,b"}]
    out = perfdoctor.render_github(findings)
    assert out.startswith("::error::")
    assert "%25" in out and "%0A" in out and "\n" not in out


# -------------------------------------------------- dump-diff regression


def _two_dumps(tmp_path, slow_phase_delay):
    """Baseline + candidate dumps from two in-process loops; the
    candidate's iterator sleeps `slow_phase_delay` per batch."""
    stepstats.enable()
    histogram.enable()
    _train_loop(steps=12)
    a = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    runtime_stats.reset()
    stepstats.enable()
    histogram.enable()
    _train_loop(steps=12, delay_io=slow_phase_delay)
    b = runtime_stats.dump_diag(str(tmp_path / "b.json"))
    return a, b


def test_compare_flags_exactly_the_regressed_phase(tmp_path):
    """ACCEPTANCE (deterministic half): a dump differing from its
    baseline ONLY in the data_wait phase flags exactly that phase —
    nothing else."""
    import copy

    stepstats.enable()
    _train_loop(steps=8)
    path = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    a = runtime_stats.load_dumps([path])[0]
    b = copy.deepcopy(a)
    ph = b["snapshot"]["stepstats"]["phases"]["data_wait"]
    ph["sum"] *= 20.0
    result = runtime_stats.compare(a, b)
    assert result["verdict"] == "regression"
    assert [e["metric"] for e in result["regressions"]] \
        == ["phase:data_wait"]
    assert result["improvements"] == []


def test_compare_end_to_end_injected_io_slowdown(tmp_path):
    """ACCEPTANCE (end-to-end half): two real runs, the second with a
    10ms sleep per batch — the verdict is regression and data_wait is
    the WORST phase regression by a wide margin (its ratio dwarfs any
    scheduler jitter on the untouched phases)."""
    a_path, b_path = _two_dumps(tmp_path, slow_phase_delay=0.01)
    a, b = runtime_stats.load_dumps([a_path, b_path])
    result = runtime_stats.compare(a, b)
    assert result["verdict"] == "regression"
    phase_regs = [e for e in result["regressions"]
                  if e["kind"] == "phase"]
    assert phase_regs, result["regressions"]
    worst = max(phase_regs, key=lambda e: e["ratio"])
    assert worst["metric"] == "phase:data_wait"
    assert worst["ratio"] > 5.0
    # the io histogram series regresses consistently with the phase
    assert any(e["metric"].startswith("hist:io:next_batch")
               for e in result["regressions"])


def test_compare_quiet_on_identical_dumps(tmp_path):
    stepstats.enable()
    _train_loop(steps=8)
    path = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    d = runtime_stats.load_dumps([path])[0]
    result = runtime_stats.compare(d, d)
    assert result["verdict"] == "flat"
    assert result["regressions"] == []
    assert result["improvements"] == []
    assert result["compared"] > 0


def test_compare_render_and_verdict_shape(tmp_path):
    a_path, b_path = _two_dumps(tmp_path, slow_phase_delay=0.01)
    a, b = runtime_stats.load_dumps([a_path, b_path])
    result = runtime_stats.compare(a, b)
    text = runtime_stats.render_compare(result)
    assert "VERDICT: regression" in text
    assert "phase:data_wait" in text
    # machine-readable: JSON round-trips
    assert json.loads(json.dumps(result))["verdict"] == "regression"
    for e in result["regressions"]:
        assert set(e) == {"metric", "kind", "unit", "before", "after",
                          "ratio"}


def test_compare_time_counter_noise_below_floor_is_quiet():
    """The *_seconds counters are time-like: microsecond jitter below
    min_seconds must not produce a verdict, while a real change above
    the floor still does."""
    a = {"snapshot": {"counters": {"health_seconds": 2e-5}}}
    b = {"snapshot": {"counters": {"health_seconds": 5e-5}}}
    assert runtime_stats.compare(a, b)["verdict"] == "flat"
    a = {"snapshot": {"counters": {"monitor_seconds": 0.01}}}
    b = {"snapshot": {"counters": {"monitor_seconds": 0.05}}}
    result = runtime_stats.compare(a, b)
    assert result["verdict"] == "regression"
    assert [e["metric"] for e in result["regressions"]] \
        == ["counter:monitor_seconds"]


def test_compare_threshold_is_configurable(tmp_path):
    stepstats.enable()
    _train_loop(steps=8)
    path = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    d = runtime_stats.load_dumps([path])[0]
    import copy

    d2 = copy.deepcopy(d)
    ph = d2["snapshot"]["stepstats"]["phases"]["forward"]
    ph["sum"] = ph["sum"] * 1.15  # +15%
    assert runtime_stats.compare(d, d2, threshold=0.2)["verdict"] == "flat"
    tight = runtime_stats.compare(d, d2, threshold=0.1)
    assert any(e["metric"] == "phase:forward"
               for e in tight["regressions"])


# ------------------------------------------------------------------- CLI


def _run_cli(args, timeout=240):
    from conftest import hermetic_subprocess_env

    env = hermetic_subprocess_env(REPO)
    env.pop("MXNET_TPU_DIAG", None)
    env.pop("MXNET_TPU_PROFILE", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")]
        + args, env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout)


def test_cli_doctor_and_compare_smoke_with_wall_budget(tmp_path):
    """CI satellite: one doctor run + one compare run, github
    annotations present, and the whole CLI round stays inside the
    wall-time budget (these ride tier-1)."""
    stepstats.enable()
    histogram.enable()
    _train_loop(steps=10, storm=True)
    a = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    t0 = time.perf_counter()
    r = _run_cli(["--doctor", a, "--format", "github"])
    assert r.returncode == 0, r.stderr
    assert "Perf doctor:" in r.stdout
    assert "::error::" in r.stdout  # the storm is warn-severity
    assert "recompile" in r.stdout
    r2 = _run_cli(["--compare", a, a, "--format", "github"])
    assert r2.returncode == 0, r2.stderr
    assert '"verdict": "flat"' in r2.stdout
    assert "::error::" not in r2.stdout  # identical dumps: quiet
    elapsed = time.perf_counter() - t0
    # two fresh-interpreter invocations; observed ~8s on CPU CI —
    # catch a pathological doctor/compare slowdown, not noise
    assert elapsed < 120, "doctor+compare CLIs took %.1fs" % elapsed


def test_cli_compare_exit_code_gates_regressions(tmp_path):
    """rc=1 on regression, rc=0 on improvements-only — pinned with a
    synthetic pair (only data_wait differs) so concurrent-CI jitter
    cannot flip the exit codes."""
    import copy

    stepstats.enable()
    _train_loop(steps=8)
    a_path = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    a = runtime_stats.load_dumps([a_path])[0]
    b = copy.deepcopy(a)
    b["snapshot"]["stepstats"]["phases"]["data_wait"]["sum"] *= 20.0
    b_path = str(tmp_path / "b.json")
    with open(b_path, "w") as f:
        json.dump({k: v for k, v in b.items() if k != "_path"}, f)
    r = _run_cli(["--compare", a_path, b_path])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "phase:data_wait" in r.stdout
    # the last line is grep-able machine JSON in text mode too
    verdict_line = [ln for ln in r.stdout.strip().splitlines()
                    if ln.startswith("{")][-1]
    assert json.loads(verdict_line)["verdict"] == "regression"
    # reversed direction: improvements only -> rc 0
    r2 = _run_cli(["--compare", b_path, a_path])
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_cli_doctor_rejects_second_file_of_same_kind(tmp_path):
    """--doctor analyzes one dump (+ one trace); a second file of the
    same kind is a usage error (rc 2), not a silent keep-last."""
    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    for p in (a, b):
        with open(p, "w") as f:
            json.dump({"snapshot": {}}, f)
    r = _run_cli(["--doctor", a, b])
    assert r.returncode == 2, r.stdout + r.stderr
    assert "--cluster" in r.stderr


def test_cli_compare_rejects_directory_operand(tmp_path):
    """--compare diffs exactly two dump files; a directory operand is
    a usage error (rc 2), never a silent diff of the wrong pair."""
    d = tmp_path / "dumps"
    d.mkdir()
    a = str(tmp_path / "a.json")
    with open(a, "w") as f:
        json.dump({"snapshot": {}}, f)
    r = _run_cli(["--compare", str(d), a])
    assert r.returncode == 2, r.stdout + r.stderr
    assert "directory" in r.stderr


def test_cli_doctor_json_output(tmp_path):
    stepstats.enable()
    _train_loop(steps=12, storm=True)
    a = runtime_stats.dump_diag(str(tmp_path / "a.json"))
    r = _run_cli(["--doctor", a, "--json"])
    assert r.returncode == 0, r.stderr
    findings = json.loads(r.stdout)
    assert isinstance(findings, list) and findings
    assert {"rule", "severity", "score", "title", "anchor", "evidence",
            "action"} <= set(findings[0])
