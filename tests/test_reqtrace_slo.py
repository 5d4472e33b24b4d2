"""Request x-ray + SLO layer (mxnet_tpu/reqtrace.py, mxnet_tpu/slo.py).

Pins the PR's contracts: tail-based sampling is deterministic (a fixed
workload replayed after ``reset()`` retains the identical rid set:
rejects and slow completions always, a 1-in-N head sample of the
healthy rest), lifecycle records carry the complete seam-by-seam ms
ladder, the ``slo-fast-burn`` / ``slo-budget-exhausted`` doctor rules
fire on burning traffic and stay quiet on healthy traffic (and under
MIN_EVENTS), the ``slo-shed`` autopilot reflex respects its
off/dry-run/armed gate and its knob bounds, ``--compare`` treats a
one-sided objective as a note and a burn increase as a regression,
and the end-to-end drill (induced slow tail + one injected NaN through a real
``InferenceServer``) produces the retained ring, a merged chrome
trace with cross-thread flow events, and a ``diagnose.py --slo``
rendering with window evidence from a diag dump.
Docs: docs/OBSERVABILITY.md "Request x-ray & SLOs".
"""

import json
import os
import time

import numpy as np
import pytest

from mxnet_tpu import (autopilot, histogram, metrics_timeline, perfdoctor,
                       profiler, reqtrace, runtime_stats, serving, slo)
from mxnet_tpu.serving import InferenceServer, RequestRejected

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_xray_state():
    """Restore the default-off telemetry world after every test (the
    bench-gate disabled-path bounds depend on it)."""
    was_on = histogram.is_enabled()
    yield
    for srv in serving.servers():
        srv.stop(drain=False, timeout=5.0)
    serving.reset()
    profiler.set_state("stop")
    with profiler._state["lock"]:
        profiler._state["events"] = []
    profiler._state["config"]["filename"] = "profile.json"
    autopilot.disable()
    autopilot.reset()
    reqtrace.reset()
    slo.reset()
    runtime_stats.reset()
    if not was_on:
        histogram.disable()


class _Req:
    """Minimal stand-in for a serving request at the trace seams."""

    def __init__(self, n, t_submit):
        self.n = n
        self.t_submit = t_submit


def _lifecycle(i, e2e_s, base=1000.0):
    """Drive one ok request through every seam with fixed timestamps."""
    t0 = base + i
    req = _Req(1, t0)
    reqtrace.on_submit(req, depth=0)
    reqtrace.on_submitted(req)
    req.t_batched = t0 + 0.001
    reqtrace.on_join([req], bucket=2)
    reqtrace.on_exec([req], "w0", 1, t0 + 0.002, t0 + 0.003)
    reqtrace.on_done(req, "ok", t_done=t0 + e2e_s)
    return req


# ------------------------------------------------------ tail sampling


def test_tail_sampling_determinism():
    """The same workload replayed after ``reset()`` retains the
    IDENTICAL rid set: rejects and slow completions always, plus the
    deterministic 1-in-N head sample — never a random choice."""
    rejects = {7, 23, 64}
    slow = {11, 40, 41, 83}
    n_items = 90

    def replay():
        reqtrace.reset()
        reqtrace.enable(ring=512, sample=5, slow_ms=50.0, p99_mult=1e9)
        for i in range(1, n_items + 1):
            if i in rejects:
                reqtrace.on_reject("rejected_queue", n=2)
            else:
                _lifecycle(i, 0.120 if i in slow else 0.005)
        return [r["rid"] for r in reqtrace.snapshot()["ring"]]

    expected = {i for i in range(1, n_items + 1)
                if i in rejects or i in slow
                or (i % 5 == 0 and i not in rejects)}
    first = replay()
    second = replay()
    assert first == second, "replayed workload retained a different ring"
    assert set(first) == expected
    snap = reqtrace.snapshot()
    assert snap["seen"] == n_items
    assert snap["retained"] == len(expected)
    assert snap["dropped"] == n_items - len(expected)


def test_p99_multiple_retention_needs_warm_window():
    """The rolling-p99 slow rule must not fire before WINDOW_WARM
    completions; once warmed, an e2e past p99 x mult is retained."""
    reqtrace.enable(ring=512, sample=10 ** 9, slow_ms=0.0, p99_mult=3.0)
    # cold window: an outlier among the first few is NOT retained
    for i in range(1, 21):
        _lifecycle(i, 0.100 if i == 5 else 0.010)
    assert reqtrace.snapshot()["ring"] == []
    # warmed window: the outlier is retained as "slow"
    reqtrace.reset()
    reqtrace.enable(ring=512, sample=10 ** 9, slow_ms=0.0, p99_mult=3.0)
    for i in range(1, 65):
        _lifecycle(i, 0.010)
    _lifecycle(65, 0.100)
    ring = reqtrace.snapshot()["ring"]
    assert [r["rid"] for r in ring] == [65]
    assert ring[0]["retained"] == "slow"


def test_record_carries_complete_seam_ladder():
    """A retained record holds the full submit->done ms ladder plus the
    bucket/batch/worker/pad stamps written at each seam."""
    reqtrace.enable(ring=16, sample=1, slow_ms=0.0, p99_mult=1e9)
    _lifecycle(1, 0.005)
    ring = reqtrace.snapshot()["ring"]
    assert len(ring) == 1
    rec = ring[0]
    assert rec["outcome"] == "ok" and rec["retained"] == "head"
    assert rec["bucket"] == 2 and rec["batch"] == 1
    assert rec["worker"] == "w0" and rec["pad_rows"] == 1
    assert rec["queue_depth"] == 0
    assert rec["e2e_ms"] == pytest.approx(5.0, rel=1e-3)
    assert rec["queue_ms"] == pytest.approx(1.0, rel=1e-2)
    assert rec["stage_ms"] == pytest.approx(1.0, rel=1e-2)
    assert rec["compute_ms"] == pytest.approx(1.0, rel=1e-2)
    assert rec["scatter_ms"] == pytest.approx(2.0, rel=1e-2)


def test_rejects_always_retained_with_fresh_rid():
    """Front-door rejections never vanish: each consumes a rid and
    lands in the ring as a degenerate always-retained record."""
    reqtrace.enable(ring=16, sample=10 ** 9, slow_ms=0.0, p99_mult=1e9)
    reqtrace.on_reject("rejected_queue", n=3)
    reqtrace.on_reject("rejected_shape", n=1)
    snap = reqtrace.snapshot()
    assert snap["by_outcome"] == {"rejected_queue": 1,
                                  "rejected_shape": 1}
    assert [r["rid"] for r in snap["ring"]] == [1, 2]
    assert all(r["retained"] == r["outcome"] for r in snap["ring"])


# -------------------------------------------------------------- slo


def test_parse_objectives():
    objs = slo.parse_objectives(
        "e2e:25ms:99.9, avail:99.5, bogus:x:y, :50, nothing")
    assert [(o["name"], o["kind"]) for o in objs] == [
        ("e2e", "latency"), ("avail", "availability")]
    assert objs[0]["threshold_ms"] == 25.0
    assert objs[0]["target"] == pytest.approx(0.999)
    assert objs[1]["threshold_ms"] is None
    assert objs[1]["target"] == pytest.approx(0.995)
    # "nothing" has no target; 1-token entries are invalid too
    assert slo.parse_objectives("") == []
    assert slo.enable(spec="") is False and not slo.is_enabled()


def test_slo_fast_burn_fires_and_stays_quiet():
    """Burning traffic trips slo-fast-burn with both-window evidence;
    healthy traffic produces zero findings."""
    assert slo.enable(spec="e2e:5ms:99", ring=256, scale=1.0)
    for i in range(40):
        slo.on_request(100.0 if i % 3 == 0 else 1.0, True)
    snap = slo.snapshot()
    ob = snap["objectives"][0]
    assert ob["fast_burn"]
    assert ob["windows"]["5m"]["burn"] >= slo.FAST_BURN
    assert ob["windows"]["1h"]["events"] >= slo.MIN_EVENTS
    findings = perfdoctor._check_slo({"snapshot": {"slo": snap}})
    fast = [f for f in findings if f["rule"] == "slo-fast-burn"]
    assert len(fast) == 1
    assert "fast pair burning" in fast[0]["evidence"][0]
    assert "5m burn" in fast[0]["evidence"][0]
    # quiet pair: the same objective under healthy traffic
    slo.reset()
    assert slo.enable(spec="e2e:5ms:99", ring=256, scale=1.0)
    for _ in range(40):
        slo.on_request(1.0, True)
    quiet = perfdoctor._check_slo({"snapshot": {"slo": slo.snapshot()}})
    assert quiet == []


def test_slo_budget_exhausted_respects_min_events():
    """An exhausted budget only pages once MIN_EVENTS requests exist —
    two bad requests at startup must not."""
    assert slo.enable(spec="avail:99", ring=256, scale=1.0)
    for _ in range(20):
        slo.on_request(None, False)
    early = perfdoctor._check_slo({"snapshot": {"slo": slo.snapshot()}})
    assert [f for f in early if f["rule"] == "slo-budget-exhausted"] == []
    for _ in range(20):
        slo.on_request(None, False)
    snap = slo.snapshot()
    assert snap["objectives"][0]["budget_remaining"] <= 0.0
    findings = perfdoctor._check_slo({"snapshot": {"slo": snap}})
    assert any(f["rule"] == "slo-budget-exhausted" for f in findings)


# --------------------------------------------------- autopilot reflex


class _StubServer:
    def __init__(self):
        self.num_workers = 2
        self.max_queue = 1024
        self.max_bucket = 16
        self.calls = []

    def set_workers(self, n):
        self.num_workers = n
        self.calls.append(("workers", n))

    def set_max_queue(self, n):
        self.max_queue = n
        self.calls.append(("max_queue", n))


_FINDING = {"rule": "slo-fast-burn", "score": 0.9, "severity": "warn",
            "title": "objective 'e2e' burning", "anchor": "slo:e2e",
            "evidence": ["fast pair burning"], "action": "shed load"}


def test_autopilot_slo_gate_states():
    """off -> nothing ledgered; dry_run -> ledgered, no knob touched;
    armed -> queue bound shrinks toward the floor and a worker is
    added, both within bounds under repeated firings."""
    srv = _StubServer()
    autopilot.enable(cooldown=0.0, max_actions=100,
                     gates={"slo-shed": "off"})
    autopilot.reset()
    autopilot._reflex_slo(dict(_FINDING), srv, 1)
    assert autopilot.ledger() == [] and srv.calls == []

    autopilot.enable(cooldown=0.0, max_actions=100,
                     gates={"slo-shed": "dry_run"})
    autopilot.reset()
    autopilot._reflex_slo(dict(_FINDING), srv, 2)
    led = autopilot.ledger()
    assert len(led) == 1 and led[0]["mode"] == "dry_run"
    assert led[0]["reflex"] == "slo-shed"
    assert led[0]["rule"] == "slo-fast-burn"
    assert "MXNET_TPU_AUTOPILOT_SLO" in led[0]["reason"]
    assert srv.calls == []

    autopilot.enable(cooldown=0.0, max_actions=100,
                     gates={"slo-shed": "armed"})
    autopilot.reset()
    autopilot._reflex_slo(dict(_FINDING), srv, 3)
    led = autopilot.ledger()
    assert led[-1]["mode"] == "fired"
    adj = led[-1]["outcome"]["adjusted"]
    assert adj["max_queue"] == [1024, 768]
    assert adj["workers"] == [2, 3]
    # bounded: repeated firings converge to the floor/cap, never past
    for tick in range(4, 40):
        autopilot._reflex_slo(dict(_FINDING), srv, tick)
    assert srv.max_queue >= autopilot.SERVE_MIN_QUEUE_DEFAULT
    assert srv.num_workers <= autopilot.SERVE_MAX_WORKERS_DEFAULT
    assert autopilot.ledger()[-1]["outcome"]["reason"] \
        == "every knob already at its bound"


def test_evaluate_serving_dispatches_slo_reflex():
    """The serving evaluation tick routes a live slo-fast-burn finding
    into the slo-shed reflex (dry-run by default)."""
    assert slo.enable(spec="e2e:5ms:99", ring=256, scale=1.0)
    for _ in range(40):
        slo.on_request(100.0, True)
    autopilot.enable(cooldown=0.0, gates={"slo-shed": "dry_run"})
    autopilot.reset()
    autopilot._evaluate_serving(None, 1)
    led = autopilot.ledger()
    assert any(e["reflex"] == "slo-shed"
               and e["rule"] == "slo-fast-burn" for e in led)


# ------------------------------------------------------------ compare


def _slo_snapshot(name, burned):
    return {"enabled": True, "window_scale": 1.0, "ring_cap": 4096,
            "objectives": [{"name": name, "kind": "latency",
                            "threshold_ms": 5.0, "target": 0.99,
                            "good": 90, "bad": 10, "total": 100,
                            "budget_remaining": 1.0 - burned,
                            "windows": {}, "fast_burn": False,
                            "slow_burn": False}]}


def test_compare_slo_burn_regression_and_one_sided_note():
    a = {"snapshot": {"slo": _slo_snapshot("e2e", 0.1)}}
    b = {"snapshot": {"slo": _slo_snapshot("e2e", 0.5)}}
    res = runtime_stats.compare(a, b)
    assert res["verdict"] == "regression"
    reg = [e for e in res["regressions"]
           if e["metric"] == "slo:e2e budget_burned"]
    assert len(reg) == 1
    assert reg[0]["before"] == pytest.approx(10.0)
    assert reg[0]["after"] == pytest.approx(50.0)
    # an objective declared on only one side is a note, not a verdict
    res2 = runtime_stats.compare({"snapshot": {}}, b)
    assert res2["verdict"] == "flat"
    notes = [e for e in res2["notes"]
             if e["metric"] == "slo:e2e budget_burned"]
    assert len(notes) == 1 and notes[0]["side"] == "after-only"
    assert "SLO objectives differ" in runtime_stats.render_compare(res2)


# ----------------------------------------------------------- e2e drill


def _drill_model(inputs, bucket):
    """Callable model: first feature >= 100 induces a slow batch,
    first feature < 0 produces a NaN output row (sentinel food)."""
    x = np.asarray(inputs["data"], dtype=np.float32)
    marker = x[:, 0]
    if np.any(marker >= 100.0):
        time.sleep(0.03)
    out = np.sum(x, axis=1, keepdims=True).astype(np.float32)
    out[marker < 0.0] = np.nan
    return [out]


def test_request_xray_slo_drill(tmp_path, capsys):
    """The PR's acceptance drill: a soak with an induced slow tail and
    one injected NaN yields (a) a ring retaining every slow/rejected/
    sentinel request with complete seam records, (b) a merged chrome
    trace whose flow events link one request across threads, and (c) a
    slo-fast-burn finding with window evidence rendered by
    ``diagnose.py --slo`` from a diag dump."""
    import importlib.util

    reqtrace.enable(ring=512, sample=1, slow_ms=20.0, p99_mult=1e9)
    assert slo.enable(spec="e2e:10ms:99", ring=512, scale=1.0)
    trace_path = str(tmp_path / "drill_trace.json")
    profiler.set_config(filename=trace_path)
    profiler.set_state("run")

    n_ok, n_slow = 40, 0
    with InferenceServer(_drill_model, input_shapes={"data": (4,)},
                         buckets=(1, 2, 4), workers=1) as srv:
        for i in range(n_ok):
            v = 100.0 if i % 4 == 0 else 1.0
            n_slow += int(v >= 100.0)
            x = np.full((1, 4), v, dtype=np.float32)
            out = srv.infer(x, timeout=30.0)
            assert out[0].shape == (1, 1)
        with pytest.raises(RequestRejected):
            srv.infer(np.full((1, 4), -1.0, dtype=np.float32),
                      timeout=30.0)

    # (a) ring: every slow and the sentinel request, full seam ladders
    snap = reqtrace.snapshot()
    assert snap["seen"] == n_ok + 1
    assert snap["by_outcome"]["ok"] == n_ok
    assert snap["by_outcome"]["rejected_nonfinite"] == 1
    slow_recs = [r for r in snap["ring"] if r["retained"] == "slow"]
    assert len(slow_recs) >= n_slow
    for rec in slow_recs:
        assert rec["e2e_ms"] >= 20.0
        for key in ("bucket", "batch", "worker", "pad_rows", "queue_ms",
                    "stage_ms", "compute_ms", "scatter_ms"):
            assert rec[key] is not None, "seam %r missing" % key
    sentinel = [r for r in snap["ring"]
                if r["outcome"] == "rejected_nonfinite"]
    assert len(sentinel) == 1 and sentinel[0]["e2e_ms"] > 0.0
    assert reqtrace.exemplar() is not None

    # Prometheus: SLO gauge families + a request-id exemplar on serve:*
    text = metrics_timeline.prometheus_text()
    assert 'mxnet_tpu_slo_budget_remaining{objective="e2e"}' in text
    assert 'mxnet_tpu_slo_burn_rate{objective="e2e",window="5m"}' in text
    assert 'request_id="' in text

    # report(): both new sections render with the outcome breakdown
    report = runtime_stats.report()
    assert "Request x-ray" in report
    assert "SLO / error budgets" in report
    assert "rejected_nonfinite=1" in report

    # (b) merged chrome trace: one request's s/t/f flow across threads
    raw = profiler.dump(finished=True)
    merged = profiler.merge_traces([raw], str(tmp_path / "merged.json"))
    with open(merged) as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    flows = {}
    for ev in events:
        if ev.get("ph") in ("s", "t", "f") and ev.get("cat", "").endswith("req"):
            flows.setdefault(ev["id"], []).append(ev)
    linked = [rid for rid, evs in flows.items()
              if {e["ph"] for e in evs} >= {"s", "t", "f"}]
    assert linked, "no request carried a complete s/t/f flow"
    tids = {e["tid"] for e in flows[linked[0]]}
    assert len(tids) >= 2, "flow events never crossed a thread"
    names = {e.get("name") for e in events}
    assert "req:queue" in names and "req:exec" in names

    # (c) diagnose --slo from a diag dump renders the fast-burn finding
    dump_path = str(tmp_path / "drill_diag.json")
    runtime_stats.dump_diag(dump_path)
    spec = importlib.util.spec_from_file_location(
        "diagnose", os.path.join(REPO, "tools", "diagnose.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    assert diag.check_slo(dump_path) == 0
    out = capsys.readouterr().out
    assert "** FAST BURN **" in out
    assert "fast burn: spending error budget" in out
    assert "fast pair burning" in out and "5m burn" in out
    assert diag.check_requests(dump_path) == 0
    out = capsys.readouterr().out
    assert "Request x-ray" in out and "rejected_nonfinite" in out
    # a dump without the sections refuses to vacuously pass (rc 2)
    bare = str(tmp_path / "bare_diag.json")
    with open(bare, "w") as f:
        json.dump({"snapshot": {"ops": {}}}, f)
    assert diag.check_slo(bare) == 2
    assert diag.check_requests(bare) == 2
