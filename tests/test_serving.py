"""Continuous-batching inference server (mxnet_tpu/serving.py).

Pins the subsystem's contracts: bucketed outputs stay within tolerance
of the unbatched Predictor and padding never bleeds into results,
concurrent clients get their own answers, the NaN sentinel rejects (one
rate-limited warning, never a silent bad payload), shutdown drains,
the serve:* telemetry reaches histograms / Prometheus / diag dumps /
--compare / the perf doctor.  Docs: docs/SERVING.md.
"""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, histogram
from mxnet_tpu import ndarray as nd
from mxnet_tpu import runtime_stats
from mxnet_tpu import serving
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import (InferenceServer, RequestRejected,
                               ServerStopped)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_serving_state():
    """Serving raises the histogram layer on construction; restore the
    pre-test state so the bench-gate disabled-path bounds (and any
    other telemetry test) see their default-off world."""
    was_on = histogram.is_enabled()
    yield
    for srv in serving.servers():
        srv.stop(drain=False, timeout=5.0)
    serving.reset()
    runtime_stats.reset()
    if not was_on:
        histogram.disable()


def _export_predictor(tmp_path, in_dim=5, prefix="serving_dense"):
    block = gluon.nn.HybridSequential()
    block.add(gluon.nn.Dense(7))
    block.add(gluon.nn.Dense(3))
    block.hybridize()
    block.initialize()
    block(nd.array(np.random.uniform(size=(1, in_dim))))
    path = str(tmp_path / prefix)
    block.export(path)
    return Predictor(open(path + "-symbol.json").read(),
                     open(path + "-0000.params", "rb").read(),
                     {"data": (1, in_dim)})


def _reference(pred, x):
    """Unbatched Predictor output for one request (bound at the
    request's own batch shape, sharing weights)."""
    clone = pred._reshape_clone({"data": x.shape})
    clone.forward(data=x)
    return clone.get_output(0)


# ------------------------------------------------------------ exactness

# a bucketed batch and the unbatched predictor are differently shaped,
# differently fused programs: every backend keeps them within a few
# float32 ulps of each other, none keeps them bit-identical
SERVE_RTOL, SERVE_ATOL = 1e-5, 1e-6


def test_bucket_padding_within_tolerance_and_never_bleeds(tmp_path):
    """Every bucket size: a request padded up to the bucket matches the
    unbatched Predictor on its valid rows within tolerance, and padding
    never bleeds into results — the same bucket executable fed two
    different paddings returns identical valid rows."""
    pred = _export_predictor(tmp_path)
    with InferenceServer(pred, buckets=(1, 2, 4, 8)) as srv:
        for n in (1, 2, 3, 5, 8):
            x = np.random.uniform(size=(n, 5)).astype(np.float32)
            out = srv.infer(x)
            assert len(out) == 1 and out[0].shape == (n, 3)
            np.testing.assert_allclose(
                out[0], _reference(pred, x), rtol=SERVE_RTOL,
                atol=SERVE_ATOL,
                err_msg="bucketed output for n=%d differs from "
                        "unbatched" % n)
        x = np.random.uniform(size=(5, 5)).astype(np.float32)
        rows = []
        for pad in (0.0, 1e6):
            buf = np.full((8, 5), pad, np.float32)
            buf[:5] = x
            (o,) = srv._bucket_fn(8)({"data": serving._device_put(buf)})
            rows.append(np.asarray(o)[:5])
        assert np.array_equal(rows[0], rows[1]), \
            "valid rows depend on what the pad rows hold"
    snap = srv.snapshot()
    assert snap["requests"] == 5
    assert snap["samples"] == 1 + 2 + 3 + 5 + 8
    # n=3 -> bucket 4 (1 pad), n=5 -> bucket 8 (3 pads)
    assert snap["padded_rows"] >= 4
    # every built bucket executable compiled exactly once
    assert snap["bucket_compiles"] == len(snap["per_bucket"])


def test_concurrent_clients_get_their_own_rows(tmp_path):
    """Threaded clients with distinct inputs each get their own rows
    back (within tolerance of the unbatched predictor; another
    client's rows would be off by orders of magnitude more) while the
    batcher packs them arbitrarily."""
    pred = _export_predictor(tmp_path)
    rng = np.random.RandomState(3)
    per_client = 8
    clients = 6
    results = {}
    errors = []

    with InferenceServer(pred, buckets=(1, 2, 4, 8, 16)) as srv:
        def client(cid):
            try:
                for i in range(per_client):
                    n = int(rng.randint(1, 6))
                    x = np.random.RandomState(cid * 100 + i).uniform(
                        size=(n, 5)).astype(np.float32)
                    out = srv.submit(x).result(30.0)
                    results[(cid, i)] = (x, out[0])
            except Exception as e:  # surfaced below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    assert not errors, errors
    assert len(results) == clients * per_client
    for (cid, i), (x, got) in results.items():
        np.testing.assert_allclose(
            got, _reference(pred, x), rtol=SERVE_RTOL, atol=SERVE_ATOL,
            err_msg="client %d request %d got someone else's rows"
                    % (cid, i))


def test_shape_and_queue_rejections(tmp_path):
    pred = _export_predictor(tmp_path)
    with InferenceServer(pred, buckets=(1, 2, 4)) as srv:
        # wrong trailing shape: explicit error, never a silent retrace
        with pytest.raises(RequestRejected):
            srv.submit(np.zeros((1, 6), np.float32))
        # missing leading sample axis
        with pytest.raises(RequestRejected):
            srv.submit(np.zeros((5,), np.float32))
        # sample count past the largest bucket
        with pytest.raises(RequestRejected):
            srv.submit(np.zeros((5, 5), np.float32))
        # unknown input name
        with pytest.raises(RequestRejected):
            srv.submit({"nope": np.zeros((1, 5), np.float32)})
        assert srv.snapshot()["rejected"]["shape"] == 4
        assert srv.snapshot()["bucket_compiles"] == 0


def test_queue_backpressure():
    """A full queue rejects at submit — bounded latency via explicit
    backpressure, not an unbounded backlog."""
    gate = threading.Event()

    def slow_model(inputs, bucket):
        gate.wait(10.0)
        return [inputs["data"]]

    srv = InferenceServer(slow_model, input_shapes={"data": (3,)},
                          buckets=(1, 2), max_queue=2, workers=1)
    with srv:
        futs = [srv.submit(np.zeros((1, 3), np.float32))
                for _ in range(2)]
        # queue holds 2 samples max; the pipeline may have pulled some
        # already, so flood until the bound trips
        with pytest.raises(RequestRejected):
            for _ in range(8):
                futs.append(srv.submit(np.zeros((1, 3), np.float32)))
        gate.set()
        for f in futs:
            f.result(10.0)
    assert srv.snapshot()["rejected"]["queue"] >= 1


# ------------------------------------------------------------- sentinel


class _CaptureHandler(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_nonfinite_sentinel_rejects_with_one_warning():
    """A NaN in a served output is exactly one rate-limited warning +
    a rejected response; healthy requests in other batches still
    serve."""
    from mxnet_tpu.log import reset_rate_limits

    reset_rate_limits("serving:")

    def model(inputs, bucket):
        x = inputs["data"]
        # rows whose first feature is negative go non-finite
        import jax.numpy as jnp

        bad = x[:, :1] < 0
        return [jnp.where(bad, jnp.nan, x.sum(axis=1, keepdims=True))]

    srv = InferenceServer(model, input_shapes={"data": (3,)},
                          buckets=(1, 2, 4), workers=1)
    handler = _CaptureHandler()
    logger = serving._logger()
    logger.addHandler(handler)
    try:
        with srv:
            good = srv.infer(np.ones((2, 3), np.float32))
            assert np.isfinite(good[0]).all()
            with pytest.raises(RequestRejected):
                srv.infer(-np.ones((1, 3), np.float32))
            # a second bad request inside the warn interval: rejected
            # again, but NO second warning line
            with pytest.raises(RequestRejected):
                srv.infer(-np.ones((2, 3), np.float32))
    finally:
        logger.removeHandler(handler)
    warnings = [r for r in handler.records
                if "non-finite" in r.getMessage()]
    assert len(warnings) == 1, \
        "expected exactly one rate-limited sentinel warning, got %d" \
        % len(warnings)
    snap = srv.snapshot()
    assert snap["rejected"]["nonfinite"] == 2
    assert snap["rejections"] and \
        snap["rejections"][-1]["reason"] == "non-finite output"
    assert runtime_stats.snapshot()["counters"][
        "serve_rejected_nonfinite"] == 2


def test_mixed_batch_scatter_isolates_bad_rows():
    """When a good and a bad request land in ONE batch, only the bad
    request is rejected — the good one gets its (finite) rows."""
    plug = threading.Event()

    def model(inputs, bucket):
        x = np.asarray(inputs["data"])
        if x[0, 0] > 50:  # the plug batch: hold the worker busy
            plug.wait(10.0)
        bad = x[:, :1] < 0
        return [np.where(bad, np.nan,
                         x.sum(axis=1, keepdims=True,
                               dtype=np.float32))]

    srv = InferenceServer(model, input_shapes={"data": (3,)},
                          buckets=(4,), workers=1)
    with srv:
        f_plug = srv.submit(np.full((1, 3), 100, np.float32))
        time.sleep(0.05)  # the plug is in the worker; queue the pair
        f_good = srv.submit(np.ones((1, 3), np.float32))
        f_bad = srv.submit(-np.ones((1, 3), np.float32))
        plug.set()
        f_plug.result(10.0)
        out = f_good.result(10.0)
        assert np.allclose(out[0], 3.0)
        with pytest.raises(RequestRejected):
            f_bad.result(10.0)
    # good+bad were packed into one bucket-4 batch behind the plug
    assert srv.snapshot()["batches"] == 2


# ------------------------------------------------------------- shutdown


def test_stop_drains_accepted_requests():
    served = []

    def model(inputs, bucket):
        time.sleep(0.002)
        return [inputs["data"]]

    srv = InferenceServer(model, input_shapes={"data": (2,)},
                          buckets=(1, 2, 4), workers=2)
    srv.start()
    futs = [srv.submit(np.full((1, 2), i, np.float32))
            for i in range(30)]
    srv.stop(drain=True)
    for i, f in enumerate(futs):
        out = f.result(1.0)  # already done: drain served everything
        served.append(out)
        assert np.all(out[0] == i), "drain lost/mixed request %d" % i
    assert len(served) == 30
    with pytest.raises(RequestRejected):
        srv.submit(np.zeros((1, 2), np.float32))


def test_stop_without_drain_fails_pending():
    gate = threading.Event()

    def model(inputs, bucket):
        gate.wait(5.0)
        return [inputs["data"]]

    srv = InferenceServer(model, input_shapes={"data": (2,)},
                          buckets=(1,), workers=1, max_queue=64)
    srv.start()
    futs = [srv.submit(np.zeros((1, 2), np.float32)) for _ in range(8)]
    srv.stop(drain=False, timeout=0.2)
    gate.set()
    outcomes = []
    for f in futs:
        try:
            f.result(5.0)
            outcomes.append("ok")
        except (ServerStopped, RequestRejected):
            outcomes.append("stopped")
    # at least the still-queued tail was failed fast, none left hanging
    assert "stopped" in outcomes
    assert len(outcomes) == 8


# ------------------------------------------------------------ telemetry


def test_predictor_forward_telemetry(tmp_path):
    """Satellite: the legacy Predictor.forward feeds the histogram /
    counter seam like Trainer.step, so predictor runs show up in diag
    dumps."""
    pred = _export_predictor(tmp_path, prefix="serving_pred_telemetry")
    base = runtime_stats.snapshot()["counters"].get(
        "predictor_forwards", 0)
    histogram.enable()
    pred.forward(data=np.zeros((1, 5), np.float32))
    pred.forward(data=np.zeros((1, 5), np.float32))
    snap = runtime_stats.snapshot()
    assert snap["counters"]["predictor_forwards"] == base + 2
    h = snap["histograms"]["predictor:forward"]
    assert h["count"] == 2 and h["max"] > 0


def test_serve_histograms_and_prometheus(tmp_path):
    """`curl /metrics` during a load run exposes the serve:* quantile
    families (the PR 10 endpoint reads the shared histogram state)."""
    from urllib.request import urlopen

    from mxnet_tpu import metrics_timeline

    pred = _export_predictor(tmp_path, prefix="serving_prom")
    with InferenceServer(pred, buckets=(1, 2, 4)) as srv:
        for n in (1, 2, 3):
            srv.infer(np.random.rand(n, 5).astype(np.float32))
        metrics_timeline.serve(port=0, host="127.0.0.1")
        try:
            port = metrics_timeline.server_port()
            body = urlopen("http://127.0.0.1:%d/metrics" % port,
                           timeout=10).read().decode()
        finally:
            metrics_timeline.stop_server()
    for series in ("serve:e2e", "serve:queue_wait", "serve:batch"):
        assert 'series="%s"' % series in body, \
            "%s missing from /metrics" % series
    assert 'quantile="0.99"' in body
    assert "mxnet_tpu_serve_requests_total" in body
    assert "mxnet_tpu_serve_samples_total" in body


def test_serving_jsonl_timeline(tmp_path):
    """Per-batch JSONL samples are whole-line records shaped like
    metrics_timeline samples, so the trend doctor and the timeline
    loaders take them unchanged."""
    from mxnet_tpu import metrics_timeline, perfdoctor

    pred = _export_predictor(tmp_path, prefix="serving_jsonl")
    path = str(tmp_path / "serve_timeline.jsonl")
    with InferenceServer(pred, buckets=(1, 2, 4),
                         metrics_path=path) as srv:
        for n in (1, 2, 3, 1):
            srv.infer(np.random.rand(n, 5).astype(np.float32))
    samples = metrics_timeline.parse_jsonl(open(path).read())
    assert len(samples) == 4
    for s in samples:
        assert s["wall_ms"] > 0 and s["bucket"] >= s["n"] >= 1
        assert 0 < s["occupancy"] <= 1
    kind, data = perfdoctor.classify(path)
    assert kind == "timeline" and len(data["samples"]) == 4


def test_diag_dump_and_diagnose_serving_roundtrip(tmp_path):
    """The serving section rides runtime_stats diag dumps and renders
    through `tools/diagnose.py --serving` (live and from-dump)."""
    import importlib.util

    pred = _export_predictor(tmp_path, prefix="serving_diag")
    with InferenceServer(pred, buckets=(1, 2)) as srv:
        srv.infer(np.random.rand(2, 5).astype(np.float32))
    dump_path = str(tmp_path / "serve_diag.json")
    runtime_stats.dump_diag(dump_path)
    data = json.load(open(dump_path))
    section = data["snapshot"]["serving"]
    assert section["enabled"] and section["requests"] == 1
    assert section["per_bucket"]["2"]["batches"] == 1

    spec = importlib.util.spec_from_file_location(
        "diagnose", os.path.join(REPO, "tools", "diagnose.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    assert diag.check_serving(dump_path) == 0
    # a dump with no serving run refuses to vacuously pass
    empty = dict(data)
    empty["snapshot"] = dict(data["snapshot"],
                             serving={"enabled": False})
    empty_path = str(tmp_path / "no_serving.json")
    json.dump(empty, open(empty_path, "w"))
    assert diag.check_serving(empty_path) == 2
    # the rendered report carries the section too
    text = runtime_stats._render(data["snapshot"])
    assert "Inference serving" in text


def test_compare_learns_serving_qps(tmp_path):
    """A QPS regression between two serving dumps fails --compare:
    serving:ms_per_sample is oriented up-is-worse."""
    def dump(qps, e2e_ms):
        h = histogram.Histogram()
        for _ in range(64):
            h.observe(e2e_ms / 1e3)
        return {"snapshot": {
            "ops": {}, "totals": {}, "counters": {},
            "serving": {"enabled": True, "qps": qps},
            "histograms": {"serve:e2e": h.snapshot()}}}

    result = runtime_stats.compare(dump(1000.0, 2.0), dump(400.0, 6.0))
    metrics = {e["metric"]: e for e in result["regressions"]}
    assert result["verdict"] == "regression"
    assert "serving:ms_per_sample" in metrics
    assert metrics["serving:ms_per_sample"]["ratio"] == pytest.approx(
        2.5, rel=1e-6)
    assert "hist:serve:e2e p99" in metrics
    # flat when nothing moved
    assert runtime_stats.compare(dump(1000.0, 2.0),
                                 dump(1000.0, 2.0))["verdict"] == "flat"


# ----------------------------------------------------------- perfdoctor


def _serving_dump(qw_p99_ms=50.0, batch_p99_ms=5.0, requests=200,
                  compiles=5, ladder=(1, 2, 4, 8, 16), batches=100):
    def hist(p99_ms, count):
        h = histogram.Histogram()
        for _ in range(count):
            h.observe(p99_ms / 1e3)
        return h.snapshot()

    return {"snapshot": {
        "ops": {}, "totals": {},
        "counters": {"serve_requests": requests,
                     "serve_batches": batches,
                     "serve_bucket_compiles": compiles},
        "serving": {"enabled": True, "requests": requests,
                    "batches": batches, "bucket_compiles": compiles,
                    "buckets": list(ladder), "mean_occupancy": 0.9},
        "histograms": {"serve:queue_wait": hist(qw_p99_ms, requests),
                       "serve:batch": hist(batch_p99_ms, batches),
                       "serve:e2e": hist(qw_p99_ms + batch_p99_ms,
                                         requests)}}}


def test_perfdoctor_serve_queue_dominated():
    from mxnet_tpu import perfdoctor

    findings = perfdoctor.diagnose(dump=_serving_dump())
    rules = {f["rule"]: f for f in findings}
    assert "serve-queue-dominated" in rules
    f = rules["serve-queue-dominated"]
    assert f["anchor"] == "serve:queue_wait"
    assert "raise the max bucket" in f["action"]
    # queue-wait dominates e2e -> ranked as a big share
    assert f["score"] > 0.5
    # GitHub annotations render for serving findings like any other
    gh = perfdoctor.render_github(findings)
    assert "serve-queue-dominated" in gh
    # a healthy run (queue wait << compute) stays silent
    quiet = perfdoctor.diagnose(dump=_serving_dump(qw_p99_ms=1.0,
                                                   batch_p99_ms=5.0))
    assert "serve-queue-dominated" not in {f["rule"] for f in quiet}


def test_perfdoctor_serve_bucket_churn():
    from mxnet_tpu import perfdoctor

    churn = perfdoctor.diagnose(dump=_serving_dump(
        qw_p99_ms=1.0, compiles=14, ladder=(1, 2, 4, 8, 16)))
    rules = {f["rule"]: f for f in churn}
    assert "serve-bucket-churn" in rules
    assert "one-per-bucket" in rules["serve-bucket-churn"]["evidence"][0]
    # warmup compiles (<= ladder size) are not churn
    warm = perfdoctor.diagnose(dump=_serving_dump(qw_p99_ms=1.0,
                                                  compiles=5))
    assert "serve-bucket-churn" not in {f["rule"] for f in warm}
    # the WORST churn — a server re-created per batch, every ladder
    # entry recompiled each time — shows a small per-server section
    # (<= one build per bucket) while the cumulative counters carry
    # the real cost; the rule must fire from the counters even though
    # compiles outnumber batches
    worst = _serving_dump(qw_p99_ms=1.0, compiles=5, batches=1)
    worst["snapshot"]["counters"]["serve_bucket_compiles"] = 100
    worst["snapshot"]["counters"]["serve_batches"] = 20
    fired = perfdoctor.diagnose(dump=worst)
    assert "serve-bucket-churn" in {f["rule"] for f in fired}
