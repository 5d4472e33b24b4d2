#!/usr/bin/env python
"""Synthetic open-loop load generator for the serving layer.

The bench of the continuous-batching subsystem (``mxnet_tpu/serving.py``,
docs/SERVING.md): Poisson arrivals at an offered request rate (open
loop — arrivals do NOT wait for completions, so queueing delay is
measured honestly instead of being absorbed by a slow client), mixed
request shapes (each request carries 1..k samples), p50/p99/p99.9
latency per offered-QPS level, and a serial one-at-a-time
``Predictor.forward`` baseline for the speedup headline.  One JSON
report on stdout; per-batch serving samples optionally land in a JSONL
timeline whose soak is gated through the perf-doctor trend rules
(leak slope / throughput decay), the ROADMAP's serving contract.

Usage::

    python tools/loadgen.py                         # default sweep
    python tools/loadgen.py --qps 200,400,800 --duration 3 \
        --out loadgen_report.json --metrics serve_timeline.jsonl

Also reachable as ``python bench.py --serve`` (the bench artifact
path).  Methodology: docs/SERVING.md "Latency SLOs".
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

# requests carry 1..4 samples by default (the "mixed shapes" axis: the
# batcher packs them into one bucketed batch regardless)
DEFAULT_SIZES = (1, 2, 4)
# the bench ladder tops out at 32: on a small host the per-batch fixed
# cost dominates, and a taller ladder is precisely the perf doctor's
# "raise max bucket" lever
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32)
# a level is "sustained" when the achieved rate keeps up with this
# fraction of the offered rate
SUSTAIN_FRACTION = 0.9


def build_demo_predictor(in_dim=64, hidden=64, out_dim=8, seed=7):
    """A small exported MLP loaded back through the Predictor — the
    same deployment path a real model takes (export → symbol JSON +
    params blob → ``Predictor``).  Returns ``(predictor, input_shape)``
    with the predictor bound at batch 1."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu.predictor import Predictor

    mxrandom.seed(seed)
    np.random.seed(seed)
    block = gluon.nn.HybridSequential()
    block.add(gluon.nn.Dense(hidden, activation="relu"))
    block.add(gluon.nn.Dense(out_dim))
    block.hybridize()
    block.initialize()
    block(mx.nd.zeros((1, in_dim)))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "loadgen_model")
        block.export(path)
        sym_json = open(path + "-symbol.json").read()
        params = open(path + "-0000.params", "rb").read()
    pred = Predictor(sym_json, params, {"data": (1, in_dim)})
    return pred, (in_dim,)


def output_device(pred):
    """Where the model computes: the device the predictor's *output
    array* lives on after one forward (jax's default device says
    nothing about where a model's parameters were placed)."""
    pred.forward(data=np.zeros(pred._exec.arg_dict["data"].shape,
                               np.float32))
    (dev,) = pred._outputs[0].data_jax.devices()
    return {"platform": dev.platform, "device_kind": dev.device_kind}


def _latency_summary(lat_s):
    if not lat_s:
        return {"p50_ms": None, "p99_ms": None, "p999_ms": None,
                "mean_ms": None}
    ordered = sorted(lat_s)  # once; the percentiles index into it

    def pick(q):
        idx = min(len(ordered) - 1,
                  int(round(q / 100.0 * (len(ordered) - 1))))
        return ordered[idx] * 1e3

    return {"p50_ms": pick(50), "p99_ms": pick(99),
            "p999_ms": pick(99.9),
            "mean_ms": sum(ordered) / len(ordered) * 1e3}


# fixed quantile ladder for the per-request CDF — enough points to
# chart the tail shape, few enough to stay a one-line JSON object
CDF_QUANTILES = (10.0, 25.0, 50.0, 75.0, 90.0, 95.0, 99.0, 99.9)


def _latency_cdf(lat_s):
    """Per-request latency CDF (ms) at fixed quantiles plus the max —
    a tail chart needs more than three points, and the request x-ray's
    slow-tail triage starts from exactly this curve."""
    if not lat_s:
        return None
    ordered = sorted(lat_s)

    def pick(q):
        idx = min(len(ordered) - 1,
                  int(round(q / 100.0 * (len(ordered) - 1))))
        return ordered[idx] * 1e3

    cdf = {"p%g" % q: pick(q) for q in CDF_QUANTILES}
    cdf["max"] = ordered[-1] * 1e3
    return cdf


def slo_verdict():
    """Per-objective verdict from the live ``mxnet_tpu.slo`` counters
    (they saw every request the sweep pushed through the server):
    achieved good fraction vs target, budget burned.  None when no
    objective is declared (``MXNET_TPU_SLO`` unset)."""
    from mxnet_tpu import slo

    objs = slo.snapshot().get("objectives") or []
    if not objs:
        return None
    out = []
    for ob in objs:
        total = ob["total"]
        achieved = (ob["good"] / total) if total else None
        out.append({"objective": ob["name"], "kind": ob["kind"],
                    "threshold_ms": ob["threshold_ms"],
                    "target": ob["target"], "events": total,
                    "achieved": achieved,
                    "budget_burned": 1.0 - ob["budget_remaining"],
                    "met": bool(achieved is not None
                                and achieved >= ob["target"])})
    return out


def serial_baseline(pred, sample_shape, sizes=DEFAULT_SIZES,
                    n_requests=200, seed=0):
    """One-at-a-time ``Predictor.forward``: the pre-serving deployment
    path, closed loop.  One weight-sharing clone per request size (the
    fairest serial setup — no rebinding inside the loop); returns the
    sustained request rate and its latency percentiles."""
    rng = np.random.RandomState(seed)
    clones = {k: pred._reshape_clone({"data": (k,) + sample_shape})
              for k in sizes}
    pool = {k: rng.rand(k, *sample_shape).astype(np.float32)
            for k in sizes}
    for k in sizes:  # warm every clone's executable
        clones[k].forward(data=pool[k]).get_output(0)
    ks = [sizes[i % len(sizes)] for i in range(n_requests)]
    lat = []
    t_start = time.perf_counter()
    for k in ks:
        t0 = time.perf_counter()
        clones[k].forward(data=pool[k]).get_output(0)
        lat.append(time.perf_counter() - t0)
    span = time.perf_counter() - t_start
    out = {"requests": n_requests, "qps": n_requests / span,
           "samples_per_s": sum(ks) / span}
    out.update(_latency_summary(lat))
    return out


def run_open_loop(server, qps, duration, sample_shape,
                  sizes=DEFAULT_SIZES, seed=0, timeout=30.0):
    """One offered-QPS level: Poisson arrivals (exponential gaps) for
    ``duration`` seconds, submissions never waiting on completions.
    The arrival schedule is precomputed so the client loop stays cheap
    — on small hosts the loadgen shares cores with the server it
    drives.  Returns the level report (offered/achieved rates, latency
    percentiles, rejection count)."""
    from mxnet_tpu.serving import RequestRejected

    rng = np.random.RandomState(seed)
    pool = {k: [rng.rand(k, *sample_shape).astype(np.float32)
                for _ in range(8)]
            for k in sizes}
    # open loop: the schedule is fixed up front and never waits on the
    # server — a slow server faces growing queues, not a slowing client
    gaps = rng.exponential(1.0 / qps, size=int(qps * duration * 2) + 16)
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration]
    futures = []
    rejected = 0
    i = 0
    t_start = time.perf_counter()
    t_end = t_start + duration
    n = len(arrivals)
    while i < n:
        now = time.perf_counter()
        if now >= t_end:
            break
        due = t_start + arrivals[i]
        if now < due:
            time.sleep(min(due - now, 5e-4))
            continue
        k = sizes[i % len(sizes)]
        try:
            futures.append(server.submit(pool[k][i % 8]))
        except RequestRejected:
            rejected += 1
        i += 1
    lat = []
    errors = 0
    last_done = t_start
    for f in futures:
        try:
            f.result(timeout)
        except Exception:
            errors += 1
            continue
        lat.append(f.t_done - f.t_submit)
        if f.t_done > last_done:
            last_done = f.t_done
    span = max(last_done - t_start, 1e-9)
    out = {"offered_qps": qps, "submitted": i, "rejected": rejected,
           "errors": errors, "served": len(lat),
           "achieved_qps": len(lat) / span,
           "sustained": len(lat) / span >= SUSTAIN_FRACTION * qps}
    out.update(_latency_summary(lat))
    out["cdf_ms"] = _latency_cdf(lat)
    return out


# the throughput trend rule uses mean windows sized for training-step
# timelines; a short serving soak on a loaded CI box sees enough
# scheduler jitter that a couple of slow batches shift a mean window.
# Before a timeline-throughput finding may fail the soak gate it must
# be CONFIRMED on medians over enough samples (leak findings pass
# through untouched — a leak slope is monotonic, not jitter).
TREND_CONFIRM_MIN_SAMPLES = 16
TREND_QUIET_FLOOR_MS = 2.0


def _throughput_confirmed(samples):
    """Median-window recheck of the throughput-decay verdict."""
    from mxnet_tpu import perfdoctor

    walls = [s["wall_ms"] for s in samples
             if s.get("wall_ms") is not None]
    if len(walls) < TREND_CONFIRM_MIN_SAMPLES:
        return False  # too few batches to call a trend under load
    k = max(3, len(walls) // 4)

    def med(xs):
        s = sorted(xs)
        return s[len(s) // 2]

    e_med, l_med = med(walls[:k]), med(walls[-k:])
    if e_med < TREND_QUIET_FLOOR_MS and l_med < TREND_QUIET_FLOOR_MS:
        return False  # sub-floor batches: pure noise territory
    return l_med > (1.0 + perfdoctor.TREND_SLOWDOWN) * e_med


def trend_doctor(metrics_path):
    """Perf-doctor trend rules over the serving JSONL timeline (the
    soak gate: no leak slope, no throughput decay).  Returns the
    finding list (possibly empty); a missing/empty timeline returns
    None — the caller decides whether that fails the gate."""
    from mxnet_tpu import metrics_timeline, perfdoctor

    if not metrics_path or not os.path.exists(metrics_path):
        return None
    samples = metrics_timeline.parse_jsonl(open(metrics_path).read())
    if not samples:
        return None
    findings = perfdoctor.diagnose(timeline=samples)
    kept = []
    for f in findings:
        if f["rule"] == "timeline-leak":
            kept.append(f)
        elif f["rule"] == "timeline-throughput" \
                and _throughput_confirmed(samples):
            kept.append(f)
    return kept


def serial_server_level(pred, qps, duration, sample_shape,
                        sizes=DEFAULT_SIZES, seed=0):
    """The one-at-a-time counterfactual under the SAME offered load: a
    FIFO replay of the identical Poisson arrival schedule through
    serial ``Predictor.forward`` calls — real measured service times,
    M/G/1 queueing arithmetic (``start = max(arrival, prev
    completion)``), zero thread contention (deliberately flattering to
    the serial side).  Past the serial capacity its queue — and p99 —
    grows with the run length, which is exactly the failure mode
    continuous batching removes."""
    rng = np.random.RandomState(seed)
    pool = {k: [rng.rand(k, *sample_shape).astype(np.float32)
                for _ in range(8)]
            for k in sizes}
    gaps = rng.exponential(1.0 / qps, size=int(qps * duration * 2) + 16)
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration]
    clones = {k: pred._reshape_clone({"data": (k,) + sample_shape})
              for k in sizes}
    for k in sizes:
        clones[k].forward(data=pool[k][0]).get_output(0)
    completion = 0.0
    lat = []
    for i, a in enumerate(arrivals):
        k = sizes[i % len(sizes)]
        t0 = time.perf_counter()
        clones[k].forward(data=pool[k][i % 8]).get_output(0)
        svc = time.perf_counter() - t0
        a = float(a)
        start = a if a > completion else completion
        completion = start + svc
        lat.append(completion - a)
    span = max(completion, 1e-9)
    out = {"offered_qps": qps, "submitted": len(arrivals),
           "served": len(lat), "achieved_qps": len(lat) / span,
           "sustained": len(lat) / span >= SUSTAIN_FRACTION * qps,
           "mode": "serial-replay"}
    out.update(_latency_summary(lat))
    return out


def sweep(qps_levels=None, duration=2.0, sizes=DEFAULT_SIZES,
          buckets=DEFAULT_BUCKETS, serial_requests=200,
          metrics_path=None, workers=None, seed=0, model=None,
          serial_at_load=True):
    """The full bench: closed-loop serial ``Predictor.forward``
    baseline, one open-loop level per offered QPS (auto-derived from
    the serial rate when not given: 1x/2x/4x/6x), the serial-server
    counterfactual at the highest sustained level (same offered load,
    no batching), and the trend-doctor soak gate over the serving
    timeline.  Returns the JSON-ready report."""
    from mxnet_tpu.serving import InferenceServer

    if model is None:
        pred, sample_shape = build_demo_predictor()
    else:
        pred, sample_shape = model
    device = output_device(pred)
    serial = serial_baseline(pred, sample_shape, sizes=sizes,
                             n_requests=serial_requests, seed=seed)
    if not qps_levels:
        base = serial["qps"]
        qps_levels = [round(base * m, 1) for m in (1, 2, 4, 6)]
    server = InferenceServer(pred, buckets=buckets, workers=workers)
    levels = []
    with server as srv:
        srv.warmup()
        for qps in qps_levels:
            levels.append(run_open_loop(srv, qps, duration,
                                        sample_shape, sizes=sizes,
                                        seed=seed))
        serving_snap = srv.snapshot()
    sustained = [lv for lv in levels if lv["sustained"]]
    best = max(sustained, key=lambda lv: lv["achieved_qps"]) \
        if sustained else None
    # the soak gate runs at ONE steady operating point (the best
    # sustained level) with the per-batch timeline on — gating across
    # the escalating sweep would read the load ramp itself as a
    # throughput regression
    doctor = soak = None
    if metrics_path and best is not None:
        if os.path.exists(metrics_path):
            # a stale timeline from a prior run would feed the trend
            # doctor someone else's regression
            os.remove(metrics_path)
        soak_server = InferenceServer(pred, buckets=buckets,
                                      workers=workers,
                                      metrics_path=metrics_path,
                                      name="serve-soak")
        with soak_server as srv:
            srv.warmup()
            soak = run_open_loop(srv, best["offered_qps"],
                                 max(duration * 2, 1.0), sample_shape,
                                 sizes=sizes, seed=seed + 1)
        doctor = trend_doctor(metrics_path)
    serial_best = None
    if serial_at_load and best is not None:
        serial_best = serial_server_level(pred, best["offered_qps"],
                                          duration, sample_shape,
                                          sizes=sizes, seed=seed)
    report = {
        "metric": "serving open-loop sweep (Poisson arrivals, request "
                  "sizes %s, buckets %s, %.1fs/level)"
                  % (list(sizes), list(buckets), duration),
        **device,
        "serial": serial,
        "levels": levels,
        "soak": soak,
        "serial_server_at_best_load": serial_best,
        "serving": {k: serving_snap.get(k) for k in
                    ("batches", "samples", "requests", "mean_occupancy",
                     "bucket_compiles", "qps", "rejected")},
        "max_sustained_qps": best["achieved_qps"] if best else None,
        "speedup_vs_serial": (best["achieved_qps"] / serial["qps"])
        if best else None,
        # tail comparison at the SAME offered load: batching vs the
        # one-at-a-time server (<= 1.0 means equal-or-better p99)
        "p99_vs_serial_at_load": (best["p99_ms"] / serial_best["p99_ms"])
        if best and serial_best and best.get("p99_ms")
        and serial_best.get("p99_ms") else None,
        # and vs the closed-loop serial baseline at ITS OWN pace (the
        # latency a lone client saw before any load existed)
        "p99_vs_serial_closed_loop": (best["p99_ms"] / serial["p99_ms"])
        if best and best.get("p99_ms") and serial.get("p99_ms")
        else None,
        "trend_doctor_findings": doctor,
        "soak_clean": (not doctor) if doctor is not None else None,
        # per-objective SLO verdict over EVERY request of the sweep
        # (declared via MXNET_TPU_SLO; None when no objective is on)
        "slo": slo_verdict(),
    }
    return report


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Open-loop load generator for the continuous-"
                    "batching inference server (docs/SERVING.md).")
    p.add_argument("--qps", default=None,
                   help="comma list of offered request rates (default: "
                        "1x/2x/4x/6x the measured serial baseline)")
    p.add_argument("--duration", type=float, default=2.0,
                   help="seconds per offered-QPS level")
    p.add_argument("--sizes", default="1,2,4",
                   help="comma list of request sample counts (mixed "
                        "request shapes)")
    p.add_argument("--buckets", default="1,2,4,8,16",
                   help="server bucket ladder")
    p.add_argument("--workers", type=int, default=None,
                   help="server pipeline workers "
                        "(default MXNET_TPU_SERVE_WORKERS or 2)")
    p.add_argument("--metrics", default=None,
                   help="serving JSONL timeline path (enables the "
                        "trend-doctor soak gate)")
    p.add_argument("--out", default=None,
                   help="also write the JSON report here")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    from mxnet_tpu.util import enable_compile_cache

    enable_compile_cache()
    qps_levels = [float(q) for q in args.qps.split(",")] \
        if args.qps else None
    report = sweep(
        qps_levels=qps_levels, duration=args.duration,
        sizes=tuple(int(s) for s in args.sizes.split(",")),
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        metrics_path=args.metrics, workers=args.workers,
        seed=args.seed)
    print(json.dumps(report))
    # human-readable SLO verdict lines ride stderr: stdout stays the
    # one-JSON-report contract bench.py and CI parsers rely on
    for v in report.get("slo") or []:
        ach = ("%.4f%%" % (v["achieved"] * 100.0)
               if v["achieved"] is not None else "n/a")
        print("SLO %s (%s): target %.4f%%, achieved %s over %d "
              "requests, budget burned %.1f%% -> %s"
              % (v["objective"], v["kind"], v["target"] * 100.0, ach,
                 v["events"], v["budget_burned"] * 100.0,
                 "met" if v["met"] else "MISSED"), file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    # the sweep is informational; the soak gate is the pass/fail bit —
    # and a REQUESTED gate that never ran (no sustained level, or the
    # timeline export went dark) must not pass vacuously
    if args.metrics:
        return 0 if report["soak_clean"] is True else 1
    return 0 if report["soak_clean"] in (True, None) else 1


if __name__ == "__main__":
    sys.exit(main())
