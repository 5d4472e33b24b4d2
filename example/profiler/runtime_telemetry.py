"""Telemetry walkthrough: a ~20-step Gluon training loop whose chrome
trace shows the full step anatomy (dispatch cache hit/miss, io,
autograd, trainer) AND a live/peak device-memory timeline, plus the
always-on runtime_stats counters, per-op XLA cost analytics, the
recompile-storm detector, the numerics health layer (device-side
grad-norm/NaN sentinels, flight recorder, first-NaN warning + dump),
and the PR-8 analysis layer: per-step phase attribution (stepstats),
the perf doctor's ranked findings, and the dump-diff regression report,
plus the PR-10 continuous-monitoring layer: the live metrics timeline,
its JSONL export + Prometheus /metrics endpoint (scraped mid-loop
below), and the trend doctor's reading of an induced throughput drift.

Run directly (the script activates the profiler, buffer tracker, and
health monitor itself), or with zero code changes on any script via
the env vars:

    MXNET_TPU_PROFILE=trace.json python your_train.py
    MXNET_TPU_DIAG=diag.json     python your_train.py   # + kill -USR1
    MXNET_TPU_HEALTH=1           python your_train.py
    MXNET_TPU_STEPSTATS=1        python your_train.py   # step anatomy
    MXNET_TPU_METRICS=m.jsonl  MXNET_TPU_METRICS_PORT=9100 \
        python your_train.py                            # live timeline

Docs: docs/OBSERVABILITY.md.
"""

import argparse
import json
import os
import tempfile
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import (autograd, device_memory, gluon, health, perfdoctor,
                       profiler, runtime_stats, stepstats)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    out = args.out or os.path.join(tempfile.gettempdir(),
                                   "runtime_telemetry.json")
    if not os.environ.get("MXNET_TPU_PROFILE"):
        profiler.set_config(filename=out)
        profiler.set_state("run")
    # start all layers from zero so the trace/counter cross-check at
    # the end is exact (dumps(reset=True) drains any prior events);
    # the tracker is on BEFORE the loop so parameter buffers count
    profiler.dumps(reset=True)
    runtime_stats.reset()
    device_memory.reset()
    device_memory.start()
    # per-step phase attribution: where each iteration's wall time goes
    # (data wait / forward / backward / update / ... / remainder)
    stepstats.enable()

    # ---- a small imperative training loop, fully instrumented; the
    # health monitor computes grad-norm/NaN sentinels ON DEVICE and the
    # host only pays at the per-step drain
    mon = health.enable(dump_path=os.path.join(tempfile.gettempdir(),
                                               "runtime_telemetry_flight"
                                               ".json"))
    net = gluon.nn.Dense(4)
    net.initialize()
    mon.install(net)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    batch_size = 2
    X = rs.rand(args.steps * batch_size, 6).astype(np.float32)
    Y = rs.randint(0, 4, (args.steps * batch_size,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch_size)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    for batch in it:
        with autograd.record():
            loss = loss_fn(net(batch.data[0]), batch.label[0])
        loss.backward()
        mon.note_loss(loss)
        trainer.step(batch_size)

    # ---- provoke the recompile-storm detector: a churning attr value
    # bakes a new jit-cache key per call (the fix: traced_attrs)
    x = mx.nd.ones((4, 4))
    for i in range(runtime_stats.STORM_THRESHOLD + 2):
        mx.nd.clip(x, 0.0, 100.0 + i)  # watch stderr for the warning

    path = profiler.dump(finished=True)
    trace = json.load(open(path))["traceEvents"]
    names = {e["name"] for e in trace}
    print("trace: %s (%d events)" % (path, len(trace)))
    print("step anatomy spans:",
          sorted(n for n in names if not n.startswith("dispatch:")))
    hits = sum(1 for e in trace
               if e.get("args", {}).get("cache") == "hit")
    misses = sum(1 for e in trace
                 if e.get("args", {}).get("cache") == "miss")
    print("dispatch spans: %d cache hits, %d misses" % (hits, misses))

    mem_events = [e for e in trace if e.get("ph") == "C"
                  and e["name"] == "device_memory"]
    print("memory counter events: %d (open the trace: a live/peak-bytes"
          " track renders alongside the spans)" % len(mem_events))

    gn_events = [e for e in trace if e.get("ph") == "C"
                 and e["name"] == "grad_norm"]
    print("grad_norm counter events: %d (the numerics timeline — "
          "nan_total renders next to it)" % len(gn_events))
    flight = health.snapshot()["flight"]
    print("flight recorder: %d per-step record(s); latest: step %d "
          "loss %.4f grad_norm %.4f nan %d"
          % (len(flight), flight[-1]["step"], flight[-1]["loss"],
             flight[-1]["grad_norm"], int(flight[-1]["nan_total"])))
    assert all(r["nan_total"] == 0 for r in flight), \
        "a healthy demo loop must stay NaN-free"

    print("\nruntime_stats.report():")
    print(runtime_stats.report())
    snap = runtime_stats.snapshot()
    assert snap["totals"]["jit_cache_misses"] == misses, \
        "trace and counters must agree on compiles"
    assert snap["memory"]["totals"]["peak_bytes"] > 0

    # the production diagnostic: same picture, one atomic JSON file
    # (a live run does this on SIGUSR1 when MXNET_TPU_DIAG is set)
    diag = runtime_stats.dump_diag(os.path.join(
        tempfile.gettempdir(), "runtime_telemetry_diag.json"))
    print("\ndiag dump: %s (pretty-print: python -m "
          "mxnet_tpu.runtime_stats %s)" % (diag, diag))

    # ---- the perf doctor: ranked findings over the dump.  This run
    # deliberately provoked a recompile storm above, so the doctor must
    # rank it first with the churned attr as evidence.  CLI equivalent:
    #   python tools/diagnose.py --doctor <diag.json> [<trace.json>]
    ss = stepstats.snapshot()
    assert ss["steps"] == args.steps - 1  # first window arms the clock
    print("\nperf doctor on this run's dump:")
    _kind, dump = perfdoctor.classify(diag)
    findings = perfdoctor.diagnose(dump=dump)
    print(perfdoctor.render(findings, inputs=[diag]))
    assert any(f["rule"] == "recompile-storm" for f in findings), \
        "the provoked storm must be diagnosed"

    # ---- dump-diff regression report: rerun the same loop with a
    # delayed iterator and let compare() name the regressed phase.
    # CLI equivalent (rc=1 on regression, JSON verdict line for CI):
    #   python tools/diagnose.py --compare base.json slow.json
    runtime_stats.reset()
    stepstats.enable()
    it = mx.io.NDArrayIter(X, Y, batch_size=batch_size)
    orig_next = it.next

    def slow_next():
        time.sleep(0.005)  # the injected input-pipeline regression
        return orig_next()

    it.next = slow_next
    for batch in it:
        with autograd.record():
            loss = loss_fn(net(batch.data[0]), batch.label[0])
        loss.backward()
        trainer.step(batch_size)
    slow = runtime_stats.dump_diag(os.path.join(
        tempfile.gettempdir(), "runtime_telemetry_diag_slow.json"))
    a, b = runtime_stats.load_dumps([diag, slow])
    result = runtime_stats.compare(a, b, threshold=0.75)
    print("\ndump-diff (baseline vs delayed-io rerun):")
    print(runtime_stats.render_compare(result))
    assert result["verdict"] == "regression"
    assert any(e["metric"] == "phase:data_wait"
               for e in result["regressions"]), \
        "the injected io delay must be named"

    # ---- the live metrics timeline: per-step samples into a ring + a
    # JSONL file, a Prometheus /metrics endpoint scraped MID-LOOP, and
    # the trend doctor catching an induced mid-run drift.  Production
    # equivalent (zero code changes):
    #   MXNET_TPU_METRICS=m.jsonl MXNET_TPU_METRICS_PORT=9100 python ...
    import urllib.request

    from mxnet_tpu import metrics_timeline

    runtime_stats.reset()
    jsonl = os.path.join(tempfile.gettempdir(),
                         "runtime_telemetry_metrics.jsonl")
    if os.path.exists(jsonl):
        os.remove(jsonl)
    metrics_timeline.enable(path=jsonl)
    metrics_timeline.serve(port=0)  # 0 = pick a free port
    port = metrics_timeline.server_port()
    steps = max(30, args.steps)
    X2 = rs.rand(steps * batch_size, 6).astype(np.float32)
    Y2 = rs.randint(0, 4, (steps * batch_size,)).astype(np.float32)
    it = mx.io.NDArrayIter(X2, Y2, batch_size=batch_size)
    orig_next2 = it.next
    seen = [0]

    def drifting_next():
        seen[0] += 1
        if seen[0] > steps // 2:
            time.sleep(0.02)  # the induced mid-run drift
        if seen[0] == steps // 2:
            # scrape our own endpoint while the loop is live
            body = urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % port,
                timeout=10).read().decode()
            wall = [ln for ln in body.splitlines()
                    if ln.startswith("mxnet_tpu_step_duration_seconds")]
            print("\nmid-loop /metrics scrape (port %d): %d lines; %s"
                  % (port, len(body.splitlines()),
                     wall[0] if wall else "<no step yet>"))
        return orig_next2()

    it.next = drifting_next
    for batch in it:
        with autograd.record():
            loss = loss_fn(net(batch.data[0]), batch.label[0])
        loss.backward()
        trainer.step(batch_size)
    print("timeline: %d ring sample(s), %d JSONL line(s) at %s"
          % (len(metrics_timeline.samples()),
             metrics_timeline.snapshot()["written"], jsonl))
    trend = perfdoctor.diagnose(timeline=metrics_timeline.samples())
    print("\ntrend doctor on the live ring:")
    print(perfdoctor.render(trend))
    # What is asserted is read from the JSONL the loop wrote: the drift
    # is in the samples, phase by phase.  A sleep is a floor under the
    # late steps' data wait whatever else the machine runs, and a median
    # is not moved by the odd step the scheduler held up.  The doctor's
    # verdict above compares window *means* of wall-clock step times,
    # which on a loaded machine one such step can tip either way
    # (tests/test_metrics_timeline.py pins its rules on crafted series).
    with open(jsonl) as f:
        waits = [json.loads(line)["phases_ms"]["data_wait"] for line in f]
    half = len(waits) // 2      # the drift sets in half way, give or take
    early = float(np.median(waits[:half - 2]))
    late = float(np.median(waits[half + 2:]))
    print("data wait per step, from the JSONL: median %.3f ms before the "
          "drift, %.3f ms after" % (early, late))
    assert late >= 20.0 > 2.0 * early, \
        "the induced drift must be in the samples, in the drifting phase"

    # leave global collection off for any in-process caller (tests run
    # this example inside the suite)
    metrics_timeline.disable()
    stepstats.disable()
    return path


if __name__ == "__main__":
    main()
