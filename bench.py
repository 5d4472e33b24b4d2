"""Benchmark: ResNet-50 training throughput, single chip.

Headline metric (BASELINE.md): ResNet-50 training img/s — reference
MXNet 1.2 on V100 fp32: 298.51 img/s @ bs=32, 363.69 img/s @ bs=128
(docs/faq/perf.md:225-236).  vs_baseline compares at the SAME batch
size (128 default) against the bs=128 V100 number; pass a batch on the
CLI to measure other configs.

The whole train step (fwd+bwd+SGD momentum+BN stat update) is one
jitted XLA computation (parallel/gluon_step.py); compute in bfloat16
with fp32 master weights (MXU-native mixed precision, the analog of the
reference's multi-precision SGD).  The model runs channel-last
(layout="NHWC"); pass a third CLI arg "NCHW" for the reference layout.

Two numbers are measured and recorded in the ONE printed JSON line,
which names the device they were taken on (platform, device_kind,
device count):

- ``value``        — a Python loop of step() dispatches with a loss
  fetch per rep: what a live training loop sees, host dispatch
  included.
- ``device_value`` — DEVICE_CHAIN (=50) training steps chained into ONE
  jitted computation (lax.fori_loop via GluonTrainStep.make_chained),
  so host dispatch is paid once per chain.  The ``steps`` CLI arg does
  NOT affect this metric — chained rates at different depths are not
  comparable, so the depth is pinned.

Every mode measures the chip: on any platform but ``tpu`` it exits
non-zero with one line.  The one exception is an explicit
``JAX_PLATFORMS=cpu`` (the tier-1 tests): the mode runs for its counts
and correctness checks, its record says ``"platform": "cpu"``, and it
prints no verdict and no wall-time comparison — a CPU timing is not a
speed.  There is no recorded baseline to gate against yet (ROADMAP.md
Speed #1 builds the benchmark and its cells).

Usage: python bench.py [batch] [steps] [NHWC|NCHW]
       python bench.py --compiled-step [batch] [steps] [image]
           (or MXNET_TPU_COMPILED_STEP=1): eager Trainer loop vs the
           fused whole-step program on the same model/seed — emits
           before/after diag dumps + one runtime_stats.compare()
           verdict (docs/COMPILED_STEP.md).
       python bench.py --zero [batch] [steps]
           (ZeRO weight-update sharding, docs/ZERO.md): eager Trainer
           loop vs trainer.compile(..., zero=True) on a BN-free MLP —
           emits before/after diag dumps + one runtime_stats.compare()
           verdict and gates on trajectory match + >=0.8*n per-device
           state shrink.
       python bench.py --serve [duration_s]
           serving bench: the tools/loadgen.py open-loop sweep
           (Poisson arrivals, p50/p99/p99.9 vs offered QPS, serial
           Predictor baseline + same-load serial-server replay) over
           the continuous-batching InferenceServer; prints the JSON
           report (docs/SERVING.md).
"""

import json
import os
import statistics
import sys
import time

import numpy as np

BASELINE_IMG_S = 363.69  # ResNet-50 training bs=128, V100 fp32 (docs/faq/perf.md)
# fixed chain depth of the device metric (rates at different depths are
# not comparable: the single dispatch amortizes differently)
DEVICE_CHAIN = 50


def require_chip():
    """True on a TPU.  Any other platform exits non-zero with one line
    — unless the CPU was asked for explicitly (``JAX_PLATFORMS=cpu``,
    the tier-1 tests), which returns False: the caller runs for its
    counts and prints no verdict."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu":
        return True
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return False
    sys.exit("bench: jax platform is %r, not 'tpu' — nothing to measure "
             "(set JAX_PLATFORMS=cpu to run the CPU checks on purpose)"
             % platform)


def device_fields():
    """What every printed record says about where it ran."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "devices": len(devs)}


def _cost_capture():
    """Context that forces compile-time cost/x-ray capture while the
    wrapped warmup step compiles, so the --compiled-step / --zero A/B
    diag dumps embed the per-scope x-ray table.  An explicit
    MXNET_TPU_COST_ANALYSIS=0 in the environment still wins."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prev = os.environ.get("MXNET_TPU_COST_ANALYSIS")
        if prev is None:
            os.environ["MXNET_TPU_COST_ANALYSIS"] = "1"
        try:
            yield
        finally:
            if prev is None:
                os.environ.pop("MXNET_TPU_COST_ANALYSIS", None)

    return ctx()


def run_compiled_compare(batch=8, steps=6, image=64, layout="NHWC",
                         net_fn=None, out_prefix="bench_compiled",
                         data_shape=None, num_classes=1000):
    """``--compiled-step`` mode: eager Trainer loop vs the fused
    whole-step program (mxnet_tpu/compiled_step.py) on the same model,
    seed, and synthetic data — the ROADMAP's one-``--compare``-run
    contract for perf PRs.

    Runs each side with stepstats/diag timing on, resets the counters
    after a warmup step, dumps both diag snapshots
    (``<out_prefix>.eager.diag.json`` / ``.fused.diag.json``) and
    prints one machine-readable JSON line; returns (rc, record).  The
    counts gate everywhere: rc 0 needs the losses to match and the
    fused side's warm dispatches to collapse to ~1 call/step.  The
    times gate only on the chip: there rc 0 also needs a step-wall
    improvement, ``runtime_stats.compare()``'s table is printed (the
    new ``phase:compiled_step`` / ``op:compiled_step`` rows on the
    fused side read as 0→inf "new cost" entries by compare()'s
    documented semantics — the wall/dispatch rows carry the actual
    before/after) and the record carries a verdict.  A CPU run prints
    neither.  ``net_fn(`` builds a fresh identically-seeded model
    (defaults to the bench ResNet-50)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu import runtime_stats as rts
    from mxnet_tpu import stepstats

    on_chip = require_chip()
    stepstats.enable()

    def default_net():
        from mxnet_tpu.gluon.model_zoo import vision

        net = vision.resnet50_v1(layout=layout)
        probe = (1, 3, 32, 32) if layout == "NCHW" else (1, 32, 32, 3)
        net.initialize()
        net(mx.nd.zeros(probe))
        return net

    build = net_fn or default_net
    if data_shape is None:
        data_shape = (batch, 3, image, image) if layout == "NCHW" \
            else (batch, image, image, 3)
    rng = np.random.RandomState(0)
    xs = [rng.rand(*data_shape).astype(np.float32)
          for _ in range(steps + 1)]
    ys = [rng.randint(0, num_classes, (batch,)).astype(np.int32)
          for _ in range(steps + 1)]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def fresh(seed=7):
        mxrandom.seed(seed)
        np.random.seed(seed)
        return build()

    def steady_anatomy():
        snap = rts.snapshot()
        ss = snap.get("stepstats") or {}
        n = ss.get("steps") or 1
        wall = ((ss.get("wall") or {}).get("sum") or 0.0) / n * 1e3
        # per-step RATES divide by the counted steps, not the stepstats
        # window count: the first end_step after reset() only arms the
        # clock, so windows = steps-1 and using it would inflate the
        # headline dispatches/step by N/(N-1)
        steps = (snap.get("counters") or {}).get("trainer_steps") or 1
        warm = (snap.get("totals") or {}).get("jit_cache_hits", 0) / steps
        return snap, wall, warm

    # ---- eager side ---------------------------------------------------
    net = fresh()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 1e-4})
    losses_eager = []

    def eager_step(x, y):
        xa, ya = mx.nd.array(x), mx.nd.array(y)
        with autograd.record():
            l = loss_fn(net(xa), ya)
        l.backward()
        trainer.step(batch)
        return l

    eager_step(xs[0], ys[0])  # warmup: compiles land before the window
    rts.reset()
    for x, y in zip(xs[1:], ys[1:]):
        losses_eager.append(eager_step(x, y))
    # capture the dump BEFORE the loss fetches: the readback means are
    # measurement overhead, not part of the measured loop
    eager_dump, eager_wall, eager_warm = steady_anatomy()
    eager_path = out_prefix + ".eager.diag.json"
    rts.dump_diag(eager_path)
    losses_eager = [float(np.asarray(l.mean().data_jax))
                    for l in losses_eager]

    # ---- fused side ---------------------------------------------------
    rts.reset()
    net = fresh()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 1e-4})
    cs = trainer.compile(net, loss_fn)
    with _cost_capture():  # warmup compiles -> x-ray lands in the dump
        cs.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    rts.reset()
    losses_fused = []
    for x, y in zip(xs[1:], ys[1:]):
        losses_fused.append(cs.step(mx.nd.array(x), mx.nd.array(y)))
    fused_dump, fused_wall, fused_warm = steady_anatomy()
    fused_path = out_prefix + ".fused.diag.json"
    rts.dump_diag(fused_path)
    losses_fused = [float(np.asarray(l.mean().data_jax))
                    for l in losses_fused]

    # ---- verdict ------------------------------------------------------
    # step 1 ran the same function on the same init: near-bit-equal.
    # later steps drift in the last float ulps (the fused program's
    # XLA autodiff reassociates conv-backward reductions vs the
    # per-op tape) and training amplifies it — trajectory-level
    # tolerance, not bit equality, is the right check there.
    losses_match = bool(
        np.allclose(losses_eager[:1], losses_fused[:1], rtol=1e-5)
        and np.allclose(losses_eager, losses_fused, rtol=5e-2))
    ok = losses_match and fused_warm <= 2.0
    record = {
        "metric": "compiled_step eager-vs-fused (bs=%d, data %s, %d "
                  "steps, same seed)" % (batch, list(data_shape[1:]),
                                         steps),
        "warm_dispatches_per_step": {"eager": round(eager_warm, 1),
                                     "fused": round(fused_warm, 1)},
        "losses_match": losses_match,
        "dumps": [eager_path, fused_path],
    }
    if on_chip:
        result = rts.compare(eager_dump, fused_dump)
        print(rts.render_compare(result), file=sys.stderr)
        ok = ok and fused_wall < eager_wall
        record["verdict"] = "improvement" if ok else "regression"
        # raw compare() verdict: the fused side's NEW
        # phase:compiled_step / op:compiled_step rows read as 0->inf
        # entries by its documented new-cost semantics — the wall /
        # dispatch / per-phase rows carry the real before/after
        record["compare_verdict"] = result["verdict"]
        record["step_wall_ms"] = {"eager": round(eager_wall, 3),
                                  "fused": round(fused_wall, 3)}
    else:
        record["step_wall_ms"] = "not measured"
    record.update(device_fields())
    print(json.dumps(record))
    if not ok:
        print("compiled-step compare FAILED: losses_match=%s "
              "fused_warm=%.1f/step fused_wall=%.3fms vs eager "
              "%.3fms" % (losses_match, fused_warm, fused_wall,
                          eager_wall), file=sys.stderr)
    return (0 if ok else 1), record


def run_zero_compare(batch=64, steps=8, features=256, hidden=512,
                     classes=100, out_prefix="bench_zero"):
    """``--zero`` mode: the same eager Trainer loop vs the ZeRO
    weight-update-sharded whole-step program
    (``trainer.compile(net, loss, zero=True)`` —
    parallel/gluon_step.py) on one model, seed, and synthetic data.

    The model is a BN-free multi-layer perceptron on purpose: batch-norm
    statistics are computed per dp shard under the sharded step, which
    is a (documented) modeling difference, not a ZeRO numerics bug —
    an elementwise-optimizer MLP isolates what this mode is gating:
    the loss trajectory staying equivalent while per-device
    param+optimizer-state bytes shrink ~n× and the new collective
    traffic (``zero_allgather_bytes`` / ``zero_reduce_bytes``) is
    accounted.  Emits both diag dumps (``<out_prefix>.eager.diag.json``
    / ``.zero.diag.json``), prints one JSON record line, and returns
    (rc, record): rc 0 iff the trajectories match AND the measured
    state shrink clears 0.8×n — counts, which hold on any platform.
    Only on the chip are ``runtime_stats.compare()``'s table (the
    zero:* rows land in its one-sided ``notes`` — a topology change,
    not a regression), the step walls and a verdict printed."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu import runtime_stats as rts
    from mxnet_tpu import stepstats
    from mxnet_tpu.gluon import nn

    on_chip = require_chip()
    stepstats.enable()

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden, activation="relu"),
                nn.Dense(classes))
        net.initialize()
        net(mx.nd.zeros((2, features)))
        return net

    def fresh(seed=7):
        mxrandom.seed(seed)
        np.random.seed(seed)
        return build()

    rng = np.random.RandomState(0)
    xs = [rng.rand(batch, features).astype(np.float32)
          for _ in range(steps + 1)]
    ys = [rng.randint(0, classes, (batch,)).astype(np.int32)
          for _ in range(steps + 1)]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt_args = {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}

    def steady_wall():
        snap = rts.snapshot()
        ss = snap.get("stepstats") or {}
        n = ss.get("steps") or 1
        return snap, ((ss.get("wall") or {}).get("sum") or 0.0) / n * 1e3

    # ---- eager side ---------------------------------------------------
    net = fresh()
    trainer = gluon.Trainer(net.collect_params(), "sgd", opt_args)
    losses_eager = []

    def eager_step(x, y):
        xa, ya = mx.nd.array(x), mx.nd.array(y)
        with autograd.record():
            l = loss_fn(net(xa), ya)
        l.backward()
        trainer.step(batch)
        return l

    eager_step(xs[0], ys[0])  # warmup: compiles land before the window
    rts.reset()
    for x, y in zip(xs[1:], ys[1:]):
        losses_eager.append(eager_step(x, y))
    eager_dump, eager_wall = steady_wall()
    eager_path = out_prefix + ".eager.diag.json"
    rts.dump_diag(eager_path)
    losses_eager = [float(np.asarray(l.mean().data_jax))
                    for l in losses_eager]

    # ---- ZeRO side ----------------------------------------------------
    rts.reset()
    net = fresh()
    trainer = gluon.Trainer(net.collect_params(), "sgd", opt_args)
    zs = trainer.compile(net, loss_fn, zero=True)
    with _cost_capture():  # warmup compiles -> x-ray lands in the dump
        zs.step(mx.nd.array(xs[0]), mx.nd.array(ys[0]))
    rts.reset()
    losses_zero = []
    for x, y in zip(xs[1:], ys[1:]):
        losses_zero.append(zs.step(mx.nd.array(x), mx.nd.array(y)))
    zero_dump, zero_wall = steady_wall()
    zero_path = out_prefix + ".zero.diag.json"
    rts.dump_diag(zero_path)
    losses_zero = [float(np.asarray(l.mean().data_jax))
                   for l in losses_zero]

    # ---- verdict ------------------------------------------------------
    # same trajectory contract as --compiled-step: the fused program's
    # XLA autodiff + the dp-sharded mean reassociate reductions, so
    # later steps drift in the last ulps and training amplifies it
    losses_match = bool(
        np.allclose(losses_eager[:1], losses_zero[:1], rtol=1e-5)
        and np.allclose(losses_eager, losses_zero, rtol=5e-2))
    layout = zs.zero_layout
    n = layout["n"]
    shrink = (layout["replicated_param_bytes"]
              / max(1, layout["per_device_param_bytes"]))
    counters = (zero_dump.get("counters") or {})
    zsteps = counters.get("zero_steps") or 1
    ok = losses_match and shrink >= 0.8 * n
    record = {
        "metric": "zero eager-vs-sharded (bs=%d, mlp %d-%dx2-%d, %d "
                  "steps, same seed, dp=%d)"
                  % (batch, features, hidden, classes, steps, n),
        "losses_match": losses_match,
        "dp": n,
        "state_shrink_x": round(shrink, 2),
        "per_device_param_bytes": layout["per_device_param_bytes"],
        "per_device_state_bytes": layout["per_device_state_bytes"],
        "replicated_param_bytes": layout["replicated_param_bytes"],
        "allgather_mb_per_step": round(
            counters.get("zero_allgather_bytes", 0) / zsteps / 1e6, 3),
        "reduce_mb_per_step": round(
            counters.get("zero_reduce_bytes", 0) / zsteps / 1e6, 3),
        "dumps": [eager_path, zero_path],
    }
    if on_chip:
        result = rts.compare(eager_dump, zero_dump)
        print(rts.render_compare(result), file=sys.stderr)
        record["verdict"] = "improvement" if ok else "regression"
        record["compare_verdict"] = result["verdict"]
        record["step_wall_ms"] = {"eager": round(eager_wall, 3),
                                  "zero": round(zero_wall, 3)}
    else:
        record["step_wall_ms"] = "not measured"
    record.update(device_fields())
    print(json.dumps(record))
    if not ok:
        print("zero compare FAILED: losses_match=%s shrink=%.2fx "
              "(need >= %.1fx at dp=%d)"
              % (losses_match, shrink, 0.8 * n, n), file=sys.stderr)
    return (0 if ok else 1), record


def run_serve_bench(duration=2.0):
    """``--serve`` mode: the loadgen sweep.  The report names the
    device the model's *output array* lives on (loadgen.sweep reads it
    off the predictor's output), not jax's default device.  Returns
    (rc, report): rc 0 iff the sweep sustained a level and the timeline
    soak gated clean through the trend doctor."""
    require_chip()
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    import loadgen

    metrics = os.path.join(here, "bench_serve_timeline.jsonl")
    # a fresh soak timeline per run: stale samples from a prior run
    # would feed the trend doctor a fake regression
    if os.path.exists(metrics):
        os.remove(metrics)
    report = loadgen.sweep(duration=duration, metrics_path=metrics)
    report["unit"] = "requests/s"
    print(json.dumps(report))
    # the bench ALWAYS requests the soak timeline, so a missing gate
    # (soak_clean None: export failed or no level sustained) is a
    # failure, not a vacuous pass
    ok = bool(report["max_sustained_qps"]) \
        and report["soak_clean"] is True
    if not ok:
        print("serve bench FAILED: max_sustained_qps=%s soak_clean=%s"
              % (report["max_sustained_qps"], report["soak_clean"]),
              file=sys.stderr)
    return (0 if ok else 1), report


def main():
    if "--zero" in sys.argv and "jax" not in sys.modules \
            and "XLA_FLAGS" not in os.environ \
            and os.environ.get("JAX_PLATFORMS") == "cpu":
        # the sharding is degenerate at one device: on an explicit CPU
        # run force virtual devices BEFORE jax initializes (same trick
        # as conftest.py / tools/scaling_report.py); a real multi-chip
        # backend keeps its own device count
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    from mxnet_tpu.util import enable_compile_cache

    enable_compile_cache()
    if "--zero" in sys.argv:
        nums = [int(a) for a in sys.argv[1:]
                if a != "--zero" and a.lstrip("-").isdigit()]
        batch = nums[0] if nums else 64
        steps = nums[1] if len(nums) > 1 else 8
        rc, _rec = run_zero_compare(batch=batch, steps=steps)
        sys.exit(rc)
    if "--serve" in sys.argv:
        nums = [a for a in sys.argv[1:] if a not in ("--serve",)]
        duration = float(nums[0]) if nums else 2.0
        rc, _rep = run_serve_bench(duration=duration)
        sys.exit(rc)
    if "--compiled-step" in sys.argv or \
            os.environ.get("MXNET_TPU_COMPILED_STEP") == "1":
        # tolerate BOTH argv shapes: the compare form
        # `--compiled-step [batch] [steps] [image]` and the standard
        # `bench.py [batch] [steps] [NHWC|NCHW]` that launch wiring
        # uses with MXNET_TPU_COMPILED_STEP=1 — a layout token selects
        # the layout instead of crashing int() (and NCHW is compared
        # as NCHW)
        layout = "NHWC"
        nums = []
        for a in sys.argv[1:]:
            if a == "--compiled-step":
                continue
            if a in ("NHWC", "NCHW"):
                layout = a
            else:
                nums.append(int(a))
        batch = nums[0] if len(nums) > 0 else 8
        steps = nums[1] if len(nums) > 1 else 6
        image = nums[2] if len(nums) > 2 else 64
        rc, _rec = run_compiled_compare(batch=batch, steps=steps,
                                        image=image, layout=layout)
        sys.exit(rc)

    require_chip()
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.gluon_step import GluonTrainStep
    from mxnet_tpu.parallel.mesh import create_mesh

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    layout = sys.argv[3] if len(sys.argv) > 3 else "NHWC"

    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])  # one chip

    net = vision.resnet50_v1(layout=layout)
    probe_shape = (1, 3, 32, 32) if layout == "NCHW" else (1, 32, 32, 3)
    net.initialize()
    net(mx.nd.zeros(probe_shape))  # resolve deferred shapes
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    step = GluonTrainStep(net, loss, mesh=mesh, lr=0.1, momentum=0.9,
                          wd=1e-4, compute_dtype="bfloat16")

    rng = np.random.RandomState(0)
    data_shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    x = rng.rand(*data_shape).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.int32)
    x, y = step.put_batch(x, y)  # device-resident synthetic batch

    # ---- device metric: DEVICE_CHAIN steps in one dispatch -----------
    chained = step.make_chained(DEVICE_CHAIN)
    key = mxrandom.next_key()
    chained(x, y, key).block_until_ready()  # compile + warm
    device_rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        chained(x, y, key).block_until_ready()
        device_rates.append(DEVICE_CHAIN * batch
                            / (time.perf_counter() - t0))
    device_img_s = statistics.median(device_rates)

    # ---- dispatch loop (what a live training loop sees) --------------
    for _ in range(3):
        l = step(x, y)
    l.block_until_ready()
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            l = step(x, y)
        float(np.asarray(l))  # the loop's loss fetch
        rates.append(steps * batch / (time.perf_counter() - t0))
    img_s = statistics.median(rates)

    record = {
        "metric": "resnet50_v1 training img/s (bs=%d, bf16 compute, %s, "
                  "1 chip, median of 3)" % (batch, layout),
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "device_value": round(device_img_s, 2),
        "device_metric": "img/s with %d steps chained in one jit "
                         "(median of 3)" % DEVICE_CHAIN,
    }
    record.update(device_fields())
    print(json.dumps(record))


if __name__ == "__main__":
    main()
