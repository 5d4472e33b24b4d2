#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

Drives the main path once through the entry points a user calls, at the
published width of the one model the repo has measured (ResNet-50 v1
from ``gluon.model_zoo.vision``, 1000 classes, 224x224, NHWC, bf16
compute over f32 masters), with seeded random weights:

  train/benchmark  GluonTrainStep (the step the benchmark's cells time), bs=128
  train/users      gluon.Trainer + trainer.compile + cs.step (README)
  serve            net.export -> Predictor -> InferenceServer, requests
                   of 1-8 images against the unbatched predictor
  kernels          every Pallas kernel compiled by Mosaic (interpret
                   mode is a failure) and compared with its XLA oracle

Every phase's failure is fatal.  One process, no child that needs the
chip.  Exit 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
only when every phase passed on a TPU; any other platform exits
non-zero before a phase runs and prints no result.

Usage:
    python chip_smoke.py                one chip (what the driver runs)
    python chip_smoke.py --chips 4      the builder's four-chip mode: the
                                        benchmark's step over {'dp': 4}
                                        at global bs=512, then
                                        __graft_entry__.dryrun_multichip(4)
    python chip_smoke.py --cpu-dry-run  toy shapes on the CPU platform,
                                        kernels in the Pallas
                                        interpreter: checks this script's
                                        control flow (tests/
                                        test_chip_smoke.py) and proves
                                        nothing about the chip
"""

import argparse
import functools
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# published width; the dry run shrinks everything so the CPU finishes
FULL = {"net": "resnet50_v1", "classes": 1000, "image": 224, "batch": 128}
TINY = {"net": "resnet18_v1", "classes": 10, "image": 32, "batch": 8}
TRAIN_STEPS = 4
SERVE_SIZES = (1, 3, 8, 2)  # images per request, against buckets (1, 8)

# serving outputs vs the unbatched predictor: different batch shapes
# compile to different programs (conv algorithm, fusion, reduction
# order), so equality is to a tolerance — relative to the largest
# reference logit — never bit-exact
SERVE_TOL = 2e-2
# bf16 kernels vs their XLA oracles, relative to the oracle's largest
# element (one bf16 ulp is 2^-8; both sides round differently)
KERNEL_TOL = 3e-2
# host memory at which the run gives up: this much over the ~14 GB a
# TPU process holds per chip it drives (measured: 14 GB on the one-chip
# machine of 40 GiB, past 30 GB at start-up on the four-chip host of
# 140 GiB).  A Mosaic compile that runs away (PR 21: one conv-dW block
# size passed 32 GB) would otherwise use the machine up and lose it,
# and every line of output with it
HOST_RSS_PER_CHIP_GB = 14.0
HOST_RSS_HEADROOM_GB = 16.0


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def start_memory_guard(n_chips):
    """Exit non-zero, saying so, once this process's resident memory
    passes the limit for ``n_chips`` (Linux /proc; elsewhere there is
    no guard)."""
    limit_gb = HOST_RSS_PER_CHIP_GB * n_chips + HOST_RSS_HEADROOM_GB

    def rss_gb():
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1e6
        return 0.0

    def watch():
        while True:
            if rss_gb() > limit_gb:
                print("chip_smoke: FAIL host memory passed %.0f GB (a "
                      "compile running away?)" % limit_gb,
                      file=sys.stderr, flush=True)
                os._exit(3)
            time.sleep(0.25)

    if os.path.exists("/proc/self/status"):
        threading.Thread(target=watch, daemon=True).start()


def check_on(platform, what, arrays):
    """Every array lives on ``platform`` devices and nowhere else."""
    found = {d.platform for a in arrays for d in a.devices()}
    check(found == {platform}, "%s live on %s, not on %s"
          % (what, sorted(found), platform))


# ------------------------------------------------------------------ model


def build_net(cfg):
    """The seeded model on the default context (the chip, when one is
    attached), deferred shapes resolved by one small eager forward."""
    import mxnet_tpu as mx
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu.gluon.model_zoo import vision

    mxrandom.seed(7)
    np.random.seed(7)
    net = getattr(vision, cfg["net"])(classes=cfg["classes"],
                                      layout="NHWC")
    net.initialize()
    net(mx.nd.zeros((1, 32, 32, 3))).wait_to_read()
    return net


def fixed_batch(cfg, batch):
    rng = np.random.RandomState(0)
    x = rng.rand(batch, cfg["image"], cfg["image"], 3).astype(np.float32)
    y = rng.randint(0, cfg["classes"], (batch,)).astype(np.int32)
    return x, y


def timed_steps(run, n):
    """``run()`` n times on a fixed batch -> (losses, first-call seconds
    = compile + one step, ms of each later step).  ``run`` returns the
    loss as a host float, so every step ends in a device->host fetch.
    The later steps are listed one by one: a second compile hiding in
    step 2 (PR 21 found one) shows as an outlier, not in a mean."""
    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(run())
        times.append(time.perf_counter() - t0)
    return losses, times[0], ["%.1f" % (t * 1e3) for t in times[1:]]


def check_losses(name, losses):
    check(all(np.isfinite(l) for l in losses),
          "%s: non-finite loss in %s" % (name, losses))
    check(losses[-1] < losses[0],
          "%s: loss did not fall on a fixed batch: %s" % (name, losses))


# ----------------------------------------------------------------- phases


def phase_train_benchmark(cfg, platform, net, n_devices=1):
    """The step the benchmark's cells time: GluonTrainStep over a
    {'dp': n} mesh.
    The step trains its own copy of the parameters; ``net`` keeps its
    initial ones."""
    import jax

    from mxnet_tpu import gluon
    from mxnet_tpu.parallel.gluon_step import GluonTrainStep
    from mxnet_tpu.parallel.mesh import create_mesh

    mesh = create_mesh({"dp": n_devices},
                       devices=jax.devices()[:n_devices])
    step = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, lr=0.01, momentum=0.9, wd=1e-4,
                          compute_dtype="bfloat16")
    batch = cfg["batch"] * n_devices
    x, y = step.put_batch(*fixed_batch(cfg, batch))
    check(len(x.sharding.device_set) == n_devices,
          "batch sharding spans %d devices, expected %d"
          % (len(x.sharding.device_set), n_devices))

    holder = {}

    def run():
        holder["loss"] = step(x, y)
        return float(np.asarray(holder["loss"]))

    losses, first_s, step_ms = timed_steps(run, TRAIN_STEPS)
    check_losses("train/benchmark", losses)
    check_on(platform, "parameters", step.train_vals)
    check_on(platform, "loss", [holder["loss"]])
    for v in step.train_vals:
        check(len(v.sharding.device_set) == n_devices
              and v.sharding.is_fully_replicated,
              "a parameter is not replicated over %d devices"
              % n_devices)
    say("PASS train/benchmark: GluonTrainStep %s dp=%d bs=%d bf16, "
        "losses %s, first call %.1f s (compile + 1 step), then %s "
        "ms/step (set-up information, not a metric)"
        % (cfg["net"], n_devices, batch,
           ["%.4f" % l for l in losses], first_s, step_ms))
    return first_s


def phase_train_users(cfg, platform, net):
    """The step users call: gluon.Trainer + trainer.compile + cs.step
    (trains ``net``'s own parameters in place)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9,
                             "wd": 1e-4})
    cs = trainer.compile(net, gluon.loss.SoftmaxCrossEntropyLoss())
    xh, yh = fixed_batch(cfg, cfg["batch"])
    x, y = mx.nd.array(xh), mx.nd.array(yh)
    holder = {}

    def run():
        holder["loss"] = cs.step(x, y)
        return float(holder["loss"].mean().asnumpy())

    losses, first_s, step_ms = timed_steps(run, TRAIN_STEPS)
    check_losses("train/users", losses)
    check_on(platform, "parameters",
             [p.data().data_jax for p in net.collect_params().values()])
    check_on(platform, "loss", [holder["loss"].data_jax])
    say("PASS train/users: trainer.compile %s bs=%d f32, losses %s, "
        "first call %.1f s (compile + 1 step), then %s ms/step "
        "(set-up information, not a metric)"
        % (cfg["net"], cfg["batch"], ["%.4f" % l for l in losses],
           first_s, step_ms))


def phase_serve(cfg, platform, net):
    """net.export -> Predictor (default placement) -> InferenceServer."""
    import jax

    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import InferenceServer

    sample = (cfg["image"], cfg["image"], 3)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "smoke_model")
        net.export(path)
        with open(path + "-symbol.json") as f:
            sym_json = f.read()
        with open(path + "-0000.params", "rb") as f:
            params = f.read()
    pred = Predictor(sym_json, params, {"data": (1,) + sample})
    rng = np.random.RandomState(1)
    requests = [rng.rand(k, *sample).astype(np.float32)
                for k in SERVE_SIZES]
    worst = 0.0
    with InferenceServer(pred, buckets=(1, 8)) as srv:
        srv.warmup()
        futures = [srv.submit(r) for r in requests]
        results = [f.result(timeout=300.0)[0] for f in futures]
        # where a bucket executable computes (the server itself hands
        # back host arrays)
        probe = srv._bucket_fn(1)(
            {"data": jax.device_put(np.zeros((1,) + sample, np.float32))})
    for req, got in zip(requests, results):
        want = np.concatenate(
            [pred.forward(data=req[i:i + 1]).get_output(0)
             for i in range(len(req))])
        check(got.shape == want.shape == (len(req), cfg["classes"]),
              "served shape %s vs %s" % (got.shape, want.shape))
        check(np.isfinite(got).all(), "served output is not finite")
        err = float(np.abs(got - want).max()
                    / max(1.0, np.abs(want).max()))
        worst = max(worst, err)
        check(err <= SERVE_TOL,
              "served output differs from the unbatched predictor by "
              "%.3g (tolerance %.3g)" % (err, SERVE_TOL))
    check_on(platform, "Predictor outputs", [pred._outputs[0].data_jax])
    check_on(platform, "bucket executable outputs", probe)
    say("PASS serve: InferenceServer buckets (1, 8), requests of %s "
        "images, max rel diff vs unbatched Predictor.forward %.3g "
        "(tolerance %.3g), outputs on %s"
        % (list(SERVE_SIZES), worst, SERVE_TOL, platform))


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(1e-6, np.abs(want).max()))


def _check_kernel(name, fn, oracle, args, tiny):
    """Compile ``fn`` (Mosaic unless this is the CPU dry run), compare
    with ``oracle`` on the same arguments."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    if not tiny:
        check("tpu_custom_call" in lowered.as_text(),
              "%s: lowered program has no TPU custom call (interpret "
              "mode?)" % name)
    got = jax.tree_util.tree_leaves(lowered.compile()(*args))
    want = jax.tree_util.tree_leaves(jax.jit(oracle)(*args))
    errs = [_rel_err(g, w) for g, w in zip(got, want)]
    check(all(np.isfinite(e) and e <= KERNEL_TOL for e in errs),
          "%s: rel err %s vs XLA oracle (tolerance %.3g)"
          % (name, errs, KERNEL_TOL))
    say("PASS kernel %s: %s, max rel err %.3g"
        % (name, "interpreted" if tiny else "Mosaic-compiled", max(errs)))


def kernel_cases(tiny):
    """(name, kernel fn, XLA oracle, args) for every Pallas kernel at
    shapes the models use."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    rng = np.random.RandomState(2)

    def rand(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)

    seq, heads = (256, 2) if tiny else (2048, 8)
    for d in (64, 128):
        for causal in (False, True):
            q, k, v, g = (rand((2, heads, seq, d)) for _ in range(4))

            def fwd_bwd(attn, q, k, v, g, causal=causal):
                out, vjp = jax.vjp(
                    lambda q, k, v: attn(q, k, v, causal=causal), q, k, v)
                return (out,) + vjp(g)

            # interpret only asks for the interpreter on the CPU platform
            flash = functools.partial(att.flash_attention, interpret=tiny)
            yield ("flash_attention fwd+bwd bf16 seq=%d d=%d causal=%s"
                   % (seq, d, causal),
                   functools.partial(fwd_bwd, flash),
                   functools.partial(fwd_bwd, att.mha_reference),
                   (q, k, v, g))

    from mxnet_tpu.ops import pallas_conv

    n = 4 if tiny else 128
    # ResNet-50's four 3x3/s1 shapes: C=64 takes the im2col form, the
    # rest the per-tap form; 28, 14 and 7 are widths off the sublane
    # tile.  (Strided convs stay with XLA: Mosaic refuses strided loads
    # of 16-bit data.)
    for hw, c in ((56, 64), (28, 128), (14, 256), (7, 512)):
        x, dy = rand((n, hw, hw, c)), rand((n, hw, hw, c))
        check(pallas_conv.supported(x.shape, dy.shape, (3, 3), (1, 1),
                                    (1, 1), (1, 1), 1),
              "conv_dw_nhwc C=%d %dx%d: supported() says no" % (c, hw, hw))
        yield ("conv_dw_nhwc bf16 3x3/s1 C=%d %dx%d" % (c, hw, hw),
               lambda x, dy: pallas_conv.conv_dw_nhwc(x, dy, (3, 3),
                                                      (1, 1)),
               lambda x, dy: pallas_conv.conv_dw_xla(
                   x, dy, (3, 3), (1, 1), (1, 1)).astype(jnp.float32),
               (x, dy))


def phase_kernels(tiny):
    for name, fn, oracle, args in kernel_cases(tiny):
        _check_kernel(name, fn, oracle, args, tiny)


# ------------------------------------------------------------------- main


def device_gate(dry_run):
    """Print what JAX sees; refuse to go on unless it is a TPU (or the
    CPU platform, for the dry run that was asked for)."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("platform: %s  device_kind: %s  devices: %d  jax: %s"
        % (device["platform"], device["kind"], device["count"],
           jax.__version__))
    want = "cpu" if dry_run else "tpu"
    if device["platform"] != want:
        sys.exit("chip_smoke: jax platform is %r, this run needs %r "
                 "(no accelerator found?)" % (device["platform"], want))
    return device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the builder's four-chip mode")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="toy shapes on the CPU platform; proves nothing "
                         "about the chip")
    args = ap.parse_args(argv)
    tiny = args.cpu_dry_run
    cfg = TINY if tiny else FULL

    device = device_gate(tiny)
    platform = device["platform"]
    start_memory_guard(device["count"])
    if tiny:
        say("CPU DRY RUN: toy shapes, interpreted kernels — this proves "
            "nothing about the chip")
    else:
        check(device["count"] >= args.chips,
              "need %d chips, jax sees %d" % (args.chips, device["count"]))

    sys.path.insert(0, HERE)
    previous = record = None
    if not tiny:
        from mxnet_tpu.util import enable_compile_cache

        cache_dir = enable_compile_cache()
        record = os.path.join(cache_dir, "chip_smoke_compile.json")
        if os.path.exists(record):
            with open(record) as f:
                previous = json.load(f)
        say("compile cache: %s (%s)" % (
            cache_dir, "the train step's first call took %.1f s on the "
            "previous run with this cache" % previous["first_call_s"]
            if previous else "no earlier run recorded here"))

    t0 = time.perf_counter()
    net = build_net(cfg)
    say("built %s in %.1f s (eager per-op compiles)"
        % (cfg["net"], time.perf_counter() - t0))
    if args.chips == 4:
        import __graft_entry__ as graft

        first_s = phase_train_benchmark(cfg, platform, net, n_devices=4)
        graft.dryrun_multichip(4)
        say("PASS dryrun_multichip(4): dp / dp x tp / sp / pp / ep on "
            "%d %s devices" % (device["count"], device["kind"]))
    else:
        first_s = phase_train_benchmark(cfg, platform, net)
        phase_train_users(cfg, platform, net)
        phase_serve(cfg, platform, net)
        phase_kernels(tiny)
    if tiny:
        say("CPU DRY RUN finished: no result, nothing was proved about "
            "the chip")
        return 0
    os.makedirs(os.path.dirname(record), exist_ok=True)
    with open(record, "w") as f:
        json.dump({"first_call_s": first_s}, f)
    if previous:
        say("compile cache: the train step's first call took %.1f s now, "
            "%.1f s on the previous run (information, not a gate)"
            % (first_s, previous["first_call_s"]))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        sys.exit("chip_smoke: FAIL %s" % e)
