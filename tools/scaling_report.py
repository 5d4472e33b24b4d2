"""Collective scaling report: how the compiled sharded programs scale
with device count (VERDICT r4 task #5 / SCALING.md).

For each n in --devices, a child process with n virtual CPU devices
(`--xla_force_host_platform_device_count=n`) builds two training steps
on tiny shapes —

- **dp**: the flagship ResNet-50 v1 data-parallel GluonTrainStep
  (the step of the benchmark's ResNet-50 cells), params replicated,
  GSPMD inserting the gradient all-reduce; and
- **dp2 x tp2 x pp(n/4)**: the 3-axis composition from
  `__graft_entry__._dryrun_dp_tp_pp` — GPipe collective-permute ring
  over 'pp', Megatron row-parallel psum over 'tp', dp grad all-reduce —

compiles them, and reads off the *post-SPMD-partitioning* HLO:
collective op counts and total per-device collective payload bytes
(sum of every collective instruction's output shape — shapes after
partitioning are per-shard, so this is the traffic one device sends
per step, the quantity that must fit the ICI budget), plus measured
per-device parameter/optimizer bytes from the live sharded arrays.

This is the closest a 1-host container gets to the 256-chip
scaling-efficiency north star (BASELINE.md): hardware can't be
simulated, but the *collective structure* — what rides the
interconnect and how it grows with n — is exactly what the compiled
HLO pins.  Reference analog: tools/bandwidth/ measures its kvstore
traffic empirically; here the compiler's program IS the spec.

Usage:
    python tools/scaling_report.py                  # writes SCALING_TABLE.md
        (SCALING.md is the committed narrative AROUND these tables —
         refresh its numbers from the regenerated SCALING_TABLE.md)
    python tools/scaling_report.py --devices 8,16   # subset
    python tools/scaling_report.py --child 8        # (internal)
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"= ((?:\([^)]*\))|(?:\w+\[[^\]]*\](?:\{[^}]*\})?)) "
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start)?\(")


def _shape_bytes(shape_text):
    """Bytes of 'f32[128,64]{1,0}' or a '(tuple, of, shapes)'."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        count = 1
        for d in dims.split(","):
            if d:
                count *= int(d)
        total += count * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text):
    """{op: {'count': N, 'bytes': per-device payload}} over the HLO."""
    stats = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for m in _INSTR_RE.finditer(hlo_text):
        shape_text, op = m.group(1), m.group(2)
        stats[op]["count"] += 1
        stats[op]["bytes"] += _shape_bytes(shape_text)
    return stats


def _sharded_bytes(vals):
    return sum(int(v.addressable_shards[0].data.nbytes) for v in vals)


def _child(n):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.gluon_step import GluonTrainStep
    from mxnet_tpu.parallel.mesh import create_mesh

    out = {"n": n}

    # ---- zero: ZeRO weight-update sharding (MLP + Adam) -------------
    # params + Adam state born sharded 1/n (parallel/gluon_step.py
    # zero=True, docs/ZERO.md); the compiled HLO shows the grad
    # reduce-scatter + param all-gather replacing the dp all-reduce.
    # A BN-free MLP keeps n=256 lowering cheap — the shrink evidence
    # is model-independent.
    from mxnet_tpu import optimizer as _opt
    from mxnet_tpu.gluon import nn

    mesh_z = create_mesh({"dp": n})
    mlp = nn.HybridSequential()
    mlp.add(nn.Dense(512, activation="relu"),
            nn.Dense(512, activation="relu"), nn.Dense(100))
    mlp.initialize(ctx=mx.cpu())
    mlp(mx.nd.zeros((2, 256), ctx=mx.cpu()))
    zstep = GluonTrainStep(mlp, gluon.loss.SoftmaxCrossEntropyLoss(),
                           mesh=mesh_z, zero=True,
                           optimizer=_opt.create("adam",
                                                 learning_rate=1e-3))
    xz, yz = zstep.put_batch(np.zeros((n, 256), np.float32),
                             np.zeros((n,), np.int32))
    hloz = zstep.program_for(xz, yz).as_text()
    out["zero"] = {
        "param_bytes_per_dev": _sharded_bytes(zstep.train_vals),
        "opt_bytes_per_dev": _sharded_bytes(zstep.opt_state),
        "replicated_param_bytes":
            zstep.zero_layout["replicated_param_bytes"],
        "collectives": collective_stats(hloz),
    }
    if n > 64:
        # the ResNet-50 dp / 3-axis sections compile minutes-slow at
        # SPMD widths past 64; the zero table is what scales to 256
        json.dump(out, sys.stdout)
        return

    # ---- dp: flagship ResNet-50 step --------------------------------
    mesh = create_mesh({"dp": n})
    net = vision.resnet50_v1(classes=10)
    net.initialize(ctx=mx.cpu())
    net(mx.nd.zeros((1, 3, 32, 32), ctx=mx.cpu()))
    step = GluonTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, lr=0.1, momentum=0.9)
    x, y = step.put_batch(
        np.zeros((2 * n, 3, 32, 32), np.float32),
        np.zeros((2 * n,), np.int32))
    hlo = step.program_for(x, y).as_text()
    out["dp"] = {
        "param_bytes_per_dev": _sharded_bytes(step.train_vals),
        "opt_bytes_per_dev": _sharded_bytes(
            [s for s in step.opt_state if hasattr(s, "addressable_shards")]),
        "collectives": collective_stats(hlo),
    }

    # ---- dp2 x tp2 x pp(n/4): 3-axis composition --------------------
    if n >= 8 and n % 4 == 0:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from mxnet_tpu.parallel.pp import GPipe

        pp = n // 4
        mesh3 = create_mesh({"dp": 2, "tp": 2, "pp": pp})
        d, h = 64, 128
        rs = np.random.RandomState(0)
        params = {
            "w1": jnp.asarray(rs.randn(pp, d, h).astype(np.float32) * .3),
            "w2": jnp.asarray(rs.randn(pp, h, d).astype(np.float32) * .3),
        }
        gb = 4 * pp
        xx = jnp.asarray(rs.randn(gb, d).astype(np.float32))
        tt = jnp.asarray(rs.randn(gb, d).astype(np.float32))

        def stage_fn(p, cur):
            return lax.psum(jnp.tanh(cur @ p["w1"]) @ p["w2"], "tp")

        pipe = GPipe(stage_fn, mesh3, n_microbatches=pp,
                     batch_spec=P("dp", None),
                     param_specs={"w1": P("pp", None, "tp"),
                                  "w2": P("pp", "tp", None)})

        @jax.jit
        def train_step(ps):
            def loss_fn(q):
                return ((pipe(q, xx) - tt) ** 2).mean()

            loss, grads = jax.value_and_grad(loss_fn)(ps)
            return loss, jax.tree_util.tree_map(
                lambda w, g: w - 0.05 * g, ps, grads)

        hlo3 = train_step.lower(params).compile().as_text()
        out["dp_tp_pp"] = {"pp": pp, "collectives": collective_stats(hlo3)}

    json.dump(out, sys.stdout)


def _spawn(n):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=%d" % n).strip()
    env["PYTHONPATH"] = REPO
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--child", str(n)],
                       capture_output=True, text=True, timeout=3600,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError("child n=%d failed:\n%s" % (n, r.stderr[-4000:]))
    return json.loads(r.stdout)


def _fmt_bytes(b):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if b < 1024 or unit == "GiB":
            return "%.1f %s" % (b, unit) if unit != "B" else "%d B" % b
        b /= 1024.0


def main(device_counts):
    results = [_spawn(n) for n in device_counts]
    lines = []
    w = lines.append
    w("# SCALING_TABLE.md — collective structure vs device count")
    w("")
    w("Generated by `python tools/scaling_report.py` (virtual CPU mesh, "
      "post-SPMD HLO; see the tool docstring for method).  'bytes' = "
      "per-device collective payload per training step — the traffic "
      "each chip puts on the interconnect.")
    w("")
    w("## Data-parallel ResNet-50 training step (bs=2/device)")
    w("")
    w("| n | param B/dev | opt B/dev | all-reduce (count / bytes) | "
      "other collectives |")
    w("|---|---|---|---|---|")
    for r in results:
        if "dp" not in r:
            continue
        dp = r["dp"]
        c = dp["collectives"]
        other = ", ".join("%s %d/%s" % (op, c[op]["count"],
                                        _fmt_bytes(c[op]["bytes"]))
                          for op in _COLLECTIVES
                          if op != "all-reduce" and c[op]["count"])
        w("| %d | %s | %s | %d / %s | %s |" % (
            r["n"], _fmt_bytes(dp["param_bytes_per_dev"]),
            _fmt_bytes(dp["opt_bytes_per_dev"]),
            c["all-reduce"]["count"], _fmt_bytes(c["all-reduce"]["bytes"]),
            other or "—"))
    w("")
    w("## ZeRO weight-update sharding (MLP 256-512×2-100 + Adam, "
      "`zero=True`)")
    w("")
    w("Params and Adam moments live sharded 1/n from step 0; the grad "
      "all-reduce becomes reduce-scatter + param all-gather "
      "(docs/ZERO.md).  'shrink' = replicated param bytes / measured "
      "per-device param bytes (padding makes it slightly under n).")
    w("")
    w("| n | param B/dev | opt B/dev | shrink | all-gather | "
      "reduce-scatter / all-reduce |")
    w("|---|---|---|---|---|---|")
    for r in results:
        if "zero" not in r:
            continue
        z = r["zero"]
        c = z["collectives"]
        shrink = z["replicated_param_bytes"] / max(
            1, z["param_bytes_per_dev"])
        rs_cell = ", ".join(
            "%s %d/%s" % (op, c[op]["count"], _fmt_bytes(c[op]["bytes"]))
            for op in ("reduce-scatter", "all-reduce")
            if c[op]["count"]) or "—"
        ag = c["all-gather"]
        w("| %d | %s | %s | %.2f× | %s | %s |" % (
            r["n"], _fmt_bytes(z["param_bytes_per_dev"]),
            _fmt_bytes(z["opt_bytes_per_dev"]), shrink,
            ("%d/%s" % (ag["count"], _fmt_bytes(ag["bytes"])))
            if ag["count"] else "—", rs_cell))
    w("")
    w("## dp2 × tp2 × pp(n/4) composition (GPipe ring + Megatron psum)")
    w("")
    w("| n | pp | all-reduce | collective-permute | all-gather | "
      "reduce-scatter |")
    w("|---|---|---|---|---|---|")
    for r in results:
        if "dp_tp_pp" not in r:
            continue
        c = r["dp_tp_pp"]["collectives"]

        def cell(op):
            return ("%d / %s" % (c[op]["count"], _fmt_bytes(c[op]["bytes"]))
                    if c[op]["count"] else "—")

        w("| %d | %d | %s | %s | %s | %s |" % (
            r["n"], r["dp_tp_pp"]["pp"], cell("all-reduce"),
            cell("collective-permute"), cell("all-gather"),
            cell("reduce-scatter")))
    w("")
    return results, "\n".join(lines)


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child(int(sys.argv[sys.argv.index("--child") + 1]))
    else:
        counts = [8, 16, 32, 64, 128, 256]
        if "--devices" in sys.argv:
            counts = [int(x) for x in
                      sys.argv[sys.argv.index("--devices") + 1].split(",")]
        results, md = main(counts)
        print(md)
        with open(os.path.join(REPO, "SCALING_TABLE.md"), "w") as f:
            f.write(md + "\n")
