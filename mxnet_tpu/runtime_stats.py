"""Always-cheap runtime counters + the recompile-storm detector.

``profiler.py`` records *events* (chrome-trace spans) and pays an
allocation per event, so it is opt-in; this module is the always-on
complement: monotonic counters bumped from the dispatch hot path with
plain dict increments (GIL-atomic, no locks, no allocation), readable
at any time via :func:`snapshot` / :func:`report` even when the
profiler is off.

Feeding layers (PR 2): ``ops/registry.py`` (jit-cache hit/miss and the
cache key of every compile), ``ndarray`` imperative dispatch (compile
wall-time, fallback/uncached paths), ``executor`` / Gluon ``Trainer`` /
``io`` / ``kvstore`` (step anatomy counters), and ``monitor.py``
(deliberate host-sync overhead).

Recompile-storm detector: every jit-cache miss registers the cache key
that missed.  When one op accumulates more than :data:`STORM_THRESHOLD`
compiles, a rate-limited warning (through ``log.py``) names the attr
key component that churned — per-step recompiles are the canonical
silent 100x slowdown on XLA backends ("Operator Fusion in XLA",
arXiv:2301.13062).  When the profiler is running the dispatch layer
additionally feeds input aval signatures, so shape/dtype churn (which
recompiles *inside* an existing jax.jit entry) is detected too.

Memory & cost analytics (PR 3): ``snapshot()`` additionally carries a
``memory`` section (live/peak device bytes from ``device_memory.py``),
a ``costs`` section (per-op XLA cost/memory analysis captured at
compile time by ``ops/registry.py``), and :func:`roofline` derives
achieved GB/s / GFLOP/s per op from profiled dispatch wall-time — the
in-production analog of an offline device-trace audit.
:func:`dump_diag` writes the whole picture atomically to a JSON file;
``MXNET_TPU_DIAG=<file>`` arms a ``SIGUSR1`` handler (plus an atexit
dump) so a live training job can be asked for it at any time, and
``python -m mxnet_tpu.runtime_stats [dump.json]`` pretty-prints it.

Numerics health (PR 5): ``snapshot()`` embeds a ``health`` section —
the device-resident NaN/Inf monitor and training flight recorder from
``health.py`` — so :func:`report`, the diag dump, and the CLI all
carry the numerics picture; the CLI also renders standalone
flight-recorder dumps (files whose top level is ``health`` only).

Distributed telemetry (PR 7): ``snapshot()`` carries a ``histograms``
section (log2-bucketed latency distributions from ``histogram.py``:
kvstore push/pull RTT per shard, warm dispatch, io next-batch wait,
checkpoint writes, trainer steps) and diag dumps are stamped with this
process's rank/role identity (``log.process_identity``).
:func:`cluster_report` merges several ranks' diag dumps into one
cluster view — per-rank latency table, merged distributions, and a
straggler callout with the p99/median skew ratio — rendered by
``tools/diagnose.py --cluster`` and by this module's CLI when given
more than one dump file.

Environment variables
---------------------
``MXNET_TPU_RECOMPILE_STORM_THRESHOLD``  compiles per op before the
    storm warning fires (default 8; ``0`` disables the detector).
``MXNET_TPU_RECOMPILE_STORM_INTERVAL``   minimum seconds between storm
    warnings for the same op (default 30).
``MXNET_TPU_DIAG``  diagnostic-dump destination; arms SIGUSR1 + atexit
    dump, and turns on the device-memory tracker and compile-time cost
    capture so the dump is populated.
"""

from __future__ import annotations

import itertools
import json
import os
import time

from . import device_memory
from . import histogram as _histogram
from . import stepstats as _stepstats
from .log import (get_logger, process_identity, rank_suffix_path,
                  warn_rate_limited)

__all__ = ["snapshot", "report", "reset", "inc",
           "record_dispatch", "record_compile_key", "add_compile_seconds",
           "add_dispatch_seconds", "add_compiled_step_seconds",
           "record_fallback", "note_aval_key",
           "roofline", "diag_snapshot", "dump_diag", "main",
           "health_probe", "cluster_report", "render_cluster",
           "load_dumps", "compare", "render_compare",
           "STORM_THRESHOLD", "STORM_WARN_INTERVAL"]

STORM_THRESHOLD = int(os.environ.get(
    "MXNET_TPU_RECOMPILE_STORM_THRESHOLD", "8"))
STORM_WARN_INTERVAL = float(os.environ.get(
    "MXNET_TPU_RECOMPILE_STORM_INTERVAL", "30"))

# MXNET_TPU_DIAG also turns on dispatch wall-time collection (the
# denominator of the diag dump's achieved GB/s / GFLOP/s columns) —
# without it a DIAG-only run would dump a roofline with cost columns
# but no rates.  Import-time, like the rest of the DIAG arming.
DIAG_TIMING = bool(os.environ.get("MXNET_TPU_DIAG"))

# THE table of published per-chip peaks, keyed by jax's ``device_kind``.
# A device that is not here has no roofline: ``device_peaks`` raises and
# the headroom columns are left out — never a default.  Source: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16 (394 is the int8
# figure), 16 GB HBM2e at 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind=None):
    """Published ``{"bf16_flops", "hbm_bytes_per_s"}`` of one chip of
    ``device_kind`` (default: the first jax device's).  Raises
    ``KeyError`` for a kind the table does not list."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device_kind %r (known: %s) — a "
            "roofline needs a chip from the table in runtime_stats.py"
            % (device_kind, sorted(DEVICE_PEAKS))) from None

# recent cache keys kept per op for churn diagnosis
_STORM_KEY_WINDOW = 8
# distinct aval signatures remembered per op; saturates so a long
# profiled run with genuinely dynamic shapes cannot grow unboundedly
# (the storm warning fires at STORM_THRESHOLD, far below this cap)
_AVAL_CAP = 64

# name -> {"calls", "hits", "misses", "uncached", "fallbacks",
#          "compile_seconds"}.  Increments are plain unsynchronized
# dict read-modify-writes: no locks on the hot path by design, so
# concurrent dispatch from other threads (PS server updater, prefetch
# workers) may drop the occasional count.  Counters are exact on a
# single thread (what the tests/bench assert) and best-effort
# diagnostics under concurrency.
_PER_OP: dict = {}
# generic named counters (trainer_steps, io_batches, monitor_seconds…)
# mxlint: disable=thread-shared-state -- documented best-effort counters: plain GIL-atomic increments, exact single-threaded, approximate under concurrency
_COUNTERS: dict = {}
# name -> {"compiles", "keys", "avals", "warned"}
_STORM: dict = {}

_logger_cache = []


def _logger():
    if not _logger_cache:
        _logger_cache.append(get_logger("mxnet_tpu.runtime_stats"))
    return _logger_cache[0]


def _op_stats(name):
    s = _PER_OP.get(name)
    if s is None:
        s = _PER_OP[name] = {"calls": 0, "hits": 0, "misses": 0,
                             "uncached": 0, "fallbacks": 0,
                             "compile_seconds": 0.0,
                             "dispatch_seconds": 0.0, "timed_calls": 0}
    return s


# ------------------------------------------------------------ hot path


def record_dispatch(name, kind):
    """One op dispatch: ``kind`` is ``"hit"`` / ``"miss"`` (jit cache)
    or ``"uncached"`` (autograd vjp capture, per-call RNG keys — paths
    that bypass the static cache by design)."""
    s = _PER_OP.get(name)
    if s is None:
        s = _op_stats(name)
    s["calls"] += 1
    if kind == "hit":
        s["hits"] += 1
    elif kind == "miss":
        s["misses"] += 1
    else:
        s["uncached"] += 1


def record_compile_key(name, key):
    """Called by the op registry on every jit-cache miss with the cache
    key that missed; drives the recompile-storm detector."""
    st = _STORM.get(name)
    if st is None:
        st = _STORM[name] = {"compiles": 0, "keys": [], "avals": set(),
                             "warned": 0}
    st["compiles"] += 1
    st["keys"].append(key)
    if len(st["keys"]) > _STORM_KEY_WINDOW:
        del st["keys"][0]
    if STORM_THRESHOLD and st["compiles"] > STORM_THRESHOLD:
        _maybe_warn_storm(
            name, st,
            "compiled %d times (threshold %d); churning %s"
            % (st["compiles"], STORM_THRESHOLD,
               _describe_attr_churn(st["keys"])))


def add_compile_seconds(name, seconds):
    """Attribute compile wall-time to an op (measured by the dispatch
    layer as the duration of the jit-cache-miss call: trace + XLA
    compile dominate; execution is async-dispatched)."""
    _op_stats(name)["compile_seconds"] += seconds
    if _stepstats._state["on"]:
        _stepstats.add("compile", seconds)


def add_dispatch_seconds(name, seconds):
    """Attribute one timed dispatch's wall-time to an op.  Fed by the
    dispatch layer only while the profiler records (the timestamps exist
    for the span anyway) or ``MXNET_TPU_DIAG`` is set (DIAG_TIMING,
    which ``histogram.enable()`` also raises) — the denominator of the
    achieved GB/s / GFLOP/s columns.  Cache-warm hits only.  This is
    HOST wall-time of the dispatch call: on a synchronous backend (CPU
    tests) it tracks execution, but async device dispatch returns
    early, so the derived rates are cache-warm dispatch diagnostics,
    not physics — a measured device trace (the benchmark's ``--trace
    1`` run) stays the ground-truth instrument.  When latency histograms are on
    the sample additionally lands in the ``dispatch:warm``
    distribution."""
    s = _op_stats(name)
    s["dispatch_seconds"] += seconds
    s["timed_calls"] += 1
    if _histogram._state["on"]:
        _histogram.observe("dispatch:warm", seconds)
    if _stepstats._state["on"]:
        _stepstats.add("dispatch_warm", seconds)


def add_compiled_step_seconds(seconds):
    """Attribute one warm whole-step program call's wall-time
    (``compiled_step.py``).  The shape of :func:`add_dispatch_seconds`
    — per-op row ``compiled_step`` — but BOTH distribution feeds go to
    dedicated series (``compiled_step`` histogram, ``compiled_step``
    stepstats phase), never ``dispatch:warm``/``dispatch_warm``: the
    whole-step call IS the step's compute, and mixing seconds-long
    step samples into the sub-ms per-op dispatch distribution would
    wreck its mean/p99 and read as a dispatch regression in
    ``compare()`` when it is the opposite."""
    s = _op_stats("compiled_step")
    s["dispatch_seconds"] += seconds
    s["timed_calls"] += 1
    if _histogram._state["on"]:
        _histogram.observe("compiled_step", seconds)
    if _stepstats._state["on"]:
        _stepstats.add("compiled_step", seconds)


def record_fallback(name, kind):
    """A dispatch left the compiled path: ``"eager-trace"`` (attrs that
    fail jit staging) or ``"cross-device"`` (inputs gathered to one
    device and retried)."""
    _op_stats(name)["fallbacks"] += 1
    k = "fallback:" + kind
    _COUNTERS[k] = _COUNTERS.get(k, 0) + 1


def note_aval_key(name, aval_key):
    """Track distinct input shape/dtype signatures per op (fed by the
    dispatch layer only while the profiler runs — aval churn recompiles
    inside an existing jax.jit entry, invisible to the registry cache).
    The per-op set saturates at ``_AVAL_CAP`` signatures, so
    ``distinct_avals`` in :func:`snapshot` is exact up to the cap."""
    st = _STORM.get(name)
    if st is None:
        st = _STORM[name] = {"compiles": 0, "keys": [], "avals": set(),
                             "warned": 0}
    avals = st["avals"]
    if aval_key in avals or len(avals) >= _AVAL_CAP:
        return
    avals.add(aval_key)
    if STORM_THRESHOLD and len(avals) > STORM_THRESHOLD:
        _maybe_warn_storm(
            name, st,
            "saw %d distinct input shape/dtype signatures (threshold %d; "
            "latest: %s); churning input avals — each one compiles inside "
            "the op's jax.jit entry"
            % (len(avals), STORM_THRESHOLD, _fmt_aval(aval_key)))


def inc(name, delta=1):
    """Bump a generic named counter (int or float delta)."""
    _COUNTERS[name] = _COUNTERS.get(name, 0) + delta


def health_probe():
    """A few-dict-read counter probe for the health flight recorder's
    per-step records: compile/fallback totals plus the live/peak
    device-memory bytes.  Deliberately NOT :func:`snapshot` — this runs
    once per drained training step, so it must stay O(ops), no cost
    aggregation, no registry import."""
    misses = compiles = fallbacks = 0
    for s in list(_PER_OP.values()):
        misses += s["misses"]
        fallbacks += s["fallbacks"]
    for st in list(_STORM.values()):
        compiles += st["compiles"]
    live, peak = device_memory.live_totals()
    return {"jit_cache_misses": misses, "compiles": compiles,
            "fallbacks": fallbacks,
            "trainer_steps": _COUNTERS.get("trainer_steps", 0),
            "live_bytes": live,
            "peak_bytes": peak}


# ------------------------------------------------------- storm detector


def _maybe_warn_storm(name, st, detail):
    if warn_rate_limited(
            _logger(), "recompile-storm:" + name, STORM_WARN_INTERVAL,
            "recompile storm: op %r %s.  Every recompile stalls dispatch "
            "for a full XLA compile — hoist per-step attrs into "
            "traced_attrs or stabilize input shapes "
            "(docs/OBSERVABILITY.md).",
            name, detail):
        st["warned"] += 1


def _attr_pairs(key):
    """The (attr, value) pairs of a registry cache key, if it has the
    attr-key shape; handles both the plain and traced-attr key forms."""
    if not isinstance(key, tuple):
        return None
    if len(key) == 2 and isinstance(key[0], tuple) and \
            isinstance(key[1], tuple) and \
            all(isinstance(p, tuple) and len(p) == 2 and
                isinstance(p[0], str) for p in key[0]) and \
            all(isinstance(n, str) for n in key[1]):
        return key[0]  # traced form: ((static pairs), traced names)
    if all(isinstance(p, tuple) and len(p) == 2 and isinstance(p[0], str)
           for p in key):
        return key
    return None


def _describe_attr_churn(keys):
    seen: dict = {}
    for k in keys:
        pairs = _attr_pairs(k)
        if pairs is None:
            continue
        for a, v in pairs:
            try:
                seen.setdefault(a, set()).add(v)
            except TypeError:  # unhashable normalized value; count repr
                seen.setdefault(a, set()).add(repr(v))
    churned = sorted(a for a, vs in seen.items() if len(vs) > 1)
    if churned:
        return "attr key component(s): %s" % ", ".join(churned)
    return "cache key (attrs stable across recent keys; suspect input " \
           "avals or key structure)"


def _fmt_aval(aval_key):
    try:
        return ", ".join("%s%s" % (dt, list(sh)) for sh, dt in aval_key)
    except (TypeError, ValueError):
        return repr(aval_key)


# ---------------------------------------------------------- read side


def snapshot():
    """A consistent copy of every counter: ``{"ops": {...}, "totals":
    {...}, "counters": {...}, "storms": {...}, "memory": {...},
    "costs": {...}}``.  Works with the profiler off — this is the
    always-on view.  ``memory`` is the device-buffer tracker's view
    (``device_memory.snapshot``); ``costs`` aggregates the XLA
    cost/memory analyses captured per jit-cache entry at compile time
    (``ops.registry.cost_snapshot`` — includes the jit-cache footprint:
    entries + output/temp bytes per op)."""
    # list() the dict items first: the C-level copy is atomic under the
    # GIL, so a concurrent thread first-dispatching a new op (or the
    # SIGUSR1 handler's own timing) cannot raise "dictionary changed
    # size during iteration" mid-snapshot
    ops = {name: dict(s) for name, s in list(_PER_OP.items())}
    totals = {"op_calls": 0, "jit_cache_hits": 0, "jit_cache_misses": 0,
              "uncached_calls": 0, "fallbacks": 0, "compile_seconds": 0.0,
              "dispatch_seconds": 0.0}
    for s in ops.values():
        totals["op_calls"] += s["calls"]
        totals["jit_cache_hits"] += s["hits"]
        totals["jit_cache_misses"] += s["misses"]
        totals["uncached_calls"] += s["uncached"]
        totals["fallbacks"] += s["fallbacks"]
        totals["compile_seconds"] += s["compile_seconds"]
        totals["dispatch_seconds"] += s.get("dispatch_seconds", 0.0)
    storms = {name: {"compiles": st["compiles"], "warned": st["warned"],
                     "distinct_avals": len(st["avals"])}
              for name, st in list(_STORM.items())}
    # read-side only: the registry/health imports are lazy (both import
    # this module at their tops), and the iteration never runs on
    # dispatch.  health.snapshot() never syncs — pending device stats
    # are reported as a count.
    from . import checkpoint as _checkpoint
    from . import compiled_step as _compiled
    from . import health as _health
    from .ops import registry as _registry

    costs = _registry.cost_snapshot()
    costs.update(_compiled.cost_snapshot())
    # the serving layer is deliberately NOT imported here: a training
    # process that never served pays nothing (sys.modules read only)
    import sys as _sys

    _serving = _sys.modules.get("mxnet_tpu.serving")
    # same deliberate laziness for the symbol pass manager: reading
    # sys.modules costs nothing when no graph pass ever ran
    _passes = _sys.modules.get("mxnet_tpu.symbol.passes")
    return {"ops": ops, "totals": totals, "counters": dict(_COUNTERS),
            "graph_passes": _passes.pass_stats_snapshot()
            if _passes is not None else {},
            "storms": storms, "memory": device_memory.snapshot(),
            "costs": costs,
            "xray": _compiled.xray_snapshot(),
            "health": _health.snapshot(),
            "checkpoint": _checkpoint.snapshot(),
            "histograms": _histogram.snapshot(),
            "stepstats": _stepstats.snapshot(),
            "serving": _serving.snapshot() if _serving is not None
            else {"enabled": False},
            "requests": _reqtrace.snapshot(),
            "slo": _slo.snapshot(),
            "identity": process_identity()}


def roofline(snap=None, top=None, device_kind=None):
    """Per-op achieved GB/s and GFLOP/s vs the chip roofline, derived by
    dividing each op's cost-model bytes/flops per call by its profiled
    mean dispatch wall-time; rows sorted by headroom (µs above the
    roofline bound) descending.  Ops never profiled get cost columns
    only, and so does every op when ``device_kind`` (default: this
    process's device) is not in :data:`DEVICE_PEAKS` — an unknown chip
    has no bound.  Works on a live :func:`snapshot` or a loaded diag
    dump."""
    snap = snap or snapshot()
    try:
        peaks = device_peaks(device_kind)
    except KeyError:
        peaks = None
    rows = []
    for name, cost in sorted(snap.get("costs", {}).items()):
        row = {"op": name,
               "cache_entries": cost.get("cache_entries", 0),
               "analyzed": cost.get("analyzed", 0)}
        bpc = cost.get("bytes_per_call")
        fpc = cost.get("flops_per_call")
        if bpc is not None:
            row["bytes_per_call"] = bpc
        if fpc is not None:
            row["flops_per_call"] = fpc
        s = snap["ops"].get(name) or {}
        timed = s.get("timed_calls", 0)
        secs = s.get("dispatch_seconds", 0.0)
        if timed and secs > 0:
            per_call = secs / timed
            row["us_per_call"] = per_call * 1e6
            if bpc:
                row["achieved_gbps"] = bpc / per_call / 1e9
            if fpc:
                row["achieved_gflops"] = fpc / per_call / 1e9
            bound = peaks and max(
                (bpc or 0.0) / peaks["hbm_bytes_per_s"],
                (fpc or 0.0) / peaks["bf16_flops"])
            if bound:
                row["bound_us"] = bound * 1e6
                row["headroom_us"] = (per_call - bound) * 1e6
        rows.append(row)
    rows.sort(key=lambda r: -r.get("headroom_us", float("-inf")))
    return rows[:top] if top else rows


def report():
    """Text tables of the full snapshot: per-op dispatch counters, named
    counters, per-op XLA cost model + achieved rates, jit-cache
    footprint, and device-memory accounting.  Section headers always
    print (empty sections say why), so the output is self-describing on
    a fresh process too."""
    from . import autopilot as _autopilot

    snap = snapshot()
    # the ledger is deliberately not part of snapshot() (compare()
    # flattens snapshot sections numerically); the human report carries
    # it the way diag dumps do
    ap = _autopilot.ledger_section()
    if ap.get("enabled") or ap.get("entries"):
        snap = dict(snap)
        snap["autopilot"] = ap
    return _render(snap)


def _render(snap, top=None):
    lines = ["%-32s %9s %9s %7s %9s %10s %11s"
             % ("Op", "Calls", "Hits", "Misses", "Uncached",
                "Fallbacks", "Compile(s)")]
    for name, s in sorted(snap["ops"].items(),
                          key=lambda kv: -kv[1]["calls"]):
        lines.append("%-32s %9d %9d %7d %9d %10d %11.3f"
                     % (name[:32], s["calls"], s["hits"], s["misses"],
                        s["uncached"], s["fallbacks"], s["compile_seconds"]))
    t = snap["totals"]
    lines.append("%-32s %9d %9d %7d %9d %10d %11.3f"
                 % ("TOTAL", t["op_calls"], t["jit_cache_hits"],
                    t["jit_cache_misses"], t["uncached_calls"],
                    t["fallbacks"], t["compile_seconds"]))
    if snap["counters"]:
        lines.append("")
        lines.append("%-32s %12s" % ("Counter", "Value"))
        for name, v in sorted(snap["counters"].items()):
            lines.append("%-32s %12s"
                         % (name[:32],
                            ("%.3f" % v) if isinstance(v, float) else v))
    lines.extend(_stepstats.render(snap.get("stepstats") or {}))
    if snap.get("graph_passes"):
        lines.extend(_render_passes(snap["graph_passes"]))
    lines.extend(_render_costs(snap, top=top))
    lines.extend(_render_xray(snap.get("xray") or {}, top=top))
    lines.extend(_render_memory(snap.get("memory") or {}))
    lines.extend(_render_health(snap.get("health") or {}))
    serving = snap.get("serving") or {}
    if serving.get("enabled"):
        lines.extend(_render_serving(serving,
                                     snap.get("histograms") or {}))
    requests = snap.get("requests") or {}
    if requests.get("enabled") or requests.get("seen"):
        lines.extend(_render_requests(requests))
    slo_sec = snap.get("slo") or {}
    if slo_sec.get("enabled") or slo_sec.get("objectives"):
        lines.extend(_render_slo(slo_sec))
    ap = snap.get("autopilot") or {}
    if ap.get("enabled") or ap.get("entries"):
        lines.extend(_render_autopilot(ap))
    lines.extend(_render_hists(snap.get("histograms") or {}))
    return "\n".join(lines)


def _fmt_ms(v):
    return "-" if v is None else "%.3f" % (v * 1e3)


def _render_passes(passes):
    """Per-pass node/flops/bytes deltas recorded by the symbol pass
    manager (symbol/passes.py) — what each graph rewrite cost."""

    def _delta(before, after):
        if before is None or after is None:
            return "-"
        return "%+d" % (after - before)

    lines = ["", "Graph passes (node/flops/bytes deltas per rewrite)",
             "%-24s %5s %8s %7s %7s %12s %12s %10s"
             % ("Pass", "Runs", "Changed", "Nodes", "dNodes",
                "dFLOPs", "dBytes", "Verify(s)")]
    for name in sorted(passes):
        st = passes[name]
        lines.append("%-24s %5d %8d %7s %7s %12s %12s %10.3f"
                     % (name[:24], st.get("runs", 0), st.get("changed", 0),
                        st.get("nodes_after") if st.get("nodes_after")
                        is not None else "-",
                        _delta(st.get("nodes_before"),
                               st.get("nodes_after")),
                        _delta(st.get("flops_before"),
                               st.get("flops_after")),
                        _delta(st.get("bytes_before"),
                               st.get("bytes_after")),
                        st.get("verify_seconds", 0.0)))
    return lines


def _render_hists(hists):
    lines = ["", "Latency histograms (ms)"]
    if not hists:
        lines.append("(no histograms — histogram.enable() or "
                     "MXNET_TPU_HISTOGRAMS=1; auto-on under "
                     "MXNET_TPU_PROFILE / MXNET_TPU_DIAG)")
        return lines
    lines.append("%-32s %9s %9s %9s %9s %9s %9s"
                 % ("Name", "Count", "Mean", "p50", "p90", "p99", "Max"))
    for name in sorted(hists):
        h = hists[name]
        lines.append("%-32s %9d %9s %9s %9s %9s %9s"
                     % (name[:32], h.get("count", 0), _fmt_ms(h.get("mean")),
                        _fmt_ms(h.get("p50")), _fmt_ms(h.get("p90")),
                        _fmt_ms(h.get("p99")), _fmt_ms(h.get("max"))))
    return lines


def _render_costs(snap, top=None):
    lines = ["", "XLA cost model (per op; rates from profiled dispatch "
             "wall-time)",
             "%-28s %8s %12s %10s %9s %9s %10s"
             % ("Op", "Entries", "GFLOP/call", "MB/call", "GB/s",
                "GFLOP/s", "Headroom")]
    rows = roofline(snap, top=top)
    if not any(r.get("analyzed") for r in rows):
        lines.append("(no entries analyzed — cost capture is "
                     "compile-time-only and needs the profiler running, "
                     "MXNET_TPU_DIAG, or MXNET_TPU_COST_ANALYSIS=1)")
    for r in rows:
        if not r.get("analyzed"):
            continue
        lines.append("%-28s %8d %12s %10s %9s %9s %10s" % (
            r["op"][:28], r["cache_entries"],
            _fmt(r.get("flops_per_call"), 1e9),
            _fmt(r.get("bytes_per_call"), 1e6),
            _fmt(r.get("achieved_gbps")),
            _fmt(r.get("achieved_gflops")),
            ("%.0fus" % r["headroom_us"])
            if "headroom_us" in r else "-"))
    lines.append("")
    lines.append("Jit-cache footprint (estimated output+temp bytes per "
                 "op, summed over entries)")
    lines.append("%-28s %8s %9s %10s %10s"
                 % ("Op", "Entries", "Analyzed", "Out MB", "Temp MB"))
    foot = [(name, c) for name, c in sorted(snap.get("costs", {}).items())
            if c.get("cache_entries")]
    if not foot:
        lines.append("(jit cache empty)")
    for name, c in sorted(foot, key=lambda kv: -(
            kv[1].get("output_bytes", 0) + kv[1].get("temp_bytes", 0))):
        lines.append("%-28s %8d %9d %10s %10s" % (
            name[:28], c["cache_entries"], c.get("analyzed", 0),
            _fmt(c.get("output_bytes"), 1e6),
            _fmt(c.get("temp_bytes"), 1e6)))
    return lines


def _render_xray(xr, top=None):
    """Render the fused-step x-ray tables (newest program per label):
    per-scope flops/bytes with shares of the whole-program
    cost_analysis totals, the explicit unattributed remainder last —
    rows sum to TOTAL by the conservation contract."""
    programs = (xr or {}).get("programs") or []
    if not programs:
        return []
    newest = {}
    for t in programs:  # seq-sorted: later wins
        newest[t.get("label", "compiled_step")] = t
    lines = []
    for label, t in sorted(newest.items()):
        lines.append("")
        flags = []
        if t.get("estimated"):
            flags.append("estimated totals: no cost_analysis truth")
        if t.get("overattributed"):
            flags.append("estimates scaled to totals")
        lines.append("Fused-step x-ray: %s (%d instructions%s)"
                     % (label, t.get("instructions", 0),
                        ("; " + "; ".join(flags)) if flags else ""))
        lines.append("%-44s %10s %6s %10s %6s %9s"
                     % ("Scope", "GFLOP", "", "MB", "", "Coll MB"))
        rows = sorted(t.get("scopes", {}).items(),
                      key=lambda kv: -kv[1].get("bytes", 0.0))
        if top:
            rows = rows[:top]
        un = t.get("unattributed") or {}
        rows.append(("unattributed", un))
        for name, r in rows:
            lines.append("%-44s %10s %5.1f%% %10s %5.1f%% %9s" % (
                name[:44], _fmt(r.get("flops"), 1e9),
                100.0 * r.get("flops_share", 0.0),
                _fmt(r.get("bytes"), 1e6),
                100.0 * r.get("bytes_share", 0.0),
                _fmt(r.get("collective_bytes"), 1e6)))
        tot = t.get("totals") or {}
        lines.append("%-44s %10s %6s %10s %6s %9s" % (
            "TOTAL", _fmt(tot.get("flops"), 1e9), "",
            _fmt(tot.get("bytes_accessed"), 1e6), "", ""))
    return lines


def _render_memory(mem):
    lines = ["", "Device memory (buffer tracker)"]
    if not mem.get("enabled") and not mem.get("totals", {}).get(
            "allocations"):
        lines.append("(tracker off — device_memory.start(), "
                     "MXNET_TPU_MEMORY_TRACK=1, or MXNET_TPU_DIAG)")
        return lines
    t = mem["totals"]
    lines.append("live %s in %d buffers; peak %s; allocated %s in %d "
                 "allocations%s"
                 % (_fmt(t["live_bytes"], 1e6) + "MB", t["live_count"],
                    _fmt(t["peak_bytes"], 1e6) + "MB",
                    _fmt(t["allocated_bytes"], 1e6) + "MB",
                    t["allocations"],
                    "" if mem.get("enabled") else " (tracker stopped)"))
    lines.append("%-28s %10s %8s %10s %10s"
                 % ("Creating op", "Live MB", "Buffers", "Peak MB",
                    "Alloc MB"))
    for name, b in mem.get("per_op", {}).items():
        lines.append("%-28s %10s %8d %10s %10s" % (
            name[:28], _fmt(b["live_bytes"], 1e6), b["live_count"],
            _fmt(b["peak_bytes"], 1e6), _fmt(b["allocated_bytes"], 1e6)))
    lines.append("%-28s %10s %8s %10s %10s"
                 % ("Dtype", "Live MB", "Buffers", "Peak MB", "Alloc MB"))
    for name, b in mem.get("per_dtype", {}).items():
        lines.append("%-28s %10s %8d %10s %10s" % (
            name[:28], _fmt(b["live_bytes"], 1e6), b["live_count"],
            _fmt(b["peak_bytes"], 1e6), _fmt(b["allocated_bytes"], 1e6)))
    return lines


def _render_serving(serving, hists):
    """The "Inference serving" section of ``report()`` / diag-dump
    rendering and of ``tools/diagnose.py --serving``: totals, derived
    QPS, per-bucket occupancy, rejection counts, and the ``serve:*``
    latency percentiles from the shared histogram section."""
    lines = ["", "Inference serving (continuous batching)"]
    rej = serving.get("rejected") or {}
    lines.append("%d request(s) / %d sample(s) in %d batch(es); "
                 "buckets %s; %d bucket executable build(s); "
                 "QPS %s; mean occupancy %s; queue depth %d"
                 % (serving.get("requests", 0),
                    serving.get("samples", 0),
                    serving.get("batches", 0),
                    serving.get("buckets"),
                    serving.get("bucket_compiles", 0),
                    _fmt(serving.get("qps")),
                    _fmt(serving.get("mean_occupancy")),
                    serving.get("queue_depth", 0)))
    lines.append("rejected: %d queue-full, %d non-finite, %d bad-shape; "
                 "%d padded row(s) total"
                 % (rej.get("queue", 0), rej.get("nonfinite", 0),
                    rej.get("shape", 0), serving.get("padded_rows", 0)))
    outcomes = serving.get("outcomes") or {}
    if any(outcomes.values()):
        lines.append("outcomes: " + ", ".join(
            "%s=%d" % (k, outcomes.get(k, 0))
            for k in ("ok", "rejected_queue", "rejected_shape",
                      "rejected_nonfinite", "error")))
    per_bucket = serving.get("per_bucket") or {}
    if per_bucket:
        lines.append("%-10s %9s %9s %10s %10s"
                     % ("Bucket", "Batches", "Samples", "Occupancy",
                        "p99 ms"))
        for b in sorted(per_bucket, key=int):
            v = per_bucket[b]
            h = hists.get("serve:batch:b%s" % b) or {}
            occ = v["samples"] / (int(b) * v["batches"]) \
                if v["batches"] else 0.0
            lines.append("%-10s %9d %9d %9.0f%% %10s"
                         % (b, v["batches"], v["samples"], occ * 100,
                            _fmt_ms(h.get("p99"))))
    lat = [(name, hists[name]) for name in
           ("serve:queue_wait", "serve:batch", "serve:e2e")
           if hists.get(name)]
    for name, h in lat:
        lines.append("%-18s count %6d  mean %sms  p50 %sms  p99 %sms  "
                     "max %sms"
                     % (name, h.get("count", 0), _fmt_ms(h.get("mean")),
                        _fmt_ms(h.get("p50")), _fmt_ms(h.get("p99")),
                        _fmt_ms(h.get("max"))))
    if not lat:
        lines.append("(no serve:* latency series — histograms were off "
                     "during the run)")
    return lines


def _fmt_msv(v):
    """Format an already-in-milliseconds value (reqtrace records)."""
    return "-" if v is None else "%.2f" % v


def _render_requests(req):
    """The "Request x-ray" section of ``report()`` / diag-dump
    rendering and of ``tools/diagnose.py --requests``: sampling
    config + totals, per-outcome counts, and the slowest retained
    lifecycle records (seam-by-seam ms ladder)."""
    lines = ["", "Request x-ray (tail-sampled lifecycle ring)"]
    lines.append("%d request(s) seen: %d retained, %d dropped "
                 "(head 1-in-%d; slow >= %s, p99 x%g, rolling p99 %s)"
                 % (req.get("seen", 0), req.get("retained", 0),
                    req.get("dropped", 0), req.get("sample_n", 1),
                    ("%gms" % req["slow_ms"]) if req.get("slow_ms")
                    else "p99-rule only",
                    req.get("p99_mult", 0),
                    _fmt_msv(req.get("rolling_p99_ms")) + "ms"
                    if req.get("rolling_p99_ms") is not None else "-"))
    by = req.get("by_outcome") or {}
    if by:
        lines.append("outcomes: " + ", ".join(
            "%s=%d" % (k, by[k]) for k in sorted(by)))
    ring = req.get("ring") or []
    worst = sorted((r for r in ring if r.get("e2e_ms") is not None),
                   key=lambda r: -r["e2e_ms"])[:8]
    if not worst:
        lines.append("(lifecycle ring empty)")
        return lines
    lines.append("%-8s %-22s %6s %6s %4s %9s %9s %9s"
                 % ("Rid", "Outcome[kept]", "Bucket", "Batch", "Pad",
                    "Queue ms", "Comp ms", "E2e ms"))
    for r in worst:
        kept = r.get("retained")
        oc = str(r.get("outcome"))
        if kept and kept != oc:
            oc = "%s[%s]" % (oc, kept)
        lines.append("%-8s %-22s %6s %6s %4s %9s %9s %9s"
                     % (r.get("rid"), oc[:22],
                        r.get("bucket") if r.get("bucket") is not None
                        else "-",
                        r.get("batch") if r.get("batch") is not None
                        else "-",
                        r.get("pad_rows")
                        if r.get("pad_rows") is not None else "-",
                        _fmt_msv(r.get("queue_ms")),
                        _fmt_msv(r.get("compute_ms")),
                        _fmt_msv(r.get("e2e_ms"))))
    return lines


def _render_slo(slo):
    """The "SLO / error budgets" section of ``report()`` / diag-dump
    rendering and of ``tools/diagnose.py --slo``: per-objective
    good/bad totals, remaining error budget, and the multi-window burn
    rates the ``slo-fast-burn`` / ``slo-budget-exhausted`` doctor
    rules fire on."""
    lines = ["", "SLO / error budgets (multi-window burn rates)"]
    objs = slo.get("objectives") or []
    if not objs:
        lines.append("(no objectives — declare via "
                     "MXNET_TPU_SLO=name:25ms:99.9)")
        return lines
    scale = slo.get("window_scale", 1.0)
    if scale != 1.0:
        lines.append("(window scale %g — spans compressed)" % scale)
    for ob in objs:
        thr = "" if ob.get("threshold_ms") is None \
            else " < %gms" % ob["threshold_ms"]
        flag = " ** FAST BURN **" if ob.get("fast_burn") \
            else (" * slow burn *" if ob.get("slow_burn") else "")
        rem = ob.get("budget_remaining")
        lines.append("%s (%s%s @ %.5g%%): %d good / %d bad; error "
                     "budget remaining %s%s"
                     % (ob.get("name"), ob.get("kind"), thr,
                        (ob.get("target") or 0.0) * 100,
                        ob.get("good", 0), ob.get("bad", 0),
                        "-" if rem is None else "%.1f%%" % (rem * 100),
                        flag))
        w = ob.get("windows") or {}
        if w:
            lines.append("  burn: " + "  ".join(
                "%s=%.2f (%d ev)" % (lab, w[lab].get("burn", 0.0),
                                     w[lab].get("events", 0))
                for lab in ("5m", "1h", "30m", "6h") if lab in w))
    return lines


def _render_autopilot(ap):
    """The "Observability autopilot" section of ``report()`` / diag-dump
    rendering and of ``tools/diagnose.py --autopilot``: engine config,
    decision counters, per-reflex gates, and the action ledger
    (newest last — the append order IS the audit order)."""
    lines = ["", "Observability autopilot (gated reflexes)"]
    c = ap.get("counters") or {}
    lines.append("%s; every %s evaluation tick(s), cooldown %ss, "
                 "max %s action(s)/reflex; %d eval(s): %d fired, %d "
                 "dry-run, %d suppressed"
                 % ("enabled" if ap.get("enabled") else "disabled",
                    ap.get("interval", "?"), ap.get("cooldown_s", "?"),
                    ap.get("max_actions", "?"), c.get("evals", 0),
                    c.get("fired", 0), c.get("dry_run", 0),
                    c.get("suppressed", 0)))
    gates = ap.get("gates") or {}
    if gates:
        lines.append("gates: " + ", ".join(
            "%s=%s" % (r, gates[r]) for r in sorted(gates)))
    entries = ap.get("entries") or []
    if not entries:
        lines.append("(ledger empty — no reflex has tripped; dry-run "
                     "entries appear here too)")
        return lines
    lines.append("%-22s %8s %-10s %-20s %s"
                 % ("Rule", "Step", "Mode", "Reflex", "Action/outcome"))
    for e in entries:
        what = e.get("reason") if e.get("mode") == "suppressed" \
            else e.get("action")
        out = e.get("outcome")
        if out:
            what = "%s -> %s" % (what, out)
        lines.append("%-22s %8s %-10s %-20s %s"
                     % (str(e.get("rule"))[:22], e.get("step", "?"),
                        e.get("mode", "?"),
                        str(e.get("reflex"))[:20], what))
    return lines


def _render_health(health):
    lines = ["", "Numerics health (device-resident NaN/Inf monitor)"]
    if not health or (not health.get("enabled")
                      and not health.get("totals", {}).get("drained")):
        lines.append("(monitor off — health.enable() or "
                     "MXNET_TPU_HEALTH=1; docs/OBSERVABILITY.md)")
        return lines
    t = health.get("totals", {})
    lines.append("step %d (interval %d, stats: %s): %d observed, %d "
                 "drained, %d pending, %d dropped; %d nan-step(s), %d "
                 "inf-step(s)%s"
                 % (health.get("step", 0), health.get("interval", 1),
                    ",".join(health.get("stats", ())),
                    t.get("observed", 0), t.get("drained", 0),
                    health.get("pending", 0), t.get("dropped", 0),
                    t.get("nan_steps", 0), t.get("inf_steps", 0),
                    "" if health.get("enabled") else " (monitor off)"))
    fn = health.get("first_nan")
    if fn:
        lines.append("FIRST NON-FINITE: step %d tensor %r (%d nan, %d "
                     "inf)" % (fn.get("step", -1), fn.get("key"),
                               int(fn.get("nan_total", 0)),
                               int(fn.get("inf_total", 0))))
    ckpt = health.get("checkpoint")
    if ckpt:
        if ckpt.get("last_good_path"):
            lines.append("RESUME FROM: %s (step %s) — "
                         "checkpoint.auto_resume() restores params/"
                         "optimizer/RNG/step in one call"
                         % (ckpt["last_good_path"], ckpt.get("step")))
        else:
            lines.append("Checkpointing on (%s) but no checkpoint "
                         "committed yet" % ckpt.get("directory"))
    flight = health.get("flight") or []
    lines.append("Flight recorder (%d record(s), newest last)"
                 % len(flight))
    if flight:
        lines.append("%8s %12s %12s %8s %8s %-24s %10s"
                     % ("Step", "Loss", "GradNorm", "NaN", "Inf",
                        "FirstBad", "Misses"))
        for r in flight[-12:]:
            lines.append("%8d %12s %12s %8d %8d %-24s %10s" % (
                r.get("step", -1), _fmt(r.get("loss")),
                _fmt(r.get("grad_norm")),
                int(r.get("nan_total", 0)), int(r.get("inf_total", 0)),
                str(r.get("first_bad"))[:24],
                (r.get("counters") or {}).get("jit_cache_misses", "-")))
    return lines


def _fmt(v, scale=1.0):
    if v is None:
        return "-"
    return "%.2f" % (v / scale)


def reset():
    """Zero every counter and re-arm the storm detector (tests).

    Deliberately leaves the device-memory tracker alone — live-buffer
    accounting must survive a counter reset; use
    ``device_memory.reset()`` to drop that too.  Latency histograms
    are pure counters and reset with everything else."""
    from . import autopilot as _autopilot
    from . import metrics_timeline as _metrics_timeline
    from . import reqtrace as _reqtrace
    from . import slo as _slo
    from .log import reset_rate_limits

    _PER_OP.clear()
    _COUNTERS.clear()
    _STORM.clear()
    _histogram.reset()
    _stepstats.reset()
    _metrics_timeline.reset()
    _reqtrace.reset()
    _slo.reset()
    _autopilot.reset()
    reset_rate_limits("recompile-storm:")
    reset_rate_limits("slo:")


# ------------------------------------------------------ diagnostic dump


def diag_snapshot(top=20):
    """The full diagnostic picture as one JSON-serializable dict:
    counters snapshot (with memory + costs + latency histograms), the
    top-``top`` roofline rows, each storming op's recent cache keys
    (repr'd), and — under a distributed launch — this process's
    rank/role identity, so per-rank dumps are attributable and
    :func:`cluster_report` can merge them."""
    snap = snapshot()
    # the dump is "the full picture": swap in the UNtrimmed memory
    # breakdown (snapshot()'s default keeps report() tables short)
    snap["memory"] = device_memory.snapshot(top=None)
    storm_keys = {name: [repr(k) for k in list(st["keys"])]
                  for name, st in list(_STORM.items()) if st["keys"]}
    out = {"version": 1, "pid": os.getpid(), "time": time.time(),
           "identity": process_identity(),
           "snapshot": snap, "roofline": roofline(snap, top=top),
           "recent_storm_keys": storm_keys}
    # the recent per-step time series (metrics_timeline ring) rides
    # along like roofline/storm keys — top-level, NOT inside
    # "snapshot", so compare()'s per-section flattening never
    # double-counts the per-step metrics it already derives
    from . import metrics_timeline as _metrics_timeline

    tl = _metrics_timeline.timeline()
    if tl:
        out["timeline"] = tl
    # the autopilot's action ledger rides the same way (top-level, not
    # inside "snapshot": its entries are audit records, not numeric
    # series for compare() to flatten)
    from . import autopilot as _autopilot

    ap = _autopilot.ledger_section()
    if ap.get("enabled") or ap.get("entries"):
        out["autopilot"] = ap
    return out


# per-call temp-name sequence; next() on a C iterator is signal-atomic
_tmp_seq = itertools.count()


def dump_diag(path=None, top=20):
    """Atomically write :func:`diag_snapshot` as JSON to ``path``
    (default: ``$MXNET_TPU_DIAG`` or ``mxnet_tpu_diag.json``); returns
    the absolute path.  Write-to-temp + ``os.replace`` so a reader (or
    a second SIGUSR1) never sees a torn file; the temp name is unique
    per call (atomic counter), so a SIGUSR1 interrupting an in-progress
    dump writes its own temp file instead of truncating the outer
    one's — whichever replace lands last, the final file is whole.

    An explicit ``path`` is honored verbatim; the env/default fallback
    self-suffixes with this process's role+rank (``rank_suffix_path``)
    so a multi-rank run without launch.py's env rewriting cannot
    clobber rank 0's dump."""
    if path is None:
        path = rank_suffix_path(os.environ.get("MXNET_TPU_DIAG")
                                or "mxnet_tpu_diag.json")
    path = os.path.abspath(path)
    tmp = os.path.join(os.path.dirname(path),
                       ".%s.%d.%d.tmp" % (os.path.basename(path),
                                          os.getpid(), next(_tmp_seq)))
    with open(tmp, "w") as f:
        json.dump(diag_snapshot(top=top), f, indent=1, default=repr)
    os.replace(tmp, path)
    _maybe_push_diag(top)
    return path


def _maybe_push_diag(top):
    """``MXNET_TPU_DIAG_PUSH``: after writing the local dump, also push
    the snapshot to parameter-server shard 0 (``diag_put``) when a
    dist_async kvstore was registered via
    ``profiler.set_kvstore_handle`` — the operator can then pull every
    rank's dump from one place (``kv.cluster_diag()`` /
    ``tools/diagnose.py --cluster``) without touching worker
    filesystems.  Best-effort: a dead server must never break a diag
    dump."""
    try:
        if int(os.environ.get("MXNET_TPU_DIAG_PUSH") or 0) <= 0:
            return
    except ValueError:
        return
    try:
        from . import profiler as _prof

        kv = _prof._kvstore_handle
        if kv is not None and hasattr(kv, "push_diag"):
            kv.push_diag(top=top)
    except Exception as e:
        warn_rate_limited(
            _logger(), "diag-push", 60,
            "pushing the diag snapshot to the parameter server failed "
            "(%s: %s) — the local dump was still written",
            type(e).__name__, e)


def _install_diag_handler(path):
    """SIGUSR1 -> dump_diag(path).  Safe to call from tests; tolerates
    platforms without SIGUSR1 and non-main threads."""
    import signal

    sig = getattr(signal, "SIGUSR1", None)
    if sig is None:
        return False

    def _handler(_signum, _frame):
        try:
            dump_diag(path)
        except Exception:  # a diag request must never kill training
            _logger().exception("MXNET_TPU_DIAG dump failed")

    try:
        signal.signal(sig, _handler)
    except ValueError:  # not the main thread
        return False
    return True


# the env-armed atexit dump can be disarmed by pure-reader processes
# (the CLI / diagnose.py): a reader inheriting MXNET_TPU_DIAG from the
# shell must not overwrite the training run's dump with its own empty
# snapshot on exit
_DIAG_STATE = {"armed": True}


def _dump_diag_at_exit(path):
    if not _DIAG_STATE["armed"]:
        return
    try:
        dump_diag(path)
    except Exception:
        pass


def _activate_diag_from_env():
    """``MXNET_TPU_DIAG=<file>``: arm SIGUSR1 and dump at exit — ask a
    live run for its roofline/memory picture with ``kill -USR1 <pid>``
    (docs/OBSERVABILITY.md).  The same env turns on the device-memory
    tracker (device_memory.py) and compile-time cost capture
    (ops/registry.py) so the dump has data."""
    path = os.environ.get("MXNET_TPU_DIAG")
    if not path:
        return False
    import atexit

    # the same self-suffix dump_diag's env fallback applies: the armed
    # handlers must write the per-rank file, not rank 0's
    path = rank_suffix_path(path)
    _install_diag_handler(path)
    atexit.register(_dump_diag_at_exit, path)
    return True


_activate_diag_from_env()
# deferred from histogram.py's / stepstats.py's import (their enable()
# writes this module's DIAG_TIMING, so arming must wait until the
# global exists)
_histogram._activate_from_env()
_stepstats._activate_from_env()
# the metrics timeline is imported here (bottom of module: everything
# it lazily reads exists) and armed after stepstats/histograms — its
# enable() raises their state too
from . import metrics_timeline as _metrics_timeline  # noqa: E402

_metrics_timeline._activate_from_env()
# fused-step x-ray kill switch (MXNET_TPU_XRAY=0) and hang-forensics
# stack dumps (MXNET_TPU_STACKDUMP=<file> arms SIGUSR2) join the same
# import-time activation chain
from . import stackdump as _stackdump  # noqa: E402
from . import xray as _xray  # noqa: E402

_xray._activate_from_env()
_stackdump._activate_from_env()
# the request x-ray (MXNET_TPU_REQTRACE) and the SLO / error-budget
# layer (MXNET_TPU_SLO) arm before the autopilot below: its SLO reflex
# reads the burn verdicts these produce
from . import reqtrace as _reqtrace  # noqa: E402
from . import slo as _slo  # noqa: E402

_reqtrace._activate_from_env()
_slo._activate_from_env()
# the observability autopilot (MXNET_TPU_AUTOPILOT=1) arms last: its
# reflexes read every layer raised above
from . import autopilot as _autopilot  # noqa: E402

_autopilot._activate_from_env()


# -------------------------------------------------- cluster aggregation


# the latency metrics the cluster report tables and skew analysis read
# out of each rank's histogram section, in straggler-priority order
_CLUSTER_METRICS = ("kv:push_rtt", "kv:pull_rtt", "trainer:step",
                    "io:next_batch")


def load_dumps(paths):
    """Load diag dumps for :func:`cluster_report`; a directory expands
    to the ``*.json`` files inside it (sorted).  Each dump dict gains a
    ``_path`` key for attribution in the rendered report.  A metrics
    JSONL file (``MXNET_TPU_METRICS``) or a bare JSON sample array
    loads as a timeline-only dump (``{"timeline": {"samples": ...}}``)
    so the CLI and the perf doctor take both kinds."""
    import glob

    from . import metrics_timeline as _metrics_timeline

    files = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(os.path.join(p, "*.json"))))
        else:
            files.append(p)
    dumps = []
    for f in files:
        with open(f) as fh:
            text = fh.read()
        # the shared sniffer: JSONL / sample-array / one-line-sample
        # files become timeline-only dumps; corrupt content raises
        # instead of rendering as an empty (finding-free) dump
        kind, d = _metrics_timeline.sniff_text(text, path=f)
        if kind == "timeline":
            d = {"timeline": d}
        d["_path"] = f
        dumps.append(d)
    return dumps


def _rank_key(ident, fallback):
    if not ident:
        return fallback
    return "%s %s" % (ident.get("role", "?"), ident.get("rank", "?"))


def cluster_report(dumps):
    """Merge per-rank diag dumps into one cluster view.

    Returns ``{"ranks": [...], "merged": {...}, "skews": [...],
    "straggler": {...}|None}``: a per-rank row (identity, step/push
    counters, per-metric p50/p99), cluster-wide merged histograms
    (associative bucket merge), per-metric skew — the slowest rank and
    its p99 / median-p99 ratio — and the overall straggler callout (the
    highest-ratio metric, push RTT first in ties by priority order).
    Works on loaded dump dicts (:func:`load_dumps`) or raw snapshots."""
    ranks = []
    for i, d in enumerate(dumps):
        snap = d.get("snapshot", d)
        ident = d.get("identity") or snap.get("identity")
        counters = snap.get("counters") or {}
        ranks.append({
            "key": _rank_key(ident, d.get("_path", "rank%d" % i)),
            "identity": ident, "pid": d.get("pid"),
            "path": d.get("_path"),
            "steps": counters.get("trainer_steps", 0),
            "pushes": counters.get("kvstore_pushes", 0),
            "pulls": counters.get("kvstore_pulls", 0),
            "retries": counters.get("kvstore_retries", 0),
            "time": d.get("time") or 0,
            "hists": snap.get("histograms") or {}})
    # a dump directory may hold several generations of one rank's dump;
    # keep only the newest per key — duplicate keys would make
    # median_of_others exclude that rank twice and inflate the
    # straggler ratio
    newest: dict = {}
    for r in ranks:
        if r["key"] not in newest or r["time"] >= newest[r["key"]]["time"]:
            newest[r["key"]] = r
    ranks = list(newest.values())
    names = set()
    for r in ranks:
        names.update(r["hists"])
    merged = {n: _histogram.merge_snapshots(
        [r["hists"][n] for r in ranks if n in r["hists"]])
        for n in sorted(names)}
    skews = []
    for metric in _CLUSTER_METRICS:
        rows = [(r, r["hists"][metric]) for r in ranks
                if r["hists"].get(metric, {}).get("p99") is not None]
        if len(rows) < 2:
            continue
        worst_rank, worst = max(rows, key=lambda rh: rh[1]["p99"])
        # worst vs the median of the OTHER ranks (see
        # histogram.median_of_others for why not the full median)
        med = _histogram.median_of_others(
            [(r["key"], h["p99"]) for r, h in rows], worst_rank["key"])
        skews.append({"metric": metric, "rank": worst_rank["key"],
                      "p50": worst["p50"], "p99": worst["p99"],
                      "median_p99": med,
                      "ratio": (worst["p99"] / med) if med else
                      float("inf")})
    straggler = max(skews, key=lambda s: s["ratio"]) if skews else None
    return {"ranks": ranks, "merged": merged, "skews": skews,
            "straggler": straggler}


def render_cluster(report):
    """Text tables for a :func:`cluster_report` result."""
    ranks = report["ranks"]
    lines = ["Cluster telemetry (%d rank dump(s))" % len(ranks),
             "%-14s %7s %7s %7s %7s %10s %10s %10s %10s"
             % ("Rank", "Steps", "Pushes", "Pulls", "Retries",
                "Push p50", "Push p99", "Step p50", "Step p99")]
    for r in sorted(ranks, key=lambda r: r["key"]):
        push = r["hists"].get("kv:push_rtt") or {}
        step = r["hists"].get("trainer:step") or {}
        lines.append("%-14s %7d %7d %7d %7d %10s %10s %10s %10s"
                     % (r["key"][:14], r["steps"], r["pushes"], r["pulls"],
                        r["retries"], _fmt_ms(push.get("p50")),
                        _fmt_ms(push.get("p99")), _fmt_ms(step.get("p50")),
                        _fmt_ms(step.get("p99"))))
    for s in report["skews"]:
        lines.append("skew %-14s slowest %-12s p50 %sms p99 %sms = "
                     "%.2fx the other ranks' median p99 (%sms)"
                     % (s["metric"], s["rank"], _fmt_ms(s["p50"]),
                        _fmt_ms(s["p99"]), s["ratio"],
                        _fmt_ms(s["median_p99"])))
    st = report["straggler"]
    if st is not None and st["ratio"] > _histogram.STRAGGLER_RATIO:
        lines.append("STRAGGLER: %s — %s p99 %sms is %.2fx the other "
                     "ranks' median p99 (%sms); investigate that "
                     "process/host (docs/OBSERVABILITY.md 'Distributed "
                     "telemetry')"
                     % (st["rank"], st["metric"], _fmt_ms(st["p99"]),
                        st["ratio"], _fmt_ms(st["median_p99"])))
    elif st is not None:
        lines.append("slowest rank: %s (%s p99 %sms, %.2fx median — "
                     "within the straggler threshold %.1fx)"
                     % (st["rank"], st["metric"], _fmt_ms(st["p99"]),
                        st["ratio"], _histogram.STRAGGLER_RATIO))
    else:
        lines.append("(no shared latency metric across >=2 dumps — "
                     "run workers with MXNET_TPU_HISTOGRAMS=1)")
    hist_lines = _render_hists(report["merged"])
    hist_lines[1] = "Merged latency histograms — all ranks (ms)"
    lines.extend(hist_lines)
    return "\n".join(lines)


# ------------------------------------------------- dump-diff regression


def _steps_of(snap):
    """Step count of a snapshot: stepstats windows when present, else
    the trainer_steps counter — the per-step normalizer that makes two
    runs of different lengths comparable."""
    ss = snap.get("stepstats") or {}
    if ss.get("steps"):
        return ss["steps"]
    return (snap.get("counters") or {}).get("trainer_steps", 0)


def _comparable_metrics(dump, min_seconds):
    """Flatten one diag dump (or raw snapshot) into ``{metric: (value,
    unit, kind)}`` rows for :func:`compare` — every metric oriented so
    that UP means WORSE.  Time-like metrics below ``min_seconds`` in
    total are dropped (sub-noise phases must not produce findings)."""
    snap = dump.get("snapshot", dump)
    steps = _steps_of(snap)
    out = {}
    # step anatomy: per-step mean ms per phase (+ wall + remainder)
    ss = snap.get("stepstats") or {}
    if ss.get("steps"):
        n = ss["steps"]

        def _phase_row(name, h, kind):
            total = (h or {}).get("sum") or 0.0
            if total >= min_seconds:
                out[name] = (total / n * 1e3, "ms/step", kind)

        _phase_row("step_wall", ss.get("wall"), "wall")
        for p, h in (ss.get("phases") or {}).items():
            _phase_row("phase:%s" % p, h, "phase")
        _phase_row("phase:unattributed", ss.get("unattributed"), "phase")
    # latency histograms: mean + p99 per series
    for name, h in (snap.get("histograms") or {}).items():
        if (h.get("sum") or 0.0) < min_seconds:
            continue
        if h.get("mean") is not None:
            out["hist:%s mean" % name] = (h["mean"] * 1e3, "ms", "histogram")
        if h.get("p99") is not None:
            out["hist:%s p99" % name] = (h["p99"] * 1e3, "ms", "histogram")
    # per-op cache-warm dispatch rate (the roofline denominator)
    for name, s in (snap.get("ops") or {}).items():
        timed = s.get("timed_calls", 0)
        secs = s.get("dispatch_seconds", 0.0)
        if timed and secs >= min_seconds:
            out["op:%s us/call" % name] = (secs / timed * 1e6, "us",
                                           "op")
    # cost counters, normalized per step when a step clock exists
    totals = snap.get("totals") or {}
    counters = snap.get("counters") or {}
    for key, label in (("compile_seconds", "s"),):
        v = totals.get(key)
        if v:
            out["total:%s" % key] = (v / steps if steps else v,
                                     label + ("/step" if steps else ""),
                                     "counter")
    for key in ("jit_cache_misses", "fallbacks"):
        v = totals.get(key, 0)
        if v:
            out["total:%s" % key] = (v / steps if steps else v,
                                     "/step" if steps else "count",
                                     "counter")
    for key in ("kvstore_retries", "kvstore_dup_suppressed",
                "kvstore_dead_shard_warnings", "health_seconds",
                "monitor_seconds", "serve_rejected"):
        v = counters.get(key, 0)
        # the *_seconds counters are time-like: below the noise floor
        # they are pure clock jitter, not a verdict-worthy signal
        if key.endswith("_seconds") and v < min_seconds:
            continue
        if v:
            out["counter:%s" % key] = (v / steps if steps else v,
                                       "/step" if steps else "count",
                                       "counter")
    # ZeRO weight-update sharding collective traffic, per zero step
    # (parallel/gluon_step.py counters).  kind "zero" gets special
    # treatment in compare(): one-sided presence (an eager-vs-zero or
    # dp-vs-zero A/B) is a topology CHANGE, not a regression — those
    # rows land in "notes", never in the verdict.
    zsteps = counters.get("zero_steps", 0)
    if zsteps:
        for key in ("zero_allgather_bytes", "zero_reduce_bytes"):
            v = counters.get(key, 0)
            if v:
                out["zero:%s" % key] = (v / zsteps / 1e6, "MB/step",
                                        "zero")
    # fused-step x-ray: the newest program's per-scope share of whole-
    # program bytes, oriented up-is-worse (a targeted perf PR drives
    # its region's share DOWN).  kind "xray" shares the "zero" rule in
    # compare(): a scope present on only one side is a model/topology
    # change — a note, never a verdict.  Sub-percent scopes are noise.
    xprogs = ((snap.get("xray") or {}).get("programs")) or []
    xnewest = {}
    for t in xprogs:  # seq-sorted: later wins
        xnewest[t.get("label", "compiled_step")] = t
    for label, t in sorted(xnewest.items()):
        rows = dict(t.get("scopes") or {})
        rows["unattributed"] = t.get("unattributed") or {}
        for scope, rec in rows.items():
            share = rec.get("bytes_share") or 0.0
            if share >= 0.01:
                out["xray:%s:%s bytes_share" % (label, scope)] = (
                    share * 100.0, "%", "xray")
    # symbol graph passes: post-rewrite whole-graph flops/bytes (XLA
    # cost analysis, recorded when a PassContext opts into
    # measure_cost).  kind "graphpass" shares the "zero"/"xray" rule in
    # compare(): a pass run on only one side (an f32-vs-AMP A/B) is a
    # program change worth noting, never a perf verdict by itself.
    for pname, st in (snap.get("graph_passes") or {}).items():
        for key, unit, scale in (("flops_after", "GFLOP", 1e9),
                                 ("bytes_after", "MB", 1e6)):
            v = st.get(key)
            if v:
                out["graphpass:%s %s" % (pname, key)] = (
                    v / scale, unit, "graphpass")
    # device-memory peak
    peak = ((snap.get("memory") or {}).get("totals") or {}).get(
        "peak_bytes", 0)
    if peak:
        out["memory:peak_bytes"] = (peak / 1e6, "MB", "memory")
    # serving throughput, oriented up-is-worse (ms per served sample):
    # a QPS regression between two load runs fails --compare like any
    # latency regression (the serve:* histogram rows above carry the
    # percentile side)
    serving = snap.get("serving") or {}
    qps = serving.get("qps")
    if qps:
        out["serving:ms_per_sample"] = (1e3 / qps, "ms", "serving")
    # SLO error budget, oriented up-is-worse as the BURNED fraction
    # (100% = budget exhausted).  kind "slo" shares the one-sided rule
    # with "zero"/"xray": an objective declared on only one side is a
    # config change — a note, never a perf verdict.
    for ob in ((snap.get("slo") or {}).get("objectives")) or []:
        if not ob.get("total"):
            continue
        rem = ob.get("budget_remaining")
        burned = 1.0 - (rem if rem is not None else 1.0)
        out["slo:%s budget_burned" % ob.get("name")] = (
            burned * 100.0, "%", "slo")
    return out


def compare(a, b, threshold=0.2, min_seconds=1e-3):
    """Diff two diag dumps (baseline ``a`` vs candidate ``b``) into a
    machine-readable verdict — the one-command before/after of a perf
    PR (``tools/diagnose.py --compare A B``).

    Every comparable metric (step-anatomy phase means, latency-histogram
    mean/p99, per-op warm-dispatch rates, per-step compile/miss/fallback
    counters, device-memory peak) is oriented so UP means WORSE; a
    metric whose relative change exceeds ``threshold`` lands in
    ``regressions`` (worse) or ``improvements`` (better).  Metrics whose
    summed time stays under ``min_seconds`` on both sides are ignored —
    sub-noise phases must not page anyone.  Identical dumps compare
    flat (zero findings) by construction.

    Returns ``{"verdict": "regression"|"improvement"|"flat",
    "regressions": [...], "improvements": [...], "compared": N,
    "threshold": ..., "a"/"b": {"path", "steps"}}`` with each finding
    ``{"metric", "kind", "unit", "before", "after", "ratio"}`` sorted
    worst-first."""
    # significance (which metrics are worth a verdict) comes from the
    # floored collection; VALUES come from an unfloored pass — a metric
    # straddling the floor (just under on one side, just over on the
    # other) must compare its real small values (ratio ~1), not read
    # as 0 -> infinity.  A genuinely new cost still reads as 0 -> inf.
    ma = _comparable_metrics(a, min_seconds)
    mb = _comparable_metrics(b, min_seconds)
    ma_all = _comparable_metrics(a, 0.0)
    mb_all = _comparable_metrics(b, 0.0)
    regressions, improvements, notes = [], [], []
    compared = 0
    for metric in sorted(set(ma) | set(mb)):
        va = ma_all.get(metric) or ma.get(metric)
        vb = mb_all.get(metric) or mb.get(metric)
        before = va[0] if va else 0.0
        after = vb[0] if vb else 0.0
        unit, kind = (vb or va)[1], (vb or va)[2]
        compared += 1
        if before <= 0.0 and after <= 0.0:
            continue
        ratio = (after / before) if before > 0.0 else float("inf")
        entry = {"metric": metric, "kind": kind, "unit": unit,
                 "before": before, "after": after, "ratio": ratio}
        if kind in ("zero", "xray", "graphpass", "slo") \
                and (va is None or vb is None):
            # collective-bytes counters, x-ray scopes or graph-pass
            # costs existing on only one side mean the two runs used
            # different sharding topologies / model structures /
            # rewrite pipelines — worth surfacing, but 0 -> N is a
            # change of shape, not a performance verdict
            entry["side"] = "after-only" if va is None else "before-only"
            notes.append(entry)
            continue
        if ratio > 1.0 + threshold:
            regressions.append(entry)
        elif ratio < 1.0 - threshold:
            improvements.append(entry)
    regressions.sort(key=lambda e: -e["ratio"])
    improvements.sort(key=lambda e: e["ratio"])
    verdict = ("regression" if regressions else
               "improvement" if improvements else "flat")
    return {"verdict": verdict, "threshold": threshold,
            "min_seconds": min_seconds, "compared": compared,
            "regressions": regressions, "improvements": improvements,
            "notes": notes,
            "a": {"path": a.get("_path"),
                  "steps": _steps_of(a.get("snapshot", a))},
            "b": {"path": b.get("_path"),
                  "steps": _steps_of(b.get("snapshot", b))}}


def render_compare(result):
    """Text report for a :func:`compare` result."""
    lines = ["Dump diff: %s -> %s (threshold %.0f%%, %d metric(s) "
             "compared)"
             % (result["a"]["path"] or "A", result["b"]["path"] or "B",
                result["threshold"] * 100, result["compared"])]

    def _rows(title, entries):
        if not entries:
            return
        lines.append(title)
        lines.append("  %-44s %12s %12s %8s"
                     % ("Metric", "Before", "After", "Change"))
        for e in entries:
            change = ("+inf" if e["ratio"] == float("inf")
                      else "%+.0f%%" % ((e["ratio"] - 1.0) * 100))
            lines.append("  %-44s %12.3f %12.3f %8s  (%s)"
                         % (e["metric"][:44], e["before"], e["after"],
                            change, e["unit"]))

    _rows("REGRESSIONS (worse in B)", result["regressions"])
    _rows("improvements (better in B)", result["improvements"])
    for e in result.get("notes", []):
        why = ("the traced model/step structure differs between the "
               "dumps" if e.get("kind") == "xray" else
               "the declared SLO objectives differ between the dumps"
               if e.get("kind") == "slo" else
               "sharding topology differs between the dumps")
        lines.append("  note: %s present %s (%.3f -> %.3f %s) — %s"
                     % (e["metric"], e.get("side", "one-sided"),
                        e["before"], e["after"], e["unit"], why))
    if not result["regressions"] and not result["improvements"]:
        lines.append("no change past the threshold — dumps are "
                     "performance-equivalent")
    lines.append("VERDICT: %s" % result["verdict"])
    return "\n".join(lines)


# ---------------------------------------------------------------- CLI


def main(argv=None):
    """``python -m mxnet_tpu.runtime_stats [dump.json ...]`` —
    pretty-print a diag dump, this process's live counters when no file
    is given (useful at a debugger prompt / fresh REPL), or — given
    SEVERAL per-rank dumps (or a directory of them) — the merged
    cluster report with the straggler callout."""
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.runtime_stats",
        description="Pretty-print runtime telemetry: a MXNET_TPU_DIAG "
                    "JSON dump (several merge into a cluster report), "
                    "or the current process's counters.")
    p.add_argument("dump", nargs="*", default=None,
                   help="diag dump(s) written by dump_diag() / SIGUSR1 "
                        "(a directory expands to its *.json); two or "
                        "more render the merged cluster report; omit "
                        "for the live in-process view")
    p.add_argument("--top", type=int, default=20,
                   help="roofline rows to show from a dump")
    args = p.parse_args(argv)
    # under `python -m` THIS file is the __main__ module while the
    # framework counts into the canonical `mxnet_tpu.runtime_stats`
    # import — always render through the canonical module
    from mxnet_tpu import runtime_stats as _canonical

    # this process is a READER: never let an inherited MXNET_TPU_DIAG
    # overwrite the dump it came to display (both module copies may
    # have armed an atexit hook under `python -m`)
    _DIAG_STATE["armed"] = False
    _canonical._DIAG_STATE["armed"] = False

    if not args.dump:
        print(_canonical.report())
        return 0
    try:
        dumps = _canonical.load_dumps(args.dump)
    except ValueError as e:
        print("error: %s" % e, file=sys.stderr)
        return 2
    if not dumps:
        # a directory argument can expand to zero *.json files
        print("no diag dumps found in: %s" % " ".join(args.dump),
              file=sys.stderr)
        return 2
    if len(dumps) > 1:
        print(_canonical.render_cluster(_canonical.cluster_report(dumps)))
        return 0
    data = dumps[0]
    ident = data.get("identity")
    if ident:
        print("diag dump from %s %s (pid %s)"
              % (ident.get("role", "?"), ident.get("rank", "?"),
                 data.get("pid", "?")))
    snap = data.get("snapshot", data)
    tl = data.get("timeline")
    tl_samples = (tl.get("samples") if isinstance(tl, dict) else tl) \
        if tl else None
    if "ops" not in snap:
        if tl_samples:
            # a metrics JSONL file / timeline-only dump: just the series
            from mxnet_tpu import metrics_timeline as _mt

            print(_mt.render(tl_samples))
            return 0
        # standalone flight-recorder dump (health.dump_flight / the
        # first-NaN auto-dump): render just the numerics section
        health = data.get("health") or snap.get("health") or {}
        if data.get("reason"):
            print("flight-recorder dump (reason: %s, pid %s)"
                  % (data["reason"], data.get("pid", "?")))
        print("\n".join(_canonical._render_health(health)))
        return 0
    # the action ledger rides the dump top-level (like the timeline):
    # merge it into the rendered view so the audit trail prints too
    ap = data.get("autopilot")
    if ap and "autopilot" not in snap:
        snap = dict(snap)
        snap["autopilot"] = ap
    print(_canonical._render(snap, top=args.top))
    storms = data.get("recent_storm_keys") or {}
    print()
    print("Recent storm keys")
    if not storms:
        print("(no recompile storms recorded)")
    for name, keys in sorted(storms.items()):
        print("%-28s %s" % (name[:28], "; ".join(keys[-3:])))
    if tl_samples:
        from mxnet_tpu import metrics_timeline as _mt

        print()
        print(_mt.render(tl_samples))
    return 0


if __name__ == "__main__":
    import sys

    try:
        sys.exit(main())
    except BrokenPipeError:  # `... | head` closed the pipe: fine
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
