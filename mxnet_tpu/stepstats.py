"""Step-time attribution — where each training step's wall time goes.

``profiler.py`` records raw spans and ``histogram.py`` records raw
latency distributions; neither answers the first question of every perf
investigation: *which phase of the step is the time in?*  This module
decomposes the wall time between consecutive ``Trainer.step`` returns
(one full iteration: data wait + forward/backward + reduce + update)
into the canonical phases below, with an explicit **unattributed
remainder** — so the breakdown always sums to the step wall time and
never silently over-claims (arXiv:2301.13062's fusion/idle-gap lens,
applied host-side).

Phases (:data:`PHASES`; shared vocabulary with ``tools/diagnose.py
--doctor`` — same names, ms units):

- ``data_wait``        ``DataIter.__next__`` (batch assembly / input wait)
- ``forward``          the ``autograd.record()`` region / symbolic
  ``executor:forward`` (exclusive of nested dispatch/compile feeds)
- ``backward``         ``autograd.backward`` / ``executor:backward``
- ``dispatch_warm``    cache-warm op dispatch wall time
- ``compile``          jit-cache-miss wall time (trace + XLA compile)
- ``compiled_step``    the one warm whole-step program call
  (``compiled_step.py``: fused fwd+bwd+update; its build/compile time
  lands in ``compile``)
- ``kvstore``          allreduce / kvstore push+pull (incl. dist RTT)
- ``optimizer_update`` worker-side optimizer update
- ``checkpoint_write`` in-step checkpoint snapshot (the async capture,
  or the full write in ``MXNET_TPU_CKPT_ASYNC=0`` mode)
- ``health_drain``     numerics-health queue drain (the layer's one sync)

Leaf phases accumulate measured durations directly (``compiled_step``
is a leaf: the one warm whole-step call, timed by ``compiled_step.py``
whenever dispatch timing is on); container phases
(``forward``, ``backward``, ``kvstore``, ``optimizer_update``,
``data_wait``, ``checkpoint_write``)
record their wall time **exclusive** of any attribution that landed
inside their window (:func:`begin`/:func:`end` snapshot the running
attributed total), so a warm op dispatch inside an allreduce is counted
once, under ``dispatch_warm`` — phase sums stay disjoint and their
total can never exceed the step wall.

Collection contract matches ``runtime_stats``/``histogram``: all
mutation is GIL-atomic dict arithmetic on the training thread, feeding
sites guard on ``_state["on"]`` *before* taking timestamps, and the
disabled path is one dict read (bench-gated in
``tests/test_bench_gate.py``).  Counts are exact for the reference
single-training-thread loop and best-effort under concurrency.

Per-phase per-step values land in private ``histogram.Histogram``
instances, so :func:`snapshot` carries full distributions (p50/p90/p99)
that merge associatively — ``runtime_stats.compare`` diffs them between
two diag dumps and the perf doctor ranks bottlenecks from the shares.

Environment variables
---------------------
``MXNET_TPU_STEPSTATS``  ``1`` enables attribution from import, ``0``
    forces it off; unset, it auto-enables when ``MXNET_TPU_PROFILE`` or
    ``MXNET_TPU_DIAG`` is set (those runs already pay for timestamps,
    and the diag dump should carry a populated "Step anatomy").
"""

from __future__ import annotations

import os
import time

from .histogram import Histogram

__all__ = ["PHASES", "PHASE_LABELS", "enable", "disable", "is_enabled",
           "add", "begin", "end", "end_step", "snapshot", "anatomy",
           "device_anatomy_ms", "render", "reset"]

# canonical phase vocabulary, in render order.  The perf doctor
# (tools/diagnose.py --doctor) and runtime_stats.compare both name
# phases from this table so a finding and a diff row agree on names
# and units.
PHASES = ("data_wait", "forward", "backward", "dispatch_warm", "compile",
          "compiled_step", "kvstore", "optimizer_update",
          "checkpoint_write", "health_drain")

PHASE_LABELS = {
    "data_wait": "data wait (io:next_batch)",
    "forward": "forward (autograd:record)",
    "backward": "backward (autograd:backward)",
    "dispatch_warm": "warm dispatch",
    "compile": "compile (jit-cache miss)",
    "compiled_step": "compiled whole-step call",
    "kvstore": "allreduce / kvstore",
    "optimizer_update": "optimizer update",
    "checkpoint_write": "checkpoint snapshot",
    "health_drain": "health drain",
    # device-trace phases (device_anatomy_ms)
    "device_compute": "device compute (HLO)",
    "hbm_prefetch": "HBM prefetch (overlapped)",
    "unattributed": "unattributed remainder",
}

_state = {"on": False}
# phase -> seconds accumulated since the last step boundary
_window: dict = {}
# "attr": total attributed seconds in the current window (what
# containers subtract); "boundary": perf_counter of the last step end
_cur = {"attr": 0.0, "boundary": None}
# "steps": closed step windows; "overattributed": windows whose
# attribution exceeded the measured wall (clock noise / cross-thread
# feeds) — remainder clamped to 0 and the event counted, never hidden
# mxlint: disable=thread-shared-state -- single-writer by contract: end_step runs on the training thread between steps
_agg = {"steps": 0, "overattributed": 0, "last": None}
# per-phase per-step distributions + "wall" + "unattributed"
_HISTS: dict = {}

_perf_counter = time.perf_counter


def enable():
    """Turn attribution on; also raises the dispatch layer's cache-warm
    timing flag (``runtime_stats.DIAG_TIMING``) so the ``dispatch_warm``
    and ``compile`` phases have a feed without the profiler running."""
    _state["on"] = True
    from . import runtime_stats as _rts

    _rts.DIAG_TIMING = True


def disable():
    """Turn attribution off (accumulated anatomy is kept; ``reset()``
    drops it).  Dispatch timing reverts to its env/histogram-derived
    state."""
    _state["on"] = False
    from . import histogram as _histogram
    from . import runtime_stats as _rts

    _rts.DIAG_TIMING = bool(os.environ.get("MXNET_TPU_DIAG")) \
        or _histogram._state["on"]


def is_enabled():
    return _state["on"]


# ------------------------------------------------------------ hot path


def add(phase, seconds):
    """Leaf feed: attribute ``seconds`` of the current step window to
    ``phase``.  Callers guard on ``_state["on"]`` before taking their
    timestamps; this re-check makes a mid-window disable safe."""
    if not _state["on"]:
        return
    _window[phase] = _window.get(phase, 0.0) + seconds
    _cur["attr"] += seconds


def begin():
    """Open a container-phase window: returns an opaque token for
    :func:`end`.  Container phases record their wall time exclusive of
    everything attributed inside them (nested leaf/container feeds), so
    phase sums stay disjoint."""
    return (_perf_counter(), _cur["attr"])


def end(phase, token):
    """Close a container-phase window opened by :func:`begin`."""
    if not _state["on"] or token is None:
        return
    wall = _perf_counter() - token[0]
    nested = _cur["attr"] - token[1]
    excl = wall - nested
    if excl > 0.0:
        _window[phase] = _window.get(phase, 0.0) + excl
        _cur["attr"] += excl


def _hist(name):
    h = _HISTS.get(name)
    if h is None:
        h = _HISTS[name] = Histogram()
    return h


def end_step():
    """Close the current step window (called by ``Trainer.step`` after
    the checkpoint hook).  The first boundary only arms the clock — the
    partial warmup window before it (model init, first compiles before
    any step completed) is discarded, so every recorded window spans
    exactly one full iteration."""
    if not _state["on"]:
        return
    now = _perf_counter()
    boundary = _cur["boundary"]
    _cur["boundary"] = now
    window = dict(_window)
    _window.clear()
    _cur["attr"] = 0.0
    if boundary is None:
        return
    wall = now - boundary
    attributed = sum(window.values())
    remainder = wall - attributed
    if remainder < 0.0:
        _agg["overattributed"] += 1
        remainder = 0.0
    _agg["steps"] += 1
    _hist("wall").observe(wall)
    for p in PHASES:
        _hist(p).observe(window.get(p, 0.0))
    _hist("unattributed").observe(remainder)
    last = {"wall": wall, "unattributed": remainder}
    last.update(window)
    _agg["last"] = last


# ----------------------------------------------------------- read side


def snapshot():
    """JSON-ready view: ``{"enabled", "steps", "overattributed",
    "wall": hist, "phases": {phase: hist}, "unattributed": hist,
    "last": {...}}`` (histogram snapshots merge associatively — the
    dump-diff and cluster machinery rely on it).  Empty when no step
    window has closed yet."""
    out = {"enabled": _state["on"], "steps": _agg["steps"],
           "overattributed": _agg["overattributed"]}
    if _agg["steps"]:
        out["wall"] = _hist("wall").snapshot()
        out["phases"] = {p: _HISTS[p].snapshot()
                         for p in PHASES if p in _HISTS}
        out["unattributed"] = _hist("unattributed").snapshot()
        if _agg["last"] is not None:
            out["last"] = dict(_agg["last"])
    return out


def _ms(v):
    return None if v is None else v * 1e3


def anatomy(snap=None):
    """Derived per-step anatomy from a :func:`snapshot` (live when
    omitted): ``{"steps", "step_wall_ms": {mean,p50,p99,sum},
    "phases": {phase: {mean_ms,p50_ms,p99_ms,share}},
    "unattributed": {...}}`` where ``share`` is the phase's fraction of
    the summed step wall time.  The shared currency of ``report()``'s
    "Step anatomy" table, the perf doctor's ranking, and
    ``runtime_stats.compare``."""
    snap = snapshot() if snap is None else snap
    steps = snap.get("steps", 0)
    if not steps:
        return {"steps": 0, "phases": {}}
    wall = snap.get("wall") or {}
    wall_sum = wall.get("sum") or 0.0

    def _derive(h):
        total = h.get("sum") or 0.0
        return {"mean_ms": _ms(h.get("mean")), "p50_ms": _ms(h.get("p50")),
                "p99_ms": _ms(h.get("p99")), "sum_ms": _ms(total),
                "share": (total / wall_sum) if wall_sum else 0.0}

    phases = {p: _derive(h)
              for p, h in (snap.get("phases") or {}).items()}
    return {"steps": steps,
            "step_wall_ms": {"mean_ms": _ms(wall.get("mean")),
                             "p50_ms": _ms(wall.get("p50")),
                             "p99_ms": _ms(wall.get("p99")),
                             "sum_ms": _ms(wall_sum)},
            "phases": phases,
            "unattributed": _derive(snap.get("unattributed") or {}),
            "overattributed": snap.get("overattributed", 0)}


def device_anatomy_ms(step_wall_ms, phases_ms):
    """Shape a measured device-trace breakdown into the same anatomy
    structure the host-side phases use: ``{"step_wall_ms",
    "phases_ms": {phase: ms}, "unattributed_ms"}`` with
    the explicit-remainder convention (``unattributed`` clamped to 0;
    when async device phases overlap the wall and sum past it, the
    excess is reported as ``overlap_ms`` instead of being hidden).
    Phase keys should come from :data:`PHASE_LABELS` so the doctor and
    the tool agree on names and units."""
    phases = {k: round(float(v), 3) for k, v in phases_ms.items()
              if v and v > 0.0}
    attributed = sum(phases.values())
    wall = round(float(step_wall_ms), 3)
    out = {"step_wall_ms": wall,
           "phases_ms": phases,
           "unattributed_ms": round(max(0.0, wall - attributed), 3)}
    if attributed > wall:
        out["overlap_ms"] = round(attributed - wall, 3)
    return out


def render(snap=None):
    """Text table for the "Step anatomy" section of ``report()`` /
    diag-dump pretty-printing."""
    snap = snapshot() if snap is None else snap
    lines = ["", "Step anatomy (per-step phase attribution, ms)"]
    if not snap or not snap.get("steps"):
        lines.append("(no step windows closed — stepstats.enable() or "
                     "MXNET_TPU_STEPSTATS=1; auto-on under "
                     "MXNET_TPU_PROFILE / MXNET_TPU_DIAG)")
        return lines
    a = anatomy(snap)

    def _fmt(v):
        return "-" if v is None else "%.3f" % v

    lines.append("%d step window(s)%s" % (
        a["steps"],
        "" if not a.get("overattributed") else
        " (%d over-attributed; remainder clamped to 0)"
        % a["overattributed"]))
    lines.append("%-28s %8s %9s %9s %9s %7s"
                 % ("Phase", "Share", "Mean", "p50", "p99", "Sum(s)"))
    w = a["step_wall_ms"]
    lines.append("%-28s %8s %9s %9s %9s %7.3f"
                 % ("step wall", "100.0%", _fmt(w["mean_ms"]),
                    _fmt(w["p50_ms"]), _fmt(w["p99_ms"]),
                    (w["sum_ms"] or 0.0) / 1e3))
    rows = [(p, a["phases"][p]) for p in PHASES if p in a["phases"]]
    rows.append(("unattributed", a["unattributed"]))
    for p, d in rows:
        lines.append("%-28s %7.1f%% %9s %9s %9s %7.3f"
                     % (PHASE_LABELS.get(p, p)[:28], d["share"] * 100.0,
                        _fmt(d["mean_ms"]), _fmt(d["p50_ms"]),
                        _fmt(d["p99_ms"]), (d["sum_ms"] or 0.0) / 1e3))
    return lines


def reset():
    """Drop every accumulator and re-open the warmup window (tests)."""
    _window.clear()
    _cur["attr"] = 0.0
    _cur["boundary"] = None
    _agg["steps"] = 0
    _agg["overattributed"] = 0
    _agg["last"] = None
    _HISTS.clear()


def _activate_from_env():
    """Import-time arming — called by ``runtime_stats`` once its module
    globals exist (enable() writes ``runtime_stats.DIAG_TIMING``)."""
    flag = os.environ.get("MXNET_TPU_STEPSTATS")
    if flag == "0":
        return False
    if flag == "1" or os.environ.get("MXNET_TPU_PROFILE") \
            or os.environ.get("MXNET_TPU_DIAG"):
        enable()
        return True
    return False
