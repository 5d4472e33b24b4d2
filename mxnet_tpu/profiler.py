"""Profiler — chrome://tracing output + aggregate stats.

Reference: src/profiler/profiler.h:256 (Profiler singleton, ProfileStat
arrays, chrome-tracing JSON dump :87,437), aggregate_stats.cc,
python/mxnet/profiler.py:33 (set_config/set_state/dump, custom
domains/tasks/counters/markers).

TPU-native: two layers. (1) A Python-side event recorder with the same
API (set_config/set_state/dump/dumps, Domain/Task/Frame/Counter/Marker)
producing chrome-tracing JSON — this traces the *framework* (op
dispatch, iterator, kvstore). (2) ``start_xla_trace``/``stop_xla_trace``
wrap ``jax.profiler`` for device-side traces viewable in TensorBoard /
Perfetto — the analog of the reference's device-level opr profiling,
since XLA owns kernel timing on TPU.  The two meet in
:func:`boundary_span`: a layer-boundary span (once per step or rarer)
is a ``jax.profiler.TraceAnnotation``, so it lies on the device
trace's own clock beside the XLA ops of whatever jax profiler session
is on, and while recorder (1) runs it is also an "X" event under the
same name.  :func:`span` stays the guard-first tool of per-op loops and
reaches recorder (1) only.  A boundary span that happens once (a
``GluonTrainStep``'s set-up, its first call) is *kept* besides: a record
in a bounded in-process list on that same clock (:func:`kept_spans`),
next to the compile log (:func:`compile_log`), jax's own report of every
program this process traced, lowered, compiled or loaded from the
persistent cache.  Nothing has to be on for either, so what happened
before the first step can be read after it.

Distributed telemetry (PR 7): under a ``tools/launch.py`` job every
event carries a rank-tagged pid (worker rank, or 10000 + shard id for
servers), the dumped JSON gains ``process_name``/``process_sort_index``
metadata plus an ``mxtpu`` header — role/rank, a perf-counter →
wall-clock anchor pair captured at import, and the kvstore-ping clock
offset (``set_clock_offset``; ``DistAsyncKVStore.estimate_clock_offset``)
— and :func:`merge_traces` folds several ranks' files into ONE
chrome trace on a common timeline, so the whole cluster's step anatomy
renders in a single viewer.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

from .log import process_identity, rank_suffix_path

_state = {
    "config": {"profile_all": False, "profile_symbolic": True,
               "profile_imperative": True, "profile_memory": False,
               "profile_api": False, "aggregate_stats": False,
               "filename": "profile.json"},
    "running": False,
    "events": [],
    "lock": threading.Lock(),
    "xla_dir": None,
    # estimated wall-clock offset of this process vs PS shard 0
    # (seconds; set_clock_offset) — merge_traces subtracts it
    "clock_offset": None,
}

# rank-tagged trace pid: distinct per role/rank so merged traces show
# one labelled track per process (servers offset far above any worker
# rank).  Single-process runs keep the historical pid 0.
_IDENTITY = process_identity()
TRACE_PID = 0 if _IDENTITY is None else (
    _IDENTITY["rank"] if _IDENTITY["role"] != "server"
    else 10000 + _IDENTITY["rank"])

# perf_counter↔wall anchor pair, captured back-to-back at import: event
# timestamps are perf_counter µs (monotonic, per-process epoch), so
# cross-process merging needs each file to say where its epoch sits on
# the wall clock
_ANCHOR = (time.perf_counter_ns() / 1000.0, time.time() * 1e6)


def set_clock_offset(offset_seconds):
    """Record this process's estimated wall-clock offset (seconds)
    relative to the cluster reference clock (PS shard 0) — stamped into
    the trace header for :func:`merge_traces`."""
    _state["clock_offset"] = float(offset_seconds)


# mxlint: disable=thread-shared-state -- startup publication, set once
_kvstore_handle = None


def set_kvstore_handle(kv):
    """Register the kvstore used to reach parameter-server processes
    (reference: profiler.py set_kvstore_handle — enables
    profile_process='server')."""
    global _kvstore_handle
    _kvstore_handle = kv


def _server_command(fn, kwargs):
    import json as _json

    if _kvstore_handle is None:
        raise ValueError("profile_process='server' needs "
                         "profiler.set_kvstore_handle(kv) first")
    _kvstore_handle._send_command_to_servers(
        "profiler", _json.dumps({"fn": fn, "kwargs": kwargs}))


def set_config(**kwargs):
    """reference: profiler.py:33 set_config.  With
    profile_process='server' the config is forwarded to every
    parameter-server process (reference: KVStoreServerProfilerCommand,
    include/mxnet/kvstore.h:49)."""
    if kwargs.pop("profile_process", "worker") == "server":
        return _server_command("set_config", kwargs)
    _state["config"].update(kwargs)


def set_state(state="stop", profile_process="worker"):
    """'run' | 'stop' (reference: profiler.py:89)."""
    if profile_process == "server":
        return _server_command("set_state", {"state": state})
    if state == "run":
        _state["running"] = True
    elif state == "stop":
        _state["running"] = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


def is_running():
    return _state["running"]


def _now_us():
    return time.perf_counter_ns() / 1000.0


def add_event(name, cat, ph, ts=None, pid=None, tid=None, args=None,
              dur=None, id=None):
    if not _state["running"]:
        return
    ev = {"name": name, "cat": cat, "ph": ph,
          "ts": ts if ts is not None else _now_us(),
          "pid": TRACE_PID if pid is None else pid,
          "tid": tid if tid is not None else threading.get_ident()}
    if args:
        ev["args"] = args
    if dur is not None:
        ev["dur"] = dur
    if id is not None:
        # flow-event binding ("s"/"t"/"f" sharing one id render as a
        # single arrowed flow across threads/processes)
        ev["id"] = id
        if ph in ("s", "t", "f"):
            ev["bp"] = "e"
    with _state["lock"]:
        _state["events"].append(ev)


class scope:
    """``with profiler.scope('fwd'):`` records a complete event."""

    __slots__ = ("name", "cat", "args", "t0")

    def __init__(self, name, cat="framework", args=None):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self.t0 = _now_us()
        return self

    def __exit__(self, *a):
        add_event(self.name, self.cat, "X", ts=self.t0,
                  dur=_now_us() - self.t0, args=self.args)
        return False


class _NullSpan:
    """Shared do-nothing context manager: the disabled-profiler fast
    path of :func:`span` — no allocation, no timestamps."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL_SPAN = _NullSpan()


def span(name, cat="framework", args=None):
    """Guard-first complete-event span for framework hot loops.

    Returns a shared no-op when the profiler is not recording, so
    instrumented code pays one flag check and no event/span allocation
    when telemetry is off (the hard constraint of PR 2's tentpole).
    Exceptions propagate; the event is still recorded."""
    if not _state["running"]:
        return _NULL_SPAN
    return scope(name, cat, args)


class _BothClocks:
    """A jax annotation and a chrome-trace ``scope`` entered as one."""

    __slots__ = ("annotation", "recorded")

    def __init__(self, annotation, recorded):
        self.annotation = annotation
        self.recorded = recorded

    def __enter__(self):
        self.recorded.__enter__()
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        return self.recorded.__exit__(*exc)


class _Kept:
    """A bounded list of records: past ``bound`` the oldest goes, and is
    counted in ``dropped``."""

    def __init__(self, bound):
        self._records = collections.deque(maxlen=bound)
        self._lock = threading.Lock()
        self.dropped = 0

    def add(self, record):
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(record)

    def records(self):
        with self._lock:
            return list(self._records)

    def clear(self):
        with self._lock:
            self._records.clear()
            self.dropped = 0


# what a process builds before its first step, with room: a language cell
# of the benchmark leaves some five hundred records of a few hundred bytes
KEPT_BOUND = 4096

KeptSpan = collections.namedtuple(
    "KeptSpan", "name start_ns end_ns parent thread stats")
CompileRecord = collections.namedtuple(
    "CompileRecord", "kind fun_name start_ns end_ns thread span retrieval_s")

_kept_spans = _Kept(KEPT_BOUND)
_compile_log = _Kept(KEPT_BOUND)


class _PerThread(threading.local):
    """What is open on the calling thread."""

    def __init__(self):
        self.spans = []     # names of the kept spans, innermost last
        self.building = []  # starts of jax's traces, lowerings, compiles
        # seconds a load from the persistent cache took whose backend
        # record is still to come, else None
        self.hit = None


_thread = _PerThread()


class _KeptSpan:
    """A boundary span that also leaves a :class:`KeptSpan` record.
    ``stats`` may be added to until the span closes; what is added inside
    the span reaches the record and not the annotation."""

    __slots__ = ("name", "stats", "inner", "start_ns")

    def __init__(self, name, stats, inner):
        self.name, self.stats, self.inner = name, stats, inner

    def __enter__(self):
        # stamped outside the annotation: the record encloses it
        self.start_ns = time.time_ns()
        _thread.spans.append(self.name)
        self.inner.__enter__()
        return self

    def __exit__(self, *exc):
        self.inner.__exit__(*exc)
        open_here = _thread.spans
        open_here.pop()
        _kept_spans.add(KeptSpan(
            self.name, self.start_ns, time.time_ns(),
            open_here[-1] if open_here else None, threading.get_ident(),
            dict(self.stats)))
        return False


def boundary_span(name, step_num=None, keep=False, **stats):
    """Span at a layer boundary (once per step or rarer), on the jax
    profiler's clock.

    Always a ``jax.profiler.TraceAnnotation(name, **stats)`` — with
    ``step_num`` a ``StepTraceAnnotation``, which the profiler's viewers
    group steps by — so whoever has a jax profiler session on (the
    benchmark's traced tail, :func:`start_xla_trace`, a TensorBoard
    capture) finds it in plane ``/host:CPU`` on the calling thread's
    line, its keyword arguments as stats, on the clock of the device
    planes' ``XLA Ops``.  A span's parent is the span that encloses it
    on that line.  No switch: with no session on, entering and leaving
    costs about a microsecond.  While the chrome-trace recorder is
    running the span is also recorded there as the usual "X" event
    under the same name (category ``boundary``).

    ``keep=True``, for a span that happens once (never one of every
    step): it also leaves a :class:`KeptSpan` in :func:`kept_spans`,
    whatever is or is not on, and so does every boundary span opened on
    its thread while it is open.  The record's times are
    ``time.time_ns()``, the clock the profiler stamps the annotation
    with (an xplane counts from its session's ``profile_start_time`` on
    that clock) and the clock of :func:`compile_log`.

    Not for per-op loops: those keep the guard-first :func:`span`."""
    import jax

    if step_num is None:
        annotation = jax.profiler.TraceAnnotation(name, **stats)
    else:
        stats["step_num"] = step_num
        annotation = jax.profiler.StepTraceAnnotation(name, **stats)
    if _state["running"]:
        annotation = _BothClocks(annotation,
                                 scope(name, "boundary", stats or None))
    if keep or _thread.spans:
        return _KeptSpan(name, stats, annotation)
    return annotation


def kept_spans():
    """The kept boundary spans, oldest first: ``KeptSpan(name, start_ns,
    end_ns, parent, thread, stats)``, the times ``time.time_ns()``,
    ``parent`` the name of the innermost kept span open on the thread
    when this one opened.  A span is recorded when it closes, so a child
    precedes its parent.  At most ``KEPT_BOUND`` (:func:`kept_dropped`)."""
    return _kept_spans.records()


def compile_log():
    """What jax reported of every program this process built, oldest
    first: ``CompileRecord(kind, fun_name, start_ns, end_ns, thread, span,
    retrieval_s)``.  ``kind`` is ``trace`` (python to jaxpr), ``lower``
    (jaxpr to MLIR), ``compile`` (the backend compiled it) or
    ``cache_load`` (the backend record of a program that the persistent
    cache held; ``retrieval_s`` is what jax says reading it took, None
    for the other kinds).  ``fun_name`` is jax's; ``span`` the innermost
    kept span open on the thread, or None; the times are
    ``time.time_ns()``'s.  A trace that ran inside another trace or inside
    a lowering on its thread (the ``jit`` functions a step program calls,
    thousands for a language model) leaves no record.
    At most ``KEPT_BOUND`` (:func:`kept_dropped`)."""
    return _compile_log.records()


def kept_dropped():
    """How many records each list has lost to its bound."""
    return {"kept_spans": _kept_spans.dropped,
            "compile_log": _compile_log.dropped}


def clear_kept():
    """Forget the kept spans, the compile log and their drop counts."""
    _kept_spans.clear()
    _compile_log.clear()


_COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_scalar(event, value, **_kw):
    # jax reports the start of each of the three before it does the work
    if event in _COMPILE_KINDS:
        _thread.building.append(value)


def _on_event(event, **_kw):
    if event == _CACHE_HIT_EVENT:
        _thread.hit = 0.0


def _on_duration(event, seconds, **_kw):
    if event == _CACHE_RETRIEVAL_EVENT:
        _thread.hit = seconds


def _on_time_span(event, start, end, fun_name=None, **_kw):
    kind = _COMPILE_KINDS.get(event)
    if kind is None:
        return
    # what opened since this one's start was inside it and is reported
    building = _thread.building
    while building and building[-1] >= start:
        building.pop()
    if kind == "trace" and building:
        # a jit function that a program calls, met while the program is
        # traced or by one of its lowering rules: no program
        return
    retrieval_s = None
    if kind == "compile" and _thread.hit is not None:
        kind, retrieval_s, _thread.hit = "cache_load", _thread.hit, None
    open_here = _thread.spans
    _compile_log.add(CompileRecord(
        kind, fun_name, int(start * 1e9), int(end * 1e9),
        threading.get_ident(), open_here[-1] if open_here else None,
        retrieval_s))


def _listen_to_jax():
    """Once, at import: jax reports to the compile log from here on.  The
    listeners run only when a program is built."""
    from jax import monitoring

    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_time_span_listener(_on_time_span)


def counter(name, values, cat="framework"):
    """Guard-first chrome-trace counter ("C") event: one flag check and
    nothing else while the profiler is off.  ``values`` is the
    ``{series: number}`` args dict — the per-step telemetry sinks
    (device-memory timeline, numerics-health ``grad_norm`` /
    ``nan_total``) emit through this."""
    if not _state["running"]:
        return
    add_event(name, cat, "C", args=values)


def _identity_meta():
    """chrome-trace metadata events naming this process's track, plus
    the ``mxtpu`` header dict :func:`merge_traces` aligns clocks with.
    Uses the SAME import-time identity as ``TRACE_PID`` — events are
    already tagged with it, so a header from a fresh env read could
    name a rank whose pid no event carries."""
    ident = _IDENTITY
    if ident is not None:
        pname = "%s %d (pid %d)" % (ident["role"], ident["rank"],
                                    os.getpid())
    else:
        pname = "process %d" % os.getpid()
    meta = [
        {"name": "process_name", "ph": "M", "pid": TRACE_PID,
         "args": {"name": pname}},
        {"name": "process_sort_index", "ph": "M", "pid": TRACE_PID,
         "args": {"sort_index": TRACE_PID}},
    ]
    header = {"role": ident["role"] if ident else None,
              "rank": ident["rank"] if ident else None,
              "pid": os.getpid(), "trace_pid": TRACE_PID,
              "perf_anchor_us": _ANCHOR[0], "wall_anchor_us": _ANCHOR[1],
              "clock_offset_us": None if _state["clock_offset"] is None
              else _state["clock_offset"] * 1e6}
    return meta, header


def dump(finished=True, profile_process="worker"):
    """Write chrome-tracing JSON; returns the absolute path.

    ``finished=True`` also stops recording (reference semantics:
    profiler.py dump's `finished` finalizes the profiler).  The file
    carries rank-tagged process metadata and the ``mxtpu`` clock
    header, so per-rank files are :func:`merge_traces`-ready."""
    if profile_process == "server":
        return _server_command("dump", {"finished": finished})
    if finished:
        _state["running"] = False
    fname = _state["config"].get("filename", "profile.json")
    with _state["lock"]:
        events = list(_state["events"])
    meta, header = _identity_meta()
    with open(fname, "w") as f:
        # metadata trails the real events: chrome accepts "M" records
        # anywhere, and readers that index traceEvents[0] keep seeing a
        # timestamped span
        json.dump({"traceEvents": events + meta,
                   "displayTimeUnit": "ms", "mxtpu": header}, f)
    return os.path.abspath(fname)


def merge_traces(paths, out="merged_trace.json"):
    """Merge per-rank chrome traces into ONE file on a shared timeline.

    Each input's event timestamps are per-process ``perf_counter`` µs;
    using the file's ``mxtpu`` header they are re-based onto the wall
    clock (anchor pair) minus the rank's kvstore-ping clock offset, so
    spans line up across machines to within the ping RTT/2.  Files
    without a header (pre-PR-7, or hand-made) are kept on their own
    epoch.  Colliding pids between files are remapped to keep one
    track per process; the merged timeline is normalized to start at
    t=0.  Returns the absolute output path."""
    merged = []
    used_pids: set = set()
    sources = []
    for path in paths:
        with open(path) as f:
            data = json.load(f)
        events = data.get("traceEvents", [])
        header = data.get("mxtpu") or {}
        shift = 0.0
        if header.get("perf_anchor_us") is not None:
            # event ts (per-process perf µs) → this process's wall
            # clock (anchor pair) → the reference clock: offset is
            # server_minus_this (PSClient.ping), so reference time =
            # local wall + offset — ADD it
            shift = header["wall_anchor_us"] - header["perf_anchor_us"] \
                + (header.get("clock_offset_us") or 0.0)
        pids = {ev.get("pid", 0) for ev in events}
        remap = {}
        for p in sorted(pids):
            new = p
            while new in used_pids:
                new += 100000  # far past any rank/server tag
            remap[p] = new
            used_pids.add(new)
        for ev in events:
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift
            ev["pid"] = remap.get(ev.get("pid", 0), ev.get("pid", 0))
            merged.append(ev)
        sources.append({"path": os.path.abspath(path),
                        "role": header.get("role"),
                        "rank": header.get("rank"),
                        "trace_pids": sorted(remap.values()),
                        "clock_offset_us": header.get("clock_offset_us")})
    timed = [ev["ts"] for ev in merged if "ts" in ev]
    if timed:
        t0 = min(timed)
        for ev in merged:
            if "ts" in ev:
                ev["ts"] -= t0
    with open(out, "w") as f:
        json.dump({"traceEvents": merged, "displayTimeUnit": "ms",
                   "mxtpu": {"merged_from": sources}}, f)
    return os.path.abspath(out)


def dumps(reset=False):
    """In-memory aggregate table (reference: aggregate_stats.cc)."""
    with _state["lock"]:
        events = list(_state["events"])
        if reset:
            _state["events"] = []
    agg = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        st = agg.setdefault(ev["name"], {"count": 0, "total_us": 0.0,
                                         "min_us": float("inf"), "max_us": 0.0})
        d = ev.get("dur", 0.0)
        st["count"] += 1
        st["total_us"] += d
        st["min_us"] = min(st["min_us"], d)
        st["max_us"] = max(st["max_us"], d)
    lines = ["%-40s %8s %12s %12s %12s" % ("Name", "Calls", "Total(us)",
                                           "Min(us)", "Max(us)")]
    for name, st in sorted(agg.items(), key=lambda kv: -kv[1]["total_us"]):
        lines.append("%-40s %8d %12.1f %12.1f %12.1f"
                     % (name[:40], st["count"], st["total_us"],
                        st["min_us"], st["max_us"]))
    return "\n".join(lines)


def pause(profile_process="worker"):
    """Stop recording without clearing events (reference: profiler.py
    pause → MXProfilePause).  ``profile_process='server'`` forwards to
    the parameter-server processes like ``set_state`` does."""
    if profile_process == "server":
        return _server_command("pause", {})
    _state["running"] = False


def resume(profile_process="worker"):
    """Resume a paused recording; ``profile_process='server'`` forwards
    to the parameter-server processes like ``set_state`` does."""
    if profile_process == "server":
        return _server_command("resume", {})
    _state["running"] = True


# ------------------------------------------------------------- XLA traces


def start_xla_trace(log_dir="/tmp/mxnet_tpu_trace"):
    """Device-side trace via jax.profiler (TensorBoard/Perfetto viewable)."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _state["xla_dir"] = log_dir


def stop_xla_trace():
    import jax

    jax.profiler.stop_trace()
    return _state["xla_dir"]


# ------------------------------------------------------------- user scopes
# reference: c_api_profile.cc domains/tasks/frames/counters/markers


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._t0 = None

    def start(self):
        self._t0 = _now_us()

    def stop(self):
        if self._t0 is not None:
            add_event(self.name, self.domain.name, "X", ts=self._t0,
                      dur=_now_us() - self._t0)
            self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


class Task(_Span):
    pass


class Frame(_Span):
    pass


class Event(_Span):
    def __init__(self, name):
        super().__init__(Domain("event"), name)


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self.value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self.value = value
        add_event(self.name, self.domain.name, "C",
                  args={self.name: value})

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)

    __iadd__ = lambda self, d: (self.increment(d), self)[1]
    __isub__ = lambda self, d: (self.decrement(d), self)[1]


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        add_event(self.name, self.domain.name, "i",
                  args={"scope": scope})


# ------------------------------------------------------- env activation


def _dump_at_exit():
    if _state["running"] or _state["events"]:
        dump(finished=True)


def _activate_from_env():
    """``MXNET_TPU_PROFILE=<file>``: record the whole process and dump
    the chrome trace at exit — zero-code-change profiling of any
    training script (docs/OBSERVABILITY.md)."""
    fname = os.environ.get("MXNET_TPU_PROFILE")
    if not fname:
        return False
    import atexit

    # multi-rank runs launched WITHOUT tools/launch.py (which rewrites
    # the env per process) self-suffix the path — a non-zero rank must
    # not silently overwrite rank 0's trace
    set_config(filename=rank_suffix_path(fname), profile_all=True)
    set_state("run")
    atexit.register(_dump_at_exit)
    return True


_activate_from_env()
_listen_to_jax()
