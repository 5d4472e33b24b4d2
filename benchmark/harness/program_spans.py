"""The program's own spans, read from the profiler's trace.

Two kinds, both on the clock of the device planes' ``XLA Ops``:

- **Device phases.**  The program wraps every Gluon block, the loss, the
  ``value_and_grad`` call and the optimizer update in ``jax.named_scope``
  (``mxnet_tpu.xray``), so every instruction of the step program carries
  its scope in its ``op_name``.  ``phase_of`` reads it, ``device_phase_s``
  sums the first chip's instruction time by it.
- **Host phases.**  ``GluonTrainStep.__call__`` opens
  ``mxnet_tpu.profiler.boundary_span``s (``jax.profiler.TraceAnnotation``)
  named ``mxtpu.step[.put_batch|.key|.scalars|.launch]``; they land in
  plane ``/host:CPU`` on the calling thread's line.  ``host_spans`` reads
  them; a span's parent is the span that encloses it on its line.

A program without these spans (an older commit) gives nothing to read, and
every ``*_ms_per_step`` here then returns None: the harness leaves the
metric out.
"""

import functools
import glob
import os
import re
import tempfile

from . import trace

PHASES = ("forward", "backward", "optimizer", "other")
_IN_GRAD = re.compile(r"(^|[/(])grad/")     # xray.GRAD_MARKER as a scope
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
PREFIX = "mxtpu."
OUTSIDE = "outside_step"


# ----------------------------------------------------------- device phases


def phase_of(op_name):
    """``forward`` | ``backward`` | ``optimizer`` | ``other`` for one
    instruction's ``op_name``, by ``xray.canonical_scope``.  Inside the
    ``grad`` scope that every train step puts around its ``value_and_grad``
    call, a block's or the loss's scope is forward and the same under
    ``transpose(...)`` backward; the optimizer region is optimizer.  Other
    is what carries no scope of the program's (a bare primitive, an empty
    name), the ZeRO regions, and the instructions of other programs: the
    PRNG key's carry jax's own ``while/body`` and no ``grad``."""
    from mxnet_tpu import xray

    head = (xray.canonical_scope(op_name) or "").split("/", 1)[0]
    if head == "optimizer" or (head in ("forward", "backward")
                               and _IN_GRAD.search(op_name)):
        return head
    return "other"


def _instruction_phase(op, modules):
    """The phase of one executed instruction from the first module that
    knows it.  A fusion is backward if any instruction inside it is, else
    optimizer, else forward: the compiler duplicates cheap forward
    instructions into the backward fusions that consume them, and fuses the
    update into the weight gradient, and such a fusion runs in the backward
    pass.  (``hlo_cost.Module.instructions`` alone names a fusion without
    convolutions by its first named instruction, which read 6.1 ms of a
    ResNet-50 step's backward fusions as forward.)"""
    for module in modules:
        if op.name in module.instructions:
            inside = {phase_of(module.instructions[op.name][1])}
            for called in _CALLS.findall(op.text):
                inside.update(phase_of(module.instructions[name][1])
                              for name in module._comps.get(called, ()))
            return next(p for p in ("backward", "optimizer", "forward",
                                    "other") if p in inside)
    return "other"


def device_phase_s(recorded, modules):
    """{phase: seconds} of the first chip's busy time inside the traced
    window.  ``trace.leaf_ops`` (containers excluded) go to the phase of
    their ``op_name`` scope, a fusion whole to the latest phase found inside
    it (``_instruction_phase``).  Busy time that no such instruction covers
    goes to other: the waits for overlapped slices and copies
    (``async-done``, which ``leaf_ops`` leaves out as a container, 0.98 ms
    of a ResNet-50 step).  So the four sum to that chip's ``trace.busy``:
    one core runs one instruction at a time."""
    seconds = dict.fromkeys(PHASES, 0.0)
    if not recorded.devices:
        return seconds
    phases = {}     # every step repeats the same instructions
    leaves = trace.leaf_ops(recorded)
    for op in leaves:
        if op.name not in phases:
            phases[op.name] = _instruction_phase(op, modules)
        seconds[phases[op.name]] += (op.end - op.start) / 1e9
    first = trace.busy(recorded)[min(recorded.devices)]
    uncovered = trace.subtract(
        first, trace.merge((op.start, op.end) for op in leaves))
    seconds["other"] += trace.total(uncovered) / 1e9
    return seconds


def _on_a_chip(obs):
    """The run's Trace if it has a device plane and a traced tail."""
    recorded = obs.get("trace")
    return recorded if recorded and recorded.devices and obs.get("tail") \
        else None


def device_phase_ms_per_step(obs, phase):
    """What the ``step.<phase>_ms`` readers return: the phase's time on the
    first chip per step of the traced tail; None where no instruction
    carries a scope of the program's."""
    recorded = _on_a_chip(obs)
    if not recorded:
        return None
    seconds = device_phase_s(recorded, obs.get("modules") or [])
    if sum(seconds[p] for p in PHASES[:3]) <= 0:
        return None
    return seconds[phase] * 1e3 / obs["tail"]["steps"]


# ------------------------------------------------------------- host phases


def _newest_run_xplane():
    """The newest trace of a run of ``benchmark/run.py`` that is still on
    disk: ``<tmp>/benchmark_run_*/trace`` (newest by modification time, in
    case a crashed run left a directory behind)."""
    found = [trace.newest_xplane(d) for d in glob.glob(os.path.join(
        tempfile.gettempdir(), "benchmark_run_*", "trace"))]
    found = [p for p in found if p]
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _read_host_spans(path, prefix):
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for index, line in enumerate(plane.lines):
            where = "%s#%d" % (line.name, index)
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, where,
                                  dict(e.stats)))
    return tuple(sorted(spans, key=lambda s: (s[1], -s[2])))


def host_spans(path=None, prefix=PREFIX):
    """The program's host spans in the xplane at ``path``: the events of
    plane ``/host:CPU`` whose names start with ``prefix``, as ``(name,
    start_ns, end_ns, line, stats)`` by start, parsed once per path.

    STOPGAP for ``path=None``: ``run.py``'s ``Trace`` keeps neither the
    host spans it was not asked for nor the file's path, and neither file
    may be edited by the change that adds this one.  Readers run while the
    run's temporary directory still exists, so the newest trace under
    ``<tmp>/benchmark_run_*/trace`` is the run's own.  It goes once
    ``Trace.from_xplane`` keeps the program's spans (ROADMAP, benchmark
    queue)."""
    path = path or _newest_run_xplane()
    return list(_read_host_spans(path, prefix)) if path else []


def parents(spans):
    """[index of the innermost span that encloses span i on its line, or
    None]: for ``spans`` sorted by start, enclosing span first."""
    out, open_on = [], {}
    for i, (_, start, end, line, _) in enumerate(spans):
        stack = open_on.setdefault(line, [])
        while stack and spans[stack[-1]][2] < end:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(i)
    return out


def self_ns(spans):
    """[duration of span i less what its child spans cover]."""
    own = [end - start for _, start, end, _, _ in spans]
    for i, parent in enumerate(parents(spans)):
        if parent is not None:
            own[parent] -= spans[i][2] - spans[i][1]
    return own


def span_ms_per_step(spans, name, window, steps):
    """Summed duration of the spans called ``name`` that lie inside
    ``window`` = (start_ns, end_ns), in ms per step; None without one."""
    lo, hi = window
    inside = [end - start for n, start, end, _, _ in spans
              if n == name and start >= lo and end <= hi]
    return sum(inside) / 1e6 / steps if inside and steps else None


def host_span_ms_per_step(obs, name):
    """What the ``entry.<phase>_ms_per_step`` readers return."""
    recorded = _on_a_chip(obs)
    if not recorded:
        return None
    return span_ms_per_step(host_spans(), name, recorded.window(),
                            obs["tail"]["steps"])


def idle_by_span(recorded, spans):
    """[[span name, seconds]]: the first chip's idle gaps inside the window,
    each given to the innermost (shortest) program span that covers more
    than half of it, else to ``outside_step``; longest first."""
    lo, hi = recorded.window()
    if not recorded.devices or hi <= lo:
        return []
    first = trace.busy(recorded)[min(recorded.devices)]
    by_name = {}
    for start, end in trace.gaps(first, lo, hi):
        covering = [(e - s, name) for name, s, e, _, _ in spans
                    if 2 * (min(e, end) - max(s, start)) > end - start]
        best = min(covering)[1] if covering else OUTSIDE
        by_name[best] = by_name.get(best, 0) + (end - start)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[name, ns / 1e9] for name, ns in ranked]
