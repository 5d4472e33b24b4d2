"""From the profiler's trace to device numbers.

The jax profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but jax.  On a TPU every
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per executed HLO instruction, named by the instruction's full text, with
its start and duration in nanoseconds on the clock the host's spans use
(plane ``/host:CPU``, ``jax.profiler.TraceAnnotation``).  The line ``Async
XLA Ops`` holds the start-to-done span of every asynchronous operation
(prefetch copies, and the collectives across chips).

All reductions work on plain intervals ``(start_ns, end_ns)``, so a small
recorded trace checks them (``tests/benchmark/test_trace_reduction.py``).
"""

import glob
import os
import re

from . import hlo_cost

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "traced_window"


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


class Op:
    """One executed instruction on one device."""

    __slots__ = ("start", "end", "text", "name", "opcode")

    def __init__(self, start, end, text, parsed=None):
        self.start, self.end, self.text = start, end, text
        self.name, self.opcode = parsed or \
            hlo_cost.split_instruction(text)[:2]


class Trace:
    """``devices``: {ordinal: {"ops": [Op], "async": [Op], "modules":
    [(start, end, name)]}}; ``spans``: [(name, start, end)] of the host
    annotations whose names were asked for."""

    def __init__(self, devices, spans):
        self.devices = devices
        self.spans = spans
        self._busy = None

    @classmethod
    def from_xplane(cls, path, span_names):
        from jax.profiler import ProfileData

        devices, spans = {}, []
        parsed = {}     # every step repeats the same instruction texts

        def _op(event):
            text = event.name
            if text not in parsed:
                parsed[text] = hlo_cost.split_instruction(text)[:2]
            return Op(event.start_ns, event.start_ns + event.duration_ns,
                      text, parsed[text])

        for plane in ProfileData.from_file(path).planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = devices.setdefault(
                    int(m.group(1)), {"ops": [], "async": [], "modules": []})
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        dev["ops"] = [_op(e) for e in line.events]
                    elif line.name == ASYNC_LINE:
                        dev["async"] = [_op(e) for e in line.events]
                    elif line.name == MODULES_LINE:
                        dev["modules"] = [
                            (e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.name in span_names:
                            spans.append((e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
        return cls(devices, sorted(spans, key=lambda s: s[1]))

    def window(self):
        """(start, end) of the traced window: the host span that wraps it,
        or, in a trace without one, first to last device event."""
        for name, start, end in self.spans:
            if name == WINDOW_SPAN:
                return start, end
        starts = [op.start for d in self.devices.values() for op in d["ops"]]
        ends = [op.end for d in self.devices.values() for op in d["ops"]]
        return (min(starts), max(ends)) if starts else (0, 0)


# ------------------------------------------------------------- intervals


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes):
    """The part of merged ``intervals`` that merged ``holes`` leave."""
    out, j = [], 0
    for start, end in intervals:
        cursor = start
        while j < len(holes) and holes[j][1] <= cursor:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(intervals, lo, hi):
    return subtract([(lo, hi)], intervals)


# ------------------------------------------------------------ reductions


def busy(trace):
    """{ordinal: merged intervals in which an instruction ran}, clipped to
    the traced window."""
    if trace._busy is None:
        lo, hi = trace.window()
        trace._busy = {
            n: clip(merge((op.start, op.end) for op in d["ops"]), lo, hi)
            for n, d in trace.devices.items()}
    return trace._busy


def busy_and_window_s(trace):
    """(seconds an operation ran, averaged over the chips; window seconds)."""
    lo, hi = trace.window()
    per_device = [total(iv) for iv in busy(trace).values()]
    if not per_device:
        return 0.0, (hi - lo) / 1e9
    return sum(per_device) / len(per_device) / 1e9, (hi - lo) / 1e9


def idle_gaps(trace, top=10):
    """[[host span, seconds]]: the first chip's idle time inside the window
    by what the host was doing, longest first.  Each gap goes to the span
    that covers most of it, or to ``none``."""
    lo, hi = trace.window()
    if not trace.devices or hi <= lo:
        return []
    first = busy(trace)[min(trace.devices)]
    spans = [s for s in trace.spans if s[0] != WINDOW_SPAN]
    by_name = {}
    for start, end in gaps(first, lo, hi):
        best, best_cover = "none", 0
        for name, s, e in spans:
            cover = min(e, end) - max(s, start)
            if cover > best_cover:
                best, best_cover = name, cover
        by_name[best] = by_name.get(best, 0) + (end - start)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def leaf_ops(trace):
    """The first chip's instructions that do work of their own: not the
    containers (while, call) whose children are listed too."""
    if not trace.devices:
        return []
    lo, hi = trace.window()
    return [op for op in trace.devices[min(trace.devices)]["ops"]
            if op.opcode not in hlo_cost.CONTAINERS
            and op.start >= lo and op.end <= hi]


def _cost(op, modules):
    """(operations, source op_name) of an instruction from the first module
    that knows it, else (0, "")."""
    for module in modules:
        hit = module.instructions.get(op.name)
        if hit:
            return hit
    return 0, ""


def stable_label(op, modules):
    """A name for an instruction that survives a recompile: its opcode and
    the jax operation it was lowered from (the heaviest one of a fusion),
    taken from the module's metadata; else opcode and result type."""
    op_name = _cost(op, modules)[1]
    if op_name:
        return "%s %s" % (op.opcode,
                          re.sub(r"jit\([^)]*\)/", "", op_name)[-96:])
    _, _, (result, _, _) = hlo_cost.split_instruction(op.text)
    return "%s %s" % (op.opcode, re.sub(r"\{[^}]*\}", "", result)[:96])


def device_ops(trace, modules, top=10):
    """[[stable name, seconds]]: where the first chip's time went."""
    by_label = {}
    for op in leaf_ops(trace):
        label = stable_label(op, modules)
        by_label[label] = by_label.get(label, 0) + (op.end - op.start)
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return [[label, ns / 1e9] for label, ns in ranked]


def roofline(trace, modules, peaks):
    """Sum of the roofline bounds over sum of the measured times of the
    first chip's instructions, and which bound dominates.

    An instruction's bound is the larger of its operations over the peak
    rate and its least HBM bytes over the peak bandwidth.  Bytes that would
    need more than the peak bandwidth for the measured time are cut to what
    that time could move (a strided read lists its whole operand).  Waits
    for overlapped transfers count in the time and have no bound.
    -> {"share", "bound_s", "time_s", "flops_bound_s", "bytes_bound_s"} or
    None where there is nothing to read."""
    flops_peak, bw_peak = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    time_s = bound_s = by_flops = by_bytes = 0.0
    for op in leaf_ops(trace):
        seconds = (op.end - op.start) / 1e9
        if op.opcode.endswith(("-start", "-done")):
            # issue of, and wait for, an overlapped transfer: the wait is
            # time the chip lost, and nothing it could have done faster
            time_s += seconds
            continue
        flops = _cost(op, modules)[0]
        nbytes = min(hlo_cost.min_hbm_bytes(op.text), bw_peak * seconds)
        t_flops, t_bytes = flops / flops_peak, nbytes / bw_peak
        time_s += seconds
        bound_s += max(t_flops, t_bytes)
        if t_flops >= t_bytes:
            by_flops += t_flops
        else:
            by_bytes += t_bytes
    if time_s <= 0:
        return None
    return {"share": bound_s / time_s, "bound_s": bound_s, "time_s": time_s,
            "flops_bound_s": by_flops, "bytes_bound_s": by_bytes}


def collectives(trace):
    """Per chip, averaged: seconds in which a collective was in flight, and
    the part of them in which no other instruction ran on that chip.
    -> (in flight, exposed) or None in a trace with no collective."""
    lo, hi = trace.window()
    in_flight, exposed, seen = 0.0, 0.0, False
    for dev in trace.devices.values():
        coll = [(op.start, op.end) for op in dev["ops"] + dev["async"]
                if hlo_cost.is_collective(op.opcode)]
        if coll:
            seen = True
        coll = clip(merge(coll), lo, hi)
        compute = clip(merge(
            (op.start, op.end) for op in dev["ops"]
            if not hlo_cost.is_collective(op.opcode)
            and op.opcode not in hlo_cost.CONTAINERS), lo, hi)
        in_flight += total(coll)
        exposed += total(subtract(coll, compute))
    if not seen:
        return None
    n = len(trace.devices)
    return in_flight / n / 1e9, exposed / n / 1e9
