"""Device time under the program's named scopes (``xray.scope``), beside
``program_spans.py``, which sorts the same instructions by phase.

An instruction belongs to a scope when the scope's name is a component of
its ``op_name`` (a fusion: of its heaviest member's, as
``hlo_cost.Module.instructions`` names it), whatever wraps it
(``transpose(jvp(...))``, ``checkpoint``, a loop's body).  One core runs one
instruction at a time, so the times of disjoint sets of scopes add up to
no more than the chip's busy time.
"""

import re

from . import program_spans, trace


def _under(names):
    return re.compile(r"(^|[/(])(%s)([/)]|$)"
                      % "|".join(re.escape(n) for n in names))


def scope_s(recorded, modules, names):
    """Seconds of the first chip's instructions inside the traced window
    whose ``op_name`` lies under one of the scopes ``names``."""
    under = _under(names)
    hit = {}        # every step repeats the same instructions
    seconds = 0.0
    for op in trace.leaf_ops(recorded):
        if op.name not in hit:
            hit[op.name] = bool(under.search(trace._cost(op, modules)[1]))
        if hit[op.name]:
            seconds += (op.end - op.start) / 1e9
    return seconds


def scope_ms_per_step(obs, names):
    """What the ``<layer>.*_ms_per_step`` readers of named scopes return:
    None without a device trace or where no instruction carries one of the
    scopes (a program that lacks them)."""
    recorded = program_spans._on_a_chip(obs)
    if not recorded:
        return None
    seconds = scope_s(recorded, obs.get("modules") or [], names)
    return seconds * 1e3 / obs["tail"]["steps"] if seconds > 0 else None
