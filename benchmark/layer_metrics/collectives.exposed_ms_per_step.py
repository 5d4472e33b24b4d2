"""Layer ``collectives``: the part of ``collectives.ms_per_step`` in which
no other instruction ran on that chip: what the all-reduce costs the step
(device trace)."""

from benchmark.harness import trace


def read(obs):
    found = obs["trace"] and trace.collectives(obs["trace"])
    return found[1] * 1e3 / obs["tail"]["steps"] if found else None
