"""Layer ``collectives``: time per step in which a collective was in
flight, averaged over the chips (device trace)."""

from benchmark.harness import trace


def read(obs):
    found = obs["trace"] and trace.collectives(obs["trace"])
    return found[0] * 1e3 / obs["tail"]["steps"] if found else None
