"""Layer ``moe``: the first chip's busy time per step on the routed path:
scopes ``moe.route`` (scores, top-k, weights), ``moe.dispatch`` (sort into
expert tiles, gather), ``moe.experts`` (the held experts' grouped
feed-forward) and ``moe.combine`` (weighted scatter-add); the shared expert
(``moe.shared``) is not in it (device trace; ``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("moe.route", "moe.dispatch", "moe.experts", "moe.combine"))
