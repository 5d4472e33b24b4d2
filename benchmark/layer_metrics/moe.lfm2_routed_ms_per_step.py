"""Layer ``moe``: ``moe.routed_ms_per_step`` for the cells of
``lfm2_8b_a1b_ep4``: the same four scopes through the same call (a metric's
``workloads`` list is an entry of its own, which a PR that adds a cell may
not edit; a ``benchmark`` PR folds the two)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("moe.route", "moe.dispatch", "moe.experts", "moe.combine"))
