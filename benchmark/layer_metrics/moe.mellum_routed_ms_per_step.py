"""Layer ``moe``: ``moe.routed_ms_per_step`` for the cells of
``mellum2_12b_ep4``: the same four scopes through the same call, and
``moe.aux`` beside them (the balancing term's statistics: the count of the
pairs an expert and the mean of the router's probabilities); a ``benchmark``
PR folds the doubles."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
              "moe.aux"))
