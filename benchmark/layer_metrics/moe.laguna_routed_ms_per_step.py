"""Layer ``moe``: ``moe.routed_ms_per_step`` for the cells of
``laguna_s_2_1_ep32``: the routed path's four scopes (``moe.route``, with
the selection bias's balancing rule, ``moe.dispatch``, ``moe.experts``,
``moe.combine``) through the same call; the shared expert (``moe.shared``)
is not in it."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("moe.route", "moe.dispatch", "moe.experts", "moe.combine"))
