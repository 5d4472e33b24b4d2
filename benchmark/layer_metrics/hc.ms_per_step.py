"""Layer ``hc``: ``hc.ms_per_step``, the hyper-connections of a widened
residual stream (``ops/llm.py``: ``hc_coefficients`` under ``hc.coef``,
``hc_pre_mix`` and ``hc_post_mix`` under ``hc.mix``), their forward,
recomputed and backward passes: the first chip's busy time per step under
the two scopes (device trace; ``harness/scope_time.py``).  None where the
program has no such scope."""

from benchmark.harness import hc_cost, scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, hc_cost.SCOPES)
