"""Layer ``lm_head``: ``lm_head.ms_per_step`` for the cells of
``mellum2_12b_ep4``: the first chip's busy time per step under the scope
``lm_head``, here one untied head fused with the next-token loss over token
chunks, forward and backward (device trace; ``harness/scope_time.py``); a
``benchmark`` PR folds the doubles."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("lm_head",))
