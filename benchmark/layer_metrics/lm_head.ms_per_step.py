"""Layer ``lm_head``: the first chip's busy time per step under the scope
``lm_head``: the shared head applied to both streams, fused with the loss
of both terms over token chunks, forward and backward (device trace;
``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("lm_head",))
