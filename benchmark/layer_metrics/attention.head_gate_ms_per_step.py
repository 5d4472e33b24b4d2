"""Layer ``attention``: the first chip's busy time per step under the scope
``gqa.gate`` alone: the per-head gate's product ``x W_g``, its sigmoid and
the multiply into the attention kernel's result, forward, recomputed
forward and backward (device trace; ``harness/scope_time.py``).  Near 0
where the compiler fused them into the neighbouring products, whose names
the fusions then take; None on a program without the scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("gqa.gate",))
