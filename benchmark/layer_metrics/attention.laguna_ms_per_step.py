"""Layer ``attention``: the first chip's busy time per step under the scopes
of the gated grouped-query attention of ``laguna_s_2_1_ep32``: ``gqa.proj``
(the q / k / v / o products, the two head norms, the partial rotation, of
both layer types), ``gqa.gate`` (the per-head gate's product, sigmoid and
multiply), ``swa.attention`` (a window layer's flash kernels and what
surrounds them) and ``gqa.attention`` (a full layer's), forward, recomputed
forward and backward (device trace; ``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("gqa.proj", "gqa.gate", "swa.attention", "gqa.attention"))
