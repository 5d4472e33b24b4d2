"""Layer ``attention``: the first chip's busy time per step under the scopes
of grouped-query attention with windowed and full layers: ``gqa.proj`` (the
q / k / v / o products, the two head norms, the rotation, of both layer
types), ``swa.attention`` (a window layer's flash kernels and what surrounds
them) and ``gqa.attention`` (a full layer's), forward, recomputed forward
and backward (device trace; ``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("gqa.proj", "swa.attention", "gqa.attention"))
