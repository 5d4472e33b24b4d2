"""Layer ``attention``: the first chip's busy time per step under the
grouped-query attention scopes ``gqa.proj`` (the q / k / v / o products,
the two head norms, the rotation) and ``gqa.attention`` (the flash kernels
and what surrounds them), forward, recomputed forward and backward (device
trace; ``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("gqa.proj", "gqa.attention"))
