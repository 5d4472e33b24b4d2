"""Layer ``attention``: the first chip's busy time per step under the
latent-attention scopes ``mla.proj`` (the low-rank projections, norms and
rotary embedding) and ``mla.attention`` (the flash kernels), forward,
recomputed forward and backward (device trace;
``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("mla.proj", "mla.attention"))
