"""Layer ``shortconv``: the first chip's busy time per step under the
short-convolution scopes ``shortconv.proj`` (the in- and out-projection's
products) and ``shortconv.conv`` (the gates and the causal convolution over
the sequence), forward, recomputed forward and backward (device trace;
``harness/scope_time.py``)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(
        obs, ("shortconv.proj", "shortconv.conv"))
