"""Layer ``step``: device time per step of the instructions that carry no
scope of the program: copies and prefetch waits, casts and the gradient
norm outside the blocks, the PRNG key's programs.  It is the coverage of
``step.forward_ms``, ``step.backward_ms`` and ``step.optimizer_ms``: the
four sum to the instructions' time, and this one going up is a finding
(device trace, first chip; ``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.device_phase_ms_per_step(obs, "other")
