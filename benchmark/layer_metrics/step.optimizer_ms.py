"""Layer ``step``: device time per step under the ``optimizer`` scope: the
update of parameters and optimizer state (device trace, first chip;
``harness/program_spans.py``).  An update that the compiler fused into a
weight gradient counts as backward."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.device_phase_ms_per_step(obs, "optimizer")
