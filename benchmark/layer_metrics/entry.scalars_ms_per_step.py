"""Layer ``entry``: host time per step inside the program's span
``mxtpu.step.scalars`` (``_OptimizerUpdate.host_scalars``: the update counts
advanced and two Python floats a parameter refilled, ``optimizer=`` steps
only), summed over the traced window (profiler's clock;
``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.host_span_ms_per_step(obs, "mxtpu.step.scalars")
