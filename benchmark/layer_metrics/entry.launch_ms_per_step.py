"""Layer ``entry``: host time per step inside the program's span
``mxtpu.step.launch`` (the ``jax.jit`` call of the step program: argument
flattening, sharding checks, enqueue), summed over the traced window
(profiler's clock; ``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.host_span_ms_per_step(obs, "mxtpu.step.launch")
