"""Layer ``setup``: ``setup.trace_lower_s``, Python tracing and MLIR lowering
of every program built before the first measured step: the compile log's
``trace`` and ``lower`` intervals (nested ones count once), paid whether the
compile cache is warm or cold.  One bucket of ``harness/setup_phases.py``'s
partition of ``setup_s``; None on a program without the kept spans and the
compile log (before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "trace_lower")
