"""Layer ``setup``: ``setup.other_s``, what no record covers: imports, chip
attach, the model's draw on the host, the eager run of the probe programs, the
check's own compute and transfers (the entry's side, which carries no span).
One bucket of ``harness/setup_phases.py``'s partition of ``setup_s``; None on
a program without the kept spans and the compile log (before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "other")
