"""Layer ``setup``: ``setup.compile_s``, the backend compiles of the programs
that missed the persistent cache before the first measured step: the compile
log's ``compile`` intervals.  Mosaic's share of a backend compile is inside it
and not told apart.  One bucket of ``harness/setup_phases.py``'s partition of
``setup_s``; None on a program without the kept spans and the compile log
(before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "compile")
