"""Layer ``setup``: ``setup.cache_load_s``, the backend records of the programs
that came from the persistent cache before the first measured step: the
compile log's ``cache_load`` intervals (reading the entry, loading the
executable).  One bucket of ``harness/setup_phases.py``'s partition of
``setup_s``; None on a program without the kept spans and the compile log
(before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "cache_load")
