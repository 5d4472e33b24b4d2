"""Layer ``setup``: ``setup.programs_compiled``, how many programs the backend
compiled before the first measured step (the compile log's ``compile``
records): ``setup.compile_s`` as a count, which does not depend on the
machine's speed.  None on a program without the compile log (before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "programs_compiled")
