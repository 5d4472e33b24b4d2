"""Layer ``setup``: ``setup.warmup_s``, from the start of the timed step's
call 0 (the last kept ``mxtpu.step`` with ``step_num`` 0) to set-up's end, less
the compile-log intervals and state spans inside it: the first step's run and
the warm-up steps.  One bucket of ``harness/setup_phases.py``'s partition of
``setup_s``; None on a program without the kept spans and the compile log
(before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "warmup")
