"""Layer ``setup``: ``setup.state_s``, self time of the kept spans
``mxtpu.setup.place`` (masters and optimizer state onto the mesh) and
``mxtpu.setup.orders`` with ``.learn`` and ``.relay`` (reading or learning the
orders the state is held in, the program that moves the leaves), less the
compile-log intervals inside them.  One bucket of ``harness/setup_phases.py``'s
partition of ``setup_s``; None on a program without the kept spans and the
compile log (before PR 36)."""

from benchmark.harness import setup_phases


def read(obs):
    return setup_phases.read(obs, "state")
