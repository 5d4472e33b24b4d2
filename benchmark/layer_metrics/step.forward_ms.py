"""Layer ``step``: device time per step under the forward scopes of the step
program: every Gluon block (``xray.block_scope``) and ``loss``, not under
``transpose(...)`` (device trace, first chip; ``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.device_phase_ms_per_step(obs, "forward")
