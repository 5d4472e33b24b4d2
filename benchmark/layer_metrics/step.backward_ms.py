"""Layer ``step``: device time per step under the same scopes as
``step.forward_ms`` where the ``op_name`` has them under ``transpose(...)``:
the backward pass inside ``grad`` (device trace, first chip;
``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def read(obs):
    return program_spans.device_phase_ms_per_step(obs, "backward")
