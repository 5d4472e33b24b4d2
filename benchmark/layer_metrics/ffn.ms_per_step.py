"""Layer ``ffn``: ``ffn.ms_per_step``, the gated-SiLU feed-forward
(``ops/llm.py::gated_silu``: the dense layers and the shared experts
alike; a shared expert's instructions lie under ``moe.shared`` too), its
forward, recomputed and backward passes, under the scope ``ffn.gated``.
None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("ffn.gated",))
