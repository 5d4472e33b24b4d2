"""Layer ``moe``: ``moe.wgrad_ms_per_step``, the routed sum's weight
gradients: the backward loops' three products a step that contract an
expert's rows, each with its read-add-write of the expert's float32
``(in, width)`` slice of the carried sum, under the scope ``moe.wgrad``
inside ``moe.experts`` (so the routed path's metrics count them too).  The
number of its instructions a step against the pairs on the held experts
says how many rows a write covers.  None where the program has no such
scope (before PR 35)."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.scope_ms_per_step(obs, ("moe.wgrad",))
