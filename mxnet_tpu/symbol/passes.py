"""Pass manager over the Symbol DAG — verified rewrites by construction.

Composable IR -> IR transforms in the style of TVM's IR (arxiv
1810.00952): a :class:`Pass` wraps one graph rewrite, and the manager
re-runs the graph verifier (:mod:`.verify`) on the rewrite's output before anyone
downstream can bind it.  A pass that produces an invalid graph fails
loudly with the pass *and* the finding named — it never hands a broken
DAG to the executor, where the same fault would surface as an opaque
trace error deep inside jit.

Per-pass bookkeeping lands in :mod:`..runtime_stats` (the
``graph_passes`` snapshot section): run counts, verify wall time, node
deltas, and — when the context opts in with ``measure_cost=True`` —
XLA-reported flops/bytes before and after the rewrite, so
``runtime_stats.report()`` and ``--compare`` show what a rewrite
actually bought.  Cost measurement compiles the whole graph twice and
is therefore opt-in.

Identity contract: a pass that has nothing to do must return the input
Symbol *itself* (not a reconstruction).  The manager skips
re-verification for identity returns — callers like
``simple_bind``'s ``part is not self`` check rely on object identity,
and verifying an unchanged input would turn pre-existing oddities in
user graphs into new errors.
"""

from __future__ import annotations

import time as _time

from ..base import MXNetError
from .verify import verify_graph

__all__ = ["Pass", "FunctionPass", "PassContext", "PassError",
           "sequential", "pass_stats_snapshot", "reset_pass_stats"]


class PassError(MXNetError):
    """A pass produced an invalid graph (or failed internally)."""


class PassContext:
    """Shared knobs for one pass-pipeline run.

    ``input_shapes`` / ``input_dtypes`` seed the verifier's abstract
    interpretation (without them verification is partial: structural +
    cache-key checks always run in full).  ``verify=False`` disables
    post-pass verification (escape hatch; production callers keep it
    on).  ``measure_cost=True`` additionally compiles the graph before
    and after each pass and records XLA flops/bytes deltas —
    expensive, off by default.  ``options`` is a free-form dict for
    pass-specific parameters.
    """

    def __init__(self, input_shapes=None, input_dtypes=None, options=None,
                 verify=True, measure_cost=False):
        self.input_shapes = dict(input_shapes or {})
        self.input_dtypes = dict(input_dtypes or {})
        self.options = dict(options or {})
        self.verify = verify
        self.measure_cost = measure_cost


# {pass name: {"runs", "changed", "verify_seconds", "nodes_before",
#              "nodes_after", "flops_before", "flops_after",
#              "bytes_before", "bytes_after"}}
_PASS_STATS = {}


def reset_pass_stats():
    _PASS_STATS.clear()


def pass_stats_snapshot():
    """Deep copy of per-pass stats for runtime_stats.snapshot()."""
    return {name: dict(st) for name, st in _PASS_STATS.items()}


def _node_count(sym):
    return sum(1 for _ in sym._topo_nodes())


def _graph_cost(sym, ctx):
    """XLA cost analysis of the whole graph: {"flops", "bytes"} or None.

    Compiles the inference-mode eval fn on avals derived from the
    context's input shapes — the same lowering the executor would jit.
    """
    try:
        import jax

        from ..executor import make_eval_fn
        from ..ops import registry as _reg
        from .verify import variable_dtypes

        arg_shapes, _out, aux_shapes = sym.infer_shape(**ctx.input_shapes)
        if arg_shapes is None or any(s is None for s in arg_shapes):
            return None
        dtypes = variable_dtypes(sym, ctx.input_dtypes)
        args = sym.list_arguments()
        auxs = sym.list_auxiliary_states()
        arg_avals = [jax.ShapeDtypeStruct(tuple(s), dtypes.get(n, "float32"))
                     for n, s in zip(args, arg_shapes)]
        aux_avals = [jax.ShapeDtypeStruct(tuple(s), dtypes.get(n, "float32"))
                     for n, s in zip(auxs, aux_shapes)]
        fn, _meta = make_eval_fn(sym, is_train=False)
        compiled = jax.jit(fn).lower(arg_avals, aux_avals, 0).compile()
        cost = _reg.compiled_cost(compiled)
        if not cost:
            return None
        return {"flops": cost.get("flops"),
                "bytes": cost.get("bytes_accessed")}
    except Exception:
        return None


def _record(name, changed, verify_seconds, nodes_before, nodes_after,
            cost_before, cost_after):
    st = _PASS_STATS.setdefault(name, {
        "runs": 0, "changed": 0, "verify_seconds": 0.0,
        "nodes_before": None, "nodes_after": None,
        "flops_before": None, "flops_after": None,
        "bytes_before": None, "bytes_after": None,
    })
    st["runs"] += 1
    st["changed"] += 1 if changed else 0
    st["verify_seconds"] += verify_seconds
    st["nodes_before"] = nodes_before
    st["nodes_after"] = nodes_after
    if cost_before:
        st["flops_before"] = cost_before.get("flops")
        st["bytes_before"] = cost_before.get("bytes")
    if cost_after:
        st["flops_after"] = cost_after.get("flops")
        st["bytes_after"] = cost_after.get("bytes")
    try:
        from .. import runtime_stats as _rts

        _rts.inc("graph_pass_runs")
        if changed:
            _rts.inc("graph_pass_rewrites")
    except Exception:
        pass


class Pass:
    """Base class: subclasses set ``name`` and implement ``run``."""

    name = "pass"

    def run(self, sym, ctx):  # pragma: no cover - abstract
        raise NotImplementedError

    def __call__(self, sym, ctx=None):
        ctx = ctx or PassContext()
        nodes_before = _node_count(sym)
        cost_before = _graph_cost(sym, ctx) if ctx.measure_cost else None
        try:
            new_sym = self.run(sym, ctx)
        except PassError:
            raise
        except MXNetError as e:
            raise PassError("pass %r failed: %s" % (self.name, e)) from e
        changed = new_sym is not sym
        verify_seconds = 0.0
        if changed and ctx.verify:
            t0 = _time.perf_counter()
            result = verify_graph(new_sym,
                                  input_shapes=ctx.input_shapes,
                                  input_dtypes=ctx.input_dtypes)
            verify_seconds = _time.perf_counter() - t0
            if not result.ok:
                first = result.findings[0]
                raise PassError(
                    "pass %r produced an invalid graph — refusing to "
                    "hand it to the executor.  First finding: %s\n"
                    "All findings:\n%s"
                    % (self.name, first.format(), result.format()))
        nodes_after = nodes_before if not changed else _node_count(new_sym)
        cost_after = None
        if ctx.measure_cost:
            cost_after = cost_before if not changed \
                else _graph_cost(new_sym, ctx)
        _record(self.name, changed, verify_seconds, nodes_before,
                nodes_after, cost_before, cost_after)
        return new_sym

    def __repr__(self):
        return "<%s %r>" % (type(self).__name__, self.name)


class FunctionPass(Pass):
    """Wrap a ``fn(sym, ctx) -> sym`` as a Pass."""

    def __init__(self, name, fn):
        self.name = name
        self._fn = fn

    def run(self, sym, ctx):
        return self._fn(sym, ctx)


class _Sequential(Pass):
    def __init__(self, passes, name="sequential"):
        self.name = name
        self.passes = list(passes)

    def run(self, sym, ctx):  # pragma: no cover - __call__ overridden
        raise NotImplementedError

    def __call__(self, sym, ctx=None):
        ctx = ctx or PassContext()
        for p in self.passes:
            sym = p(sym, ctx)
        return sym


def sequential(passes, name="sequential"):
    """Compose passes left-to-right; each is individually verified."""
    return _Sequential(passes, name=name)
