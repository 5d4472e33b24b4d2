"""Shape/dtype-inference conformance: the symbolic layer vs XLA.

For every registered table op with a canonical input spec
(tools/mxlint/registry_audit.canonical_spec), build a one-node Symbol
over explicit variables and cross-check:

* ``Symbol.infer_shape`` output shapes == direct ``jax.eval_shape`` on
  the op's bound fn over the spec avals (PRNG key prepended for random
  ops, exactly as the executor does);
* ``Symbol.infer_type`` output dtypes == the dtypes the same trace
  actually produces;
* ``verify_graph`` abstract interpretation agrees (clean, all nodes
  traced) when seeded with the spec shapes AND dtypes.

Known divergences are pragma'd in :data:`DTYPE_GAPS` with a reason and
enforced stale: when an op stops diverging, the test fails until its
pragma is removed.  This keeps the three shape/dtype oracles in this
repo — infer_shape/infer_type, the graph verifier, and XLA itself —
provably in sync as ops are added.
"""

import numpy as np
import pytest

import mxnet_tpu  # noqa: F401 - populates the op registry
from mxnet_tpu.ops import registry as R
from mxnet_tpu.symbol.symbol import Symbol, _Node
from mxnet_tpu.symbol.verify import verify_graph
from tools.mxlint.registry_audit import canonical_spec

# ops whose infer_type output dtypes are KNOWN not to match the traced
# dtypes, with the reason.  infer_type models the classic f32 training
# graph (int8 only for the "_quantize"-suffixed offline params); the
# int8 quantization ops produce integer activations that the coarse
# name-contract model does not represent.  Stale-pragma enforced below.
DTYPE_GAPS = {
    "_contrib_quantize": "produces uint8 activations; infer_type "
                         "models f32 graphs + int8 offline params only",
    "_contrib_quantize_v2": "produces int8 activations",
    "_contrib_requantize": "int32 accumulators -> int8 activations",
    "_contrib_quantized_conv": "int8 operands -> int32 accumulator out",
    "_contrib_quantized_fully_connected": "int8 operands -> int32 "
                                          "accumulator out",
    "_contrib_quantized_pooling": "uint8 in, uint8 out",
    "_contrib_quantized_flatten": "uint8 in, uint8 out",
    "_contrib_moe_route": "expert ids are int32 whatever the scores' type",
}

# shape-side gaps: none today — every canonical-spec op's infer_shape
# matches XLA.  Keep the dict (and its stale enforcement) so the first
# future divergence must be declared, not silently skipped.
SHAPE_GAPS = {}


def _spec_ops():
    return [name for name in sorted(R.OP_INPUT_NAMES)
            if name in R._OP_REGISTRY and canonical_spec(name) is not None]


def _one_node_symbol(name):
    """One-node Symbol over fresh variables matching the spec slots.

    Returns (symbol, {var name: shape}, {var name: dtype}, expected
    output avals from a direct jax.eval_shape of the bound op fn).
    """
    import jax

    from mxnet_tpu.ndarray.ndarray import RANDOM_OPS

    input_specs, attrs = canonical_spec(name)
    op = R.get(name)
    canon = op.canonicalize_attrs(attrs)
    fn = op.bind_attrs(canon)
    avals = [jax.ShapeDtypeStruct(tuple(s), np.dtype(d))
             for s, d in input_specs]
    full = avals
    if name in RANDOM_OPS:
        k = jax.random.PRNGKey(0)
        full = [jax.ShapeDtypeStruct(tuple(k.shape), k.dtype)] + avals
    out = jax.eval_shape(fn, *full)
    outs = list(out) if isinstance(out, (tuple, list)) else [out]
    try:
        nout = op.nout(canon)
    except Exception:
        nout = len(outs)
    slots = R.OP_INPUT_NAMES[name]
    variables = [_Node(None, "cf_%s_%s" % (name, slots[i]), {}, [], 1)
                 for i in range(len(input_specs))]
    node = _Node(name, "cf_%s" % name, canon,
                 [(v, 0) for v in variables], nout)
    sym = Symbol([(node, i) for i in range(nout)])
    shapes = {v.name: tuple(sp[0])
              for v, sp in zip(variables, input_specs)}
    dtypes = {v.name: np.dtype(sp[1])
              for v, sp in zip(variables, input_specs)}
    return sym, shapes, dtypes, outs


@pytest.mark.parametrize("name", _spec_ops())
def test_infer_shape_matches_eval_shape(name):
    sym, shapes, _dtypes, outs = _one_node_symbol(name)
    expected = [tuple(o.shape) for o in outs]
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(**shapes)
    assert all(s is not None for s in arg_shapes + aux_shapes), \
        (arg_shapes, aux_shapes)
    matches = out_shapes == expected
    if name in SHAPE_GAPS:
        assert not matches, (
            "%s now infers shapes exactly — remove its stale SHAPE_GAPS "
            "pragma (%r)" % (name, SHAPE_GAPS[name]))
        return
    assert matches, "infer_shape %s != eval_shape %s" % (out_shapes,
                                                         expected)


@pytest.mark.parametrize("name", _spec_ops())
def test_infer_type_matches_traced_dtypes(name):
    sym, _shapes, _dtypes, outs = _one_node_symbol(name)
    expected = [np.dtype(o.dtype) for o in outs]
    _arg_t, out_t, _aux_t = sym.infer_type()
    matches = [np.dtype(t) for t in out_t] == expected
    if name in DTYPE_GAPS:
        assert not matches, (
            "%s now infers output dtypes exactly — remove its stale "
            "DTYPE_GAPS pragma (%r)" % (name, DTYPE_GAPS[name]))
        return
    assert matches, \
        "infer_type %s != traced %s" % ([str(t) for t in out_t],
                                        [str(t) for t in expected])


@pytest.mark.parametrize("name", _spec_ops())
def test_verifier_agrees_on_canonical_spec(name):
    """The graph verifier's abstract interpretation (which seeds dtypes,
    unlike infer_shape's all-f32 model) must trace every canonical-spec
    op cleanly — including the quantize family the dtype model can't."""
    sym, shapes, dtypes, _outs = _one_node_symbol(name)
    r = verify_graph(sym, input_shapes=shapes, input_dtypes=dtypes)
    assert r.ok, [f.format() for f in r.findings]
    assert r.evaluated == 1 and r.skipped == [], (r.evaluated, r.skipped)


def test_every_gap_names_a_spec_op():
    """Pragmas must point at live canonical-spec ops — a renamed or
    deleted op must not leave a dangling gap entry behind."""
    ops = set(_spec_ops())
    for gap in (DTYPE_GAPS, SHAPE_GAPS):
        stale = sorted(set(gap) - ops)
        assert not stale, "gap pragmas for unknown ops: %s" % stale
