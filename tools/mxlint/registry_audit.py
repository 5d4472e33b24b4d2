"""Runtime op-registry audit — the importing half of registry-consistency.

The AST rule (checkers.py) proves what it can without importing; this
module imports ``mxnet_tpu.ops`` and audits the *actual* registry:

- every ``OP_INPUT_NAMES`` key (including entries added dynamically by
  quantization/extended/contrib modules) names a registered op;
- ``OP_AUX_INPUTS`` / ``OP_LABEL_INPUTS`` are consistent subsets;
- every op in ``OP_INPUT_NAMES`` traces under ``jax.eval_shape`` on a
  canonical input spec — proof the op stays inside the traceable
  subset with zero FLOPs and zero device memory;
- every registered op function carries a docstring (doc-less ops are
  reported; the tier-1 gate grandfathers the pre-existing ones via
  tools/mxlint/baseline.json).

**Transform conformance** (:func:`transform_audit`): beyond plain
tracing, every canonical-spec op is abstractly pushed through the two
jax transforms the rest of the stack depends on — ``jax.vjp``
(autograd/executor backward; differentiability over the non-aux float
inputs, with cotangent shapes checked against the primals) and
``jax.vmap`` (batching; the future sharding work composes through it) —
still under ``jax.eval_shape``, so the whole audit costs zero FLOPs and
zero device memory.  The per-op trace/grad/vmap verdicts form the
capability matrix rendered into docs/OP_CAPABILITIES.md by
``tools/mxlint/capabilities.py``; by-design exemptions live in
:data:`TRANSFORM_PRAGMAS`, and pre-existing failures are grandfathered
(shrink-only) in the baseline's ``transforms`` section.

Used by tests/test_lint_clean.py; also runnable standalone::

    python -m tools.mxlint.registry_audit
"""

from __future__ import annotations

__all__ = ["audit_registry", "canonical_spec", "AuditResult",
           "transform_audit", "TRANSFORM_PRAGMAS", "TRANSFORMS"]

_F32 = "float32"


def _rnn_param_len(input_size, state_size, num_layers, dirs, gates):
    """Total packed RNN parameter length (matches ops/rnn.py _unpack)."""
    total = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        for _ in range(dirs):
            total += gates * state_size * in_sz       # w_i2h
            total += gates * state_size * state_size  # w_h2h
            total += 2 * gates * state_size           # b_i2h + b_h2h
    return total


def canonical_spec(name):
    """(input_specs, attrs) for one table op, or None if unknown.

    input_specs: list of (shape, dtype) matching OP_INPUT_NAMES[name]
    order.  Shapes are minimal-but-representative: conv-like ops get
    NCHW images, sequence ops get (T, B, C), etc.
    """
    f = _F32
    i32 = "int32"
    specs = {
        "Convolution": ([((2, 3, 8, 8), f), ((4, 3, 3, 3), f), ((4,), f)],
                        {"kernel": (3, 3), "num_filter": 4}),
        "Deconvolution": ([((2, 4, 8, 8), f), ((4, 3, 3, 3), f),
                           ((3,), f)],
                          {"kernel": (3, 3), "num_filter": 3,
                           "no_bias": False}),
        "FullyConnected": ([((2, 8), f), ((4, 8), f), ((4,), f)],
                           {"num_hidden": 4}),
        "BatchNorm": ([((2, 3, 4, 4), f)] + [((3,), f)] * 4, {}),
        "LayerNorm": ([((2, 8), f), ((8,), f), ((8,), f)], {}),
        "InstanceNorm": ([((2, 3, 4, 4), f), ((3,), f), ((3,), f)], {}),
        "L2Normalization": ([((2, 8), f)], {}),
        "Embedding": ([((2, 3), i32), ((10, 4), f)],
                      {"input_dim": 10, "output_dim": 4}),
        "LeakyReLU": ([((2, 3, 4, 4), f), ((3,), f)],
                      {"act_type": "prelu"}),
        "SoftmaxOutput": ([((2, 5), f), ((2,), f)], {}),
        "choose_element_0index": ([((2, 5), f), ((2,), f)], {}),
        "fill_element_0index": ([((2, 5), f), ((2,), f), ((2,), f)], {}),
        "SVMOutput": ([((2, 5), f), ((2,), f)], {}),
        "LinearRegressionOutput": ([((2, 5), f), ((2, 5), f)], {}),
        "MAERegressionOutput": ([((2, 5), f), ((2, 5), f)], {}),
        "LogisticRegressionOutput": ([((2, 5), f), ((2, 5), f)], {}),
        "CTCLoss": ([((10, 2, 5), f), ((2, 4), f), ((2,), i32),
                     ((2,), i32)],
                    {"use_data_lengths": True, "use_label_lengths": True}),
        "SequenceMask": ([((4, 2, 3), f), ((2,), i32)],
                         {"use_sequence_length": True}),
        "SequenceLast": ([((4, 2, 3), f), ((2,), i32)],
                         {"use_sequence_length": True}),
        "SequenceReverse": ([((4, 2, 3), f), ((2,), i32)],
                            {"use_sequence_length": True}),
        "dot": ([((2, 3), f), ((3, 4), f)], {}),
        "batch_dot": ([((2, 3, 4), f), ((2, 4, 5), f)], {}),
        "where": ([((2, 3), f), ((2, 3), f), ((2, 3), f)], {}),
        "take": ([((5, 3), f), ((2,), i32)], {}),
        "ROIPooling": ([((1, 3, 8, 8), f), ((2, 5), f)],
                       {"pooled_size": (2, 2), "spatial_scale": 1.0}),
        "BilinearSampler": ([((1, 3, 8, 8), f), ((1, 2, 4, 4), f)], {}),
        "GridGenerator": ([((1, 6), f)],
                          {"transform_type": "affine",
                           "target_shape": (4, 4)}),
        "SpatialTransformer": ([((1, 3, 8, 8), f), ((1, 6), f)],
                               {"target_shape": (4, 4)}),
        "RNN": ([((4, 2, 3), f),
                 ((_rnn_param_len(3, 4, 1, 1, 1),), f),
                 ((1, 2, 4), f), ((1, 2, 4), f)],
                {"state_size": 4, "num_layers": 1, "mode": "rnn_tanh"}),
        "_contrib_rms_norm": ([((2, 5, 8), f), ((8,), f)], {}),
        "_contrib_rope": ([((2, 3, 5, 8), f)], {"theta": 1e4}),
        "_contrib_gated_silu": ([((2, 5, 8), f), ((12, 8), f), ((12, 8), f),
                                 ((8, 12), f)], {}),
        "_contrib_mla_qkv": ([((2, 5, 8), f), ((6, 8), f), ((12, 6), f),
                              ((6, 8), f), ((16, 4), f), ((6,), f),
                              ((4,), f)], {"num_heads": 2, "theta": 1e4}),
        "_contrib_mla_out": ([((2, 2, 5, 4), f), ((8, 8), f)], {}),
        "_contrib_gqa_qkv": ([((2, 5, 8), f), ((16, 8), f), ((8, 8), f),
                              ((8, 8), f), ((4,), f), ((4,), f)],
                             {"theta": 1e4}),
        "_contrib_gqa_out": ([((2, 4, 5, 4), f), ((8, 16), f)], {}),
        "_contrib_head_gate": ([((2, 4, 5, 4), f), ((2, 5, 8), f),
                                ((4, 8), f)], {}),
        "_contrib_gated_short_conv": ([((2, 5, 24), f), ((8, 3), f)], {}),
        "_contrib_moe_route": ([((6, 8), f), ((12, 8), f), ((12,), f)],
                               {"k": 3, "scale": 2.5}),
        "_contrib_moe_experts": ([((6, 8), f), ((6, 3), i32), ((6, 3), f),
                                  ((4, 8, 5), f), ((4, 8, 5), f),
                                  ((4, 5, 8), f)],
                                 {"first_expert": 4, "tile": 4}),
        "_contrib_linear_cross_entropy": ([((8, 6), f), ((11, 6), f),
                                           ((8,), i32)], {"chunk": 4}),
        "_contrib_hc_coefficients": ([((2, 5, 16), f), ((16, 24), f),
                                      ((24,), f), ((3,), f)],
                                     {"streams": 4, "iters": 3}),
        "_contrib_hc_pre_mix": ([((2, 5, 16), f), ((4, 2, 5), f)], {}),
        "_contrib_hc_post_mix": ([((2, 5, 16), f), ((4, 4, 2, 5), f),
                                  ((4, 2, 5), f), ((2, 5, 4), f)], {}),
        "_contrib_quantize": ([((2, 3), f), ((1,), f), ((1,), f)], {}),
        "_contrib_quantize_v2": ([((2, 3), f)],
                                 {"min_calib_range": -1.0,
                                  "max_calib_range": 1.0}),
        "_contrib_dequantize": ([((2, 3), "int8"), ((1,), f),
                                 ((1,), f)], {}),
        "_contrib_requantize": ([((2, 3), "int32"), ((1,), f), ((1,), f)],
                                {"min_calib_range": -1.0,
                                 "max_calib_range": 1.0}),
        "_contrib_quantized_fully_connected": (
            [((2, 8), "uint8"), ((4, 8), "int8"), ((4,), "int8")]
            + [((1,), f)] * 6,
            {"num_hidden": 4}),
        "_contrib_quantized_conv": (
            [((1, 3, 8, 8), "uint8"), ((4, 3, 3, 3), "int8"),
             ((4,), "int8")] + [((1,), f)] * 6,
            {"kernel": (3, 3), "num_filter": 4, "stride": (1, 1),
             "pad": (0, 0), "dilate": (1, 1)}),
        "_contrib_quantized_pooling": (
            [((1, 3, 8, 8), "uint8"), ((1,), f), ((1,), f)],
            {"kernel": (2, 2), "stride": (2, 2), "pad": (0, 0),
             "pool_type": "max"}),
        "_contrib_quantized_flatten": (
            [((2, 3, 4), "uint8"), ((1,), f), ((1,), f)], {}),
        "_contrib_Proposal": (
            [((1, 24, 4, 4), f), ((1, 48, 4, 4), f), ((1, 3), f)],
            {"rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4}),
        "_contrib_PSROIPooling": (
            [((1, 12, 8, 8), f), ((2, 5), f)],
            {"output_dim": 3, "pooled_size": 2, "group_size": 2}),
        "_contrib_DeformableConvolution": (
            [((1, 3, 8, 8), f), ((1, 18, 6, 6), f), ((4, 3, 3, 3), f),
             ((4,), f)],
            {"kernel": (3, 3), "num_filter": 4}),
        "Correlation": ([((1, 3, 8, 8), f), ((1, 3, 8, 8), f)],
                        {"kernel_size": 1, "max_displacement": 1}),
        "group_adagrad_update": ([((4, 3), f), ((4, 3), f), ((4,), f)],
                                 {}),
    }
    return specs.get(name)


class AuditResult:
    """Outcome of audit_registry(): lists of problem strings."""

    __slots__ = ("table_errors", "shape_errors", "missing_docstrings")

    def __init__(self):
        self.table_errors = []       # table <-> registry inconsistencies
        self.shape_errors = []       # eval_shape failures / missing specs
        self.missing_docstrings = []  # (op_name, fn_name) doc-less ops

    @property
    def ok(self):
        return not (self.table_errors or self.shape_errors)


def audit_registry(eval_shapes=True, matrix=None):
    """Audit the live registry; importing mxnet_tpu.ops as needed.

    ``matrix``: an already-computed :func:`transform_audit` result to
    derive the eval_shape verdicts from — callers running both audits
    (the tier-1 gate, :func:`main`) pass it so each op is traced once,
    not once per audit.  When omitted and ``eval_shapes`` is true, the
    transform audit is computed here."""
    from mxnet_tpu.ops import registry as R

    res = AuditResult()
    registered = set(R._OP_REGISTRY)

    # --- table cross-checks (authoritative: includes dynamic entries)
    for key in R.OP_INPUT_NAMES:
        if key not in registered:
            res.table_errors.append(
                "OP_INPUT_NAMES key %r is not a registered op" % key)
    for key, aux in R.OP_AUX_INPUTS.items():
        if key not in R.OP_INPUT_NAMES:
            res.table_errors.append(
                "OP_AUX_INPUTS key %r missing from OP_INPUT_NAMES" % key)
            continue
        extra = [n for n in aux if n not in R.OP_INPUT_NAMES[key]]
        if extra:
            res.table_errors.append(
                "OP_AUX_INPUTS[%r] names %r not in OP_INPUT_NAMES[%r]"
                % (key, extra, key))
    for key in R.OP_LABEL_INPUTS:
        if key not in R.OP_INPUT_NAMES:
            res.table_errors.append(
                "OP_LABEL_INPUTS key %r missing from OP_INPUT_NAMES" % key)

    # --- docstring coverage over canonical ops
    seen = set()
    for op in R._OP_REGISTRY.values():
        if op.name in seen:
            continue
        seen.add(op.name)
        if not (op.fn.__doc__ or "").strip():
            res.missing_docstrings.append((op.name, op.fn.__name__))
    res.missing_docstrings.sort()

    # --- eval_shape: every table op must trace on its canonical spec.
    # The actual tracing lives in transform_audit (whose "trace"
    # verdict is exactly this check); missing specs are reported here.
    if eval_shapes:
        if matrix is None:
            matrix = transform_audit()
        for name in sorted(R.OP_INPUT_NAMES):
            if name not in registered:
                continue  # already a table error above
            if canonical_spec(name) is None:
                res.shape_errors.append(
                    "no canonical eval_shape spec for table op %r — add "
                    "one to tools/mxlint/registry_audit.py" % name)
                continue
            verdict, detail = matrix.get(name, {}).get(
                "trace", ("fail", "op not audited"))
            if verdict == "fail":
                res.shape_errors.append(
                    "eval_shape(%s) failed: %s" % (name, detail))
    return res


# ------------------------------------------------- transform conformance

TRANSFORMS = ("trace", "grad", "vmap")

# By-design transform exemptions: {op: {"grad"|"vmap": one-line reason}}.
# A pragma here is the runtime analog of `# mxlint: disable=...` — it
# renders as "pragma" in the capability matrix instead of ✗ and is NOT
# grandfathering: the reason must hold by construction, not by history.
TRANSFORM_PRAGMAS = {}


def _diff_argnums(name, input_specs, key_offset):
    """Positions (into the full arg list) the vjp differentiates:
    non-aux, float-dtype tensor inputs.  The PRNG key (when present)
    and integer inputs (indices, lengths) are never gradient targets,
    matching the executor's grad_req handling."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import registry as R

    names = R.OP_INPUT_NAMES[name]
    aux = set(R.OP_AUX_INPUTS.get(name, ()))
    nums = []
    for i, (_shape, dtype) in enumerate(input_specs):
        if names[i] in aux:
            continue
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            continue
        nums.append(key_offset + i)
    return nums


def _check_grad(fn, args, argnums):
    """eval_shape the op's vjp over `argnums`; cotangent shapes must
    round-trip to the primal shapes.  Returns None or an error string."""
    import jax
    import jax.numpy as jnp

    def run(*all_args):
        def f(*diff):
            full = list(all_args)
            for j, d in zip(argnums, diff):
                full[j] = d
            return fn(*full)

        out, vjp_fn = jax.vjp(f, *[all_args[i] for i in argnums])
        cot = jax.tree_util.tree_map(jnp.ones_like, out)
        return vjp_fn(cot)

    try:
        grads = jax.eval_shape(run, *args)
    except Exception as e:
        return "%s: %s" % (type(e).__name__, str(e).split("\n")[0][:200])
    for j, g in zip(argnums, grads):
        if tuple(g.shape) != tuple(args[j].shape):
            return ("cotangent shape %s does not match primal %s for "
                    "input %d" % (tuple(g.shape), tuple(args[j].shape), j))
    return None


def _check_vmap(fn, args, batch=2):
    """eval_shape the op under jax.vmap on a leading batch axis; every
    output must carry the batch dimension."""
    import jax

    batched = [jax.ShapeDtypeStruct((batch,) + tuple(a.shape), a.dtype)
               for a in args]
    try:
        out = jax.eval_shape(jax.vmap(fn), *batched)
    except Exception as e:
        return "%s: %s" % (type(e).__name__, str(e).split("\n")[0][:200])
    leaves = jax.tree_util.tree_leaves(out)
    for leaf in leaves:
        if not leaf.shape or leaf.shape[0] != batch:
            return ("output %s lost the batch axis (expected leading %d)"
                    % (tuple(leaf.shape), batch))
    return None


def transform_audit():
    """Trace/grad/vmap conformance for every canonical-spec table op.

    Returns ``{op_name: {"trace"|"grad"|"vmap": (verdict, detail)}}``
    with verdict one of ``"ok"`` / ``"fail"`` / ``"pragma"`` / ``"n/a"``
    (no differentiable inputs).  Abstract-only: zero FLOPs, zero device
    memory — cheap enough to ride tier-1 on CPU.
    """
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ndarray.ndarray import RANDOM_OPS
    from mxnet_tpu.ops import registry as R

    matrix = {}
    registered = set(R._OP_REGISTRY)
    for name in sorted(R.OP_INPUT_NAMES):
        if name not in registered:
            continue  # a table error, reported by audit_registry()
        spec = canonical_spec(name)
        if spec is None:
            continue  # a shape error, reported by audit_registry()
        input_specs, attrs = spec
        op = R.get(name)
        attrs = op.canonicalize_attrs(attrs)
        fn = op.bind_attrs(attrs)
        args = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
                for s, d in input_specs]
        key_offset = 0
        if name in RANDOM_OPS:
            k = jax.random.PRNGKey(0)
            args = [jax.ShapeDtypeStruct(tuple(k.shape), k.dtype)] + args
            key_offset = 1
        caps = {}
        pragmas = TRANSFORM_PRAGMAS.get(name, {})
        # trace
        try:
            jax.eval_shape(fn, *args)
            caps["trace"] = ("ok", "")
            traced = True
        except Exception as e:
            caps["trace"] = ("fail", "%s: %s"
                             % (type(e).__name__,
                                str(e).split("\n")[0][:200]))
            traced = False
        # grad
        if "grad" in pragmas:
            caps["grad"] = ("pragma", pragmas["grad"])
        elif not traced:
            caps["grad"] = ("fail", "op does not trace")
        else:
            argnums = _diff_argnums(name, input_specs, key_offset)
            if not argnums:
                caps["grad"] = ("n/a", "no differentiable inputs")
            else:
                err = _check_grad(fn, args, argnums)
                caps["grad"] = ("ok", "") if err is None else ("fail", err)
        # vmap
        if "vmap" in pragmas:
            caps["vmap"] = ("pragma", pragmas["vmap"])
        elif not traced:
            caps["vmap"] = ("fail", "op does not trace")
        else:
            err = _check_vmap(fn, args)
            caps["vmap"] = ("ok", "") if err is None else ("fail", err)
        matrix[name] = caps
    return matrix


def main(argv=None):
    import argparse
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    p = argparse.ArgumentParser(
        prog="python -m tools.mxlint.registry_audit",
        description="Runtime audit of the mxnet_tpu op registry.")
    p.add_argument("--update-baseline", action="store_true",
                   help="grandfather the current doc-less ops into "
                        "tools/mxlint/baseline.json (registry section) "
                        "and the current transform failures "
                        "(transforms section)")
    args = p.parse_args(argv)
    matrix = transform_audit()
    res = audit_registry(matrix=matrix)  # ops traced once, not twice
    for e in res.table_errors + res.shape_errors:
        print("audit: %s" % e)
    tfails = {"grad": [], "vmap": []}
    for name, caps in sorted(matrix.items()):
        for t in ("grad", "vmap"):
            verdict, detail = caps[t]
            if verdict != "fail":
                continue
            print("transform: %s under %s: %s" % (name, t, detail))
            # a trace-collapsed op is a shape error (gated above), not
            # a grad/vmap grandfather candidate — once its trace bug is
            # fixed, genuine transform defects must still surface
            if detail != "op does not trace":
                tfails[t].append(name)
    print("registry audit: %d table error(s), %d eval_shape error(s), "
          "%d op(s) without docstrings, %d transform failure(s) over "
          "%d op(s)"
          % (len(res.table_errors), len(res.shape_errors),
             len(res.missing_docstrings),
             sum(len(v) for v in tfails.values()), len(matrix)))
    from .cli import DEFAULT_BASELINE

    if args.update_baseline:
        from .findings import (save_registry_grandfather,
                               save_transform_grandfather)

        save_registry_grandfather(
            DEFAULT_BASELINE, [n for n, _ in res.missing_docstrings])
        save_transform_grandfather(DEFAULT_BASELINE, tfails)
        print("baseline registry section updated: %d op name(s), "
              "transforms section: %d grad / %d vmap failure(s) -> %s"
              % (len(res.missing_docstrings), len(tfails["grad"]),
                 len(tfails["vmap"]), DEFAULT_BASELINE))
        return 0 if res.ok else 1
    # exit code mirrors the tier-1 gate: non-grandfathered transform
    # failures fail the standalone run too (rc-checking CI pipelines
    # must not need the pytest gate to catch a grad/vmap regression)
    tnew = 0
    allowed = {}
    if os.path.exists(DEFAULT_BASELINE):
        from .findings import load_transform_grandfather

        allowed = load_transform_grandfather(DEFAULT_BASELINE)
    for t in ("grad", "vmap"):
        tnew += len(set(tfails[t]) - allowed.get(t, set()))
    return 0 if (res.ok and tnew == 0) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
