"""nd.contrib — control flow + contrib op namespace.

Reference: python/mxnet/ndarray/contrib.py (foreach, while_loop, cond)
over src/operator/control_flow.cc:1255,1316,1378.

TPU-native: instead of CachedOp subgraph nodes, the body is traced once
and lowered to lax.scan / lax.while_loop / lax.cond — the exact XLA
constructs the reference ops were designed to mirror (SURVEY.md §2.1
'Control-flow ops': "maps directly to XLA scan/while/cond").  Eager
semantics are preserved: inputs/outputs are NDArrays.
"""

from __future__ import annotations

from .ndarray import NDArray

__all__ = ["foreach", "while_loop", "cond"]


def _wrap(v, ctx):
    return NDArray(v, ctx)


def _unwrap(x):
    return x._data if isinstance(x, NDArray) else x


def _tree_unwrap(xs):
    if isinstance(xs, (list, tuple)):
        return [_tree_unwrap(x) for x in xs]
    return _unwrap(xs)


def _tree_wrap(vs, ctx):
    if isinstance(vs, (list, tuple)):
        return [_tree_wrap(v, ctx) for v in vs]
    return _wrap(vs, ctx)


def foreach(body, data, init_states):
    """Run `body(data_i, states) -> (out, new_states)` over axis 0 of
    data, stacking outputs (reference: contrib.foreach / _foreach op).

    Lowers to one lax.scan — the whole loop compiles to a single XLA
    While with the body fused.  Under autograd.record() it runs as an
    eager Python loop instead, so every op (including uses of
    closed-over Parameters) lands on the tape — exactly the
    reference's imperative foreach (python/mxnet/ndarray/contrib.py),
    whose eager path is a plain for loop.
    """
    import jax
    from jax import lax

    from .. import autograd as _ag

    single_data = isinstance(data, NDArray)
    ctx = (data if single_data else data[0])._ctx

    if _ag.is_recording():
        from . import stack as _stack

        def tree_slice(d, i):
            if isinstance(d, (list, tuple)):
                return [tree_slice(v, i) for v in d]
            return d[i]

        def tree_stack(rows_):
            if isinstance(rows_[0], (list, tuple)):
                return [tree_stack([r[k] for r in rows_])
                        for k in range(len(rows_[0]))]
            return _stack(*rows_, axis=0)

        def first_leaf(d):
            while isinstance(d, (list, tuple)):
                d = d[0]
            return d

        n = first_leaf(data).shape[0]
        states = init_states
        rows = []
        for i in range(n):
            out, states = body(tree_slice(data, i), states)
            rows.append(out)
        if not rows:
            return [], states
        return tree_stack(rows), states

    xs = _tree_unwrap(data)
    init = _tree_unwrap(init_states)

    def scan_body(carry, x):
        states_nd = _tree_wrap(carry, ctx)
        x_nd = _tree_wrap(x, ctx)
        out, new_states = body(x_nd, states_nd)
        return _tree_unwrap(new_states), _tree_unwrap(out)

    carry, ys = lax.scan(scan_body, init, xs)
    outs = _tree_wrap(ys, ctx)
    states = _tree_wrap(carry, ctx)
    return outs, states


def while_loop(cond, func, loop_vars, max_iterations=None):
    """reference: contrib.while_loop / _while_loop op.

    cond(*loop_vars) -> boolean scalar; func(*loop_vars) ->
    (step_output, new_loop_vars).  Per the reference, outputs are
    stacked into a max_iterations-capacity buffer (rows past the actual
    iteration count are undefined in the reference; zeros here).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    if max_iterations is None:
        raise ValueError("max_iterations is required (static bound for XLA)")
    max_iterations = int(max_iterations)
    ctx = loop_vars[0]._ctx
    init = [_unwrap(v) for v in loop_vars]

    if not any(isinstance(v, jax.core.Tracer) for v in init):
        # eager semantics (reference python/mxnet/ndarray/contrib.py
        # while_loop): a plain Python loop — func runs only while cond
        # holds; if cond is never satisfied, outputs are empty (the
        # reference documents exactly this asymmetry vs symbolic mode)
        from .. import autograd as _ag

        recording = _ag.is_recording()
        vars_ = list(loop_vars)
        rows = []
        steps = 0
        while steps < max_iterations and bool(np.asarray(_unwrap(cond(*vars_)))):
            out, new_vars = func(*vars_)
            # func may carry no per-step outputs (the reference accepts
            # an empty list; None is the natural Python spelling)
            out = ([] if out is None
                   else out if isinstance(out, (list, tuple)) else [out])
            # keep NDArray rows when recording so the stacked outputs
            # stay on the tape; raw values otherwise
            rows.append(list(out) if recording
                        else [_unwrap(o) for o in out])
            new_vars = new_vars if isinstance(new_vars, (list, tuple)) else [new_vars]
            vars_ = [v if isinstance(v, NDArray) else _wrap(v, ctx)
                     for v in new_vars]
            steps += 1
        outs = []
        if rows and recording:
            from . import stack as _stack
            from . import zeros as _zeros

            for k in range(len(rows[0])):
                row_k = [r[k] if isinstance(r[k], NDArray)
                         else _wrap(r[k], ctx) for r in rows]
                pad = [_zeros(tuple(row_k[0].shape), ctx=ctx,
                              dtype=row_k[0].dtype)
                       for _ in range(max_iterations - steps)]
                outs.append(_stack(*(row_k + pad), axis=0))
        elif rows:
            for k in range(len(rows[0])):
                buf = jnp.zeros((max_iterations,) + tuple(rows[0][k].shape),
                                rows[0][k].dtype)
                for i, row in enumerate(rows):
                    buf = buf.at[i].set(row[k])
                outs.append(_wrap(buf, ctx))
        return outs, list(vars_)

    # traced: output structure via abstract evaluation — func is never
    # executed on real data (shapes only), then one lax.while_loop
    def _probe(*vs):
        out, _ = func(*_tree_wrap(list(vs), ctx))
        out = ([] if out is None
               else out if isinstance(out, (list, tuple)) else [out])
        return [_unwrap(o) for o in out]

    probe_out = jax.eval_shape(_probe, *init)

    bufs = [jnp.zeros((max_iterations,) + tuple(o.shape),
                      dtype=o.dtype) for o in probe_out]

    def cond_fn(state):
        i, vars_, _ = state
        c = cond(*_tree_wrap(list(vars_), ctx))
        return jnp.logical_and(i < max_iterations,
                               _unwrap(c).astype(bool).reshape(()))

    def body_fn(state):
        i, vars_, bufs_ = state
        out, new_vars = func(*_tree_wrap(list(vars_), ctx))
        out = ([] if out is None
               else out if isinstance(out, (list, tuple)) else [out])
        new_bufs = tuple(b.at[i].set(_unwrap(o)) for b, o in zip(bufs_, out))
        return (i + 1, tuple(_unwrap(v) for v in new_vars), new_bufs)

    i, final_vars, final_bufs = lax.while_loop(
        cond_fn, body_fn, (jnp.asarray(0), tuple(init), tuple(bufs)))
    outs = [_wrap(b, ctx) for b in final_bufs]
    return outs, [_wrap(v, ctx) for v in final_vars]


def cond(pred, then_func, else_func):
    """reference: contrib.cond / _cond op → lax.cond.

    Eager (concrete pred): only the selected branch runs, matching the
    reference's imperative semantics.  Traced: lax.cond.
    """
    import jax
    import numpy as np
    from jax import lax

    p = _unwrap(pred)
    ctx = pred._ctx if isinstance(pred, NDArray) else None

    if not isinstance(p, jax.core.Tracer):
        return then_func() if bool(np.asarray(p)) else else_func()

    def t(_):
        return _tree_unwrap(then_func())

    def e(_):
        return _tree_unwrap(else_func())

    res = lax.cond(p.astype(bool).reshape(()), t, e, None)
    return _tree_wrap(res, ctx)


def _install_contrib_ops(namespace):
    """Expose contrib-registered ops as nd.contrib.* (reference: the
    _contrib_ C++ prefix populating ndarray/contrib.py)."""
    from ..ops import registry as _reg
    from . import register as _register

    names = [n for n in _reg.list_ops()
             if n in ("box_nms", "box_iou", "MultiBoxPrior", "MultiBoxTarget",
                      "MultiBoxDetection", "ROIAlign", "_contrib_Proposal",
                      "_contrib_PSROIPooling",
                      "_contrib_DeformableConvolution",
                      "BilinearResize2D",
                      "AdaptiveAvgPooling2D", "boolean_mask", "quadratic",
                      "arange_like", "getnnz", "index_copy", "index_add",
                      "adamw_update", "_contrib_flash_attention",
                      "_contrib_div_sqrt_dim",
                      "_contrib_interleaved_matmul_selfatt_qk",
                      "_contrib_interleaved_matmul_selfatt_valatt",
                      "_contrib_rms_norm", "_contrib_rope",
                      "_contrib_gated_silu", "_contrib_mla_qkv",
                      "_contrib_mla_out", "_contrib_gqa_qkv",
                      "_contrib_gqa_out", "_contrib_head_gate",
                      "_contrib_gated_short_conv",
                      "_contrib_moe_route",
                      "_contrib_moe_experts",
                      "_contrib_linear_cross_entropy",
                      "_contrib_hc_coefficients", "_contrib_hc_pre_mix",
                      "_contrib_hc_post_mix")]
    _register.populate(namespace, names)
    return namespace


_install_contrib_ops(globals())
