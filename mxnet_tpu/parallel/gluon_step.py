"""Functional sharded training step built from a Gluon block.

This is the flagship TPU training path: the whole train step —
forward, loss, backward, optimizer update, BatchNorm running-stat
update — is ONE jitted SPMD computation over a device mesh.  The
reference splits this across GraphExecutor fwd/bwd + KVStore push/pull
+ python optimizer updates (SURVEY.md §3.1/§3.4); GSPMD inserts the
gradient all-reduce over the 'dp' mesh axis automatically, riding ICI.

ZeRO weight-update sharding (``zero=True`` / ``MXNET_TPU_ZERO=1``,
Xu et al. arXiv:2004.13336): instead of every device holding the full
replicated parameters + optimizer state, each parameter is flattened,
padded to a multiple of the 'dp' axis size n, and laid out as 1-D
shards — each device owns exactly 1/n of every parameter and of every
optimizer-state leaf (state is *born* on that layout, never
materialized replicated).  Inside the one donated program the flat
shards are constrained to replicated for the forward (GSPMD emits the
param all-gather, overlapped with forward compute), the backward's
summed gradients are constrained back to the 1/n layout (the
reduce-scatter; on some backends GSPMD expresses it as
all-reduce + slice — semantically identical), and the optimizer update
runs elementwise on the shards.  The math is unchanged — elementwise
updates commute with sharding — so the step is bit-exact vs the
unsharded dp step.  Docs: docs/ZERO.md.

``optimizer=`` accepts any ``compiled_step_safe`` Optimizer (SGD, NAG,
Signum, Adam, Adamax, FTML, Ftrl, RMSProp, AdaGrad, AdaDelta): the
real fused-kernel update is traced into the step, with per-step
scalars (scheduler lr, bias corrections, t) refilled host-side each
call — the compiled_step.py protocol.  The default stays the fused
sgd-momentum closure.

Used by bench.py, __graft_entry__.py and the multi-chip Trainer path.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os

import numpy as _np

from .. import autograd
from .. import health as _health
from .. import profiler as _profiler
from .. import random as _random
from .. import runtime_stats as _rts
from .. import xray as _xray
from ..base import MXNetError
from ..gluon.block import staged_call
from ..ndarray import NDArray

__all__ = ["GluonTrainStep", "GluonStep", "sgd_momentum_init",
           "sgd_momentum_update", "zero_env_enabled"]


def zero_env_enabled():
    """True when ``MXNET_TPU_ZERO=1`` asks training wiring to run the
    ZeRO weight-update-sharded step (docs/ZERO.md)."""
    return os.environ.get("MXNET_TPU_ZERO") == "1"


def _padded_size(size, n):
    """``size`` rounded up to a multiple of ``n`` — the flat-shard
    granularity (each of the n devices owns padded/n elements)."""
    return -(-size // n) * n


def _pure_loss_builder(block, loss_block, trainable, aux,
                       aux_loss_weight=None):
    """Build loss(train_vals, aux_vals, x, y, key) -> (loss, new_aux).

    aux_loss_weight: when set, ``weight * block.collect_aux_losses()``
    (MoE load-balancing etc.) is added to the task loss INSIDE the
    staged step — the ergonomic channel replacing hand-written loss
    Blocks that stash the net to reach its aux losses."""

    def pure_loss(train_vals, aux_vals, x, y, key):
        override = {p: NDArray(v) for p, v in zip(trainable, train_vals)}
        override.update({p: NDArray(v) for p, v in zip(aux, aux_vals)})

        def fwd(x_nd):
            out = block(x_nd)
            with _xray.scope(_xray.REGION_LOSS):
                loss = loss_block(out, NDArray(y))
                loss = loss.mean()
                if aux_loss_weight is not None:
                    loss = loss \
                        + aux_loss_weight * block.collect_aux_losses()
            return loss

        loss, scope = staged_call(fwd, override, key, (NDArray(x),))
        new_aux = tuple(
            scope.aux_updates.get(p, override[p]._data) for p in aux)
        return loss._data, new_aux

    return pure_loss


def sgd_momentum_init(train_vals):
    import jax.numpy as jnp

    return tuple(jnp.zeros_like(v) for v in train_vals)


def sgd_momentum_update(lr, momentum=0.9, wd=0.0):
    """Fused SGD(+momentum, +wd) matching the reference semantics
    (src/operator/optimizer_op.cc sgd_mom_update)."""

    def update(train_vals, grads, states):
        new_vals, new_states = [], []
        for w, g, s in zip(train_vals, grads, states):
            g = g + wd * w
            s = momentum * s + g
            new_vals.append((w - lr * s).astype(w.dtype))
            new_states.append(s)
        return tuple(new_vals), tuple(new_states)

    return update


def _global_grad_norm(grads):
    """Fused global grad L2 norm over RAVELED f32 views — the same
    reduction shape on the dp and ZeRO paths (full vs flat-padded
    grads; the pads are exact zeros), so the two paths' health
    trajectories agree bit for bit."""
    import jax.numpy as jnp

    if not grads:
        return jnp.zeros((), jnp.float32)
    total = None
    for g in grads:
        s = jnp.sum(jnp.square(jnp.ravel(g).astype(jnp.float32)))
        total = s if total is None else total + s
    return jnp.sqrt(total)


class _OptimizerUpdate:
    """The real fused-kernel ``Optimizer`` traced into the functional
    step — compiled_step.py's updater-tracing idiom, functional-state
    edition.

    State trees are discovered from 1-element probe weights, never a
    full-size replicated materialization: that is what lets the ZeRO
    path allocate the real leaves directly onto their 1/n shard layout
    (state sharded from step 0).  Probe leaves must be zero-initialized
    — true for every compiled-step-safe optimizer; anything else would
    need a replicated materialization first and raises instead.
    Per-step scalars (scheduler lr, Adam bias correction, ``t``) are
    recomputed host-side each step by :meth:`host_scalars` and enter
    the jitted program as traced arguments via ``scalar_feed``, so
    schedules never recompile and eager vs functional numerics agree
    to the bit.
    """

    def __init__(self, optimizer, dtypes):
        import jax.numpy as jnp

        from ..compiled_step import _state_leaves

        if not getattr(optimizer, "compiled_step_safe", False):
            raise MXNetError(
                "GluonTrainStep(optimizer=...): %s is not compiled-step "
                "safe (host syncs, cross-step host recurrences, or raw "
                "host-scalar math in update()) — see compiled_step.py "
                "for the supported set" % type(optimizer).__name__)
        self.opt = optimizer
        self.templates = []        # per-index probe state tree
        self.leaf_dtypes = []      # per-index [leaf dtype, ...]
        for i, dt in enumerate(dtypes):
            probe = optimizer.create_state(i, NDArray(jnp.zeros((1,), dt)))
            leaves = []
            _state_leaves(probe, leaves)
            for nd in leaves:
                if float(_np.asarray(nd._data).sum()) != 0.0:
                    raise MXNetError(
                        "GluonTrainStep: %s state for parameter %d is "
                        "not zero-initialized — it cannot be allocated "
                        "directly onto a shard layout"
                        % (type(optimizer).__name__, i))
            self.templates.append(probe)
            self.leaf_dtypes.append([nd._data.dtype for nd in leaves])
        self.slots = [(i, name) for i in range(len(dtypes))
                      for name in sorted(optimizer.step_scalars(i))]

    def init_state(self, alloc):
        """Flat state-leaf tuple via ``alloc(param_index, leaf_dtype)``
        — the caller chooses placement (ZeRO passes jitted zeros with
        sharded out_shardings, so leaves are born 1/n per device)."""
        return tuple(alloc(i, dt)
                     for i, dts in enumerate(self.leaf_dtypes)
                     for dt in dts)

    def host_scalars(self):
        """Advance the host step counters and refill every per-step
        scalar slot — one float per (index, name) — for the next call."""
        opt = self.opt
        table = {}
        for i in range(len(self.templates)):
            opt._update_count(i)
            table[i] = opt.step_scalars(i)
        return tuple(float(table[i][name]) for i, name in self.slots)

    def apply(self, train_vals, grads, state_vals, scalars):
        """Traced: run the real ``update()`` on NDArray views of the
        traced values; returns (new train values, new state leaves)."""
        from ..compiled_step import _rebuild_state, _state_leaves
        from ..optimizer import optimizer as _optmod

        it = iter(state_vals)
        traced = [_rebuild_state(t, it) for t in self.templates]
        feed = {(i, name): scalars[k]
                for k, (i, name) in enumerate(self.slots)}
        new_vals = []
        with _optmod.scalar_feed(feed):
            for j, (w, g) in enumerate(zip(train_vals, grads)):
                w_nd, g_nd = NDArray(w), NDArray(g)
                self.opt.update(j, w_nd, g_nd, traced[j])
                new_vals.append(w_nd._data)
        new_state = []
        for t in traced:
            leaves = []
            _state_leaves(t, leaves)
            new_state.extend(nd._data for nd in leaves)
        return tuple(new_vals), tuple(new_state)


def _put(vals, shard):
    """Place functional values onto their shardings up front: committed
    single-device arrays cannot be implicitly resharded by jit, and
    this also avoids a first-step transfer.  jnp.array(copy=True)
    first: device_put to an equivalent sharding aliases the source
    buffer, and the first donated step would then delete the Gluon
    Parameter's own array out from under the user."""
    import jax
    import jax.numpy as jnp

    vals = tuple(jnp.array(v, copy=True) for v in vals)
    if isinstance(shard, tuple):
        return tuple(jax.device_put(v, s) for v, s in zip(vals, shard))
    return tuple(jax.device_put(v, shard) for v in vals)


def _cast_floating(x, dtype):
    """The batch in the compute dtype; token ids and other integer inputs
    stay what they are."""
    import jax.numpy as jnp

    return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x


def _is_models(order):
    return order == tuple(range(len(order)))


def _in_order(v, order):
    """``v`` with its dimensions in ``order``, major first."""
    import jax.numpy as jnp

    return v if _is_models(order) else jnp.transpose(v, order)


def _in_model_order(stored, order):
    """Inverse of :func:`_in_order`."""
    return _in_order(stored, tuple(int(i) for i in _np.argsort(order)))


def _shard_in_order(shard, order):
    """``shard`` of a leaf for the same leaf held in ``order``."""
    from jax.sharding import NamedSharding, PartitionSpec as _P

    spec = tuple(shard.spec) + (None,) * (len(order) - len(shard.spec))
    return NamedSharding(shard.mesh, _P(*(spec[i] for i in order)))


def _orders_dir():
    """Where the orders a step learned are kept: jax's persistent compile
    cache directory, None if there is none."""
    import jax

    return jax.config.jax_compilation_cache_dir


def _read_orders(path, held):
    """The orders kept at ``path`` if they fit the trees ``held``."""
    try:
        with open(path) as f:
            orders = tuple(tuple(tuple(o) for o in tree)
                           for tree in json.load(f))
    except (OSError, ValueError, TypeError):
        return None
    fits = len(orders) == len(held) and all(
        len(tree) == len(vals) and all(
            sorted(o) == list(range(v.ndim)) for o, v in zip(tree, vals))
        for tree, vals in zip(orders, held))
    return orders if fits else None


def _write_orders(path, orders):
    try:
        with open(path + ".part", "w") as f:
            json.dump(orders, f)
        os.replace(path + ".part", path)
    except OSError:     # a cache that cannot be written to: learn again
        pass


def _state_tree(i, doc):
    """One of the three state trees of a ``GluonTrainStep``: read and
    assigned in the model's shapes, kept as the step holds it."""

    def get(self):
        held = self._held[i]
        if self._orders is None:
            return held
        return tuple(_in_model_order(s, o)
                     for s, o in zip(held, self._orders[i]))

    def put(self, vals):
        vals = tuple(vals)
        if self._orders is not None:
            vals = tuple(_in_order(v, o)
                         for v, o in zip(vals, self._orders[i]))
        self._held[i] = vals

    return property(get, put, doc=doc)


class GluonTrainStep:
    """Compile a Gluon block + loss + optimizer into one sharded step.

    Parameters live as jax arrays in this object (functional style); call
    ``sync_to_params()`` to write them back into the block's Parameters
    for checkpointing with the normal Gluon API.

    Where the state lives, and in which order: ``train_vals``,
    ``opt_state`` and ``aux_vals`` are donated to every step and rebound
    to its results.  On the replicated/dp path each leaf is *held* with
    its dimensions in the order the step program's compiler would lay it
    out in (a convolution weight's update is fused into the fusion that
    computes its gradient, which writes another layout than the runtime's
    default for the model's shape): at the first call the step is
    compiled once ahead of time with ``Layout.AUTO`` on the state, only
    to read that order per leaf (the answer is kept beside jax's
    persistent compile cache, where there is one, and read from there
    by later processes); the state is transposed into it, once; and the
    program that runs takes and returns the state in that order, in the
    runtime's default layout, with free transposes at its edges.
    So no step copies a weight or its momentum between layouts, and
    nothing depends on a non-default layout surviving the persistent
    compile cache (an executable loaded from it does not keep one).  The
    three attributes read in the model's shapes whatever the order held
    (a leaf held in another order is transposed back on reading).  The
    ZeRO path holds flat 1-D shards, for which there is nothing to choose.

    compute_dtype: 'bfloat16' casts activations/weights for the matmul/
    conv path while keeping master weights and the update fp32 — the
    TPU-native analog of the reference's multi-precision SGD
    (mp_sgd_update, src/operator/optimizer_op.cc).

    zero: weight-update sharding (module docstring) — params and
    optimizer state live as flat 1/n 'dp' shards; default from
    ``MXNET_TPU_ZERO``.  ``self.zero_layout`` describes the layout and
    the per-step collective bytes (also fed into the
    ``zero_allgather_bytes`` / ``zero_reduce_bytes`` runtime counters).

    optimizer: a ``compiled_step_safe`` Optimizer instance traced into
    the step (the real fused-kernel update); None keeps the fused
    sgd-momentum closure built from ``lr/momentum/wd``.
    """

    train_vals = _state_tree(0, "The trainable parameters' values.")
    opt_state = _state_tree(1, "The optimizer's state leaves.")
    aux_vals = _state_tree(2, "The values of the parameters that take no "
                              "gradient (batch-norm statistics).")

    def __init__(self, block, loss_block, mesh=None, lr=0.1, momentum=0.9,
                 wd=0.0, compute_dtype=None, param_spec_fn=None,
                 data_spec=None, label_spec=None, aux_loss_weight=None,
                 zero=None, optimizer=None):
        import jax
        from jax.sharding import NamedSharding

        from .mesh import (data_parallel_sharding, get_default_mesh,
                           replicated_sharding)

        self.block = block
        self.mesh = mesh or get_default_mesh()
        self._zero = zero_env_enabled() if zero is None else bool(zero)
        if self._zero and param_spec_fn is not None:
            raise MXNetError(
                "GluonTrainStep: zero=True owns the parameter layout "
                "(flat 1-D 'dp' shards) and cannot compose with "
                "param_spec_fn tensor sharding")
        params = list(block.collect_params().values())
        self.trainable = [p for p in params if p.grad_req != "null"]
        self.aux = [p for p in params if p.grad_req == "null"]
        self._held = [tuple(p.data().data_jax for p in self.trainable), (),
                      tuple(p.data().data_jax for p in self.aux)]
        if optimizer is not None:
            self._opt_update = _OptimizerUpdate(
                optimizer, [v.dtype for v in self._held[0]])
            self._update = None
        else:
            self._opt_update = None
            self._update = sgd_momentum_update(lr, momentum, wd)
        self._compute_dtype = compute_dtype
        self.last_grad_norm = None
        self._step = None
        self._calls = 0        # step_num of the next mxtpu.step span
        self._leaves = None    # array arguments of one launch
        self._orders = None    # per state leaf, the order it is held in
        self._relaid = 0       # leaves held in another order than the model's
        pure_loss = _pure_loss_builder(block, loss_block, self.trainable,
                                       self.aux,
                                       aux_loss_weight=aux_loss_weight)

        repl = replicated_sharding(self.mesh)
        x_shard = (NamedSharding(self.mesh, data_spec) if data_spec is not None
                   else data_parallel_sharding(self.mesh, 1))
        if label_spec is not None:
            y_shard = NamedSharding(self.mesh, label_spec)
        elif data_spec is not None and len(data_spec):
            # labels are rank-1: shard them along the data spec's batch axis
            from jax.sharding import PartitionSpec as _P
            y_shard = NamedSharding(self.mesh, _P(data_spec[0]))
        elif data_spec is not None:
            y_shard = x_shard  # P(): replicated batch -> replicated labels
        else:
            y_shard = x_shard
        # place batch-sharded inputs via these shardings
        self.batch_sharding = x_shard
        self.label_sharding = y_shard
        self._repl = repl

        if self._zero:
            self._build_zero(pure_loss, compute_dtype, repl,
                             x_shard, y_shard)
        else:
            self._build_classic(pure_loss, compute_dtype, repl,
                                x_shard, y_shard, param_spec_fn)

    # ------------------------------------------------- replicated/dp path
    def _build_classic(self, pure_loss, cast, repl, x_shard, y_shard,
                       param_spec_fn):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        opt_update = self._opt_update
        update = self._update

        if param_spec_fn is None:
            tv_shard = aux_shard = repl
        else:
            # per-parameter shardings (tensor parallelism etc.) — the
            # optimizer state mirrors the parameter sharding
            tv_shard = tuple(
                NamedSharding(self.mesh, param_spec_fn(p.name, p.shape))
                for p in self.trainable)
            aux_shard = tuple(
                NamedSharding(self.mesh, param_spec_fn(p.name, p.shape))
                for p in self.aux)
        if opt_update is None:
            self.opt_state = sgd_momentum_init(self.train_vals)
            state_shard = tv_shard
        else:
            shapes = [v.shape for v in self.train_vals]
            self.opt_state = opt_update.init_state(
                lambda i, dt: jnp.zeros(shapes[i], dt))
            if param_spec_fn is None:
                state_shard = repl
            else:
                # one sharding per state leaf, mirroring its parameter
                state_shard = tuple(
                    tv_shard[i]
                    for i, dts in enumerate(opt_update.leaf_dtypes)
                    for _ in dts)

        def fwd_bwd(train_vals, aux_vals, x, y, key):
            def loss_of(tv):
                if cast is not None:
                    tv = tuple(v.astype(cast) if v.dtype == _np.float32
                               else v for v in tv)
                    x_ = _cast_floating(x, cast)
                else:
                    x_ = x
                return pure_loss(tv, aux_vals, x_, y, key)

            with _xray.scope(_xray.GRAD_MARKER):
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_vals)
            grads = tuple(g.astype(v.dtype)
                          for g, v in zip(grads, train_vals))
            return loss, grads, new_aux, _global_grad_norm(grads)

        if opt_update is None:
            def step(train_vals, opt_state, aux_vals, x, y, key):
                loss, grads, new_aux, gnorm = fwd_bwd(
                    train_vals, aux_vals, x, y, key)
                with _xray.scope(_xray.REGION_OPT):
                    new_vals, new_state = update(train_vals, grads,
                                                 opt_state)
                return loss, new_vals, new_state, new_aux, gnorm

            sig_in = (tv_shard, state_shard, aux_shard, x_shard, y_shard,
                      repl)
        else:
            def step(train_vals, opt_state, aux_vals, x, y, key, scalars):
                loss, grads, new_aux, gnorm = fwd_bwd(
                    train_vals, aux_vals, x, y, key)
                with _xray.scope(_xray.REGION_OPT):
                    new_vals, new_state = opt_update.apply(
                        train_vals, grads, opt_state, scalars)
                return loss, new_vals, new_state, new_aux, gnorm

            sig_in = (tv_shard, state_shard, aux_shard, x_shard, y_shard,
                      repl, repl)

        def per_leaf(shard, vals):
            return shard if isinstance(shard, tuple) else (shard,) * len(vals)

        self.train_vals = _put(self.train_vals, tv_shard)
        # fresh and the step's own, so placed without _put's copy: Adam's
        # state of a model that fills the chip does not fit there twice
        self.opt_state = tuple(map(
            jax.device_put, self.opt_state,
            per_leaf(state_shard, self.opt_state)))
        self.aux_vals = _put(self.aux_vals, aux_shard)

        self._state_shard = tuple(
            per_leaf(shard, vals) for shard, vals in zip(
                (tv_shard, state_shard, aux_shard), self._held))
        self._rest_in = sig_in[3:]
        # un-jitted; composed by make_chained().  self._step is built by
        # _adopt_orders() at the first call: the order the state is held
        # in (class docstring) needs the batch's shape to be learned
        self._step_py = step

    def _compilers_orders(self, x, y, rest):
        """Per state leaf, its dimensions in the order (major first) the
        compiler lays it out in when the choice is its own: the step
        compiled ahead of time with ``Layout.AUTO`` on the state, and
        dropped once read."""
        import jax
        from jax.experimental.layout import Format, Layout

        auto = jax.tree.map(lambda shard: Format(Layout.AUTO, shard),
                            self._state_shard)

        def spec(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        def asked(*args):
            # a function of its own: jax keeps what it compiles for as
            # long as the function lives, and this program is only read
            return self._step_py(*args)

        compiled = jax.jit(
            asked, in_shardings=(*auto, *self._rest_in),
            out_shardings=(self._repl, *auto, self._repl),
            donate_argnums=(0, 1, 2)).lower(
            *jax.tree.map(spec, tuple(self._held)), spec(x), spec(y),
            *rest).compile()
        # the results': the layout the fused update writes
        return tuple(
            tuple(tuple(f.layout.major_to_minor) for f in tree)
            for tree in compiled.output_formats[1:4])

    def _orders_path(self, x, y):
        """The file that keeps what ``_compilers_orders`` answers for a
        batch like (x, y), named after what the answer can depend on;
        None where no compile cache is kept.  (With the answer kept, a
        process whose programs all come from the cache loads the step
        once, as before, and not the program that is only read too.)"""
        import jax

        cache = _orders_dir()
        if not cache:
            return None
        chip = self.mesh.devices.flat[0]
        asked = repr((
            jax.__version__, chip.client.platform_version, chip.device_kind,
            tuple(self.mesh.shape.items()), type(self.block).__name__,
            type(getattr(self._opt_update, "opt", None)).__name__,
            str(self._compute_dtype),
            [p.name for p in self.trainable + self.aux],
            [(v.shape, str(v.dtype), str(shard.spec))
             for tree, shards in zip(self._held, self._state_shard)
             for v, shard in zip(tree, shards)],
            x.shape, str(x.dtype), y.shape, str(y.dtype)))
        return os.path.join(cache, "mxtpu-step-orders-%s.json"
                            % hashlib.sha256(asked.encode()).hexdigest()[:32])

    def _adopt_orders(self, x, y, rest):
        """Once, at the first call: learn the compiler's order of every
        state leaf (or read what an earlier process learned), transpose
        the state into it and build the jitted step that takes and
        returns it so."""
        import jax

        path = self._orders_path(x, y)
        orders = path and _read_orders(path, self._held)
        if not orders:
            orders = self._compilers_orders(x, y, rest)
            if path:
                _write_orders(path, orders)
        self._orders = orders
        # one program moves the leaves whose order is not the model's
        moving = [(i, j) for i, tree in enumerate(orders)
                  for j, order in enumerate(tree) if not _is_models(order)]
        if moving:
            moved = dict(zip(moving, jax.jit(
                lambda *leaves: tuple(
                    _in_order(v, orders[i][j])
                    for v, (i, j) in zip(leaves, moving)),
                out_shardings=tuple(
                    _shard_in_order(self._state_shard[i][j], orders[i][j])
                    for i, j in moving))(
                *(self._held[i][j] for i, j in moving))))
            self._held = [
                tuple(moved.get((i, j), v) for j, v in enumerate(tree))
                for i, tree in enumerate(self._held)]
        self._relaid = len(moving)
        _rts.inc("step_state_relayouts")
        self._step = self._jit(self._step_py, 1)

    def _jit(self, fn, n_tail):
        """jit ``fn(train_vals, opt_state, aux_vals, x, y, key[, scalars])
        -> (loss, train_vals, opt_state, aux_vals, *tail)`` on the state
        as it is held: donated, each leaf transposed to the model's order
        on the way in and back on the way out (free: the held order is
        the order the compiler lays the leaf out in)."""
        import jax

        orders = self._orders
        shards = tuple(
            tuple(_shard_in_order(s, o) for s, o in zip(tree, tree_orders))
            for tree, tree_orders in zip(self._state_shard, orders))

        def reorder(one, state):
            return tuple(
                tuple(one(v, o) for v, o in zip(tree, tree_orders))
                for tree, tree_orders in zip(state, orders))

        @functools.wraps(fn)
        def on_held(train_vals, opt_state, aux_vals, *rest):
            loss, *out = fn(*reorder(_in_model_order,
                                     (train_vals, opt_state, aux_vals)),
                            *rest)
            return (loss, *reorder(_in_order, out[:3]), *out[3:])

        return jax.jit(
            on_held,
            in_shardings=(*shards, *self._rest_in),
            # pin outputs to the input layouts: the functional state must
            # keep its sharding across steps (otherwise the compiler may
            # re-shard e.g. a bias, and step 2's in_shardings reject it)
            out_shardings=(self._repl, *shards) + (self._repl,) * n_tail,
            donate_argnums=(0, 1, 2),
        )

    # ------------------------------------------------- ZeRO sharded path
    def _build_zero(self, pure_loss, cast, repl, x_shard, y_shard):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as _P

        opt_update = self._opt_update
        update = self._update
        mesh = self.mesh
        n = int(mesh.shape["dp"])
        flat_shard = NamedSharding(mesh, _P("dp"))
        self._flat_shard = flat_shard

        layout = []
        for p, v in zip(self.trainable, self.train_vals):
            size = int(v.size)
            layout.append({"name": p.name,
                           "shape": tuple(int(s) for s in v.shape),
                           "dtype": str(v.dtype), "size": size,
                           "padded": _padded_size(size, n)})

        def _flat_put(v, meta):
            flat = _np.zeros((meta["padded"],), _np.dtype(meta["dtype"]))
            flat[:meta["size"]] = _np.asarray(v).reshape(-1)
            return jax.device_put(flat, flat_shard)

        self.train_vals = tuple(
            _flat_put(v, m) for v, m in zip(self.train_vals, layout))
        self.aux_vals = _put(self.aux_vals, repl)

        # optimizer state is BORN on the shard layout — a jitted zeros
        # with sharded out_shardings allocates 1/n per device directly;
        # the replicated full-size state never exists at any point
        def _shard_zeros(padded, dtype):
            return jax.jit(lambda: jnp.zeros((padded,), dtype),
                           out_shardings=flat_shard)()

        if opt_update is not None:
            self.opt_state = opt_update.init_state(
                lambda i, dt: _shard_zeros(layout[i]["padded"], dt))
            leaves_per = [len(d) for d in opt_update.leaf_dtypes]
            leaf_dtypes = [[str(d) for d in dts]
                           for dts in opt_update.leaf_dtypes]
        else:
            self.opt_state = tuple(
                _shard_zeros(m["padded"], _np.dtype(m["dtype"]))
                for m in layout)
            leaves_per = [1] * len(layout)
            leaf_dtypes = [[m["dtype"]] for m in layout]

        isz = [_np.dtype(m["dtype"]).itemsize for m in layout]
        gather_bytes = sum(m["padded"] * s for m, s in zip(layout, isz))
        self.zero_layout = {
            "n": n,
            "params": layout,
            "state_leaves": leaves_per,
            "state_dtypes": leaf_dtypes,
            # logical collective payload per step: every param is
            # gathered once for the forward and its grad reduced once
            # into the shard layout
            "per_step_allgather_bytes": gather_bytes,
            "per_step_reduce_bytes": gather_bytes,
            "replicated_param_bytes": sum(
                m["size"] * s for m, s in zip(layout, isz)),
            "per_device_param_bytes": sum(
                m["padded"] // n * s for m, s in zip(layout, isz)),
            "per_device_state_bytes": sum(
                m["padded"] // n * s * l
                for m, s, l in zip(layout, isz, leaves_per)),
        }

        sizes = [m["size"] for m in layout]
        shapes = [m["shape"] for m in layout]
        wsc = jax.lax.with_sharding_constraint

        def fwd_bwd(train_flat, aux_vals, x, y, key):
            def loss_of(tf):
                # the param all-gather: constraining each flat shard to
                # replicated makes GSPMD materialize the full value on
                # every device inside this one program, overlapped with
                # forward compute
                with _xray.scope(_xray.REGION_ZERO_AG):
                    tv = tuple(
                        wsc(f, repl)[:size].reshape(shape)
                        for f, size, shape in zip(tf, sizes, shapes))
                if cast is not None:
                    tv = tuple(v.astype(cast) if v.dtype == _np.float32
                               else v for v in tv)
                    x_ = _cast_floating(x, cast)
                else:
                    x_ = x
                return pure_loss(tv, aux_vals, x_, y, key)

            with _xray.scope(_xray.GRAD_MARKER):
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_flat)
            # norm over the still-replicated grads: identical reduction
            # to the dp path's, so health trajectories match bit-exact
            with _xray.scope(_xray.REGION_ZERO_GNORM):
                gnorm = _global_grad_norm(grads)
            # the reduce-scatter: the backward's dp-summed grads are
            # constrained back to the 1/n flat layout — each device
            # keeps only the shard its update needs (GSPMD may lower
            # this as all-reduce + slice on backends without a fused
            # reduce-scatter; the data movement is semantically the
            # ZeRO reduce-scatter either way)
            with _xray.scope(_xray.REGION_ZERO_RS):
                grads = tuple(wsc(g.astype(f.dtype), flat_shard)
                              for g, f in zip(grads, train_flat))
            return loss, grads, new_aux, gnorm

        if opt_update is None:
            def step(train_flat, opt_flat, aux_vals, x, y, key):
                loss, grads, new_aux, gnorm = fwd_bwd(
                    train_flat, aux_vals, x, y, key)
                # elementwise update on the 1/n shards (pads carry
                # exact zeros through: zero grad -> zero update)
                with _xray.scope(_xray.REGION_OPT):
                    new_vals, new_state = update(train_flat, grads,
                                                 opt_flat)
                return loss, new_vals, new_state, new_aux, gnorm

            sig_in = (flat_shard, flat_shard, repl, x_shard, y_shard,
                      repl)
        else:
            def step(train_flat, opt_flat, aux_vals, x, y, key, scalars):
                loss, grads, new_aux, gnorm = fwd_bwd(
                    train_flat, aux_vals, x, y, key)
                with _xray.scope(_xray.REGION_OPT):
                    new_vals, new_state = opt_update.apply(
                        train_flat, grads, opt_flat, scalars)
                return loss, new_vals, new_state, new_aux, gnorm

            sig_in = (flat_shard, flat_shard, repl, x_shard, y_shard,
                      repl, repl)

        self._step_py = step
        self._step = jax.jit(
            step,
            in_shardings=sig_in,
            out_shardings=(repl, flat_shard, flat_shard, repl, repl),
            donate_argnums=(0, 1, 2),
        )

    # --------------------------------------------------------- execution
    def make_chained(self, n_steps):
        """Jit n_steps training steps as ONE device computation.

        One host dispatch covers the whole chain (lax.fori_loop carrying
        the functional state), so per-call host overhead is paid
        once per n_steps instead of once per step — the device-only
        timing primitive bench.py's device metric is built on (the
        same chaining trick as tools/bench_device_latency.py, extended
        to the full fwd+bwd+update+BN-stat step).  The per-iteration RNG
        key is fold_in(key, i), so chained(n) visits the same key
        sequence regardless of chain depth.

        The param/optimizer/aux carry is DONATED into the chain (like
        the single-step path): without donation XLA must keep the
        undonated inputs alive across the whole fori_loop, doubling
        peak param+optimizer memory.  Donation invalidates the input
        buffers, so the final carry is written back into this object's
        state — chained(n) advances training exactly like n ``__call__``
        steps (same fold_in key schedule) and repeat calls keep working.

        Works in both layouts (the ZeRO chain carries the flat shards);
        not with ``optimizer=``: its per-step scalars are refilled
        host-side each step and cannot cross a fori_loop.

        Returns fn(x, y, key) -> last_loss.
        """
        import jax
        import jax.numpy as jnp
        from jax import lax

        if self._opt_update is not None:
            raise MXNetError(
                "make_chained: per-step optimizer scalars (schedules, "
                "bias corrections) are refilled host-side each step and "
                "cannot cross a fori_loop chain; use optimizer=None "
                "(the fused sgd-momentum closure) for chained "
                "micro-benchmarks")

        step = self._step_py

        def chained(train_vals, opt_state, aux_vals, x, y, key):
            def body(i, carry):
                tv, os_, av, _ = carry
                loss, tv, os_, av, _gn = step(tv, os_, av, x, y,
                                              jax.random.fold_in(key, i))
                # fp32 carry regardless of compute dtype (bf16 steps
                # return a bf16 loss; the carry structure must be fixed)
                return (tv, os_, av, loss.astype(jnp.float32))

            init = (train_vals, opt_state, aux_vals,
                    jnp.zeros((), jnp.float32))
            tv, os_, av, loss = lax.fori_loop(0, n_steps, body, init)
            return loss, tv, os_, av

        def run(x, y, key):
            if run._jitted is None:
                # ZeRO: flat shards, a plain jit.  Else the carry is the
                # state as it is held (class docstring)
                if self._zero:
                    run._jitted = jax.jit(chained, donate_argnums=(0, 1, 2))
                else:
                    if self._orders is None:
                        self._adopt_orders(x, y, (key,))
                    run._jitted = self._jit(chained, 0)
            loss, *self._held = run._jitted(*self._held, x, y, key)
            return loss

        run._jitted = None  # from the first run on; donation introspection
        return run

    def put_batch(self, x, y):
        """Place a host batch onto the mesh with the dp sharding."""
        import jax

        return (jax.device_put(_np.asarray(x), self.batch_sharding),
                jax.device_put(_np.asarray(y), self.label_sharding))

    def __call__(self, x, y):
        """One training step on device arrays/numpy; returns loss (async)."""
        import jax

        span = _profiler.boundary_span
        with span("mxtpu.step", step_num=self._calls):
            self._calls += 1
            if not isinstance(x, jax.Array):
                with span("mxtpu.step.put_batch"):
                    x, y = self.put_batch(x, y)
            with span("mxtpu.step.key"):
                key = _random.next_key()
            rest = [key]
            if self._opt_update is not None:
                with span("mxtpu.step.scalars"):
                    rest.append(self._opt_update.host_scalars())
            if self._step is None:
                self._adopt_orders(x, y, rest)
            args = [*self._held, x, y, *rest]
            if self._leaves is None:    # fixed from the first call on
                self._leaves = len(jax.tree_util.tree_leaves(args))
            with span("mxtpu.step.launch", leaves=self._leaves,
                      relaid_leaves=self._relaid):
                loss, *self._held, gnorm = self._step(*args)
            # the last references to the donated arrays: released inside
            # the span (0.7-2.4 ms a step on the chip), not after it
            del args
            self.last_grad_norm = gnorm
            if self._zero:
                zl = self.zero_layout
                _rts.inc("zero_steps")
                _rts.inc("zero_allgather_bytes",
                         zl["per_step_allgather_bytes"])
                _rts.inc("zero_reduce_bytes", zl["per_step_reduce_bytes"])
            if _health._state["on"]:
                hm = _health.monitor()
                if hm is not None:
                    hm.observe_scalar("grad_norm", gnorm)
        return loss

    def program_for(self, x, y):
        """The step compiled ahead of time for a batch like (x, y), for
        ``as_text()``, ``cost_analysis()`` and ``memory_analysis()``: the
        program ``__call__`` runs for it, compiled once more."""
        import jax

        rest = [jax.random.PRNGKey(0)]  # shape/dtype stand-in only
        if self._opt_update is not None:
            rest.append(tuple(0.0 for _ in self._opt_update.slots))
        if self._step is None:
            self._adopt_orders(x, y, rest)
        return self._step.lower(*self._held, x, y, *rest).compile()

    def sync_to_params(self):
        """Write functional values back into the Gluon Parameters.

        Values are gathered off the mesh first: the Parameters feed the
        normal eager API afterwards, and a mesh-committed array mixed
        with default-device eager operands is a placement error on
        multi-device hosts.  In the ZeRO layout each flat value is
        unpadded and reshaped back to the parameter's shape."""
        import jax.numpy as jnp

        if self._zero:
            for p, v, m in zip(self.trainable, self.train_vals,
                               self.zero_layout["params"]):
                host = jnp.asarray(
                    _np.asarray(v)[:m["size"]].reshape(m["shape"]))
                for d in p._data:
                    d._assign(host)
        else:
            for p, v in zip(self.trainable, self.train_vals):
                host = jnp.asarray(_np.asarray(v))
                for d in p._data:
                    d._assign(host)
        for p, v in zip(self.aux, self.aux_vals):
            host = jnp.asarray(_np.asarray(v))
            for d in p._data:
                d._assign(host)

    # ------------------------------------------------ sharded checkpoint
    def zero_shard_payloads(self):
        """``{rank: payload}`` for every locally-addressable 'dp'
        position — the per-rank shard files of a sharded checkpoint.
        Each payload carries exactly the 1/n slice that rank owns
        (params + optimizer-state leaves), so a rank never persists
        another rank's bytes; in a multi-host run each process sees
        only its own ranks here."""
        if not self._zero:
            raise MXNetError(
                "zero_shard_payloads: this step was not built with "
                "zero=True")
        n = self.zero_layout["n"]
        out = {}

        def collect(vals, kind):
            for j, v in enumerate(vals):
                shard_len = int(v.shape[0]) // n
                for s in v.addressable_shards:
                    rank = int(s.index[0].start or 0) // shard_len
                    slot = out.setdefault(
                        rank, {"params": {}, "state": {}})
                    slot[kind][j] = _np.asarray(s.data)

        collect(self.train_vals, "params")
        collect(self.opt_state, "state")
        return out

    def save_zero(self, step, mgr=None):
        """Commit a sharded checkpoint: one global manifest over
        per-rank shard files (``CheckpointManager.save_sharded`` — the
        rank-0 commit barrier lives there), layout metadata in the
        ``aux`` sideband so resume can re-shard."""
        from .. import checkpoint as _ckpt

        mgr = mgr if mgr is not None else _ckpt.manager()
        if mgr is None:
            raise MXNetError(
                "save_zero: no checkpoint manager — call "
                "checkpoint.enable(directory) first or pass mgr=")
        n = self.zero_layout["n"]
        files = {"zero-shard-%05d-of-%05d" % (r, n): payload
                 for r, payload in self.zero_shard_payloads().items()}
        aux = {"zero_layout": self.zero_layout}
        if self._opt_update is not None:
            # host-side optimizer hyper-state (update counts drive
            # Adam-family bias correction; schedulers drive lr) — the
            # device shards alone do not make the step resumable
            aux["optimizer"] = _ckpt._strip_optimizer(
                self._opt_update.opt)
        return mgr.save_sharded(step, files, aux=aux)

    def restore_zero(self, manifest, mgr=None):
        """Load a sharded checkpoint back into this step's flat shards,
        RE-SHARDING when the checkpoint's dp width differs from the
        current mesh (the layout-change resume path): each full flat
        vector is rebuilt from the old ranks' slices, stripped of the
        old padding, re-padded to the current multiple and placed onto
        the current 'dp' layout.  Restores the RNG stream too; returns
        the checkpoint step."""
        import jax

        from .. import checkpoint as _ckpt

        if not self._zero:
            raise MXNetError(
                "restore_zero: this step was not built with zero=True")
        mgr = mgr if mgr is not None else _ckpt.manager()
        if mgr is None:
            raise MXNetError("restore_zero: no checkpoint manager")
        aux = mgr.load_aux(manifest)
        if not aux or "zero_layout" not in aux:
            raise MXNetError(
                "restore_zero: checkpoint %s carries no zero_layout "
                "sideband — not a sharded checkpoint"
                % manifest.get("path"))
        old = aux["zero_layout"]
        ranks = mgr.load_shard_files(manifest)
        if len(ranks) != old["n"]:
            raise MXNetError(
                "restore_zero: checkpoint %s has %d of %d rank shard "
                "files" % (manifest.get("path"), len(ranks), old["n"]))
        if old["state_leaves"] != self.zero_layout["state_leaves"]:
            raise MXNetError(
                "restore_zero: optimizer state structure changed "
                "(%r leaves saved vs %r now) — restore with the same "
                "optimizer family"
                % (old["state_leaves"], self.zero_layout["state_leaves"]))

        def rebuild(kind, j, meta_old, meta_new, dtype):
            full = _np.concatenate(
                [ranks[r][kind][j] for r in range(old["n"])])
            flat = _np.zeros((meta_new["padded"],), dtype)
            flat[:meta_new["size"]] = full[:meta_old["size"]]
            return jax.device_put(flat, self._flat_shard)

        new_params = []
        for j, (mo, mn) in enumerate(zip(old["params"],
                                         self.zero_layout["params"])):
            if (mo["name"], mo["size"]) != (mn["name"], mn["size"]):
                raise MXNetError(
                    "restore_zero: parameter %d mismatch (%s/%d saved "
                    "vs %s/%d now) — the model changed"
                    % (j, mo["name"], mo["size"], mn["name"], mn["size"]))
            new_params.append(
                rebuild("params", j, mo, mn, _np.dtype(mn["dtype"])))
        self.train_vals = tuple(new_params)

        new_state = []
        leaf = 0
        for i, count in enumerate(self.zero_layout["state_leaves"]):
            mo, mn = old["params"][i], self.zero_layout["params"][i]
            for c in range(count):
                dt = _np.dtype(self.zero_layout["state_dtypes"][i][c])
                new_state.append(rebuild("state", leaf, mo, mn, dt))
                leaf += 1
        self.opt_state = tuple(new_state)
        blob = aux.get("optimizer")
        if blob is not None and self._opt_update is not None:
            import pickle

            src = pickle.loads(blob)
            hyper = dict(src.__dict__)
            hyper.pop("param_dict", None)
            self._opt_update.opt.__dict__.update(hyper)
        rng = manifest.get("rng")
        if rng:
            _random.set_state(rng)
        return int(manifest.get("step", 0))


#: ISSUE-14 spelling: ``GluonStep(..., zero=True)``
GluonStep = GluonTrainStep
