"""Functional sharded training step built from a Gluon block.

This is the flagship TPU training path: the whole train step —
forward, loss, backward, optimizer update, BatchNorm running-stat
update — is ONE jitted SPMD computation over a device mesh.  The
reference splits this across GraphExecutor fwd/bwd + KVStore push/pull
+ python optimizer updates (SURVEY.md §3.1/§3.4); GSPMD inserts the
gradient all-reduce over the 'dp' mesh axis automatically, riding ICI.

``zero=True`` holds parameters and optimizer state as flat 1/n 'dp'
shards instead (ZeRO weight-update sharding: ``_FlatShards``,
docs/ZERO.md).

``optimizer=`` accepts any ``compiled_step_safe`` Optimizer (SGD, NAG,
Signum, Adam, Adamax, FTML, Ftrl, RMSProp, AdaGrad, AdaDelta): the
real fused-kernel update is traced into the step, with per-step
scalars (scheduler lr, bias corrections, t) refilled host-side each
call — the compiled_step.py protocol.  The default stays the fused
sgd-momentum rule, its hyper-parameters constants of the program.

Used by the benchmark's cells (``benchmark/entries``), chip_smoke.py,
__graft_entry__.py and ``trainer.compile(..., zero=True)``.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os

import numpy as _np

from .. import health as _health
from .. import profiler as _profiler
from .. import random as _random
from .. import runtime_stats as _rts
from .. import xray as _xray
from ..base import MXNetError
from ..gluon.block import staged_call
from ..ndarray import NDArray

__all__ = ["GluonTrainStep"]


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


class _UpdateRule:
    """Which rule updates the parameters inside the step program.

    ``leaf_dtypes``: per parameter, the dtypes of its state leaves (every
    leaf starts as zeros of the parameter's shape, wherever the step
    holds it).  ``slots``: the (parameter index, name) of every per-step
    scalar the host refills; none by default, and an empty tuple of
    scalars adds no parameter to the program.  ``apply(train_vals, grads,
    state_vals, scalars)`` is traced into the step and returns (new
    values, new state leaves)."""

    slots = ()
    opt = None      # the Optimizer whose host side a checkpoint keeps

    def init_state(self, alloc):
        """Flat state-leaf tuple via ``alloc(param_index, leaf_dtype)``
        — the caller chooses placement (ZeRO passes jitted zeros with
        sharded out_shardings, so leaves are born 1/n per device)."""
        return tuple(alloc(i, dt)
                     for i, dts in enumerate(self.leaf_dtypes)
                     for dt in dts)

    def host_scalars(self):
        """The values of ``slots`` for the next call."""
        return ()

    def host_state(self):
        """What of the rule lives on the host, for a checkpoint."""
        return None

    def load_host_state(self, blob):
        pass


class _FusedSGD(_UpdateRule):
    """Fused SGD(+momentum, +wd) matching the reference semantics
    (src/operator/optimizer_op.cc sgd_mom_update): one momentum leaf per
    parameter in the parameter's dtype, ``lr`` / ``momentum`` / ``wd``
    constants of the program."""

    def __init__(self, lr, momentum, wd, dtypes):
        self.lr, self.momentum, self.wd = lr, momentum, wd
        self.leaf_dtypes = [[dt] for dt in dtypes]

    def apply(self, train_vals, grads, state_vals, scalars):
        new_vals, new_states = [], []
        for w, g, s in zip(train_vals, grads, state_vals):
            g = g + self.wd * w
            s = self.momentum * s + g
            new_vals.append((w - self.lr * s).astype(w.dtype))
            new_states.append(s)
        return tuple(new_vals), tuple(new_states)


class _OptimizerUpdate(_UpdateRule):
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
    to the bit.  An optimizer that knows no parameters yet is given
    ``params`` as ``gluon.Trainer`` gives them, so a Parameter's
    ``lr_mult`` and ``wd_mult`` count in those scalars.
    """

    def __init__(self, optimizer, dtypes, params):
        import jax.numpy as jnp

        from ..compiled_step import _state_leaves

        if not getattr(optimizer, "compiled_step_safe", False):
            raise MXNetError(
                "GluonTrainStep(optimizer=...): %s is not compiled-step "
                "safe (host syncs, cross-step host recurrences, or raw "
                "host-scalar math in update()) — see compiled_step.py "
                "for the supported set" % type(optimizer).__name__)
        self.opt = optimizer
        if not optimizer.param_dict:
            optimizer.param_dict = dict(enumerate(params))
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

    def host_scalars(self):
        """Advance the host step counters and refill every per-step
        scalar slot — one float per (index, name) — for the next call."""
        opt = self.opt
        table = {}
        with _profiler.boundary_span("mxtpu.step.scalars"):
            for i in range(len(self.templates)):
                opt._update_count(i)
                table[i] = opt.step_scalars(i)
            return tuple(float(table[i][name]) for i, name in self.slots)

    def host_state(self):
        """The optimizer's hyper-state (update counts drive Adam-family
        bias correction; schedulers drive lr): the device shards alone
        do not make the step resumable."""
        from .. import checkpoint as _ckpt

        return _ckpt._strip_optimizer(self.opt)

    def load_host_state(self, blob):
        import pickle

        hyper = dict(pickle.loads(blob).__dict__)
        hyper.pop("param_dict", None)
        self.opt.__dict__.update(hyper)

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


def _values(params):
    return tuple(p.data().data_jax for p in params)


def _put(vals, shards):
    """Place functional values onto their shardings up front: committed
    single-device arrays cannot be implicitly resharded by jit, and
    this also avoids a first-step transfer.  jnp.array(copy=True)
    first: device_put to an equivalent sharding aliases the source
    buffer, and the first donated step would then delete the Gluon
    Parameter's own array out from under the user."""
    import jax
    import jax.numpy as jnp

    return tuple(jax.device_put(jnp.array(v, copy=True), s)
                 for v, s in zip(vals, shards))


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


class _InCompilersOrder:
    """The form the state is held in, replicated or ``param_spec_fn``-
    sharded: every leaf in the model's shape on the mesh, its dimensions
    in the order the step program's compiler lays it out in
    (``GluonTrainStep``'s docstring)."""

    def __init__(self, mesh, param_spec_fn):
        self._mesh, self._spec_fn = mesh, param_spec_fn

    def place(self, trainable, aux, rule):
        """-> [train_vals, opt_state, aux_vals] on the mesh, the optimizer
        state born there.  Sets ``shards``: per tree, per leaf, its
        sharding in the model's order."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from .mesh import replicated_sharding

        repl = replicated_sharding(self._mesh)

        def shard(p):
            # per-parameter shardings (tensor parallelism etc.)
            if self._spec_fn is None:
                return repl
            return NamedSharding(self._mesh, self._spec_fn(p.name, p.shape))

        tv_shard = tuple(shard(p) for p in trainable)
        train_vals = _values(trainable)
        shapes = [v.shape for v in train_vals]
        # fresh and the step's own, so placed without _put's copy: Adam's
        # state of a model that fills the chip does not fit there twice
        state = rule.init_state(lambda i, dt: jax.device_put(
            jnp.zeros(shapes[i], dt), tv_shard[i]))
        # one sharding per state leaf, mirroring its parameter
        self.shards = (tv_shard,
                       tuple(tv_shard[i]
                             for i, dts in enumerate(rule.leaf_dtypes)
                             for _ in dts),
                       tuple(shard(p) for p in aux))
        return [_put(train_vals, tv_shard), state,
                _put(_values(aux), self.shards[2])]

    # traced into the step program
    def models(self, held):
        """What is held of the parameters, in the model's shapes."""
        return held

    def for_update(self, grads, held):
        """-> (the gradients as the update rule takes them, their norm)."""
        grads = tuple(g.astype(v.dtype) for g, v in zip(grads, held))
        return grads, _global_grad_norm(grads)

    # on the host
    def orders(self, held, learn):
        """Per state leaf, the order it is held in: the compiler's."""
        _rts.inc("step_state_relayouts")
        return learn()

    def params_on_host(self, train_vals):
        return [_np.asarray(v) for v in train_vals]

    def after_step(self):
        pass


class _FlatShards:
    """The ZeRO form (weight-update sharding, Xu et al. arXiv:2004.13336):
    instead of every device holding the full replicated parameters +
    optimizer state, each parameter is flattened, padded to a multiple
    of the 'dp' axis size n, and laid out as 1-D shards — each device
    owns exactly 1/n of every parameter and of every optimizer-state
    leaf (state is *born* on that layout, never materialized
    replicated); batch-norm statistics stay replicated.  Inside the one
    donated program the flat shards are constrained to replicated for
    the forward (GSPMD emits the param all-gather, overlapped with
    forward compute), the backward's summed gradients are constrained
    back to the 1/n layout (the reduce-scatter; on some backends GSPMD
    expresses it as all-reduce + slice — semantically identical), and
    the optimizer update runs elementwise on the shards (pads carry
    exact zeros through: zero grad -> zero update).  The math is
    unchanged — elementwise updates commute with sharding — so the step
    is bit-exact vs the unsharded dp step.  ``zero_layout`` describes
    the layout and the per-step collective bytes; the sharded
    checkpoint is written and read here.  Docs: docs/ZERO.md."""

    def __init__(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from .mesh import replicated_sharding

        self._n = int(mesh.shape["dp"])
        self._repl = replicated_sharding(mesh)
        self._flat = NamedSharding(mesh, _P("dp"))

    def _flat_put(self, flat, meta, dtype):
        """``flat`` (its first ``size`` elements) padded onto the layout."""
        import jax

        padded = _np.zeros((meta["padded"],), dtype)
        padded[:meta["size"]] = flat[:meta["size"]]
        return jax.device_put(padded, self._flat)

    def place(self, trainable, aux, rule):
        import jax
        import jax.numpy as jnp

        n = self._n
        train_vals = _values(trainable)
        layout = []
        for p, v in zip(trainable, train_vals):
            size = int(v.size)
            layout.append({"name": p.name,
                           "shape": tuple(int(s) for s in v.shape),
                           "dtype": str(v.dtype), "size": size,
                           "padded": _padded_size(size, n)})
        train = tuple(
            self._flat_put(_np.asarray(v).reshape(-1), m,
                           _np.dtype(m["dtype"]))
            for v, m in zip(train_vals, layout))
        # optimizer state is BORN on the shard layout — a jitted zeros
        # with sharded out_shardings allocates 1/n per device directly;
        # the replicated full-size state never exists at any point
        state = rule.init_state(lambda i, dt: jax.jit(
            lambda: jnp.zeros((layout[i]["padded"],), dt),
            out_shardings=self._flat)())
        self.shards = ((self._flat,) * len(train),
                       (self._flat,) * len(state),
                       (self._repl,) * len(aux))

        leaves_per = [len(dts) for dts in rule.leaf_dtypes]
        isz = [_np.dtype(m["dtype"]).itemsize for m in layout]
        gather_bytes = sum(m["padded"] * s for m, s in zip(layout, isz))
        self.zero_layout = {
            "n": n,
            "params": layout,
            "state_leaves": leaves_per,
            "state_dtypes": [[str(d) for d in dts]
                             for dts in rule.leaf_dtypes],
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
        return [train, state, _put(_values(aux), self.shards[2])]

    # traced into the step program
    def models(self, held):
        import jax

        # the param all-gather
        with _xray.scope(_xray.REGION_ZERO_AG):
            return tuple(
                jax.lax.with_sharding_constraint(f, self._repl)
                [:m["size"]].reshape(m["shape"])
                for f, m in zip(held, self.zero_layout["params"]))

    def for_update(self, grads, held):
        import jax

        # norm over the still-replicated grads: identical reduction
        # to the dp path's, so health trajectories match bit-exact
        with _xray.scope(_xray.REGION_ZERO_GNORM):
            gnorm = _global_grad_norm(grads)
        # the reduce-scatter: each device keeps only the shard of the
        # dp-summed grads its update needs
        with _xray.scope(_xray.REGION_ZERO_RS):
            grads = tuple(
                jax.lax.with_sharding_constraint(g.astype(f.dtype),
                                                 self._flat)
                for g, f in zip(grads, held))
        return grads, gnorm

    # on the host
    def orders(self, held, learn):
        """The model's for every leaf: a flat shard has one dimension, and
        no order was ever learned for the replicated statistics."""
        return tuple(tuple(tuple(range(v.ndim)) for v in tree)
                     for tree in held)

    def params_on_host(self, train_vals):
        return [_np.asarray(v)[:m["size"]].reshape(m["shape"])
                for v, m in zip(train_vals, self.zero_layout["params"])]

    def after_step(self):
        zl = self.zero_layout
        _rts.inc("zero_steps")
        _rts.inc("zero_allgather_bytes", zl["per_step_allgather_bytes"])
        _rts.inc("zero_reduce_bytes", zl["per_step_reduce_bytes"])

    # ------------------------------------------------ sharded checkpoint
    def shard_payloads(self, train_vals, opt_state):
        """``{rank: payload}`` for every locally-addressable 'dp'
        position — the per-rank shard files of a sharded checkpoint.
        Each payload carries exactly the 1/n slice that rank owns
        (params + optimizer-state leaves), so a rank never persists
        another rank's bytes; in a multi-host run each process sees
        only its own ranks here."""
        out = {}

        def collect(vals, kind):
            for j, v in enumerate(vals):
                shard_len = int(v.shape[0]) // self._n
                for s in v.addressable_shards:
                    rank = int(s.index[0].start or 0) // shard_len
                    slot = out.setdefault(
                        rank, {"params": {}, "state": {}})
                    slot[kind][j] = _np.asarray(s.data)

        collect(train_vals, "params")
        collect(opt_state, "state")
        return out

    def save(self, step, train_vals, opt_state, rule, mgr):
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
        files = {"zero-shard-%05d-of-%05d" % (r, self._n): payload
                 for r, payload in self.shard_payloads(
                     train_vals, opt_state).items()}
        aux = {"zero_layout": self.zero_layout}
        blob = rule.host_state()
        if blob is not None:
            aux["optimizer"] = blob
        return mgr.save_sharded(step, files, aux=aux)

    def restore(self, manifest, rule, mgr):
        """-> (the checkpoint's step, train_vals, opt_state) on this
        layout, RE-SHARDING when the checkpoint's dp width differs from
        the current mesh (the layout-change resume path): each full flat
        vector is rebuilt from the old ranks' slices, stripped of the old
        padding, re-padded to the current multiple and placed onto the
        current 'dp' layout.  Restores the rule's host state and the RNG
        stream too."""
        from .. import checkpoint as _ckpt

        mgr = mgr if mgr is not None else _ckpt.manager()
        if mgr is None:
            raise MXNetError("restore_zero: no checkpoint manager")
        aux = mgr.load_aux(manifest)
        if not aux or "zero_layout" not in aux:
            raise MXNetError(
                "restore_zero: checkpoint %s carries no zero_layout "
                "sideband — not a sharded checkpoint"
                % manifest.get("path"))
        old, new = aux["zero_layout"], self.zero_layout
        ranks = mgr.load_shard_files(manifest)
        if len(ranks) != old["n"]:
            raise MXNetError(
                "restore_zero: checkpoint %s has %d of %d rank shard "
                "files" % (manifest.get("path"), len(ranks), old["n"]))
        if old["state_leaves"] != new["state_leaves"]:
            raise MXNetError(
                "restore_zero: optimizer state structure changed "
                "(%r leaves saved vs %r now) — restore with the same "
                "optimizer family"
                % (old["state_leaves"], new["state_leaves"]))

        def rebuild(kind, j, meta, dtype):
            full = _np.concatenate(
                [ranks[r][kind][j] for r in range(old["n"])])
            return self._flat_put(full, meta, dtype)

        train_vals = []
        for j, (mo, mn) in enumerate(zip(old["params"], new["params"])):
            if (mo["name"], mo["size"]) != (mn["name"], mn["size"]):
                raise MXNetError(
                    "restore_zero: parameter %d mismatch (%s/%d saved "
                    "vs %s/%d now) — the model changed"
                    % (j, mo["name"], mo["size"], mn["name"], mn["size"]))
            train_vals.append(
                rebuild("params", j, mn, _np.dtype(mn["dtype"])))
        leaves = [(i, dt) for i, dts in enumerate(new["state_dtypes"])
                  for dt in dts]
        opt_state = [
            rebuild("state", j, new["params"][i], _np.dtype(dt))
            for j, (i, dt) in enumerate(leaves)]
        blob = aux.get("optimizer")
        if blob is not None:
            rule.load_host_state(blob)
        rng = manifest.get("rng")
        if rng:
            _random.set_state(rng)
        return int(manifest.get("step", 0)), train_vals, opt_state


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

    zero: weight-update sharding — params and optimizer state live as
    flat 1/n 'dp' shards (``_FlatShards``).  ``self.zero_layout``
    describes the layout and the per-step collective bytes (also fed
    into the ``zero_allgather_bytes`` / ``zero_reduce_bytes`` runtime
    counters).

    optimizer: a ``compiled_step_safe`` Optimizer instance traced into
    the step (the real fused-kernel update); None keeps the fused
    sgd-momentum rule built from ``lr/momentum/wd``.
    """

    train_vals = _state_tree(0, "The trainable parameters' values.")
    opt_state = _state_tree(1, "The optimizer's state leaves.")
    aux_vals = _state_tree(2, "The values of the parameters that take no "
                              "gradient (batch-norm statistics).")

    def __init__(self, block, loss_block, mesh=None, lr=0.1, momentum=0.9,
                 wd=0.0, compute_dtype=None, param_spec_fn=None,
                 data_spec=None, label_spec=None, aux_loss_weight=None,
                 zero=False, optimizer=None):
        from jax.sharding import NamedSharding

        from .mesh import (data_parallel_sharding, get_default_mesh,
                           replicated_sharding)

        self.block = block
        self.mesh = mesh or get_default_mesh()
        if zero and param_spec_fn is not None:
            raise MXNetError(
                "GluonTrainStep: zero=True owns the parameter layout "
                "(flat 1-D 'dp' shards) and cannot compose with "
                "param_spec_fn tensor sharding")
        params = list(block.collect_params().values())
        self.trainable = [p for p in params if p.grad_req != "null"]
        self.aux = [p for p in params if p.grad_req == "null"]
        dtypes = [v.dtype for v in _values(self.trainable)]
        # which rule updates the parameters, and in what form the state
        # is held between steps: everything below is written once
        self._rule = (_FusedSGD(lr, momentum, wd, dtypes)
                      if optimizer is None
                      else _OptimizerUpdate(optimizer, dtypes,
                                            self.trainable))
        self._form = (_FlatShards(self.mesh) if zero
                      else _InCompilersOrder(self.mesh, param_spec_fn))
        self._compute_dtype = compute_dtype
        self.last_grad_norm = None
        self._step = None
        self._calls = 0        # step_num of the next mxtpu.step span
        self._leaves = None    # array arguments of one launch
        self._orders = None    # per state leaf, the order it is held in
        self._relaid = 0       # leaves held in another order than the model's

        repl = replicated_sharding(self.mesh)
        x_shard = (NamedSharding(self.mesh, data_spec) if data_spec is not None
                   else data_parallel_sharding(self.mesh, 1))
        if label_spec is not None:
            y_shard = NamedSharding(self.mesh, label_spec)
        elif data_spec is not None and len(data_spec):
            # labels are rank-1: shard them along the data spec's batch axis
            from jax.sharding import PartitionSpec as _P
            y_shard = NamedSharding(self.mesh, _P(data_spec[0]))
        else:
            y_shard = x_shard  # P(): replicated batch -> replicated labels
        # place batch-sharded inputs via these shardings
        self.batch_sharding = x_shard
        self.label_sharding = y_shard
        self._repl = repl
        self._rest_in = (x_shard, y_shard, repl, repl)  # ..., key, scalars

        with _profiler.boundary_span("mxtpu.setup.place",
                                     keep=True) as placed:
            self._held = self._form.place(self.trainable, self.aux,
                                          self._rule)
            placed.stats.update(
                leaves=sum(len(tree) for tree in self._held),
                bytes=sum(v.nbytes for tree in self._held for v in tree))
        # un-jitted.  self._step is built by _adopt_orders() at the first
        # call: the order the state is held in (class docstring) needs
        # the batch's shape to be learned
        self._step_py = self._build(_pure_loss_builder(
            block, loss_block, self.trainable, self.aux,
            aux_loss_weight=aux_loss_weight), compute_dtype)

    def _build(self, pure_loss, cast):
        """step(train_vals, opt_state, aux_vals, x, y, key, scalars) ->
        (loss, train_vals, opt_state, aux_vals, grad norm), the state as
        the form holds it."""
        import jax

        form, rule = self._form, self._rule

        def fwd_bwd(train_vals, aux_vals, x, y, key):
            def loss_of(held):
                tv = form.models(held)
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
            return (loss, new_aux, *form.for_update(grads, train_vals))

        def step(train_vals, opt_state, aux_vals, x, y, key, scalars):
            loss, new_aux, grads, gnorm = fwd_bwd(
                train_vals, aux_vals, x, y, key)
            with _xray.scope(_xray.REGION_OPT):
                new_vals, new_state = rule.apply(train_vals, grads,
                                                 opt_state, scalars)
            return loss, new_vals, new_state, new_aux, gnorm

        return step

    def _compilers_orders(self, x, y, rest):
        """Per state leaf, its dimensions in the order (major first) the
        compiler lays it out in when the choice is its own: the step
        compiled ahead of time with ``Layout.AUTO`` on the state, and
        dropped once read."""
        import jax
        from jax.experimental.layout import Format, Layout

        auto = jax.tree.map(lambda shard: Format(Layout.AUTO, shard),
                            self._form.shards)

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
            type(self._rule.opt).__name__,
            str(self._compute_dtype),
            [p.name for p in self.trainable + self.aux],
            [(v.shape, str(v.dtype), str(shard.spec))
             for tree, shards in zip(self._held, self._form.shards)
             for v, shard in zip(tree, shards)],
            x.shape, str(x.dtype), y.shape, str(y.dtype)))
        return os.path.join(cache, "mxtpu-step-orders-%s.json"
                            % hashlib.sha256(asked.encode()).hexdigest()[:32])

    def _learned_orders(self, x, y, rest):
        """The compiler's order of every state leaf: what an earlier
        process learned, else learned now and kept."""
        with _profiler.boundary_span("mxtpu.setup.orders.learn",
                                     keep=True) as learn:
            path = self._orders_path(x, y)
            orders = path and _read_orders(path, self._held)
            learn.stats["source"] = "file" if orders else "compiled"
            if not orders:
                orders = self._compilers_orders(x, y, rest)
                if path:
                    _write_orders(path, orders)
        return orders

    def _adopt_orders(self, x, y, rest):
        """Once, at the first call: settle the order every state leaf is
        held in, transpose the state into it and build the jitted step
        that takes and returns it so."""
        import jax

        span = _profiler.boundary_span
        with span("mxtpu.setup.orders", keep=True):
            shards = self._form.shards
            self._orders = orders = self._form.orders(
                self._held,
                functools.partial(self._learned_orders, x, y, rest))
            moving = [(i, j) for i, tree in enumerate(orders)
                      for j, order in enumerate(tree)
                      if not _is_models(order)]
            if moving:
                # one program moves the leaves whose order is not the
                # model's
                with span("mxtpu.setup.orders.relay", keep=True,
                          relaid_leaves=len(moving)):
                    moved = dict(zip(moving, jax.jit(
                        lambda *leaves: tuple(
                            _in_order(v, orders[i][j])
                            for v, (i, j) in zip(leaves, moving)),
                        out_shardings=tuple(
                            _shard_in_order(shards[i][j], orders[i][j])
                            for i, j in moving))(
                        *(self._held[i][j] for i, j in moving))))
                self._held = [
                    tuple(moved.get((i, j), v) for j, v in enumerate(tree))
                    for i, tree in enumerate(self._held)]
            self._relaid = len(moving)
            self._step = self._jit()

    def _jit(self):
        """The step jitted on the state as it is held: donated, each leaf
        transposed to the model's order on the way in and back on the way
        out (free: the held order is the order the compiler lays the leaf
        out in; none is traced where the order is the model's)."""
        import jax

        fn, orders = self._step_py, self._orders
        shards = tuple(
            tuple(_shard_in_order(s, o) for s, o in zip(tree, tree_orders))
            for tree, tree_orders in zip(self._form.shards, orders))

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
            out_shardings=(self._repl, *shards, self._repl),
            donate_argnums=(0, 1, 2),
        )

    # --------------------------------------------------------- execution
    def put_batch(self, x, y):
        """Place a host batch onto the mesh with the dp sharding."""
        import jax

        return (jax.device_put(_np.asarray(x), self.batch_sharding),
                jax.device_put(_np.asarray(y), self.label_sharding))

    def __call__(self, x, y):
        """One training step on device arrays/numpy; returns loss (async)."""
        import jax

        span = _profiler.boundary_span
        # call 0 is kept with what opens inside it: there the step program
        # is traced, lowered and compiled or loaded (profiler.kept_spans)
        with span("mxtpu.step", step_num=self._calls, keep=not self._calls):
            self._calls += 1
            if not isinstance(x, jax.Array):
                with span("mxtpu.step.put_batch"):
                    x, y = self.put_batch(x, y)
            with span("mxtpu.step.key"):
                key = _random.next_key()
            rest = (key, self._rule.host_scalars())
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
            self._form.after_step()
            if _health._state["on"]:
                hm = _health.monitor()
                if hm is not None:
                    hm.observe_scalar("grad_norm", gnorm)
        return loss

    def program_for(self, x, y):
        """The step compiled ahead of time for a batch like (x, y), for
        ``as_text()``, ``cost_analysis()`` and ``memory_analysis()``: the
        program ``__call__`` runs for it, compiled once more.  The one
        place that stands in for the key and the rule's host scalars."""
        import jax

        # shape/dtype stand-ins only
        rest = (jax.random.PRNGKey(0), tuple(0.0 for _ in self._rule.slots))
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

        values = self._form.params_on_host(self.train_vals) \
            + [_np.asarray(v) for v in self.aux_vals]
        for p, v in zip(self.trainable + self.aux, values):
            host = jnp.asarray(v)
            for d in p._data:
                d._assign(host)

    # ------------------------------------------------ sharded checkpoint
    def _flat_shards(self, asked):
        if not isinstance(self._form, _FlatShards):
            raise MXNetError(
                "%s: this step was not built with zero=True" % asked)
        return self._form

    @property
    def zero_layout(self):
        """The ZeRO layout and its per-step collective bytes."""
        return self._form.zero_layout

    def zero_shard_payloads(self):
        """``{rank: payload}``: the 1/n slice of parameters and optimizer
        state each locally-addressable 'dp' rank owns."""
        return self._flat_shards("zero_shard_payloads").shard_payloads(
            self.train_vals, self.opt_state)

    def save_zero(self, step, mgr=None):
        """Commit a sharded checkpoint (``_FlatShards.save``)."""
        return self._flat_shards("save_zero").save(
            step, self.train_vals, self.opt_state, self._rule, mgr)

    def restore_zero(self, manifest, mgr=None):
        """Load a sharded checkpoint back into this step's flat shards,
        re-sharding when its dp width differs from the current mesh
        (``_FlatShards.restore``); returns the checkpoint step."""
        step, self.train_vals, self.opt_state = self._flat_shards(
            "restore_zero").restore(manifest, self._rule, mgr)
        return step
