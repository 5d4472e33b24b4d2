"""Whole-step compilation — forward+backward+update as ONE XLA program.

On the eager mainline every op is its own cached ``jax.jit``
executable: XLA can only fuse inside op boundaries, and ``stepstats``
shows ``dispatch_warm`` as a standing per-step tax (one host dispatch
per op per step).  Per the Julia→TPU full-compilation result
(arXiv:1810.09868) and the XLA fusion analysis (arXiv:2301.13062), the
win comes from handing XLA the *whole* training step: this module
traces the hybridized forward, the loss, the backward
(``jax.value_and_grad``), and the REAL optimizer update — the same
``Updater``/fused-kernel path ``gluon.Trainer`` runs, not a hand-rolled
SGD — into one jitted program with **donated** parameter / optimizer
/ aux buffers, so the update is in-place on device, cross-op fusion is
free, and the per-step host cost amortizes to ~one dispatch.

Contract
--------
- ``compile_step(block, loss, trainer)`` (or ``trainer.compile(block,
  loss)``) returns a :class:`CompiledStep`; ``cs.step(x, y)`` replaces
  the whole ``record()/backward()/trainer.step()`` iteration and
  returns the loss block's output (per-sample losses, async).
- Programs are cached per ``(batch shape, dtype, rescale_grad)`` like
  the dispatch layer's per-op jit cache: a shape change builds a new
  entry (counted as a ``compiled_step`` jit-cache miss, visible to the
  recompile-storm detector), it never silently retraces per step.
- **Donation/rebind**: the params', optimizer states', and aux states'
  device buffers are donated into each call (XLA reuses them for the
  outputs — no 2x working set) and the fresh outputs are rebound into
  the same ``NDArray`` objects before ``step()`` returns.  Everything
  that reads those NDArrays afterwards — checkpointing, health hooks,
  ``save_parameters``, eager evaluation — sees the updated values;
  *other* NDArray handles aliasing the old buffers are invalidated,
  like any in-place update.
- **Per-step scalars** (scheduler lr, Adam bias correction, FTML /
  Adamax ``t``) are recomputed host-side each step by
  ``Optimizer.step_scalars`` — the same double-precision host math the
  eager path runs — and fed into the program as traced arguments
  (``optimizer.scalar_feed``), so schedules never recompile and eager
  vs compiled numerics agree to the bit for the fused-kernel
  optimizers.
- Supported optimizers declare ``compiled_step_safe = True`` (SGD,
  NAG, Signum, Adam, Adamax, FTML, Ftrl, RMSProp, AdaGrad, AdaDelta);
  the rest — host syncs (LBSGD), cross-step host recurrences (Nadam),
  raw host-scalar NDArray math — keep the eager path and raise a clear
  error here.
- ``compile_step(..., zero=True)`` routes the same seam through
  :class:`ZeroCompiledStep`: the fused program with ZeRO
  weight-update sharding over the 'dp' mesh axis — grads
  reduce-scattered to 1/n shards, the update on each device's
  param+state shard, updated params all-gathered inside the program
  (parallel/gluon_step.py zero path; docs/ZERO.md).
- The eager path stays the untouched default and the
  debugging/interop mode.

Observability: each ``step()`` emits the same ``trainer:step``
span/histogram as the eager Trainer, counts ``trainer_steps`` /
``compiled_step_steps``, feeds the dedicated ``compiled_step``
stepstats phase when dispatch timing is on, registers entry builds as
``compiled_step`` jit-cache misses with their compile seconds, and
captures the program's XLA cost/memory analysis into the diag dump's
cost section when cost capture is active (the per-op jit-entry
convention).  Docs: docs/COMPILED_STEP.md.
"""

from __future__ import annotations

import weakref

from . import health as _health
from . import profiler as _prof
from . import random as _random
from . import runtime_stats as _rts
from . import xray as _xray
from .base import MXNetError
from .ndarray import NDArray
from .optimizer import optimizer as _opt
from .ops import registry as _registry

__all__ = ["CompiledStep", "ZeroCompiledStep", "compile_step",
           "donation_active", "cost_snapshot", "xray_snapshot"]

# live CompiledStep instances, for the read-side cost aggregation
# (runtime_stats.snapshot merges cost_snapshot() into its "costs"
# section) — weak so a dropped step never outlives its model
_LIVE: "weakref.WeakSet[CompiledStep]" = weakref.WeakSet()

# flips True the first time buffers are handed to a donating program
# call and stays: by-reference checkpoint captures must pin
# (materialize) from then on, because later steps donate the
# param/optimizer buffers regardless of Python references
# (checkpoint.save_trainer consults this); a failed build or guard
# never donated, so it never forces pinning
_state = {"donating": False}


def donation_active():
    """True once any CompiledStep has stepped in this process — device
    buffers captured by reference may be donated (invalidated) by a
    later step, so zero-copy snapshot captures must materialize at
    capture time."""
    return _state["donating"]


def compile_step(block, loss, trainer, zero=False, mesh=None):
    """Compile ``block`` + ``loss`` + ``trainer``'s optimizer into one
    donated whole-step XLA program (see module docstring).

    ``zero=True`` routes through :class:`ZeroCompiledStep` — the same
    fused program with ZeRO weight-update sharding over the 'dp' mesh
    axis (docs/ZERO.md); ``mesh`` optionally pins the device mesh for
    that path."""
    if zero:
        return ZeroCompiledStep(block, loss, trainer, mesh=mesh)
    return CompiledStep(block, loss, trainer)


def _guard_trainer(trainer, zero=False):
    """The shared compile-time eligibility checks: a traceable
    fused-kernel optimizer, updates running locally (not on kvstore
    servers / across processes), and — for the single-program
    replicated path only — a single context."""
    opt = trainer._optimizer
    if not getattr(opt, "compiled_step_safe", False):
        raise MXNetError(
            "compiled_step: optimizer %s is not compiled-step safe "
            "(host syncs, cross-step host recurrences, or raw "
            "host-scalar math in update()); supported: SGD, NAG, "
            "Signum, Adam, Adamax, FTML, Ftrl, RMSProp, AdaGrad, "
            "AdaDelta.  Use the eager Trainer path instead."
            % type(opt).__name__)
    if trainer._update_on_kvstore:
        raise MXNetError(
            "compiled_step: updates run on the kvstore servers "
            "(update_on_kvstore=True) — the update cannot be traced "
            "into a device program; use the eager path")
    kv_type = trainer._kvstore_type
    kv_name = kv_type if isinstance(kv_type, str) \
        else getattr(kv_type, "type", "") or ""
    if "dist" in kv_name:
        raise MXNetError(
            "compiled_step: dist kvstore training is not compiled "
            "(gradients must cross processes); use the eager path "
            "or the sharded parallel/gluon_step.py step")
    if not zero and len(trainer._contexts) > 1:
        raise MXNetError(
            "compiled_step: multi-context (per-device replica) "
            "training is not compiled; use parallel/gluon_step.py "
            "for the sharded whole-step path")


class _Entry:
    """One jitted whole-step program for a fixed input signature."""

    __slots__ = ("fn", "n_state_leaves", "cost", "xray")

    def __init__(self, fn, n_state_leaves):
        self.fn = fn
        self.n_state_leaves = n_state_leaves
        self.cost = None
        self.xray = None


def _state_leaves(st, out):
    """Collect the NDArray leaves of one updater state tree, in the
    deterministic traversal order every phase (flatten, trace rebuild,
    post-call rebind) shares."""
    if st is None:
        return
    if isinstance(st, NDArray):
        out.append(st)
    elif isinstance(st, (tuple, list)):
        for c in st:
            _state_leaves(c, out)
    else:
        raise MXNetError(
            "compiled_step: unsupported optimizer state leaf %r — "
            "states must be (nested tuples/lists of) NDArrays or None"
            % type(st).__name__)


def _rebuild_state(st, it):
    """The same tree with each NDArray leaf replaced by an NDArray
    wrapping the next traced value from ``it``."""
    if st is None:
        return None
    if isinstance(st, NDArray):
        return NDArray(next(it))
    if isinstance(st, tuple):
        return tuple(_rebuild_state(c, it) for c in st)
    return [_rebuild_state(c, it) for c in st]


class CompiledStep:
    """Fused fwd+bwd+update over the mainline Gluon/Trainer stack."""

    def __init__(self, block, loss, trainer):
        import jax  # noqa: F401  (fail early off-jax environments)

        self.block = block
        self.loss_block = loss
        self.trainer = trainer
        opt = trainer._optimizer
        _guard_trainer(trainer)
        params = list(block.collect_params().values())
        self.trainable = [p for p in params if p.grad_req != "null"]
        self.aux = [p for p in params if p.grad_req == "null"]
        if not self.trainable:
            raise MXNetError("compiled_step: block has no trainable "
                             "parameters")
        self._index = {}
        for p in self.trainable:
            i = trainer._param2idx.get(p.name)
            if i is None:
                raise MXNetError(
                    "compiled_step: parameter %r is not managed by this "
                    "Trainer — pass the same collect_params() the "
                    "Trainer was built with" % p.name)
            self._index[p] = i
        ours = {id(p) for p in self.trainable}
        for p in trainer._params:
            if p.grad_req != "null" and id(p) not in ours:
                raise MXNetError(
                    "compiled_step: Trainer parameter %r is not part of "
                    "this block — it would silently stop updating; "
                    "compile the block that owns every trainable "
                    "parameter" % p.name)
        # one slot per (param index, per-step scalar name): the traced
        # arguments the host refills from Optimizer.step_scalars each
        # step.  Discovered once — only the names matter here.
        self._slots = []
        for p in self.trainable:
            i = self._index[p]
            for name in sorted(opt.step_scalars(i)):
                self._slots.append((i, name))
        self._cache = {}
        _LIVE.add(self)

    # ------------------------------------------------------------ build
    def _updater(self):
        return self.trainer._updaters[0]

    def _ensure_states(self):
        """Materialize updater state for every trainable index — what
        ``Updater.__call__`` does lazily on the eager path, done
        eagerly here so the state tree exists before tracing."""
        import jax

        opt = self.trainer._optimizer
        upd = self._updater()
        for p in self.trainable:
            i = self._index[p]
            if i not in upd.states:
                upd.states[i] = opt.create_state_multi_precision(
                    i, p.data())
                upd.states_synced[i] = True
                # commit the fresh state to the weight's device: the
                # program hands it back committed, and an input that
                # flips from uncommitted to committed recompiles the
                # whole step on its second call
                (dev,) = p.data()._data.devices()
                fresh = []
                _state_leaves(upd.states[i], fresh)
                for leaf in fresh:
                    leaf._assign(jax.device_put(leaf._data, dev))

    def _collect_state(self):
        """``(leaf NDArrays, values)`` for every trainable index, in
        slot order.  Re-collected every step — checkpoint restore may
        rebuild the state tree objects, so cached leaf lists would go
        stale and update orphans."""
        upd = self._updater()
        leaves = []
        for p in self.trainable:
            _state_leaves(upd.states[self._index[p]], leaves)
        return leaves, tuple(nd._data for nd in leaves)

    def _build(self, x_nd, y_nd):
        """Trace + jit one whole-step program for this signature."""
        import jax
        import jax.numpy as jnp

        from .gluon.block import staged_call

        # resolve deferred shapes with one eager warmup forward, like
        # HybridBlock._call_cached does before its staging trace
        from . import autograd as _ag
        from .gluon.parameter import DeferredInitializationError

        try:
            for p in self.block.collect_params().values():
                p._check_initialized()
        except DeferredInitializationError:
            with _ag.pause():
                self.block(x_nd)
            params = list(self.block.collect_params().values())
            self.trainable = [p for p in params if p.grad_req != "null"]
            self.aux = [p for p in params if p.grad_req == "null"]
        self._ensure_states()
        trainable = self.trainable
        aux = self.aux
        block = self.block
        loss_block = self.loss_block
        upd = self._updater()
        indices = [self._index[p] for p in trainable]
        state_trees = [upd.states[i] for i in indices]
        per_tree_leaves = []
        for st in state_trees:
            leaves = []
            _state_leaves(st, leaves)
            per_tree_leaves.append(len(leaves))
        n_leaves = sum(per_tree_leaves)
        slots = list(self._slots)

        def step_fn(pvals, svals, avals, x, y, seed, scalars):
            aux_override = {p: NDArray(v) for p, v in zip(aux, avals)}

            def loss_sum(tv):
                override = {p: NDArray(v)
                            for p, v in zip(trainable, tv)}
                override.update(aux_override)

                def fwd(x_in):
                    out = block(x_in)
                    with _xray.scope(_xray.REGION_LOSS):
                        loss = loss_block(out, NDArray(y))
                    if not isinstance(loss, NDArray):
                        raise MXNetError(
                            "compiled_step: the loss must return one "
                            "NDArray, got %r" % type(loss).__name__)
                    return loss

                loss, scope = staged_call(fwd, override, seed,
                                          (NDArray(x),))
                new_aux = tuple(
                    scope.aux_updates.get(p, aux_override[p]._data)
                    for p in aux)
                # ones-cotangent over the loss output — exactly what
                # eager `l.backward()` seeds, so gradients match the
                # tape bit for bit
                return jnp.sum(loss._data), (loss._data, new_aux)

            # x-ray: the grad wrapper is a direction marker only — the
            # transpose() metadata XLA records inside is what flags
            # backward instructions; canonical_scope filters the marker
            with _xray.scope(_xray.GRAD_MARKER):
                (_, (loss_vec, new_aux)), grads = jax.value_and_grad(
                    loss_sum, has_aux=True)(tuple(pvals))

            # the REAL optimizer update: rebuild each state tree with
            # traced leaves, swap it into the live Updater, and run the
            # same fused-kernel update path the eager Trainer runs —
            # per-step scalars arrive through the feed as traced args
            it = iter(svals)
            traced_states = {i: _rebuild_state(st, it)
                             for i, st in zip(indices, state_trees)}
            feed = {(i, name): scalars[k]
                    for k, (i, name) in enumerate(slots)}
            real_states = upd.states
            new_pvals = []
            try:
                upd.states = traced_states
                with _opt.scalar_feed(feed), \
                        _xray.scope(_xray.REGION_OPT):
                    for j, p in enumerate(trainable):
                        w_nd = NDArray(pvals[j])
                        g_nd = NDArray(grads[j])
                        upd(indices[j], g_nd, w_nd)
                        new_pvals.append(w_nd._data)
            finally:
                upd.states = real_states
            new_svals = []
            for i in indices:
                leaves = []
                _state_leaves(traced_states[i], leaves)
                new_svals.extend(nd._data for nd in leaves)
            return (loss_vec, tuple(new_pvals), tuple(new_svals),
                    tuple(new_aux))

        fn = jax.jit(step_fn, donate_argnums=(0, 1, 2))
        return _Entry(fn, n_leaves)

    def _analyze(self, entry, args):
        """Capture the program's XLA cost/memory analysis at compile
        time (one extra AOT compile, like ``Op.analyze_entry`` — only
        when cost capture is active)."""
        if not _registry.cost_capture_active():
            return
        import time as _time

        import jax

        t0 = _time.perf_counter()
        try:
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") else a, args)
            compiled = entry.fn.lower(*specs).compile()
            entry.cost = _registry.compiled_cost(compiled)
            entry.xray = _xray.analyze(compiled, cost=entry.cost)
        except Exception:  # analysis must never break the step
            entry.cost = None
        _rts.inc("cost_analysis_entries" if entry.cost
                 else "cost_analysis_failures")
        if entry.xray:
            _rts.inc("xray_programs")
        _rts.inc("cost_analysis_seconds", _time.perf_counter() - t0)

    # ------------------------------------------------------------- step
    def step(self, x, y):
        """One fused training step; returns the loss output (async).

        Runs under the SAME per-step instrumentation as the eager
        ``Trainer.step`` (``gluon.trainer._StepTelemetry``: trainer:step
        span + step-wall histogram, health step clock + crash dump,
        device-memory counter event, auto-checkpoint hook — pinned,
        because the next call donates the captured buffers — stepstats
        window close, metrics-timeline sample), so every later
        observability layer extends both paths in one place."""
        from .gluon.trainer import _StepTelemetry

        _rts.inc("trainer_steps")
        _rts.inc("compiled_step_steps")
        hm = _health.monitor() if _health._state["on"] else None
        batch_size = int(x.shape[0]) if hasattr(x, "shape") else None
        with _StepTelemetry(self.trainer, batch_size, hm, compiled=True):
            return self._step_impl(x, y)

    def _step_impl(self, x, y):
        x_nd = x if isinstance(x, NDArray) else NDArray(_as_jax(x))
        y_nd = y if isinstance(y, NDArray) else NDArray(_as_jax(y))
        trainer = self.trainer
        opt = trainer._optimizer
        batch = int(x_nd.shape[0])
        # same rescale contract as Trainer._step: scale/batch, resolved
        # before the update reads it (and baked per cache entry — the
        # key carries it, so a batch/scale change builds a new program)
        opt.rescale_grad = trainer._scale / batch
        key = (tuple(x_nd.shape), str(x_nd.dtype),
               tuple(y_nd.shape), str(y_nd.dtype),
               float(opt.rescale_grad))
        entry = self._cache.get(key)
        hit = entry is not None
        timed = _prof._state["running"] or _rts.DIAG_TIMING
        t0 = _prof._now_us() if (timed or not hit) else 0

        if not hit:
            _rts.record_dispatch("compiled_step", "miss")
            _rts.record_compile_key("compiled_step", key)
            entry = self._build(x_nd, y_nd)
            self._cache[key] = entry
        else:
            _rts.record_dispatch("compiled_step", "hit")

        # advance the optimizer's host step counters (the eager path
        # does this inside update(); the feed suppresses it in-trace),
        # then refill the per-step scalar slots with fresh host values
        table = {}
        for p in self.trainable:
            i = self._index[p]
            opt._update_count(i)
            table[i] = opt.step_scalars(i)
        scalars = tuple(float(table[i][name]) for i, name in self._slots)
        seed = _random.next_key()

        leaves, svals = self._collect_state()
        if len(leaves) != entry.n_state_leaves:
            raise MXNetError(
                "compiled_step: optimizer state changed structure "
                "(%d leaves vs %d at trace time) — rebuild the "
                "CompiledStep after swapping optimizers"
                % (len(leaves), entry.n_state_leaves))
        pvals = tuple(p.data()._data for p in self.trainable)
        avals = tuple(p.data()._data for p in self.aux)
        args = (pvals, svals, avals, x_nd._data, y_nd._data, seed,
                scalars)
        # latched at the point buffers are actually handed to a donating
        # call (a failed build/guard above never donated anything, and
        # must not force pinned checkpoints process-wide)
        _state["donating"] = True
        loss_v, new_p, new_s, new_aux = entry.fn(*args)

        # rebind: the donated inputs are gone; the same NDArray objects
        # now carry the updated buffers, so checkpointing/health/eager
        # interop keep working with zero copies
        for p, v in zip(self.trainable, new_p):
            p._data[0]._assign(v)
        for nd, v in zip(leaves, new_s):
            nd._assign(v)
        for p, v in zip(self.aux, new_aux):
            p._data[0]._assign(v)

        dur = (_prof._now_us() - t0) if (timed or not hit) else 0
        if not hit:
            _rts.add_compile_seconds("compiled_step", dur / 1e6)
            # AOT cost/memory capture AFTER the timed window (the
            # registry convention: analysis wall-time has its own
            # counter); donated args still expose shape/dtype metadata
            self._analyze(entry, args)
        elif timed:
            _rts.add_compiled_step_seconds(dur / 1e6)
        if _prof._state["running"]:
            ev = {"op": "compiled_step",
                  "cache": "hit" if hit else "miss"}
            if not hit:
                ev["compile_ms"] = round(dur / 1e3, 3)
            _prof.add_event("dispatch:compiled_step", "operator", "X",
                            ts=t0, dur=dur, args=ev)
        return NDArray(loss_v, x_nd._ctx)


class ZeroCompiledStep:
    """``trainer.compile(block, loss, zero=True)``: the whole-step
    program with ZeRO weight-update sharding (the
    parallel/gluon_step.py zero path) behind the same ``step()`` /
    telemetry contract as :class:`CompiledStep`.

    Differences from the replicated CompiledStep:

    - **Functional state**: params and optimizer state live as flat
      1/n 'dp' shards inside the wrapped ``GluonTrainStep``, not in the
      Gluon Parameters.  ``sync_to_params()`` writes them back, and
      runs automatically on the step right before an auto-checkpoint
      interval boundary so the captured parameter snapshot is fresh
      (optimizer state in that snapshot is the sharded run's business:
      use ``save_zero``/``restore_zero`` — the sharded checkpoint —
      for a complete resumable unit, docs/ZERO.md).
    - ``step()`` returns the mean loss (a scalar NDArray), not the
      per-sample loss vector: the sharded step reduces the loss inside
      the program.
    - ``rescale_grad`` semantics: gradients leave the backward as
      mean-of-batch (the sharded step differentiates the mean loss),
      so the optimizer's effective rescale is ``trainer._scale`` — set
      at build time and baked into the program; changing the scale
      afterwards requires a rebuild and raises.
    """

    def __init__(self, block, loss, trainer, mesh=None):
        from .parallel.gluon_step import GluonTrainStep

        self.block = block
        self.loss_block = loss
        self.trainer = trainer
        _guard_trainer(trainer, zero=True)
        opt = trainer._optimizer
        self._scale = float(trainer._scale)
        opt.rescale_grad = self._scale
        self._gstep = GluonTrainStep(block, loss, mesh=mesh, zero=True,
                                     optimizer=opt)
        self.zero_layout = self._gstep.zero_layout
        self._cache = {}
        _LIVE.add(self)

    # -------------------------------------------------------- interop
    def sync_to_params(self):
        """Gather the sharded functional params off the mesh back into
        the Gluon Parameters (checkpoint/eager-eval interop)."""
        self._gstep.sync_to_params()

    def save_zero(self, step, mgr=None):
        return self._gstep.save_zero(step, mgr=mgr)

    def restore_zero(self, manifest, mgr=None):
        return self._gstep.restore_zero(manifest, mgr=mgr)

    # ------------------------------------------------------------- step
    def step(self, x, y):
        """One fused ZeRO training step; returns the mean loss (async).
        Same per-step instrumentation as ``CompiledStep.step`` (see
        its docstring) plus the ``zero_*`` collective-bytes counters
        the wrapped sharded step emits."""
        from .gluon.trainer import _StepTelemetry

        _rts.inc("trainer_steps")
        _rts.inc("compiled_step_steps")
        hm = _health.monitor() if _health._state["on"] else None
        batch_size = int(x.shape[0]) if hasattr(x, "shape") else None
        with _StepTelemetry(self.trainer, batch_size, hm, compiled=True):
            return self._step_impl(x, y)

    def _step_impl(self, x, y):
        import numpy as np

        if float(self.trainer._scale) != self._scale:
            raise MXNetError(
                "zero compiled step: the loss scale changed (%s -> %s) "
                "after the program baked it — rebuild with "
                "trainer.compile(..., zero=True)"
                % (self._scale, self.trainer._scale))
        xv = getattr(x, "_data", x)
        yv = getattr(y, "_data", y)
        xq, yq = self._gstep.put_batch(np.asarray(xv), np.asarray(yv))
        key = (tuple(xq.shape), str(xq.dtype),
               tuple(yq.shape), str(yq.dtype))
        entry = self._cache.get(key)
        hit = entry is not None
        timed = _prof._state["running"] or _rts.DIAG_TIMING
        t0 = _prof._now_us() if (timed or not hit) else 0
        if not hit:
            _rts.record_dispatch("compiled_step", "miss")
            _rts.record_compile_key("compiled_step", key)
            entry = _Entry(None, 0)     # the program is the wrapped step's
            self._cache[key] = entry
        else:
            _rts.record_dispatch("compiled_step", "hit")

        loss = self._gstep(xq, yq)

        dur = (_prof._now_us() - t0) if (timed or not hit) else 0
        if not hit:
            _rts.add_compile_seconds("compiled_step", dur / 1e6)
            self._analyze(entry, (xq, yq))
        elif timed:
            _rts.add_compiled_step_seconds(dur / 1e6)
        if _prof._state["running"]:
            ev = {"op": "compiled_step", "zero": True,
                  "cache": "hit" if hit else "miss"}
            if not hit:
                ev["compile_ms"] = round(dur / 1e3, 3)
            _prof.add_event("dispatch:compiled_step", "operator", "X",
                            ts=t0, dur=dur, args=ev)

        # auto-checkpoint fires in _StepTelemetry.__exit__ when this
        # step crosses the interval boundary — the Gluon Parameters
        # must carry THIS step's values by then (the functional shards
        # are the source of truth otherwise)
        from . import checkpoint as _ckpt

        mgr = _ckpt.manager()
        if mgr is not None and mgr.interval \
                and (mgr.step_clock + 1) % mgr.interval == 0:
            self._gstep.sync_to_params()
        return NDArray(loss)

    def _analyze(self, entry, batch):
        """AOT cost/memory capture of the sharded program (the
        CompiledStep._analyze convention) — feeds the diag-dump cost
        section the perfdoctor zero rule reads."""
        if not _registry.cost_capture_active():
            return
        import time as _time

        t0 = _time.perf_counter()
        try:
            compiled = self._gstep.program_for(*batch)
            entry.cost = _registry.compiled_cost(compiled)
            entry.xray = _xray.analyze(compiled, cost=entry.cost,
                                       label="zero_step", zero=True)
        except Exception:  # analysis must never break the step
            entry.cost = None
        _rts.inc("cost_analysis_entries" if entry.cost
                 else "cost_analysis_failures")
        if entry.xray:
            _rts.inc("xray_programs")
        _rts.inc("cost_analysis_seconds", _time.perf_counter() - t0)


def _as_jax(a):
    import jax.numpy as jnp

    return jnp.asarray(a)


def cost_snapshot():
    """Read-side aggregate over every live CompiledStep's program
    cache, shaped like ``ops.registry.cost_snapshot`` rows so the diag
    dump / report cost section renders it like any per-op jit entry."""
    entries = []
    for cs in list(_LIVE):
        entries.extend(list(cs._cache.values()))
    if not entries:
        return {}
    analyzed = [e.cost for e in entries if e.cost]
    rec = {"cache_entries": len(entries), "analyzed": len(analyzed)}
    for k, dst in (("flops", "flops_per_call"),
                   ("bytes_accessed", "bytes_per_call")):
        vals = [c[k] for c in analyzed if k in c]
        if vals:
            rec[dst] = sum(vals) / len(vals)
    for k in ("output_bytes", "temp_bytes", "argument_bytes"):
        vals = [c[k] for c in analyzed if k in c]
        if vals:
            rec[k] = int(sum(vals))
    return {"compiled_step": rec}


def xray_snapshot():
    """Read-side aggregate of every live program's x-ray table (the
    cost_snapshot convention): ``{"programs": [table, ...]}`` ordered
    oldest→newest by capture sequence, ``{}`` when nothing was
    captured.  runtime_stats.snapshot merges this as its ``xray``
    section; the report/diagnose renderers and the perfdoctor rules
    read the newest table per program label."""
    programs = []
    for cs in list(_LIVE):
        for e in list(cs._cache.values()):
            t = getattr(e, "xray", None)
            if t:
                programs.append(t)
    if not programs:
        return {}
    programs.sort(key=lambda t: t.get("seq", 0))
    return {"programs": programs}
