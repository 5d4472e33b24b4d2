"""Gluon Trainer — applies an Optimizer to a set of Parameters.

Reference: python/mxnet/gluon/trainer.py:27 (kvstore setup :169,
step :302, allreduce_grads :331, update :363).

TPU-native notes: on a single chip the update is a direct fused
optimizer-op call per parameter (the reference's updater path).  For
multi-device data parallel, grads living on different devices are
reduced through the KVStore façade ('local'/'device'/'tpu'), whose
'tpu' backend lowers push+pull to an XLA psum over the mesh
(SURVEY.md §2.3) — the sharded flagship path instead jits the whole
train step over the mesh (parallel/gluon_step.py).
"""

from __future__ import annotations

from .. import autopilot as _autopilot
from .. import checkpoint as _ckpt
from .. import device_memory as _dm
from .. import health as _health
from .. import histogram as _histogram
from .. import kvstore as _kvstore
from .. import metrics_timeline as _metrics
from .. import optimizer as _optimizer
from .. import profiler as _profiler
from .. import runtime_stats as _rts
from .. import stepstats as _stepstats
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class _StepTelemetry:
    """THE shared per-step instrumentation of ``Trainer.step`` and
    ``compiled_step.CompiledStep.step``: the ``trainer:step`` span +
    step-wall histogram around the body, the health flight dump when an
    exception unwinds the step, and the accreting end-of-step hook tail
    (device-memory counter event, health step clock, auto-checkpoint,
    stepstats window close, metrics-timeline sample).  One place to
    extend when the next observability layer lands — a hook added here
    fires on BOTH training paths.

    ``compiled=True`` tags the span and pins the auto-checkpoint
    capture (the compiled path donates the param/optimizer buffers on
    its next call — ``checkpoint.save_trainer``'s pin contract)."""

    def __init__(self, trainer, batch_size, hm, compiled=False):
        self.trainer = trainer
        self.batch_size = batch_size
        self.hm = hm
        self.compiled = compiled

    def __enter__(self):
        self._hist_on = _histogram._state["on"]
        if self._hist_on:
            self._t0 = _profiler._now_us()
        args = None
        if _profiler._state["running"]:
            args = {"batch_size": self.batch_size}
            if self.compiled:
                args["compiled"] = 1
        self._span = _profiler.span("trainer:step", "trainer", args=args)
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._span.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            if self.hm is not None:
                # the ring holds the steps leading up to the crash —
                # dump it before the exception unwinds the training loop
                self.hm.dump_on_crash()
            return False
        if self._hist_on:
            # step wall-time distribution (guard-first): the per-rank
            # series the cluster report compares for step-time skew
            _histogram.observe("trainer:step",
                               (_profiler._now_us() - self._t0) / 1e6)
        if _dm._state["on"]:
            # per-step live/peak-bytes counter event: anchors the trace's
            # memory timeline even when no buffer was (de)allocated
            _dm.emit_counter()
        if self.hm is not None:
            self.hm.end_step()
        # auto-checkpoint hook (checkpoint.enable()/MXNET_TPU_CKPT):
        # advances the manager's step clock and snapshots at interval
        # boundaries without blocking.  Disabled: one dict read.
        if _ckpt._state["on"]:
            _ckpt.on_step(self.trainer, pin=self.compiled)
        # step-anatomy boundary (stepstats.py): closes the window that
        # opened at the previous step's end, so the recorded wall time
        # covers the whole iteration.  Disabled: one dict read.
        if _stepstats._state["on"]:
            _stepstats.end_step()
        # live metrics timeline: one per-step sample AFTER end_step so
        # the sample carries this step's phase window.  Disabled: one
        # dict read.
        if _metrics._state["on"]:
            _metrics.on_step(self.batch_size)
        # observability autopilot: gated reflexes over the live ring,
        # AFTER the timeline sample so the evidence includes this step.
        # Disabled: one dict read.  An ARMED halt-after-checkpoint
        # reflex raises AutopilotHalt through here by design.
        if _autopilot._state["on"]:
            _autopilot.on_step(self.trainer)
        return False


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError("params must be a list/dict of Parameters")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise ValueError("invalid parameter %r" % (p,))
            self._param2idx[p.name] = i
            self._params.append(p)
            p._trainer = self
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False

    def _check_contexts(self):
        contexts = None
        for p in self._params:
            ctx = p.list_ctx() if p._data is not None or p._deferred_init else None
            if ctx is None:
                continue
            if contexts is None:
                contexts = ctx
            elif contexts != ctx:
                raise ValueError(
                    "All Parameters must be initialized on the same set of "
                    "contexts, but %s has %s vs %s" % (p.name, ctx, contexts))
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, _optimizer.Optimizer):
            if optimizer_params:
                raise ValueError(
                    "optimizer_params must be empty when optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = _optimizer.create(optimizer, **optimizer_params)
            self._optimizer.param_dict = param_dict
        self._updaters = [_optimizer.get_updater(self._optimizer)
                          for _ in self._contexts] or \
                         [_optimizer.get_updater(self._optimizer)]

    def _init_kvstore(self):
        """reference: trainer.py _init_kvstore — dist stores are used even
        with one local context (the other replicas are other processes);
        update_on_kvstore routes the optimizer server-side."""
        kv = None
        if self._kvstore_type:
            kv = _kvstore.create(self._kvstore_type) \
                if isinstance(self._kvstore_type, str) else self._kvstore_type
        # a dist store synchronizes across PROCESSES, so one local
        # context is the normal layout; local stores only matter with
        # multiple local contexts
        if kv is not None and "dist" not in kv.type and \
                len(self._contexts) <= 1:
            kv = None
        if kv is not None:
            if "async" in kv.type and self._update_on_kvstore is False:
                # reference trainer.py raises the same way: async pushes
                # are applied by the server optimizer, so worker-side
                # updates are not expressible
                raise ValueError(
                    "Please set update_on_kvstore=True when training "
                    "with dist_async; updates must run on the kvstore "
                    "servers")
            if self._update_on_kvstore is None:
                # async PS REQUIRES server-side updates; sync dist and
                # local reduce default to worker-side updates
                self._update_on_kvstore = "async" in kv.type
            if self._update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    kv.init(i, p.data(self._contexts[0]))
            self._kvstore = kv
        else:
            self._update_on_kvstore = False
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def optimizer(self):
        return self._optimizer

    # ------------------------------------------------------- compiled step
    def compile(self, block, loss, zero=False, mesh=None):
        """Fuse ``block``'s forward + ``loss`` + backward + this
        trainer's optimizer update into ONE donated XLA program
        (``compiled_step.CompiledStep``): ``cs = trainer.compile(net,
        loss_fn)`` then ``cs.step(x, y)`` replaces the whole
        ``record()/backward()/step()`` iteration.  The eager path stays
        the default/debug mode; see docs/COMPILED_STEP.md for the
        donation/rebind contract and the supported-optimizer set.

        ``zero=True`` builds the same fused program with ZeRO
        weight-update sharding over the 'dp' mesh axis — params and
        optimizer state live as 1/n per-device shards inside the program
        (``compiled_step.ZeroCompiledStep``, docs/ZERO.md); ``mesh``
        optionally pins the device mesh for that path."""
        from .. import compiled_step as _compiled

        return _compiled.compile_step(block, loss, self, zero=zero,
                                      mesh=mesh)

    # ------------------------------------------------------------ step
    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce grads across devices, then update
        (reference: trainer.py step:302).

        With the numerics health layer enabled (``health.enable()`` /
        ``MXNET_TPU_HEALTH=1``) each sampled step additionally feeds the
        global monitor a fused device-side global grad-norm, per-grad
        NaN/Inf sentinels, and per-param update-to-weight ratios, then
        advances its clock (drain + flight record happen at interval
        boundaries); an unhandled exception dumps the flight recorder
        before propagating.  Disabled: one dict read."""
        _rts.inc("trainer_steps")
        hm = _health.monitor() if _health._state["on"] else None
        with _StepTelemetry(self, batch_size, hm):
            self._step(batch_size, ignore_stale_grad, hm)

    def _health_grads_and_prev(self, hm):
        """Feed gradients to the health monitor and snapshot the
        pre-update weight buffers (device references only — no copies,
        no syncs).  Returns the snapshot for ``_health_updates``."""
        if hm is None or not hm.sampling:
            return None
        named = [(p.name, p.list_grad()[0]) for p in self._params
                 if p.grad_req != "null"]
        hm.observe_grads(named)
        return [(p, p.list_data()[0]._data) for p in self._params
                if p.grad_req != "null"]

    def _health_updates(self, hm, prev):
        """Feed per-param update-to-weight ratios from the pre/post
        update buffer pairs captured by ``_health_grads_and_prev``."""
        if prev is None:
            return
        for p, old in prev:
            hm.observe_update(p.name, p.list_data()[0]._data, old)

    def _step(self, batch_size, ignore_stale_grad, hm=None):
        # rescale BEFORE the kvstore ships the optimizer server-side
        # (reference: step() calls _check_and_rescale_grad first; changing
        # batch_size after init would silently use the stale rescale)
        new_rescale = self._scale / batch_size
        if self._kv_initialized and self._update_on_kvstore and \
                new_rescale != self._optimizer.rescale_grad:
            import warnings

            warnings.warn("batch_size change detected after kvstore "
                          "init; server-side optimizer keeps the "
                          "original rescale_grad")
        self._optimizer.rescale_grad = new_rescale
        if not self._kv_initialized:
            self._contexts = self._contexts or self._check_contexts()
            self._init_kvstore()
        if self._update_on_kvstore:
            # server-side update: push grads, pull back fresh WEIGHTS
            # (reference: trainer.py _update with update_on_kvstore).
            # Health caveat: the aggregated gradient only ever exists on
            # the server, so grad_norm/grad:* here reflect THIS worker's
            # local pre-aggregation grads (the update-to-weight ratios
            # below do reflect the applied server update).
            prev = self._health_grads_and_prev(hm)
            for i, p in enumerate(self._params):
                if p.grad_req == "null":
                    continue
                self._kvstore.push(i, p.list_grad())
                self._kvstore.pull(i, out=p.list_data())
            self._health_updates(hm, prev)
            return
        self._allreduce_grads()
        prev = self._health_grads_and_prev(hm)
        self._update(ignore_stale_grad)
        self._health_updates(hm, prev)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            # reference: trainer.py raises — with a server-side optimizer
            # a push already UPDATES, so the two-phase workflow would pull
            # weights into gradient buffers and corrupt training
            raise ValueError(
                "allreduce_grads() is not supported when updates run on "
                "the kvstore (update_on_kvstore=True); use step() or pass "
                "update_on_kvstore=False")
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        with _profiler.span("trainer:allreduce", "trainer"):
            for i, p in enumerate(self._params):
                if p.grad_req != "null":
                    grads = p.list_grad()
                    self._kvstore.push(i, grads)
                    self._kvstore.pull(i, out=grads)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise ValueError(
                "update() is not supported when updates run on the "
                "kvstore (update_on_kvstore=True); use step() or pass "
                "update_on_kvstore=False")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        # optimizer_update is a container phase: warm dispatch of the
        # fused optimizer ops inside stays in dispatch_warm; this
        # records the update's exclusive remainder (stepstats.py)
        ss_on = _stepstats._state["on"]
        if ss_on:
            ss_tok = _stepstats.begin()
        with _profiler.span("trainer:update", "trainer"):
            self._update_impl(ignore_stale_grad)
        if ss_on:
            _stepstats.end("optimizer_update", ss_tok)

    def _update_impl(self, ignore_stale_grad=False):
        n_dev = max(len(p.list_data()) for p in self._params) \
            if self._params else 1
        while len(self._updaters) < n_dev:
            # one Updater per device copy: per-index optimizer state must
            # not be shared across copies (reference: trainer.py _updaters)
            self._updaters.append(_optimizer.get_updater(self._optimizer))
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            for upd, data, grad in zip(self._updaters,
                                       p.list_data(), p.list_grad()):
                if getattr(p, "_grad_stype", "default") == "row_sparse" \
                        and getattr(self._optimizer, "lazy_update", False):
                    # sparse_grad param (e.g. Embedding): wrap the dense
                    # autograd result as row_sparse (device-side nonzero-row
                    # scan) so the optimizer's lazy kernel touches only the
                    # used rows; skipped for optimizers w/o lazy kernels
                    from ..ndarray import sparse as _sp
                    grad = _sp.cast_storage(grad, "row_sparse")
                upd(i, grad, data)

    # ------------------------------------------------------------ states
    def save_states(self, fname):
        """Save optimizer/updater state (reference: trainer.py
        save_states) — atomically (temp + fsync + rename via
        ``checkpoint.atomic_write``) and with a version header, so a
        crash mid-save can never leave a torn states file under the
        final name (docs/CHECKPOINTING.md)."""
        import pickle

        payload = self._updaters[0].get_states(dump_optimizer=True) \
            if hasattr(self._updaters[0], "get_states") \
            else self._updaters[0].states
        if not isinstance(payload, bytes):
            payload = pickle.dumps(payload,
                                   protocol=pickle.HIGHEST_PROTOCOL)
        with _ckpt.atomic_write(fname) as tmp:
            with open(tmp, "wb") as f:
                f.write(_ckpt.TRAINER_STATES_MAGIC)
                f.write(bytes([_ckpt.TRAINER_STATES_VERSION]))
                f.write(b"\n")
                f.write(payload)

    def load_states(self, fname):
        """Load optimizer/updater state; understands both the versioned
        header format and legacy headerless pickles."""
        import pickle

        with open(fname, "rb") as f:
            head = f.read(len(_ckpt.TRAINER_STATES_MAGIC))
            if head == _ckpt.TRAINER_STATES_MAGIC:
                version = f.read(1)[0]
                if version > _ckpt.TRAINER_STATES_VERSION:
                    raise ValueError(
                        "trainer states file %s has version %d; this "
                        "build understands <= %d"
                        % (fname, version, _ckpt.TRAINER_STATES_VERSION))
                f.read(1)  # newline
                states = f.read()
            else:
                states = pickle.loads(head + f.read())
        for u in self._updaters:
            if hasattr(u, "set_states"):
                u.set_states(states)
            else:
                u.states = pickle.loads(states) \
                    if isinstance(states, bytes) else states
