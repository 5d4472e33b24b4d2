"""Continuous-batching multi-tenant inference server over the predictor
stack.

The deployment surface so far (``predictor.py``, ``native/src/
predict.cc``) runs one request at a time: no concurrency, no batching,
no latency accounting — fine for an offline scorer, useless for the
millions-of-users north star.  This module is the serving layer:

- an :class:`InferenceServer` wraps a loaded model (a
  :class:`~mxnet_tpu.predictor.Predictor`, a hybridized Gluon block, or
  a pure callable) behind a thread-safe request queue;
- a batcher thread packs concurrent requests into **bucketed batch
  shapes** (a configurable ladder, default 1/2/4/8/16, padded to the
  bucket with the padded rows masked out of the scatter) — the
  reference's ``BucketingModule`` idiom applied to serving: ONE cached
  jitted executable per bucket, built lazily on first use and counted
  (``serve_bucket_compiles``), so shape churn is always an explicit
  jit-cache miss and never a silent retrace (XLA whole-program fusion
  economics, arXiv:2301.13062);
- a small worker pool pipelines host→device staging, device compute,
  and device→host result scatter, so on an async backend the device
  never idles behind host copies (the scatter's ``device_get`` is the
  module's ONE deliberate host-sync sink, pragma'd at the source per
  the mxlint callgraph rule);
- every batch feeds the operational substrate: per-request queue-wait
  and end-to-end latency into ``histogram.py`` (``serve:queue_wait``,
  ``serve:e2e``, ``serve:batch`` plus per-bucket ``serve:batch:b<B>``),
  request/sample/byte/occupancy counters into ``runtime_stats``
  (scrapeable live through the PR 10 Prometheus endpoint), an optional
  JSONL timeline of per-batch samples (``MXNET_TPU_SERVE_METRICS``,
  ``log.rank_suffix_path`` honored) shaped like ``metrics_timeline``
  samples so the perf-doctor trend rules run over a serving soak
  unchanged, and health-layer NaN/Inf sentinels on served outputs —
  a non-finite row is a rate-limited warning + a rejected response +
  a flight record, never a silent bad payload;
- :meth:`InferenceServer.stop` drains the queue before the workers
  exit, so shutdown never drops an accepted request;
- request-grain observability rides the same seams guard-first: the
  ``reqtrace`` lifecycle ring (tail-sampled per-request records +
  chrome-trace flow events) and the ``slo`` error-budget counters —
  one dict read per seam when disabled (docs/OBSERVABILITY.md
  "Request x-ray & SLOs").

No cell of the benchmark measures it yet; the open-loop generator a
serving cell will use is ``benchmark/harness/loadgen.py`` (PERF.md §7).
Doctor rules: ``perfdoctor``'s
``serve-queue-dominated`` / ``serve-bucket-churn``; section rendering:
``tools/diagnose.py --serving``.  Docs: docs/SERVING.md.

Environment variables
---------------------
``MXNET_TPU_SERVE_BUCKETS``   comma bucket ladder (default
    ``1,2,4,8,16``); the largest bucket is the max batch.
``MXNET_TPU_SERVE_QUEUE``     max queued samples before submissions are
    rejected with :class:`RequestRejected` (default 1024) — explicit
    backpressure instead of unbounded latency.
``MXNET_TPU_SERVE_WAIT_MS``   max milliseconds a partial batch waits
    for more requests while every worker is busy (default 2.0; with an
    idle worker a partial batch dispatches immediately, so an unloaded
    server adds no batching latency).
``MXNET_TPU_SERVE_WORKERS``   pipeline worker threads (default 2).
``MXNET_TPU_SERVE_METRICS``   JSONL path for per-batch timeline
    samples (rank-suffixed via ``log.rank_suffix_path``).
``MXNET_TPU_SERVE_SENTINEL``  ``0`` disables the served-output NaN/Inf
    sentinel (default on).
``MXNET_TPU_SERVE_WARN_INTERVAL``  min seconds between non-finite
    rejection warnings (default 60).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

import numpy as np

from . import autopilot as _autopilot
from . import device_memory as _dm
from . import health as _health
from . import histogram as _histogram
from . import reqtrace as _reqtrace
from . import runtime_stats as _rts
from . import slo as _slo
from .log import get_logger, rank_suffix_path, warn_rate_limited

__all__ = ["InferenceServer", "RequestRejected", "ServerStopped",
           "DEFAULT_BUCKETS", "snapshot", "servers"]

DEFAULT_BUCKETS = (1, 2, 4, 8, 16)

WARN_INTERVAL = float(os.environ.get(
    "MXNET_TPU_SERVE_WARN_INTERVAL", "60"))

_logger_cache: list = []


def _logger():
    if not _logger_cache:
        _logger_cache.append(get_logger("mxnet_tpu.serving"))
    return _logger_cache[0]


class RequestRejected(RuntimeError):
    """The server refused (queue full, bad shape) or rejected (non-
    finite output) this request — the caller always gets an explicit
    error, never a silent bad payload."""


class ServerStopped(RuntimeError):
    """The server stopped without serving this request (``stop(
    drain=False)``)."""


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_buckets():
    raw = os.environ.get("MXNET_TPU_SERVE_BUCKETS")
    if not raw:
        return DEFAULT_BUCKETS
    try:
        out = tuple(sorted({int(b) for b in raw.split(",") if b.strip()}))
    except ValueError:
        return DEFAULT_BUCKETS
    return out or DEFAULT_BUCKETS


def _fetch(values):
    """Materialize a batch's output device buffers on host.

    THE deliberate host-sync sink of the serving layer: it runs on a
    pipeline worker thread at the scatter stage — after the device
    compute was dispatched — never on a compute path, and the whole
    output list transfers in one batched ``device_get``."""
    import jax

    return jax.device_get(list(values))  # mxlint: disable=trace-host-sync


def _device_put(array):
    """Stage one padded host batch onto the default device (the
    host→device leg of the pipeline; async on real backends)."""
    import jax

    return jax.device_put(array)


# ------------------------------------------------------------- requests


class _Request:
    """One queued inference request: named input arrays with a leading
    sample axis, plus the future the caller waits on.

    The completion event is allocated LAZILY — only a caller that
    blocks in :meth:`result` before the batch lands pays for a
    ``threading.Event``; the ``_done`` flag itself is a plain
    GIL-atomic attribute write, keeping the per-request submit/scatter
    cost low at high request rates."""

    __slots__ = ("inputs", "n", "t_submit", "t_batched", "t_done",
                 "_done", "_event", "_outputs", "_error",
                 # request x-ray (reqtrace.py): id + lifecycle record,
                 # set only while tracing is on — readers use getattr,
                 # so the disabled path never touches these slots
                 "rid", "trace")

    def __init__(self, inputs, n):
        self.inputs = inputs
        self.n = n
        self.t_submit = time.perf_counter()
        self.t_batched = None
        self.t_done = None
        self._done = False
        self._event = None
        self._outputs = None
        self._error = None

    # -------------------------------------------------------- future API
    def done(self):
        return self._done

    def result(self, timeout=None):
        """Block until served; returns the list of per-output numpy
        arrays (leading axis = this request's sample count).  Raises
        :class:`RequestRejected` / :class:`ServerStopped` on
        rejection."""
        if not self._done:
            ev = self._event
            if ev is None:
                ev = self._event = threading.Event()
            # re-check after publishing the event: a completion that
            # raced the allocation set _done first, then (at worst)
            # missed an event created after its set — the re-check
            # plus the bounded waits below make that race benign
            deadline = None if timeout is None \
                else time.perf_counter() + timeout
            while not self._done:
                if deadline is None:
                    ev.wait(0.5)
                elif not ev.wait(min(0.5, deadline -
                                     time.perf_counter())) \
                        and time.perf_counter() >= deadline:
                    raise TimeoutError(
                        "inference request not served within %.3fs"
                        % timeout)
        if self._error is not None:
            raise self._error
        return self._outputs

    def _finish(self):
        self.t_done = time.perf_counter()
        self._done = True
        ev = self._event
        if ev is not None:
            ev.set()

    def _complete(self, outputs):
        self._outputs = outputs
        self._finish()

    def _fail(self, error):
        self._error = error
        self._finish()


# --------------------------------------------------------- model adapters


class _PredictorModel:
    """Bucket executables over a loaded :class:`Predictor`: one
    weight-sharing ``_reshape_clone`` per bucket whose executor forward
    is called as a pure jitted function (thread-safe — no shared
    executor state is mutated per call)."""

    def __init__(self, predictor):
        self._pred = predictor
        self.input_names = list(predictor.get_input_names())
        exec_args = predictor._exec.arg_dict
        self.sample_shapes = {n: tuple(exec_args[n].shape[1:])
                              for n in self.input_names}
        self.dtypes = {n: np.dtype(predictor._type_dict.get(n, np.float32))
                       for n in self.input_names}

    def build(self, bucket):
        shapes = {n: (bucket,) + self.sample_shapes[n]
                  for n in self.input_names}
        clone = self._pred._reshape_clone(shapes)
        exc = clone._exec
        fwd, _bwd, _diff = exc._get_fns(False)
        arg_names = exc._arg_names
        base_args = [a._data for a in exc.arg_arrays]
        aux_vals = [a._data for a in exc.aux_arrays]
        input_idx = {n: arg_names.index(n) for n in self.input_names}

        def run(inputs):
            args = list(base_args)
            for name, val in inputs.items():
                args[input_idx[name]] = val
            outs, _new_aux = fwd(args, aux_vals, 0)
            return list(outs)

        return run


class _BlockModel:
    """Bucket executables over a (hybridized) Gluon block with one
    input.  The block call mutates shared cached-graph state, so calls
    are serialized under one lock; each bucket shape jit-caches its own
    executable inside the block's cached graph."""

    def __init__(self, block, sample_shape, input_name="data",
                 dtype=np.float32):
        self._block = block
        self._lock = threading.Lock()
        self.input_names = [input_name]
        self.sample_shapes = {input_name: tuple(sample_shape)}
        self.dtypes = {input_name: np.dtype(dtype)}

    def build(self, bucket):
        from .ndarray import NDArray

        name = self.input_names[0]

        def run(inputs):
            with self._lock:
                out = self._block(NDArray(inputs[name]))
            outs = out if isinstance(out, (list, tuple)) else [out]
            return [o._data for o in outs]

        return run


class _CallableModel:
    """Bucket executables over a user callable ``fn(inputs, bucket) ->
    output(s)`` (jax arrays in, jax/numpy arrays out) — the test /
    custom-runtime seam."""

    def __init__(self, fn, input_shapes, dtypes=None):
        self._fn = fn
        self.input_names = list(input_shapes)
        self.sample_shapes = {n: tuple(s) for n, s in input_shapes.items()}
        self.dtypes = {n: np.dtype((dtypes or {}).get(n, np.float32))
                       for n in self.input_names}

    def build(self, bucket):
        fn = self._fn

        def run(inputs):
            out = fn(inputs, bucket)
            return list(out) if isinstance(out, (list, tuple)) else [out]

        return run


def _adapt(model, input_shapes=None, input_name="data", dtype=np.float32):
    from .predictor import Predictor

    if isinstance(model, Predictor):
        return _PredictorModel(model)
    if callable(model) and not hasattr(model, "register_forward_hook"):
        if not input_shapes:
            raise ValueError("a callable model needs input_shapes "
                             "({name: per-sample shape})")
        return _CallableModel(model, input_shapes)
    # Gluon block
    if not input_shapes:
        raise ValueError("a block model needs input_shapes "
                         "({name: per-sample shape})")
    if len(input_shapes) != 1:
        raise ValueError("block serving supports exactly one input")
    (name, shape), = input_shapes.items()
    return _BlockModel(model, shape, input_name=name, dtype=dtype)


# --------------------------------------------------------------- server


# LIVE servers, newest last.  A stopped server is removed (a long-
# lived process re-creating servers must not leak models and compiled
# bucket executables through this registry) and leaves its final stats
# snapshot in _FINAL, so diag dumps of a finished load run still carry
# the serving section without pinning the server object.
_SERVERS: list = []
_FINAL: list = []


class InferenceServer:
    """Continuous-batching inference server over a loaded model.

    Parameters
    ----------
    model : Predictor | gluon.Block | callable
        The loaded model.  A ``Predictor`` brings its own input
        names/shapes; a block or callable needs ``input_shapes``
        (``{name: per-sample shape}``, no batch axis).
    buckets : tuple of int, optional
        Batch-size ladder (default ``MXNET_TPU_SERVE_BUCKETS`` or
        1/2/4/8/16).  The largest bucket caps a single request's
        sample count.
    max_wait_ms / max_queue / workers : optional
        Batch-formation wait, queued-sample bound, and pipeline worker
        count — each defaulting from its ``MXNET_TPU_SERVE_*`` env row.
    metrics_path : str, optional
        JSONL destination for per-batch timeline samples (default
        ``MXNET_TPU_SERVE_METRICS``).

    Use as a context manager (``with InferenceServer(pred) as srv:``)
    or call :meth:`start` / :meth:`stop` explicitly.
    """

    def __init__(self, model, input_shapes=None, buckets=None,
                 max_wait_ms=None, max_queue=None, workers=None,
                 metrics_path=None, name="serve"):
        self._model = _adapt(model, input_shapes=input_shapes)
        self.buckets = tuple(sorted(set(buckets or _env_buckets())))
        if not self.buckets or any(b <= 0 for b in self.buckets):
            raise ValueError("buckets must be positive ints")
        self.max_bucket = self.buckets[-1]
        self.max_wait = (_env_float("MXNET_TPU_SERVE_WAIT_MS", 2.0)
                         if max_wait_ms is None else float(max_wait_ms)) \
            / 1e3
        self.max_queue = _env_int("MXNET_TPU_SERVE_QUEUE", 1024) \
            if max_queue is None else int(max_queue)
        self.num_workers = max(1, _env_int("MXNET_TPU_SERVE_WORKERS", 2)
                               if workers is None else int(workers))
        self.name = name
        self._sentinel_on = os.environ.get(
            "MXNET_TPU_SERVE_SENTINEL") != "0"
        self._metrics_path = metrics_path \
            if metrics_path is not None \
            else os.environ.get("MXNET_TPU_SERVE_METRICS")
        self._metrics_file = None
        self._metrics_lock = threading.Lock()

        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        # mxlint: disable=thread-shared-state -- mutated under _cond; the one lock-free read is a monitoring gauge (staleness harmless)
        self._queued_samples = 0
        self._inflight = 0
        # mxlint: disable=thread-shared-state -- monotonic publication flag: set without the lock, loops re-check it under their condition
        self._stopping = False
        self._running = False
        # mxlint: disable=thread-shared-state -- written in start() before the workers it names exist (Thread.start happens-before)
        self._threads: list = []
        # mxlint: disable=thread-shared-state -- mutated under _batch_cond; the batcher's emptiness peek under _cond is advisory pacing
        self._batchq: collections.deque = collections.deque()
        self._batch_cond = threading.Condition()

        # mxlint: disable=thread-shared-state -- double-checked build cache: lock-free dict get fast path, builds serialized under _bucket_lock
        self._bucket_fns: dict = {}
        self._bucket_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self.stats = {"requests": 0, "samples": 0, "batches": 0,
                      "padded_rows": 0, "rejected_queue": 0,
                      "rejected_nonfinite": 0, "rejected_shape": 0,
                      "completed": 0, "errors": 0,
                      "bucket_compiles": 0, "knob_adjusts": 0,
                      "per_bucket": {b: {"batches": 0, "samples": 0}
                                     for b in self.buckets},
                      "first_batch_t": None, "last_batch_t": None}
        self._rejections: collections.deque = collections.deque(maxlen=64)
        # runtime knob-adjust audit trail (set_workers/set_max_wait_ms/
        # set_max_queue); mutated under _stats_lock
        self._adjustments: collections.deque = collections.deque(
            maxlen=32)
        # live worker-thread count, mutated under _batch_cond: grown by
        # set_workers spawning, shrunk by idle workers retiring when it
        # exceeds num_workers
        self._worker_count = 0
        self._batch_seq = 0
        # serving is an observability-first surface: latency percentiles
        # ARE the product, so raise the histogram layer unless the env
        # explicitly forces it off (the metrics_timeline convention)
        if os.environ.get("MXNET_TPU_HISTOGRAMS") != "0":
            _histogram.enable()
        _SERVERS.append(self)

    # ----------------------------------------------------------- lifecycle
    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.stop(drain=exc_type is None)
        return False

    def start(self):
        """Start the batcher + worker threads (idempotent)."""
        if self._running:
            return self
        self._stopping = False
        self._running = True
        t = threading.Thread(target=self._batcher_loop,
                             name="mxtpu-serve-batcher", daemon=True)
        t.start()
        self._threads = [t]
        with self._batch_cond:
            self._worker_count = self.num_workers
        for i in range(self.num_workers):
            w = threading.Thread(target=self._worker_loop,
                                 name="mxtpu-serve-worker-%d" % i,
                                 daemon=True)
            w.start()
            self._threads.append(w)
        return self

    def stop(self, drain=True, timeout=60.0):
        """Stop the server.  ``drain=True`` (default) serves every
        already-accepted request first; ``drain=False`` fails pending
        requests with :class:`ServerStopped`.  New submissions are
        refused either way."""
        if not self._running:
            # a constructed-but-never-started (or already-stopped)
            # server must still leave the live registry — it would
            # otherwise pin the model forever and its zero-stats
            # section would shadow a real run's in module snapshot()
            if self in _SERVERS:
                _SERVERS.remove(self)
            return
        with self._cond:
            self._stopping = True
            if not drain:
                while self._queue:
                    req = self._queue.popleft()
                    self._queued_samples -= req.n
                    req._fail(ServerStopped("server stopped before "
                                            "serving this request"))
            self._cond.notify_all()
        with self._batch_cond:
            self._batch_cond.notify_all()
        for t in self._threads:
            t.join(timeout)
        self._running = False
        self._close_metrics()
        # drop out of the live registry; the final stats snapshot stays
        # readable (module snapshot() / diag dumps of a finished run)
        _FINAL[:] = [self.snapshot()]
        if self in _SERVERS:
            _SERVERS.remove(self)

    def warmup(self):
        """Build + compile every bucket executable up front (one padded
        all-zeros batch per bucket), so the first real request never
        pays a compile."""
        for b in self.buckets:
            fn = self._bucket_fn(b)
            inputs = {n: _device_put(np.zeros((b,) + s, self._model.dtypes[n]))
                      for n, s in self._model.sample_shapes.items()}
            _fetch(fn(inputs))
        return self

    # ------------------------------------------------------------- submit
    def submit(self, inputs):
        """Queue one request; returns a future with ``result(timeout)``.

        ``inputs``: one array (single-input models) or ``{name:
        array}``; every array carries a leading sample axis ``k`` (1 <=
        k <= the largest bucket) over the model's per-sample shape.
        Raises :class:`RequestRejected` up front on a full queue or a
        shape/name mismatch — shape churn is an explicit error, never a
        silent retrace of a new executable."""
        named = self._validate(inputs)
        n = next(iter(named.values())).shape[0]
        req = _Request(named, n)
        with self._cond:
            if self._stopping or not self._running:
                raise RequestRejected("server is not accepting requests"
                                      " (stopped)")
            if self._queued_samples + n > self.max_queue:
                self._count_reject("rejected_queue", n)
                raise RequestRejected(
                    "queue full (%d queued samples, max %d) — backpressure;"
                    " retry or add capacity" % (self._queued_samples,
                                               self.max_queue))
            depth = self._queued_samples
            self._queue.append(req)
            self._queued_samples += n
            # request x-ray: open the lifecycle record while still
            # holding _cond, so the batcher can never see a traced
            # request before its record exists.  Disabled: 1 dict read.
            if _reqtrace._state["on"]:
                _reqtrace.on_submit(req, depth)
            # one waiter on this condition in steady state (the
            # batcher) — notify() keeps the submit hot path cheap
            self._cond.notify()
        # flow-span tail of the submit seam, OUTSIDE _cond: the
        # profiler takes its own lock and must never nest under the
        # server condvar
        if _reqtrace._state["on"]:
            _reqtrace.on_submitted(req)
        return req

    def infer(self, inputs, timeout=60.0):
        """Blocking convenience: ``submit(inputs).result(timeout)``."""
        return self.submit(inputs).result(timeout)

    def _validate(self, inputs):
        shapes = self._model.sample_shapes
        if not isinstance(inputs, dict):
            if len(shapes) != 1:
                raise RequestRejected(
                    "model has inputs %s — pass a {name: array} dict"
                    % sorted(shapes))
            inputs = {next(iter(shapes)): inputs}
        unknown = set(inputs) - set(shapes)
        missing = set(shapes) - set(inputs)
        if unknown or missing:
            self._count_reject("rejected_shape")
            raise RequestRejected(
                "request inputs %s != model inputs %s"
                % (sorted(inputs), sorted(shapes)))
        named = {}
        n = None
        for name, arr in inputs.items():
            arr = np.asarray(arr, dtype=self._model.dtypes[name],
                             order="C")
            want = shapes[name]
            if arr.ndim != len(want) + 1 or tuple(arr.shape[1:]) != want:
                self._count_reject("rejected_shape")
                raise RequestRejected(
                    "input %r shape %s != (k,)+%s — requests carry an "
                    "explicit leading sample axis" % (name, arr.shape,
                                                      want))
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                self._count_reject("rejected_shape")
                raise RequestRejected("inconsistent sample counts "
                                      "across inputs")
            named[name] = arr
        if not n or n > self.max_bucket:
            self._count_reject("rejected_shape")
            raise RequestRejected(
                "request sample count %s outside 1..%d (the largest "
                "bucket) — split large requests client-side"
                % (n, self.max_bucket))
        return named

    def _count_reject(self, kind, n=0):
        with self._stats_lock:
            self.stats[kind] += 1
        _rts.inc("serve_rejected")
        _rts.inc("serve_" + kind)
        # front-door rejects (queue/shape) never enter the pipeline —
        # record them as explicit lifecycle outcomes and SLO bad events
        # here; nonfinite rejections carry a full record and reach both
        # layers through _reject_nonfinite instead
        if kind != "rejected_nonfinite":
            if _reqtrace._state["on"]:
                _reqtrace.on_reject(kind, n)
            if _slo._state["on"]:
                _slo.on_request(None, False)

    # ------------------------------------------------------------ batching
    def _bucket_for(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_bucket

    def _batcher_loop(self):
        """Form batches: greedily pack whole queued requests up to the
        largest bucket; dispatch immediately when the bucket is full or
        a worker sits idle, else wait up to ``max_wait`` for more
        arrivals (continuous batching: zero added latency unloaded,
        bucket-filling under load)."""
        while True:
            with self._cond:
                while not self._queue and not self._stopping:
                    self._cond.wait()
                if not self._queue:
                    break  # stopping and fully drained
                picked, total = self._pick_locked([], 0)
                deadline = time.perf_counter() + self.max_wait
                while total < self.max_bucket and not self._stopping:
                    if self._inflight < self.num_workers \
                            and not self._batchq:
                        break  # an idle worker: serve what we have now
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                    picked, total = self._pick_locked(picked, total)
                self._inflight += 1
            bucket = self._bucket_for(total)
            now = time.perf_counter()
            for r in picked:
                r.t_batched = now
            # batch-join seam: stamp bucket/batch-id, flow-step the
            # head-sampled members.  Disabled: one dict read per batch.
            if _reqtrace._state["on"]:
                _reqtrace.on_join(picked, bucket)
            with self._batch_cond:
                # bounded pipeline: at most one staged batch per worker
                # beyond what is executing, so accepted requests stay in
                # the accounted queue and ``max_queue`` is a real bound
                # on in-server backlog (explicit backpressure at submit)
                while len(self._batchq) >= self.num_workers:
                    self._batch_cond.wait(timeout=0.05)
                self._batchq.append((picked, total, bucket))
                self._batch_cond.notify()
        # wake the workers so they can observe the drained shutdown
        with self._batch_cond:
            self._batch_cond.notify_all()

    def _pick_locked(self, picked, total):
        while self._queue and total + self._queue[0].n <= self.max_bucket:
            r = self._queue.popleft()
            self._queued_samples -= r.n
            picked.append(r)
            total += r.n
        return picked, total

    def _bucket_fn(self, bucket):
        fn = self._bucket_fns.get(bucket)
        if fn is not None:
            return fn
        with self._bucket_lock:
            fn = self._bucket_fns.get(bucket)
            if fn is None:
                t0 = time.perf_counter()
                fn = self._bucket_fns[bucket] = self._model.build(bucket)
                with self._stats_lock:
                    self.stats["bucket_compiles"] += 1
                _rts.inc("serve_bucket_compiles")
                if _histogram._state["on"]:
                    _histogram.observe("serve:bucket_build",
                                       time.perf_counter() - t0)
        return fn

    # ------------------------------------------------------------- workers
    def _worker_loop(self):
        while True:
            with self._batch_cond:
                while not self._batchq:
                    if self._worker_count > self.num_workers:
                        # shrunk via set_workers: surplus workers
                        # retire when idle (never mid-batch)
                        self._worker_count -= 1
                        return
                    if self._stopping and self._batcher_done():
                        return
                    self._batch_cond.wait(timeout=0.1)
                picked, total, bucket = self._batchq.popleft()
                # a batcher blocked on the pipeline bound can stage the
                # next batch now
                self._batch_cond.notify_all()
            try:
                self._serve_batch(picked, total, bucket)
            except Exception as e:  # a bad batch must not kill the pool
                failed = 0
                for r in picked:
                    if not r.done():
                        r._fail(RequestRejected(
                            "batch execution failed: %s: %s"
                            % (type(e).__name__, e)))
                        failed += 1
                        if _reqtrace._state["on"]:
                            _reqtrace.on_done(r, "error", r.t_done)
                        if _slo._state["on"]:
                            _slo.on_request(
                                (r.t_done - r.t_submit) * 1e3, False)
                if failed:
                    with self._stats_lock:
                        self.stats["errors"] += failed
                warn_rate_limited(
                    _logger(), "serving:batch-error", WARN_INTERVAL,
                    "serving batch failed (%s: %s) — %d request(s) "
                    "rejected", type(e).__name__, e, len(picked))
            finally:
                with self._cond:
                    self._inflight -= 1
                    self._cond.notify_all()

    def _batcher_done(self):
        return self._threads and not self._threads[0].is_alive()

    def _serve_batch(self, picked, total, bucket):
        t0 = time.perf_counter()
        hist_on = _histogram._state["on"]
        rt_on = _reqtrace._state["on"]
        slo_on = _slo._state["on"]
        if hist_on:
            for r in picked:
                _histogram.observe("serve:queue_wait",
                                   r.t_batched - r.t_submit)
        # host→device staging: one zero-padded host array per input
        # (rows past `total` are padding; their outputs are masked out
        # of the scatter below)
        inputs = {}
        bytes_in = 0
        for name, sshape in self._model.sample_shapes.items():
            dt = self._model.dtypes[name]
            buf = np.empty((bucket,) + sshape, dtype=dt)
            off = 0
            for r in picked:
                buf[off:off + r.n] = r.inputs[name]
                off += r.n
            if off < bucket:
                buf[off:] = 0  # the pad rows (masked out of the scatter)
            bytes_in += buf.nbytes
            inputs[name] = _device_put(buf)
        t_staged = time.perf_counter() if rt_on else None
        # device compute (async dispatch on real backends) …
        outs = self._bucket_fn(bucket)(inputs)
        # … then the one host-sync: the result scatter's batched fetch
        host_outs = _fetch(outs)
        t1 = time.perf_counter()
        if rt_on:
            # execution seam: worker/pad/staging/compute stamps, once
            # per batch (host floats only — the fetch already synced)
            _reqtrace.on_exec(picked, threading.current_thread().name,
                              bucket - total, t_staged, t1)
        bad_rows = self._sentinel(host_outs, total)
        bytes_out = sum(int(o.nbytes) for o in host_outs)
        off = 0
        completed = 0
        for r in picked:
            rows = slice(off, off + r.n)
            off += r.n
            if bad_rows is not None and bad_rows[rows].any():
                self._reject_nonfinite(r, bucket)
                continue
            r._complete([np.asarray(o[rows]) for o in host_outs])
            completed += 1
            if rt_on:
                _reqtrace.on_done(r, "ok", r.t_done)
            if slo_on:
                _slo.on_request((r.t_done - r.t_submit) * 1e3, True)
        if completed:
            with self._stats_lock:
                self.stats["completed"] += completed
        if hist_on:
            _histogram.observe("serve:batch", t1 - t0)
            _histogram.observe("serve:batch:b%d" % bucket, t1 - t0)
            for r in picked:
                _histogram.observe("serve:e2e", r.t_done - r.t_submit)
        self._account_batch(picked, total, bucket, t0, t1,
                            bytes_in, bytes_out)

    def _sentinel(self, host_outs, total):
        """Per-row non-finite mask over the valid rows of every float
        output (the serving analog of the health layer's device
        sentinels — here the batch is already on host for the scatter,
        so the check is a cheap vectorized reduction), or None when
        disabled/clean."""
        if not self._sentinel_on:
            return None
        bad = None
        for o in host_outs:
            if not np.issubdtype(o.dtype, np.floating):
                continue
            row_bad = ~np.isfinite(
                o[:total].reshape(total, -1)).all(axis=1)
            bad = row_bad if bad is None else (bad | row_bad)
        if bad is None or not bad.any():
            return None
        full = np.zeros(host_outs[0].shape[0], dtype=bool)
        full[:total] = bad
        return full

    def _reject_nonfinite(self, req, bucket):
        req._fail(RequestRejected(
            "served output contains non-finite values — response "
            "rejected (serving NaN sentinel; docs/SERVING.md)"))
        self._count_reject("rejected_nonfinite")
        rec = {"t": time.time(), "bucket": bucket, "n": req.n,
               "reason": "non-finite output"}
        self._rejections.append(rec)
        # flight-record the incident alongside training numerics
        # history when the health layer is live (ring read/append only
        # — never drains the monitor's device queue)
        mon = _health._GLOBAL[0] if _health._state["on"] and \
            _health._GLOBAL else None
        if mon is not None:
            mon.flight.append({"step": -1, "time": rec["t"],
                               "loss": None, "grad_norm": None,
                               "nan_total": 1.0, "inf_total": 0.0,
                               "first_bad": "serve:output",
                               "counters": None})
        warn_rate_limited(
            _logger(), "serving:nonfinite", WARN_INTERVAL,
            "non-finite values in a served output (bucket %d, %d "
            "sample(s)) — response rejected, not returned.  Check the "
            "model's numerics (docs/SERVING.md 'Output sentinels').",
            bucket, req.n)
        # sentinel hits are always-retained lifecycle outcomes and SLO
        # bad events (the request DID consume pipeline capacity)
        if _reqtrace._state["on"]:
            _reqtrace.on_done(req, "rejected_nonfinite", req.t_done)
        if _slo._state["on"]:
            _slo.on_request((req.t_done - req.t_submit) * 1e3, False)

    def _account_batch(self, picked, total, bucket, t0, t1,
                       bytes_in, bytes_out):
        wall = t1 - t0
        with self._stats_lock:
            s = self.stats
            s["requests"] += len(picked)
            s["samples"] += total
            s["batches"] += 1
            s["padded_rows"] += bucket - total
            pb = s["per_bucket"][bucket]
            pb["batches"] += 1
            pb["samples"] += total
            if s["first_batch_t"] is None:
                s["first_batch_t"] = t0
            s["last_batch_t"] = t1
            self._batch_seq += 1
            seq = self._batch_seq
        _rts.inc("serve_requests", len(picked))
        _rts.inc("serve_samples", total)
        _rts.inc("serve_batches")
        _rts.inc("serve_padded_rows", bucket - total)
        _rts.inc("serve_bytes_in", bytes_in)
        _rts.inc("serve_bytes_out", bytes_out)
        if self._metrics_path:
            waits = [r.t_batched - r.t_submit for r in picked]
            e2es = [r.t_done - r.t_submit for r in picked
                    if r.t_done is not None]
            self._write_metrics({
                "t": time.time(), "step": seq, "wall_ms": wall * 1e3,
                "throughput": (total / wall) if wall > 0 else None,
                "bucket": bucket, "n": total,
                "occupancy": total / bucket,
                "queue_wait_ms": sum(waits) / len(waits) * 1e3
                if waits else 0.0,
                "e2e_ms": sum(e2es) / len(e2es) * 1e3 if e2es else None,
                "queue_depth": self._queued_samples,
                "live_bytes": _dm.live_totals()[0]})
        # observability autopilot serving seam: gated reflexes over the
        # live serving stats, AFTER this batch's accounting committed.
        # Disabled: one dict read.
        if _autopilot._state["on"]:
            _autopilot.on_serve(self)

    # ------------------------------------------------------- JSONL export
    def _write_metrics(self, sample):
        """One atomic line per batch (the ``metrics_timeline`` JSONL
        convention: whole-record writes, rank-suffixed path, export
        goes dark with one warning on IO failure)."""
        with self._metrics_lock:
            f = self._metrics_file
            if f is None:
                path = rank_suffix_path(self._metrics_path)
                try:
                    f = open(path, "a", buffering=1)
                except OSError as e:
                    warn_rate_limited(
                        _logger(), "serving:metrics-open", 60,
                        "cannot open MXNET_TPU_SERVE_METRICS file %s "
                        "(%s) — serving timeline export disabled",
                        path, e)
                    self._metrics_path = None
                    return
                self._metrics_file = f
            try:
                f.write(json.dumps(sample, separators=(",", ":"),
                                   default=repr) + "\n")
            except (OSError, ValueError) as e:
                warn_rate_limited(
                    _logger(), "serving:metrics-write", 60,
                    "writing a serving timeline sample failed (%s) — "
                    "export disabled", e)
                self._metrics_path = None
                self._close_metrics_locked()

    def _close_metrics(self):
        with self._metrics_lock:
            self._close_metrics_locked()

    def _close_metrics_locked(self):
        f = self._metrics_file
        self._metrics_file = None
        if f is not None:
            try:
                f.close()
            except OSError:
                pass

    # -------------------------------------------------------- runtime knobs
    def _note_adjust(self, knob, old, new):
        rec = {"t": time.time(), "knob": knob, "old": old, "new": new}
        with self._stats_lock:
            self.stats["knob_adjusts"] += 1
            self._adjustments.append(rec)
        _rts.inc("serve_knob_adjusts")

    def set_workers(self, n):
        """Adjust the pipeline worker count at runtime (thread-safe).
        Growing spawns workers immediately on a running server;
        shrinking lets surplus workers retire at their next idle wait
        (a worker never abandons a batch mid-execution).  The batcher
        reads ``num_workers`` fresh every iteration, so the dispatch
        and pipeline bounds follow without a restart."""
        n = max(1, int(n))
        # both conditions guard reads of ``num_workers`` (the batcher's
        # idle-worker check under _cond, the pipeline bound under
        # _batch_cond); no other path holds the two at once, so the
        # nested acquisition cannot deadlock
        with self._cond, self._batch_cond:
            old = self.num_workers
            self.num_workers = n
            spawn = 0
            if self._running and not self._stopping:
                spawn = max(0, n - self._worker_count)
                self._worker_count += spawn
            self._batch_cond.notify_all()
            self._cond.notify_all()
        for _ in range(spawn):
            w = threading.Thread(
                target=self._worker_loop,
                name="mxtpu-serve-worker-%d" % len(self._threads),
                daemon=True)
            w.start()
            self._threads.append(w)
        if n != old:
            self._note_adjust("workers", old, n)
        return n

    def set_max_wait_ms(self, ms):
        """Adjust the batch-formation wait at runtime (thread-safe:
        published under the batcher's condition, read fresh per
        batch)."""
        ms = max(0.0, float(ms))
        with self._cond:
            old = self.max_wait * 1e3
            self.max_wait = ms / 1e3
            self._cond.notify_all()
        if ms != old:
            self._note_adjust("max_wait_ms", round(old, 3),
                              round(ms, 3))
        return ms

    def set_max_queue(self, n):
        """Adjust the queued-sample bound (the load-shed threshold) at
        runtime (thread-safe: ``submit`` reads it fresh per request)."""
        n = max(1, int(n))
        old = self.max_queue
        self.max_queue = n
        if n != old:
            self._note_adjust("max_queue", old, n)
        return n

    # ----------------------------------------------------------- read side
    def queue_depth(self):
        """Currently queued samples (accepted, not yet batched)."""
        return self._queued_samples

    def snapshot(self):
        """JSON-ready serving stats: request/sample/batch totals,
        rejection counts by kind, per-bucket occupancy, bucket-
        executable compiles, derived QPS over the served window, and
        the recent rejection records.  Latency distributions live in
        the shared histogram section (``serve:*`` series)."""
        with self._stats_lock:
            s = dict(self.stats)
            per_bucket = {b: dict(v)
                          for b, v in self.stats["per_bucket"].items()}
        qps = None
        if s["first_batch_t"] is not None and s["samples"]:
            span = (s["last_batch_t"] or 0) - s["first_batch_t"]
            if span > 0:
                qps = s["samples"] / span
        out = {"enabled": True, "running": self._running,
               "name": self.name, "buckets": list(self.buckets),
               "workers": self.num_workers,
               "max_queue": self.max_queue,
               "max_wait_ms": self.max_wait * 1e3,
               "queue_depth": self._queued_samples,
               "requests": s["requests"], "samples": s["samples"],
               "batches": s["batches"],
               "padded_rows": s["padded_rows"],
               "bucket_compiles": s["bucket_compiles"],
               "rejected": {"queue": s["rejected_queue"],
                            "nonfinite": s["rejected_nonfinite"],
                            "shape": s["rejected_shape"]},
               # per-outcome breakdown: every request a client ever
               # handed us lands in exactly one of these buckets
               "outcomes": {"ok": s["completed"],
                            "rejected_queue": s["rejected_queue"],
                            "rejected_shape": s["rejected_shape"],
                            "rejected_nonfinite":
                                s["rejected_nonfinite"],
                            "error": s["errors"]},
               "per_bucket": {str(b): v for b, v in per_bucket.items()
                              if v["batches"]},
               "qps": qps,
               "knob_adjusts": s["knob_adjusts"],
               "adjustments": list(self._adjustments)[-8:],
               "rejections": list(self._rejections)[-16:]}
        mean_occ = None
        if s["batches"]:
            # occupancy = valid rows / bucket rows over the whole run
            total_rows = sum(b * v["batches"]
                             for b, v in per_bucket.items())
            if total_rows:
                mean_occ = s["samples"] / total_rows
        out["mean_occupancy"] = mean_occ
        return out


# ------------------------------------------------------- module surface


def servers():
    """Every LIVE (not yet stopped) server, oldest first."""
    return list(_SERVERS)


def snapshot():
    """The newest live server's :meth:`InferenceServer.snapshot`, the
    most recently stopped server's final stats when none is live, or a
    disabled stub — what ``runtime_stats.snapshot()['serving']``
    embeds (via ``sys.modules``, so a process that never imported the
    serving layer pays nothing)."""
    if _SERVERS:
        return _SERVERS[-1].snapshot()
    if _FINAL:
        return dict(_FINAL[0])
    return {"enabled": False}


def reset():
    """Forget every live server and retained final snapshot (tests)."""
    _SERVERS.clear()
    _FINAL.clear()
    from .log import reset_rate_limits

    reset_rate_limits("serving:")
