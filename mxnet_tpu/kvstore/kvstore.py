"""KVStore — the parameter-synchronisation façade.

Reference: include/mxnet/kvstore.h:59-442, src/kvstore/kvstore_local.h:69,
comm.h (CommCPU/CommDevice/CommDeviceTree), kvstore_nccl.h, kvstore_dist.h.

TPU-native design: there is no parameter server and no NCCL — reduction
is either trivial (single process: sum the pushed list, one fused XLA
kernel) or an ``lax.psum`` over the device mesh inside the jitted train
step (kvstore type 'tpu'; see mxnet_tpu/parallel/).  The KVStore *API*
(init/push/pull/set_optimizer/rank/num_workers/barrier) is kept verbatim
so Module/Trainer code written against the reference runs unchanged:

- 'local' / 'device' / 'nccl' / 'tpu'  → in-process store; push sums
  across the per-device gradient copies (the reference's Comm::Reduce,
  comm.h:57) and runs the updater if set.
- 'dist_sync' → multi-process via ``jax.distributed`` when launched
  under tools/launch.py (DMLC_* env parity); cross-worker reduction uses
  a host-level allreduce over the process group.  On a single process it
  degrades to 'local' with num_workers=1.
- 'dist_async' → true parameter-server mode: pushes apply immediately on
  host-side PS processes (kvstore/ps.py, launched by
  ``launch.py -s N``), the reference's Hogwild-style async semantics.
"""

from __future__ import annotations

import json
import os
import pickle

import numpy as _np

from .. import profiler as _profiler
from .. import runtime_stats as _rts
from .. import stepstats as _stepstats
from ..base import MXNetError
from ..ndarray import NDArray, array, zeros
from ..optimizer import Optimizer, get_updater
from .gradient_compression import GradientCompression

__all__ = ["KVStore", "create"]


def create(name="local"):
    """Create a KVStore (reference: kvstore.cc:40 factory)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    name = name.lower()
    if name in ("local", "local_update_cpu", "local_allreduce_cpu",
                "local_allreduce_device", "device", "nccl", "tpu"):
        return KVStore(name)
    if name in ("dist", "dist_sync", "dist_sync_device", "dist_device_sync"):
        return DistKVStore(name)
    if name == "dist_async":
        return DistAsyncKVStore(name)
    raise MXNetError("unknown KVStore type %r" % name)


class KVStore:
    """Single-process store (reference: KVStoreLocal, kvstore_local.h:69)."""

    def __init__(self, type_name="local"):
        self._type = type_name
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._compression = None
        self._str_keys = set()

    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    # ------------------------------------------------------------- core
    def _canon(self, key):
        return key

    def init(self, key, value):
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                raise MXNetError("key %r already initialized" % (k,))
            self._store[k] = v.copy()

    def push(self, key, value, priority=0):
        """Reduce pushed values per key; apply updater if set
        (reference: KVStoreLocal::PushImpl → Comm::Reduce comm.h:57)."""
        _rts.inc("kvstore_pushes")
        # step-anatomy kvstore phase (base + dist backends all route
        # through this wrapper): a container window, so the add_n
        # reduce dispatch inside stays in dispatch_warm (stepstats.py)
        ss_on = _stepstats._state["on"]
        if ss_on:
            ss_tok = _stepstats.begin()
        with _profiler.span("kvstore:push", "kvstore",
                            args={"type": self._type}
                            if _profiler._state["running"] else None):
            self._push_impl(key, value, priority)
        if ss_on:
            _stepstats.end("kvstore", ss_tok)

    def _push_impl(self, key, value, priority):
        keys, values = _key_value_list(key, value)
        for k, vlist in zip(keys, values):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            merged = vlist[0]
            if len(vlist) > 1:
                from ..ndarray import imperative_invoke

                merged = imperative_invoke("add_n", list(vlist), {})[0]
            else:
                merged = merged.copy()
            if self._compression is not None:
                merged = self._compression.compress_decompress(k, merged)
            if self._updater is not None:
                self._updater(_key_int(k), merged, self._store[k])
            else:
                # reference semantics (kvstore_local.h:213): without an
                # updater the store holds the REDUCED value, replacing —
                # this is what makes Trainer's push(grads)/pull(grads)
                # return the cross-device gradient sum
                self._store[k] = merged

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Broadcast stored value (reference: Comm::Broadcast comm.h:62)."""
        assert out is not None
        _rts.inc("kvstore_pulls")
        ss_on = _stepstats._state["on"]
        if ss_on:
            ss_tok = _stepstats.begin()
        with _profiler.span("kvstore:pull", "kvstore",
                            args={"type": self._type}
                            if _profiler._state["running"] else None):
            self._pull_impl(key, out, priority, ignore_sparse)
        if ss_on:
            _stepstats.end("kvstore", ss_tok)

    def _pull_impl(self, key, out, priority, ignore_sparse):
        keys, outs = _key_value_list(key, out)
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            for o in olist:
                self._store[k].copyto(o)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull selected rows (reference: PullRowSparse kvstore.h:232).

        Rows outside row_ids are zeroed in the output — dense emulation of
        the row_sparse pull contract."""
        assert out is not None and row_ids is not None
        keys, outs = _key_value_list(key, out)
        if isinstance(row_ids, NDArray):
            row_ids = [row_ids] * len(outs[0])
        for k, olist in zip(keys, outs):
            full = self._store[k]
            for o, rid in zip(olist, row_ids if isinstance(row_ids, list)
                              else [row_ids] * len(olist)):
                idx = rid.asnumpy().astype(_np.int64) if isinstance(rid, NDArray) \
                    else _np.asarray(rid, dtype=_np.int64)
                dense = _np.zeros(full.shape, dtype=full.asnumpy().dtype)
                src = full.asnumpy()
                dense[idx] = src[idx]
                o[:] = dense

    # ------------------------------------------------------------- config
    def set_updater(self, updater):
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        """reference: kvstore.py set_optimizer → server-side optimizer;
        here the 'server' is in-process."""
        if not isinstance(optimizer, Optimizer):
            raise TypeError("optimizer must be an Optimizer")
        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """2-bit gradient compression with error feedback
        (reference: gradient_compression.h:52)."""
        params = dict(compression_params)
        ctype = params.get("type", "2bit")
        if ctype != "2bit":
            raise MXNetError("only 2bit compression is supported (parity)")
        self._compression = GradientCompression(
            threshold=float(params.get("threshold", 0.5)))

    # ------------------------------------------------------------- dist API
    def barrier(self):
        pass

    def _send_command_to_servers(self, head, body):
        """reference: MXKVStoreSendCommmandToServers, a silent no-op on
        non-dist stores.  We keep the no-op for parity (reference scripts
        issue server commands unconditionally) but warn, so a 'server
        profiling' request that goes nowhere doesn't surface only as a
        mysteriously missing trace file later."""
        import warnings

        warnings.warn(
            "kvstore type %r has no server processes to command — the "
            "request is ignored (server commands need 'dist_async' under "
            "tools/launch.py -s N)" % self._type, stacklevel=2)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "updater is not set"
        from ..checkpoint import atomic_write

        with atomic_write(fname) as tmp:
            with open(tmp, "wb") as f:
                f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "updater is not set"
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())


class DistKVStore(KVStore):
    """Multi-process synchronous store over jax.distributed.

    Reference: kvstore_dist.h:44 (worker) + kvstore_dist_server.h:155.
    The ps-lite push/pull wire protocol is replaced by collective
    reduction across the jax process group (DCN); server-side optimizer
    semantics (sync aggregation of num_workers pushes before update,
    kvstore_dist_server.h:346) are preserved by reducing first, then
    applying the updater once per pushed key.
    """

    def __init__(self, type_name):
        super().__init__(type_name)
        self._rank = int(os.environ.get("DMLC_WORKER_ID",
                                        os.environ.get("JAX_PROCESS_ID", 0)))
        self._num_workers = int(os.environ.get("DMLC_NUM_WORKER", 1))
        self._group = None
        if self._num_workers > 1:
            self._init_process_group()

    def _init_process_group(self):
        import jax

        # normally already joined at import (mxnet_tpu._maybe_init_distributed
        # reads the same DMLC_* contract); handle direct construction too.
        if jax.distributed.is_initialized():
            self._group = True
            return
        coord = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9091")
        try:
            jax.distributed.initialize(
                coordinator_address="%s:%s" % (coord, port),
                num_processes=self._num_workers,
                process_id=self._rank)
            self._group = True
        except Exception as e:
            raise MXNetError("dist kvstore init failed: %s" % e)

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    def _push_impl(self, key, value, priority):
        keys, values = _key_value_list(key, value)
        for k, vlist in zip(keys, values):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            merged = vlist[0]
            if len(vlist) > 1:
                from ..ndarray import imperative_invoke

                merged = imperative_invoke("add_n", list(vlist), {})[0]
            else:
                merged = merged.copy()
            if self._compression is not None:
                # per-worker quantize BEFORE aggregation (reference:
                # PushCompressed kvstore_dist.h:378 — each worker sends
                # its own quantized gradient; residual stays worker-side)
                merged = self._compression.compress_decompress(k, merged)
            if self._num_workers > 1:
                merged = self._allreduce(merged)
            if self._updater is not None:
                self._updater(_key_int(k), merged, self._store[k])
            else:
                # replace with the reduced value (reference:
                # kvstore_dist_server.h:360 CopyFromTo(merged, stored))
                self._store[k] = merged

    def init(self, key, value):
        """Init + broadcast rank 0's value so every replica starts from
        identical weights (reference: dist kv.init stores on the server
        once; workers pull the same tensor, kvstore_dist.h InitImpl)."""
        super().init(key, value)
        if self._num_workers > 1:
            keys, _ = _key_value(key, value)
            for k in keys:
                v = self._store[k]
                src = v if self._rank == 0 else \
                    NDArray(v._data * 0, v._ctx)
                self._store[k] = self._allreduce(src)

    def _allreduce(self, arr):
        """Cross-process sum over DCN via a tiny jitted psum."""
        import jax

        from ..parallel import host_allreduce

        return NDArray(host_allreduce(arr._data), arr._ctx)

    def barrier(self):
        if self._num_workers > 1:
            import jax

            # a zero-byte allreduce doubles as a barrier
            self._allreduce(array(_np.zeros(1, dtype=_np.float32)))


class DistAsyncKVStore(KVStore):
    """`dist_async`: true parameter-server mode over the host-side PS
    (`kvstore/ps.py`).

    Reference semantics (kvstore_dist_server.h async branch): each
    worker's push is applied to the server weights IMMEDIATELY — no
    cross-worker aggregation barrier — and pull returns whatever the
    server currently holds, so workers run at their own pace with stale
    weights (Hogwild-style).  The server runs the optimizer; workers
    ship it once via set_optimizer (reference: kvstore.py
    _send_command_to_servers).
    """

    def __init__(self, type_name="dist_async"):
        super().__init__(type_name)
        self._rank = int(os.environ.get("DMLC_WORKER_ID", 0))
        self._num_workers = int(os.environ.get("DMLC_NUM_WORKER", 1))
        launched = "DMLC_ROLE" in os.environ or \
            "MXTPU_PS_PORTS" in os.environ
        if not launched and self._num_workers == 1:
            # no launcher env: degrade to an in-process store like the
            # other dist types (a notebook `mx.kv.create('dist_async')`
            # must not dial a nonexistent server)
            self._client = None
            return
        if int(os.environ.get("DMLC_NUM_SERVER", "1")) == 0:
            # launched with -n but not -s: without this check the client
            # would dial the jax.distributed coordinator port (which IS
            # listening) and hang in recv instead of failing fast
            raise MXNetError(
                "dist_async needs parameter-server processes — relaunch "
                "with `tools/launch.py -n %d -s <servers>`"
                % self._num_workers)
        from .ps import PSClient

        try:
            self._client = PSClient()
        except OSError as e:
            raise MXNetError(
                "dist_async needs parameter-server processes — start the "
                "job with `tools/launch.py -n <workers> -s <servers>` "
                "(%s)" % e)
        # diag-push cadence: MXNET_TPU_DIAG_PUSH=N>1 parks this rank's
        # diag snapshot on shard 0 every N pushes (N=1: on dump only)
        try:
            self._diag_push_every = int(
                os.environ.get("MXNET_TPU_DIAG_PUSH", "0") or 0)
        except ValueError:
            self._diag_push_every = 0
        self._diag_push_count = 0
        # register as the server-command channel (profiler forwarding,
        # diag push on dump) — the reference needs an explicit
        # set_kvstore_handle call; the TPU-native form self-registers
        # since a process has at most one dist store
        _profiler.set_kvstore_handle(self)
        if os.environ.get("MXNET_TPU_PROFILE") or \
                _profiler._state["running"]:
            # profiled run: estimate the worker→server clock offset now
            # so this rank's chrome trace can be merged onto the
            # cluster timeline (profiler.merge_traces)
            try:
                self.estimate_clock_offset()
            except Exception:
                pass  # telemetry must never block store construction

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    def init(self, key, value):
        """Rank 0's value becomes the server copy (reference: InitImpl
        pushes init only from worker 0)."""
        if self._client is None:
            return super().init(key, value)
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            if self._rank == 0:
                self._client.init(k, v.asnumpy())
        self.barrier()

    def _push_impl(self, key, value, priority):
        if self._client is None:
            return super()._push_impl(key, value, priority)
        keys, values = _key_value_list(key, value)
        for k, vlist in zip(keys, values):
            merged = vlist[0]
            if len(vlist) > 1:
                from ..ndarray import imperative_invoke

                merged = imperative_invoke("add_n", list(vlist), {})[0]
            if self._compression is not None:
                merged = self._compression.compress_decompress(k, merged)
            self._client.push(k, merged.asnumpy())
        if self._diag_push_every > 1:
            self._diag_push_count += 1
            if self._diag_push_count % self._diag_push_every == 0:
                try:
                    self.push_diag()
                except Exception:
                    pass  # interval telemetry must never fail a push

    def _pull_impl(self, key, out, priority, ignore_sparse):
        if self._client is None:
            return super()._pull_impl(key, out, priority, ignore_sparse)
        assert out is not None
        keys, outs = _key_value_list(key, out)
        for k, olist in zip(keys, outs):
            fetched = self._client.pull(k)
            for o in olist:
                o[:] = fetched

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        if self._client is None:
            return super().row_sparse_pull(key, out, priority, row_ids)
        assert out is not None and row_ids is not None
        keys, outs = _key_value_list(key, out)
        for k, olist in zip(keys, outs):
            full = self._client.pull(k)
            rids = row_ids if isinstance(row_ids, list) \
                else [row_ids] * len(olist)
            for o, rid in zip(olist, rids):
                idx = rid.asnumpy().astype(_np.int64) \
                    if isinstance(rid, NDArray) \
                    else _np.asarray(rid, dtype=_np.int64)
                dense = _np.zeros_like(full)
                dense[idx] = full[idx]
                o[:] = dense

    def set_optimizer(self, optimizer):
        """Ship the optimizer to the servers; the update runs
        server-side (reference: server-side `Executor` running the
        pickled optimizer, kvstore_dist_server.h:95)."""
        import copy
        import pickle

        if self._client is None:
            return super().set_optimizer(optimizer)
        if not isinstance(optimizer, Optimizer):
            raise TypeError("optimizer must be an Optimizer")
        self._optimizer = optimizer
        if self._rank == 0:
            # strip param_dict before shipping: it holds live Parameters
            # whose pickling embeds full weight tensors — the server only
            # needs the per-index multipliers (reference: server gets the
            # optimizer string, not the weights)
            wire = copy.copy(optimizer)
            wire.param_dict = {}
            wire.lr_mult = dict(optimizer.lr_mult)
            wire.wd_mult = dict(optimizer.wd_mult)
            for idx, p in optimizer.param_dict.items():
                if getattr(p, "lr_mult", 1.0) != 1.0:
                    wire.lr_mult[idx] = p.lr_mult
                if getattr(p, "wd_mult", 1.0) != 1.0:
                    wire.wd_mult[idx] = p.wd_mult
            self._client.set_optimizer(
                pickle.dumps(wire, protocol=pickle.HIGHEST_PROTOCOL))
        self.barrier()

    def barrier(self):
        if self._client is not None:
            self._client.barrier()

    def _send_command_to_servers(self, head, body):
        """Generic controller channel (reference: ps-lite server commands
        — stop/set-optimizer/gradient-compression/profiler)."""
        if self._client is None:
            return super()._send_command_to_servers(head, body)  # warns
        self._client.send_command(head, body)

    def stop_servers(self):
        """Send the stop command (reference: scheduler 'stop' on
        finalize)."""
        if self._client is not None:
            self._client.stop_servers()
        # deregister the server-command channel: an atexit diag dump
        # after shutdown must not try to push through a stopped store
        if _profiler._kvstore_handle is self:
            _profiler.set_kvstore_handle(None)

    # --------------------------------------------- distributed telemetry
    def server_stats(self):
        """Every PS shard's server-side metrics (per-key bytes in/out +
        applied-mutation versions, per-peer request counts, apply/handle
        latency histograms, queue depth, accepted connections, plus the
        ``dedup`` exactly-once table and ``durability`` checkpoint
        state) — the ``stats`` command (docs/OBSERVABILITY.md
        "Distributed telemetry").  Empty list on a degraded in-process
        store."""
        if self._client is None:
            return []
        return self._client.server_stats()

    def checkpoint_servers(self):
        """Ask every PS shard to commit its durable store snapshot NOW
        (the reserved ``ckpt`` command head): one
        ``{"enabled", "step", "path"}`` dict per shard — ``enabled`` is
        False for servers running without ``MXNET_TPU_PS_CKPT``
        (docs/CHECKPOINTING.md "Server-side durability").  Empty list
        on a degraded in-process store."""
        if self._client is None:
            return []
        return self._client.checkpoint_shards()

    def push_diag(self, top=20):
        """Park this rank's ``runtime_stats.diag_snapshot()`` on PS
        shard 0 (``diag_put``) so the operator can pull every rank's
        dump from one place.  Returns False on a degraded store."""
        if self._client is None:
            return False
        from .. import runtime_stats as _rts2

        snap = _rts2.diag_snapshot(top=top)
        ident = snap.get("identity") or {}
        # the rank key travels on its own line ahead of the payload so
        # the server never JSON-parses the (potentially large) dump
        key = "%s %s" % (ident.get("role", "worker"),
                         ident.get("rank", "?"))
        self._client.command_shard(
            0, "diag_put",
            key + "\n" + json.dumps(snap, default=repr))
        return True

    def cluster_diag(self):
        """Fetch every rank's parked diag dump from shard 0:
        ``{"worker 3": dump-dict, ...}`` — feed the values to
        ``runtime_stats.cluster_report`` for the merged view."""
        if self._client is None:
            return {}
        raw = self._client.command_shard(0, "diag_get") or {}
        return {k: json.loads(v) for k, v in raw.items()}

    def request_restart(self, rank=None, reason=""):
        """Park a supervised-relaunch request for ``rank`` (default:
        THIS worker) on PS shard 0 — the reserved ``restart_rank``
        head the ``tools/launch.py --supervise`` loop polls and honors
        (the autopilot's kv-RTT straggler reflex).  Returns the
        shard's ack dict, or False on a degraded in-process store."""
        if self._client is None:
            return False
        return self._client.request_restart(
            self.rank if rank is None else int(rank), reason=reason)

    def estimate_clock_offset(self, samples=5):
        """Ping shard 0 and register this process's wall-clock offset
        with the profiler (``set_clock_offset``) so per-rank chrome
        traces merge onto one cluster timeline.  Returns the offset in
        seconds (None on a degraded store)."""
        if self._client is None:
            return None
        offset, _rtt = self._client.ping(0, samples=samples)
        _profiler.set_clock_offset(offset)
        return offset


def _key_value(key, value):
    """Normalize (key(s), value(s)) to parallel lists."""
    if isinstance(key, (str, int)):
        return [key], [value if isinstance(value, NDArray) else value]
    assert len(key) == len(value)
    return list(key), list(value)


def _key_value_list(key, value):
    """Normalize to (keys, list-of-NDArray-lists)."""
    if isinstance(key, (str, int)):
        vlist = value if isinstance(value, (list, tuple)) else [value]
        return [key], [list(vlist)]
    out_keys = list(key)
    out_vals = []
    for v in value:
        out_vals.append(list(v) if isinstance(v, (list, tuple)) else [v])
    return out_keys, out_vals


def _key_int(key):
    if isinstance(key, int):
        return key
    try:
        return int(key)
    except (TypeError, ValueError):
        return key
