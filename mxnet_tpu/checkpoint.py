"""Checkpoint & recovery subsystem — atomic async checkpointing and
one-call auto-resume.

The observability cycle (runtime telemetry, memory/cost analytics, the
numerics health layer) made training runs self-observing; this module
makes them *survivable*.  Every legacy persistence path in the
reference wrote in place, blocking, and non-atomically
(``model.py:save_checkpoint``, ``Block.save_parameters``,
``Trainer.save_states``) — a preempted TPU worker or a SIGKILL
mid-write loses the run.  Here all of them route through one
crash-consistent substrate:

- :func:`atomic_write` — temp file in the target directory + flush +
  ``os.fsync`` + ``os.replace`` (+ directory fsync), so no persistence
  path can leave a torn file under its final name.
- :class:`CheckpointManager` — directory-per-checkpoint layout with a
  ``MANIFEST.json`` commit record carrying per-file SHA-256 checksums.
  A checkpoint exists iff its manifest is valid and every checksum
  matches; :meth:`CheckpointManager.latest` skips torn or corrupt
  checkpoints (warning through ``log.py``) and falls back to the
  newest fully-valid one.  Keep-last-N retention prunes committed
  checkpoints beyond ``keep`` plus any stale temp directories.
- **Asynchronous snapshots.**  XLA device buffers are immutable and
  every in-place NDArray write *rebinds* the buffer
  (``NDArray._assign``), so capturing the current ``_data`` references
  under the training thread is a **zero-copy, sync-free, consistent
  device-side snapshot** — the optimizer stepping afterwards creates
  new buffers and never mutates captured ones.  Host materialization
  and disk I/O happen on a background writer thread; the one batched
  ``jax.device_get`` there (:func:`_materialize`) is the module's
  single deliberate host-sync sink, pragma'd per the callgraph rule
  exactly like ``health._fetch``.  Back-to-back saves coalesce: while
  one snapshot is being written, only the newest queued snapshot
  survives (counted in ``totals['coalesced']``).
- **Complete resumable unit.**  One manifest covers parameters,
  optimizer/Trainer updater state (device buffers captured the same
  zero-copy way), the stripped optimizer hyper-state (update counters,
  schedulers — never ``param_dict``), the framework RNG state
  (seed + counter), the step clock, and a ``runtime_stats``
  health/flight probe.  :meth:`CheckpointManager.restore` (or
  module-level :func:`auto_resume`) puts all of it back in one call.

Cost model (pinned by ``tests/test_bench_gate.py``): disabled — the
default — the :func:`on_step` hook inside ``gluon.Trainer.step`` costs
one dict read and nothing else.  Enabled, a sampled step pays reference
captures plus a pickle of host-side scalars; the device and the
training thread never block on disk.

Environment variables (docs/ENV_VARS.md, docs/CHECKPOINTING.md)
---------------------------------------------------------------
``MXNET_TPU_CKPT``            checkpoint directory: enable the global
    manager at import (auto-save from ``Trainer.step``).
``MXNET_TPU_CKPT_INTERVAL``   save every N trainer steps (default 100).
``MXNET_TPU_CKPT_KEEP``       keep-last-N retention (default 5).
``MXNET_TPU_CKPT_ASYNC``      ``0`` forces blocking (synchronous)
    writes (default 1: background writer thread).

Security note: checkpoint payloads (``trainer.pkl``) are plain pickle,
like the reference's ``Trainer.save_states`` — load checkpoints only
from directories you trust, same trust model as the reference.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import re
import shutil
import threading
import time

from . import histogram as _histogram
from . import runtime_stats as _rts
from . import stepstats as _stepstats
from .log import get_logger, warn_rate_limited

__all__ = ["atomic_write", "CheckpointManager", "enable", "disable",
           "is_enabled", "manager", "on_step", "auto_resume", "lineage",
           "save_legacy", "load_legacy", "load_aux", "MANIFEST_NAME",
           "TRAINER_STATES_MAGIC", "TRAINER_STATES_VERSION"]

MANIFEST_NAME = "MANIFEST.json"
MANIFEST_VERSION = 1

# Trainer.save_states header (gluon/trainer.py): magic + u8 version +
# newline, then the pickle payload.  Legacy headerless files still load.
TRAINER_STATES_MAGIC = b"MXTPUTRAINER"
TRAINER_STATES_VERSION = 1

_state = {"on": False}
_GLOBAL: list = []              # [CheckpointManager] while enabled

_logger_cache: list = []
_tmp_seq = iter(range(1, 1 << 62))


def _logger():
    if not _logger_cache:
        _logger_cache.append(get_logger("mxnet_tpu.checkpoint"))
    return _logger_cache[0]


# ------------------------------------------------------------ atomic IO


@contextlib.contextmanager
def atomic_write(path):
    """Yield a temp path in ``path``'s directory; on clean exit fsync it
    and ``os.replace`` onto ``path`` (then fsync the directory), so the
    final name only ever holds a complete file.  On error the temp file
    is removed and nothing under ``path`` changes.

    THE atomic-write primitive every persistence path routes through
    (``Block.save_parameters``, ``Trainer.save_states``,
    ``model.save_checkpoint``, the manager's data files + manifest).
    """
    path = os.path.abspath(path)
    d = os.path.dirname(path)
    tmp = os.path.join(d, ".%s.%d.%d.tmp" % (os.path.basename(path),
                                             os.getpid(), next(_tmp_seq)))
    try:
        yield tmp
        _fsync_file(tmp)
        os.replace(tmp, path)
        _fsync_dir(d)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def _fsync_file(path):
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path):
    # directory fsync makes the rename itself durable; some platforms
    # (or exotic filesystems) refuse O_RDONLY on dirs — best effort
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _sha256(path, chunk=1 << 20):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            b = f.read(chunk)
            if not b:
                return h.hexdigest()
            h.update(b)


# ------------------------------------------------- device-side capture


class _NDLeaf:
    """Marker for an NDArray leaf inside a captured/serialized state
    tree: holds the immutable device buffer at capture time and the
    materialized numpy value after the background write.  Restoring
    turns it back into an NDArray."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def __reduce__(self):
        return (_NDLeaf, (self.value,))


def _capture_tree(obj):
    """Zero-copy capture: NDArray leaves become :class:`_NDLeaf` refs to
    their current (immutable) device buffer; containers are rebuilt so
    later mutation of the live tree cannot touch the snapshot; host
    scalars pass through.  Never syncs."""
    from .ndarray import NDArray

    if isinstance(obj, NDArray):
        return _NDLeaf(obj._data)
    if isinstance(obj, dict):
        return {k: _capture_tree(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_capture_tree(v) for v in obj)
    return obj


def _tree_leaves(obj, out):
    if isinstance(obj, _NDLeaf):
        out.append(obj)
    elif isinstance(obj, dict):
        for v in obj.values():
            _tree_leaves(v, out)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            _tree_leaves(v, out)
    return out


def _restore_tree(obj, ctx=None):
    """Inverse of capture after a round trip: _NDLeaf(numpy) → NDArray."""
    from .ndarray import array

    if isinstance(obj, _NDLeaf):
        return array(obj.value, ctx=ctx)
    if isinstance(obj, dict):
        return {k: _restore_tree(v, ctx) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_restore_tree(v, ctx) for v in obj)
    return obj


def _materialize(snapshot):
    """Bring every captured device buffer in a snapshot to host, in ONE
    batched transfer, replacing each :class:`_NDLeaf`'s buffer with its
    numpy value in place.

    THE deliberate host-sync sink of the checkpoint layer: it runs only
    on the background writer thread (or inside an explicitly blocking
    ``save``), never on a compute path — the training step queues
    buffer references and moves on."""
    import jax
    import numpy as np

    leaves = []
    _tree_leaves(snapshot.get("params", {}), leaves)
    _tree_leaves(snapshot.get("trainer", {}), leaves)
    if not leaves:
        return snapshot
    host = jax.device_get([lf.value for lf in leaves])  # mxlint: disable=trace-host-sync
    for lf, hv in zip(leaves, host):
        lf.value = np.asarray(hv)
    return snapshot


def _strip_optimizer(optimizer):
    """Pickle an Optimizer's hyper-state without ``param_dict`` (live
    Parameters — pickling them would materialize full weight tensors on
    the training thread; the per-index multipliers are folded into
    lr_mult/wd_mult exactly like the dist kvstore wire copy)."""
    import copy

    wire = copy.copy(optimizer)
    wire.param_dict = {}
    wire.lr_mult = dict(optimizer.lr_mult)
    wire.wd_mult = dict(optimizer.wd_mult)
    for idx, p in getattr(optimizer, "param_dict", {}).items():
        if getattr(p, "lr_mult", 1.0) != 1.0:
            wire.lr_mult[idx] = p.lr_mult
        if getattr(p, "wd_mult", 1.0) != 1.0:
            wire.wd_mult[idx] = p.wd_mult
    return pickle.dumps(wire, protocol=pickle.HIGHEST_PROTOCOL)


# ------------------------------------------------------------- manager


class CheckpointManager:
    """Atomic, asynchronous, self-validating checkpoint store.

    Layout: ``<directory>/<prefix>-<step:08d>/`` holding ``params.npz``,
    ``trainer.pkl`` (when trainer state was captured), and the
    ``MANIFEST.json`` commit record.  The whole checkpoint is staged in
    a temp directory and renamed into place only after every file (and
    the manifest) is fsynced — a checkpoint either exists completely or
    not at all; :meth:`latest` additionally re-hashes every file so a
    corrupted-on-disk checkpoint is skipped, not half-loaded.
    """

    def __init__(self, directory, keep=5, interval=None, async_write=None,
                 prefix="ckpt"):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.keep = max(1, int(keep))
        self.interval = int(interval) if interval else 0
        if async_write is None:
            async_write = os.environ.get("MXNET_TPU_CKPT_ASYNC", "1") != "0"
        self.async_write = bool(async_write)
        self.prefix = prefix
        self._final_re = re.compile(
            r"^%s-(\d{8,})$" % re.escape(prefix))
        self.step_clock = 0
        self.last_good = None       # {"path", "step"} of newest commit
        self.last_error = None
        # best-effort monitoring counters; each key has exactly one
        # writer (trainer thread: saves/coalesced; writer thread:
        # written/errors/corrupt_skipped), so no lock is shared
        # mxlint: disable=thread-shared-state -- single writer per key
        self.totals = {"saves": 0, "written": 0, "coalesced": 0,
                       "corrupt_skipped": 0, "errors": 0}
        self._cv = threading.Condition()
        self._queued = None         # newest pending snapshot
        self._writing = False
        self._stop = False
        self._thread = None
        self._prune_stale_tmp()

    # ------------------------------------------------------------ save
    def save_trainer(self, trainer, step=None, extra=None, pin=False):
        """Snapshot a ``gluon.Trainer``'s complete resumable unit —
        parameters, updater state, optimizer hyper-state, RNG, step —
        without blocking: device buffers are captured by reference
        (immutable under XLA; in-place writes rebind), everything else
        is host scalars.  Returns immediately in async mode.

        ``pin=True`` materializes the captured buffers to host BEFORE
        returning (one batched transfer on the calling thread): the
        compiled whole-step path (compiled_step.py) DONATES the param /
        optimizer buffers into its next program call, which would
        invalidate by-reference captures before the background writer
        reads them — pinning trades one bounded sync per checkpoint
        interval for a snapshot donation cannot corrupt.  Pinning also
        engages AUTOMATICALLY once any CompiledStep has stepped in this
        process (``compiled_step.donation_active``), so a manual
        ``save_trainer`` or a mixed eager/compiled loop can never hand
        the writer buffers a later compiled step deletes."""
        from . import compiled_step as _compiled
        from . import random as _random

        pin = pin or _compiled.donation_active()

        step = self.step_clock if step is None else int(step)
        params = {}
        for p in trainer._params:
            data = p._data
            if data is None:
                continue
            params[p.name] = _NDLeaf(p.list_data()[0]._data)
        updater = trainer._updaters[0] if trainer._updaters else None
        trainer_state = {}
        if updater is not None:
            trainer_state["states"] = _capture_tree(updater.states)
            trainer_state["optimizer"] = _strip_optimizer(
                trainer._optimizer)
        snapshot = {"step": step, "params": params,
                    "trainer": trainer_state,
                    "rng": dict(_random.get_state()),
                    "extra": extra}
        if pin:
            _materialize(snapshot)
        return self._submit(snapshot)

    def save(self, step, params, extra=None, aux=None):
        """Snapshot a plain ``{name: NDArray}`` mapping (no trainer).

        ``aux``, when given, is an opaque picklable sideband payload
        committed alongside the arrays (``aux.pkl``, checksummed in the
        manifest) and read back with :func:`load_aux` — the hook
        non-Trainer state owners (the dist parameter-server shards, the
        coming ZeRO per-rank shard files) persist their bookkeeping
        through, atomically with the data it describes."""
        caps = {k: _NDLeaf(getattr(v, "_data", v))
                for k, v in params.items()}
        from . import random as _random

        snapshot = {"step": int(step), "params": caps, "trainer": {},
                    "rng": dict(_random.get_state()), "extra": extra,
                    "aux": aux}
        return self._submit(snapshot)

    def save_sharded(self, step, shard_files, aux=None):
        """Commit a SHARDED checkpoint: per-rank payload files under ONE
        global manifest (the ZeRO weight-update-sharding persistence
        path — each rank writes only the 1/n of params + optimizer
        state it owns, so checkpoint I/O shrinks with the data).

        ``shard_files`` maps file stem → picklable payload for the
        ranks THIS process owns.  Every process stages into the same
        deterministic directory (``<final>.tmp-shared`` — covered by
        the stale-tmp prune on crash), fsyncs its own files, then joins
        a ``host_allreduce`` barrier; process 0 ALONE then checksums
        everything staged, writes the single manifest (shard filenames
        in ``shard_files``, per-file SHA-256 in ``files`` so
        :meth:`verify`/:meth:`latest` gain corruption detection for
        free) and performs the atomic rename — the rank-0 commit
        barrier.  A SIGKILL anywhere before that rename leaves only a
        staging dir the next manager init removes; the previous valid
        checkpoint is untouched.  Synchronous by design: shard payloads
        are already host numpy (1/n sized), and the commit barrier must
        not race the next step's donation."""
        import jax
        import numpy as np

        from . import random as _random
        from .parallel.mesh import host_allreduce

        t0 = time.perf_counter()
        step = int(step)
        final = os.path.join(self.directory,
                             "%s-%08d" % (self.prefix, step))
        tmp = final + ".tmp-shared"
        proc0 = jax.process_index() == 0
        if proc0:
            # a stale staging dir from a crashed attempt would leak its
            # files into this manifest (the listdir below) — clear it
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp, exist_ok=True)
        host_allreduce(1.0)     # staging dir exists and is clean
        try:
            for name, payload in shard_files.items():
                fpath = os.path.join(tmp, name + ".pkl")
                with open(fpath, "wb") as f:
                    pickle.dump(payload, f,
                                protocol=pickle.HIGHEST_PROTOCOL)
                    f.flush()
                    os.fsync(f.fileno())
            host_allreduce(1.0)  # every rank's shard files are durable
            if not proc0:
                return final
            files = {}
            shard_names = sorted(os.listdir(tmp))
            for name in shard_names:
                fpath = os.path.join(tmp, name)
                files[name] = {"sha256": _sha256(fpath),
                               "bytes": os.path.getsize(fpath)}
            # empty params.npz keeps whole-checkpoint readers
            # (load_params, external tools) working unchanged
            ppath = os.path.join(tmp, "params.npz")
            with open(ppath, "wb") as f:
                np.savez(f)
                f.flush()
                os.fsync(f.fileno())
            files["params.npz"] = {"sha256": _sha256(ppath),
                                   "bytes": os.path.getsize(ppath)}
            if aux is not None:
                apath = os.path.join(tmp, "aux.pkl")
                with open(apath, "wb") as f:
                    pickle.dump(aux, f,
                                protocol=pickle.HIGHEST_PROTOCOL)
                    f.flush()
                    os.fsync(f.fileno())
                files["aux.pkl"] = {"sha256": _sha256(apath),
                                    "bytes": os.path.getsize(apath)}
            manifest = {"version": MANIFEST_VERSION, "step": step,
                        "time": time.time(), "pid": os.getpid(),
                        "files": files, "params": [],
                        "has_trainer": False,
                        "has_aux": aux is not None,
                        "shard_files": shard_names,
                        "rng": dict(_random.get_state()),
                        "probe": self._probe(), "extra": None,
                        "lineage": {"previous":
                                    self.last_good["path"]
                                    if self.last_good else None}}
            mpath = os.path.join(tmp, MANIFEST_NAME)
            with open(mpath, "w") as f:
                json.dump(manifest, f, indent=1, default=repr)
                f.flush()
                os.fsync(f.fileno())
            _fsync_dir(tmp)
            retired = None
            if os.path.isdir(final):
                retired = "%s.retire-%d-%d" % (final, os.getpid(),
                                               next(_tmp_seq))
                os.replace(final, retired)
            os.replace(tmp, final)
            _fsync_dir(self.directory)
            if retired is not None:
                shutil.rmtree(retired, ignore_errors=True)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self.last_good = {"path": final, "step": step}
        self.totals["saves"] += 1
        self.totals["written"] += 1
        _rts.inc("checkpoint_saves")
        _rts.inc("checkpoint_writes")
        _rts.inc("checkpoint_sharded_saves")
        write_seconds = time.perf_counter() - t0
        _rts.inc("checkpoint_write_seconds", write_seconds)
        if _histogram._state["on"]:
            _histogram.observe("checkpoint:write", write_seconds)
        self._prune()
        return final

    def _submit(self, snapshot):
        snapshot["probe"] = self._probe()
        snapshot["time"] = time.time()
        self.totals["saves"] += 1
        _rts.inc("checkpoint_saves")
        if not self.async_write:
            self._write(snapshot)
            return None
        with self._cv:
            if self._queued is not None:
                # writer still busy with an older snapshot: only the
                # newest pending one survives (bounded memory — at most
                # two snapshots' buffers are ever pinned)
                self.totals["coalesced"] += 1
                _rts.inc("checkpoint_coalesced")
            self._queued = snapshot
            if self._thread is None or not self._thread.is_alive():
                self._stop = False
                self._thread = threading.Thread(
                    target=self._writer_loop,
                    name="mxtpu-checkpoint-writer", daemon=True)
                self._thread.start()
            self._cv.notify_all()
        return None

    def _probe(self):
        """Lightweight runtime_stats/health marker for the manifest —
        counter dict reads only, never a drain, never a sync."""
        from . import health as _health

        probe = _rts.health_probe()
        hm = _health.monitor()
        if hm is not None:
            probe["health"] = {"step": hm.step,
                               "nan_steps": hm.totals["nan_steps"],
                               "inf_steps": hm.totals["inf_steps"],
                               "first_nan": dict(hm.first_nan)
                               if hm.first_nan else None}
        return probe

    # ---------------------------------------------------- writer thread
    def _writer_loop(self):
        while True:
            with self._cv:
                while self._queued is None and not self._stop:
                    self._cv.wait()
                if self._stop and self._queued is None:
                    return
                snapshot, self._queued = self._queued, None
                self._writing = True
            try:
                self._write(snapshot)
            except Exception as e:  # a failed write must not kill training
                self.last_error = "%s: %s" % (type(e).__name__, e)
                self.totals["errors"] += 1
                _rts.inc("checkpoint_errors")
                _logger().exception("async checkpoint write failed "
                                    "(step %s)", snapshot.get("step"))
            finally:
                with self._cv:
                    self._writing = False
                    self._cv.notify_all()

    def wait(self, timeout=None):
        """Block until no snapshot is pending or being written (tests,
        clean shutdown).  Returns False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._queued is not None or self._writing:
                rem = None if deadline is None \
                    else deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    return False
                self._cv.wait(rem)
        return True

    def close(self):
        """Flush pending snapshots and stop the writer thread."""
        self.wait()
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    # ------------------------------------------------------- the commit
    def _write(self, snapshot):
        import numpy as np

        t0 = time.perf_counter()
        _materialize(snapshot)
        step = snapshot["step"]
        final = os.path.join(self.directory,
                             "%s-%08d" % (self.prefix, step))
        tmp = "%s.tmp-%d-%d" % (final, os.getpid(), next(_tmp_seq))
        os.makedirs(tmp)
        try:
            files = {}
            params_np = {k: lf.value
                         for k, lf in snapshot["params"].items()}
            ppath = os.path.join(tmp, "params.npz")
            with open(ppath, "wb") as f:
                np.savez(f, **params_np)
                f.flush()
                os.fsync(f.fileno())
            files["params.npz"] = {"sha256": _sha256(ppath),
                                   "bytes": os.path.getsize(ppath)}
            if snapshot["trainer"]:
                tpath = os.path.join(tmp, "trainer.pkl")
                with open(tpath, "wb") as f:
                    pickle.dump(snapshot["trainer"], f,
                                protocol=pickle.HIGHEST_PROTOCOL)
                    f.flush()
                    os.fsync(f.fileno())
                files["trainer.pkl"] = {"sha256": _sha256(tpath),
                                        "bytes": os.path.getsize(tpath)}
            if snapshot.get("aux") is not None:
                apath = os.path.join(tmp, "aux.pkl")
                with open(apath, "wb") as f:
                    pickle.dump(snapshot["aux"], f,
                                protocol=pickle.HIGHEST_PROTOCOL)
                    f.flush()
                    os.fsync(f.fileno())
                files["aux.pkl"] = {"sha256": _sha256(apath),
                                    "bytes": os.path.getsize(apath)}
            manifest = {"version": MANIFEST_VERSION, "step": step,
                        "time": snapshot["time"], "pid": os.getpid(),
                        "files": files,
                        "params": sorted(snapshot["params"]),
                        "has_trainer": bool(snapshot["trainer"]),
                        "has_aux": snapshot.get("aux") is not None,
                        "rng": snapshot["rng"],
                        "probe": snapshot.get("probe"),
                        "extra": snapshot.get("extra"),
                        "lineage": {"previous":
                                    self.last_good["path"]
                                    if self.last_good else None}}
            mpath = os.path.join(tmp, MANIFEST_NAME)
            with open(mpath, "w") as f:
                json.dump(manifest, f, indent=1, default=repr)
                f.flush()
                os.fsync(f.fileno())
            _fsync_dir(tmp)
            retired = None
            if os.path.isdir(final):
                # same-step overwrite: move the old committed dir ASIDE
                # (not rmtree — a crash between delete and rename would
                # lose BOTH copies of this step) and delete it only
                # after the new commit has landed.  The ``.retire-``
                # name is NOT in the stale-tmp prune set: if we crash
                # here, manager init restores it to its final name.
                retired = "%s.retire-%d-%d" % (final, os.getpid(),
                                               next(_tmp_seq))
                os.replace(final, retired)
            os.replace(tmp, final)
            _fsync_dir(self.directory)
            if retired is not None:
                shutil.rmtree(retired, ignore_errors=True)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self.last_good = {"path": final, "step": step}
        self.totals["written"] += 1
        _rts.inc("checkpoint_writes")
        write_seconds = time.perf_counter() - t0
        _rts.inc("checkpoint_write_seconds", write_seconds)
        if _histogram._state["on"]:
            # full commit wall-time (materialize + hash + fsync +
            # rename) — the tail of this distribution is what decides
            # whether async saves coalesce under a given interval
            _histogram.observe("checkpoint:write", write_seconds)
        self._prune()
        return final

    def _prune_stale_tmp(self):
        """Remove leftover staging dirs from crashed writes, and
        recover a ``.retire-`` dir (a committed checkpoint moved aside
        during a same-step overwrite) whose replacement never landed —
        that dir IS the only surviving copy of its step."""
        for name in os.listdir(self.directory):
            base, sep, _ = name.partition(".retire-")
            if sep and self._final_re.match(base):
                final = os.path.join(self.directory, base)
                path = os.path.join(self.directory, name)
                try:
                    if os.path.isdir(final):
                        shutil.rmtree(path, ignore_errors=True)
                    else:
                        os.replace(path, final)
                except OSError:
                    pass
                continue
            if ".tmp-" in name and self._final_re.match(
                    name.split(".tmp-")[0]):
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)

    def _prune(self):
        """Keep-last-N retention over committed checkpoints; stale temp
        staging dirs go too.  Torn final dirs (no valid manifest) older
        than the newest valid checkpoint are garbage from a previous
        crash and are removed, and quarantined ``.corrupt-*`` dirs are
        bounded to ``keep`` (newest kept for forensics) so recurring
        corruption cannot grow disk use without bound."""
        entries = self._scan()
        valid = [(s, p) for s, p, m in entries if m is not None]
        for step, path in valid[self.keep:]:
            shutil.rmtree(path, ignore_errors=True)
        if valid:
            newest = valid[0][0]
            for step, path, m in entries:
                if m is None and step < newest:
                    shutil.rmtree(path, ignore_errors=True)
        quarantined = sorted(
            n for n in os.listdir(self.directory)
            if ".corrupt-" in n
            and self._final_re.match(n.split(".corrupt-")[0]))
        for name in quarantined[:max(0, len(quarantined) - self.keep)]:
            shutil.rmtree(os.path.join(self.directory, name),
                          ignore_errors=True)
        self._prune_stale_tmp()

    # -------------------------------------------------------- read side
    def _scan(self):
        """[(step, path, manifest-or-None)] newest first; manifest is
        None when missing/unparseable (a torn checkpoint)."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            m = self._final_re.match(name)
            if not m:
                continue
            path = os.path.join(self.directory, name)
            if not os.path.isdir(path):
                continue
            manifest = None
            try:
                with open(os.path.join(path, MANIFEST_NAME)) as f:
                    manifest = json.load(f)
            except (OSError, ValueError):
                manifest = None
            out.append((int(m.group(1)), path, manifest))
        out.sort(key=lambda e: e[0], reverse=True)
        return out

    def verify(self, path, manifest=None):
        """Re-hash every file a manifest names; True iff the checkpoint
        is bit-for-bit what was committed."""
        if manifest is None:
            try:
                with open(os.path.join(path, MANIFEST_NAME)) as f:
                    manifest = json.load(f)
            except (OSError, ValueError):
                return False
        try:
            for fname, meta in manifest.get("files", {}).items():
                fpath = os.path.join(path, fname)
                if os.path.getsize(fpath) != meta["bytes"] or \
                        _sha256(fpath) != meta["sha256"]:
                    return False
        except OSError:
            return False
        return True

    def latest(self):
        """The newest fully-valid checkpoint's manifest (with ``path``
        added), or None.  Torn checkpoints (no manifest — e.g. a
        SIGKILL mid-write) and corrupt ones (checksum mismatch) are
        skipped with a warning and QUARANTINED (renamed aside with a
        ``.corrupt`` marker, content kept for forensics) so every later
        scan neither re-hashes them nor re-counts the same corruption,
        falling back to the previous valid checkpoint."""
        for step, path, manifest in self._scan():
            if manifest is not None and self.verify(path, manifest):
                manifest = dict(manifest)
                manifest["path"] = path
                return manifest
            self.totals["corrupt_skipped"] += 1
            _rts.inc("checkpoint_corrupt_skipped")
            quarantine = "%s.corrupt-%d-%d" % (path, os.getpid(),
                                               next(_tmp_seq))
            try:
                os.replace(path, quarantine)
            except OSError:
                quarantine = path  # leave in place; next scan retries
            warn_rate_limited(
                _logger(), "checkpoint:corrupt:%s" % path, 60,
                "skipping torn/corrupt checkpoint %s (%s; quarantined "
                "as %s) — falling back to the previous valid "
                "checkpoint", path,
                "no valid manifest" if manifest is None
                else "checksum mismatch", quarantine)
        return None

    def load_params(self, manifest):
        """``{name: NDArray}`` from a checkpoint's params file."""
        import numpy as np

        from .ndarray import array

        with np.load(os.path.join(manifest["path"], "params.npz"),
                     allow_pickle=False) as data:
            return {k: array(data[k]) for k in data.files}

    def load_aux(self, manifest):
        """The opaque sideband payload saved via ``save(..., aux=)``,
        or None when the checkpoint carries none.  Plain pickle — same
        trust model as ``trainer.pkl`` (load only checkpoints from
        directories you trust)."""
        return load_aux(manifest)

    def load_shard_files(self, manifest):
        """``{rank: payload}`` from a sharded checkpoint's per-rank
        files (see :meth:`save_sharded`).  Rank indices are parsed from
        the ``<stem>-<rank>-of-<n>`` filename convention; checksums
        were already verified by ``latest()``/``verify`` before the
        manifest was handed out."""
        pat = re.compile(r"-(\d+)-of-(\d+)(?:\.pkl)?$")
        out = {}
        for name in manifest.get("shard_files", []):
            m = pat.search(name)
            if not m:
                continue
            with open(os.path.join(manifest["path"], name), "rb") as f:
                out[int(m.group(1))] = pickle.load(f)
        return out

    def restore(self, trainer=None, block=None, manifest=None):
        """One-call auto-resume: load the newest valid checkpoint back
        into a ``Trainer`` (parameters by name, updater state, optimizer
        hyper-state, RNG, step clock) and/or a Gluon ``block``
        (parameters via ``collect_params``).  Returns the manifest (with
        ``path`` and ``step``) or None when no valid checkpoint exists.
        """
        from . import random as _random

        from .base import MXNetError

        if manifest is None:
            # drain the writer first: a snapshot queued just before the
            # restore must be visible (and committed) before we decide
            # what "latest" is — otherwise it would land AFTER the
            # rollback and leave lineage pointing past the live state
            self.wait()
            manifest = self.latest()
        if manifest is None:
            return None
        params = self.load_params(manifest)
        targets = {}
        if trainer is not None:
            targets.update({p.name: p for p in trainer._params})
        if block is not None:
            targets.update(block.collect_params().items())
        matched = 0
        for name, value in params.items():
            p = targets.get(name)
            if p is not None and p._data is not None:
                p.set_data(value)
                matched += 1
        if targets and params and matched == 0:
            # a "successful" resume that restored nothing is the worst
            # failure mode: fresh weights with a restored step clock
            raise MXNetError(
                "checkpoint %s matched NONE of the %d target "
                "parameter(s) (checkpoint has %s...) — name/prefix "
                "mismatch or parameters not yet initialized (run one "
                "forward first)"
                % (manifest["path"], len(targets),
                   sorted(params)[:3]))
        if targets and matched < len(params):
            warn_rate_limited(
                _logger(), "checkpoint:partial:%s" % manifest["path"],
                60, "checkpoint %s: only %d of %d saved parameter(s) "
                "matched a target by name — the rest were NOT restored",
                manifest["path"], matched, len(params))
        missing = sorted(n for n in targets if n not in params)
        if missing:
            # the reverse gap is just as dangerous: a target param the
            # checkpoint never saw (e.g. a newly added layer) keeps its
            # fresh init while step/RNG/optimizer state are restored
            warn_rate_limited(
                _logger(), "checkpoint:missing:%s" % manifest["path"],
                60, "checkpoint %s does not cover %d target "
                "parameter(s) (%s...) — they keep their current "
                "(likely freshly initialized) values",
                manifest["path"], len(missing), missing[:3])
        if trainer is not None and manifest.get("has_trainer"):
            with open(os.path.join(manifest["path"], "trainer.pkl"),
                      "rb") as f:
                trainer_state = pickle.load(f)
            contexts = getattr(trainer, "_contexts", None) or []
            for i, u in enumerate(trainer._updaters):
                # fresh copy per updater, materialized on that
                # updater's device: per-device optimizer state must
                # never alias across updaters (trainer.py _update_impl
                # keeps one Updater per device copy) and must live next
                # to the weights it updates
                ctx = contexts[i] if i < len(contexts) else None
                states = _restore_tree(trainer_state.get("states", {}),
                                       ctx=ctx)
                u.states = states
                u.states_synced = dict.fromkeys(states, False)
            blob = trainer_state.get("optimizer")
            if blob is not None:
                src = pickle.loads(blob)
                hyper = dict(src.__dict__)
                hyper.pop("param_dict", None)
                trainer._optimizer.__dict__.update(hyper)
        rng = manifest.get("rng")
        if rng:
            _random.set_state(rng)
        self.step_clock = int(manifest.get("step", 0))
        self.last_good = {"path": manifest["path"],
                          "step": self.step_clock}
        _rts.inc("checkpoint_restores")
        return manifest

    def snapshot_info(self):
        """JSON-serializable view (never syncs)."""
        return {"enabled": _state["on"] and bool(_GLOBAL)
                and _GLOBAL[0] is self,
                "directory": self.directory, "keep": self.keep,
                "interval": self.interval,
                "async": self.async_write,
                "step_clock": self.step_clock,
                "last_good": dict(self.last_good)
                if self.last_good else None,
                "last_error": self.last_error,
                "totals": dict(self.totals)}


# ------------------------------------------------------ module surface


def enable(directory, interval=None, keep=None, async_write=None,
           prefix="ckpt"):
    """Create (or replace) the global :class:`CheckpointManager` and arm
    the guard-first ``Trainer.step`` hook (:func:`on_step`).  Returns
    the manager."""
    if interval is None:
        interval = int(os.environ.get("MXNET_TPU_CKPT_INTERVAL", "100"))
    if keep is None:
        keep = int(os.environ.get("MXNET_TPU_CKPT_KEEP", "5"))
    mgr = CheckpointManager(directory, keep=keep, interval=interval,
                            async_write=async_write, prefix=prefix)
    if _GLOBAL:
        _GLOBAL[0].close()
    _GLOBAL.clear()
    _GLOBAL.append(mgr)
    _state["on"] = True
    return mgr


def disable():
    """Disarm the hook; the manager flushes pending writes and stays
    readable."""
    _state["on"] = False
    if _GLOBAL:
        _GLOBAL[0].close()


def is_enabled():
    return _state["on"]


def manager():
    """The global manager while enabled, else None."""
    return _GLOBAL[0] if _state["on"] and _GLOBAL else None


def on_step(trainer, pin=False):
    """``Trainer.step`` hook: advance the global manager's step clock
    and auto-save at interval boundaries.  ONE dict read when disabled
    (the default) — safe on the hot path.

    ``pin=True`` (the compiled-step path) materializes each snapshot
    at capture: the whole-step program donates the param/optimizer
    buffers on the next call, so by-reference captures must be brought
    to host before then (``save_trainer``'s pin contract).

    The global clock assumes ONE Trainer drives the run (the reference
    training-loop shape).  Multi-trainer setups (e.g. GANs) should
    disable auto-checkpointing and call
    ``manager().save_trainer(trainer, step=...)`` per trainer with
    distinct prefixes — each manifest snapshots the params of the
    trainer it was captured from."""
    if not _state["on"]:
        return
    mgr = _GLOBAL[0]
    mgr.step_clock += 1
    if mgr.interval and mgr.step_clock % mgr.interval == 0:
        # step-anatomy checkpoint_write phase: the TRAINING-thread cost
        # only (async mode: the device-reference capture; sync mode:
        # the full write).  The background writer's commit time stays
        # in the checkpoint:write histogram, not in any step's window.
        ss_on = _stepstats._state["on"]
        if ss_on:
            ss_tok = _stepstats.begin()
        mgr.save_trainer(trainer, step=mgr.step_clock, pin=pin)
        if ss_on:
            _stepstats.end("checkpoint_write", ss_tok)


def load_aux(manifest):
    """Read a checkpoint's opaque ``aux.pkl`` sideband payload (see
    ``CheckpointManager.save``); None when the manifest carries none.
    The file's checksum was already verified by ``latest()``/``verify``
    before the manifest was handed out."""
    if not manifest or not manifest.get("has_aux"):
        return None
    with open(os.path.join(manifest["path"], "aux.pkl"), "rb") as f:
        return pickle.load(f)


def auto_resume(trainer=None, block=None, zero_step=None):
    """One call: restore the newest valid checkpoint from the global
    manager into ``trainer``/``block``.  Returns the resumed step (int)
    or None when checkpointing is off or nothing valid exists.

    ``zero_step`` (a ``GluonTrainStep(..., zero=True)`` or
    ``ZeroCompiledStep``) selects the SHARDED resume path instead: the
    newest valid checkpoint's per-rank shard files are loaded and
    re-sharded onto the current mesh layout (``restore_zero`` — a run
    saved at one dp width resumes at another).  A newest checkpoint
    that is not sharded restores nothing (warned, returns None) rather
    than silently mixing the two formats."""
    mgr = manager()
    if mgr is None:
        return None
    if zero_step is not None:
        mgr.wait()
        manifest = mgr.latest()
        if manifest is None:
            return None
        if not manifest.get("shard_files"):
            warn_rate_limited(
                _logger(), "checkpoint:notsharded:%s" % manifest["path"],
                60, "auto_resume(zero_step=): newest checkpoint %s is "
                "not sharded — nothing restored (save with save_zero "
                "or pass trainer=/block= for the replicated format)",
                manifest["path"])
            return None
        step = zero_step.restore_zero(manifest, mgr=mgr)
        mgr.step_clock = step
        mgr.last_good = {"path": manifest["path"], "step": step}
        _rts.inc("checkpoint_restores")
        return step
    manifest = mgr.restore(trainer=trainer, block=block)
    return None if manifest is None else int(manifest.get("step", 0))


def lineage():
    """``{"last_good_path", "step"}`` of the newest committed (or
    restored) checkpoint — what the health layer's flight dump embeds so
    an operator knows exactly where to resume from.  None when off."""
    if not _state["on"] or not _GLOBAL:
        return None
    lg = _GLOBAL[0].last_good
    if lg is None:
        return {"last_good_path": None, "step": None,
                "directory": _GLOBAL[0].directory}
    return {"last_good_path": lg["path"], "step": lg["step"],
            "directory": _GLOBAL[0].directory}


def snapshot():
    """Global manager view, or a disabled stub."""
    if _GLOBAL:
        return _GLOBAL[0].snapshot_info()
    return {"enabled": False}


def reset():
    """Disable and drop the global manager (tests)."""
    _state["on"] = False
    if _GLOBAL:
        try:
            _GLOBAL[0].close()
        except Exception:
            pass
    _GLOBAL.clear()


# --------------------------------------------- legacy prefix/epoch API


def save_legacy(prefix, epoch, symbol, arg_params, aux_params):
    """The ``model.save_checkpoint`` file layout (``<prefix>-symbol.json``
    + ``<prefix>-<epoch:04d>.params``) written atomically, plus a
    sidecar ``<prefix>-<epoch:04d>.manifest.json`` carrying checksums so
    :func:`load_legacy` can detect torn/corrupt files."""
    from .ndarray import save as nd_save

    if symbol is not None:
        with atomic_write("%s-symbol.json" % prefix) as tmp:
            symbol.save(tmp)
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)
    with atomic_write(param_name) as tmp:
        nd_save(tmp, save_dict)
    files = {os.path.basename(param_name):
             {"sha256": _sha256(param_name),
              "bytes": os.path.getsize(param_name)}}
    sym_name = "%s-symbol.json" % prefix
    if symbol is not None and os.path.exists(sym_name):
        files[os.path.basename(sym_name)] = {
            "sha256": _sha256(sym_name),
            "bytes": os.path.getsize(sym_name)}
    manifest = {"version": MANIFEST_VERSION, "epoch": int(epoch),
                "time": time.time(), "files": files}
    with atomic_write("%s-%04d.manifest.json" % (prefix, epoch)) as tmp:
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)


def load_legacy(prefix, epoch):
    """Verify (when the sidecar manifest exists) then load the legacy
    checkpoint files; a checksum mismatch raises a clear error instead
    of feeding half-written weights into a run."""
    from .base import MXNetError

    mpath = "%s-%04d.manifest.json" % (prefix, epoch)
    if os.path.exists(mpath):
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            manifest = None
        if manifest:
            d = os.path.dirname(os.path.abspath(mpath))
            for fname, meta in manifest.get("files", {}).items():
                fpath = os.path.join(d, fname)
                try:
                    ok = os.path.getsize(fpath) == meta["bytes"] and \
                        _sha256(fpath) == meta["sha256"]
                except OSError:
                    ok = False
                if not ok:
                    raise MXNetError(
                        "checkpoint file %s fails its manifest checksum "
                        "(%s) — the file is torn or corrupt; restore an "
                        "earlier epoch or a CheckpointManager checkpoint"
                        % (fpath, mpath))
    from .ndarray import load as nd_load
    from .symbol import load as sym_load

    symbol = sym_load("%s-symbol.json" % prefix)
    save_dict = nd_load("%s-%04d.params" % (prefix, epoch))
    arg_params = {}
    aux_params = {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        if tp == "aux":
            aux_params[name] = v
    return symbol, arg_params, aux_params


def _activate_from_env():
    directory = os.environ.get("MXNET_TPU_CKPT")
    if directory:
        enable(directory)
        return True
    return False


_activate_from_env()
