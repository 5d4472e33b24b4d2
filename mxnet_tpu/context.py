"""Device context — TPU-native equivalent of MXNet's Context.

Reference: include/mxnet/base.h:104 (``Context``), python/mxnet/context.py.

In the reference a Context names a (device_type, device_id) pair and every
NDArray/op dispatch routes through it (engine queues are per-context,
``src/engine/threaded_engine_perdevice.cc:93``).  Here a Context is a thin,
hashable handle onto a ``jax.Device``: placement is done with
``jax.device_put`` and XLA's async dispatch replaces the per-device worker
queues.  ``cpu()`` maps to the host platform, ``tpu()`` to the accelerator
platform (``gpu()`` is accepted as an alias for accelerator contexts so that
reference scripts run unchanged).
"""

from __future__ import annotations

import threading

__all__ = [
    "Context",
    "cpu",
    "cpu_pinned",
    "tpu",
    "gpu",
    "current_context",
    "num_gpus",
    "num_tpus",
]


class Context:
    """A device context.

    Parameters
    ----------
    device_type : {'cpu', 'tpu', 'gpu', 'cpu_pinned', 'cpu_shared'}
        'gpu' is an alias for the accelerator platform so code written
        against the reference API (``mx.gpu(0)``) keeps working on TPU.
    device_id : int
        Index into ``jax.devices(platform)``.
    """

    # mirror of the reference's DeviceType enum (include/mxnet/base.h:108)
    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "gpu"}
    devstr2type = {"cpu": 1, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "gpu": 6}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- jax bridge ------------------------------------------------------
    @property
    def jax_device(self):
        """The underlying ``jax.Device`` for this context."""
        import jax

        dt = self.device_type
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            # local_devices: a Context is per-PROCESS (multi-process runs
            # must never place data on another rank's device)
            try:
                return jax.local_devices(backend="cpu")[self.device_id]
            except RuntimeError:
                # no host platform registered (rare); fall back to default
                return jax.local_devices()[self.device_id]
        # tpu / gpu → whatever accelerator platform is present.  No
        # fallback: a context that names a chip the process does not
        # have is an error, never a quiet host (or chip 0) placement.
        devs = _accelerator_devices()
        if not 0 <= self.device_id < len(devs):
            from .base import MXNetError

            raise MXNetError(
                "%s: this process has %d accelerator device(s) (jax "
                "platform %r); use mx.cpu() or current_context()"
                % (self, len(devs), jax.default_backend()))
        return devs[self.device_id]

    def empty_cache(self):
        """Parity with reference Context.empty_cache (gpu mem pool flush).

        XLA owns the HBM allocator; there is no user-visible pool to flush,
        so this is a documented no-op.
        """


def _accelerator_devices():
    import jax

    devs = []
    try:
        all_devs = jax.local_devices()
    except RuntimeError:
        return devs
    for d in all_devs:
        if d.platform not in ("cpu",):
            devs.append(d)
    return devs


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    """Parity alias: pinned host memory context (host memory on TPU)."""
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias for accelerator context so reference scripts run unchanged."""
    return Context("gpu", device_id)


def num_gpus():
    """Number of accelerator devices visible (reference: MXGetGPUCount)."""
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())


def current_context():
    """The current default context (thread-local, set via ``with ctx:``)."""
    if not hasattr(Context._default_ctx, "value"):
        # TPU-native default: prefer the accelerator if one exists.
        Context._default_ctx.value = (
            Context("tpu", 0) if _accelerator_devices() else Context("cpu", 0)
        )
    return Context._default_ctx.value
