"""mxnet_tpu — a TPU-native deep-learning framework with the API surface
of Apache MXNet 1.5 (reference surveyed in SURVEY.md).

Usage mirrors the reference::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
    with mx.autograd.record():
        y = (x * 2).sum()

Compute lowers to XLA (jax) — imperative NDArray ops through a per-op
jit cache, ``hybridize()``/Symbol/Module through whole-graph staging —
and distribution rides ``jax.sharding`` meshes instead of KVStore's
NCCL/ps-lite backends (kvstore='tpu' façade provided for parity).
"""

__version__ = "0.1.0"


def _maybe_init_distributed():
    """Join the jax.distributed process group when launched by
    tools/launch.py (DMLC_* env contract, reference: ps-lite's
    Postoffice::Start reading DMLC_ROLE/DMLC_PS_ROOT_*).  Must run at
    import, before anything touches the XLA backend."""
    import os

    n = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if n <= 1 or os.environ.get("DMLC_ROLE", "worker") != "worker":
        return
    if int(os.environ.get("DMLC_NUM_SERVER", "0") or 0) > 0:
        # dist_async launch (launch.py -s N): worker coordination is
        # the host-side parameter server (kvstore/ps.py), not a
        # jax.distributed process group — joining one would be pure
        # startup cost and requires jax features some builds lack
        return
    import jax

    if jax.distributed.is_initialized():
        return  # user script already joined the group
    jax.distributed.initialize(
        coordinator_address="%s:%s" % (
            os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1"),
            os.environ.get("DMLC_PS_ROOT_PORT", "9091")),
        num_processes=n,
        process_id=int(os.environ.get("DMLC_WORKER_ID", "0")))


_maybe_init_distributed()

from .base import MXNetError, AttrScope, NameManager  # noqa: F401
from .context import (Context, cpu, cpu_pinned, current_context, gpu,  # noqa: F401
                      num_gpus, num_tpus, tpu)
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from .ndarray import NDArray  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401

# API layers above the core — populated over the build plan (SURVEY.md §7);
# each module raises a clear error at *use* time if incomplete, never at import.
# Deliberately NOT listed: `serving` (the continuous-batching inference
# server, docs/SERVING.md) — a training process must never pay its
# import; `runtime_stats` reads its diag section via sys.modules, and
# deployments opt in with `from mxnet_tpu import serving`
# (tests/test_bench_gate.py pins the zero-import-cost contract).
_OPTIONAL = [
    "initializer", "optimizer", "metric", "lr_scheduler", "callback",
    "symbol", "io", "recordio", "gluon", "module", "kvstore", "executor",
    "cached_op", "profiler", "runtime", "test_utils", "visualization",
    "parallel", "contrib", "model", "image", "operator", "monitor",
    "executor_manager", "rtc", "engine", "predictor", "rnn", "log",
    "util", "name", "attribute", "runtime_stats", "device_memory",
    "health", "checkpoint", "metrics_timeline", "compiled_step",
]


def _import_optional():
    import importlib
    import importlib.util
    import sys

    mod_self = sys.modules[__name__]
    for name in _OPTIONAL:
        # skip only modules not yet written; real import errors propagate
        if importlib.util.find_spec("." + name, __name__) is None:
            continue
        m = importlib.import_module("." + name, __name__)
        setattr(mod_self, name, m)
    # aliases matching the reference namespace
    if hasattr(mod_self, "symbol"):
        mod_self.sym = mod_self.symbol
        mod_self.Symbol = mod_self.symbol.Symbol
    if hasattr(mod_self, "module"):
        mod_self.mod = mod_self.module
        mod_self.Module = mod_self.module.Module
    if hasattr(mod_self, "kvstore"):
        mod_self.kv = mod_self.kvstore
    if hasattr(mod_self, "visualization"):
        mod_self.viz = mod_self.visualization
    if hasattr(mod_self, "initializer"):
        mod_self.init = mod_self.initializer
    if hasattr(mod_self, "io"):
        mod_self.DataIter = mod_self.io.DataIter
        mod_self.DataBatch = mod_self.io.DataBatch
    if hasattr(mod_self, "executor"):
        mod_self.Executor = mod_self.executor.Executor
    if hasattr(mod_self, "callback"):
        mod_self.do_checkpoint = mod_self.callback.do_checkpoint
    if hasattr(mod_self, "model"):
        mod_self.save_checkpoint = mod_self.model.save_checkpoint
        mod_self.load_checkpoint = mod_self.model.load_checkpoint


_import_optional()
