"""The device gate, the peaks table, the compile cache and the counters the
benchmark takes from the toolchain (never from the program)."""

import json
import os
import sys

from . import manifest

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def process_age_s():
    """Seconds since this process was started, from the kernel's record of
    its start: set-up counts from there, imports included."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])  # starttime, field 22 of the whole line
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_peaks(root=manifest.ROOT):
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        return json.load(f)


def require_chip(chips, root=manifest.ROOT):
    """-> (devices, peaks of their kind).  Anything but ``chips`` TPUs of a
    kind in the peaks table ends the process: no result line, code 3.  No
    CPU fallback and no default peak."""
    import jax

    devices = jax.devices()
    peaks = load_peaks(root)
    first = devices[0]
    if first.platform != "tpu":
        print("benchmark: refusing to measure on platform %r: the cells "
              "are defined on a TPU" % first.platform, file=sys.stderr)
        raise SystemExit(3)
    if first.device_kind not in peaks:
        print("benchmark: no published peaks for device_kind %r (table: %s)"
              % (first.device_kind, sorted(peaks)), file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print("benchmark: the cell asks for %d chips, jax finds %d"
              % (chips, len(devices)), file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips], peaks[first.device_kind]


def enable_compile_cache(root=manifest.ROOT):
    """jax's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if that is
    set, else at the fixed ``<checkout>/.jax_cache`` (the path the program's
    own ``enable_compile_cache`` uses).  Every program is kept, however
    quick its compile: an eager path makes hundreds of small ones, and
    set-up pays each again in every run otherwise."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) and cache hits, from jax's own monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.built = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, _seconds, **_kw):
        if name == BACKEND_COMPILE_EVENT:
            self.built += 1

    def _on_event(self, name, **_kw):
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1


def memory_peak_bytes(devices):
    """Peak bytes on the fullest of ``devices``: the allocator's high-water
    mark of buffers plus the largest program's temporaries.

    On this runtime ``memory_stats()["peak_bytes_in_use"]`` counts buffers
    (parameters, optimizer state, inputs, results) and not the scratch a
    running program holds, which for a training step is most of the memory
    (activations kept for the backward pass).  The scratch of every loaded
    program is in ``get_compiled_memory_stats().temp_size_in_bytes``; the
    sum is the high-water mark when the largest program runs while the
    long-lived buffers are live, which is the steady state of every cell.
    -> (peak, buffers, temporaries)."""
    buffers = 0
    for d in devices:
        stats = d.memory_stats() or {}
        buffers = max(buffers, int(stats.get("peak_bytes_in_use", 0)))
    temp = 0
    for exe in devices[0].client.live_executables():
        temp = max(temp, int(exe.get_compiled_memory_stats()
                             .temp_size_in_bytes))
    return buffers + temp, buffers, temp


def loaded_hlo_modules(devices):
    """{module name: optimized HLO text} of every program now loaded."""
    out = {}
    for exe in devices[0].client.live_executables():
        for module in exe.hlo_modules():
            out[module.name] = module.to_string()
    return out
