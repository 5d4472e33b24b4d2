#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

Builds the cell's entry on the chip(s), takes the system's side of the
correctness check, warms up the cell's shapes (all of that is ``setup_s``),
measures for ``--seconds``, with ``--trace 1`` traces a short tail after the
window, reads the device's memory, runs the plain reference, and prints one
JSON object as the last line of its standard output.  Everything else goes
to earlier lines.  On any platform but a TPU of a kind in ``peaks.json``,
or with fewer chips than the cell asks for, it prints no result and exits
with code 3.
"""

import argparse
import json
import math
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.harness import (check, device, hlo_cost,  # noqa: E402
                               manifest, trace)


class Context:
    """What an entry is given: its cell's data, the seed and the devices."""

    def __init__(self, cell, seed, devices):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.devices = devices

    @staticmethod
    def say(message):
        print("[%8.2f] %s" % (device.process_age_s(), message), flush=True)


SPAN_NAMES = {"dispatch", "loss_fetch", trace.WINDOW_SPAN}


def traced_tail(session, trace_dir):
    """Trace a short tail after the window; -> (its window, Trace or None).
    The python tracer stays off: it slows the host it measures."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            tail = session.measure_traced(jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    path = trace.newest_xplane(trace_dir)
    return tail, path and trace.Trace.from_xplane(path, SPAN_NAMES)


def run(cell, seed, seconds, traced, devices, peaks, out_dir):
    """-> the result object of one run (the last line, as a dict)."""
    compiles = device.CompileCounter()
    ctx = Context(cell, seed, devices)
    say = ctx.say
    reference = cell.reference()
    session = cell.entry().build(ctx)
    say("built %s: %d programs so far (%d from the cache)"
        % (cell.traffic["entry"], compiles.built, compiles.cache_hits))
    system = session.system_outputs(reference)
    say("system's side of the check done: %d programs (%d from the cache)"
        % (compiles.built, compiles.cache_hits))
    session.warm_up()
    setup_s = device.process_age_s()
    say("set-up %.2f s: %d programs (%d from the cache)"
        % (setup_s, compiles.built, compiles.cache_hits))

    built_before = compiles.built
    program_before = session.program_counters()
    window = session.measure(seconds)
    compiles_in_window = compiles.built - built_before
    program_delta = {k: v - program_before[k]
                     for k, v in session.program_counters().items()}
    say("window: %s; programs built in the window: %d (program's own "
        "counters: %s)%s"
        % (window["summary"], compiles_in_window, program_delta,
           "; " + window["why"] if window["why"] else ""))

    tail = recorded = None
    if traced:
        tail, recorded = traced_tail(session, os.path.join(out_dir, "trace"))
        say("traced tail: %s (the window above ran untraced: the ratio is "
            "what tracing costs)" % tail["summary"])

    peak, buffers, temporaries = device.memory_peak_bytes(devices)
    say("memory: peak %.3f GB = buffers %.3f GB (allocator's high-water "
        "mark) + temporaries %.3f GB (largest loaded program)"
        % (peak / 1e9, buffers / 1e9, temporaries / 1e9))
    modules = []
    if recorded:
        modules = [hlo_cost.Module(text)
                   for text in device.loaded_hlo_modules(devices).values()]

    # only now may the reference use the device: the peak above is the
    # system's own
    correct = check.against_reference(reference, cell.config, system, say)
    correct = correct and window["ok"] and compiles_in_window == 0 \
        and not any(program_delta.values())

    values = dict(window["values"], peak_hbm_gb=peak / 1e9, setup_s=setup_s)
    first = devices[0]
    info = {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": {}, "device": info}
    if not traced:
        for metric in cell.end_to_end:
            result["metrics"][metric["name"]] = {
                "value": values[metric["name"]], "unit": metric["unit"]}
        return result

    observations = {
        "cell": cell, "peaks": peaks, "chips": len(devices),
        "values": values, "window": window, "tail": tail,
        "compiles_in_window": compiles_in_window, "trace": recorded,
        "modules": modules, "reference": reference,
    }
    for metric in cell.per_layer:
        value = cell.reader(metric["name"]).read(observations)
        if value is not None and math.isfinite(value):
            result["metrics"][metric["name"]] = {"value": value,
                                                 "unit": metric["unit"]}
    if recorded:
        info["busy_s"], info["window_s"] = trace.busy_and_window_s(recorded)
        result["breakdown"] = {
            "device_ops": trace.device_ops(recorded, modules),
            "idle_gaps": trace.idle_gaps(recorded)}
    return result


def main(argv=None, gate=device.require_chip, root=manifest.ROOT):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = manifest.Manifest(root).cell(args.workload)
    devices, peaks = gate(cell.chips, root)
    cache = device.enable_compile_cache(root)
    Context.say("%s on %d x %s; compile cache at %s"
                % (cell.name, len(devices), devices[0].device_kind, cache))
    # traces and anything else a run writes: under TMPDIR, which the driver
    # gives each side, and removed when the run ends
    with tempfile.TemporaryDirectory(prefix="benchmark_run_") as out_dir:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     devices, peaks, out_dir)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
