#!/usr/bin/env python3
"""The spread of a training cell of the benchmark over seeds, in one process
on the chip: what a new cell is admitted by (PERF.md section 2).

    python tools/cell_spread.py <cell> <seed> [<seed> ...]
                                [--warmup-groups N] [--seconds S]

For every seed it builds the cell's entry as ``benchmark/run.py`` does
(``cell.entry().build(Context(cell, seed, devices))``: the model drawn from
the seed, the timed step), calls its ``warm_up()`` and its ``measure()``,
and leaves the correctness check and the reference out: those are
``run.py``'s, seven of its nine minutes a run of a language cell.  The step
program compiles once.  Printed for every seed: the entry's own lines (a
language cell's warm-up prints the routers' loads after every group),
``train_samples_per_s``, each group's time and the routed layers' counters;
at the end the spread as the driver takes it (the distance between the first
and third quartile of ``statistics.quantiles(values, n=4)`` over the
median) and the mean drift from one group of a window to the next.  A seed's
line also holds every dispatch's and every fetch's time in the window
(``dispatch_s``, ``fetch_s``: a group that lost time shows whether the host
held a dispatch or the device held the fetch) and the host's garbage
collections while the window ran (``collections``: generation, seconds from
the window's start, seconds taken).  ``--warmup-groups`` overrides the
traffic file's, to find the value to write there.  The lines also go to
``chiprun_out/cell_spread.jsonl``.
"""

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import device, manifest  # noqa: E402


def quartile_spread(values):
    """(third quartile - first quartile) / median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


class Collections:
    """The host's garbage collections while it is entered: [generation,
    start in seconds from the entry, seconds taken] each."""

    def __init__(self):
        self.seen, self._opened, self._began = [], None, None

    def __call__(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._began = now
        elif self._began is not None:
            self.seen.append([info["generation"], self._began - self._opened,
                              now - self._began])
            self._began = None

    def __enter__(self):
        self._opened = time.perf_counter()
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def one_seed(cell, seed, devices, seconds):
    ctx = run.Context(cell, seed, devices)
    session = cell.entry().build(ctx)
    fetch, last = session.fetch, [time.perf_counter()]

    def timed_fetch(handle):
        value = fetch(handle)
        now = time.perf_counter()
        ctx.say("group ended %.3f s after the one before" % (now - last[0]))
        last[0] = now
        return value

    session.fetch = timed_fetch
    session.warm_up()
    with Collections() as collections:
        window = session.measure(seconds)
    n = session.steps_per_fetch
    line = {
        "cell": cell.name, "seed": seed, "ok": window["ok"],
        "train_samples_per_s": window["values"]["train_samples_per_s"],
        "steps": window["steps"],
        "group_s": [sum(window["dispatch_s"][i * n:(i + 1) * n]) + waited
                    for i, waited in enumerate(window["fetch_s"])],
        "dispatch_s": window["dispatch_s"], "fetch_s": window["fetch_s"],
        "collections": collections.seen,
        "counters": window.get("counters", {})}
    del session, window, fetch, timed_fetch
    gc.collect()
    return line


def main(argv=None, gate=device.require_chip, root=ROOT):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cell")
    parser.add_argument("seeds", type=int, nargs="+")
    parser.add_argument("--warmup-groups", type=int)
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args(argv)

    cell = manifest.Manifest(root).cell(args.cell)
    if args.warmup_groups is not None:
        cell.traffic["warmup_groups"] = args.warmup_groups
    devices, _ = gate(cell.chips, root)
    device.enable_compile_cache(root)
    out = os.path.join(root, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    lines = []
    with open(os.path.join(out, "cell_spread.jsonl"), "a") as log:
        for seed in args.seeds:
            lines.append(one_seed(cell, seed, devices, args.seconds))
            print("SEED " + json.dumps(lines[-1]), flush=True)
            log.write(json.dumps(lines[-1]) + "\n")
            log.flush()
    rates = [line["train_samples_per_s"] for line in lines]
    drifts = [b / a - 1 for line in lines
              for a, b in zip(line["group_s"], line["group_s"][1:])]
    summary = {"cell": cell.name, "seeds": len(lines),
               "warmup_groups": cell.traffic.get("warmup_groups"),
               "min": min(rates), "median": statistics.median(rates),
               "max": max(rates),
               "quartile_spread": quartile_spread(rates)
               if len(rates) > 1 else None,
               "mean_group_over_group": statistics.mean(drifts)
               if drifts else None,
               "all_ok": all(line["ok"] for line in lines)}
    print("SPREAD " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
