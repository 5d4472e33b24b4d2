"""Open-loop load for serving entries: a seeded Poisson schedule sent from
one thread, every request timed from the instant it was due.

Copied in substance from ``tools/loadgen.py`` (the schedule, the sleeping
loop, the percentile by rank) with its clock corrected: that tool measured
``t_done - t_submit``, so a generator that ran late, or a submit call that
stalled, hid queueing time.  Here a late send lengthens the latencies of the
requests it delayed and is reported as lateness beside them, so a starved
generator cannot read as a fast server.  No cell uses this module yet (see
PERF.md, Open questions: the serving cell); its tests pin the clock rule.
"""

import time

import numpy as np


def poisson_schedule(rate_per_s, seconds, rng):
    """Due instants (seconds from the start) of a Poisson process of
    ``rate_per_s`` over ``seconds``, from the numpy ``RandomState``."""
    gaps = rng.exponential(1.0 / rate_per_s,
                           size=int(rate_per_s * seconds * 2) + 16)
    due = np.cumsum(gaps)
    return due[due < seconds]


def percentile(samples, q):
    """The ``q``-th percentile by rank.  Refused (ValueError) unless at
    least ten samples lie beyond it: with fewer, it is a maximum."""
    ordered = sorted(samples)
    beyond = len(ordered) * (100.0 - q) / 100.0
    if beyond < 10:
        raise ValueError(
            "p%g of %d samples has %.1f samples beyond it; ten are needed"
            % (q, len(ordered), beyond))
    return ordered[min(len(ordered) - 1,
                       int(round(q / 100.0 * (len(ordered) - 1))))]


def run_open_loop(due_s, send, clock=time.perf_counter, sleep=time.sleep,
                  tick_s=5e-4):
    """Call ``send(i)`` for every due instant, never earlier, never waiting
    for a reply.  ``send`` returns a handle (or raises: the request failed
    at the door).  -> (start on ``clock``, [(due, sent, handle or None)]),
    ``due`` and ``sent`` in seconds from the start."""
    start = clock()
    records = []
    for i, due in enumerate(due_s):
        while True:
            now = clock() - start
            if now >= due:
                break
            sleep(min(due - now, tick_s))
        try:
            handle = send(i)
        except Exception:       # the entry decides what a refusal is
            handle = None
        records.append((float(due), clock() - start, handle))
    return start, records


def summarize(records, done_s, limit_s):
    """``done_s[i]``: when request i's result reached the host, in seconds
    from the start, or None if it failed.  Latency is ``done - due``.
    -> attempted, failed, latencies, lateness of the sends, completions per
    second, and the share of all requests (failed ones miss) inside
    ``limit_s``."""
    latency = [d - due for (due, _, _), d in zip(records, done_s)
               if d is not None]
    late = [sent - due for due, sent, _ in records]
    span = max([d for d in done_s if d is not None], default=0.0)
    return {
        "attempted": len(records),
        "failed": len(records) - len(latency),
        "latency_s": latency,
        "late_s": late,
        "completed_per_s": len(latency) / span if span > 0 else 0.0,
        "within_limit_share": sum(1 for v in latency if v <= limit_s)
        / len(records) if records else 0.0,
    }
