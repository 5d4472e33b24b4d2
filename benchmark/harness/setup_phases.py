"""``setup_s`` divided from inside the program.

The program keeps two lists in memory, both on ``time.time_ns()``
(``mxnet_tpu.profiler``): the **kept spans**, boundary spans that happen once
per ``GluonTrainStep`` (``mxtpu.setup.place``, ``mxtpu.setup.orders`` with
``.learn`` and ``.relay``, and the ``mxtpu.step*`` spans of each step's call
0), and the **compile log**, jax's own report of every program the process
traced, lowered, compiled or loaded from the persistent cache, each with its
``fun_name``.  ``partition`` gives every nanosecond from the process's start
to set-up's end to exactly one bucket, innermost first:

    trace_lower   a compile-log interval of kind ``trace`` or ``lower``
    compile       one of kind ``compile`` (the program missed the cache)
    cache_load    one of kind ``cache_load`` (it came from the cache)
    state         else, inside ``mxtpu.setup.place`` / ``mxtpu.setup.orders*``
    warmup        else, from the start of call 0 of the last ``GluonTrainStep``
                  first called before set-up's end (the timed step; the check's
                  float32 step comes before it) to set-up's end
    other         else: imports, chip attach, the model's draw on the host,
                  the probe's eager programs' runs, the check's own compute:
                  the entry's side, which carries no span

so the six sum to ``setup_s``.  A program without the two lists (an older
commit) gives nothing to read and every reader returns None: the harness
leaves the metric out.
"""

import time

from . import device

BUCKETS = ("trace_lower", "compile", "cache_load", "state", "warmup", "other")
BUCKET_OF_KIND = {"trace": "trace_lower", "lower": "trace_lower",
                  "compile": "compile", "cache_load": "cache_load"}
STATE_SPANS = ("mxtpu.setup.place", "mxtpu.setup.orders",
               "mxtpu.setup.orders.learn", "mxtpu.setup.orders.relay")
STEP_SPAN = "mxtpu.step"
# seconds under which a record is not given a row of the table
TABLE_FLOOR_S = 0.05


def program_records():
    """-> (kept spans, compile log, {list: records lost to its bound}) as
    the program holds them now; None where it has no such lists."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    kept, log = (getattr(profiler, name, None)
                 for name in ("kept_spans", "compile_log"))
    if kept is None or log is None:
        return None
    return kept(), log(), profiler.kept_dropped()


def warmup_start_ns(spans, end_ns):
    """Start of call 0 of the last step first called before ``end_ns``."""
    starts = [s.start_ns for s in spans
              if s.name == STEP_SPAN and s.stats.get("step_num") == 0
              and s.end_ns <= end_ns]
    return max(starts) if starts else None


def partition(spans, log, start_ns, end_ns):
    """{bucket: nanoseconds} of ``[start_ns, end_ns)``; whole numbers that
    sum to ``end_ns - start_ns``.  Of the intervals that cover an instant
    the compile log's win over the state spans, those over the warm-up, and
    among equals the one that started last (the innermost)."""
    covers = []     # (start, end, (rank, start), bucket)
    for r in log:
        if r.end_ns <= end_ns:
            covers.append((r.start_ns, r.end_ns, (3, r.start_ns),
                           BUCKET_OF_KIND[r.kind]))
    for s in spans:
        if s.name in STATE_SPANS and s.end_ns <= end_ns:
            covers.append((s.start_ns, s.end_ns, (2, s.start_ns), "state"))
    warm = warmup_start_ns(spans, end_ns)
    if warm is not None:
        covers.append((warm, end_ns, (1, warm), "warmup"))
    covers = [(max(a, start_ns), min(b, end_ns), key, bucket)
              for a, b, key, bucket in covers
              if min(b, end_ns) > max(a, start_ns)]

    out = dict.fromkeys(BUCKETS, 0)
    edges = sorted({start_ns, end_ns, *(c[0] for c in covers),
                    *(c[1] for c in covers)})
    opening = sorted(covers)
    nxt, live = 0, []
    for lo, hi in zip(edges, edges[1:]):
        while nxt < len(opening) and opening[nxt][0] <= lo:
            live.append(opening[nxt])
            nxt += 1
        live = [c for c in live if c[1] > lo]
        bucket = max(live, key=lambda c: c[2])[3] if live else "other"
        out[bucket] += hi - lo
    return out


def built_as(log, kind, end_ns):
    """How many records of ``kind`` end before ``end_ns``."""
    return sum(1 for r in log if r.kind == kind and r.end_ns <= end_ns)


def table(log, start_ns, end_ns, dropped=None):
    """The compile log as lines: what was built before ``end_ns``, longest
    first, with its kind, jax's ``fun_name``, the second of the process's
    life it started in and the kept span it ran under."""
    rows = sorted((r for r in log if r.end_ns <= end_ns),
                  key=lambda r: r.start_ns - r.end_ns)
    lines = ["compile log: %d records before set-up's end%s"
             % (len(rows), "; lost to the bound: %s" % dropped
                if dropped and any(dropped.values()) else ""),
             "%9s  %-10s %9s  %-44s %s" % ("seconds", "kind", "at", "fun_name",
                                           "under")]
    rest = {}
    for r in rows:
        seconds = (r.end_ns - r.start_ns) / 1e9
        if seconds < TABLE_FLOOR_S:
            n, total = rest.get(r.kind, (0, 0.0))
            rest[r.kind] = (n + 1, total + seconds)
            continue
        lines.append("%9.3f  %-10s %9.2f  %-44s %s%s" % (
            seconds, r.kind, (r.start_ns - start_ns) / 1e9,
            str(r.fun_name)[:44], r.span or "-",
            "" if r.retrieval_s is None
            else "  (read in %.3f s)" % r.retrieval_s))
    for kind, (n, total) in sorted(rest.items()):
        lines.append("%9.3f  %-10s %9s  %d records under %.2f s each"
                     % (total, kind, "", n, TABLE_FLOOR_S))
    return lines


_last = [None, None]    # the observations last read, their phases


def phases(obs):
    """What the ``setup.*`` readers share: {bucket: seconds} plus
    ``programs_compiled`` for this run, None on a program without the
    lists.  The first reader to ask prints the compile log's table."""
    if _last[0] is not obs:
        _last[:] = obs, _phases(obs)
    return _last[1]


def _phases(obs):
    records = program_records()
    if records is None:
        return None
    spans, log, dropped = records
    # the process's start on the records' clock, from the kernel's record of
    # it as ``setup_s`` was (to /proc/uptime's 10 ms)
    start_ns = time.time_ns() - int(device.process_age_s() * 1e9)
    end_ns = start_ns + int(obs["values"]["setup_s"] * 1e9)
    out = {bucket: ns / 1e9 for bucket, ns
           in partition(spans, log, start_ns, end_ns).items()}
    out["programs_compiled"] = float(built_as(log, "compile", end_ns))
    print("\n".join(table(log, start_ns, end_ns, dropped)), flush=True)
    print("set-up by phase: " + ", ".join(
        "%s %.2f s" % (bucket, out[bucket]) for bucket in BUCKETS)
        + "; cache loads: %d; state spans: %s" % (
            built_as(log, "cache_load", end_ns),
            ", ".join("%s %.2f s %s" % (s.name, (s.end_ns - s.start_ns) / 1e9,
                                        s.stats)
                      for s in spans if s.name in STATE_SPANS)), flush=True)
    return out


def read(obs, key):
    """What a ``setup.<key>`` reader returns."""
    found = phases(obs)
    return None if found is None else found[key]
