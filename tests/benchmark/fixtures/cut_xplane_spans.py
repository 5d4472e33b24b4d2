"""How ``resnet50_train_bs128_spans_2steps.xplane.pb.gz`` was cut from a
chip trace of PR 25 (run by hand, once, in the sandbox; needs tensorflow's
xplane_pb2, which the benchmark itself never imports):

    python3 cut_xplane_spans.py <in.xplane.pb> <out.xplane.pb> <first step>
                                <steps>

``cut_xplane.py`` (PR 22, kept as it was with its two traces) plus the
program's own host spans: every event of plane ``/host:CPU`` whose name
starts with ``mxtpu.`` is kept with its stats (``step_num``, ``leaves``).
As there: the TPU planes' ``XLA Ops``, ``Async XLA Ops`` and ``XLA Modules``
lines and the host's benchmark spans, inside the time of ``steps`` runs of
the largest module starting at its ``first step``-th run, with a little
room on both sides; the ``traced_window`` span is clipped to that time.
Nothing else is altered: names, offsets and durations are the profiler's.
The optimized HLO text of ``jit_step`` lies beside the trace
(``*.jit_step.hlo.txt.gz``, from ``device.loaded_hlo_modules`` in the same
run): the instructions' ``op_name`` scopes are in the module, not in the
trace.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

SPANS = {"dispatch", "loss_fetch", "traced_window"}
PROGRAM_PREFIX = "mxtpu."
LINES = {"XLA Ops", "Async XLA Ops", "XLA Modules"}
ROOM_PS = 200_000_000      # 0.2 ms


def main(src, dst, first, steps):
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    lo = hi = None
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules" or lo is not None:
                continue
            runs = sorted(line.events, key=lambda e: -e.duration_ps)
            longest = runs[0].metadata_id
            runs = sorted((e for e in line.events
                           if e.metadata_id == longest),
                          key=lambda e: e.offset_ps)[first:first + steps]
            base = line.timestamp_ns * 1000
            lo = base + runs[0].offset_ps - ROOM_PS
            hi = base + runs[-1].offset_ps + runs[-1].duration_ps + ROOM_PS
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        used, used_stats = set(), set()
        for line in plane.lines:
            if device and line.name not in LINES:
                continue
            base = line.timestamp_ns * 1000
            kept = []
            for e in line.events:
                start, end = base + e.offset_ps, base + e.offset_ps \
                    + e.duration_ps
                name = plane.event_metadata[e.metadata_id].name
                if device and start >= lo and end <= hi:
                    kept.append(e)
                elif not device and (name in SPANS or name.startswith(
                        PROGRAM_PREFIX)) and end > lo and start < hi:
                    if name == "traced_window":
                        e.offset_ps = max(start, lo) - base
                        e.duration_ps = min(end, hi) - max(start, lo)
                    kept.append(e)
            if not kept:
                continue
            new_line = new.lines.add(
                id=line.id, name=line.name, display_name=line.display_name,
                timestamp_ns=line.timestamp_ns)
            for e in kept:
                copy = new_line.events.add(metadata_id=e.metadata_id,
                                           offset_ps=e.offset_ps,
                                           duration_ps=e.duration_ps)
                if not device:
                    for stat in e.stats:
                        copy.stats.add().CopyFrom(stat)
                        used_stats.add(stat.metadata_id)
                used.add(e.metadata_id)
        for mid in used:
            meta = plane.event_metadata[mid]
            new.event_metadata[mid].id = meta.id
            new.event_metadata[mid].name = meta.name
        for sid in used_stats:
            meta = plane.stat_metadata[sid]
            new.stat_metadata[sid].id = meta.id
            new.stat_metadata[sid].name = meta.name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print("kept", {p.name: sum(len(l.events) for l in p.lines)
                   for p in out.planes})


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
