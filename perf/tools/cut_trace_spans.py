"""python perf/tools/cut_trace_spans.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> [ms]

Cuts a recorded trace down to a fixture for perf/spans.py: cut_trace.py's
cut of the device planes (here only the ``XLA Ops`` and ``XLA Modules``
lines, events of the first <ms> milliseconds, default 150, instruction
texts shortened, of the metadata's stats only ``tf_op``) AND the host
plane's annotated spans (``executor.*``, with their stats) that begin
inside the same stretch, from 20 ms before its first op, on their own
thread's line. Needs tensorflow's xplane_pb2, as cut_trace.py does."""

import gzip
import re
import sys

KEEP_LINES = ("XLA Ops", "XLA Modules")
HOST_SPANS = "executor."
LEAD_PS = 20e9    # spans that began up to 20 ms before the first op


def main():
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = sys.argv[1], sys.argv[2]
    keep_ps = (float(sys.argv[3]) if len(sys.argv) > 3 else 150.0) * 1e9
    raw = (gzip.open(src, "rb") if src.endswith(".gz")
           else open(src, "rb")).read()
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    out = xplane_pb2.XSpace()
    t0 = min(ln.timestamp_ns * 1000 + ev.offset_ps
             for plane in space.planes
             if plane.name.startswith("/device:TPU:")
             for ln in plane.lines if ln.name == "XLA Ops"
             for ev in ln.events)

    def keeps(ln, ev, lead=0.0):
        at = ln.timestamp_ns * 1000 + ev.offset_ps
        return -lead <= at - t0 <= keep_ps

    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        new = out.planes.add()
        new.id, new.name = plane.id, plane.name
        used_meta, used_stats = set(), set()
        for ln in plane.lines:
            if device and ln.name not in KEEP_LINES:
                continue
            nl = new.lines.add()
            nl.id, nl.name = ln.id, ln.name
            nl.timestamp_ns = ln.timestamp_ns
            for ev in ln.events:
                name = plane.event_metadata[ev.metadata_id].name
                if device and not keeps(
                        ln, ev, 0.0 if ln.name == "XLA Ops" else LEAD_PS):
                    continue
                if not device and not (name.startswith(HOST_SPANS)
                                       and keeps(ln, ev, LEAD_PS)):
                    continue
                ne = nl.events.add()
                ne.metadata_id = ev.metadata_id
                ne.offset_ps, ne.duration_ps = ev.offset_ps, ev.duration_ps
                used_meta.add(ev.metadata_id)
                if not device:      # a span's own stats (step=...)
                    for st in ev.stats:
                        ne.stats.add().CopyFrom(st)
                        used_stats.add(st.metadata_id)
            if not nl.events:
                del new.lines[-1]
        for mid in used_meta:
            meta = plane.event_metadata[mid]
            nm = new.event_metadata[mid]
            name = meta.name
            if len(name) > 200:   # as cut_trace.py: head, and the target
                target = re.search(r'custom_call_target="[^"]*"', name)
                name = name[:160] + " ... " + (
                    target.group(0) if target else "")
            nm.id, nm.name = meta.id, name
            for st in meta.stats:
                if plane.stat_metadata[st.metadata_id].name == "tf_op":
                    nm.stats.add().CopyFrom(st)
                    used_stats.add(st.metadata_id)
                    if st.WhichOneof("value") == "ref_value":
                        used_stats.add(st.ref_value)
        for sid in used_stats:
            new.stat_metadata[sid].CopyFrom(plane.stat_metadata[sid])
        if not new.lines:
            del out.planes[-1]
    blob = out.SerializeToString()
    with gzip.open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes before gzip, "
          f"{[(p.name, [(ln.name, len(ln.events)) for ln in p.lines]) for p in out.planes]}")


if __name__ == "__main__":
    main()
