"""python perf/tools/cut_trace.py <in.xplane.pb[.gz]> <out.xplane.pb> [ms]

Cuts a recorded device trace down to a fixture: the device planes only,
events of their first <ms> milliseconds (default 150), the metadata
those events name, instruction texts shortened to head and tail. Needs tensorflow's xplane_pb2 (present in this
installation); the benchmark itself reads traces with jax alone."""

import gzip
import re
import sys


def main():
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = sys.argv[1], sys.argv[2]
    keep_ns = float(sys.argv[3]) * 1e6 if len(sys.argv) > 3 else 150e6
    raw = (gzip.open(src, "rb") if src.endswith(".gz")
           else open(src, "rb")).read()
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        starts = [ln.timestamp_ns * 1000 + ev.offset_ps
                  for ln in plane.lines if ln.name == "XLA Ops"
                  for ev in ln.events]
        if not starts:
            continue
        t0 = min(starts)
        new = out.planes.add()
        new.id, new.name = plane.id, plane.name
        used_meta, used_stats = set(), set()
        for ln in plane.lines:
            nl = new.lines.add()
            nl.id, nl.name = ln.id, ln.name
            nl.timestamp_ns, nl.duration_ps = ln.timestamp_ns, ln.duration_ps
            for ev in ln.events:
                at = ln.timestamp_ns * 1000 + ev.offset_ps
                if at - t0 > keep_ns * 1000:
                    continue
                ne = nl.events.add()
                ne.metadata_id = ev.metadata_id
                ne.offset_ps, ne.duration_ps = ev.offset_ps, ev.duration_ps
                used_meta.add(ev.metadata_id)
            if not nl.events:
                del new.lines[-1]
        for mid in used_meta:
            meta = plane.event_metadata[mid]
            nm = new.event_metadata[mid]
            # an instruction's text runs to kilobytes of operands: keep
            # its head (name, result, opcode) and a custom call's
            # target, which is all the reduction reads
            name = meta.name
            if len(name) > 200:
                target = re.search(r'custom_call_target="[^"]*"', name)
                name = name[:160] + " ... " + (
                    target.group(0) if target else "")
            nm.id, nm.name = meta.id, name
            for st in meta.stats:
                name = plane.stat_metadata[st.metadata_id].name
                if name in ("long_name", "hlo_category", "tf_op"):
                    nm.stats.add().CopyFrom(st)
                    used_stats.add(st.metadata_id)
        for sid in used_stats:
            new.stat_metadata[sid].CopyFrom(plane.stat_metadata[sid])
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {len(out.SerializeToString())} bytes, "
          f"{[(p.name, [(ln.name, len(ln.events)) for ln in p.lines]) for p in out.planes]}")


if __name__ == "__main__":
    main()
