"""Device-time roofline attribution: per-op HLO profiles, measured MFU,
and compute/memory-bound verdicts.

Every MFU number the bench suite prints is *analytic* — hand-derived
FLOP counts over wall time — and the time-attribution plane (monitor.py
step phases) stops at host-side phases: nothing says which HLO ops eat
the device, or whether they are compute- or memory-bound. This module
is the device half, the modern analog of the reference's CUPTI tracer +
timeline pair (reference: platform/device_tracer.cc + tools/timeline.py
— the seam profiler.py explicitly delegates to jax.profiler):

1. **Per-op device timings** — ``parse_xplane(dir)`` decodes the XSpace
   protobuf that ``jax.profiler`` writes (a self-contained wire-format
   reader: the tensorflow profiler protos are not a dependency) and
   aggregates per-HLO-op device seconds off the ``/device:*`` planes.
   No device plane (this CPU container), an empty/partial trace dir, or
   a parse failure all degrade to ``None`` with ONE warning — the
   profile then builds from the compile report instead
   (``source: "estimate"``), the same degrade contract as the compile
   report's guarded cost_analysis.

2. **HLO -> framework mapping** — ``classify_hlo`` buckets XLA op names
   into groups (matmul / elementwise / reduction / data_movement /
   collective / fusion / overhead) and ``map_to_framework_ops`` names
   the program ops that lower into each bucket via
   ``LoweredBlock.op_histogram`` — the per-op list the next kernel PR
   starts from.

3. **Roofline verdict + measured MFU** — joining device seconds with
   the compile report's cost_analysis flops/bytes gives arithmetic
   intensity; against the backend's ridge point
   (``peak_flops / peak_bytes_per_sec``, table in ``DEVICE_PEAKS``,
   overridable via the ``device_peak_*`` flags) the program is
   ``compute_bound`` (intensity >= ridge), ``memory_bound`` (below it),
   or ``overhead`` when it achieves under ``OVERHEAD_FRACTION`` of the
   roofline-permitted FLOP rate — neither roof is near, the time went
   to dispatch/latency. ``measured_mfu`` is achieved FLOP/s over
   ``peak_flops`` — the measured twin of the bench tables' analytic
   MFU.

The result is a versioned per-program **device profile**
(``DEVICE_PROFILE_FIELDS``) surfaced everywhere the existing planes
reach: the ``/profile`` monitor route, ``pt_program_mfu{program=}`` and
``pt_device_op_seconds{op=}`` instruments, a ``roofline`` section in
fleet digests (``/fleet`` shows per-rank MFU), a per-op device-time
annotation in ``debugger.pprint_program``, and a ``measured_mfu`` field
in bench rows beside the analytic one.

Sampling: the executor builds a profile every
``device_profile_every_n_steps`` phase-SAMPLED steps (the honest device
phase supplies the device seconds; with ``device_profile_xplane`` on it
additionally wraps the step in a jax.profiler trace). Off by default —
the disabled executor hot path is one boolean check, zero allocations.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

from paddle_tpu import flags as _flags
from paddle_tpu import monitor as _monitor

# ---------------------------------------------------------------------------
# backend peaks + ridge point
# ---------------------------------------------------------------------------

# Peak dense-matmul FLOP/s (bf16) per v5e chip — THE single definition
# in the program (perf/peaks.json is the benchmark's own table).
V5E_PEAK_BF16 = 197e12

# device_kind (the string jax reports for the device) -> (peak FLOP/s,
# peak memory bytes/s). The ridge point (intensity where the compute and
# memory roofs meet) is their ratio: v5e ~240 FLOP/B. The v5e row is the
# published peak (Google Cloud "TPU v5e": 197 TFLOP/s bf16, 819 GB/s);
# the "cpu" row is a rough single-socket figure that only ORDERS the
# verdicts of the CPU test suite. A device that is not listed is an
# error, not a default: add its row (or set both device_peak_* flags).
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (V5E_PEAK_BF16, 819e9),
    "cpu": (5e11, 5e10),
}


def backend_peaks(device_kind: Optional[str] = None) -> Tuple[float, float]:
    """(peak_flops, peak_bytes_per_sec) for ``device_kind`` (default:
    jax's first device), honoring the ``device_peak_flops`` /
    ``device_peak_bytes_per_sec`` flag overrides. Raises for a device
    the table does not list unless both flags are set."""
    f = float(_flags.get_flag("device_peak_flops"))
    b = float(_flags.get_flag("device_peak_bytes_per_sec"))
    if f > 0 and b > 0:
        return f, b
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no roofline peaks for device kind '{device_kind}' (known: "
            f"{sorted(DEVICE_PEAKS)}); add a DEVICE_PEAKS row with its "
            f"source, or set the device_peak_flops and "
            f"device_peak_bytes_per_sec flags")
    pf, pb = DEVICE_PEAKS[device_kind]
    return (f if f > 0 else pf), (b if b > 0 else pb)


# Below this fraction of the roofline-permitted FLOP rate the verdict is
# "overhead": the program reaches neither roof, the time went to
# dispatch / latency / launch gaps rather than compute or bandwidth.
OVERHEAD_FRACTION = 1 / 3


# ---------------------------------------------------------------------------
# xplane parsing (self-contained protobuf wire reader)
# ---------------------------------------------------------------------------

# XSpace wire schema (tensorflow/tsl profiler protos; stable since 2020
# — the fields read here have never been renumbered):
#   XSpace.planes = 1;  XPlane.name = 2, .lines = 3, .event_metadata = 4
#   (map<int64, XEventMetadata>: key = 1, value = 2; XEventMetadata.name
#   = 2, .stats = 5), .stat_metadata = 5 (map<int64, XStatMetadata>,
#   XStatMetadata.name = 2);  XLine.name = 2, .events = 4;
#   XEvent.metadata_id = 1, .duration_ps = 3;  XStat.metadata_id = 1,
#   .str_value = 5, .ref_value = 7 (a stat_metadata id whose NAME is the
#   value). The one stat read is an event metadata's ``tf_op``: the HLO
#   instruction's op_name, which carries the scope the lowering opened
#   around the program op it came from (core/interp.exec_ops).


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7
        if shift > 70:
            raise ValueError("varint overrun")


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _read_varint(buf, i)
        elif wt == 2:
            ln, i = _read_varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        else:
            raise ValueError(f"unsupported wire type {wt}")
        if i > n:
            raise ValueError("truncated message")
        yield fnum, wt, v


def _map_entry(buf: bytes):
    """(key, value bytes) of one protobuf map entry (key = 1, value = 2)."""
    key, val = None, b""
    for fnum, _wt, v in _fields(buf):
        if fnum == 1:
            key = v
        elif fnum == 2:
            val = v
    return key, val


def _name_and_stats(buf: bytes):
    """(name, [raw XStat, ...]) of an XEventMetadata / XStatMetadata."""
    name, stats = "", []
    for fnum, _wt, v in _fields(buf):
        if fnum == 2:
            name = v.decode(errors="replace")
        elif fnum == 5:
            stats.append(v)
    return name, stats


def _parse_plane(buf: bytes):
    """(name, {metadata_id: event_name},
    [(line_name, [(metadata_id, duration_ps), ...]), ...],
    {metadata_id: tf_op})."""
    name = ""
    meta: Dict[int, str] = {}
    raw_stats: Dict[int, List[bytes]] = {}
    stat_names: Dict[int, str] = {}
    lines: List[Tuple[str, List[Tuple[int, int]]]] = []
    for fnum, _wt, v in _fields(buf):
        if fnum == 2:
            name = v.decode(errors="replace")
        elif fnum == 5:  # stat_metadata map entry
            sid, sm = _map_entry(v)
            if sid is not None:
                stat_names[sid] = _name_and_stats(sm)[0]
        elif fnum == 4:  # event_metadata map entry
            mid, em = _map_entry(v)
            if mid is not None:
                meta[mid], mstats = _name_and_stats(em)
                if mstats:
                    raw_stats[mid] = mstats
        elif fnum == 3:  # XLine
            line_name = ""
            events: List[Tuple[int, int]] = []
            for f2, _w2, v2 in _fields(v):
                if f2 == 2:
                    line_name = v2.decode(errors="replace")
                elif f2 == 4:  # XEvent
                    mid = dur_ps = 0
                    for f3, _w3, v3 in _fields(v2):
                        if f3 == 1:
                            mid = v3
                        elif f3 == 3:
                            dur_ps = v3
                    events.append((mid, dur_ps))
            lines.append((line_name, events))
    # the stat names follow the event metadata in the file: resolve last
    tf_ops: Dict[int, str] = {}
    for mid, mstats in raw_stats.items():
        for st in mstats:
            key, val = None, ""
            for f, _w, v in _fields(st):
                if f == 1:
                    key = stat_names.get(v)
                elif f == 5:
                    val = v.decode(errors="replace")
                elif f == 7:
                    val = stat_names.get(v, "")
            if key == "tf_op" and val:
                tf_ops[mid] = val
    return name, meta, lines, tf_ops


def scope_of(tf_op: str) -> Optional[str]:
    """``<phase>/<name scope>/<op type>`` out of an HLO op_name
    (``jit(step_fn)/bwd/enc0/ffn/mul_grad/transpose(jvp())/dot_general``
    -> ``bwd/enc0/ffn/mul_grad``): from the first component that is a
    phase up to the first that is a registered op type; None when the
    lowering put no scope into it. A control-flow op's sub-block nests
    under it, so the scope is the OUTERMOST op's."""
    parts, depth, cur = [], 0, []
    for ch in tf_op.rsplit(":", 1)[0]:   # the stat is <op_name>:<type>
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    from paddle_tpu.core.registry import GRAD_OP_SUFFIX, has_op
    from paddle_tpu.framework import OP_ROLES

    at = next((i for i, p in enumerate(parts) if p in OP_ROLES), None)
    if at is None:
        return None
    for j in range(at + 1, len(parts)):
        p = parts[j]
        if has_op(p) or (p.endswith(GRAD_OP_SUFFIX)
                         and has_op(p[:-len(GRAD_OP_SUFFIX)])):
            return "/".join(parts[at:j + 1])
    return None


# A TPU device plane carries SEVERAL lines covering the same wall
# interval at different granularities ("XLA Modules" > "XLA Ops" >
# "Steps" / "XLA TraceMe"): summing them all would double- or
# triple-count every interval. The op-level line is the one this plane
# attributes; when no line carries that name (GPU stream lines are
# unnamed-per-stream kernel rows), every line EXCEPT the known
# coarser/annotation rows is aggregated.
OP_LINE_NAME = "XLA Ops"
EXCLUDED_LINES = ("XLA Modules", "Steps", "XLA TraceMe",
                  "Framework Ops", "Source code", "SparseCoreOps")


def _select_op_lines(lines):
    ops_lines = [ev for name, ev in lines if OP_LINE_NAME in name]
    if ops_lines:
        return ops_lines
    return [ev for name, ev in lines
            if not any(name.startswith(x) for x in EXCLUDED_LINES)]


def _xplane_files(path: str) -> List[str]:
    if os.path.isfile(path):
        return [path]
    found = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(".xplane.pb"):
                found.append(os.path.join(root, f))
    return sorted(found)


def _parse_capture(path: str, warn: bool = True):
    """(per-op map, per-plane op-line totals) or None — the shared
    reader behind parse_xplane/profile_from_xplane. Per-op seconds sum
    WORK across every ``/device:*`` plane; the plane totals let the
    profile take the MAX as its wall-clock device interval (concurrent
    devices overlap in time — summing them would report an 8-chip step
    as 8x its wall device time and deflate measured MFU by 8x)."""
    files = _xplane_files(path)
    if not files:
        if warn:
            warnings.warn(
                f"no .xplane.pb under {path!r}; device profile degrades "
                f"to source=\"estimate\"", RuntimeWarning, stacklevel=3)
        return None
    ops: Dict[str, Dict[str, float]] = {}
    plane_totals: List[float] = []
    try:
        for f in files:
            with open(f, "rb") as fh:
                buf = fh.read()
            for fnum, _wt, v in _fields(buf):
                if fnum != 1:  # XSpace.planes
                    continue
                name, meta, lines, tf_ops = _parse_plane(v)
                if "/device:" not in name:
                    continue
                total = 0.0
                for events in _select_op_lines(lines):
                    for mid, dur_ps in events:
                        op = meta.get(mid, f"op#{mid}")
                        cell = ops.get(op)
                        if cell is None:
                            cell = ops[op] = {"seconds": 0.0,
                                              "count": 0}
                            if mid in tf_ops:  # only where the trace
                                cell["tf_op"] = tf_ops[mid]  # has one
                        cell["seconds"] += dur_ps / 1e12
                        cell["count"] += 1
                        total += dur_ps / 1e12
                plane_totals.append(total)
    except (ValueError, OSError, IndexError) as e:
        if warn:
            warnings.warn(
                f"xplane parse of {path!r} failed ({type(e).__name__}: "
                f"{e}); device profile degrades to source=\"estimate\"",
                RuntimeWarning, stacklevel=3)
        return None
    # (a CPU process that has loaded libtpu, as one that compiled for a
    # described chip has, writes /device:TPU planes without an event)
    if not any(plane_totals):
        if warn:
            warnings.warn(
                f"xplane capture under {path!r} has no /device:* plane "
                f"with an op (backend without device tracing, e.g. CPU); "
                f"device profile degrades to source=\"estimate\"",
                RuntimeWarning, stacklevel=3)
        return None
    return ops, plane_totals


def parse_xplane(path: str,
                 warn: bool = True) -> Optional[Dict[str, Dict[str, float]]]:
    """Aggregate per-op device seconds from a jax.profiler capture.

    ``path``: a trace dir (searched recursively for ``*.xplane.pb`` —
    the layout ``jax.profiler.start_trace`` writes) or one ``.pb``
    file. Returns ``{op_name: {"seconds", "count"}}`` (plus ``"tf_op"``,
    the HLO instruction's op_name, where the trace carries that stat)
    summed over every ``/device:*`` plane, or ``None`` — with exactly
    ONE warning — when
    the capture is unavailable: no file, a truncated/corrupt proto, or
    no device plane at all (the CPU container's trace has only host
    planes). Callers then take the ``source: "estimate"`` path
    (``warn=False`` suppresses the warning: the executor's sampling
    loop warns once per process, not once per sampled step)."""
    parsed = _parse_capture(path, warn=warn)
    return None if parsed is None else parsed[0]


# ---------------------------------------------------------------------------
# HLO op classification + framework mapping
# ---------------------------------------------------------------------------

# HLO opcode prefix -> group. Keys are matched against the op name with
# its %-sigil and trailing ".<n>"/digit suffix stripped.
HLO_GROUPS: Dict[str, str] = {}
for _g, _names in (
    ("matmul", ("dot", "dot-general", "convolution", "cublas-gemm",
                "triton-gemm", "custom-call-gemm")),
    ("elementwise", ("add", "subtract", "multiply", "divide", "power",
                     "maximum", "minimum", "exponential", "exp", "log",
                     "tanh", "rsqrt", "sqrt", "negate", "abs", "sign",
                     "compare", "select", "and", "or", "not", "xor",
                     "convert", "clamp", "floor", "ceil", "round",
                     "sine", "cosine", "logistic", "remainder",
                     "shift-left", "shift-right-logical",
                     "shift-right-arithmetic", "rng", "rng-bit-generator",
                     "map")),
    ("reduction", ("reduce", "reduce-window", "sort", "argmax", "argmin",
                   "select-and-scatter", "topk")),
    ("data_movement", ("copy", "transpose", "reshape", "broadcast",
                       "slice", "dynamic-slice", "dynamic-update-slice",
                       "concatenate", "gather", "scatter", "pad", "iota",
                       "reverse", "bitcast", "bitcast-convert", "tuple",
                       "get-tuple-element", "constant", "parameter")),
    ("collective", ("all-reduce", "all-gather", "all-to-all",
                    "reduce-scatter", "collective-permute",
                    "collective-broadcast", "partition-id", "replica-id")),
    ("fusion", ("fusion", "loop_fusion", "input_fusion", "output_fusion",
                "while", "conditional", "call", "custom-call")),
    ("overhead", ("infeed", "outfeed", "copy-start", "copy-done", "send",
                  "send-done", "recv", "recv-done", "after-all",
                  "opt-barrier", "async-start", "async-done",
                  "async-update")),
):
    for _n in _names:
        HLO_GROUPS[_n] = _g

# group -> framework op types that lower into it (intersected with the
# program's actual op_histogram by map_to_framework_ops). An HLO op can
# name several candidates — attribution is a shortlist, not a proof.
FRAMEWORK_GROUPS: Dict[str, Tuple[str, ...]] = {
    "matmul": ("matmul", "mul", "fc", "conv2d", "depthwise_conv2d",
               "conv2d_transpose", "sdpa", "flash_attention",
               "sequence_conv", "linear_cross_entropy"),
    "elementwise": ("elementwise_add", "elementwise_sub",
                    "elementwise_mul", "elementwise_div", "relu",
                    "sigmoid", "tanh", "gelu", "scale", "dropout",
                    "cast", "sqrt", "square", "exp", "clip", "swish"),
    "reduction": ("reduce_sum", "reduce_mean", "reduce_max", "softmax",
                  "softmax_with_cross_entropy", "cross_entropy",
                  "layer_norm", "batch_norm", "mean", "pool2d", "topk"),
    "data_movement": ("reshape", "transpose", "concat", "split", "slice",
                      "lookup_table", "gather", "scatter", "stack",
                      "expand", "squeeze", "unsqueeze", "pad"),
    "collective": ("allreduce", "c_allreduce_sum", "c_allgather",
                   "c_reducescatter", "ring_attention", "pipe_send",
                   "pipe_recv"),
}


def classify_hlo(name: str) -> str:
    """Group an XLA/HLO op name: strips the ``%`` sigil and the
    ``.<uid>`` suffix, then looks the opcode up in ``HLO_GROUPS``.
    Async-pair opcodes (``all-reduce-start``/``-done``/``-update`` —
    modern XLA lowers collectives to these by default) fall back to
    their root opcode's group unless registered explicitly the way
    ``copy-start``/``copy-done`` are. Unknown opcodes -> ``"other"``."""
    base = name.lstrip("%").split(" ")[0]
    base = base.split(".")[0].rstrip("0123456789_")
    base = base or name
    group = HLO_GROUPS.get(base, HLO_GROUPS.get(base.lower()))
    if group is None:
        for suffix in ("-start", "-done", "-update"):
            if base.endswith(suffix):
                group = HLO_GROUPS.get(base[:-len(suffix)])
                break
    return group or "other"


def map_to_framework_ops(hlo_name: str,
                         op_histogram: Optional[Dict[str, int]]
                         ) -> List[str]:
    """Framework op types (from the program's lowering histogram) that
    plausibly lowered into ``hlo_name``'s group — the shortlist a
    kernel PR starts from. Empty when the histogram has no candidate
    (or none was supplied)."""
    if not op_histogram:
        return []
    group = classify_hlo(hlo_name)
    cands = FRAMEWORK_GROUPS.get(group, ())
    return sorted(op for op in cands if op in op_histogram)


# ---------------------------------------------------------------------------
# device-profile schema
# ---------------------------------------------------------------------------

DEVICE_PROFILE_SCHEMA_VERSION = 1

ROOFLINE_VERDICTS = ("compute_bound", "memory_bound", "overhead",
                     "unknown")

# field name -> (accepted types, required, doc); the per-program device
# profile served at /profile and embedded in fleet digests. Cost fields
# are null when the compile report had none; per-op seconds are null on
# the estimate path. Bump the version on any incompatible change.
DEVICE_PROFILE_FIELDS: Dict[str, tuple] = {
    "v": ((int,), True,
          "schema version (DEVICE_PROFILE_SCHEMA_VERSION)"),
    "ts": ((float, int), True, "wall-clock unix timestamp of the sample"),
    "program": ((str,), True, "program id ('program<uid>')"),
    "program_uid": ((int,), True, "Program._uid of the profiled program"),
    "source": ((str,), True,
               "'xplane' (per-op device timings parsed from a "
               "jax.profiler capture) or 'estimate' (compile-report-"
               "derived: no per-op seconds, device time from the "
               "executor's measured device phase)"),
    "backend": ((str,), True, "jax backend the sample ran on"),
    "steps": ((int,), True, "executor steps covered by the sample"),
    "device_seconds": ((float, int, type(None)), True,
                       "wall-clock device time over the sample: the "
                       "MAX per-device-plane op-line total on the "
                       "xplane path (concurrent devices overlap in "
                       "time; per-op seconds/shares aggregate WORK "
                       "across devices), or the executor's measured "
                       "device phase on the estimate path"),
    "wall_seconds": ((float, int, type(None)), True,
                     "host wall time of the sampled call (null when "
                     "the caller supplied only device time)"),
    "flops": ((float, int, type(None)), True,
              "total XLA cost-analysis flops over the sample (compile "
              "report flops x steps); null without a report"),
    "bytes_accessed": ((float, int, type(None)), True,
                       "total XLA cost-analysis bytes accessed over "
                       "the sample; null without a report"),
    "peak_flops": ((float, int), True,
                   "peak device FLOP/s the verdict is scored against"),
    "peak_bytes_per_sec": ((float, int), True,
                           "peak device memory bandwidth the verdict "
                           "is scored against"),
    "ridge_intensity": ((float, int), True,
                        "ridge point (peak_flops / peak_bytes_per_sec, "
                        "FLOP/B): programs above it can be compute-"
                        "bound, below it the memory roof caps them"),
    "intensity": ((float, int, type(None)), True,
                  "arithmetic intensity (flops / bytes_accessed, "
                  "FLOP/B); null without cost numbers"),
    "measured_mfu": ((float, int, type(None)), True,
                     "measured model-FLOPs utilization: achieved "
                     "FLOP/s over peak_flops — the measured twin of "
                     "the bench tables' analytic MFU"),
    "verdict": ((str,), True,
                "roofline verdict: 'compute_bound' (intensity >= "
                "ridge), 'memory_bound' (below it), 'overhead' "
                "(achieved under OVERHEAD_FRACTION of the roofline-"
                "permitted rate — neither roof is near), 'unknown' "
                "(no cost numbers)"),
    "top_ops": ((list,), True,
                "top-K ops by device seconds: [{name, group, seconds, "
                "count, share, framework_ops}] plus 'scope' "
                "('<phase>/<name scope>/<op type>') where the trace's "
                "tf_op stat names the program op, in which case "
                "framework_ops is that op's type and not the group "
                "shortlist; on the estimate path the op_histogram's "
                "types with null seconds"),
    "groups": ((dict,), True,
               "per-group device-time rollup: group -> {seconds, "
               "share, count} (empty on the estimate path)"),
}


def validate_device_profile(rec: Dict[str, Any]):
    """Raise ValueError unless ``rec`` conforms to
    DEVICE_PROFILE_FIELDS."""
    _monitor._validate_fields(rec, DEVICE_PROFILE_FIELDS,
                              DEVICE_PROFILE_SCHEMA_VERSION,
                              "device profile")
    if rec["source"] not in ("xplane", "estimate"):
        raise ValueError(
            f"device profile source {rec['source']!r} not in "
            f"('xplane', 'estimate')")
    if rec["verdict"] not in ROOFLINE_VERDICTS:
        raise ValueError(
            f"device profile verdict {rec['verdict']!r} not in "
            f"{ROOFLINE_VERDICTS}")


# ---------------------------------------------------------------------------
# profile assembly
# ---------------------------------------------------------------------------

def _roofline_verdict(flops, bytes_accessed, device_seconds,
                      peak_flops, peak_bw) -> Tuple[Optional[float],
                                                    Optional[float], str]:
    """(intensity, measured_mfu, verdict) from the joined numbers."""
    intensity = None
    if flops and bytes_accessed:
        intensity = float(flops) / float(bytes_accessed)
    mfu = None
    if flops and device_seconds:
        mfu = (float(flops) / float(device_seconds)) / peak_flops
    if intensity is None:
        return intensity, mfu, "unknown"
    ridge = peak_flops / peak_bw
    verdict = "compute_bound" if intensity >= ridge else "memory_bound"
    if mfu is not None:
        # the roofline-permitted FLOP rate at this intensity; achieving
        # well under it means neither roof is the limiter
        permitted = min(peak_flops, intensity * peak_bw)
        if (float(flops) / float(device_seconds)) < (
                OVERHEAD_FRACTION * permitted):
            verdict = "overhead"
    return intensity, mfu, verdict


def _report_costs(program, compile_report, steps: int):
    """(flops_total, bytes_total) for ``steps`` executor steps from the
    program's compile report (fetched from monitor when not passed).
    A window report covers ``window_steps`` steps; a step report one."""
    rep = compile_report
    if rep is None and program is not None:
        rep = _monitor.compile_reports().get(f"program{program._uid}")
    if rep is None:
        return None, None, None
    per = rep.get("window_steps") or 1
    scale = float(steps) / float(per)
    flops = rep.get("flops")
    ba = rep.get("bytes_accessed")
    return (None if flops is None else float(flops) * scale,
            None if ba is None else float(ba) * scale, rep)


def build_device_profile(program, *, source: str,
                         op_seconds: Optional[Dict[str, Dict]] = None,
                         device_seconds: Optional[float] = None,
                         wall_seconds: Optional[float] = None,
                         steps: int = 1,
                         compile_report: Optional[Dict] = None,
                         op_histogram: Optional[Dict[str, int]] = None,
                         backend: Optional[str] = None) -> Dict[str, Any]:
    """Assemble one device profile (DEVICE_PROFILE_FIELDS).

    ``op_seconds`` (xplane source): ``parse_xplane``'s per-op map —
    ``device_seconds`` defaults to its sum. Estimate source: no per-op
    seconds; ``top_ops`` lists the op histogram's types (count-ordered)
    with null seconds so the shape is stable across sources."""
    # an explicit ``backend`` also names the peaks row (the CPU's
    # device_kind is its platform name)
    peak_flops, peak_bw = backend_peaks(backend)
    if backend is None:
        import jax

        backend = jax.default_backend()
    if op_histogram is None and compile_report is not None:
        op_histogram = compile_report.get("op_histogram")
    flops, bytes_accessed, rep = _report_costs(
        program, compile_report, steps)
    if op_histogram is None and rep is not None:
        op_histogram = rep.get("op_histogram")
    top_k = max(int(_flags.get_flag("device_profile_top_k")), 1)
    groups: Dict[str, Dict[str, float]] = {}
    top_ops: List[Dict[str, Any]] = []
    if op_seconds:
        # shares are fractions of total device WORK (op seconds summed
        # across planes); device_seconds may be the smaller max-plane
        # wall interval on multi-device captures
        work = sum(c["seconds"] for c in op_seconds.values())
        if device_seconds is None:
            device_seconds = work
        total = work or 1.0
        for name, cell in op_seconds.items():
            g = classify_hlo(name)
            cell_g = groups.get(g)
            if cell_g is None:
                cell_g = groups[g] = {"seconds": 0.0, "share": 0.0,
                                      "count": 0}
            cell_g["seconds"] += cell["seconds"]
            cell_g["count"] += int(cell["count"])
        for g in groups.values():
            g["share"] = g["seconds"] / total
        ranked = sorted(op_seconds.items(),
                        key=lambda kv: -kv[1]["seconds"])[:top_k]
        for name, cell in ranked:
            row = {
                "name": name,
                "group": classify_hlo(name),
                "seconds": cell["seconds"],
                "count": int(cell["count"]),
                "share": cell["seconds"] / total,
            }
            scope = scope_of(cell.get("tf_op", ""))
            if scope is not None:
                # the trace names the program op: no guess needed
                row["scope"] = scope
                row["framework_ops"] = [scope.rsplit("/", 1)[1]]
            else:
                row["framework_ops"] = map_to_framework_ops(
                    name, op_histogram)
            top_ops.append(row)
    elif op_histogram:
        top_ops = [{
            "name": op, "group": "framework", "seconds": None,
            "count": int(n), "share": None, "framework_ops": [op],
        } for op, n in sorted(op_histogram.items(),
                              key=lambda kv: -kv[1])[:top_k]]
    intensity, mfu, verdict = _roofline_verdict(
        flops, bytes_accessed, device_seconds, peak_flops, peak_bw)
    return {
        "v": DEVICE_PROFILE_SCHEMA_VERSION,
        "ts": time.time(),
        "program": f"program{program._uid}" if program is not None
                   else "program?",
        "program_uid": int(program._uid) if program is not None else -1,
        "source": source,
        "backend": str(backend),
        "steps": int(steps),
        "device_seconds": device_seconds,
        "wall_seconds": wall_seconds,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "peak_flops": peak_flops,
        "peak_bytes_per_sec": peak_bw,
        "ridge_intensity": peak_flops / peak_bw,
        "intensity": intensity,
        "measured_mfu": mfu,
        "verdict": verdict,
        "top_ops": top_ops,
        "groups": groups,
    }


def profile_from_xplane(trace_dir: str, program, *,
                        steps: int = 1,
                        wall_seconds: Optional[float] = None,
                        device_seconds: Optional[float] = None,
                        compile_report: Optional[Dict] = None,
                        op_histogram: Optional[Dict[str, int]] = None,
                        record: bool = True,
                        warn: bool = True) -> Dict[str, Any]:
    """Build (and by default record) a device profile from a
    jax.profiler capture under ``trace_dir``. An unavailable capture
    (see ``parse_xplane``) degrades to the estimate path — the profile
    still builds, with ``source: "estimate"`` and ``device_seconds``
    falling back to the caller's measured value. On a multi-device
    capture the profile's ``device_seconds`` is the max per-plane
    total (devices run concurrently), while per-op seconds aggregate
    work across every plane."""
    parsed = _parse_capture(trace_dir, warn=warn)
    if parsed and parsed[0]:
        ops, plane_totals = parsed
        prof = build_device_profile(
            program, source="xplane", op_seconds=ops,
            device_seconds=max(plane_totals),
            wall_seconds=wall_seconds, steps=steps,
            compile_report=compile_report, op_histogram=op_histogram)
    else:
        prof = build_device_profile(
            program, source="estimate", device_seconds=device_seconds,
            wall_seconds=wall_seconds, steps=steps,
            compile_report=compile_report, op_histogram=op_histogram)
    if record:
        record_profile(prof)
    return prof


def estimate_profile(program, *, device_seconds: Optional[float],
                     steps: int = 1,
                     wall_seconds: Optional[float] = None,
                     compile_report: Optional[Dict] = None,
                     op_histogram: Optional[Dict[str, int]] = None,
                     record: bool = True) -> Dict[str, Any]:
    """The documented degrade path, callable directly (the bench rows
    use it: measured window seconds + the compile report's flops):
    compile-report-derived profile, ``source: "estimate"``."""
    prof = build_device_profile(
        program, source="estimate", device_seconds=device_seconds,
        wall_seconds=wall_seconds, steps=steps,
        compile_report=compile_report, op_histogram=op_histogram)
    if record:
        record_profile(prof)
    return prof


# ---------------------------------------------------------------------------
# recording + instruments + /profile
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
# program id -> latest profile; insertion-ordered, bounded like the
# compile-report buffer
_PROFILES: Dict[str, Dict[str, Any]] = {}
MAX_PROFILES = 32

_M_PROFILES = _monitor.counter(
    "pt_device_profiles_total",
    "device profiles recorded by the roofline plane, by source "
    "(xplane/estimate)")
_M_MFU = _monitor.gauge(
    "pt_program_mfu",
    "measured model-FLOPs utilization of the latest device profile, "
    "by program (achieved cost-analysis FLOP/s over the backend peak)")
_M_OP_SECONDS = _monitor.gauge(
    "pt_device_op_seconds",
    "device seconds of the MOST RECENTLY recorded profile's top-K ops, "
    "by op (xplane source only; cells are replaced wholesale on each "
    "profile, so the top-K cap bounds label cardinality — HLO names "
    "carry per-compile uid suffixes and would otherwise accrete "
    "forever)")


def record_profile(profile: Dict[str, Any]):
    """Store a device profile: bounded per-program buffer (the /profile
    route), mirrored into pt_program_mfu / pt_device_op_seconds. Never
    raises — telemetry must not fail a step."""
    try:
        prog = profile.get("program", "?")
        with _LOCK:
            _PROFILES.pop(prog, None)
            _PROFILES[prog] = profile
            while len(_PROFILES) > MAX_PROFILES:
                _PROFILES.pop(next(iter(_PROFILES)))
        _M_PROFILES.inc(labels={"source": profile.get("source", "?")})
        if profile.get("measured_mfu") is not None:
            _M_MFU.set(profile["measured_mfu"], labels={"program": prog})
        timed = [op for op in profile.get("top_ops", ())
                 if op.get("seconds") is not None]
        # the gauge mirrors ONE profile at a time: the atomic swap
        # keeps cardinality at top-K and stale ops (dead compiles,
        # other programs) out of scrapes — and a concurrent scrape
        # never sees a half-replaced set. An untimed profile (the
        # estimate path, e.g. xplane capture started failing mid-run)
        # EMPTIES the gauge: serving the last successful capture's op
        # mix next to a fresh pt_program_mfu would misattribute it.
        _M_OP_SECONDS.replace(
            ({"op": op["name"]}, op["seconds"]) for op in timed)
    except Exception as e:
        warnings.warn(f"device profile dropped: {e!r}", RuntimeWarning)


def profiles() -> Dict[str, Dict[str, Any]]:
    """Latest device profile per program (insertion order = sample
    order, oldest first)."""
    with _LOCK:
        return {k: dict(v) for k, v in _PROFILES.items()}


def latest(program=None) -> Optional[Dict[str, Any]]:
    """The most recent profile (a copy) — for ``program`` when given,
    else the newest overall."""
    with _LOCK:
        if program is not None:
            prof = _PROFILES.get(f"program{program._uid}")
        elif _PROFILES:
            prof = _PROFILES[next(reversed(_PROFILES))]
        else:
            prof = None
        return dict(prof) if prof is not None else None


def summary() -> Dict[str, Any]:
    """The /profile route body: latest profile per program plus the
    peaks the verdicts were scored against."""
    peak_flops, peak_bw = None, None
    try:
        peak_flops, peak_bw = backend_peaks()
    except KeyError:
        pass  # a device the table does not list: the route still serves
    return {
        "profiles": profiles(),
        "peak_flops": peak_flops,
        "peak_bytes_per_sec": peak_bw,
    }


def digest_section() -> Optional[Dict[str, Any]]:
    """Compact per-program roofline rollup for the fleet digest (the
    ``roofline`` section /fleet renders per rank): measured MFU, verdict
    and source only — profiles stay KV-sized. None when no profile has
    been recorded (the field is optional in the digest schema)."""
    with _LOCK:
        if not _PROFILES:
            return None
        return {prog: {"measured_mfu": p.get("measured_mfu"),
                       "verdict": p.get("verdict"),
                       "source": p.get("source")}
                for prog, p in _PROFILES.items()}


def reset():
    """Test isolation (called from monitor.reset)."""
    global _cap_warned, _parse_warned
    with _LOCK:
        _PROFILES.clear()
        _sample_counts.clear()
    _cap_warned = False
    _parse_warned = False


# ---------------------------------------------------------------------------
# executor sampling hooks
# ---------------------------------------------------------------------------

# cached hot flag values — the disabled executor hot path is one
# function call reading one int (plus monitor's telemetry boolean)
_every = 0
_xplane_on = False


def _sync_every(value):
    global _every
    _every = int(value)


def _sync_xplane(value):
    global _xplane_on
    _xplane_on = bool(value)


_flags.watch_flag("device_profile_every_n_steps", _sync_every)
_flags.watch_flag("device_profile_xplane", _sync_xplane)

_cap_warned = False
_parse_warned = False


def active() -> bool:
    """Whether executors should sample device profiles (telemetry on
    and ``device_profile_every_n_steps`` > 0)."""
    return _every > 0 and _monitor.enabled()


# PER-PROGRAM phase-sampled-step counters; counter-based (not
# absolute-step modulo) so the cadence is literally "every Nth
# phase-sampled step" — a modulo over the absolute index would need
# the step to divide BOTH periods and silently stretch the cadence to
# lcm(step_phases_every_n, device_profile_every_n_steps). Per program
# (not one process-global counter) because interleaved programs whose
# call pattern shares parity with the period would otherwise starve
# each other: train/eval alternating with _every=2 would profile the
# train program on every even count and the eval program NEVER.
# Bounded like _PROFILES (insertion-ordered, oldest evicted).
_sample_counts: Dict[int, int] = {}


def take_sample(program=None) -> bool:
    """Executor gate, called once per phase-SAMPLED step/window of
    ``program``: True on every ``device_profile_every_n_steps``-th
    call for that program (the first call profiles immediately, so
    warmup is visible). Returns False — and advances nothing — while
    the plane is off."""
    if _every <= 0 or not _monitor.enabled():
        return False
    uid = int(program._uid) if program is not None else -1
    with _LOCK:
        count = _sample_counts.pop(uid, 0)
        _sample_counts[uid] = count + 1  # re-insert: LRU refresh
        while len(_sample_counts) > MAX_PROFILES:
            _sample_counts.pop(next(iter(_sample_counts)))
    return count % _every == 0


class _Capture:
    """One armed xplane capture around a sampled step (executor use).
    ``stop()`` is idempotent and never raises; a failed start/stop
    degrades the step to the estimate path with one warning per
    process."""

    __slots__ = ("dir", "started")

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="pt_roofline_")
        self.started = False

    def stop(self) -> Optional[str]:
        if not self.started:
            self.cleanup()
            return None
        self.started = False
        try:
            import jax

            jax.profiler.stop_trace()
            return self.dir
        except Exception as e:
            _warn_capture_once(f"jax.profiler.stop_trace() failed: {e!r}")
            self.cleanup()
            return None

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def _warn_capture_once(msg: str):
    global _cap_warned
    if not _cap_warned:
        _cap_warned = True
        warnings.warn(
            f"device-profile xplane capture unavailable ({msg}); "
            f"profiles degrade to source=\"estimate\"", RuntimeWarning)


def begin_capture() -> Optional[_Capture]:
    """Arm an xplane capture for a sampled step (None when the
    ``device_profile_xplane`` flag is off or starting the trace fails
    — the step then profiles via the estimate path)."""
    if not _xplane_on:
        return None
    cap = _Capture()
    try:
        import jax

        jax.profiler.start_trace(cap.dir)
        cap.started = True
        return cap
    except Exception as e:
        _warn_capture_once(f"jax.profiler.start_trace() failed: {e!r}")
        cap.cleanup()
        return None


def note_step(program, lowered, *, steps: int = 1,
              device_s: Optional[float] = None,
              wall_s: Optional[float] = None,
              capture: Optional[_Capture] = None):
    """Executor hook: build + record this sampled step's device profile.
    Never raises. ``capture`` (an armed ``begin_capture`` handle) is
    stopped and parsed here; without one — or when the parse degrades —
    the profile is the compile-report-derived estimate with the
    executor's measured device phase as device time."""
    global _parse_warned
    try:
        hist = getattr(lowered, "op_histogram", None)
        trace_dir = capture.stop() if capture is not None else None
        if trace_dir is not None:
            try:
                prof = profile_from_xplane(
                    trace_dir, program, steps=steps,
                    wall_seconds=wall_s, device_seconds=device_s,
                    op_histogram=hist, warn=not _parse_warned)
                if prof.get("source") == "estimate":
                    # warn once per process, not once per sampled step
                    _parse_warned = True
            finally:
                capture.cleanup()
        else:
            estimate_profile(
                program, device_seconds=device_s, steps=steps,
                wall_seconds=wall_s, op_histogram=hist)
    except Exception as e:
        try:
            warnings.warn(f"device profile dropped: {e!r}",
                          RuntimeWarning)
        except Exception:
            pass
