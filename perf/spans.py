"""The program's names in a jax.profiler trace, read from the raw
.xplane.pb: per chip the ``XLA Ops`` events with the ``tf_op`` stat of
their metadata (the HLO instruction's op_name, which carries the
scopes the program's lowering opens: ``<phase>/<name scope>/<op
type>`` and, on a Pallas call, the kernel's ``<family>.<what>.<pass>``
name, as in ``attn.bthd_small.bwd``), and the
``/host:CPU`` lines with the spans the program annotates
(``executor.run`` and its children), on the same clock.

``jax.profiler.ProfileData`` (jax 0.9.0) exposes no event-metadata
stats, so this is a reader of the protobuf wire format for the few
fields needed (tests/perfbench checks it against tensorflow's
xplane_pb2 where that imports). ``load`` gives plain lists, ``reduce``
works on those lists alone; self time is perf.trace.self_times.

What the v5e's raw trace looks like (read by hand, PR 24): the device
planes' lines have timestamp 0 and the host's a timestamp of their
own; line timestamp + event offset is one clock for both. The host
plane has a line per thread; the Python thread's is named after the
process (``python3``) and holds the TraceAnnotations among the Python
tracer's own events (named ``$file.py:line function``), which ``load``
drops.

XSpace.planes = 1; XPlane.name = 2, .lines = 3, .event_metadata = 4,
.stat_metadata = 5 (maps: key = 1, value = 2); XLine.name = 2,
.timestamp_ns = 3, .events = 4; XEvent.metadata_id = 1, .offset_ps = 2,
.duration_ps = 3, .stats = 4; XEventMetadata.id = 1, .name = 2,
.stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
.uint64 = 3, .int64 = 4, .str = 5, .ref = 7 (a stat_metadata id whose
name is the value)."""

from __future__ import annotations

import functools
import gzip
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from perf import harness, trace

PHASES = ("fwd", "bwd", "opt")
HEAD_SCOPES = ("loss_head", "mlm_head")
# the name= of a pl.pallas_call: <family>.<what>.<pass>, the family any
# word (attn today; a later moe needs no edit here)
KERNEL = re.compile(r"^[A-Za-z]\w*\.\w+\.\w+$")
RUN_SPANS = ("executor.run", "executor.run_window")
CHILD_SPANS = ("executor.prepare", "executor.state", "executor.run_step",
               "executor.commit")
MODULES_LINE = "XLA Modules"
GAP_NS = 20e3      # idle gaps shorter than 20 us are between-op slack
# components jax itself puts into an op_name below the program's scope
# (control flow, partitioning); anything with brackets, a comma or an
# arrow is jax's too (jit(f), transpose(jvp()), an einsum's spec)
JAX_WORDS = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "shard_map",
    "pallas_call", "checkpoint", "remat", "custom_jvp_call",
    "custom_vjp_call", "scan", "pjit", "xla_call", "named_call"))


# --- the wire format --------------------------------------------------


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the
    bytes of a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wt = tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield tag >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: Dict[int, str]):
    """(stat name, value) of an XStat; None for a value kind not read."""
    key, val = None, None
    for f, v in _fields(buf):
        if f == 1:
            key = stat_names.get(v, str(v))
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = bytes(v).decode(errors="replace")
        elif f == 7:
            val = stat_names.get(v, "")
    return key, val


def _event(buf, with_stats: bool):
    """(metadata id, offset ps, duration ps, raw stats) of an XEvent.
    A trace holds millions of them, so this is ``_fields`` unrolled for
    the event's field numbers (all under 16: one tag byte)."""
    mid = off = dur = 0
    stats = []
    i, n = 0, len(buf)
    while i < n:
        tag = buf[i]
        i += 1
        if tag & 7 == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if tag == 8:
                mid = val
            elif tag == 16:
                off = _signed(val)
            elif tag == 24:
                dur = val
        elif tag & 7 == 2:
            ln, i = _varint(buf, i)
            if tag == 34 and with_stats:
                stats.append(buf[i:i + ln])
            i += ln
        else:
            i += 8 if tag & 7 == 1 else 4
    return mid, off, dur, stats


def _map_entry(buf):
    key, val = None, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, want_line, keep_event) -> Dict:
    """One XPlane as {"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns, tf_op or stats], ...]}]}: lines for which
    ``want_line(name)`` holds, events whose name ``keep_event`` keeps.
    A device event's fourth item is the ``tf_op`` of its metadata (""
    when it has none), a host event's the dict of its own stats."""
    name, raw_lines, raw_meta, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode(errors="replace")
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            raw_meta.append(v)
        elif f == 5:
            sid, sm = _map_entry(v)
            for f2, v2 in _fields(sm):
                if f2 == 2:
                    stat_names[sid] = bytes(v2).decode(errors="replace")
    host = not trace.DEVICE_PLANE.match(name)
    meta: Dict[int, Tuple[str, str]] = {}     # id -> (name, tf_op)
    for entry in raw_meta:
        mid, m = _map_entry(entry)
        mname, tf_op = "", ""
        for f, v in _fields(m):
            if f == 2:
                mname = bytes(v).decode(errors="replace")
            elif f == 5:
                key, val = _stat(v, stat_names)
                if key == "tf_op":
                    tf_op = val or ""
        meta[mid] = (mname, tf_op)
    lines = []
    for raw in raw_lines:
        lname, t0_ns, raw_events = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                lname = bytes(v).decode(errors="replace")
            elif f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                raw_events.append(v)
        if not raw_events or not want_line(lname):
            continue
        events = []
        for ev in raw_events:
            mid, off_ps, dur_ps, stats = _event(ev, host)
            ename, tf_op = meta.get(mid, (f"#{mid}", ""))
            if not keep_event(ename):
                continue
            extra = (dict(_stat(s, stat_names) for s in stats) if host
                     else tf_op)
            events.append([ename, t0_ns + off_ps / 1e3, dur_ps / 1e3, extra])
        if events:
            lines.append({"name": lname, "events": events})
    return {"name": name, "lines": lines}


@functools.lru_cache(maxsize=2)
def load(path: str) -> Dict:
    """{"planes": [...]} of an .xplane.pb(.gz): every ``/device:TPU:n``
    plane with its ``XLA Ops`` and ``XLA Modules`` lines, and
    ``/host:CPU`` with every thread's line less the Python tracer's
    ``$...`` events. Parsed once per path."""
    with (gzip.open(path, "rb") if path.endswith(".gz")
          else open(path, "rb")) as f:
        raw = memoryview(f.read())
    planes = []
    for f, v in _fields(raw):
        if f != 1:
            continue
        # a plane's name comes before its lines: read it alone first
        pname = next((bytes(v2).decode(errors="replace")
                      for f2, v2 in _fields(v) if f2 == 2), "")
        if trace.DEVICE_PLANE.match(pname):
            planes.append(_plane(
                v, lambda ln: ln in (trace.OPS_LINE, MODULES_LINE),
                lambda ev: True))
        elif pname == "/host:CPU":
            planes.append(_plane(v, lambda ln: True,
                                 lambda ev: not ev.startswith("$")))
    return {"planes": planes}


# --- scopes -----------------------------------------------------------


def _components(op_name: str) -> List[str]:
    """An op_name split at the slashes outside brackets
    (``transpose(jvp(bwd/enc0/ffn/relu_grad))`` is one component)."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    out.append("".join(cur))
    return out


@functools.lru_cache(maxsize=None)
def parse_scope(tf_op: str) -> Optional[Dict]:
    """{"phase", "scope", "op", "kernel"} of a ``tf_op`` stat
    (``<op_name>:<op type>``), or None when the program named nothing
    in it. The phase is the first component that is fwd, bwd or opt;
    scope and op are the components after it up to the first one that
    is jax's own (a primitive is the last component; transforms and
    jitted helpers carry brackets; control flow and partitioning are
    JAX_WORDS), the last of them the Fluid op type; kernel is the
    ``<family>.<what>.<pass>`` component where there is one."""
    parts = _components(tf_op.rsplit(":", 1)[0])
    at = next((i for i, p in enumerate(parts) if p in PHASES), None)
    if at is None:
        return None
    rest = parts[at + 1:]
    kernel = next((p for p in rest if KERNEL.match(p)), None)
    path = []
    body = rest[:-1] or rest
    for i, p in enumerate(body):
        # (a Fluid op named like jax's word is followed by jax's own:
        # the ``while`` op lowers to .../while/while/body/...)
        twice = p in JAX_WORDS and rest[i + 1:i + 2] == [p]
        if (p in JAX_WORDS and not twice or p in PHASES
                or KERNEL.match(p) or any(c in p for c in "(),>")):
            break
        path.append(p)
    return {"phase": parts[at], "scope": "/".join(path[:-1]),
            "op": path[-1] if path else "", "kernel": kernel}


# --- the reduction -----------------------------------------------------


def _chips(doc: Dict) -> List[Tuple[str, List, List]]:
    """(plane name, XLA Ops events, XLA Modules events) per chip that
    ran anything, in the order of the chips' numbers."""
    out = []
    for plane in doc["planes"]:
        m = trace.DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        by = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if by.get(trace.OPS_LINE):
            out.append((int(m.group(1)), plane["name"],
                        by[trace.OPS_LINE], by.get(MODULES_LINE, [])))
    return [c[1:] for c in sorted(out)]


def _dispatch_line(doc: Dict) -> List:
    """The events of the host thread that holds the executor's root
    spans (most of them, if several do), sorted by start."""
    best: List = []
    for plane in doc["planes"]:
        if plane["name"] != "/host:CPU":
            continue
        for ln in plane["lines"]:
            n = sum(1 for e in ln["events"] if e[0] in RUN_SPANS)
            if n > sum(1 for e in best if e[0] in RUN_SPANS):
                best = ln["events"]
    return sorted(best, key=lambda e: (e[1], -e[2]))


def _gaps(ops: List) -> List[Tuple[float, float]]:
    """(start, length) of the idle stretches of GAP_NS and more between
    the first op's start and the last op's end of one chip."""
    out, edge = [], None
    for _, start, dur, *_ in sorted(ops, key=lambda e: e[1]):
        if edge is not None and start - edge >= GAP_NS:
            out.append((edge, start - edge))
        edge = start + dur if edge is None else max(edge, start + dur)
    return out


def _innermost(spans: List, t: float) -> Optional[str]:
    """Name of the innermost of ``spans`` (sorted by start, outer
    first) that contains instant ``t``."""
    inner = None
    for name, start, dur, *_ in spans:
        if start > t:
            break
        if t < start + dur:
            inner = name
    return inner


def reduce(doc: Dict, top: int = 15) -> Optional[Dict]:
    """What the per-layer readers and the run's log need, in ns; None
    when no device op ran. Shares are of ``busy_ns``, the self time of
    all ops summed over the chips.

    - ``scoped_ns``: self time of ops whose tf_op carries a phase;
      ``by_phase_ns``; ``head_ns`` (scope under loss_head / mlm_head);
      ``kernel_ns`` by the kernel's ``<family>.<what>.<pass>``;
    - ``by_scope_ns``: {phase/scope/op: ns}, the whole table (its sum
      is ``scoped_ns``; ``scope_ns`` sums a part of it), ``top_scopes``
      its ``top`` largest as [key, ns]; ``collectives``:
      [instruction label, phase/scope/op, ns]; ``unscoped``: the largest
      ops without a phase, by their instruction's label;
    - ``host``: None without executor root spans on the host plane, else
      ``calls``, ``run_ns`` (mean root span), ``child_ns`` (mean of each
      child over the calls), ``idle_ns`` and ``idle_in_run_ns`` (the
      first chip's gaps of GAP_NS and more, all and those that begin
      inside a root span), ``idle_by_span`` (by the innermost host span
      at the gap's start, "outside" when none), ``skew_ns`` ([min,
      median, max] of a module's start after the start of the
      executor.run_step span that dispatched it: the k-th module of the
      first chip against the k-th span, which holds for a trace that
      begins with the device drained, as harness.DeviceTrace's does;
      None when there are more modules than spans)."""
    chips = _chips(doc)
    if not chips:
        return None
    busy = scoped = head = 0.0
    by_phase = {p: 0.0 for p in PHASES}
    kernels: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    coll: Dict[Tuple[str, str], float] = {}
    unscoped: Dict[str, float] = {}
    for _, ops, _ in chips:
        tf_ops = {e[0]: e[3] for e in ops}
        # an instruction runs every step: sum first, classify once
        by_name: Dict[str, float] = {}
        for name, self_ns in trace.self_times([e[:3] for e in ops]):
            by_name[name] = by_name.get(name, 0.0) + self_ns
        for name, self_ns in by_name.items():
            busy += self_ns
            sc = parse_scope(tf_ops[name]) if tf_ops[name] else None
            key = (f"{sc['phase']}/{sc['scope']}/{sc['op']}".replace(
                "//", "/") if sc else "")
            if trace.op_kind(name) == "collective":
                k = (trace.label(name), key or "(no scope)")
                coll[k] = coll.get(k, 0.0) + self_ns
            if sc is None:
                lb = trace.label(name)
                unscoped[lb] = unscoped.get(lb, 0.0) + self_ns
                continue
            scoped += self_ns
            by_phase[sc["phase"]] += self_ns
            by_scope[key] = by_scope.get(key, 0.0) + self_ns
            if sc["scope"].split("/")[0] in HEAD_SCOPES:
                head += self_ns
            if sc["kernel"]:
                kernels[sc["kernel"]] = kernels.get(sc["kernel"], 0.0) \
                    + self_ns

    def ranked(d, n=top):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:n]]

    return {
        "chips": len(chips), "busy_ns": busy, "scoped_ns": scoped,
        "by_phase_ns": by_phase, "head_ns": head, "kernel_ns": kernels,
        "by_scope_ns": by_scope, "top_scopes": ranked(by_scope),
        "collectives": [[k[0], k[1], v] for k, v in sorted(
            coll.items(), key=lambda kv: -kv[1])],
        "unscoped": ranked(unscoped, 5),
        "host": _host(doc, chips[0]),
    }


def _host(doc: Dict, first_chip) -> Optional[Dict]:
    line = _dispatch_line(doc)
    roots = [e for e in line if e[0] in RUN_SPANS]
    if not roots:
        return None
    _, ops, modules = first_chip
    child = {c: sum(e[2] for e in line if e[0] == c) / len(roots)
             for c in CHILD_SPANS}
    idle = in_run = 0.0
    by_span: Dict[str, float] = {}
    for start, length in _gaps(ops):
        idle += length
        inner = _innermost(line, start) or "outside"
        by_span[inner] = by_span.get(inner, 0.0) + length
        if _innermost(roots, start):
            in_run += length
    steps = sorted(e[1] for e in line if e[0] == "executor.run_step")
    mods = sorted(e[1] for e in modules)
    skew = None
    if mods and len(mods) <= len(steps):
        d = [m - s for s, m in zip(steps, mods)]
        skew = [min(d), statistics.median(d), max(d)]
    return {"calls": len(roots),
            "run_ns": sum(e[2] for e in roots) / len(roots),
            "child_ns": child, "idle_ns": idle, "idle_in_run_ns": in_run,
            "idle_by_span": sorted(by_span.items(), key=lambda kv: -kv[1]),
            "skew_ns": skew}


# --- a run's trace -----------------------------------------------------


def doc_for_run(run) -> Optional[Dict]:
    """``load`` of the raw trace harness.DeviceTrace left for this
    run's cell, parsed once per run: ``for_run`` and any reader that
    needs the events themselves share it. None when the run traced no
    device op or the file is gone."""
    if not run.trace:
        return None
    if getattr(run, "_spans_doc", None) is None:
        try:
            path = trace.find_xplane(
                os.path.join(harness.TRACE_ROOT, run.cell["name"]))
        except FileNotFoundError:
            return None
        run._spans_doc = load(path)
    return run._spans_doc


def for_run(run) -> Optional[Dict]:
    """``reduce`` of this run's raw trace, once per run (the first
    reader that asks also prints the report); None when the run traced
    no device op."""
    if getattr(run, "_spans", None) is None:
        doc = doc_for_run(run)
        if doc is None:
            return None
        run._spans = reduce(doc)
        if run._spans:
            report(run._spans, run.window.get("traced_steps") or 1)
    return run._spans


def scope_ns(s: Dict, accept) -> float:
    """Self time (ns) of the scopes ``accept`` takes: it is given the
    components of a ``by_scope_ns`` key, phase first, op type last
    (``["bwd", "blk3", "moe", "mul_grad"]``). "Under a ``moe`` scope" is
    ``scope_ns(s, lambda parts: "moe" in parts[1:-1])``."""
    return sum(v for k, v in s["by_scope_ns"].items()
               if accept(k.split("/")))


def share(run, key) -> Optional[float]:
    """100 x ``key(summary)`` / busy self time, or None when the trace
    carries no phase scope at all (a tree without them)."""
    s = for_run(run)
    if not s or not s["busy_ns"] or not s["scoped_ns"]:
        return None
    return 100.0 * key(s) / s["busy_ns"]


def report(s: Dict, steps: int):
    """What no single number holds, into the run's log."""
    say, n = harness.say, max(steps, 1) * s["chips"]

    def ms(ns):
        return round(ns / n / 1e6, 4)

    say(f"perf: scopes: {100 * s['scoped_ns'] / s['busy_ns']:.2f}% of "
        f"busy self time carries a phase; ms a step and chip by phase "
        f"{ {p: ms(v) for p, v in s['by_phase_ns'].items()} }, head "
        f"{ms(s['head_ns'])}, kernels "
        f"{ {k: ms(v) for k, v in sorted(s['kernel_ns'].items())} }")
    say("perf: scopes with most self time (ms a step and chip): "
        f"{[[k, ms(v)] for k, v in s['top_scopes']]}")
    say("perf: largest ops without a phase (ms a step and chip): "
        f"{[[k, ms(v)] for k, v in s['unscoped']]}")
    if s["collectives"]:
        say("perf: collectives [instruction, scope, ms a step and chip]: "
            f"{[[a, b, ms(v)] for a, b, v in s['collectives']]}")
    h = s["host"]
    if not h:
        say("perf: host: no executor span in the trace")
        return
    say(f"perf: host: {h['calls']} calls, executor.run "
        f"{h['run_ns'] / 1e6:.4f} ms a call, children (ms a call) "
        f"{ {k: round(v / 1e6, 4) for k, v in h['child_ns'].items()} }")
    say(f"perf: host: first chip idle in gaps of {GAP_NS / 1e3:.0f} us "
        f"and more {h['idle_ns'] / 1e6:.4f} ms, inside a root span "
        f"{h['idle_in_run_ns'] / 1e6:.4f} ms, by the innermost host span "
        f"at the gap's start (ms) "
        f"{ [[k, round(v / 1e6, 4)] for k, v in h['idle_by_span']] }")
    say("perf: host: a module's first instant after its "
        "executor.run_step span began (ms) [min, median, max] "
        f"{h['skew_ns'] and [round(v / 1e6, 4) for v in h['skew_ns']]}")
