"""Reduction of a jax.profiler device trace (.xplane.pb) to numbers.

``load`` reads the file with jax.profiler.ProfileData (nothing but jax)
into plain lists; ``reduce`` works on those lists alone, and the tests
check both against the trace recorded on the v5e under perf/fixtures/.

What the v5e's trace looks like (read by hand first, PR 23): one plane
per chip, ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per
executed HLO instruction, NAMED BY THE INSTRUCTION'S FULL TEXT
(``%fusion.579 = (f32[512]{0}, ...) fusion(...), kind=kOutput, ...``);
``XLA Modules`` one event per executed program (``jit_step_fn(...)``),
``Steps`` one per step, ``Async XLA Ops`` the spans of asynchronous
copies, slices and collectives whose start/done halves sit on
``XLA Ops``. A Mosaic kernel is a ``custom-call`` whose text carries
``custom_call_target="tpu_custom_call"``. Its instruction is named
after the ``name=`` its ``pl.pallas_call`` carries and a number
(``%attn.bthd_small.bwd.35``: the program's kernels since PR 24), after
XLA's own word where the compiler made the call (``%ragged-dot-none.3``)
and after the jitted function where the call has no name
(``%step_fn.36``): ``kernel_name`` is that name without the number, its
first dotted component the kernel's family. Host threads are lines of
``/host:CPU``.

Times are nanoseconds on the profiler's clock. Busy time is the UNION
of the ``XLA Ops`` intervals (nested or overlapping events count once),
per chip, averaged over the chips that ran anything."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"

# "%fusion.579 = <result type> fusion(<operands>), ..." -> instruction
# name, result type, opcode
INSTRUCTION = re.compile(
    r"^%?(?P<inst>[\w.\-]+) = (?P<result>.*?[\]})]) (?P<opcode>[a-z][\w\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> str:
    """The newest .xplane.pb under a jax.profiler trace directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]} of the device planes of an
    .xplane.pb (or .xplane.pb.gz) file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def parse(name: str) -> Tuple[str, str, str]:
    """(instruction name, opcode, result type) of an op event's name."""
    m = INSTRUCTION.match(name)
    if not m:
        return name.lstrip("%")[:60], "", ""
    return m.group("inst"), m.group("opcode"), m.group("result")


def op_kind(name: str) -> str:
    """collective | pallas | other, from the instruction's text."""
    _, opcode, _ = parse(name)
    if opcode.startswith(COLLECTIVES):      # all-reduce, all-reduce-start..
        return "collective"
    if opcode == "custom-call" and PALLAS_TARGET in name:
        return "pallas"
    return "other"


def kernel_name(name: str) -> str:
    """The kernel of a Mosaic call: its instruction's name without the
    trailing ``.N`` (``attn.bthd_small.bwd``, ``ragged-dot-none``)."""
    return re.sub(r"\.\d+$", "", parse(name)[0])


def label(name: str) -> str:
    """A short stable label for the breakdown: the instruction (its
    number tells one fusion from the next within a compile), what it
    is, and its first result's shape."""
    inst, opcode, result = parse(name)
    shape = re.search(r"[a-z0-9]+\[[\d,]*\]", result)
    what = "pallas" if PALLAS_TARGET in name else opcode
    return f"{inst} {what} {shape.group(0) if shape else ''}".strip()


def reduce(doc: Dict, top: int = 10) -> Optional[Dict]:
    """The trace's numbers:

    - ``devices``: chips whose ``XLA Ops`` line holds any event;
    - ``window_s``: first op start to last op end over all chips;
    - ``busy_s``: union of op intervals per chip, averaged over chips;
    - ``idle_share``: 1 - busy_s / window_s;
    - ``by_kind_s``: SELF seconds of ops by kind (collective, pallas,
      other), averaged over chips (an op's self time is its duration
      minus the part its nested children cover, so a while loop does
      not count its body twice);
    - ``by_kernel_s``: the ``pallas`` seconds by ``kernel_name``, and
      ``by_family_s``: by the name's first dotted component (``attn``
      for every attention kernel of the program), each summed in the
      events' order, as ``by_kind_s`` is: a program whose Mosaic calls
      are all one family's reads the same number under both;
    - ``async_collective_s``: seconds of collective spans on the
      ``Async XLA Ops`` line, averaged over chips: what a collective
      took from start to done, hidden under compute or not;
    - ``device_ops``: the ``top`` instructions by self seconds (averaged
      over chips), ``idle_gaps``: the ``top`` idle stretches on the
      first chip, summed by the instruction that ended them.
    None when no device op ran."""
    per_dev = []
    for plane in doc["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = next((ln["events"] for ln in plane["lines"]
                    if ln["name"] == OPS_LINE), [])
        if ops:
            per_dev.append((plane["name"], ops))
    if not per_dev:
        return None
    lo = min(e[1] for _, ops in per_dev for e in ops)
    hi = max(e[1] + e[2] for _, ops in per_dev for e in ops)
    busy, by_name = [], {}
    by_kind = {"collective": 0.0, "pallas": 0.0, "other": 0.0}
    by_kernel: Dict[str, float] = {}
    by_family: Dict[str, float] = {}
    kinds: Dict[str, str] = {}     # an instruction's text repeats each step
    for _, ops in per_dev:
        busy.append(union_ns([(e[1], e[1] + e[2]) for e in ops]))
        for name, self_ns in self_times(ops):
            if name not in kinds:
                kinds[name] = op_kind(name)
            by_kind[kinds[name]] += self_ns
            by_name[name] = by_name.get(name, 0.0) + self_ns
            if kinds[name] == "pallas":
                kernel = kernel_name(name)
                family = kernel.split(".")[0]
                by_kernel[kernel] = by_kernel.get(kernel, 0.0) + self_ns
                by_family[family] = by_family.get(family, 0.0) + self_ns
    async_coll = 0.0
    for plane in doc["planes"]:
        for ln in plane["lines"]:
            if ln["name"] == ASYNC_LINE:
                for name, _, dur in ln["events"]:
                    if name not in kinds:
                        kinds[name] = op_kind(name)
                    if kinds[name] == "collective":
                        async_coll += dur
    n = len(per_dev)
    busy_s = sum(busy) / n / 1e9
    window_s = (hi - lo) / 1e9
    # idle stretches of the first chip, by the instruction that ends each
    merged: Dict[str, float] = {}
    edge = lo
    for name, start, dur in sorted(per_dev[0][1], key=lambda e: e[1]):
        if start > edge:
            key = f"before {label(name)}"
            merged[key] = merged.get(key, 0.0) + (start - edge) / 1e9
        edge = max(edge, start + dur)
    if hi > edge:
        merged["before the end"] = (hi - edge) / 1e9
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "by_kind_s": {k: v / n / 1e9 for k, v in sorted(by_kind.items())},
        "by_kernel_s": {k: v / n / 1e9
                        for k, v in sorted(by_kernel.items())},
        "by_family_s": {k: v / n / 1e9
                        for k, v in sorted(by_family.items())},
        "async_collective_s": async_coll / n / 1e9,
        "device_ops": [[label(k), v / n / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            merged.items(), key=lambda kv: -kv[1])[:top]],
        "ops_seen": sum(len(ops) for _, ops in per_dev),
    }


def self_times(ops: List[List]) -> List[Tuple[str, float]]:
    """(name, self ns) per event of one line: duration minus what
    events nested inside it cover. Events on one XLA Ops line nest
    properly (a while's body inside the while)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[List] = []   # [end, index into out]
    for name, start, dur in evs:
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - start
        out.append([name, dur])
        stack.append([end, len(out) - 1])
    return [(n, max(s, 0.0)) for n, s in out]
