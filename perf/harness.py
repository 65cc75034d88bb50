"""What every kind of run shares: the run record, the device check, the
compile watch, the program's counters, the device trace and the result
line. From the program it takes only the system under test and its
counters; clocks, percentiles, peaks and the trace reduction are the
benchmark's own."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# everything a run writes goes under the checkout's .cache/ (ignored by
# git): jax's persistent cache (paddle_tpu.jax_cache) and the traces
TRACE_ROOT = os.path.join(ROOT, ".cache", "perf_trace")


def say(msg: str):
    print(msg, flush=True)


def say_first_calls(run: "Run"):
    say(f"perf: first calls (s) "
        f"{ {k: round(v, 2) for k, v in run.first_calls.items()} }")


def say_trace(run: "Run", what: str):
    keys = ("devices", "window_s", "busy_s", "idle_share", "by_kind_s",
            "by_kernel_s", "ops_seen")
    say(f"perf: traced {what}: "
        f"{run.trace and {k: run.trace[k] for k in keys}}")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


class Run:
    """One run's record: what the kind's loop observed, for the metric
    readers (perf/metrics/*.py) to reduce."""

    def __init__(self, bench: Dict, cell: Dict, config: Dict, seed: int,
                 seconds: float, traced: bool, t_start: float):
        self.bench, self.cell, self.config = bench, cell, config
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.t_start = t_start          # perf_counter at process start
        self.t_window: Optional[float] = None   # ... at the window's start
        self.devices: List = []
        self.first_calls: Dict[str, float] = {}   # program -> seconds
        self.window: Dict[str, Any] = {}    # the kind's observations
        self.counters: Dict[str, Dict] = {}  # snapshots, by name
        self.trace: Optional[Dict] = None   # perf.trace.reduce(...)
        self.check: Dict[str, Any] = {}     # the correctness sample
        self.attempted = 0
        self.failed = 0
        self.problems: List[str] = []    # why correct is False
        self.compiles_in_window = 0

    @property
    def correct(self) -> bool:
        return not self.problems

    def problem(self, msg: str):
        say(f"PROBLEM: {msg}")
        self.problems.append(msg)

    def setup_done(self):
        """The instant set-up ends and the measured window begins."""
        self.t_window = time.perf_counter()

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_start


def require_tpu(chips: int):
    """The devices to run on, or exit: the benchmark measures a TPU and
    nothing else; no flag, argument or variable lets it run elsewhere."""
    try:
        import jax

        devs = jax.devices()
    except Exception as e:  # no backend at all
        print(f"perf: jax found no device: {e}", file=sys.stderr)
        sys.exit(2)
    if devs[0].platform != "tpu":
        print(f"perf: needs a TPU, jax found platform "
              f"'{devs[0].platform}' ({devs}); a number from another "
              f"device is not a measurement of this system",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"perf: the cell asks for {chips} chips, jax found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = load_json("perf", "peaks.json")
    if device_kind not in table:
        raise KeyError(f"perf/peaks.json has no row for device kind "
                       f"'{device_kind}': add it with its source")
    return table[device_kind]


def memory_stats_line(devices) -> str:
    """What the backend says about each device's memory, whole."""
    return "; ".join(f"{d.id}: {d.memory_stats()}" for d in devices)


def peak_memory_bytes(devices) -> Optional[int]:
    """Peak bytes held on the fullest of ``devices``.

    The v5e's runtime books what a program holds while it runs (XLA's
    temporaries) under ``bytes_reserved`` and only arrays under
    ``bytes_in_use``: after tbase-train's window ``peak_bytes_in_use``
    is 0.96 GB (state and feeds) and ``peak_bytes_reserved`` 9.92 GB,
    against 10.73 GB that memory_analysis() gives the step compiled for
    this chip (PERF.md, PR 23). So the peak is the sum of the two
    high-water marks: what the largest program reserved on top of the
    arrays alive around it."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if st.get("peak_bytes_in_use"):
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


class CompileWatch:
    """Counts XLA backend compiles (jax.monitoring), the benchmark's own
    check that nothing compiles inside a measured window, whatever the
    program's telemetry says. jax logs the event around "compile or
    fetch from the persistent cache", so a cache hit counts too: it is
    work that belongs to set-up all the same."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


def program_counters() -> Dict[str, Any]:
    """Snapshot of the program's counters the readers use (they count
    only while the telemetry flag is on: traced runs)."""
    from paddle_tpu import monitor
    from paddle_tpu.ops import attention_ops

    phases = monitor.histogram("pt_step_phase_seconds")
    return {
        "attention_dispatch": dict(attention_ops.dispatch_counts()),
        "phase_sum_s": {p: phases.sum(labels={"phase": p})
                        for p in ("feed", "dispatch", "device", "fetch")},
        "phase_count": phases.count(labels={"phase": "dispatch"}),
    }


def counter_rows(name: str) -> List[Tuple[Dict[str, str], int]]:
    """[(labels, count)] of the rows of one of the program's counters
    that counted anything in this process; [] where the program has no
    such counter (any tree before it) or it counted nothing. The
    ``*_dispatch_total`` counters count at lowering with telemetry on,
    that is in traced runs."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(name, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows if r["value"]]


def telemetry(on: bool, phases_every_n: int = 0,
              request_trace: bool = False):
    """The program's telemetry flags. Untraced runs keep them off (the
    default a user runs with). Traced runs turn the counters on and
    leave the step phases off through the window and the traced
    stretch: a call whose phases are sampled waits for the device, so
    a run that samples them measures its own probe. The phases are
    sampled (``phases_every_n`` > 0: every n-th executor call) only in
    a stretch of their own (``PhaseProbe``). The serving correctness
    sample also needs the request trace, which carries each decode
    step's logit. ``telemetry(False)`` is the flags' defaults: a run
    ends with it."""
    from paddle_tpu import flags

    flags.set_flags({
        "telemetry": bool(on),
        "step_phases": phases_every_n > 0 or not on,
        "step_phases_every_n": int(phases_every_n) or 16,
        "trace_dir": (os.path.join(TRACE_ROOT, "requests")
                      if request_trace else "")})


class PhaseProbe:
    """A stretch of steady work after the window and the trace in which
    EVERY executor call's host phases are timed (each call then waits
    for the device: the stretch is serialized, which is why nothing
    else is measured in it). exec.host_ms_per_step reads the counters
    it leaves in ``run.counters``."""

    def __init__(self, run: Run):
        self.run = run

    def __enter__(self):
        telemetry(True, phases_every_n=1)
        self.run.counters["phases_before"] = program_counters()
        return self

    def __exit__(self, *exc):
        self.run.counters["phases_after"] = program_counters()
        telemetry(True)
        return False


class DeviceTrace:
    """jax.profiler around a stretch of steady work, into a fixed
    directory of the checkout, reduced by perf.trace when it closes."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = os.path.join(TRACE_ROOT, run.cell["name"])

    def __enter__(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        from perf import trace

        host_s = time.perf_counter() - self.t0
        jax.profiler.stop_trace()
        if exc[0] is not None:
            return False
        path = trace.find_xplane(self.dir)
        summary = trace.reduce(trace.load(path))
        if summary is not None:
            summary["host_window_s"] = host_s
            summary["xplane_bytes"] = os.path.getsize(path)
        self.run.trace = summary
        keep = os.environ.get("PERF_KEEP_TRACE")
        if keep:  # bring a raw trace back from the chip, to read by hand
            import gzip

            os.makedirs(keep, exist_ok=True)
            with open(path, "rb") as src, gzip.open(os.path.join(
                    keep, f"{self.run.cell['name']}.xplane.pb.gz"),
                    "wb") as dst:
                shutil.copyfileobj(src, dst)
        return False


def cell_metrics(bench: Dict, cell_name: str, group: str) -> List[Dict]:
    """The metrics of ``group`` (end_to_end | per_layer) that
    BENCHMARK.json says this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader_for(metric: str):
    """perf/metrics/<metric>.py, else the file of the metric's stem (the
    name without its last dotted part: ``device.idle_share.train`` and
    ``device.idle_share.serve`` share ``device.idle_share.py``)."""
    import importlib.util

    for stem in (metric, metric.rsplit(".", 1)[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.exists(path):
            # loaded by file: a metric's name carries dots
            spec = importlib.util.spec_from_file_location(
                "perf_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader perf/metrics/{metric}.py (or its "
                            f"stem's) for metric '{metric}'")


def result_line(run: Run) -> Dict:
    """The contract's last line: end-to-end metrics of an untraced run,
    per-layer metrics of a traced one. A reader that finds nothing to
    read returns None and its metric is left out."""
    group = "per_layer" if run.traced else "end_to_end"
    metrics = {}
    for m in cell_metrics(run.bench, run.cell["name"], group):
        value = reader_for(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": peak_memory_bytes(
                  run.devices[:run.cell["chips"]])}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if run.traced and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["problems"] = run.problems
    return line
