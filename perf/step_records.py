"""The measured window's time-line, from the program's own step
records. With telemetry on (a traced run) the executor logs one record
a call into ``monitor``'s ring, with the ``time.perf_counter`` at the
call's entry (``t0``, the clock of ``harness.Run.t_window``), the
call's ``wall_ms`` and the collector's pauses since the record before
(``gc_ms``). The loop keeps ``IN_FLIGHT`` steps in flight, so in steady
state the distance between two ``t0`` is the device's step, and one
longer than its neighbours is a call that came late: lost time, unless
the calls behind it follow sooner by as much (the device had a step in
hand and never waited).

``for_run`` sorts the ring by ``t0`` alone into the window's records,
the traced stretch's and the rest; ``reduce`` is a pure function of a
list of records and three numbers. A program whose records carry no
``t0``, an untraced run (no record at all) and a ring that wrapped
inside the window give None, and every reader then returns None."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from perf import harness
from perf.kinds.train import IN_FLIGHT

# a distance counts as late from this multiple of the window's median
LATE = 1.25
# fewer distances than this and a tenth of the window is no stretch
MIN_DISTANCES = 8


def distances(records: List[Dict]) -> List[float]:
    """Seconds between one record's ``t0`` and the next one's, less the
    stretch's first ``IN_FLIGHT``: the loop waits for nothing yet."""
    t = [r["t0"] for r in records]
    return [b - a for a, b in zip(t, t[1:])][IN_FLIGHT:]


def reduce(records: List[Dict], t_window: float, seconds: float,
           traced_steps: int) -> Optional[Dict]:
    """The window's and the traced stretch's numbers from step records
    (oldest first). The window's are the ``step`` / ``window`` records
    with ``t_window <= t0 < t_window + seconds``, the traced stretch's
    the ``traced_steps`` that follow; a record the phase plane marked
    (``sampled``: the serialised probe behind the trace) is in neither,
    nor one of set-up. None where no record carries ``t0``, the oldest
    that does began inside the window (the ring wrapped) or none lies in
    it."""
    recs = [r for r in records if "t0" in r
            and r.get("kind") in ("step", "window")]
    if not recs or recs[0]["t0"] >= t_window:
        return None
    steady = [r for r in recs if "sampled" not in r]
    end = t_window + seconds
    window = [r for r in steady if t_window <= r["t0"] < end]
    traced = [r for r in steady if r["t0"] >= end][:traced_steps]
    if not window:
        return None
    walls = sorted(r["wall_ms"] for r in window)
    out = {"window": len(window), "traced": len(traced),
           "gc_ms": sum(r.get("gc_ms", 0.0) for r in window),
           "run_ms": statistics.fmean(walls),
           "run_median_ms": statistics.median(walls),
           "run_max_ms": walls[-1],
           "step_s": None, "median_s": None, "late": [],
           "late_share": None, "drift": None, "trace_ratio": None}
    d = distances(window)
    if not d:
        return out
    out["step_s"] = statistics.fmean(d)
    out["median_s"] = med = statistics.median(d)
    # window[at] is the record behind a late distance (at the window's
    # end, its length). What of the distance was LOST: with steps in
    # flight a host that is late by less than they last leaves the
    # device busy, and the next calls follow sooner (their wait is over
    # already); what the IN_FLIGHT distances behind a late one are short
    # of the median was made up, the rest is lost. Beside it, of the
    # calls either side, their own ms and the collector's pauses charged
    # to their records: a pause inside the call in front is in ITS
    # record, one in the wait in the record behind.
    for k, x in enumerate(d):
        if x <= LATE * med:
            continue
        at = k + IN_FLIGHT + 1
        behind = d[k + 1:k + 1 + IN_FLIGHT]
        made_up = sum(max(0.0, med - y) for y in behind)
        out["late"].append({
            "at": at, "ms": x * 1e3, "next_ms": [y * 1e3 for y in behind],
            "lost_ms": max(0.0, x - med - made_up) * 1e3,
            "gc_ms": [r.get("gc_ms", 0.0) for r in window[at - 1:at + 1]],
            "call_ms": [r["wall_ms"] for r in window[at - 1:at + 1]]})
    # the window's end is a distance too: the last call, then the wait
    # for the IN_FLIGHT steps still in flight, and the clock stops
    tail = end - window[-1]["t0"]
    usual = out["run_median_ms"] / 1e3 + IN_FLIGHT * med
    if tail > LATE * usual:
        out["late"].append({
            "at": len(window), "ms": tail * 1e3, "next_ms": [],
            "lost_ms": (tail - usual) * 1e3,
            "gc_ms": [r.get("gc_ms", 0.0) for r in [window[-1]] + traced[:1]],
            "call_ms": [window[-1]["wall_ms"]]})
    if len(d) < MIN_DISTANCES:
        return out
    out["late_share"] = 100.0 * sum(
        late["lost_ms"] for late in out["late"]) / (seconds * 1e3)
    tenth = max(2, len(d) // 10)
    out["drift"] = statistics.fmean(d[-tenth:]) / statistics.fmean(d[:tenth])
    dt = distances(traced)
    if dt:
        out["trace_ratio"] = statistics.fmean(dt) / out["step_s"]
    return out


def for_run(run) -> Optional[Dict]:
    """``reduce`` of the ring as this run left it, once per run (the
    first reader that asks also prints the report)."""
    if not hasattr(run, "_step_records"):
        from paddle_tpu import monitor

        recs, s = monitor.recent_steps(), None
        if recs and run.t_window is not None:
            s = reduce(recs, run.t_window, run.window.get("seconds", 0.0),
                       run.window.get("traced_steps", 0))
        run._step_records = s
        if s:
            report(s, run.window.get("steps"))
        elif any("t0" in r for r in recs):
            harness.say(
                f"perf: step records: no time-line: the ring's "
                f"{len(recs)} records do not hold the whole window (it "
                f"wrapped)")
    return run._step_records


def report(s: Dict, steps):
    """What no single number holds, into the run's log."""
    def r3(x, per=1.0):
        if isinstance(x, list):
            return [r3(v) for v in x]
        return x if x is None or isinstance(x, int) else round(x / per, 3)

    harness.say(
        f"perf: step records: window {s['window']} (the loop counted "
        f"{steps}), traced stretch {s['traced']}; ms in a call: mean "
        f"{r3(s['run_ms'])}, median {r3(s['run_median_ms'])}, longest "
        f"{r3(s['run_max_ms'])}; ms between calls: mean "
        f"{r3(s['step_s'], 1e-3)}, median {r3(s['median_s'], 1e-3)}; late "
        f"(over {LATE} x the median, the first 12 of {len(s['late'])}) "
        f"{[{k: r3(v) for k, v in late.items()} for late in s['late'][:12]]}")
