"""Model FLOP/s utilization: analytic forward + backward FLOPs per step
(perf/flops.py; padded positions count, recomputation does not) x
steps per second, over chips x the bf16 peak (perf/peaks.json)."""

from perf import harness


def read(run):
    w = run.window
    if not w.get("steps"):
        return None
    peak = harness.peaks_for(run.devices[0].device_kind)["bf16_flops_per_s"]
    rate = w["flops_per_step"] * w["steps"] / w["seconds"]
    return 100.0 * rate / (run.cell["chips"] * peak)
