"""The attention kernels' share of their roofline: the least time the
chip could take for one step's attention calls, the larger of FLOPs
over the bf16 peak and bytes over the HBM peak (perf/flops.py, from
the per-chip shapes), over their measured device time per step: the
self time of the Mosaic calls named ``attn.*`` (perf/trace.py
``by_family_s``), no other family's kernels. At these shapes the FLOP
bound is the larger; the run's earlier line says which."""

from perf import harness


def read(run):
    t, w = run.trace, run.window
    if not t or not t["by_family_s"].get("attn") \
            or not w.get("traced_steps"):
        return None
    peaks = harness.peaks_for(run.devices[0].device_kind)
    cost = w["attention"]
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    measured = t["by_family_s"]["attn"] / w["traced_steps"]
    return 100.0 * least / measured if measured > 0 else None
