"""The Mamba-2 scan kernels' share of their roofline where ONE group's
heads are walked in head blocks (Granite-4.0-H): the least time the chip
could take for the scans one step NEEDS, the greater of their FLOPs over
the bf16 peak and their bytes over the HBM peak
(perf/flops_granitehybrid.mamba2_scan_cost, at the chunk the dispatch
counter reports: forward + backward ONCE, ``C B^T`` once a group), over
the ``mamba2.*`` kernels' self time a step in the device trace.

The kernels' time holds everything they do: a recomputed segment's
second forward and a head block's own ``C B^T`` are not needed work and
lower the share, as does the backward pass's chunk made again."""

from perf import flops_granitehybrid, harness, mamba2_spans


def read(run):
    w = run.window
    kernel_s = mamba2_spans.kernel_s(run)
    chunks = {int(lb["chunk"]) for lb, _ in mamba2_spans.dispatch_rows()
              if lb.get("impl") == "kernel"}
    if (not kernel_s or not w.get("traced_steps") or len(chunks) != 1
            or "mamba_n_groups" not in run.config):
        return None
    traffic = run.cell["traffic"]
    cost = flops_granitehybrid.mamba2_scan_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"], chunks.pop())
    peaks = harness.peaks_for(run.devices[0].device_kind)
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / kernel_s
