"""The Mamba-2 scan kernels' share of their roofline: the least time the
chip could take for one step's scan calls, the greater of their FLOPs
over the bf16 peak and their bytes over the HBM peak
(perf/flops_nemotronh.mamba2_scan_cost, at the chunk the dispatch
counter reports: x, y, B, C and their gradients once each, the saved
states written and read once), over the ``mamba2.*`` kernels' self time
a step in the device trace.

A perfect implementation reads under 100: the count leaves out the
backward pass's second reading of its inputs and the chunk it makes
again, and a head of 64 fills half the MXU's columns."""

from perf import flops_nemotronh, harness, mamba2_spans


def read(run):
    w = run.window
    kernel_s = mamba2_spans.kernel_s(run)
    chunks = {int(lb["chunk"]) for lb, _ in mamba2_spans.dispatch_rows()
              if lb.get("impl") == "kernel"}
    if not kernel_s or not w.get("traced_steps") or len(chunks) != 1:
        return None
    traffic = run.cell["traffic"]
    cost = flops_nemotronh.mamba2_scan_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"], chunks.pop())
    peaks = harness.peaks_for(run.devices[0].device_kind)
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / kernel_s
