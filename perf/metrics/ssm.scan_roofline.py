"""The selective-scan kernels' share of their roofline: the least time
the chip could take for one step's scan calls, their bytes over the HBM
peak (perf/flops_phi4flash.ssm_scan_cost: x, dt, z, y and their
gradients once each at the stream's width, B and C, the saved states;
the scan has no matmul, so no FLOPs bound it), over the ``ssm.*``
kernels' self time a step in the device trace.

A perfect implementation reads under 100: the count leaves out the
backward pass's second reading of its inputs, and the kernels are bound
by the VPU and the EUP (16 exps and some 100 vector operations a
position and 1024 channels), not by HBM: the reading says how far the
recurrence is from a pure stream."""

from perf import flops_phi4flash, harness, ssm_spans


def read(run):
    w = run.window
    kernel_s = ssm_spans.kernel_s(run)
    rows = {int(lb["chunk"]) for lb, _ in ssm_spans.dispatch_rows()
            if lb.get("impl") == "kernel"}
    if not kernel_s or not w.get("traced_steps") or len(rows) != 1:
        return None
    traffic = run.cell["traffic"]
    cost = flops_phi4flash.ssm_scan_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"], rows.pop())
    peaks = harness.peaks_for(run.devices[0].device_kind)
    least = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least * w["traced_steps"] / kernel_s
