"""The gated-convolution kernels' share of their roofline: the least
time the chip could take for one step's calls, their bytes over the HBM
peak (perf/flops_lfm2moe.sconv_cost: [B | C | u] and y once forward, [B
| C | u], dy and d[B | C | u] once backward, 11 t c elements a layer;
the op has no matmul, so no FLOPs bound it), over the ``sconv.*``
kernels' self time a step in the device trace.

An UPPER BOUND, not a share that ends at 100. The count is what the
mathematics needs (nothing is recomputed from HBM, the halo rows, 16 of
1024, are the only bytes read twice), but it holds every operand to the
HBM peak, and XLA does not keep every operand there: in
``lfm2moe-train-s8192``'s compiled step all eight calls get [B | C | u]
in memory space 1, the chip's 128 MiB beside the core (the forward from
the projection that wrote it there, the backward through an async copy
in front of the call), so 6 of the 11 t c cross no HBM inside the
kernels and the forward alone reads above the peak (PERF.md section 7
(26)). The ``benchmark`` PR that makes this an entry has to count by
placement."""

from perf import flops_lfm2moe, harness, sconv_spans


def read(run):
    w = run.window
    kernel_s = sconv_spans.kernel_s(run)
    if not kernel_s or not w.get("traced_steps"):
        return None
    traffic = run.cell["traffic"]
    cost = flops_lfm2moe.sconv_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"])
    peaks = harness.peaks_for(run.devices[0].device_kind)
    least = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least * w["traced_steps"] / kernel_s
