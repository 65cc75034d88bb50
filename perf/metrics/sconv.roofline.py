"""The gated-convolution kernels' share of their roofline: the least
time the chip could take for one step's calls, the bytes that cross HBM
inside them over the HBM peak (perf/flops_lfm2moe.sconv_cost's
``hbm_bytes``: y once forward, dy and d[B | C | u] once backward, 5 t c
elements a layer; the op has no matmul, so no FLOPs bound it), over the
``sconv.*`` kernels' self time a step in the device trace.

Counted by placement. The mathematics moves 11 t c elements a layer,
but XLA does not keep every operand in HBM: in
``lfm2moe-train-s8192``'s compiled step all eight calls get [B | C | u]
in memory space 1, the chip's 128 MiB beside the core (the forward from
the projection that wrote it there, the backward through an async copy
in front of the call, whose time is not the kernel's), so 6 of the 11
t c cross no HBM inside the kernels, and a count of all 11 against the
HBM peak read 99.1 with a forward half above the peak (PERF.md section
6, PR 54). What is counted here is in HBM in every placement XLA has
chosen. What is NOT counted: [B | C | u] read forward and backward (6 t
c), the halo rows (16 of 1024) and the filter. So the reading can only
under-read a placement that keeps the projection in HBM, it ends at 100
for a reason that is the kernels' and never passes it for one that is
the compiler's; about 45 on the chip."""

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
    least = cost["hbm_bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least * w["traced_steps"] / kernel_s
