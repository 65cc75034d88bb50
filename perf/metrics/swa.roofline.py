"""The windowed attention calls' share of their roofline: the least
time the chip could take for one step's sliding-window calls, the larger
of FLOPs over the bf16 peak and bytes over the HBM peak
(perf/flops_smallthinker.swa_cost: the BAND counted by elements, forward
+ 2 x backward, against q, k, v, o and their gradients moved once), over
the device's self time a step under the ``*/blk*/attn/swa/`` scopes.

A perfect kernel reads under 100: it computes whole blocks (252 of 512
x 512 a head at 16,384 positions and a window of 4096: 66.1M pairs for
the band's 58.7M), the backward pass computes q.k^T a second and third
time (recomputation, not counted), and the softmax's exp, max and sum
run on the VPU beside the MXU the peak is quoted for."""

from perf import flops_smallthinker, harness, swa_spans


def read(run):
    w = run.window
    s = swa_spans.summary(run)
    if not s or not w.get("traced_steps"):
        return None
    swa_s = swa_spans.swa_ns(s) / 1e9 / s["chips"]
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = flops_smallthinker.swa_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / swa_s
