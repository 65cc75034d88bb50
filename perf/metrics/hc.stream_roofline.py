"""The stream passes' share of their roofline: the least time the chip
could take to move what one step's hyper-connections must move
(perf/flops_xing4.hc_stream_cost: (8n + 5) t d elements a sublayer, two
passes forward and two backward, each stream-sized tensor of a pass
once; the larger of that over the HBM peak and the FLOPs over the bf16
peak, which the bytes decide by far), over the device's self time a step
under the ``*/hc/`` scopes: the SCOPES' time, mix included, so that it
reads the same work whatever implements it and a kernel cannot shrink
the denominator by moving work out of itself.

A perfect implementation reads under 100: the measured time holds what
the count leaves out on purpose (the Sinkhorn iterations, the mixes'
own reads and writes, Phi and its gradient). A gradient of the streams
that XLA sums outside the scopes is not in the time either way."""

from perf import flops_xing4, harness, hc_spans


def read(run):
    w = run.window
    s = hc_spans.summary(run)
    if not s or not w.get("traced_steps") or "hc_mult" not in run.config:
        return None
    hc_s = hc_spans.hc_ns(s) / 1e9 / s["chips"]
    if not hc_s:
        return None
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = flops_xing4.hc_stream_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / hc_s
