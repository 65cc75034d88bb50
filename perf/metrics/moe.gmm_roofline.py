"""The experts' grouped matmuls' share of their roofline: the least
time the chip could take for one step's nine grouped matmuls a block,
the larger of FLOPs over the bf16 peak and bytes over the HBM peak
(perf/flops_olmoe.moe_gmm_cost, from the per-chip shapes), over the
self time a step of the Mosaic calls that do them (perf/moe_spans.py:
family ``moe`` if the program names its own kernel, else XLA's
``ragged-dot-none``; nothing else is summed). At the published widths
the FLOP bound is the larger."""

from perf import flops_olmoe, harness, moe_spans


def read(run):
    w, cfg = run.window, run.config
    if "num_experts" not in cfg or not w.get("traced_steps") \
            or not moe_spans.gmm_family(run):
        return None
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = flops_olmoe.moe_gmm_cost(
        cfg, traffic["batch"] // run.cell["chips"], traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / moe_spans.gmm_s(run)
