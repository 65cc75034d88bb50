"""The indexer's share of its roofline: the least time the chip could
take for one step's index work (perf/flops_keye.dsa_index_cost: the
index products over the causal triangle once, the two products of their
backward pass and the attention's q . k^T for the loss's target over the
selected pairs; the larger of the FLOPs over the bf16 peak and the bytes
over the HBM peak), over the device's self time a step under the
``dsa/select`` and ``dsa/loss`` scopes: the SCOPES' time, so that it
reads the same work whatever implements it (XLA's ops or a ``dsa.*``
kernel) and a kernel cannot shrink the denominator by moving work out of
itself.

A perfect implementation reads under 100: the measured time holds what
the count leaves out on purpose (the top-k's counting passes, which are
no matmul; the loss's own forward products, which are recomputation;
the exp and log of the two softmaxes)."""

from perf import dsa_spans, flops_keye, harness


def read(run):
    w = run.window
    s = dsa_spans.summary(run)
    if not s or not w.get("traced_steps") or "sa_config" not in run.config:
        return None
    index_s = (dsa_spans.dsa_ns(s, "select") + dsa_spans.dsa_ns(s, "loss")
               ) / 1e9 / s["chips"]
    if not index_s:
        return None
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = flops_keye.dsa_index_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / index_s
