"""The KDA rule's share of its roofline: the least time the chip could
take for one step's delta-rule calls with a decay a key feature, the
larger of FLOPs over the bf16 peak and bytes over the HBM peak
(perf/flops_kimilinear.kda_scan_cost: the chunkwise form's matmul FLOPs
at the chunk the program's dispatch counter reports, forward + 2 x
backward, against q, k, v, o, g [t, H, dk], beta and their gradients
moved once), over the device's self time a step under the
``*/blk*/kda/rule/`` scopes: the SCOPE's time, as
gdn.scan_roofline.train reads, so that it reads the same work whatever
implements it and a kernel cannot shrink the denominator by moving work
out of itself.

A perfect implementation reads under 100: the measured time holds what
the count leaves out on purpose: the backward pass's one recomputation,
the float32 inversion (counted as half a bf16 product), every exp,
running sum and mask, the halving's six products a triangle where the
count has one, the gates."""

from perf import flops_kimilinear, harness, kda_spans


def read(run):
    w = run.window
    s = kda_spans.summary(run)
    chunks = {int(lb["chunk"]) for lb, _ in kda_spans.dispatch_rows()
              if lb.get("impl") != "recurrent"}
    if not s or not w.get("traced_steps") or len(chunks) != 1:
        return None
    scan_s = kda_spans.kda_ns(s, "rule") / 1e9 / s["chips"]
    if not scan_s:
        return None
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = flops_kimilinear.kda_scan_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"], chunks.pop())
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / scan_s
