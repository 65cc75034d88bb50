"""The delta-rule calls' share of their roofline: the least time the
chip could take for one step's gated delta-rule calls, the larger of
FLOPs over the bf16 peak and bytes over the HBM peak
(perf/flops_qwen3next.gdn_scan_cost: the chunkwise form's matmul FLOPs
at the chunk the program's dispatch counter reports, forward + 2 x
backward, against q, k, v, g, beta, o and their gradients moved once),
over the device's self time a step under the ``*/blk*/gdn/rule/``
scopes (where a later program names kernels ``gdn.*`` they sit under
that scope too).

A perfect implementation reads under 100: the measured time holds what
the count leaves out on purpose: the backward pass's one recomputation
of the per-chunk quantities, the float32 triangular solve (counted as
half a bf16 product), every exp, running sum and mask over a chunk, the
gates, and the scan's small matmuls ([64 x 128] x [128 x 128] a head a
step), which cannot fill the MXU the peak is quoted for."""

from perf import flops_qwen3next, gdn_spans, harness


def read(run):
    w = run.window
    s = gdn_spans.summary(run)
    chunks = {int(lb["chunk"]) for lb, _ in gdn_spans.dispatch_rows()
              if lb.get("impl") != "recurrent"}
    if not s or not w.get("traced_steps") or len(chunks) != 1:
        return None
    scan_s = gdn_spans.gdn_ns(s, "rule") / 1e9 / s["chips"]
    if not scan_s:
        return None
    peaks = harness.peaks_for(run.devices[0].device_kind)
    traffic = run.cell["traffic"]
    cost = flops_qwen3next.gdn_scan_cost(
        run.config, traffic["batch"] // run.cell["chips"],
        traffic["seq_len"], chunks.pop())
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * w["traced_steps"] / scan_s
