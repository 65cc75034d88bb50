"""Share of the device's busy self time under a hyper-connection scope
(``*/blk*/<attn|ffn|moe>/hc/``: every sublayer's mix with its Sinkhorn
iterations, the read and the write-back of the residual streams;
forward and backward)."""

from perf import hc_spans


def read(run):
    s = hc_spans.summary(run)
    return s and 100.0 * hc_spans.hc_ns(s) / s["busy_ns"]
