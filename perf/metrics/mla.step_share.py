"""Share of the device's busy self time in the latent-attention blocks
(scopes ``*/blk*/attn/``: norm, the two low-rank projection pairs, the
rotation and assembly of the wide q and k, the attention call, the
output projection; forward and backward; the MTP module's block too)."""

from perf import mla_spans


def read(run):
    s = mla_spans.summary(run)
    return s and 100.0 * mla_spans.attn_ns(s) / s["busy_ns"]
