"""Share of the device's busy self time in the Gated DeltaNet (linear
attention) layers' token mixers (scopes ``*/blk*/gdn/``: norm,
projections, convolution, the delta rule, gated norm, output
projection; forward and backward)."""

from perf import gdn_spans


def read(run):
    s = gdn_spans.summary(run)
    return s and 100.0 * gdn_spans.gdn_ns(s) / s["busy_ns"]
