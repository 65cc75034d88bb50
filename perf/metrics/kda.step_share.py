"""Share of the device's busy self time in the Kimi Delta Attention
layers' token mixers (scopes ``*/blk*/kda/``: norm, projections,
convolutions, the delta rule with its gates, gated norm, output
projection; forward and backward)."""

from perf import kda_spans


def read(run):
    s = kda_spans.summary(run)
    return s and 100.0 * kda_spans.kda_ns(s) / s["busy_ns"]
