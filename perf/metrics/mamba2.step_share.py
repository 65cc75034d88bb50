"""Share of the device's busy self time in the Mamba-2 mixers (scopes
``*/blk*/mamba2/``: norm, in-projection, convolution, the chunked scan,
the gated norm, output projection; forward and backward)."""

from perf import mamba2_spans


def read(run):
    s = mamba2_spans.summary(run)
    return s and 100.0 * mamba2_spans.mamba2_ns(s) / s["busy_ns"]
