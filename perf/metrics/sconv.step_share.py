"""Share of the device's busy self time in the gated short-convolution
mixers (scopes ``*/blk*/sconv/``: the pre-norm, the in-projection, the
gated convolution's one op, the out-projection; forward and backward)."""

from perf import sconv_spans


def read(run):
    s = sconv_spans.summary(run)
    return s and 100.0 * sconv_spans.sconv_ns(s) / s["busy_ns"]
