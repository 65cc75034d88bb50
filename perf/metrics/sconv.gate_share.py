"""Of the gated short-convolution mixers' time (the ``*/blk*/sconv/``
scopes), the share under ``gconv``: the gated convolution's op and its
grad op, which is everything there that is neither a projection matmul
nor the mixer's pre-norm."""

from perf import sconv_spans


def read(run):
    s = sconv_spans.summary(run)
    if not s:
        return None
    return 100.0 * sconv_spans.sconv_ns(s, sconv_spans.GATE) \
        / sconv_spans.sconv_ns(s)
