"""Of the Mamba-2 mixers' time (the ``*/blk*/mamba2/`` scopes), the
share under ``conv``, ``chunks`` and ``gate_norm``: the convolution, the
chunked scan and the gated norm behind it, which is everything there
that is neither a projection matmul nor the block's pre-norm."""

from perf import mamba2_spans


def read(run):
    s = mamba2_spans.summary(run)
    if not s:
        return None
    return 100.0 * sum(mamba2_spans.mamba2_ns(s, part)
                       for part in mamba2_spans.SCAN_PARTS) \
        / mamba2_spans.mamba2_ns(s)
