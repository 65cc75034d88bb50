"""Of the state-space mixers' time (the ``*/blk*/ssm/`` scopes), the
share under the selective scan's scope, ``sscan``: the recurrence
itself, which is everything there that is neither a projection matmul,
the convolution nor a norm."""

from perf import ssm_spans


def read(run):
    s = ssm_spans.summary(run)
    if not s:
        return None
    return 100.0 * ssm_spans.ssm_ns(s, ssm_spans.SCAN) / ssm_spans.ssm_ns(s)
