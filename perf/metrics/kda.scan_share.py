"""Of the Kimi Delta Attention mixers' time (kda.step_share.train), the
share under the ``rule`` scope: the gates, the ``kda.rule.*`` kernels
(or the chunked XLA form) and whatever XLA puts around them."""

from perf import kda_spans


def read(run):
    s = kda_spans.summary(run)
    return s and 100.0 * kda_spans.kda_ns(s, "rule") / kda_spans.kda_ns(s)
