"""Of the latent-attention blocks' time (mla.step_share.train), the
share under the ``rope`` scope: the splits, the rotation, the shared key
head's copies and the assembly of the wide q and k. Data movement that
a kernel reading the latent parts in place would not do."""

from perf import mla_spans


def read(run):
    s = mla_spans.summary(run)
    return s and 100.0 * mla_spans.attn_ns(s, "rope") / mla_spans.attn_ns(s)
