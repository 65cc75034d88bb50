"""Share of the device's busy self time under a sparse-attention
indexer's scopes (``*/blk*/attn/dsa/``: the indexer's projections, the
index scores and their top-k, the KL loss with its gradient; forward and
backward). The attention that reads the selection is not in it
(``attn.time_share.train``)."""

from perf import dsa_spans


def read(run):
    s = dsa_spans.summary(run)
    return s and 100.0 * dsa_spans.dsa_ns(s) / s["busy_ns"]
