"""Of the indexer's time (``dsa.step_share.train``'s scopes), the share
of the TOP-K: the self time under ``dsa/select`` (the op ``dsa_select``:
a chunk's index scores and their top-k, one op because the scores are
never held whole) less the ``dsa.score.fwd`` kernel's, which leaves the
threshold's bisection, the mask, the live table and the logsumexp of
the chosen scores. Where no kernel made the scores (XLA's ops a tile)
their time cannot be told from the top-k's and is in this share. What is
left of the indexer's time is the scores, the projections and the KL
loss with its gradient."""

from perf import dsa_spans


def read(run):
    s = dsa_spans.summary(run)
    if not s:
        return None
    top_k = dsa_spans.dsa_ns(s, "select") - (s.get("kernel_ns") or {}).get(
        "dsa.score.fwd", 0.0)
    return 100.0 * top_k / dsa_spans.dsa_ns(s)
