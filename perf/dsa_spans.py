"""What the sparse-attention indexer's metrics share: the time of a
lightning indexer in a traced run, from the program's scopes
(perf/spans.py: ``*/blk<i>/attn/dsa/`` with ``proj`` (the indexer's
projections, LayerNorm and rotation), ``select`` (the op ``dsa_select``:
the index scores a chunk of queries, kernel ``dsa.score.fwd``, AND their
top-k, one op, since the scores are never held whole) and ``loss`` (``dsa_index_loss``: the KL
loss with its gradient) under it, forward and backward), and the rows of
the program's ``pt_dsa_dispatch_total`` counter and of
``pt_attention_dispatch_total`` that carry ``sel``. A program without
such a scope, counter or label (any tree before the ops existed, any
other family) has nothing to read: every function here then returns
None or nothing, and raises nothing."""

from perf import harness, moe_spans, spans

COUNTER = "pt_dsa_dispatch_total"
ATTENTION = "pt_attention_dispatch_total"


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/attn/dsa`` scope, else
    None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not dsa_ns(s):
        return None
    return s


def dsa_ns(s, *part):
    """Busy self time under ``*/blk*/attn/dsa/<part>`` (all of dsa
    without one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("attn", "dsa", *part))


def dispatch_rows():
    """[(labels, calls)] of the indexer's calls this process lowered
    with telemetry on (traced runs); [] where the program has no such
    counter or call."""
    return harness.counter_rows(COUNTER)


def selected_rows():
    """[(labels, calls)] of the attention calls under a selection this
    process lowered with telemetry on; [] where the program has no such
    label or lowered no such call."""
    return [(labels, n) for labels, n in harness.counter_rows(ATTENTION)
            if labels.get("sel")]
