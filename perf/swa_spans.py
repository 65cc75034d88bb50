"""What the sliding-window attention metrics share: the time of the
windowed attention calls in a traced run, from the program's scopes
(perf/spans.py: ``*/blk<i>/attn/swa/``, the sdpa op of a window layer,
forward and backward; a global layer's sits under ``.../attn/core/``),
and the rows of ``pt_attention_dispatch_total`` that carry a ``band``.
A program without such a scope or label (any tree before the window
existed, any other family) has nothing to read: every function here
then returns None or nothing, and raises nothing."""

from perf import moe_spans, spans

COUNTER = "pt_attention_dispatch_total"


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/attn/swa`` scope, else
    None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not swa_ns(s):
        return None
    return s


def swa_ns(s):
    """Busy self time under ``*/blk*/attn/swa/``, forward and backward,
    ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("attn", "swa"))


def windowed_rows():
    """[(labels, calls)] of the windowed attention calls this process
    lowered with telemetry on (traced runs); [] where the program has no
    such label or lowered no such call."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(COUNTER, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows
            if r["value"] and r["labels"].get("band")]
