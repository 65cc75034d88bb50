"""What the gated delta-rule metrics share: the time of the linear
attention layers in a traced run, from the program's scopes
(perf/spans.py: ``*/blk<i>/gdn/`` with ``proj``, ``conv``, ``rule``,
``gate_norm`` and ``out`` under it), and the rows of the program's
``pt_linear_attention_dispatch_total`` counter. A program without such
a scope or counter (any tree before the layer existed) has nothing to
read: every function here then returns None or nothing, and raises
nothing."""

from perf import moe_spans, spans

COUNTER = "pt_linear_attention_dispatch_total"
# what is not a projection matmul: the convolution, the rule itself
# (with its gates) and the gated norm behind it
NOT_PROJECTION = ("conv", "rule", "gate_norm")


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/gdn`` scope, else None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] \
            or not spans.scope_ns(s, moe_spans.under("gdn")):
        return None
    return s


def gdn_ns(s, *part):
    """Busy self time under ``*/blk*/gdn/<part>`` (all of gdn without
    one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("gdn", *part))


def dispatch_rows():
    """[(labels, calls)] of the delta-rule calls this process lowered
    with telemetry on (traced runs); [] where the program has no such
    counter or counted nothing."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(COUNTER, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows if r["value"]]
