"""What the gated-convolution metrics share: the time of the short
convolution mixers in a traced run, from the program's scopes
(perf/spans.py: ``*/blk<i>/sconv/`` with ``in_proj``, ``gconv`` and
``out_proj`` under it), the ``sconv.*`` Mosaic kernels' self time
(perf/trace.py, by family) and the rows of the program's
``pt_causal_conv_dispatch_total`` counter that carry the label
``gated``. A program without such a scope, kernel or label (any tree
before the op existed, any other family) has nothing to read: every
function here then returns None or nothing, and raises nothing."""

from perf import moe_spans, spans

COUNTER = "pt_causal_conv_dispatch_total"
FAMILY = "sconv"
# the gated convolution's one op: what of a mixer is not a projection
GATE = "gconv"


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/sconv`` scope, else
    None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not sconv_ns(s):
        return None
    return s


def sconv_ns(s, *part):
    """Busy self time under ``*/blk*/sconv/<part>`` (all of the mixer
    without one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("sconv", *part))


def kernel_s(run):
    """Self seconds a chip of the ``sconv.*`` Mosaic kernels in the
    traced stretch; 0.0 where the trace holds none."""
    return ((run.trace or {}).get("by_family_s") or {}).get(FAMILY, 0.0)


def gated_rows():
    """[(labels, calls)] of the gated-convolution calls this process
    lowered with telemetry on (traced runs): the counter's rows that
    carry ``gated``; [] where the program has no such counter, no such
    label or counted nothing."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(COUNTER, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows
            if r["value"] and r["labels"].get("gated")]
