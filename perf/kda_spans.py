"""What the Kimi Delta Attention metrics share: the time of the KDA
layers in a traced run, from the program's scopes (perf/spans.py:
``*/blk<i>/kda/`` with ``proj``, ``conv``, ``rule``, ``gate_norm`` and
``out`` under it), and the rows of the program's
``pt_linear_attention_dispatch_total`` counter whose label ``gate`` is
``feature`` (a decay a key feature: the calls ``kda.rule.*`` serve). A
program without such a scope, counter or label (any tree before the
layer existed) has nothing to read: every function here then returns
None or nothing, and raises nothing."""

from perf import moe_spans, spans

COUNTER = "pt_linear_attention_dispatch_total"


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/kda`` scope, else None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] \
            or not spans.scope_ns(s, moe_spans.under("kda")):
        return None
    return s


def kda_ns(s, *part):
    """Busy self time under ``*/blk*/kda/<part>`` (all of kda without
    one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("kda", *part))


def dispatch_rows():
    """[(labels, calls)] of the delta-rule calls with a decay a key
    feature that this process lowered with telemetry on (traced runs);
    [] where the program has no such counter, label or call."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(COUNTER, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows
            if r["value"] and r["labels"].get("gate") == "feature"]
