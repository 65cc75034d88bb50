"""What the state-space metrics share: the time of the Mamba and GMU
mixers in a traced run, from the program's scopes (perf/spans.py:
``*/blk<i>/ssm/`` with ``proj``, ``conv``, ``xproj``, ``sscan``,
``gate`` and ``out`` under it, and ``*/blk<i>/gmu/``), the ``ssm.*``
Mosaic kernels' self time (perf/trace.py, by family) and the rows of the
program's ``pt_selective_scan_dispatch_total`` counter. A program
without such a scope, kernel or counter (any tree before the layer
existed, any other family) has nothing to read: every function here
then returns None or nothing, and raises nothing."""

from perf import moe_spans, spans

COUNTER = "pt_selective_scan_dispatch_total"
FAMILY = "ssm"
# the selective scan's scope (``sscan``: ``scan`` is a word jax puts into
# op names itself, perf/spans.JAX_WORDS, and a scope's reader stops there)
SCAN = "sscan"


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/ssm`` scope, else None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not ssm_ns(s):
        return None
    return s


def ssm_ns(s, *part):
    """Busy self time under ``*/blk*/ssm/<part>`` (all of ssm without
    one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("ssm", *part))


def gmu_ns(s):
    """... under ``*/blk*/gmu/``."""
    return spans.scope_ns(s, moe_spans.under("gmu"))


def diff_ns(s):
    """... under ``*/blk*/attn/diff/``: differential attention's
    combination outside the kernels."""
    return spans.scope_ns(s, moe_spans.under("attn", "diff"))


def kernel_s(run):
    """Self seconds a chip of the ``ssm.*`` Mosaic kernels in the traced
    stretch; 0.0 where the trace holds none."""
    return ((run.trace or {}).get("by_family_s") or {}).get(FAMILY, 0.0)


def dispatch_rows():
    """[(labels, calls)] of the selective-scan calls this process
    lowered with telemetry on (traced runs); [] where the program has no
    such counter or counted nothing."""
    from paddle_tpu import monitor

    rows = monitor.snapshot().get(COUNTER, {}).get("values", [])
    return [(r["labels"], int(r["value"])) for r in rows if r["value"]]
