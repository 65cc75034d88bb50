"""What the hyper-connection metrics share: the time of the residual
streams' mixes, reads and write-backs in a traced run, from the
program's scopes (perf/spans.py: ``*/blk<i>/<attn|ffn|moe>/hc/`` with
``mix``, ``pre`` and ``post`` under it), and the rows of the program's
``pt_hc_dispatch_total`` counter. A program without such a scope or
counter (any tree before the ops existed, any other family) has nothing
to read: every function here then returns None or nothing, and raises
nothing."""

from perf import harness, spans

COUNTER = "pt_hc_dispatch_total"


def under(*part):
    """A ``spans.scope_ns`` predicate: the scope's components after the
    phase start with a ``blk<i>``, then one sublayer's scope, then
    ``hc`` and ``part``."""
    path = ("hc",) + part

    def accept(parts):
        inner = parts[1:-1]
        return (bool(inner) and inner[0].startswith("blk")
                and tuple(inner[2:2 + len(path)]) == path)
    return accept


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/*/hc`` scope, else
    None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not spans.scope_ns(s, under()):
        return None
    return s


def hc_ns(s, *part):
    """Busy self time under ``*/blk*/*/hc/<part>`` (all of hc without
    one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, under(*part))


def dispatch_rows():
    """[(labels, calls)] of the hyper-connection calls this process
    lowered with telemetry on (traced runs); [] where the program has no
    such counter or call."""
    return harness.counter_rows(COUNTER)
