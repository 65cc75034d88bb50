"""Hyper-connection calls (mix, read, write-back; forward and backward)
lowered in this process that are not ``hc.*`` kernels: the ops as XLA's
ops (pt_hc_dispatch_total rows with impl other than "kernel"; it counts
only with telemetry on, that is in traced runs). None where the program
lowered no such call."""

from perf import hc_spans


def read(run):
    rows = hc_spans.dispatch_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") != "kernel")
