"""Selective-scan calls lowered in this process that did not take the
``ssm.scan.*`` kernels on the chip: the position-by-position form
(pt_selective_scan_dispatch_total rows with impl="recurrent") or, on a
TPU, the chunked XLA form (impl="chunked"; it counts only with telemetry
on, that is in traced runs). 0 is expected in the train cell; None where
the program lowered no such call."""

from perf import ssm_spans


def read(run):
    rows = ssm_spans.dispatch_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") != "kernel")
