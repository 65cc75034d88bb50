"""Mamba-2 scan calls lowered in this process that did not take the
``mamba2.chunk.*`` kernels on the chip: the chunked XLA form
(pt_mamba2_scan_dispatch_total rows with impl="chunked") or the
position-by-position form (impl="recurrent"); the counter counts only
with telemetry on, that is in traced runs. 0 is expected in the train
cell; None where the program lowered no such call."""

from perf import mamba2_spans


def read(run):
    rows = mamba2_spans.dispatch_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") != "kernel")
