"""Delta-rule calls with a decay a key feature lowered in this process
that are not ``kda.rule.*`` kernels: the chunked XLA form or the
step-by-step recurrence (pt_linear_attention_dispatch_total rows with
gate="feature" and impl other than "kernel"; it counts only with
telemetry on, that is in traced runs). 0 is expected in the cell; None
where the program lowered no such call."""

from perf import kda_spans


def read(run):
    rows = kda_spans.dispatch_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") != "kernel")
