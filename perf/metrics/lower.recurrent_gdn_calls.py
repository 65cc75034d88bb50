"""Gated delta-rule calls lowered in this process that took the
step-by-step recurrent form instead of the chunkwise one
(pt_linear_attention_dispatch_total rows with impl="recurrent"; it
counts only with telemetry on, that is in traced runs). 0 is expected
in every train cell; None where the program lowered no such call."""

from perf import gdn_spans


def read(run):
    rows = gdn_spans.dispatch_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") == "recurrent")
