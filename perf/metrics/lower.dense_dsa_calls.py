"""Attention calls under a selection (forward and backward) lowered in
this process as the dense composition, with its [t, t] scores a head
under a [t, t] mask (pt_attention_dispatch_total rows that carry ``sel``
other than ``operand``, ops/attention_ops.py; it counts only with
telemetry on, that is in traced runs): ``operand`` is the BHTD kernels
reading the selection in blocks beside K and V. 0 is expected. None
where the program has no such label (any tree before the selection) or
lowered no such call."""

from perf import dsa_spans


def read(run):
    rows = dsa_spans.selected_rows()
    if not rows:
        return None
    return sum(n for labels, n in rows if labels.get("sel") != "operand")
