"""Windowed attention calls (forward and backward) lowered in this
process in another form than ``skip``
(pt_attention_dispatch_total rows that carry a ``band``,
ops/attention_ops.py; it counts only with telemetry on, that is in
traced runs): ``skip`` is the kernels' band walk, in which no block
outside the band is a grid step; ``mask`` would be the causal triangle
walked and masked (2.1 times the blocks at 16,384 positions and a window
of 4096), ``dense`` the composition over [t, t] scores. 0 is expected.
None where the program has no such label (any tree before the window) or
lowered no windowed call."""

from perf import swa_spans


def read(run):
    rows = swa_spans.windowed_rows()
    if not rows:
        return None
    return sum(n for labels, n in rows if labels["band"] != "skip")
