"""Share of the device's busy self time in the windowed attention calls
(scopes ``*/blk*/attn/swa/``: the sdpa op of every sliding-window layer,
forward and backward; the projections, the rotation and the global
layers' calls are not in it)."""

from perf import swa_spans


def read(run):
    s = swa_spans.summary(run)
    return s and 100.0 * swa_spans.swa_ns(s) / s["busy_ns"]
