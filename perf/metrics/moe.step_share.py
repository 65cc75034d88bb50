"""Share of the device's busy self time in the expert layers (scopes
``*/blk*/moe/``: norm, router, dispatch, experts, combine, forward and
backward; XLA's own grouped-matmul calls added: perf/moe_spans.py)."""

from perf import moe_spans


def read(run):
    s = moe_spans.summary(run)
    return s and 100.0 * moe_spans.moe_ns(run, s) / s["busy_ns"]
