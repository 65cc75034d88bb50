"""Share of the device's busy self time in the decoder blocks (scopes
``*/blk*/``, forward and backward; XLA's own grouped-matmul calls, which
carry no scope, added: perf/moe_spans.py): how much of the step the
block is, beside embedding, head and optimizer. With one block of
sixteen it is far below a deployment's."""

from perf import moe_spans


def read(run):
    s = moe_spans.summary(run)
    return s and 100.0 * moe_spans.block_ns(run, s) / s["busy_ns"]
