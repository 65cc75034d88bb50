"""Share of the device's busy self time in the decoder blocks (scopes
``*/blk*/``, forward and backward; XLA's own grouped-matmul calls, which
carry no scope, added: perf/moe_spans.py): how much of the step the
block is, beside embedding, head and optimizer. With one block of
sixteen it is far below a deployment's. A block needs no expert layer
to count (a state-space or attention block with a dense MLP is one);
None where the trace holds no ``blk*`` scope at all."""

from perf import moe_spans, spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["busy_ns"]:
        return None
    ns = moe_spans.block_ns(run, s)
    return 100.0 * ns / s["busy_ns"] if ns else None
