"""Share of the device's busy self time under the ``*/blk*/attn/gate/``
scopes, forward and backward: a per-head output gate's sigmoid and its
product with the heads' context, broadcast over a head's features, in
front of the output projection (models/laguna.py). Whether the product
fuses into a neighbour's pass or costs a pass of its own over
[t, heads x head_dim] each way is what it shows. None where the trace
holds no such scope."""

from perf import moe_spans, spans


def read(run):
    return spans.share(run, lambda s: spans.scope_ns(
        s, moe_spans.under("attn", "gate"))) or None
