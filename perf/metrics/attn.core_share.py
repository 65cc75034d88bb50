"""Share of the device's busy self time under the ``*/blk*/attn/core/``
scopes, forward and backward: the sdpa op of every FULL (global)
attention layer of a model whose window layers' calls sit under
``.../attn/swa/`` (``swa.step_share.train``), so that the two kinds of
attention are read apart. None where the trace holds no such scope."""

from perf import moe_spans, spans


def read(run):
    return spans.share(run, lambda s: spans.scope_ns(
        s, moe_spans.under("attn", "core"))) or None
