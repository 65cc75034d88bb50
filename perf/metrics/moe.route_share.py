"""Of the expert layers' time (moe.step_share.train), the share under the
``router``, ``dispatch`` and ``combine`` scopes: routing, sorting the
chosen pairs by expert, gathering them back and weighting, which is
everything that is not an expert's matmul or the layer's norm."""

from perf import moe_spans, spans


def read(run):
    s = moe_spans.summary(run)
    if not s:
        return None
    moved = sum(spans.scope_ns(s, moe_spans.under("moe", part))
                for part in ("router", "dispatch", "combine"))
    return 100.0 * moved / moe_spans.moe_ns(run, s)
