"""What the latent-attention and multi-token-prediction metrics share:
the time of those parts in a traced run, from the program's scopes
(perf/spans.py: ``*/blk<i>/attn/`` with ``q_lora``, ``kv_lora``,
``rope``, ``core`` and ``out`` under it; the MTP module
``*/blk_mtp/...`` and its pass through the head ``*/loss_head/mtp/``).
A program without such scopes (any tree before the model existed, any
other family) has nothing to read: ``summary`` then returns None, and
nothing here raises."""

from perf import moe_spans, spans


def summary(run):
    """``spans.for_run`` where it holds a latent-attention block (an
    ``attn`` scope with a ``kv_lora`` scope under it), else None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] \
            or not spans.scope_ns(s, moe_spans.under("attn", "kv_lora")):
        return None
    return s


def attn_ns(s, *part):
    """Busy self time under ``*/blk*/attn/<part>`` (all of attn without
    one), forward and backward, ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("attn", *part))


def mtp_ns(s):
    """... under ``*/blk_mtp/`` and ``*/loss_head/mtp/``: the MTP
    module's merge, block and second pass through the head."""
    def accept(parts):
        inner = parts[1:-1]
        return inner[:1] == ["blk_mtp"] or inner[:2] == ["loss_head", "mtp"]
    return spans.scope_ns(s, accept)
