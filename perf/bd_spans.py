"""What the block-diffusion attention metrics share: the time of the
block-masked attention calls in a traced run, from the program's scopes
(perf/spans.py: ``*/blk<i>/attn/bd/``, the sdpa op of a layer trained by
block diffusion, forward and backward, the kernels and whatever XLA puts
around them), and the rows of ``pt_attention_dispatch_total`` that carry
``mask`` = ``block_diffusion``. A program without such a scope or label
(any tree before the block mask existed, any other family) has nothing
to read: every function here then returns None or nothing, and raises
nothing."""

from perf import harness, moe_spans, spans

COUNTER = "pt_attention_dispatch_total"


def summary(run):
    """``spans.for_run`` where it holds a ``blk*/attn/bd`` scope, else
    None."""
    s = spans.for_run(run)
    if not s or not s["busy_ns"] or not bd_ns(s):
        return None
    return s


def bd_ns(s):
    """Busy self time under ``*/blk*/attn/bd/``, forward and backward,
    ns summed over the chips."""
    return spans.scope_ns(s, moe_spans.under("attn", "bd"))


def masked_rows():
    """[(labels, calls)] of the block-masked attention calls this
    process lowered with telemetry on (traced runs); [] where the
    program has no such label or lowered no such call."""
    return [(labels, n) for labels, n in harness.counter_rows(COUNTER)
            if labels.get("mask") == "block_diffusion"]
