"""Gated short-convolution calls (``gated_short_conv``, forward and
backward) lowered in this process as the composition in float32 XLA ops
instead of the ``sconv.gated.*`` kernels: pt_causal_conv_dispatch_total
rows that carry the label ``gated`` and whose ``impl`` is not ``kernel``
(ops/linear_attention_ops.py; it counts only with telemetry on, that is
in traced runs). 0 is expected in the train cell, whose every such call
is bf16 on one TPU at 2048 channels a range. None where the program has
no such counter or label (any tree before the op) or lowered no such
call."""

from perf import sconv_spans


def read(run):
    rows = sconv_spans.gated_rows()
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") != "kernel")
