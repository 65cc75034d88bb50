"""``causal_conv1d`` calls (the short convolution in front of a gated
delta rule, forward and backward) lowered in this process as float32
XLA ops over a padded copy of X instead of the ``gdn.conv.*`` kernels
(pt_causal_conv_dispatch_total rows with impl="xla",
ops/linear_attention_ops.py; it counts only with telemetry on, that is
in traced runs). Listed for the cell whose every such call is bf16 on
one TPU at channels on the lanes, where ``conv_tile`` gives each a tile
and 0 is expected. None where the program has no such counter (any tree
before it) or lowered no such call."""


def read(run):
    from paddle_tpu import monitor

    rows = monitor.snapshot().get("pt_causal_conv_dispatch_total", {}).get(
        "values", [])
    rows = [r for r in rows if r["value"]]
    if not rows:
        return None
    return sum(int(r["value"]) for r in rows
               if r["labels"].get("impl") == "xla")
