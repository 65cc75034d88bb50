"""Dense embedding gradients (``lookup_table_grad``) lowered in this
process as XLA's scatter-add instead of the ``embed.grad`` kernel:
pt_embedding_grad_dispatch_total rows with impl="xla"
(ops/tensor_ops.py, ``parallel/embed_grad.embed_grad_tile``'s answer
for the call; it counts only with telemetry on, that is in traced
runs). 0 is expected in the decoder cells, whose tables are 1024 wide
or more on one TPU. None where the program has no such counter or
lowered no such call."""

from perf import harness


def read(run):
    rows = harness.counter_rows("pt_embedding_grad_dispatch_total")
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") == "xla")
