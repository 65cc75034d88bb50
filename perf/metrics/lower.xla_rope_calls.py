"""Rotary-embedding calls (``rotary_embedding`` and its grad op, q and
k together) lowered in this process as XLA's ops (``_rotate`` behind a
transpose) instead of the ``rope.fwd`` / ``rope.bwd`` kernels:
pt_rope_dispatch_total rows whose ``impl`` is ``xla``
(ops/attention_ops.py, ``parallel/rope.rope_tile``'s answer for the
call; it counts only with telemetry on, that is in traced runs, and
the eval clone's forward calls count beside the step's). 0 is expected
where the whole head rotates in rotate-half form at a width on the 128
lanes; a cell whose heads ``rope_tile`` refuses today (64 of 256
rotated, an interleaved 64, heads of 64) reads its calls, and a kernel
that takes them reads 0 there. None where the program has no such
counter or lowered no such call."""

from perf import harness


def read(run):
    rows = harness.counter_rows("pt_rope_dispatch_total")
    if not rows:
        return None
    return sum(n for lb, n in rows if lb.get("impl") == "xla")
