"""Passes of a top-k MoE layer over its row buffer lowered in this
process in the ``whole`` form: the pass walks all n * k rows of the
buffer, not the windows that hold a live row
(pt_moe_rows_dispatch_total, ops/moe_ops.py; it counts only with
telemetry on, that is in traced runs). Listed for the cells whose every
expert layer holds a SHARE of the experts its router scores, where a
sixteenth of the buffer is live and 0 is expected; a layer that holds
every expert walks its buffer whole by design and is in no such cell.
The form is the lowering branch's own: the two sums by token, which
walk the live rows while those are few and the buffer by token from
there (``windowed|by_token``), are not counted; a held pass that takes
no window is.
None where the program has no such counter (any tree before it) or
lowered no expert layer."""


def read(run):
    from paddle_tpu import monitor

    rows = monitor.snapshot().get("pt_moe_rows_dispatch_total", {}).get(
        "values", [])
    rows = [r for r in rows if r["value"]]
    if not rows:
        return None
    return sum(int(r["value"]) for r in rows
               if r["labels"].get("form") == "whole")
