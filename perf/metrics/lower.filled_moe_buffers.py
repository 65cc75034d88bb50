"""Whole-buffer zero fills the held expert layers lowered in this
process: rows of pt_moe_buffer_fills_total (ops/moe_ops.py: a row-major
pass of a held share whose first carry is ``jnp.zeros`` over all n * k
rows of its buffer and not memory nothing filled; it counts only with
telemetry on, that is in traced runs). Listed for the cells whose every
expert layer holds a SHARE of the experts its router scores, where an
eighth or a sixteenth of the buffer is live: on the chip 0 is expected
(every such pass starts from ``grouped_matmul.unfilled``), and a count
shows a silent fall back to the fills: matmuls that took no kernel, a
window that is not whole row tiles. On a CPU every such pass fills, and
the count says so.
None where the program has no such counter (any tree before it) or
lowered no windowed pass of a held layer."""


def read(run):
    from paddle_tpu import monitor

    snap = monitor.snapshot()
    if "pt_moe_buffer_fills_total" not in snap or not any(
            r["value"] and r["labels"].get("form") != "whole"
            for r in snap.get("pt_moe_rows_dispatch_total", {}).get(
                "values", [])):
        return None
    return sum(int(r["value"])
               for r in snap["pt_moe_buffer_fills_total"]["values"])
