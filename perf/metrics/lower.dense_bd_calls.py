"""Block-masked attention calls (forward and backward) lowered in this
process as the dense composition, with its [t, t] scores a head
(pt_attention_dispatch_total rows that carry ``mask`` =
``block_diffusion`` and another ``band`` than ``skip``,
ops/attention_ops.py; it counts only with telemetry on, that is in
traced runs): ``skip`` is the BHTD kernels' walk of the mask's live
blocks. 0 is expected. None where the program has no such label (any
tree before the block mask) or lowered no block-masked call."""

from perf import bd_spans


def read(run):
    rows = bd_spans.masked_rows()
    if not rows:
        return None
    return sum(n for labels, n in rows if labels.get("band") != "skip")
