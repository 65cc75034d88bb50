"""Share of the device's busy self time in the per-head QK-norm of the
attention layers: the self time of every op under a name scope
``qk_norm`` inside a ``blk<i>/attn`` (``*/blk<i>/attn/qk_norm/rms_norm``
and its grad op, forward and backward: perf/spans.py, the table by
scope), over busy time. Two float32 RMSNorms a layer over each head's
dh, which a rotary op that takes the heads' gains does in the
``rope.fwd`` / ``rope.bwd`` kernels' own pass (``layers.rotary_embedding(
norm_param_attrs=...)``): such a program has no ``qk_norm`` scope and
reads 0.0, its norm's time inside ``rope.step_share.train``. None where
the trace holds no op under any ``blk<i>/attn`` (the encoder-era cells,
a run that traced nothing)."""

from perf import spans


def _in_attn(parts):
    """The components behind ``blk<i>/attn`` of a table key (phase
    first, op type last), or None where it has no such scope."""
    for i, p in enumerate(parts[1:-2], 1):
        if p.startswith("blk") and parts[i + 1] == "attn":
            return parts[i + 2:-1]
    return None


def read(run):
    s = spans.for_run(run)
    if not s or not s["busy_ns"]:
        return None
    if not spans.scope_ns(s, lambda parts: _in_attn(parts) is not None):
        return None
    ns = spans.scope_ns(s, lambda parts: "qk_norm" in (_in_attn(parts) or ()))
    return 100.0 * ns / s["busy_ns"]
