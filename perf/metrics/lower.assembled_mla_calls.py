"""Attention calls lowered in this process that were GIVEN their queries
and keys in two parts (``QPe`` and ``KPe`` beside ``Q`` and ``K``:
latent attention's rotary features, the keys' ONE head that all query
heads share) and whose parts the sdpa op assembled itself, a wide q, a
wide k and the shared head copied once a query head, because no kernel
took the call with the parts as operands of their own
(pt_attention_dispatch_total rows with ``parts="assembled"``,
ops/attention_ops.py ``_two_parts``; it counts only with telemetry on,
that is in traced runs; ``parts="own"`` where ``attn.bhtd.fwd`` and
``attn.bhtd.bwd`` read the parts where they lie:
``flash_attention.bhtd_parts``'s answer for the call). Forward and
backward rows both count. Listed for the cells whose builders make such
calls, every one of which the fused BHTD kernels take on a TPU: 0 is
expected. None where no row carries the label (any tree before it, or a
program that made no call in two parts)."""


def read(run):
    from paddle_tpu import monitor

    rows = monitor.snapshot().get("pt_attention_dispatch_total", {}).get(
        "values", [])
    rows = [r for r in rows if r["value"] and "parts" in r["labels"]]
    if not rows:
        return None
    return sum(int(r["value"]) for r in rows
               if r["labels"]["parts"] == "assembled")
