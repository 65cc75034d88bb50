"""Backward passes of BHTD attention calls lowered in this process as
the split pair (``attn.bhtd.bwd_dq`` + ``attn.bhtd.bwd_dkv``: a live
block's scores, exp and dp computed twice, 7 matmuls, two walks of the
grid) instead of the one fused call ``attn.bhtd.bwd``
(pt_attention_dispatch_total rows with pass="bwd", family="bhtd" and a
``form`` other than "fused", ops/attention_ops.py; it counts only with
telemetry on, that is in traced runs; the label is
``flash_attention.bhtd_bwd_form``'s answer for the call). Listed for
the decoder cells, whose every such call keeps its resident rows inside
the fused kernel's VMEM cap: 0 is expected. None where no row carries
the label (any tree before it, or a program that lowered no BHTD
backward call)."""


def read(run):
    from paddle_tpu import monitor

    rows = monitor.snapshot().get("pt_attention_dispatch_total", {}).get(
        "values", [])
    rows = [r for r in rows if r["value"] and "form" in r["labels"]
            and r["labels"].get("pass") == "bwd"
            and r["labels"].get("family") == "bhtd"]
    if not rows:
        return None
    return sum(int(r["value"]) for r in rows
               if r["labels"]["form"] != "fused")
