"""Operations and bytes of the OLMoE family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out)."""

from __future__ import annotations

from typing import Dict


def olmoe_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters: each token runs ``num_experts_per_tok`` of the experts,
    and causal attention needs half of q.k^T and p.v. backward = 2 x
    forward."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    tok = batch * t
    proj = 4 * 2 * tok * d * d                      # q, k, v, out
    attn = 2 * 2 * tok * t * d / 2                  # causal: half
    router = 2 * tok * d * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * 2 * tok * d * f
    head = 2 * tok * d * cfg["vocab_size"]
    return 3.0 * (cfg["num_hidden_layers"] * (proj + attn + router + experts)
                  + head)


def moe_gmm_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                 ) -> Dict[str, float]:
    """FLOPs and HBM bytes the experts' grouped matmuls of one train
    step need. A block has three expert matrices (gate, up: [E, d, f];
    down: [E, f, d]) and each costs three grouped matmuls over the m =
    tokens x k chosen rows: the forward one, and in the backward pass
    the rows' gradient and the matrix's: nine a block, 2*m*d*f FLOPs
    each (3 forward + 6 backward of 65,536 rows x 2048 x 1024 at the
    published widths: 2.47 TFLOP). Bytes: each call reads or writes its
    rows on both sides ([m, d] and [m, f]) and the stacked matrix
    ([E, d, f]) once. The SwiGLU product between them is elementwise
    and not counted."""
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    m = batch * t * cfg["num_experts_per_tok"]
    calls = 9 * cfg["num_hidden_layers"]
    return {"flops": calls * 2.0 * m * d * f,
            "bytes": float(calls * (m * d + m * f + e * d * f) * bytes_per_el),
            "calls": calls}
