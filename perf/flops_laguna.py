"""Operations and bytes of the Laguna family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out). Its attention layers
are of two kinds, each with a head count of its own, and are counted by
kind: a full layer's causal triangle at its heads, a window layer's BAND
at its."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

# (query, key) pairs a head computes, by elements: at t 8192 and a window
# of 512, 4.06M of the triangle's 33.56M
from perf.flops_smallthinker import visible_pairs

WINDOW = "sliding_attention"


def layer_calls(cfg: Dict) -> List[Tuple[int, Optional[int]]]:
    """Per layer of the stack, (query heads, the positions its queries
    see or None for all before them), from
    ``num_attention_heads_per_layer`` and ``layer_types``."""
    return [(int(cfg["num_attention_heads_per_layer"][i]),
             int(cfg["sliding_window"])
             if cfg["layer_types"][i] == WINDOW else None)
            for i in range(cfg["num_hidden_layers"])]


def _attention_calls(cfg: Dict, batch: int, t: int, calls,
                     bytes_per_el: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the attention calls ``calls`` [(heads,
    window)], forward + backward: q.k^T and p.v forward (2 matmuls), dv,
    dp, dq, dk backward (4; the flash kernels' second q.k^T is
    recomputation), 2 * pairs * dh each a query head. Bytes: forward
    reads q, k, v and writes o, backward reads q, k, v, o, do and writes
    dq, dk, dv: six tensors of the call's query heads' width and six of
    the key/value heads', each moved once. The gate's product and the
    rotation are not attention calls."""
    hk, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    flops = sum(6 * 2.0 * batch * h * dh * visible_pairs(t, w)
                for h, w in calls)
    moved = sum(6 * (h + hk) * batch * t * dh * bytes_per_el
                for h, _ in calls)
    return {"flops": flops, "bytes": float(moved), "calls": 2 * len(calls)}


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """All attention layers: a triangle at 48 heads for a full layer, a
    band at 64 for a window layer (the cell: 2 x 2.47 + 3 x 0.40 = 6.15
    TFLOP; counted as five triangles at 48 heads it would read 12.4)."""
    return _attention_calls(cfg, batch, t, layer_calls(cfg), bytes_per_el)


def swa_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """The window layers' calls alone (``swa.family_roofline.train``).
    A kernel computes whole blocks: at a window of 512 on blocks of 512
    every row of query blocks but the first walks TWO key blocks, both
    cut by an edge of the band (31 x 512 x 512 = 8.13M pairs a head for
    the band's 4.06M with the first block's triangle), so a perfect
    kernel at these blocks reads under 50."""
    return _attention_calls(
        cfg, batch, t, [c for c in layer_calls(cfg) if c[1] is not None],
        bytes_per_el)


def laguna_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: every token runs each layer's projections
    at THAT layer's width (q and o are heads x head_dim wide, the gate's
    column a head), a dense layer's SwiGLU, and in an expert layer the
    router over all the experts it scores, the shared expert and, of its
    routed experts, the EXPECTED held share: k * held / scored of a row
    a token (an even router). backward = 2 x forward."""
    d, tok = cfg["hidden_size"], batch * t
    hk, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    scored = cfg.get("router_experts", cfg["num_experts"])
    f = cfg["moe_intermediate_size"]
    moe = (2 * d * scored
           + 3 * 2 * d * cfg["shared_expert_intermediate_size"]
           + cfg["num_experts_per_tok"] * cfg["num_experts"] / scored
           * 3 * 2 * d * f)
    dense = 3 * 2 * d * cfg["intermediate_size"]
    per_token = 2 * d * cfg["vocab_size"]
    for i, (h, _) in enumerate(layer_calls(cfg)):
        per_token += 2 * d * ((h + 2 * hk) * dh + h) + 2 * h * dh * d
        per_token += dense if cfg["mlp_layer_types"][i] == "dense" else moe
    return 3.0 * tok * per_token + attention_cost(cfg, batch, t)["flops"]
