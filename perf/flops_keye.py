"""Operations and bytes of the Keye family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out). Its attention reads, a
query, the ``topk`` keys a lightning indexer chose, and is counted by
the SELECTED pairs: what a perfect sparse kernel would compute, whatever
the kernels walk."""

from __future__ import annotations

from typing import Dict


def selected_pairs(t: int, topk: int) -> int:
    """(query, key) pairs a head reads over t positions: query p reads
    min(p + 1, topk) keys. At t 16,384 and topk 2048: 31.46M of the
    causal triangle's 134.2M (23.4%)."""
    k = min(topk, t)
    return k * (k + 1) // 2 + (t - k) * k


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """FLOPs and HBM bytes of every layer's attention call, forward +
    backward, from the SELECTED pairs: q.k^T and p.v forward (2
    matmuls), dv, dp, dq, dk backward (4; the flash kernels' second
    q.k^T is recomputation), 2 * pairs * dh each a query head. Bytes:
    six tensors of the query heads' width and six of the key/value
    heads', each moved once, and the selection read twice (t^2 / 8
    bytes: a bit a pair)."""
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    pairs = selected_pairs(t, int(cfg["sa_config"]["topk"]))
    moved = (6 * (h + hk) * batch * t * dh * bytes_per_el
             + 2 * batch * t * t // 8)
    return {"flops": layers * 6 * 2.0 * batch * h * dh * pairs,
            "bytes": float(layers * moved), "calls": 2 * layers}


def dsa_index_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """FLOPs and HBM bytes of the indexer's work over the causal
    triangle of ``pairs`` = t (t + 1) / 2 (query, key) pairs a layer,
    whatever implements it. The index products qI . kI, 2 hI dI a pair:
    once for the scores the top-k reads, and, over the SELECTED pairs
    alone, for the loss's backward pass the two products that give dqI
    and dkI (the loss's own forward scores are recomputation and do not
    count). The target P of the KL loss: the attention's q . k^T made
    again over the selected pairs, 2 h dh a pair (it is the loss's
    input, not the attention's recomputation). Bytes: qI, kI, w, the
    selection (t^2 / 8 bytes, a bit a pair, written once and read once),
    q, k and the three
    gradients, each moved once."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    triangle = t * (t + 1) // 2
    chosen = selected_pairs(t, int(sa["topk"]))
    flops = batch * (2.0 * hi * di * (triangle + 2 * chosen)
                     + 2.0 * h * dh * chosen)
    moved = (batch * t * bytes_per_el * (2 * hi * di + 2 * di + 2 * hi
                                         + (h + hk) * dh)
             + 2 * batch * t * t // 8)
    return {"flops": layers * flops, "bytes": float(layers * moved)}


def keye_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: every token runs the projections (q and o
    are heads x head_dim wide, not the hidden size), the indexer's three
    projections and the router over all the experts it scores; of its
    routed experts it runs here the EXPECTED held share, k * held /
    scored of a row a token (an even router). backward = 2 x forward.
    The attention is the selected pairs' and the indexer's work
    ``dsa_index_cost``'s."""
    d, tok = cfg["hidden_size"], batch * t
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    scored = cfg.get("router_experts", cfg["num_experts"])
    proj = (2 * d * (h + 2 * hk) * dh + 2 * h * dh * d
            + 2 * d * (hi * di + di + hi))
    moe = (2 * d * scored
           + cfg["num_experts_per_tok"] * cfg["num_experts"] / scored
           * 3 * 2 * d * cfg["moe_intermediate_size"])
    head = 2 * d * cfg["vocab_size"]
    return (3.0 * tok * (cfg["num_hidden_layers"] * (proj + moe) + head)
            + attention_cost(cfg, batch, t)["flops"]
            + dsa_index_cost(cfg, batch, t)["flops"])
