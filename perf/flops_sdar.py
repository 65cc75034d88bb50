"""Operations and bytes of the SDAR family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out). ``t`` is the DATA
tokens of a row, L; the network sees 2 L positions, the noised copy and
the clean copy, and its attention is counted by the block mask's live
pairs exactly."""

from __future__ import annotations

from typing import Dict


def visible_pairs(t: int, block: int) -> int:
    """(query, key) pairs a head computes over a row of t data tokens in
    blocks of ``block``: the noised half's own blocks, t * block; its
    view of the clean blocks before each, t^2 / 2 - t * block / 2; the
    clean half's block-causal triangle, t^2 / 2 + t * block / 2:
    t^2 + t * block (at t 4096 and blocks of 4: 16.79M; a causal row of
    2 t positions has 33.56M)."""
    assert t % block == 0, (t, block)
    return t * t + t * block


def bd_attention_cost(cfg: Dict, batch: int, t: int, layers: int,
                      bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ``layers`` block-masked attention calls,
    forward + backward: q.k^T and p.v forward (2 matmuls), dv, dp, dq, dk
    backward (4; the flash kernels' second q.k^T is recomputation), 2 *
    pairs * dh each a query head. Bytes: forward reads q, k, v and writes
    o, backward reads q, k, v, o, do and writes dq, dk, dv: six tensors
    of the query heads' width and six of the key/value heads', each of 2
    t positions and moved once."""
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pairs = visible_pairs(t, cfg["block_length"])
    moved = 6 * (h + hk) * batch * 2 * t * dh * bytes_per_el
    return {"flops": layers * 6 * 2.0 * batch * h * dh * pairs,
            "bytes": float(layers * moved), "calls": 2 * layers}


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """Every layer's attention call: all of them block-masked."""
    return bd_attention_cost(cfg, batch, t, cfg["num_hidden_layers"],
                             bytes_per_el)


def sdar_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP. Every one of the 2 t positions runs the
    projections (q and o are heads x head_dim wide, not the hidden size)
    and the router over all the experts it scores; of its routed experts
    it runs here the EXPECTED held share, k * held / scored of a row a
    position (an even router). In the LAST layer only the noised half is
    counted whole: nothing of the clean half but its keys and values
    reaches the loss, so its K and V projections are counted and its q,
    o, router and experts are not (a later change that stops computing
    them cannot read over 100%). The head is counted over t / 2 rows, the
    schedule's expected number of masked positions (p uniform over a
    block). backward = 2 x forward."""
    d = cfg["hidden_size"]
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    scored = cfg.get("router_experts", cfg["num_experts"])
    kv = 2 * d * 2 * hk * dh
    proj = 2 * d * h * dh + kv + 2 * h * dh * d
    moe = (2 * d * scored
           + cfg["num_experts_per_tok"] * cfg["num_experts"] / scored
           * 3 * 2 * d * cfg["moe_intermediate_size"])
    layers = cfg["num_hidden_layers"]
    positions = batch * ((layers - 1) * 2 * t + t) * (proj + moe) \
        + batch * t * kv
    head = batch * (t / 2) * 2 * d * cfg["vocab_size"]
    return (3.0 * (positions + head)
            + attention_cost(cfg, batch, t)["flops"])
