"""Operations and bytes of the SmallThinker family, from shapes alone
(the conventions of perf/flops.py: a multiply-add counts 2,
recomputation does not count, embedding lookups are left out). Its
attention layers are of two kinds and are counted by kind: a global
layer's causal triangle, a window layer's BAND."""

from __future__ import annotations

from typing import Dict, Optional


def layer_windows(cfg: Dict):
    """Per layer of the stack, the positions its queries see (None: all
    before them), from ``sliding_window_layout``."""
    layout = cfg["sliding_window_layout"]
    return [int(cfg["sliding_window_size"]) if layout[i % len(layout)]
            else None for i in range(cfg["num_hidden_layers"])]


def visible_pairs(t: int, window: Optional[int]) -> int:
    """(query, key) pairs a head computes over t positions, counted by
    elements: s <= p, and p - s < window. The first ``window`` queries
    see a triangle, each later one ``window`` keys: at t 16,384 and a
    window of 4096, 58.7M of the triangle's 134.2M."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _attention_calls(cfg: Dict, batch: int, t: int, windows,
                     bytes_per_el: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the attention calls of the layers whose
    windows are ``windows``, forward + backward: q.k^T and p.v forward
    (2 matmuls), dv, dp, dq, dk backward (4; the flash kernels' second
    q.k^T is recomputation), 2 * pairs * dh each a query head. Bytes:
    forward reads q, k, v and writes o, backward reads q, k, v, o, do
    and writes dq, dk, dv: six tensors of the query heads' width and six
    of the key/value heads', each moved once."""
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    flops = sum(6 * 2.0 * batch * h * dh * visible_pairs(t, w)
                for w in windows)
    moved = 6 * (h + hk) * batch * t * dh * bytes_per_el
    return {"flops": flops, "bytes": float(len(windows) * moved),
            "calls": 2 * len(windows)}


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """All attention layers: a triangle for a global layer, a band for a
    window layer. Counted as triangles alone the cell's four layers
    would read 23.1 TFLOP for 13.3, and a good kernel over 100%."""
    return _attention_calls(cfg, batch, t, layer_windows(cfg), bytes_per_el)


def swa_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2):
    """The window layers' calls alone (``swa.roofline.train``). A kernel
    computes whole blocks (at blocks of 512, 252 of them a head: 66.1M
    pairs for the band's 58.7M), so a perfect one reads under 100."""
    return _attention_calls(
        cfg, batch, t, [w for w in layer_windows(cfg) if w is not None],
        bytes_per_el)


def smallthinker_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: every token runs the projections (q and o
    are heads x head_dim wide, not the hidden size) and the router over
    all the experts it scores; of its routed experts it runs here the
    EXPECTED held share, k * held / scored of a row a token (an even
    router). backward = 2 x forward."""
    d, tok = cfg["hidden_size"], batch * t
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    scored = cfg.get("router_experts", cfg["moe_num_primary_experts"])
    proj = 2 * d * (h + 2 * hk) * dh + 2 * h * dh * d
    moe = (2 * d * scored
           + cfg["moe_num_active_primary_experts"]
           * cfg["moe_num_primary_experts"] / scored
           * 3 * 2 * d * cfg["moe_ffn_hidden_size"])
    head = 2 * d * cfg["vocab_size"]
    return (3.0 * tok * (cfg["num_hidden_layers"] * (proj + moe) + head)
            + attention_cost(cfg, batch, t)["flops"])
