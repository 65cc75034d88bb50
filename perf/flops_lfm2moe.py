"""Operations and bytes of the LFM2-MoE family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out). A block is a sequence
mixer (``mixer_kinds``: a gated short convolution or an attention layer)
and a feed-forward branch (dense in the first ``num_dense_layers``
published blocks, held experts behind); each is counted by its kind. The
gated convolution has no matmul: its three taps and two gates are 8
operations an element on the VPU, left out of ``train_flops``, and its
kernels are held to BYTES (``sconv_cost``)."""

from __future__ import annotations

from typing import Dict, List, Tuple

KINDS = {"conv": "sconv", "full_attention": "attn"}


def blocks(cfg: Dict) -> List[Tuple[str, bool]]:
    """(mixer kind, dense FF or not) of each block the configuration
    holds, by its PUBLISHED index (``first_layer`` ..)."""
    first = int(cfg.get("first_layer", 0))
    return [(KINDS[cfg["layer_types"][i]], i < int(cfg["num_dense_layers"]))
            for i in range(first, first + int(cfg["num_hidden_layers"]))]


def count(cfg: Dict, kind: str) -> int:
    """Blocks whose mixer is ``kind`` ("sconv", "attn"), or whose
    feed-forward branch is ("dense", "moe")."""
    if kind in ("dense", "moe"):
        return sum(dense == (kind == "dense") for _, dense in blocks(cfg))
    return sum(k == kind for k, _ in blocks(cfg))


def sizes(cfg: Dict) -> Dict[str, int]:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "hk": int(cfg["num_key_value_heads"]),
            "dh": d // h, "taps": int(cfg["conv_L_cache"]),
            "ff": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"])}


def sconv_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
               ) -> Dict[str, float]:
    """Bytes the gated-convolution calls of one train step move,
    forward + backward, each tensor once at the stream's width. Two
    counts. ``bytes``: every operand, whichever memory XLA keeps it in:
    forward reads [B | C | u] (3 c) and writes y (c); backward reads [B |
    C | u] and dy and writes d[B | C | u]: 11 t c elements a layer.
    ``hbm_bytes``: the 5 t c of them that cross HBM inside the kernels
    in every placement XLA has chosen (y forward; dy and d[B | C | u]
    backward); the 6 t c of [B | C | u], which the compiled step keeps
    in the memory beside the core (written there by the projection
    forward, prefetched there in front of the call backward), are NOT
    counted, so a share of the HBM peak taken from this count can only
    under-read (perf/metrics/sconv.roofline.py). No matmul, so no FLOPs
    bound it; the filter and its gradient (c x taps) are left out of
    both."""
    n, c = count(cfg, "sconv"), sizes(cfg)["d"]
    elements = n * batch * t * c * bytes_per_el
    return {"flops": 0.0, "bytes": float(11 * elements),
            "hbm_bytes": float(5 * elements), "calls": 2 * n}


def held_share(cfg: Dict) -> float:
    """The share of a token's k routed pairs an even router puts on the
    experts this chip holds."""
    return int(cfg["num_experts"]) / int(
        cfg.get("router_experts", cfg["num_experts"]))


def attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                   ) -> Dict[str, float]:
    """The attention calls of one train step, forward + backward: one
    causal call an attention block, h query heads over hk key/value
    heads of dh. Forward q.k^T and p.v over the visible pairs, backward
    dv, dp, dq, dk: 12 * pairs * dh a head. Bytes: forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv:
    six tensors at the queries' width and six at the keys'."""
    z = sizes(cfg)
    n = count(cfg, "attn")
    pairs = t * (t + 1) // 2
    return {"flops": n * 12.0 * batch * z["h"] * pairs * z["dh"],
            "bytes": float(n * 6 * batch * t * (z["h"] + z["hk"]) * z["dh"]
                           * bytes_per_el),
            "calls": 2 * n}


def mixer_params(cfg: Dict, kind: str) -> float:
    """Weights of a mixer that a token's row is multiplied by."""
    z = sizes(cfg)
    d = z["d"]
    if kind == "sconv":
        return d * 3 * d + d * d
    return d * (z["h"] + 2 * z["hk"]) * z["dh"] + z["h"] * z["dh"] * d


def ff_params(cfg: Dict, dense: bool) -> float:
    """... of a feed-forward branch, at the ACTIVE parameters ON THIS
    CHIP: of its k routed experts a token runs the expected held share
    (an even router; the rest are other chips' work)."""
    z = sizes(cfg)
    if dense:
        return 3 * z["d"] * z["ff"]
    scored = int(cfg.get("router_experts", cfg["num_experts"]))
    routed = int(cfg["num_experts_per_tok"]) * held_share(cfg)
    return z["d"] * scored + routed * 3 * z["d"] * z["f"]


def forward_flops_per_token(cfg: Dict, t: int) -> Dict[str, float]:
    """Forward FLOPs a token by what does the work: the convolution
    mixers' projections, the dense SwiGLU, attention (projections and
    the causal scores), the held experts with their routers, the head."""
    z = sizes(cfg)
    out = {"sconv": 0.0, "dense": 0.0, "attn": 0.0, "moe": 0.0,
           "head": 2.0 * z["d"] * int(cfg["vocab_size"])}
    for kind, dense in blocks(cfg):
        out[kind] += 2.0 * mixer_params(cfg, kind)
        out["dense" if dense else "moe"] += 2.0 * ff_params(cfg, dense)
    out["attn"] += attention_cost(cfg, 1, t)["flops"] / 3 / t
    return out


def lfm2moe_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step: every token
    runs its blocks' projections and the head over the held rows of the
    tied table; backward = 2 x forward; plus the attention calls."""
    tok = batch * t
    weights = sum(mixer_params(cfg, k) + ff_params(cfg, dense)
                  for k, dense in blocks(cfg))
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return (3.0 * 2 * tok * (weights + head)
            + attention_cost(cfg, batch, t)["flops"])
