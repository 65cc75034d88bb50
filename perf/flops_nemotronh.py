"""Operations and bytes of the Nemotron-H family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out). Its blocks are of three
kinds (``block_kinds``) and are counted by kind. The Mamba-2 scan in its
chunked form IS matmuls (four products a chunk and head) and counts in
``train_flops``, at the chunk the configuration states."""

from __future__ import annotations

from typing import Dict, List

KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}


def block_kinds(cfg: Dict) -> List[str]:
    """The kind of each block the configuration holds, by its PUBLISHED
    index (``first_layer`` ..)."""
    first = int(cfg.get("first_layer", 0))
    return [KINDS[cfg["hybrid_override_pattern"][i]]
            for i in range(first, first + int(cfg["num_hidden_layers"]))]


def count(cfg: Dict, kind: str) -> int:
    return block_kinds(cfg).count(kind)


def sizes(cfg: Dict) -> Dict[str, int]:
    heads, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    return {"d": int(cfg["hidden_size"]), "heads": heads, "p": p,
            "e": heads * p, "g": int(cfg["n_groups"]),
            "n": int(cfg["ssm_state_size"]),
            "gn": int(cfg["n_groups"]) * int(cfg["ssm_state_size"]),
            "h": int(cfg["num_attention_heads"]),
            "hk": int(cfg["num_key_value_heads"]),
            "dh": int(cfg["head_dim"]),
            "f": int(cfg["moe_intermediate_size"]),
            "fs": int(cfg["moe_shared_expert_intermediate_size"])}


def mamba2_scan_flops(cfg: Dict, batch: int, t: int, chunk: int) -> float:
    """Forward matmul FLOPs of ONE layer's scan in its chunked form: a
    chunk of C positions and head is C B^T (C x C x n, shared by the
    heads of a group), the decay matrix's product with x dt (C x C x p),
    C times the carried state and B^T x dt into it (C x n x p each)."""
    z = sizes(cfg)
    per = 2.0 * (chunk * chunk * z["n"] * z["g"] / z["heads"]
                 + chunk * chunk * z["p"] + 2 * chunk * z["n"] * z["p"])
    return batch * -(-t // chunk) * z["heads"] * per


def mamba2_scan_cost(cfg: Dict, batch: int, t: int, chunk: int = 128,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes the Mamba-2 scan calls of one train step
    need, forward + backward (backward = 2 x forward; the chunk made
    again is not counted). Bytes, counted LOW: x, y and their gradients
    once each at the stream's width, B and C and their gradients, and
    the float32 state saved for each chunk of ``chunk`` positions,
    written once and read once. dt, the log decay and their gradients
    (heads wide) are left out, and so is the backward pass's second
    reading of x, B and C."""
    z = sizes(cfg)
    layers = count(cfg, "mamba2")
    tok = batch * t
    moved = tok * (4 * z["e"] + 4 * z["gn"]) * bytes_per_el
    moved += 2 * batch * -(-t // chunk) * z["heads"] * z["p"] * z["n"] * 4
    return {"flops": layers * 3.0 * mamba2_scan_flops(cfg, batch, t, chunk),
            "bytes": float(layers * moved), "calls": 2 * layers}


def held_share(cfg: Dict) -> float:
    """The share of a token's k routed pairs an even router puts on the
    experts this chip holds."""
    scored = int(cfg.get("router_experts", cfg["n_routed_experts"]))
    return int(cfg["n_routed_experts"]) / scored


def moe_gmm_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                 ) -> Dict[str, float]:
    """FLOPs and HBM bytes the experts' grouped matmuls of one train
    step need at the rows an even router puts on the held experts. An
    expert is two matrices (up [d, f], down [f, d]) and each costs three
    grouped matmuls over those rows: the forward one and, backward, the
    rows' gradient and the matrix's: SIX a block where a gated unit has
    nine, 2 m d f FLOPs each. Bytes: each call its rows on both sides
    and the held experts' stacked matrix once."""
    z = sizes(cfg)
    m = batch * t * int(cfg["num_experts_per_tok"]) * held_share(cfg)
    calls = 6 * count(cfg, "moe")
    held = int(cfg["n_routed_experts"])
    return {"flops": calls * 2.0 * m * z["d"] * z["f"],
            "bytes": float(calls * (m * z["d"] + m * z["f"]
                                    + held * z["d"] * z["f"])
                           * bytes_per_el),
            "calls": calls}


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


def block_params(cfg: Dict, kind: str) -> float:
    """Weights of a block that a token's row is multiplied by, at the
    ACTIVE parameters ON THIS CHIP: of its k routed experts a token runs
    the expected held share (an even router; the rest are other chips'
    work)."""
    z = sizes(cfg)
    d = z["d"]
    if kind == "mamba2":
        return d * (2 * z["e"] + 2 * z["gn"] + z["heads"]) + z["e"] * d
    if kind == "attn":
        return d * (z["h"] + 2 * z["hk"]) * z["dh"] + z["h"] * z["dh"] * d
    scored = int(cfg.get("router_experts", cfg["n_routed_experts"]))
    routed = int(cfg["num_experts_per_tok"]) * held_share(cfg)
    return d * scored + 2 * d * z["fs"] + routed * 2 * d * z["f"]


def nemotronh_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step: every token
    runs its block's projections (``block_params``) and the head over
    the held rows of the vocabulary; backward = 2 x forward; plus the
    attention calls and the Mamba-2 scans' chunk products."""
    tok = batch * t
    blocks = sum(block_params(cfg, k) for k in block_kinds(cfg))
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return (3.0 * 2 * tok * (blocks + head)
            + attention_cost(cfg, batch, t)["flops"]
            + mamba2_scan_cost(cfg, batch, t,
                               int(cfg["chunk_size"]))["flops"])
