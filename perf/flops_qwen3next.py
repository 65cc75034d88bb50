"""Operations and bytes of the Qwen3-Next family, from shapes alone (the
conventions of perf/flops.py: a multiply-add counts 2, recomputation
does not count, embedding lookups are left out)."""

from __future__ import annotations

from typing import Dict

DEFAULT_CHUNK = 64


def layer_kinds(cfg: Dict):
    """(linear-attention layers, full-attention layers) of the stack:
    layer i is full attention where (i + 1) % interval == 0."""
    full = sum((i + 1) % cfg["full_attention_interval"] == 0
               for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def gdn_scan_flops_per_token(cfg: Dict, chunk: int) -> float:
    """Forward matmul FLOPs a token of the chunkwise gated delta rule,
    all value heads (ops/linear_attention_ops.py, C = chunk): inside a
    chunk (beta K) K^T and Q K^T (2 C dk each), the unit-triangular
    solve for U and W (C (dk + dv): half a product) and
    lower(Q K^T . D) V' (2 C dv); against the state W S, (Q exp G) S and
    the update K^T V' (2 dk dv each)."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    a_head = (2 * 2 * chunk * dk + chunk * (dk + dv) + 2 * chunk * dv
              + 3 * 2 * dk * dv)
    return float(cfg["linear_num_value_heads"] * a_head)


def qwen3next_train_flops(cfg: Dict, batch: int, t: int,
                          chunk: int = DEFAULT_CHUNK) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: every token runs the router over all the
    experts it scores and the shared expert; of its
    ``num_experts_per_tok`` routed experts it runs here the EXPECTED
    held share, k * held / scored of a row a token (an even router;
    the rest of its experts are other chips' work and is not counted).
    Causal attention needs half of q.k^T and p.v. backward = 2 x
    forward."""
    d, tok = cfg["hidden_size"], batch * t
    n_gdn, n_full = layer_kinds(cfg)
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    gdn = (2 * d * (2 * kd + 2 * vd + 2 * cfg["linear_num_value_heads"])
           + 2 * vd * d + gdn_scan_flops_per_token(cfg, chunk))
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = (2 * d * 2 * (h + hk) * dh + 2 * h * dh * d
            + 2 * 2 * t * h * dh / 2)               # causal: half
    scored = cfg.get("router_experts", cfg["num_experts"])
    f = cfg["moe_intermediate_size"]
    moe = (2 * d * scored
           + 3 * 2 * d * cfg["shared_expert_intermediate_size"] + 2 * d
           + cfg["num_experts_per_tok"] * cfg["num_experts"] / scored
           * 3 * 2 * d * f)
    head = 2 * d * cfg["vocab_size"]
    return 3.0 * tok * (n_gdn * gdn + n_full * attn
                        + cfg["num_hidden_layers"] * moe + head)


def gdn_scan_cost(cfg: Dict, batch: int, t: int, chunk: int = DEFAULT_CHUNK,
                  bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes the delta-rule calls of one train step need:
    the chunkwise form's matmul FLOPs at ``chunk`` (forward + 2 x
    backward; the backward pass's one recomputation of the per-chunk
    quantities is not counted), against each of q, k, v, o (bf16), g and
    beta (float32) and their gradients moved once. The projections, the
    convolution and the gated norm around the rule are not in it."""
    n_gdn, _ = layer_kinds(cfg)
    tok = batch * t
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    moved = tok * ((2 * kd + 2 * vd) * bytes_per_el
                   + 2 * cfg["linear_num_value_heads"] * 4)
    return {"flops": 3.0 * n_gdn * tok * gdn_scan_flops_per_token(cfg, chunk),
            "bytes": float(2 * n_gdn * moved), "calls": 2 * n_gdn}
