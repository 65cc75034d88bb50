"""Operations and bytes of the Kimi Linear family (Kimi Delta Attention
layers beside latent-attention layers with no positional embedding, a
dense first layer, sigmoid-routed experts beside a shared one), from
shapes alone (the conventions of perf/flops.py: a multiply-add counts 2,
recomputation does not count, embedding lookups are left out)."""

from __future__ import annotations

from typing import Dict

DEFAULT_CHUNK = 64


def layer_kinds(cfg: Dict):
    """(KDA layers, latent-attention layers) of the stack as cut: the
    published lists number the layers from 1."""
    la, n = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    kda = sum(1 for i in la["kda_layers"] if i <= n)
    return kda, sum(1 for i in la["full_attn_layers"] if i <= n)


def kda_scan_flops_per_token(cfg: Dict, chunk: int) -> float:
    """Forward matmul FLOPs a token of the chunkwise delta rule with a
    decay a key feature, all heads (ops/linear_attention_ops.py, C =
    chunk): inside a chunk A and P as the two [C, dk] x [dk, C]
    products they are in exact arithmetic (2 C dk each, whatever
    sub-blocks compute them), the unit-triangular solve for U and W
    (C (dk + dv): half a product) and P V' (2 C dv); against the state
    W S, (Q e^G) S and the update K^T V' (2 dk dv each)."""
    la = cfg["linear_attn_config"]
    dk = dv = la["head_dim"]
    a_head = (2 * 2 * chunk * dk + chunk * (dk + dv) + 2 * chunk * dv
              + 3 * 2 * dk * dv)
    return float(la["num_heads"] * a_head)


def mla_flops_per_token(cfg: Dict, t: int) -> float:
    """Forward matmul FLOPs a token of one latent-attention layer: the
    direct query projection (no low rank), the key/value low-rank pair
    with the shared key features, the causal scores over nope + pe
    features and the weighted sum over v_head_dim (half of each:
    causal), the output projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, pe, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    rkv = cfg["kv_lora_rank"]
    proj = (2 * d * h * (nope + pe) + 2 * d * (rkv + pe)
            + 2 * rkv * h * (nope + dv) + 2 * h * dv * d)
    return float(proj + 2 * t * h * (nope + pe + dv) / 2)


def kda_flops_per_token(cfg: Dict, chunk: int) -> float:
    """... of one KDA layer: q | k | v, the two low-rank pairs (decay,
    output gate) and the write strength's projection, the output
    projection, and the rule."""
    d, la = cfg["hidden_size"], cfg["linear_attn_config"]
    h, dh = la["num_heads"], la["head_dim"]
    wide = h * dh
    return float(2 * d * 3 * wide + 2 * d * (2 * dh + h) + 2 * 2 * dh * wide
                 + 2 * wide * d + kda_scan_flops_per_token(cfg, chunk))


def kimilinear_train_flops(cfg: Dict, batch: int, t: int,
                           chunk: int = DEFAULT_CHUNK) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: every token runs its layer's mixer, the
    first ``first_k_dense_replace`` layers' dense SwiGLU, and in an
    expert layer the router over all the experts it scores, the shared
    expert and, of its ``num_experts_per_token`` routed experts, the
    EXPECTED held share: k * held / scored of a row a token (an even
    router; the rest of its experts are other chips' work and is not
    counted). backward = 2 x forward."""
    d, tok = cfg["hidden_size"], batch * t
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    n_kda, n_mla = layer_kinds(cfg)
    scored = cfg.get("router_experts", cfg["num_experts"])
    f = cfg["moe_intermediate_size"]
    moe = (2 * d * scored + 3 * 2 * d * cfg["num_shared_experts"] * f
           + cfg["num_experts_per_token"] * cfg["num_experts"] / scored
           * 3 * 2 * d * f)
    ffn = 3 * 2 * d * cfg["intermediate_size"]
    head = 2 * d * cfg["vocab_size"]
    return 3.0 * tok * (n_kda * kda_flops_per_token(cfg, chunk)
                        + n_mla * mla_flops_per_token(cfg, t)
                        + dense * ffn + (n - dense) * moe + head)


def mla_attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                       ) -> Dict[str, float]:
    """FLOPs and HBM bytes the latent-attention CALLS of one train step
    need (the sdpa op alone), forward + backward, from shapes alone: one
    causal call a latent layer at h heads, queries and keys nope + pe
    wide (192), values and output v_head_dim wide (128); counted as
    perf/flops_joyai.mla_attention_cost counts them."""
    h = cfg["num_attention_heads"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n = layer_kinds(cfg)[1]
    return {"flops": n * 3.0 * batch * h * t * t * (dk + dv),
            "bytes": float(n * 6 * batch * t * h * (dk + dv) * bytes_per_el),
            "calls": 2 * n}


def kda_scan_cost(cfg: Dict, batch: int, t: int, chunk: int = DEFAULT_CHUNK,
                  bytes_per_el: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes the KDA rule's calls of one train step need:
    the chunkwise form's matmul FLOPs at ``chunk`` (forward + 2 x
    backward; the backward pass's one recomputation, every exp and the
    inversion beyond half a product are not counted), against each of
    q, k, v, o (bf16), g [t, H, dk] and beta [t, H] (float32) and their
    gradients moved once. The projections, the convolutions and the
    gated norm around the rule are not in it."""
    n_kda, _ = layer_kinds(cfg)
    la = cfg["linear_attn_config"]
    tok, wide = batch * t, la["num_heads"] * la["head_dim"]
    moved = tok * (4 * wide * bytes_per_el + wide * 4 + la["num_heads"] * 4)
    return {"flops": 3.0 * n_kda * tok * kda_scan_flops_per_token(cfg, chunk),
            "bytes": float(2 * n_kda * moved), "calls": 2 * n_kda}
