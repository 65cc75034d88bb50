"""Operations and bytes of the JoyAI-LLM-Flash family (latent attention,
a dense first layer, sigmoid-routed experts beside a shared one, a
multi-token-prediction module), from shapes alone (the conventions of
perf/flops.py: a multiply-add counts 2, recomputation does not count,
embedding lookups are left out)."""

from __future__ import annotations

from typing import Dict


def mla_blocks(cfg: Dict) -> int:
    """Latent-attention blocks of the stack: its layers and the MTP
    module's one."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def mla_flops_per_token(cfg: Dict, t: int) -> float:
    """Forward matmul FLOPs a token of one latent-attention block: the
    two low-rank pairs (query; key/value with the shared rotary key),
    the causal scores over nope + rope features and the weighted sum
    over v_head_dim (half of each: causal), the output projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    proj = (2 * d * rq + 2 * rq * h * (nope + rope)
            + 2 * d * (rkv + rope) + 2 * rkv * h * (nope + dv)
            + 2 * h * dv * d)
    return float(proj + 2 * t * h * (nope + rope + dv) / 2)


def joyai_train_flops(cfg: Dict, batch: int, t: int) -> float:
    """Forward + backward matmul FLOPs of one train step at the ACTIVE
    parameters ON THIS CHIP: every token runs each layer's latent
    attention, the first ``first_k_dense_replace`` layers' dense SwiGLU,
    and in an expert layer the router over all the experts it scores,
    the shared expert and, of its ``num_experts_per_tok`` routed
    experts, the EXPECTED held share: k * held / scored of a row a
    token (an even router; the rest of its experts are other chips'
    work and is not counted). The MTP module adds its merge
    projection, one more block (attention and experts) and a SECOND
    pass through the head: both head passes count. backward = 2 x
    forward."""
    d, tok = cfg["hidden_size"], batch * t
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mtp = cfg["num_nextn_predict_layers"]
    scored = cfg.get("router_experts", cfg["n_routed_experts"])
    f = cfg["moe_intermediate_size"]
    moe = (2 * d * scored + 3 * 2 * d * cfg["n_shared_experts"] * f
           + cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / scored
           * 3 * 2 * d * f)
    ffn = 3 * 2 * d * cfg["intermediate_size"]
    head = 2 * d * cfg["vocab_size"]
    return 3.0 * tok * ((n + mtp) * mla_flops_per_token(cfg, t)
                        + dense * ffn + (n - dense + mtp) * moe
                        + mtp * 2 * (2 * d) * d + (1 + mtp) * head)


def mla_attention_cost(cfg: Dict, batch: int, t: int, bytes_per_el: int = 2
                       ) -> Dict[str, float]:
    """FLOPs and HBM bytes the latent-attention CALLS of one train step
    need (the sdpa op alone, not the projections around it), forward +
    backward, from shapes alone: one causal call a block at h heads,
    queries and keys nope + rope wide (192), values and output
    v_head_dim wide (128). A head's forward pass is q.k^T over dk and
    p.v over dv, 2 * (t^2 / 2) * (dk + dv) FLOPs (causal: half); the
    backward pass dv, dp (over dv) and dq, dk (over dk): twice that
    (the recomputed q.k^T of a flash kernel is not counted). Bytes:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv: six tensors of width dk and six of dv, each
    moved once."""
    h = cfg["num_attention_heads"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n = mla_blocks(cfg)
    return {"flops": n * 3.0 * batch * h * t * t * (dk + dv),
            "bytes": float(n * 6 * batch * t * h * (dk + dv) * bytes_per_el),
            "calls": 2 * n}
