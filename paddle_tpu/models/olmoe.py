"""OLMoE: a decoder-only mixture-of-experts language model (Muennighoff
et al. 2024, arXiv:2409.02060; HF ``modeling_olmoe.py``), the zoo's first
decoder-only builder. One block, as published:

    h = x + Attn(RMSNorm(x));   y = h + MoE(RMSNorm(h))     # pre-norm, no bias
    Attn: q, k, v = x Wq, x Wk, x Wv;  q, k = RMSNorm(q), RMSNorm(k) over
          the WHOLE width, before the split into heads;  RoPE (rotate-half)
          on q and k;  causal softmax(q k^T / sqrt(dh)) v;  out = o Wo
    MoE:  p = softmax_f32(x Wr) over all experts; the top k of p, not
          renormalised;  out = sum_j p_j * (silu(x Wg[e_j]) * (x Wu[e_j])) Wd[e_j]
          every chosen (token, expert) pair computed, no capacity
    LM:   logits = RMSNorm(y_L) Wout (untied);  loss = mean next-token cross
          entropy + aux_coef * load-balancing loss + z_coef * router z-loss,
          each auxiliary loss the mean over the layers

Wq, Wk, Wv are stored as one [d, 3d] matrix (one pass over x, as
models/transformer.py does); the mathematics is the three projections'.
The ``*_colp`` / ``*_rowp`` parameter names are parallel/strategy's
tensor-parallel rules', as in the other transformer builders.

Name scopes (framework.name_scope; README "Names in the device trace"):
``embed``, ``blk<i>/attn``, ``blk<i>/moe`` with ``router``, ``dispatch``,
``experts`` and ``combine`` under it, ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

import math
from typing import Optional

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401

# logits of the last positions a build offers (model["last_logits"]): what
# a comparison with a reference can hold at a 50k vocabulary
LAST_POSITIONS = 8


class OlmoeConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    OLMoE-1B-7B); the two auxiliary loss weights are the paper's."""

    def __init__(
        self,
        vocab_size: int = 50304,
        hidden_size: int = 2048,
        intermediate_size: int = 1024,
        num_hidden_layers: int = 16,
        num_attention_heads: int = 16,
        num_experts: int = 64,
        num_experts_per_tok: int = 8,
        norm_topk_prob: bool = False,
        rms_norm_eps: float = 1e-5,
        rope_theta: float = 10000.0,
        router_aux_loss_coef: float = 0.01,
        router_z_loss_coef: float = 0.001,
    ):
        assert hidden_size % num_attention_heads == 0
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.router_aux_loss_coef = router_aux_loss_coef
        self.router_z_loss_coef = router_z_loss_coef

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def olmoe_1b_7b() -> OlmoeConfig:
    return OlmoeConfig()


def _attention(x, cfg: OlmoeConfig, p: str):
    h, dh, d = cfg.num_attention_heads, cfg.head_dim, cfg.hidden_size
    eps = cfg.rms_norm_eps
    qkv = decoder.linear(decoder.rms_norm(x, eps, f"{p}_attn_norm"), 3 * d,
                         f"{p}_attn_qkv_colp.w")
    q, k, v = layers.split(qkv, 3, dim=-1)
    q = decoder.rms_norm(q, eps, f"{p}_attn_qnorm")
    k = decoder.rms_norm(k, eps, f"{p}_attn_knorm")

    def by_head(z):   # [b, t, d] -> [b, t, h, dh]
        return layers.reshape(z, [0, 0, h, dh])

    def heads(z):   # [b, t, d] -> [b, h, t, dh]
        return layers.transpose(by_head(z), [0, 2, 1, 3])

    # q and k where the norms left them: the op transposes as it rotates
    q, k = layers.rotary_embedding(by_head(q), by_head(k),
                                   theta=cfg.rope_theta, layout="bthd")
    ctx = layers.scaled_dot_product_attention(
        q, k, heads(v), 1.0 / math.sqrt(dh), name=f"{p}_attn_sdpa")
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, d])
    return decoder.linear(ctx, d, f"{p}_attn_out_rowp.w")


def _moe(x, cfg: OlmoeConfig, p: str):
    return layers.topk_moe(
        decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_moe_norm"),
        cfg.num_experts,
        cfg.num_experts_per_tok, cfg.intermediate_size,
        norm_topk_prob=cfg.norm_topk_prob, name=f"{p}_moe")


def decoder_block(x, cfg: OlmoeConfig, i: int):
    """(y, load-balancing loss, z-loss, rows per expert, experts chosen
    per token) of block i."""
    p = f"blk{i}"
    with fluid.name_scope(p):
        with fluid.name_scope("attn"):
            x = layers.elementwise_add(x, _attention(x, cfg, p))
        with fluid.name_scope("moe"):
            out, *routing = _moe(x, cfg, p)
            x = layers.elementwise_add(x, out)
    return (x, *routing)


def _mean_of(xs):
    return layers.scale(decoder.sum_of(xs), scale=1.0 / len(xs))


def build(cfg: Optional[OlmoeConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended across their boundaries). The
    graph has no dropout, so ``is_test`` changes nothing."""
    cfg = cfg or olmoe_1b_7b()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "olmoe_tok_emb.w")
    routing = []
    for i in range(cfg.num_hidden_layers):
        x, *r = decoder_block(x, cfg, i)
        routing.append(r)
    lbs, zs, rows, top_i = (list(col) for col in zip(*routing))
    with fluid.name_scope("final_norm"):
        x = decoder.rms_norm(x, cfg.rms_norm_eps, "final_norm")

    logits, lm_loss = decoder.lm_head(x, lbl, cfg.vocab_size)
    with fluid.name_scope("loss_head"):
        lb_loss, z_loss = _mean_of(lbs), _mean_of(zs)
        loss = layers.sums([
            lm_loss,
            layers.scale(lb_loss, scale=cfg.router_aux_loss_coef),
            layers.scale(z_loss, scale=cfg.router_z_loss_coef)])
    return {
        "feeds": [ids, lbl],
        "loss": loss,
        "lm_loss": lm_loss,
        "lb_loss": lb_loss,
        "z_loss": z_loss,
        "logits": logits,
        "last_logits": decoder.last_logits(logits, LAST_POSITIONS),
        "expert_rows": rows,
        "top_i": top_i,
        "config": cfg,
    }
