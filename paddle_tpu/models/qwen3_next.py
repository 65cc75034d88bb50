"""Qwen3-Next: a decoder-only language model whose layers alternate two
token mixers, three Gated DeltaNet (linear attention) layers to one
gated softmax-attention layer, each followed by a mixture of experts
with a shared expert (Qwen team 2025; HF ``modeling_qwen3_next.py``;
Gated DeltaNet: arXiv:2412.06464). As published (80B-A3B):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * (1 + w)        # zero-centred gain
    layer i  : h = x + Mixer_i(norm(x));  y = h + MoE(norm(h))
               Mixer_i is full attention where (i + 1) % 4 == 0, else DeltaNet
    LM       : logits = norm(y_L) Wout (untied);  loss = mean next-token cross
               entropy + aux_coef * load-balancing loss (mean over the layers)

    Gated attention (h query heads, hk key/value heads, head width dh):
      [q | gate] = x Wq per head;  k = x Wk;  v = x Wv
      q, k = norm(q), norm(k) over each head's dh;  RoPE (rotate-half) on
      the first dh * partial_rotary_factor features of each head
      o = causal softmax(q k^T / sqrt(dh)) v, kv head = q head // (h / hk)
      out = (o * sigmoid(gate)) Wo

    Gated DeltaNet (hk key heads of dk, hv value heads of dv):
      q, k, v, z = x Wqkvz;  b, a = x Wba
      [q|k|v] <- silu(causal depthwise conv over the sequence, 4 taps)
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
      o = gated_delta_rule(q, k, v, g, beta)   # layers.gated_delta_rule
      out = (rmsnorm_dv(o) * w_n * silu(z)) Wo

    MoE:  p = softmax_f32(x Wr) over all experts; top k, renormalised
          out = sum_j p_j SwiGLU_{e_j}(x) + sigmoid(x w_s) * SwiGLU_shared(x)

The fused projections (q|gate|k|v of attention, q|k|v|z and b|a of the
DeltaNet) are one matrix each: one pass over x; the order inside is
storage, not mathematics. ``held_experts=(first, count)`` builds one
chip's share of every expert layer (``layers.topk_moe(held=...)``): the
router scores all ``num_experts``, the chip holds ``count`` of them.
The published multi-token-prediction module is not built.

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/gdn`` with ``proj``, ``conv``, ``rule``, ``gate_norm`` and
``out`` under it (``rule``, not ``scan``: jax puts that word into op
names itself and a reader of the trace stops at it), ``blk<i>/attn``,
``blk<i>/moe`` with ``router``, ``dispatch``, ``experts``, ``shared`` and
``combine``, ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401
from paddle_tpu.param_attr import ParamAttr

# logits of the last positions a build offers (model["last_logits"]): 64
# of one row, where OLMoE offers 8 of each of two (perf/reference/
# qwen3next.py says why)
LAST_POSITIONS = 64


class Qwen3NextConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    Qwen3-Next-80B-A3B); ``router_aux_loss_coef`` is HF's default,
    ``held_experts`` and the ``gdn_*`` pair are this builder's."""

    def __init__(
        self,
        vocab_size: int = 151936,
        hidden_size: int = 2048,
        num_hidden_layers: int = 48,
        full_attention_interval: int = 4,
        num_attention_heads: int = 16,
        num_key_value_heads: int = 2,
        head_dim: int = 256,
        partial_rotary_factor: float = 0.25,
        rope_theta: float = 1e7,
        rms_norm_eps: float = 1e-6,
        linear_conv_kernel_dim: int = 4,
        linear_key_head_dim: int = 128,
        linear_value_head_dim: int = 128,
        linear_num_key_heads: int = 16,
        linear_num_value_heads: int = 32,
        num_experts: int = 512,
        num_experts_per_tok: int = 10,
        moe_intermediate_size: int = 512,
        shared_expert_intermediate_size: int = 512,
        norm_topk_prob: bool = True,
        router_aux_loss_coef: float = 0.001,
        held_experts: Optional[Tuple[int, int]] = None,
        gdn_chunk: int = 64,
        gdn_impl: str = "chunked",
    ):
        assert num_attention_heads % num_key_value_heads == 0
        assert linear_num_value_heads % linear_num_key_heads == 0
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.full_attention_interval = full_attention_interval
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.router_aux_loss_coef = router_aux_loss_coef
        self.held_experts = tuple(held_experts) if held_experts else None
        self.gdn_chunk = gdn_chunk
        self.gdn_impl = gdn_impl

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0


def qwen3_next_80b_a3b() -> Qwen3NextConfig:
    return Qwen3NextConfig()


def _norm(x, cfg, name):
    return layers.rms_norm(x, epsilon=cfg.rms_norm_eps, zero_centered=True,
                           param_attr=ParamAttr(name=f"{name}.scale"))


def _attention(x, cfg: Qwen3NextConfig, p: str):
    h, hk, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    qgkv = decoder.linear(_norm(x, cfg, f"{p}_attn_norm"),
                          2 * (h + hk) * dh, f"{p}_attn_qgkv_colp.w")
    qg, k, v = layers.split(qgkv, [2 * h * dh, hk * dh, hk * dh], dim=-1)
    # per head: the query's dh features, then its gate's
    q, gate = layers.split(layers.reshape(qg, [0, 0, h, 2 * dh]), 2, dim=-1)
    k, v = (layers.reshape(z, [0, 0, hk, dh]) for z in (k, v))
    q = _norm(q, cfg, f"{p}_attn_qnorm")     # over each head's dh
    k = _norm(k, cfg, f"{p}_attn_knorm")

    def heads_first(z):   # [b, t, heads, dh] -> [b, heads, t, dh]
        return layers.transpose(z, [0, 2, 1, 3])

    q, k = layers.rotary_embedding(
        heads_first(q), heads_first(k), theta=cfg.rope_theta,
        rotary_dim=cfg.rotary_dim)
    # K and V keep their hk heads: the kernels read head q // (h / hk)
    ctx = layers.scaled_dot_product_attention(
        q, k, heads_first(v), 1.0 / math.sqrt(dh), name=f"{p}_attn_sdpa")
    ctx = layers.elementwise_mul(layers.transpose(ctx, [0, 2, 1, 3]),
                                 layers.sigmoid(gate))
    return decoder.linear(layers.reshape(ctx, [0, 0, h * dh]),
                          cfg.hidden_size, f"{p}_attn_out_rowp.w")


def _delta_net(x, cfg: Qwen3NextConfig, p: str):
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kd, vd = hk * dk, hv * dv
    xn = _norm(x, cfg, f"{p}_gdn_norm")
    with fluid.name_scope("proj"):
        qkvz = decoder.linear(xn, 2 * kd + 2 * vd, f"{p}_gdn_qkvz_colp.w")
        b, a = layers.split(decoder.linear(xn, 2 * hv, f"{p}_gdn_ba.w"), 2,
                            dim=-1)
        qkv, z = layers.split(qkvz, [2 * kd + vd, vd], dim=-1)
    with fluid.name_scope("conv"):
        qkv = layers.causal_conv1d(
            qkv, taps=cfg.linear_conv_kernel_dim, act="silu",
            param_attr=decoder.weight(f"{p}_gdn_conv.w"))
        q, k, v = layers.split(qkv, [kd, kd, vd], dim=-1)
    with fluid.name_scope("rule"):
        beta, g = layers.gdn_gates(
            b, a, a_log_attr=ParamAttr(name=f"{p}_gdn_A_log"),
            dt_bias_attr=ParamAttr(name=f"{p}_gdn_dt_bias"))
        o = layers.gated_delta_rule(
            layers.reshape(q, [0, 0, hk, dk]),
            layers.reshape(k, [0, 0, hk, dk]),
            layers.reshape(v, [0, 0, hv, dv]), g, beta,
            chunk=cfg.gdn_chunk, impl=cfg.gdn_impl)
    with fluid.name_scope("gate_norm"):
        o = layers.gated_rms_norm(
            o, layers.reshape(z, [0, 0, hv, dv]), epsilon=cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{p}_gdn_onorm.scale"))
    with fluid.name_scope("out"):
        return decoder.linear(layers.reshape(o, [0, 0, vd]),
                              cfg.hidden_size, f"{p}_gdn_out_rowp.w")


def _moe(x, cfg: Qwen3NextConfig, p: str):
    return layers.topk_moe(
        _norm(x, cfg, f"{p}_moe_norm"), cfg.num_experts,
        cfg.num_experts_per_tok, cfg.moe_intermediate_size,
        norm_topk_prob=cfg.norm_topk_prob, name=f"{p}_moe",
        held=cfg.held_experts,
        shared_d_ff=cfg.shared_expert_intermediate_size)


def decoder_layer(x, cfg: Qwen3NextConfig, i: int):
    """(y, load-balancing loss, rows per held expert, experts chosen per
    token) of layer i."""
    p = f"blk{i}"
    with fluid.name_scope(p):
        if cfg.is_full_attention(i):
            with fluid.name_scope("attn"):
                x = layers.elementwise_add(x, _attention(x, cfg, p))
        else:
            with fluid.name_scope("gdn"):
                x = layers.elementwise_add(x, _delta_net(x, cfg, p))
        with fluid.name_scope("moe"):
            out, lb, _, rows, top_i = _moe(x, cfg, p)
            x = layers.elementwise_add(x, out)
    return x, lb, rows, top_i


def build(cfg: Optional[Qwen3NextConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended across their boundaries). The
    graph has no dropout, so ``is_test`` changes nothing."""
    cfg = cfg or qwen3_next_80b_a3b()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "qwen3next_tok_emb.w")
    lbs, rows, top_i = [], [], []
    for i in range(cfg.num_hidden_layers):
        x, lb, r, ti = decoder_layer(x, cfg, i)
        lbs.append(lb)
        rows.append(r)
        top_i.append(ti)
    with fluid.name_scope("final_norm"):
        x = _norm(x, cfg, "final_norm")

    logits, lm_loss = decoder.lm_head(x, lbl, cfg.vocab_size)
    with fluid.name_scope("loss_head"):
        lb_loss = layers.scale(decoder.sum_of(lbs), scale=1.0 / len(lbs))
        loss = layers.sums([
            lm_loss, layers.scale(lb_loss, scale=cfg.router_aux_loss_coef)])
    return {
        "feeds": [ids, lbl],
        "loss": loss,
        "lm_loss": lm_loss,
        "lb_loss": lb_loss,
        "logits": logits,
        "last_logits": decoder.last_logits(logits, LAST_POSITIONS),
        "expert_rows": rows,
        "top_i": top_i,
        "config": cfg,
    }
