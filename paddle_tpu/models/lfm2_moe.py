"""LFM2-24B-A2B: a decoder-only hybrid whose sequence mixers are gated
short convolutions, with a grouped-query attention layer every fourth
block, two dense SwiGLU layers in front of sigmoid-routed SwiGLU experts
(HF ``modeling_lfm2_moe.py``: ``Lfm2MoeDecoderLayer``,
``Lfm2MoeShortConv``, ``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock``).
As published (23.84B-A2.3B with the table tied, 40 blocks):

    norm(x)   = x * rsqrt(mean(x^2) + eps) * w        # eps 1e-5, no bias
    block i   : h = x + Op_i(norm_op(x));  out = h + FF_i(norm_ffn(h))

    Op_i, layer_types[i] == "conv"  (the gated short convolution):
      [B | C | u] = n W_in             # [d, 3d], thirds in THAT order
      v = B * u
      c_t = sum_{j < L} w[:, j] v_{t - (L - 1) + j}   # depthwise, causal,
                                       # L = conv_L_cache taps, zeros in
                                       # front, no bias, NO activation
      Op = (C * c) W_out               # layers.short_conv_gate: ONE op

    Op_i, "full_attention" (h query heads over hk key/value heads of dh):
      q, k, v = n W_q, n W_k, n W_v
      q = norm_dh(q), k = norm_dh(k)   # per head, one gain [dh] each
      q, k = rope(q), rope(k)          # rotate-half, the whole head
      Op = (causal softmax(q k^T / sqrt(dh)) v) W_o

    FF_i, i < num_dense_layers: (silu(n W_1) * (n W_3)) W_2 at
      intermediate_size
    FF_i otherwise (E experts, top k):
      s = sigmoid_f32(n W_r);  chosen = top k of (s + b)
      w_j = routed_scaling_factor * s_j / sum_chosen s
      out = sum_j w_j (silu(n Wg[e_j]) * (n Wu[e_j])) Wd[e_j]
      b [E] (``expert_bias``) takes no gradient; after each step b_e +=
      gamma * sign(mean(count) - count_e)
      (``layers.topk_moe(select_bias=True)``); balance loss: the
      sequence-wise alpha * sum_e f_e P_e

    LM : logits = norm(x_L) E^T over the embedding table E (tied; HF's
         ``embedding_norm`` is this final norm);
         L = mean CE(logits_i, t_{i+1}) + alpha * balance losses

``first_layer`` / ``num_hidden_layers``: the blocks this builder makes,
first_layer .. first_layer + num_hidden_layers - 1 of ``layer_types``,
with their published indices (a cut keeps them: every block reads
``layer_types[i]`` and ``i < num_dense_layers`` for itself, and the
parameters' names and the name scopes carry i).
``held_experts=(first, count)`` builds one chip's share of every expert
layer (``layers.topk_moe(held=...)``).

Name scopes (README "Names in the device trace"): ``embed``;
``blk<i>/sconv`` with ``in_proj``, ``gconv`` (the gated convolution's
one op) and ``out_proj`` under it; ``blk<i>/attn`` with ``qkv``,
``qk_norm``, ``rope``, ``core`` (the sdpa op) and ``out``;
``blk<i>/ffn`` (a dense layer) or ``blk<i>/moe`` with ``router``,
``dispatch``, ``experts``, ``combine``; ``final_norm``, ``loss_head``. A
branch's pre-norm lies in the branch's scope.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import UniformInitializer
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401
from paddle_tpu.param_attr import ParamAttr

# logits of the last positions a build offers (model["last_logits"]):
# the second check of perf/reference/lfm2moe.py
LAST_POSITIONS = 64
LAYER_TYPES = (("conv", "conv", "full_attention")
               + ("conv", "conv", "conv", "full_attention") * 9 + ("conv",))
KINDS = {"conv": "sconv", "full_attention": "attn"}
TABLE = "lfm2_tok_emb.w"


class Lfm2MoeConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    LFM2-24B-A2B; ``rope_theta`` is its ``rope_parameters.rope_theta``);
    ``bias_update_rate`` (gamma) and ``balance_alpha`` are training
    settings the config does not carry, ``first_layer`` and
    ``held_experts`` this builder's."""

    def __init__(
        self,
        vocab_size: int = 65536,
        hidden_size: int = 2048,
        intermediate_size: int = 11776,
        num_hidden_layers: int = 40,
        layer_types: Sequence[str] = LAYER_TYPES,
        first_layer: int = 0,
        num_dense_layers: int = 2,
        norm_eps: float = 1e-5,
        # the gated short convolution
        conv_L_cache: int = 3,
        conv_bias: bool = False,
        # attention
        num_attention_heads: int = 32,
        num_key_value_heads: int = 8,
        rope_theta: float = 1e6,
        # experts
        num_experts: int = 64,
        num_experts_per_tok: int = 4,
        moe_intermediate_size: int = 1536,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 1.0,
        use_expert_bias: bool = True,
        bias_update_rate: float = 0.001,
        balance_alpha: float = 1e-4,
        held_experts: Optional[Tuple[int, int]] = None,
    ):
        layer_types = tuple(layer_types)
        last = first_layer + num_hidden_layers
        if not (0 <= first_layer < last <= len(layer_types)):
            raise ValueError(f"blocks {first_layer}..{last - 1} of "
                             f"{len(layer_types)} layer_types")
        if set(layer_types) - set(KINDS):
            raise ValueError(f"layer_types {sorted(set(layer_types))}: a "
                             f"mixer is one of {sorted(KINDS)}")
        if conv_bias:
            raise NotImplementedError("a bias on the gated convolution")
        if hidden_size % num_attention_heads:
            raise ValueError("hidden_size is not whole heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = layer_types
        self.first_layer = first_layer
        self.num_dense_layers = num_dense_layers
        self.norm_eps = norm_eps
        self.conv_L_cache = conv_L_cache
        self.conv_bias = conv_bias
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.rope_theta = rope_theta
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.use_expert_bias = use_expert_bias
        self.bias_update_rate = bias_update_rate
        self.balance_alpha = balance_alpha
        self.held_experts = tuple(held_experts) if held_experts else None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def blocks(self):
        """[(published index, mixer kind, dense FF or not)] of the blocks
        this builder makes."""
        return [(i, KINDS[self.layer_types[i]], i < self.num_dense_layers)
                for i in range(self.first_layer,
                               self.first_layer + self.num_hidden_layers)]


def lfm2_24b_a2b() -> Lfm2MoeConfig:
    return Lfm2MoeConfig()


def _short_conv(n, cfg: Lfm2MoeConfig, p: str):
    """The gated short convolution of the normalised input n [b, t, d]."""
    d = cfg.hidden_size
    with fluid.name_scope("in_proj"):
        bcu = decoder.linear(n, 3 * d, f"{p}_sconv_in_colp.w")
    with fluid.name_scope("gconv"):
        # torch's Conv1d default (HF's _init_weights re-draws Linear and
        # Embedding only): uniform(+-1 / sqrt(taps)), as the other
        # builders' convolutions
        bound = cfg.conv_L_cache ** -0.5
        y = layers.short_conv_gate(
            bcu, taps=cfg.conv_L_cache, param_attr=ParamAttr(
                name=f"{p}_sconv_conv.w",
                initializer=UniformInitializer(-bound, bound)))
    with fluid.name_scope("out_proj"):
        return decoder.linear(y, d, f"{p}_sconv_out_rowp.w")


def _attention(n, cfg: Lfm2MoeConfig, p: str):
    """Grouped-query attention of the normalised input n [b, t, d]:
    per-head QK-norm, then rotary positions over the whole head."""
    h, hk, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)

    def by_head(z, heads):   # [b, t, heads dh] -> [b, t, heads, dh]
        return layers.reshape(z, [0, 0, heads, dh])

    with fluid.name_scope("qkv"):
        qkv = decoder.linear(n, (h + 2 * hk) * dh, f"{p}_attn_qkv_colp.w")
        q, k, v = layers.split(qkv, [h * dh, hk * dh, hk * dh], dim=-1)
        v = layers.transpose(by_head(v, hk), [0, 2, 1, 3])
    with fluid.name_scope("qk_norm"):
        # over each head's dh
        q = decoder.rms_norm(by_head(q, h), cfg.norm_eps, f"{p}_attn_qnorm")
        k = decoder.rms_norm(by_head(k, hk), cfg.norm_eps, f"{p}_attn_knorm")
    with fluid.name_scope("rope"):
        # q and k where the projection left them: the op transposes as
        # it rotates
        q, k = layers.rotary_embedding(q, k, theta=cfg.rope_theta,
                                       layout="bthd")
    with fluid.name_scope("core"):
        # K and V keep their hk heads: the kernels read head q // (h / hk)
        ctx = layers.scaled_dot_product_attention(
            q, k, v, 1.0 / math.sqrt(dh), name=f"{p}_attn_sdpa")
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dh])
        return decoder.linear(ctx, cfg.hidden_size, f"{p}_attn_out_rowp.w")


def _dense_ffn(n, cfg: Lfm2MoeConfig, p: str):
    return decoder.swiglu_mlp(
        n, cfg.intermediate_size, cfg.hidden_size, f"{p}_ffn_w1_colp.w",
        f"{p}_ffn_w3_colp.w", f"{p}_ffn_w2_rowp.w")


def _moe(n, cfg: Lfm2MoeConfig, p: str):
    return layers.topk_moe(
        n, cfg.num_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
        name=f"{p}_moe", held=cfg.held_experts, score="sigmoid",
        routed_scale=cfg.routed_scaling_factor,
        select_bias=cfg.use_expert_bias,
        bias_update_rate=cfg.bias_update_rate)


def block(x, cfg: Lfm2MoeConfig, i: int, kind: str, dense: bool):
    """(block i of x, the expert layer's (balance loss, rows per held
    expert, experts chosen per token) or None)."""
    p = f"blk{i}"
    routing = None
    with fluid.name_scope(p):
        with fluid.name_scope(kind):
            n = decoder.rms_norm(x, cfg.norm_eps, f"{p}_op_norm")
            op = (_short_conv if kind == "sconv" else _attention)(n, cfg, p)
            x = layers.elementwise_add(x, op)
        with fluid.name_scope("ffn" if dense else "moe"):
            n = decoder.rms_norm(x, cfg.norm_eps, f"{p}_ffn_norm")
            if dense:
                out = _dense_ffn(n, cfg, p)
            else:
                out, lb, _, rows, top_i = _moe(n, cfg, p)
                routing = (lb, rows, top_i)
            x = layers.elementwise_add(x, out)
    return x, routing


def build(cfg: Optional[Lfm2MoeConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended and convolved across their
    boundaries, no reset of the taps). The graph has no dropout, so
    ``is_test`` changes nothing."""
    cfg = cfg or lfm2_24b_a2b()
    ids, lbl = decoder.token_feeds()
    # HF's initializer_range, the table's too
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size, TABLE)
    lbs, rows, top_i = [], [], []
    for i, kind, dense in cfg.blocks:
        x, routing = block(x, cfg, i, kind, dense)
        if routing:
            lbs.append(routing[0])
            rows.append(routing[1])
            top_i.append(routing[2])
    with fluid.name_scope("final_norm"):
        x = decoder.rms_norm(x, cfg.norm_eps, "final_norm")

    logits, lm_loss = decoder.tied_lm_head(x, lbl, TABLE)
    loss, lb_loss = lm_loss, None
    if lbs:
        with fluid.name_scope("loss_head"):
            lb_loss = decoder.sum_of(lbs)
            loss = layers.sums([
                lm_loss, layers.scale(lb_loss, scale=cfg.balance_alpha)])
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
