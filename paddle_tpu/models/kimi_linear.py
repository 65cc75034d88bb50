"""Kimi Linear: a decoder-only language model whose layers alternate two
token mixers, three Kimi Delta Attention (KDA) layers to one latent-
attention layer with NO positional embedding, a leading dense layer and
then mixtures of experts of DeepSeek-V3's kind ("Kimi Linear: An
Expressive, Efficient Attention Architecture", arXiv:2510.26692; HF
``modeling_kimi.py`` beside the published ``config.json``; ``fla``'s
``KimiDeltaAttention`` / ``chunk_kda``). As published (48B-A3B, 27
layers; ``linear_attn_config`` numbers them from 1: KDA at 1, 2, 3, 5,
6, 7, .., 25, 26, latent attention at 4, 8, .., 24, 27):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w                    # eps 1e-5, plain gain, f32 statistics
    layer i  : h = x + Mix_i(norm(x));  y = h + FFN_i(norm(h))
    KDA (H = 32 heads, dk = dv = 128; no positional embedding):
      q = silu(conv4(a Wq)),  k = silu(conv4(a Wk)),  v = silu(conv4(a Wv))   # 2304 -> 4096 each; three
                                                    # causal depthwise convolutions of 4 taps, no bias
      q, k <- q / |q|, k / |k| over each head's 128 features;  q <- q / sqrt(128)
      g    = -exp(A_log[h]) * softplus((a Wfa) Wfb + dt_bias)     # 2304 -> 128 -> 4096: [t, H, dk], f32, <= 0
      beta = sigmoid(a Wb)                                        # 2304 -> 32: [t, H], f32
      per head, S in R^{dk x dv} from zero:
        S_t = Diag(exp(g_t)) S_{t-1};   S_t += k_t (beta_t (v_t - S_t^T k_t))^T;   o_t = S_t^T q_t
      out  = (norm_head(o) * sigmoid((a Wga) Wgb)) Wo             # norm over each head's 128, gain [128];
                                                                  # gate 2304 -> 128 -> 4096; Wo 4096 -> 2304
    MLA (32 heads; nope 128, "rope" 64, dv 128; q_lora_rank null; mla_use_nope true):
      q = a Wq                        -> per head 192 = [q_nope | q_pe]      # 2304 -> 6144, no low rank
      [c_kv | k_pe] = a Wkva;  c_kv <- norm(c_kv)                           # 2304 -> 512 + 64
      [k_nope | v] = c_kv Wkvb        -> per head [128 | 128]
      k = [k_nope | k_pe],  k_pe ONE 64-wide head that all 32 query heads share; NOTHING is rotated
      o = causal softmax(q k^T / sqrt(192)) v;  out = o Wo                   # 4096 -> 2304
    FFN_1 : SwiGLU at 9216.   FFN_i, i > 1 : DeepSeek-V3's MoE as models/joyai_flash.py states it:
      s = sigmoid_f32(z Wr) over ALL 256; the 8 largest of (s + b) (one group: num_expert_group 1,
      topk_group 1 restrict nothing); w_j = 2.446 * s_j / sum_chosen s (moe_renormalize true);
      out = sum_j w_j SwiGLU_{e_j}(z) at 1024 + SwiGLU_shared(z) at 1024, ungated
    LM    : logits = norm(y_L) Wout (untied, 163,840 ids);  loss = mean CE(logits_i, t_{i+1})
            + alpha * balance losses;  num_nextn_predict_layers 0: no MTP module

The rule runs in its chunkwise form (``layers.gated_delta_rule`` with g
[b, t, H, dk]: the rank of g decides; ops/linear_attention_ops.py and
parallel/gated_delta_rule.py, ``kda.rule.fwd`` / ``kda.rule.bwd``), the
three convolutions as ``causal_conv1d`` calls (``gdn.conv.*``), latent
attention through ``decoder.latent_attention`` (the one JoyAI-LLM-Flash
builds, here with neither a query low rank nor a rotation).
``held_experts=(first, count)`` builds one chip's share of every expert
layer (``layers.topk_moe(held=...)``).

What ``config.json`` carries no key for (perf/configs/kimi-linear-48b-
a3b.json, ``assumed``): the low-rank pairs of the decay and of the
output gate are ``head_dim`` wide and have no bias (HF; ``fla``'s own
layer puts a bias on the gate's second matrix); A_log = log U(1, 16) a
head and dt_bias the inverse softplus of dt ~ logU(1e-3, 0.1) a feature,
float32 both; the bias update and the balance loss are DeepSeek-V3's.
The three projections q | k | v are ONE matrix here (one pass over the
token; the order inside is storage, not mathematics), and so are the
two low-rank pairs' first matrices with Wb (2304 -> 128 + 128 + 32).

Name scopes (README "Names in the device trace"): ``embed``;
``blk<i>/kda`` with ``proj`` (the three projections, the two low-rank
pairs, Wb), ``conv``, ``rule`` (the gates and the op), ``gate_norm`` and
``out`` under it; ``blk<i>/attn`` with ``q``, ``kv_lora``, ``rope``
(here only the heads' move to the front and the splits: the name stays
so that one reader serves every latent family), ``core``
and ``out``; ``blk0/ffn``; ``blk<i>/moe`` with ``router``, ``dispatch``,
``experts``, ``shared`` and ``combine``; ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import (InverseSoftplusInitializer,
                                    LogUniformInitializer)
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401
from paddle_tpu.param_attr import ParamAttr

# logits of the last positions a build offers (model["last_logits"]): the
# second check of perf/reference/kimilinear.py
LAST_POSITIONS = 8

_PUBLISHED_KDA = [i for i in range(1, 28) if i % 4 and i != 27]


class KimiLinearConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    Kimi-Linear-48B-A3B-Instruct), ``linear_attn_config`` the nested
    group as published (layers numbered from 1); ``bias_update_rate``
    (gamma) and ``balance_alpha`` are DeepSeek-V3's training settings
    (the config carries none), ``held_experts`` and ``kda_chunk`` are
    this builder's. ``head_dim``, ``num_key_value_heads`` and
    ``rope_theta`` are published and not read (``mla_use_nope``)."""

    def __init__(
        self,
        vocab_size: int = 163840,
        hidden_size: int = 2304,
        num_hidden_layers: int = 27,
        first_k_dense_replace: int = 1,
        intermediate_size: int = 9216,
        num_attention_heads: int = 32,
        q_lora_rank: Optional[int] = None,
        kv_lora_rank: int = 512,
        qk_nope_head_dim: int = 128,
        qk_rope_head_dim: int = 64,
        v_head_dim: int = 128,
        mla_use_nope: bool = True,
        rms_norm_eps: float = 1e-5,
        linear_attn_config: Optional[Dict] = None,
        num_experts: int = 256,
        num_experts_per_token: int = 8,
        moe_intermediate_size: int = 1024,
        num_shared_experts: int = 1,
        moe_renormalize: bool = True,
        routed_scaling_factor: float = 2.446,
        num_nextn_predict_layers: int = 0,
        bias_update_rate: float = 0.001,
        balance_alpha: float = 1e-4,
        held_experts: Optional[Tuple[int, int]] = None,
        kda_chunk: int = 64,
    ):
        assert mla_use_nope, "the published model rotates nothing"
        assert num_nextn_predict_layers == 0, "no MTP module is published"
        assert 0 < first_k_dense_replace <= num_hidden_layers
        la = dict(linear_attn_config or {
            "kda_layers": _PUBLISHED_KDA,
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4})
        kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
        layer_numbers = set(range(1, num_hidden_layers + 1))
        assert not kda & full and layer_numbers <= kda | full, (
            "every layer is in exactly one of the two published lists")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.intermediate_size = intermediate_size
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.mla_use_nope = mla_use_nope
        self.rms_norm_eps = rms_norm_eps
        self.linear_attn_config = la
        self.num_experts = num_experts
        self.num_experts_per_token = num_experts_per_token
        self.moe_intermediate_size = moe_intermediate_size
        self.num_shared_experts = num_shared_experts
        self.moe_renormalize = moe_renormalize
        self.routed_scaling_factor = routed_scaling_factor
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.bias_update_rate = bias_update_rate
        self.balance_alpha = balance_alpha
        self.held_experts = tuple(held_experts) if held_experts else None
        self.kda_chunk = kda_chunk

    def is_kda(self, i: int) -> bool:
        """Whether layer i (from 0) mixes by Kimi Delta Attention: read
        from the published list (numbered from 1), never from a period
        (the published tail 25, 26, 27 is none)."""
        return i + 1 in self.linear_attn_config["kda_layers"]

    def dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace


def kimi_linear_48b_a3b() -> KimiLinearConfig:
    return KimiLinearConfig()


def _kda(x, cfg: KimiLinearConfig, p: str):
    la = cfg.linear_attn_config
    h, dh, taps = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    wide = h * dh
    xn = decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_kda_norm")
    with fluid.name_scope("proj"):
        qkv = decoder.linear(xn, 3 * wide, f"{p}_kda_qkv_colp.w")
        # the first matrices of the two low-rank pairs (decay, output
        # gate) and the write strength's projection, one pass over xn
        f_a, g_a, b = layers.split(
            decoder.linear(xn, 2 * dh + h, f"{p}_kda_fgb.w"), [dh, dh, h],
            dim=-1)
        a = decoder.linear(f_a, wide, f"{p}_kda_f_b_colp.w")
        z = decoder.linear(g_a, wide, f"{p}_kda_g_b_colp.w")
    with fluid.name_scope("conv"):
        # three depthwise convolutions: their channels side by side are
        # one call of 3 * wide channels, the same numbers
        q, k, v = (
            layers.reshape(y, [0, 0, h, dh]) for y in layers.split(
                layers.causal_conv1d(
                    qkv, taps=taps, act="silu",
                    param_attr=decoder.weight(f"{p}_kda_conv.w")), 3, dim=-1))
    with fluid.name_scope("rule"):
        beta, g = layers.gdn_gates(
            b, layers.reshape(a, [0, 0, h, dh]),
            a_log_attr=ParamAttr(name=f"{p}_kda_A_log",
                                 initializer=LogUniformInitializer(1.0, 16.0)),
            dt_bias_attr=ParamAttr(
                name=f"{p}_kda_dt_bias",
                initializer=InverseSoftplusInitializer(1e-3, 0.1)))
        o = layers.gated_delta_rule(q, k, v, g, beta, chunk=cfg.kda_chunk)
    with fluid.name_scope("gate_norm"):
        o = layers.gated_rms_norm(
            o, layers.reshape(z, [0, 0, h, dh]), epsilon=cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{p}_kda_onorm.scale"),
            gate_act="sigmoid")
    with fluid.name_scope("out"):
        return decoder.linear(layers.reshape(o, [0, 0, wide]),
                              cfg.hidden_size, f"{p}_kda_out_rowp.w")


def _latent_attention(x, cfg: KimiLinearConfig, p: str):
    return decoder.latent_attention(
        x, p, heads=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, hidden=cfg.hidden_size,
        eps=cfg.rms_norm_eps, q_lora_rank=cfg.q_lora_rank, rope_theta=None)


def _dense_ffn(x, cfg: KimiLinearConfig, p: str):
    return decoder.swiglu_mlp(
        decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_ffn_norm"),
        cfg.intermediate_size, cfg.hidden_size, f"{p}_ffn_gate_colp.w",
        f"{p}_ffn_up_colp.w", f"{p}_ffn_down_rowp.w")


def _moe(x, cfg: KimiLinearConfig, p: str):
    return layers.topk_moe(
        decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_moe_norm"),
        cfg.num_experts, cfg.num_experts_per_token,
        cfg.moe_intermediate_size, norm_topk_prob=cfg.moe_renormalize,
        name=f"{p}_moe", held=cfg.held_experts,
        shared_d_ff=cfg.num_shared_experts * cfg.moe_intermediate_size,
        shared_gate=False, score="sigmoid",
        routed_scale=cfg.routed_scaling_factor, select_bias=True,
        bias_update_rate=cfg.bias_update_rate)


def decoder_layer(x, cfg: KimiLinearConfig, i: int):
    """(y, routing or None) of layer i (from 0): (balance loss, rows per
    held expert, experts chosen per token) of an expert layer."""
    p = f"blk{i}"
    with fluid.name_scope(p):
        if cfg.is_kda(i):
            with fluid.name_scope("kda"):
                x = layers.elementwise_add(x, _kda(x, cfg, p))
        else:
            with fluid.name_scope("attn"):
                x = layers.elementwise_add(x, _latent_attention(x, cfg, p))
        if cfg.dense(i):
            with fluid.name_scope("ffn"):
                return layers.elementwise_add(x, _dense_ffn(x, cfg, p)), None
        with fluid.name_scope("moe"):
            out, lb, _, rows, top_i = _moe(x, cfg, p)
            return layers.elementwise_add(x, out), (lb, rows, top_i)


def build(cfg: Optional[KimiLinearConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended and carried in the state across
    their boundaries). The graph has no dropout, so ``is_test`` changes
    nothing."""
    cfg = cfg or kimi_linear_48b_a3b()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "kimilinear_tok_emb.w")
    lbs, rows, top_i = [], [], []
    for i in range(cfg.num_hidden_layers):
        x, routing = decoder_layer(x, cfg, i)
        if routing:
            lbs.append(routing[0])
            rows.append(routing[1])
            top_i.append(routing[2])
    with fluid.name_scope("final_norm"):
        x = decoder.rms_norm(x, cfg.rms_norm_eps, "final_norm")
    logits, lm_loss = decoder.lm_head(x, lbl, cfg.vocab_size)
    with fluid.name_scope("loss_head"):
        lb_loss = decoder.sum_of(lbs)
        loss = layers.sums(
            [lm_loss, layers.scale(lb_loss, scale=cfg.balance_alpha)])
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
