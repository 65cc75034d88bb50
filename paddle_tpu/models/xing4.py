"""Xing4.0: a decoder-only language model of DeepSeek-V3's shape
(multi-head latent attention, leading dense layers, sigmoid-routed
experts with a selection bias beside an ungated shared expert, one
multi-token-prediction module: arXiv:2412.19437 2.1.1, 2.1.2, 2.2, as
``models/joyai_flash.py`` writes them out) whose residual path is
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606; the config's ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``) and
whose rotary table is yarn's, as DeepSeek applies it. As published
(29B-A4B):

With n = hc_mult streams X [b, t, n, d] (laid side by side along the
features, vec(X) [b, t, n d]: ops/hc_ops.py), and for EACH sublayer s (a
layer's attention and its feed-forward have their own Phi_s [n d, n^2 +
2n], b_s [n^2 + 2n], alpha_s [3]):

    r      = rsqrt(mean(vec(X)^2) + rms_norm_eps)     one a token, over n d
    m      = (vec(X) Phi_s) * r                        [n^2 + 2n], float32
    H_pre  = sigmoid(alpha_pre m[:n] + b_pre)                          [n]
    H_post = 2 sigmoid(alpha_post m[n:2n] + b_post)                    [n]
    M      = exp(clamp(alpha_res mat(m[2n:]) + b_res, -30, 30))     [n, n]
    20 x:  M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    H_res  = M
    h      = sum_i H_pre[i] X[i]                       the sublayer's input
    y      = F_s(h)     F_s = latent attention, the dense SwiGLU or the
                        expert layer, each behind its own input RMSNorm
    X'[j]  = sum_i H_res[j, i] X[i] + H_post[j] y

    read-in : every stream is the token's embedding
    read-out: the streams' sum -> final_norm -> the head (arXiv:2409.19606 3)

    MLA : joyai's, at ``q_lora_rank`` 768; the 64 rotary features (pairs
          (2i, 2i + 1), ONE key head all query heads share) turn by
          yarn's frequencies (factor 64 over 4096 original positions,
          beta_fast 32, beta_slow 1; cos and sin times mscale /
          mscale_all_dim = 1) and the softmax scale is
          mscale(factor, mscale_all_dim)^2 / sqrt(192), mscale(f, m) =
          0.1 m ln f + 1 (HF ``modeling_deepseek_v3.py``)
    MoE : s = sigmoid_f32(x Wr); chosen = top 4 of (s + b) among 64;
          w_j = 2 s_j / sum_chosen s; out = sum_j w_j SwiGLU_{e_j}(x) +
          SwiGLU_shared(x); b takes no gradient and moves by gamma *
          sign(mean(count) - count_e) a step; the sequence-wise balance
          loss at alpha
    LM  : logits = norm(sum_i X_L[i]) Wout (untied)
    MTP : h' = [norm_h(sum_i X_L[i]) | norm_e(Emb(t_{i+1}))] Weh   (the
          read-out BEFORE the final norm); Z = read-in(h'); one layer
          (MLA + MoE) under hyper-connections of its own; logits' =
          norm_mtp(sum_i Z'[i]) Wout, the model's own head
    L   = mean CE(logits_i, t_{i+1}) + lambda mean_{i < T-1}
          CE(logits'_i, t_{i+2}) + alpha * balance losses

Layers below ``first_k_dense_replace`` (2 as published) carry the dense
SwiGLU of ``intermediate_size``. ``held_experts=(first, count)`` builds
one chip's share of every expert layer, the MTP module's too.

What the paper and the config leave open, and what was taken
(perf/configs/xing4.0-29b-a4b.json ``assumed`` says where from): the
start values (Phi normal(0, 0.02); b at which the layer is a pre-norm
residual over the streams' mean, H_pre = 1 / n, H_post = 1, H_res near
the identity; alpha 0.01), ``hc_eps`` inside both divisions of every
iteration, no gain on the flattened norm, streams of the MTP block read
in from its merged input and summed out, bf16 streams under AMP.

Name scopes (README "Names in the device trace"): joyai's (``embed``,
``blk<i>/attn`` with ``q_lora``, ``kv_lora``, ``rope``, ``core``,
``out``; ``blk<i>/ffn``; ``blk<i>/moe`` with ``router``, ``dispatch``,
``experts``, ``shared``, ``combine``; ``blk_mtp/{merge,attn,moe}``,
``loss_head/mtp``; ``final_norm``, ``loss_head``) and, inside every
``attn`` / ``ffn`` / ``moe``, ``hc/mix``, ``hc/pre`` and ``hc/post``;
the read-in under ``embed`` (``blk_mtp/merge``), the read-out under
``final_norm`` (``blk_mtp/merge``).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401
from paddle_tpu.param_attr import ParamAttr

# logits of the last positions a build offers (model["last_logits"] and
# ["mtp_last_logits"]): the second check of perf/reference/xing4.py
LAST_POSITIONS = 8
_END = 2 ** 31 - 1   # a slice's "to the end"


class Xing4Config:
    """Keys as in the model's published ``config.json`` (defaults:
    Xing4.0-29B-A4B); ``bias_update_rate`` (gamma), ``balance_alpha``
    and ``mtp_lambda`` are DeepSeek-V3's training settings (the config
    carries none), ``held_experts`` is this builder's."""

    def __init__(
        self,
        vocab_size: int = 131072,
        hidden_size: int = 3584,
        num_hidden_layers: int = 40,
        first_k_dense_replace: int = 2,
        intermediate_size: int = 9216,
        num_attention_heads: int = 32,
        q_lora_rank: int = 768,
        kv_lora_rank: int = 512,
        qk_nope_head_dim: int = 128,
        qk_rope_head_dim: int = 64,
        v_head_dim: int = 128,
        rope_theta: float = 10000.0,
        rope_scaling: Optional[dict] = None,
        rms_norm_eps: float = 1e-6,
        n_routed_experts: int = 64,
        num_experts_per_tok: int = 4,
        moe_intermediate_size: int = 1024,
        n_shared_experts: int = 1,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.0,
        num_nextn_predict_layers: int = 1,
        hc_mult: int = 4,
        hc_sinkhorn_iters: int = 20,
        hc_eps: float = 1e-6,
        mhc_h_res_clamp_min: float = -30.0,
        mhc_h_res_clamp_max: float = 30.0,
        bias_update_rate: float = 0.001,
        balance_alpha: float = 1e-4,
        mtp_lambda: float = 0.1,
        held_experts: Optional[Tuple[int, int]] = None,
    ):
        assert num_nextn_predict_layers in (0, 1)
        assert 0 < first_k_dense_replace <= num_hidden_layers
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
        self.rope_theta = rope_theta
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.rms_norm_eps = rms_norm_eps
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.n_shared_experts = n_shared_experts
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.hc_mult = hc_mult
        self.hc_sinkhorn_iters = hc_sinkhorn_iters
        self.hc_eps = hc_eps
        self.mhc_h_res_clamp_min = mhc_h_res_clamp_min
        self.mhc_h_res_clamp_max = mhc_h_res_clamp_max
        self.bias_update_rate = bias_update_rate
        self.balance_alpha = balance_alpha
        self.mtp_lambda = mtp_lambda
        self.held_experts = tuple(held_experts) if held_experts else None

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def xing4_0_29b() -> Xing4Config:
    return Xing4Config(rope_scaling=YARN)


def yarn_mscale(factor: float, m: float) -> float:
    """DeepSeek's ``yarn_get_mscale``: 0.1 m ln(factor) + 1 above a
    factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_table_and_scale(cfg: Xing4Config):
    """(``layers.rotary_embedding``'s scaling dict or None, the softmax
    scale) of the config's ``rope_scaling``: DeepSeek's yarn multiplies
    cos and sin by mscale(factor, mscale) / mscale(factor,
    mscale_all_dim) and the softmax scale 1 / sqrt(nope + rope) by
    mscale(factor, mscale_all_dim)^2."""
    scale, rs = 1.0 / math.sqrt(cfg.qk_head_dim), cfg.rope_scaling
    if not rs:
        return None, scale
    assert rs.get("type", rs.get("rope_type")) == "yarn"
    factor = float(rs["factor"])
    all_dim = float(rs.get("mscale_all_dim") or 0.0)
    table = dict(
        factor=factor,
        original_max_position_embeddings=rs[
            "original_max_position_embeddings"],
        beta_fast=rs.get("beta_fast", 32), beta_slow=rs.get("beta_slow", 1),
        attention_factor=(yarn_mscale(factor, float(rs.get("mscale", 1)))
                          / yarn_mscale(factor, all_dim)))
    if all_dim:
        scale *= yarn_mscale(factor, all_dim) ** 2
    return table, scale


def _latent_attention(x, cfg: Xing4Config, p: str):
    table, scale = rope_table_and_scale(cfg)
    return decoder.latent_attention(
        x, p, heads=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, hidden=cfg.hidden_size,
        eps=cfg.rms_norm_eps, q_lora_rank=cfg.q_lora_rank,
        rope_theta=cfg.rope_theta, rope_scaling=table, softmax_scale=scale)


def _dense_ffn(x, cfg: Xing4Config, p: str):
    return decoder.swiglu_mlp(
        decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_ffn_norm"),
        cfg.intermediate_size, cfg.hidden_size, f"{p}_ffn_gate_colp.w",
        f"{p}_ffn_up_colp.w", f"{p}_ffn_down_rowp.w")


def _moe(x, cfg: Xing4Config, p: str):
    return layers.topk_moe(
        decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_moe_norm"),
        cfg.n_routed_experts,
        cfg.num_experts_per_tok, cfg.moe_intermediate_size,
        norm_topk_prob=cfg.norm_topk_prob, name=f"{p}_moe",
        held=cfg.held_experts,
        shared_d_ff=cfg.n_shared_experts * cfg.moe_intermediate_size,
        shared_gate=False, score="sigmoid",
        routed_scale=cfg.routed_scaling_factor, select_bias=True,
        bias_update_rate=cfg.bias_update_rate)


def read_in(x, n: int):
    """x [b, t, d] -> n streams side by side [b, t, n d], each a copy of
    x."""
    return layers.expand(x, [1, 1, n])


def read_out(xs, n: int):
    """The streams' sum [b, t, d]."""
    return decoder.sum_of(layers.split(xs, n, dim=-1))


def hyper_connected(xs, cfg: Xing4Config, p: str, sublayer):
    """Streams xs [b, t, n d] around ``sublayer`` (h [b, t, d] -> y, or
    -> (y, more)) -> (streams, more or None), inside the caller's
    ``attn`` / ``ffn`` / ``moe`` scope; the mix's parameters are
    ``<p>_hc_phi.w``, ``<p>_hc.bias`` and ``<p>_hc.alpha``."""
    with fluid.name_scope("hc"):
        with fluid.name_scope("mix"):
            h_pre, h_post, h_res = layers.hc_mix(
                xs, cfg.hc_mult, epsilon=cfg.rms_norm_eps, iters=cfg.hc_sinkhorn_iters,
                hc_eps=cfg.hc_eps,
                clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
                phi_attr=decoder.weight(f"{p}_hc_phi.w"),
                bias_attr=ParamAttr(name=f"{p}_hc.bias"),
                alpha_attr=ParamAttr(name=f"{p}_hc.alpha"))
        with fluid.name_scope("pre"):
            h = layers.hc_pre(xs, h_pre)
    y, more = sublayer(h), None
    if isinstance(y, tuple):
        y, more = y
    with fluid.name_scope("hc"), fluid.name_scope("post"):
        return layers.hc_post(xs, y, h_res, h_post), more


def _layer_body(xs, cfg: Xing4Config, p: str, dense: bool):
    """(streams, routing or None) of one decoder layer's two
    hyper-connected sublayers, inside the caller's ``blk`` scope."""
    with fluid.name_scope("attn"):
        xs, _ = hyper_connected(
            xs, cfg, f"{p}_attn", lambda h: _latent_attention(h, cfg, p))
    if dense:
        with fluid.name_scope("ffn"):
            return hyper_connected(
                xs, cfg, f"{p}_ffn", lambda h: _dense_ffn(h, cfg, p))

    def experts(h):
        out, lb, _, rows, top_i = _moe(h, cfg, p)
        return out, (lb, rows, top_i)

    with fluid.name_scope("moe"):
        return hyper_connected(xs, cfg, f"{p}_moe", experts)


def build(cfg: Optional[Xing4Config] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; the MTP
    module's targets, the token after that, are the labels shifted by
    one). Every position is real: packed documents, attended across
    their boundaries. The graph has no dropout, so ``is_test`` changes
    nothing."""
    cfg = cfg or xing4_0_29b()
    eps, table, n = cfg.rms_norm_eps, "xing4_tok_emb.w", cfg.hc_mult
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size, table)
    with fluid.name_scope("embed"):
        xs = read_in(x, n)
    lbs, rows, top_i = [], [], []

    def keep(routing):
        lbs.append(routing[0])
        rows.append(routing[1])
        top_i.append(routing[2])

    for i in range(cfg.num_hidden_layers):
        with fluid.name_scope(f"blk{i}"):
            xs, routing = _layer_body(xs, cfg, f"blk{i}",
                                      dense=i < cfg.first_k_dense_replace)
        if routing:
            keep(routing)
    with fluid.name_scope("final_norm"):
        x = read_out(xs, n)
        xn = decoder.rms_norm(x, eps, "final_norm")
    logits, lm_loss = decoder.lm_head(xn, lbl, cfg.vocab_size)
    model = {"logits": logits, "lm_loss": lm_loss}

    losses = [lm_loss]
    if cfg.num_nextn_predict_layers:
        with fluid.name_scope("blk_mtp"):
            with fluid.name_scope("merge"):
                # the next token's embedding, from the model's own table
                nxt = layers.embedding(
                    lbl, size=[cfg.vocab_size, cfg.hidden_size],
                    param_attr=decoder.weight(table))
                zs = read_in(decoder.linear(layers.concat(
                    [decoder.rms_norm(x, eps, "mtp_hnorm"),
                     decoder.rms_norm(nxt, eps, "mtp_enorm")], axis=2),
                    cfg.hidden_size, "mtp_eh_proj.w"), n)
            zs, routing = _layer_body(zs, cfg, "mtp", dense=False)
            keep(routing)
            with fluid.name_scope("merge"):
                zn = decoder.rms_norm(read_out(zs, n), eps,
                                      "mtp_final_norm")
        with fluid.name_scope("loss_head"):
            with fluid.name_scope("mtp"):
                # the model's own head, a second time
                mtp_logits = decoder.linear(zn, cfg.vocab_size,
                                            "lm_head_colp.w")
                # position i's second target is position i + 1's first;
                # the row's last position has none: it runs (every
                # kernel sees the whole row) and its loss is left out
                lbl2 = layers.concat(
                    [layers.slice(lbl, axes=[1], starts=[1], ends=[_END]),
                     layers.slice(lbl, axes=[1], starts=[-1], ends=[_END])],
                    axis=1)
                mtp_loss = layers.mean(layers.slice(
                    decoder.cross_entropy(mtp_logits, lbl2), axes=[1],
                    starts=[0], ends=[-1]))
        losses.append(layers.scale(mtp_loss, scale=cfg.mtp_lambda))
        model.update(mtp_logits=mtp_logits, mtp_loss=mtp_loss,
                     mtp_last_logits=decoder.last_logits(
                         mtp_logits, LAST_POSITIONS))

    with fluid.name_scope("loss_head"):
        lb_loss = decoder.sum_of(lbs)
        losses.append(layers.scale(lb_loss, scale=cfg.balance_alpha))
        loss = layers.sums(losses)
    model.update(feeds=[ids, lbl], loss=loss, lb_loss=lb_loss,
                 last_logits=decoder.last_logits(logits, LAST_POSITIONS),
                 expert_rows=rows, top_i=top_i, config=cfg)
    return model
