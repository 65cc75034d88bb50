"""JoyAI-LLM-Flash: a decoder-only language model of DeepSeek-V3's
shape (its ``config.json`` has DeepSeek-V3's keys; the layer equations:
arXiv:2412.19437 sections 2.1-2.2 and HF ``modeling_deepseek_v3.py``):
multi-head latent attention (MLA) whose queries and keys are wider than
its values, a leading dense layer, then mixtures of experts routed by
sigmoid scores with a selection bias beside an ungated shared expert,
and one multi-token-prediction (MTP) module. As published (48B-A2.7B):

    norm_n(x) = x * rsqrt(mean(x^2) + eps) * w                  # plain gain
    layer i   : h = x + MLA(norm(x));  y = h + FFN_i(norm(h))
                FFN_i = SwiGLU at ``intermediate_size`` for i <
                first_k_dense_replace, else the MoE

    MLA (h heads; nope, rope, dv = qk_nope_head_dim, qk_rope_head_dim,
    v_head_dim):
      c_q = norm(x Wqa);  q = c_q Wqb        -> per head [q_nope | q_rope]
      [c_kv | k_rope] = x Wkva;  c_kv <- norm(c_kv)
      [k_nope | v] = c_kv Wkvb               -> per head [nope | dv]
      q_rope, k_rope <- RoPE over their ``rope`` features, pairs
        (2i, 2i + 1); k_rope is ONE head that all h query heads share
      q = [q_nope | q_rope], k = [k_nope | k_rope]  (nope + rope wide)
      o = causal softmax(q k^T / sqrt(nope + rope)) v  (dv);  out = o Wo
      (rope_scaling null: no yarn factor on the scale. HF de-interleaves
      the rope features before a rotate-half: the scores are those above.)

    MoE (E experts, top k, one group):
      s = sigmoid_f32(x Wr);  chosen = top k of (s + b)
      w_j = routed_scaling_factor * s_j / sum_chosen s
      out = sum_j w_j SwiGLU_{e_j}(x) + SwiGLU_shared(x)    # no gate
      b [E] takes no gradient; after each step b_e += gamma *
      sign(mean(count) - count_e) (``layers.topk_moe(select_bias=True)``)
      balance loss: the sequence-wise alpha * sum_e f_e P_e

    LM  : logits = norm(y_L) Wout (untied);  L_main = mean CE(logits_i, t_{i+1})
    MTP : h'_i = [norm_h(y_L,i) | norm_e(Emb(t_{i+1}))] Weh   # y_L before the
          final norm; Emb is the model's own table
          z = Layer_mtp(h')  (one MLA + MoE layer, weights of its own)
          logits' = norm_mtp(z) Wout                      # the model's own head
          L = L_main + lambda * mean_{i < T-1} CE(logits'_i, t_{i+2})
              + alpha * balance losses

The MTP layer runs over all T positions and the last (which has no
t_{T+1} in the row) is left out of its loss: causal attention lets it
change no other position. ``Emb`` and ``Wout`` are each one parameter
with two uses; ``append_backward`` sums the two gradients.
``held_experts=(first, count)`` builds one chip's share of every expert
layer (``layers.topk_moe(held=...)``), the MTP module's too.

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/attn`` with ``q_lora``, ``kv_lora``, ``rope`` (the heads to
the front, the splits and the rotation: the sdpa op takes q and k in two
parts), ``core`` (the sdpa op) and ``out`` under it, ``blk0/ffn``,
``blk<i>/moe`` with ``router``, ``dispatch``, ``experts``, ``shared`` and
``combine``; the MTP module ``blk_mtp/{merge,attn,moe}`` and its pass
through the head ``loss_head/mtp``; ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401

# logits of the last positions a build offers (model["last_logits"] and
# ["mtp_last_logits"]): the second check of perf/reference/joyai.py
LAST_POSITIONS = 8
_END = 2 ** 31 - 1   # a slice's "to the end"


class JoyaiFlashConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    JoyAI-LLM-Flash); ``bias_update_rate`` (gamma), ``balance_alpha``
    and ``mtp_lambda`` are the paper's training settings (the config
    carries none), ``held_experts`` is this builder's."""

    def __init__(
        self,
        vocab_size: int = 129280,
        hidden_size: int = 2048,
        num_hidden_layers: int = 40,
        first_k_dense_replace: int = 1,
        intermediate_size: int = 7168,
        num_attention_heads: int = 32,
        q_lora_rank: int = 1536,
        kv_lora_rank: int = 512,
        qk_nope_head_dim: int = 128,
        qk_rope_head_dim: int = 64,
        v_head_dim: int = 128,
        rope_theta: float = 3.2e7,
        rms_norm_eps: float = 1e-6,
        n_routed_experts: int = 256,
        num_experts_per_tok: int = 8,
        moe_intermediate_size: int = 768,
        n_shared_experts: int = 1,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.5,
        num_nextn_predict_layers: int = 1,
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
        self.rms_norm_eps = rms_norm_eps
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.n_shared_experts = n_shared_experts
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.bias_update_rate = bias_update_rate
        self.balance_alpha = balance_alpha
        self.mtp_lambda = mtp_lambda
        self.held_experts = tuple(held_experts) if held_experts else None

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def joyai_llm_flash() -> JoyaiFlashConfig:
    return JoyaiFlashConfig()


def _latent_attention(x, cfg: JoyaiFlashConfig, p: str):
    return decoder.latent_attention(
        x, p, heads=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, hidden=cfg.hidden_size,
        eps=cfg.rms_norm_eps, q_lora_rank=cfg.q_lora_rank,
        rope_theta=cfg.rope_theta)


def _dense_ffn(x, cfg: JoyaiFlashConfig, p: str):
    return decoder.swiglu_mlp(
        decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_ffn_norm"),
        cfg.intermediate_size, cfg.hidden_size, f"{p}_ffn_gate_colp.w",
        f"{p}_ffn_up_colp.w", f"{p}_ffn_down_rowp.w")


def _moe(x, cfg: JoyaiFlashConfig, p: str):
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


def _layer_body(x, cfg: JoyaiFlashConfig, p: str, dense: bool):
    """(y, routing or None) of one decoder layer's two residual
    branches, inside the caller's ``blk`` scope."""
    with fluid.name_scope("attn"):
        x = layers.elementwise_add(x, _latent_attention(x, cfg, p))
    if dense:
        with fluid.name_scope("ffn"):
            return layers.elementwise_add(x, _dense_ffn(x, cfg, p)), None
    with fluid.name_scope("moe"):
        out, lb, _, rows, top_i = _moe(x, cfg, p)
        return layers.elementwise_add(x, out), (lb, rows, top_i)


def build(cfg: Optional[JoyaiFlashConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; the MTP
    module's targets, the token after that, are the labels shifted by
    one). Every position is real: packed documents, attended across
    their boundaries. The graph has no dropout, so ``is_test`` changes
    nothing."""
    cfg = cfg or joyai_llm_flash()
    eps, table = cfg.rms_norm_eps, "joyai_tok_emb.w"
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size, table)
    lbs, rows, top_i = [], [], []

    def keep(routing):
        lbs.append(routing[0])
        rows.append(routing[1])
        top_i.append(routing[2])

    for i in range(cfg.num_hidden_layers):
        with fluid.name_scope(f"blk{i}"):
            x, routing = _layer_body(x, cfg, f"blk{i}",
                                     dense=i < cfg.first_k_dense_replace)
        if routing:
            keep(routing)
    with fluid.name_scope("final_norm"):
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
                z = decoder.linear(layers.concat(
                    [decoder.rms_norm(x, eps, "mtp_hnorm"),
                     decoder.rms_norm(nxt, eps, "mtp_enorm")], axis=2),
                    cfg.hidden_size, "mtp_eh_proj.w")
            z, routing = _layer_body(z, cfg, "mtp", dense=False)
            keep(routing)
            with fluid.name_scope("merge"):
                zn = decoder.rms_norm(z, eps, "mtp_final_norm")
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
        # this slice carries no scope and never did: a scope is an attr of
        # the op, and the cell's compiled step is keyed by the program
        model.update(mtp_logits=mtp_logits, mtp_loss=mtp_loss,
                     mtp_last_logits=layers.slice(
                         mtp_logits, axes=[1], starts=[-LAST_POSITIONS],
                         ends=[_END]))

    with fluid.name_scope("loss_head"):
        lb_loss = decoder.sum_of(lbs)
        losses.append(layers.scale(lb_loss, scale=cfg.balance_alpha))
        loss = layers.sums(losses)
    model.update(feeds=[ids, lbl], loss=loss, lb_loss=lb_loss,
                 last_logits=decoder.last_logits(logits, LAST_POSITIONS),
                 expert_rows=rows, top_i=top_i, config=cfg)
    return model
