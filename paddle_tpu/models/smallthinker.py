"""SmallThinker: a decoder-only mixture-of-experts language model whose
attention layers alternate two kinds, three sliding-window layers with
rotary positions to one global layer with NO positional embedding, and
whose router reads the attention's input (PowerInfer 2025,
arXiv:2507.20984; HF ``modeling_smallthinker.py``). As published
(21B-A3B):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w              # plain gain
    layer i  : a = norm_in(x);  h = x + Attn_i(a)
               y = h + MoE(router reads a, experts read norm_post(h))
    LM       : logits = norm(y_L) Wout (untied);  loss = mean next-token cross
               entropy + aux_coef * load-balancing loss (mean over the layers)

    Attn_i (h query heads, hk key/value heads of dh; no bias, no QK-norm):
      q = a Wq (d -> h dh);  k = a Wk, v = a Wv (d -> hk dh)
      rope_layout[i] == 1: RoPE (rotate-half, the whole head) on q and k;
                     == 0: q and k as they are (NoPE: no op is appended)
      sliding_window_layout[i] == 0: visible(p, s) = s <= p
                               == 1: s <= p and p - s < sliding_window_size
      o = softmax(q k^T / sqrt(dh) over visible) v, kv head = q head // (h / hk)
      out = o Wo (h dh -> d)

    MoE:  l = a Wr (f32);  the k largest of softmax(l), renormalised over
          the k (= HF's softmax over the k chosen logits)
          out = sum_j w_j (relu(z Wg[e_j]) * (z Wu[e_j])) Wd[e_j],  z = norm_post(h)

q|k|v are one matrix: one pass over ``a``; the order inside is storage.
``held_experts=(first, count)`` builds one chip's share of every expert
layer (``layers.topk_moe(held=...)``). The published secondary experts
and sparse ReGLU predictor have no key in ``config.json`` and are not
built.

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/attn`` with ``qkv``, ``rope`` (layers that rotate), the sdpa op
under ``swa`` in a window layer and under ``core`` in a global one, and
``out``; ``blk<i>/moe`` with ``router``, ``dispatch``, ``experts`` and
``combine``; ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401

# logits of the last positions a build offers (model["last_logits"]):
# 64 of one row (perf/reference/smallthinker.py says why)
LAST_POSITIONS = 64
# The embedding table starts at normal(0, 1) (torch's nn.Embedding
# default), every other matrix at normal(0, 0.02): with the table at 0.02
# too, an untrained layer's attention output (a running mean over up to
# 16k values, nearly the same for every late position) is as large as
# the token's own row, every token routes alike and a layer's routing
# flips from step to step (PERF.md section 6, PR 38).
EMBEDDING_INIT_STD = 1.0


class SmallThinkerConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    SmallThinker-21BA3B-Instruct); ``router_aux_loss_coef`` and
    ``held_experts`` are this builder's. The two layouts may be shorter
    or longer than the stack: layer i reads entry i % len."""

    def __init__(
        self,
        vocab_size: int = 151936,
        hidden_size: int = 2560,
        num_hidden_layers: int = 52,
        num_attention_heads: int = 28,
        num_key_value_heads: int = 4,
        head_dim: int = 128,
        rope_theta: float = 1.5e6,
        rms_norm_eps: float = 1e-6,
        sliding_window_size: int = 4096,
        sliding_window_layout: Sequence[int] = (0, 1, 1, 1),
        rope_layout: Sequence[int] = (0, 1, 1, 1),
        moe_num_primary_experts: int = 64,
        moe_num_active_primary_experts: int = 6,
        moe_ffn_hidden_size: int = 768,
        norm_topk_prob: bool = True,
        router_aux_loss_coef: float = 0.001,
        held_experts: Optional[Tuple[int, int]] = None,
    ):
        assert num_attention_heads % num_key_value_heads == 0
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.sliding_window_size = sliding_window_size
        self.sliding_window_layout = tuple(sliding_window_layout)
        self.rope_layout = tuple(rope_layout)
        self.moe_num_primary_experts = moe_num_primary_experts
        self.moe_num_active_primary_experts = moe_num_active_primary_experts
        self.moe_ffn_hidden_size = moe_ffn_hidden_size
        self.norm_topk_prob = norm_topk_prob
        self.router_aux_loss_coef = router_aux_loss_coef
        self.held_experts = tuple(held_experts) if held_experts else None

    def window(self, i: int) -> Optional[int]:
        """The positions layer i's queries see, None for all before."""
        layout = self.sliding_window_layout
        return self.sliding_window_size if layout[i % len(layout)] else None

    def rotates(self, i: int) -> bool:
        return bool(self.rope_layout[i % len(self.rope_layout)])


def smallthinker_21b_a3b() -> SmallThinkerConfig:
    return SmallThinkerConfig()


def _attention(a, cfg: SmallThinkerConfig, p: str, i: int):
    """Attn_i of the normalised input ``a`` [b, t, d]."""
    h, hk, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    window = cfg.window(i)

    def by_head(z, n):   # [b, t, n dh] -> [b, t, n, dh]
        return layers.reshape(z, [0, 0, n, dh])

    def heads_first(z, n):   # [b, t, n dh] -> [b, n, t, dh]
        return layers.transpose(by_head(z, n), [0, 2, 1, 3])

    with fluid.name_scope("qkv"):
        qkv = decoder.linear(a, (h + 2 * hk) * dh, f"{p}_attn_qkv_colp.w")
        q, k, v = layers.split(qkv, [h * dh, hk * dh, hk * dh], dim=-1)
        v = heads_first(v, hk)
        if not cfg.rotates(i):
            q, k = heads_first(q, h), heads_first(k, hk)
    if cfg.rotates(i):
        with fluid.name_scope("rope"):
            # q and k where the projection left them: the op transposes
            # as it rotates
            q, k = layers.rotary_embedding(by_head(q, h), by_head(k, hk),
                                           theta=cfg.rope_theta,
                                           layout="bthd")
    with fluid.name_scope("swa" if window else "core"):
        # K and V keep their hk heads: the kernels read head q // (h / hk)
        ctx = layers.scaled_dot_product_attention(
            q, k, v, 1.0 / math.sqrt(dh), window=window,
            name=f"{p}_attn_sdpa")
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dh])
        return decoder.linear(ctx, cfg.hidden_size, f"{p}_attn_out_rowp.w")


def decoder_layer(x, cfg: SmallThinkerConfig, i: int):
    """(y, load-balancing loss, rows per held expert, experts chosen per
    token) of layer i."""
    p, eps = f"blk{i}", cfg.rms_norm_eps
    with fluid.name_scope(p):
        with fluid.name_scope("attn"):
            a = decoder.rms_norm(x, eps, f"{p}_attn_norm")
            x = layers.elementwise_add(x, _attention(a, cfg, p, i))
        with fluid.name_scope("moe"):
            out, lb, _, rows, top_i = layers.topk_moe(
                decoder.rms_norm(x, eps, f"{p}_moe_norm"),
                cfg.moe_num_primary_experts,
                cfg.moe_num_active_primary_experts, cfg.moe_ffn_hidden_size,
                norm_topk_prob=cfg.norm_topk_prob, name=f"{p}_moe",
                held=cfg.held_experts, act="relu", router_input=a)
            x = layers.elementwise_add(x, out)
    return x, lb, rows, top_i


def build(cfg: Optional[SmallThinkerConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended across their boundaries). The
    graph has no dropout, so ``is_test`` changes nothing."""
    cfg = cfg or smallthinker_21b_a3b()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "smallthinker_tok_emb.w", EMBEDDING_INIT_STD)
    lbs, rows, top_i = [], [], []
    for i in range(cfg.num_hidden_layers):
        x, lb, r, ti = decoder_layer(x, cfg, i)
        lbs.append(lb)
        rows.append(r)
        top_i.append(ti)
    with fluid.name_scope("final_norm"):
        x = decoder.rms_norm(x, cfg.rms_norm_eps, "final_norm")

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
