"""Keye-VL-2.0's language model: a decoder-only mixture-of-experts model
(a Qwen3-MoE block: grouped-query attention with per-head QK-norm, 128
SwiGLU experts top-8) whose attention is SPARSE BY A LEARNED CHOICE: a
lightning indexer (DeepSeek Sparse Attention: DeepSeek-V3.2-Exp's
technical report, equations 1-4, and its public inference code) scores
every earlier position for every query, the query keeps its ``topk``
best, and the main attention reads only those. Positions are multi-axis
rotary (Qwen2-VL's M-RoPE): a FED [3, t] tensor (temporal, height,
width), frequency pair i of a head's 64 turning by the axis
``mrope_section`` gives it. HF ``model_type`` ``KeyeVL2``; as published
(30B-A3B), for a layer, x [t, d], positions pos [3, t]:

    a   = RMSNorm(x)
    q   = a Wq [t, h, dh]    k = a Wk [t, hk, dh]    v = a Wv [t, hk, dh]
    q, k <- per-head RMSNorm with a gain (over each head's dh features),
           then rotate-half rotary: pair i of dh / 2 turns by
           pos[axis(i), t] * theta^(-2i / dh), axis(i) = 0 (i < 16),
           1 (i < 40), 2 for mrope_section [16, 24, 24]
    a'  = stop_gradient(a)                      the indexer hears no model loss
    qI  = a' WqI [t, hI, dI]    kI = LayerNorm(a' WkI) [t, dI], ONE head
    w   = a' Ww [t, hI]
    qI, kI: the first ``indexer_rope_dim`` features rotated (rotate-half)
           at the temporal axis pos[0]
    I[t, s] = hI^-1/2 dI^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])   s <= t, f32
    S_t = the min(t + 1, topk) positions s <= t of largest I[t, s], ties
          to the lower s
    o[t, i] = sum_{s in S_t} softmax_{s in S_t}(q[t, i] . k[s, i // (h / hk)]
                                                / sqrt(dh)) v[s, i // (h / hk)]
    y   = x + concat_i(o) Wo
    p[t, s] = 1/h sum_i softmax_{s in S_t}(...)[s]     detached
    L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s]
                                         - log softmax_{s in S_t}(I[t, .])[s])
    z   = RMSNorm(y);  out = y + sum over the chosen k of E experts
          (softmax over ALL experts, the k largest renormalised) SwiGLU

    loss = next-token cross entropy + aux_coef * load-balancing loss (mean
           over the layers) + index_loss_coef * mean over the layers of L_I

This is DeepSeek-V3.2-Exp's SPARSE training stage: the top-k passes no
gradient; WqI, WkI, Ww and the LayerNorm hear L_I alone (their input is
detached, the target p is detached), everything else hears the other two
terms alone. ``stage="warmup"`` builds its dense warm-up stage: the
attention reads every s <= t, L_I runs over every s <= t, and the loss
is the mean of L_I alone (the model is frozen: no other parameter gets a
gradient).

q|k|v are one matrix: one pass over ``a``; the order inside is storage.
``held_experts=(first, count)`` builds one chip's share of every expert
layer (``layers.topk_moe(held=...)``). The vision tower is not built:
text rows feed three equal position rows.

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/attn`` with ``qkv``, ``rope`` (the per-head QK-norm is inside
the rotary op), ``dsa`` (``proj``: the indexer's three projections, the
LayerNorm and its rotation; ``select``: the op ``dsa_select``, the index
scores a chunk of queries and their top-k; ``loss``: ``dsa_index_loss``),
the sdpa op under ``core`` and ``out``; ``blk<i>/moe`` with ``router``,
``dispatch``, ``experts`` and ``combine``; ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.param_attr import ParamAttr

# logits of the last positions a build offers (model["last_logits"]),
# and those rows of every layer's selection beside them
# (model["last_selected"]): 64 of one row (perf/reference/keye.py)
LAST_POSITIONS = 64
# The embedding table starts at normal(0, 1) (torch's nn.Embedding
# default), as smallthinker's: at 0.02 an untrained layer's attention
# output is as large as the token's own row (PERF.md section 6, PR 38).
EMBEDDING_INIT_STD = 1.0


class KeyeConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    Keye-VL-2.0-30B-A3B's language model; ``sa_config``'s keys flat:
    ``indexer_num_heads``, ``indexer_head_dim``, ``topk``,
    ``q_chunk_size``, ``kv_chunk_size``; its ``indexer_num_kv_heads`` is
    1 and built in); ``indexer_rope_dim``, ``index_loss_coef``,
    ``router_aux_loss_coef``, ``stage`` ("sparse" or "warmup") and
    ``held_experts`` are this builder's."""

    def __init__(
        self,
        vocab_size: int = 151936,
        hidden_size: int = 2048,
        num_hidden_layers: int = 48,
        num_attention_heads: int = 32,
        num_key_value_heads: int = 4,
        head_dim: int = 128,
        rope_theta: float = 1e7,
        mrope_section: Sequence[int] = (16, 24, 24),
        rms_norm_eps: float = 1e-6,
        num_experts: int = 128,
        num_experts_per_tok: int = 8,
        moe_intermediate_size: int = 768,
        norm_topk_prob: bool = True,
        router_aux_loss_coef: float = 0.001,
        indexer_num_heads: int = 16,
        indexer_head_dim: int = 64,
        topk: int = 2048,
        q_chunk_size: int = 512,
        kv_chunk_size: int = 512,
        indexer_rope_dim: int = 32,
        index_loss_coef: float = 1.0,
        stage: str = "sparse",
        held_experts: Optional[Tuple[int, int]] = None,
    ):
        assert num_attention_heads % num_key_value_heads == 0
        assert sum(mrope_section) == head_dim // 2, (mrope_section, head_dim)
        assert stage in ("sparse", "warmup"), stage
        assert 0 < indexer_rope_dim <= indexer_head_dim
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.mrope_section = tuple(int(n) for n in mrope_section)
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.router_aux_loss_coef = router_aux_loss_coef
        self.indexer_num_heads = indexer_num_heads
        self.indexer_head_dim = indexer_head_dim
        self.topk = topk
        self.q_chunk_size = q_chunk_size
        self.kv_chunk_size = kv_chunk_size
        self.indexer_rope_dim = indexer_rope_dim
        self.index_loss_coef = index_loss_coef
        self.stage = stage
        self.held_experts = tuple(held_experts) if held_experts else None


def keye_vl2_30b_a3b() -> KeyeConfig:
    return KeyeConfig()


def _indexer(a, pos, cfg: KeyeConfig, p: str):
    """(qI [b, hI, t, dI], kI [b, 1, t, dI], w [b, t, hI]) of the
    normalised input ``a``, detached: nothing behind this line hears the
    model's loss."""
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    a = layers.assign(a)
    a.stop_gradient = True
    qi = decoder.linear(a, hi * di, f"{p}_attn_idx_q.w")
    ki = layers.layer_norm(
        decoder.linear(a, di, f"{p}_attn_idx_k.w"), begin_norm_axis=2,
        epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=f"{p}_attn_idx_knorm.scale"),
        bias_attr=ParamAttr(name=f"{p}_attn_idx_knorm.bias"))
    w = decoder.linear(a, hi, f"{p}_attn_idx_w.w")
    qi, ki = layers.rotary_embedding(
        layers.reshape(qi, [0, 0, hi, di]), layers.reshape(ki, [0, 0, 1, di]),
        theta=cfg.rope_theta, rotary_dim=cfg.indexer_rope_dim,
        layout="bthd", positions=pos)
    return qi, ki, w


def _attention(a, pos, cfg: KeyeConfig, p: str):
    """(Attn of the normalised input ``a`` [b, t, d], the layer's L_I
    [1], its selection (bits, live table): ``layers.dsa_select``'s)."""
    h, hk, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    scale = 1.0 / math.sqrt(dh)
    tiles = dict(q_chunk=cfg.q_chunk_size, kv_chunk=cfg.kv_chunk_size)
    sparse = cfg.stage == "sparse"

    def by_head(z, n):   # [b, t, n dh] -> [b, t, n, dh]
        return layers.reshape(z, [0, 0, n, dh])

    with fluid.name_scope("qkv"):
        qkv = decoder.linear(a, (h + 2 * hk) * dh, f"{p}_attn_qkv_colp.w")
        q, k, v = layers.split(qkv, [h * dh, hk * dh, hk * dh], dim=-1)
        v = layers.transpose(by_head(v, hk), [0, 2, 1, 3])
    with fluid.name_scope("rope"):
        # q and k where the projection left them: the op norms each head
        # over its dh (QK-norm) and transposes as it rotates, one pass,
        # at the fed positions
        q, k = layers.rotary_embedding(
            by_head(q, h), by_head(k, hk), theta=cfg.rope_theta,
            layout="bthd", norm_epsilon=cfg.rms_norm_eps,
            norm_param_attrs=[ParamAttr(name=f"{p}_attn_{z}norm.scale")
                              for z in "qk"],
            positions=pos, mrope_section=cfg.mrope_section)
    with fluid.name_scope("dsa"):
        with fluid.name_scope("proj"):
            qi, ki, w = _indexer(a, pos, cfg, p)
        with fluid.name_scope("select"):
            selected, live, index_lse = layers.dsa_select(
                qi, ki, w, cfg.topk if sparse else None, **tiles)
    with fluid.name_scope("core"):
        # K and V keep their hk heads: the kernels read head q // (h / hk)
        # (the dense stage's selection is every s <= p: the same call)
        ctx, lse = layers.scaled_dot_product_attention(
            q, k, v, scale, name=f"{p}_attn_sdpa", with_lse=True,
            selected=selected, live=live)
    with fluid.name_scope("dsa"), fluid.name_scope("loss"):
        index_loss = layers.dsa_index_loss(qi, ki, w, q, k, lse, selected,
                                           index_lse, scale, **tiles)
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dh])
        return (decoder.linear(ctx, cfg.hidden_size, f"{p}_attn_out_rowp.w"),
                index_loss, (selected, live))


def decoder_layer(x, pos, cfg: KeyeConfig, i: int):
    """(y, load-balancing loss, rows per held expert, experts chosen per
    token, L_I, the selection with its live table) of layer i."""
    p, eps = f"blk{i}", cfg.rms_norm_eps
    with fluid.name_scope(p):
        with fluid.name_scope("attn"):
            a = decoder.rms_norm(x, eps, f"{p}_attn_norm")
            out, index_loss, selected = _attention(a, pos, cfg, p)
            x = layers.elementwise_add(x, out)
        with fluid.name_scope("moe"):
            out, lb, _, rows, top_i = layers.topk_moe(
                decoder.rms_norm(x, eps, f"{p}_moe_norm"),
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                norm_topk_prob=cfg.norm_topk_prob, name=f"{p}_moe",
                held=cfg.held_experts)
            x = layers.elementwise_add(x, out)
    return x, lb, rows, top_i, index_loss, selected


def build(cfg: Optional[KeyeConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t], ``labels``
    [b, t] (the next token of every position; every position is real:
    packed documents, attended across their boundaries) and
    ``position_ids`` [3, t] int64 (temporal, height, width: the batch's
    rows share them; text feeds three equal rows). The graph has no
    dropout, so ``is_test`` changes nothing."""
    cfg = cfg or keye_vl2_30b_a3b()
    ids, lbl = decoder.token_feeds()
    pos = layers.data("position_ids", shape=[3, -1], dtype="int64",
                      append_batch_size=False)
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "keye_tok_emb.w", EMBEDDING_INIT_STD)
    lbs, rows, top_i, index_losses, selections = [], [], [], [], []
    for i in range(cfg.num_hidden_layers):
        x, lb, r, ti, li, sel = decoder_layer(x, pos, cfg, i)
        lbs.append(lb)
        rows.append(r)
        top_i.append(ti)
        index_losses.append(li)
        selections.append(sel)
    with fluid.name_scope("final_norm"):
        x = decoder.rms_norm(x, cfg.rms_norm_eps, "final_norm")

    logits, lm_loss = decoder.lm_head(x, lbl, cfg.vocab_size)
    with fluid.name_scope("loss_head"):
        lb_loss = layers.scale(decoder.sum_of(lbs), scale=1.0 / len(lbs))
        index_loss = layers.scale(decoder.sum_of(index_losses),
                                  scale=1.0 / len(index_losses))
        if cfg.stage == "warmup":       # the model is frozen
            loss = layers.scale(index_loss, scale=1.0)
        else:
            loss = layers.sums([
                lm_loss,
                layers.scale(lb_loss, scale=cfg.router_aux_loss_coef),
                layers.scale(index_loss, scale=cfg.index_loss_coef)])
        # the last positions' rows of every layer's selection, and the
        # whole of it, a mask a pair (nothing of either is made unless
        # it is fetched)
        last_selected = [layers.dsa_selected_rows(*sel, last=LAST_POSITIONS)
                         for sel in selections]
        selections = [layers.dsa_selected_rows(*sel) for sel in selections]
    return {
        "feeds": [ids, lbl, pos],
        "loss": loss,
        "lm_loss": lm_loss,
        "lb_loss": lb_loss,
        "index_loss": index_loss,
        "index_losses": index_losses,
        "logits": logits,
        "last_logits": decoder.last_logits(logits, LAST_POSITIONS),
        "last_selected": last_selected,
        "selections": selections,
        "expert_rows": rows,
        "top_i": top_i,
        "config": cfg,
    }


def make_batch(cfg: KeyeConfig, batch: int, seq_len: int,
               seed: int = 0) -> Dict[str, np.ndarray]:
    """``decoder.make_batch``'s packed tokens with a text row's
    positions: three equal rows 0 .. seq_len - 1."""
    feed = decoder.make_batch(cfg, batch, seq_len, seed)
    feed["position_ids"] = np.tile(np.arange(seq_len, dtype=np.int64),
                                   (3, 1))
    return feed
