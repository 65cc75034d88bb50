"""SDAR: a decoder-only mixture-of-experts language model trained by
BLOCK DIFFUSION (SDAR, "A Synergistic Diffusion-AutoRegression Paradigm
for Scalable Sequence Generation", arXiv:2510.06303; the training mask
and loss are block diffusion's, Arriola et al. 2025, arXiv:2503.09573).
The layer is a Qwen3-MoE block (HF ``model_type`` ``sdar_moe``); what is
its own is the training step. A row of L tokens x0 is cut into blocks of
B; every block b draws a noise level p_b and each of its tokens is
replaced by the mask id with probability p_b, giving xt. The network
runs ONCE over the 2L positions [xt ; x0], both halves at positions
0 .. L - 1, under

    visible(p, s), with P = p mod L, S = s mod L, bp = P // B, bs = S // B:
      p <  L, s <  L :  bp == bs     a noised block sees itself, both ways
      p <  L, s >= L :  bs <  bp     and the CLEAN blocks before it
      p >= L, s >= L :  bs <= bp     the clean half is block-causal
      p >= L, s <  L :  never

and the loss reads the logits AT the masked positions of the noised half
alone. As published (30B-A3B):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w                # eps 1e-6, f32 statistics
    row      : ids [b, 2L] = [xt ; x0];  x = E[ids];  position of index i is i mod L
    layer    : h = x + Attn(norm_in(x));  y = h + MoE(norm_post(h))
    Attn     : q = a Wq (d -> h dh);  k = a Wk, v = a Wv (d -> hk dh);  no bias
               q, k = norm_q(q), norm_k(k) over each head's dh features
               rotate-half over the whole head, theta 1e6, at positions i mod L
               o_h = softmax(q_h k^T / sqrt(dh) over visible(p, s)) v,  kv head = h // (h / hk)
               out = concat_h(o_h) Wo (h dh -> d)
    MoE      : s = softmax_f32(z Wr) over ALL experts; the k largest, renormalised
               over the k (norm_topk_prob);  out = sum_j w_j (silu(z Wg[e_j]) *
               (z Wu[e_j])) Wd[e_j], every chosen pair computed, none dropped
    LM       : logits_i = norm(y_L)_i Wout (untied), i < L
               loss = (1 / (b L)) sum_{i < L, xt_i = mask} CE(logits_i, x0_i) / p_{block(i)}
                      + aux_coef * load-balancing loss (mean over the layers,
                      each layer's from all its 2L positions' routing)

The noise is the FEED's: ``input_ids`` [b, 2L] is [xt ; x0], ``labels``
[b, L] holds x0 at the masked positions of xt and ``ignore_index``
everywhere else, ``loss_weight`` [b, L] the 1 / p of each position's
block. No shift: the logit at a masked position predicts the token AT
that position, as generation fills a block in place. The clean half is
computed in every layer because its keys and values are the mathematics
(what generation would hold in its cache); its rows never reach the
head. q|k|v are one matrix: one pass over ``a``; the order inside is
storage. ``held_experts=(first, count)`` builds one chip's share of
every expert layer (``layers.topk_moe(held=...)``).

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/attn`` with ``qkv``, ``rope`` (the per-head QK-norm is inside
the rotary op), the sdpa op under ``bd`` and ``out``; ``blk<i>/moe`` with ``router``, ``dispatch``,
``experts`` and ``combine``; ``final_norm``, ``loss_head``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.param_attr import ParamAttr

# logits a build offers for a comparison with a reference
# (model["last_logits"]): those of the noised half's last positions, of
# which the masked ones are compared (perf/reference/sdar.py says why)
LAST_POSITIONS = 256
IGNORE_INDEX = -100
# the linear schedule's floor: p = (1 - P_MIN) t + P_MIN, t ~ U(0, 1)
P_MIN = 1e-3
TABLE = "sdar_tok_emb.w"
HEAD = "lm_head_colp.w"


class SdarConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    SDAR-30B-A3B-Chat); ``block_length``, ``mask_token_id`` (the
    published tokenizer's ``<|MASK|>``), ``router_aux_loss_coef`` and
    ``held_experts`` are this builder's."""

    def __init__(
        self,
        vocab_size: int = 151936,
        hidden_size: int = 2048,
        num_hidden_layers: int = 48,
        num_attention_heads: int = 32,
        num_key_value_heads: int = 4,
        head_dim: int = 128,
        rope_theta: float = 1e6,
        rms_norm_eps: float = 1e-6,
        num_experts: int = 128,
        num_experts_per_tok: int = 8,
        moe_intermediate_size: int = 768,
        norm_topk_prob: bool = True,
        router_aux_loss_coef: float = 0.001,
        block_length: int = 4,
        mask_token_id: int = 151669,
        held_experts: Optional[Tuple[int, int]] = None,
    ):
        assert num_attention_heads % num_key_value_heads == 0
        assert 0 <= mask_token_id < vocab_size and block_length >= 1
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.router_aux_loss_coef = router_aux_loss_coef
        self.block_length = block_length
        self.mask_token_id = mask_token_id
        self.held_experts = tuple(held_experts) if held_experts else None


def sdar_30b_a3b() -> SdarConfig:
    return SdarConfig()


def _attention(a, cfg: SdarConfig, p: str):
    """Attn of the normalised input ``a`` [b, 2L, d]."""
    h, hk, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)

    def by_head(z, n):   # [b, t, n dh] -> [b, t, n, dh]
        return layers.reshape(z, [0, 0, n, dh])

    with fluid.name_scope("qkv"):
        qkv = decoder.linear(a, (h + 2 * hk) * dh, f"{p}_attn_qkv_colp.w")
        q, k, v = layers.split(qkv, [h * dh, hk * dh, hk * dh], dim=-1)
        v = layers.transpose(by_head(v, hk), [0, 2, 1, 3])
    with fluid.name_scope("rope"):
        # q and k where the projection left them: the op norms each head
        # over its dh (QK-norm, gains ``<p>_attn_qnorm.scale`` and
        # ``_knorm``) and transposes as it rotates, one pass. Two runs
        # of the positions: the noised copy's, then the clean copy's
        q, k = layers.rotary_embedding(
            by_head(q, h), by_head(k, hk), theta=cfg.rope_theta,
            layout="bthd", periods=2, norm_epsilon=cfg.rms_norm_eps,
            norm_param_attrs=[ParamAttr(name=f"{p}_attn_{z}norm.scale")
                              for z in "qk"])
    with fluid.name_scope("bd"):
        # K and V keep their hk heads: the kernels read head q // (h / hk)
        ctx = layers.scaled_dot_product_attention(
            q, k, v, 1.0 / math.sqrt(dh),
            block_diffusion=cfg.block_length, name=f"{p}_attn_sdpa")
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dh])
        return decoder.linear(ctx, cfg.hidden_size, f"{p}_attn_out_rowp.w")


def decoder_layer(x, cfg: SdarConfig, i: int):
    """(y, load-balancing loss, rows per held expert, experts chosen per
    position) of layer i."""
    p, eps = f"blk{i}", cfg.rms_norm_eps
    with fluid.name_scope(p):
        with fluid.name_scope("attn"):
            a = decoder.rms_norm(x, eps, f"{p}_attn_norm")
            x = layers.elementwise_add(x, _attention(a, cfg, p))
        with fluid.name_scope("moe"):
            out, lb, _, rows, top_i = layers.topk_moe(
                decoder.rms_norm(x, eps, f"{p}_moe_norm"),
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                norm_topk_prob=cfg.norm_topk_prob, name=f"{p}_moe",
                held=cfg.held_experts)
            x = layers.elementwise_add(x, out)
    return x, lb, rows, top_i


def build(cfg: Optional[SdarConfig] = None, is_test: bool = False,
          embedding_init_std: float = 0.02):
    """Block-diffusion training graph. Feeds: ``input_ids`` [b, 2L] (the
    noised copy, then the clean copy), ``labels`` [b, L] (x0 where xt is
    the mask id, ``IGNORE_INDEX`` elsewhere) and ``loss_weight`` [b, L]
    float32 (1 / p of the position's block); ``make_batch`` draws them.
    Every position is real: packed documents, attended across their
    boundaries inside what the block mask lets through. The graph has no
    dropout, so ``is_test`` changes nothing. ``embedding_init_std``: the
    table's (HF's ``initializer_range`` for every matrix)."""
    cfg = cfg or sdar_30b_a3b()
    ids, lbl = decoder.token_feeds()
    weight = layers.data("loss_weight", shape=[-1], dtype="float32")
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size, TABLE,
                      embedding_init_std)
    lbs, rows, top_i = [], [], []
    for i in range(cfg.num_hidden_layers):
        x, lb, r, ti = decoder_layer(x, cfg, i)
        lbs.append(lb)
        rows.append(r)
        top_i.append(ti)
    with fluid.name_scope("final_norm"):
        # the noised half alone goes on: the clean half was there for
        # its keys and values
        noised, _ = layers.split(x, 2, dim=1)
        noised = decoder.rms_norm(noised, cfg.rms_norm_eps, "final_norm")

    with fluid.name_scope("loss_head"):
        # the rows whose label counts alone are projected; a row's cross
        # entropy times its fed weight, summed over the row, over b L
        ce = layers.linear_cross_entropy(
            noised, cfg.vocab_size, lbl, ignore_index=IGNORE_INDEX,
            param_attr=decoder.weight(HEAD))
        lm_loss = layers.mean(
            layers.elementwise_mul(ce, layers.unsqueeze(weight, [2])))
        lb_loss = layers.scale(decoder.sum_of(lbs), scale=1.0 / len(lbs))
        loss = layers.sums([
            lm_loss, layers.scale(lb_loss, scale=cfg.router_aux_loss_coef)])
        # the last positions' logits, projected apart
        head = fluid.default_main_program().global_block().var(HEAD)
        last = layers.matmul(
            layers.slice(noised, axes=[1], starts=[-LAST_POSITIONS],
                         ends=[decoder._END]), head)
    return {
        "feeds": [ids, lbl, weight],
        "loss": loss,
        "lm_loss": lm_loss,
        "lb_loss": lb_loss,
        "last_logits": last,
        "expert_rows": rows,
        "top_i": top_i,
        "config": cfg,
    }


def noise(x0: np.ndarray, block: int, mask_id: int,
          r: np.random.RandomState) -> Dict[str, np.ndarray]:
    """The feed of clean rows x0 [b, L] (ids other than ``mask_id``):
    one t ~ U(0, 1) a block of ``block`` positions, p = (1 - P_MIN) t +
    P_MIN, every token of the block replaced by ``mask_id`` with
    probability p, independently (the linear schedule of
    arXiv:2503.09573, whose NELBO weighs a masked token by 1 / p)."""
    b, n = x0.shape
    assert n % block == 0, (n, block)
    p = np.repeat((1.0 - P_MIN) * r.uniform(size=(b, n // block)) + P_MIN,
                  block, axis=1)
    masked = r.uniform(size=(b, n)) < p
    xt = np.where(masked, mask_id, x0)
    return {"input_ids": np.concatenate([xt, x0], 1).astype(np.int64),
            "labels": np.where(masked, x0, IGNORE_INDEX).astype(np.int64),
            "loss_weight": (1.0 / p).astype(np.float32)}


def make_batch(cfg: SdarConfig, batch: int, seq_len: int,
               seed: int = 0) -> Dict[str, np.ndarray]:
    """``batch`` packed rows of ``seq_len`` data tokens below the mask id,
    noised (``noise``): ``input_ids`` is [batch, 2 seq_len]."""
    r = np.random.RandomState(seed)
    x0 = r.randint(0, cfg.mask_token_id, (batch, seq_len))
    return noise(x0, cfg.block_length, cfg.mask_token_id, r)
