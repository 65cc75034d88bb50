"""Laguna: a decoder-only mixture-of-experts language model whose
attention layers are of two kinds with a head count EACH, three
sliding-window layers of 64 query heads with plain rotary positions to
one global layer of 48 under yarn over half a head, every head's
context behind a sigmoid gate of its own; a dense SwiGLU in layer 0,
then sigmoid-routed experts beside an ungated shared one (poolside
2026, ``config.json`` of Laguna-XS.2; no paper: the equations are
written from the config's keys, and what no key settles is listed as
assumed in perf/configs/laguna-xs-2.json). As published (XS.2,
33B-A3B):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w                  # plain gain, f32 statistics
    layer i  : a = norm_in(x);  h = x + Attn_i(a);  y = h + FFN_i(norm_post(h))
    Attn_i   : H = num_attention_heads_per_layer[i] (48 full, 64 window), hk = 8, dh = 128
               q = a Wq (d -> H dh);  k = a Wk, v = a Wv (d -> hk dh);  g = sigmoid(a Wg) (d -> H)
               full   : rotate the first 64 features of q and k by yarn's angles, cos and sin x 1.41589
               window : rotate the whole head, theta 1e4
               visible(p, s) = s <= p, and in a window layer p - s < 512
               o_h = softmax(q_h k^T / sqrt(dh) over visible) v,  kv head = h // (H / hk)
               out = concat_h(g_h * o_h) Wo (H dh -> d)
    yarn     : f_j = theta^(-2j/64), j < 32;  ramp_j = clip((j - low) / (high - low), 0, 1) with
               low, high = floor / ceil of 64 ln(4096 / (2 pi beta)) / (2 ln theta) at beta 64, 1,
               clipped to [0, 63] as HF's yarn does (5 and 16 at these numbers);
               inv_freq_j = f_j / 64 * ramp_j + f_j * (1 - ramp_j)
    FFN_0    : (silu(z Wg) * (z Wu)) Wd at 8192
    FFN_i>0  : s = sigmoid(z Wr) in f32 over ALL 256; the 8 largest, renormalised over the 8, x 2.5;
               out = sum_j w_j SwiGLU_{e_j}(z) + SwiGLU_shared(z), widths 512
    LM       : logits = norm(y_L) Wout (untied); loss = mean next-token CE + aux x balance loss

q|k|v|g are one matrix: one pass over ``a``; the order inside is
storage. The gate's sigmoid is float32 (the logits are cast up before
it) and its product with a head's context runs where the sdpa op left
the context, head-major, in front of the transpose that the output
projection reads. ``held_experts=(first, count)`` builds one chip's
share of every expert layer (``layers.topk_moe(held=...)``).

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/attn`` with ``qkv``, ``rope``, the sdpa op under ``swa`` in a
window layer and under ``core`` in a full one, ``gate`` and ``out``;
``blk<i>/mlp`` in a dense layer; ``blk<i>/moe`` with ``router``,
``dispatch``, ``experts``, ``shared`` and ``combine``; ``final_norm``,
``loss_head``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401

# logits of the last positions a build offers (model["last_logits"]):
# 64 of one row (perf/reference/laguna.py says why)
LAST_POSITIONS = 64
# The embedding table starts at normal(0, 1), every other matrix at
# normal(0, 0.02): models/smallthinker.py's lesson (PERF.md section 6,
# PR 38) holds here by the same arithmetic. With the table at 0.02 an
# untrained window layer's output (the mean of 512 value rows of 0.9 a
# feature, halved by the gate, through Wo) is 0.04 a feature and the
# token's own row 0.02: every token of a region routes alike and the
# held experts' rows swing with the seed.
EMBEDDING_INIT_STD = 1.0

FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

ROPE_PARAMETERS = {
    FULL: {"rope_theta": 500000.0, "rope_type": "yarn", "factor": 64.0,
           "original_max_position_embeddings": 4096, "beta_slow": 1.0,
           "beta_fast": 64.0, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000.0,
             "partial_rotary_factor": 1.0},
}


class LagunaConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    Laguna-XS.2); ``router_aux_loss_coef`` and ``held_experts`` are this
    builder's. The four per-layer lists may be longer than the stack:
    layer i reads entry i."""

    def __init__(
        self,
        vocab_size: int = 100352,
        hidden_size: int = 2048,
        intermediate_size: int = 8192,
        num_hidden_layers: int = 40,
        num_attention_heads: int = 48,
        num_key_value_heads: int = 8,
        head_dim: int = 128,
        rms_norm_eps: float = 1e-6,
        num_experts: int = 256,
        num_experts_per_tok: int = 8,
        moe_intermediate_size: int = 512,
        shared_expert_intermediate_size: int = 512,
        moe_routed_scaling_factor: float = 2.5,
        sliding_window: int = 512,
        rope_parameters: Optional[Dict[str, Dict]] = None,
        layer_types: Optional[Sequence[str]] = None,
        mlp_layer_types: Optional[Sequence[str]] = None,
        num_attention_heads_per_layer: Optional[Sequence[int]] = None,
        router_aux_loss_coef: float = 1e-4,
        held_experts: Optional[Tuple[int, int]] = None,
    ):
        n = num_hidden_layers
        if layer_types is None:    # full, then three windows to one full
            layer_types = [WINDOW if i % 4 else FULL for i in range(n)]
        if mlp_layer_types is None:
            mlp_layer_types = [SPARSE if i else DENSE for i in range(n)]
        if num_attention_heads_per_layer is None:   # as published: 48, 64
            num_attention_heads_per_layer = [
                num_attention_heads if t == FULL else 64
                for t in layer_types]
        for name, per_layer in (
                ("layer_types", layer_types),
                ("mlp_layer_types", mlp_layer_types),
                ("num_attention_heads_per_layer",
                 num_attention_heads_per_layer)):
            if len(per_layer) < n:
                raise ValueError(f"LagunaConfig: {name} has "
                                 f"{len(per_layer)} entries for {n} layers")
        for h in num_attention_heads_per_layer[:n]:
            if h % num_key_value_heads:
                raise ValueError(
                    f"LagunaConfig: {h} query heads over "
                    f"{num_key_value_heads} key/value heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = n
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.moe_routed_scaling_factor = moe_routed_scaling_factor
        self.sliding_window = sliding_window
        self.rope_parameters = {
            kind: dict(params) for kind, params in
            (rope_parameters or ROPE_PARAMETERS).items()
            if kind in (FULL, WINDOW)}
        self.layer_types = tuple(layer_types)
        self.mlp_layer_types = tuple(mlp_layer_types)
        self.num_attention_heads_per_layer = tuple(
            int(h) for h in num_attention_heads_per_layer)
        self.router_aux_loss_coef = router_aux_loss_coef
        self.held_experts = tuple(held_experts) if held_experts else None

    def heads(self, i: int) -> int:
        """Layer i's query heads."""
        return self.num_attention_heads_per_layer[i]

    def window(self, i: int) -> Optional[int]:
        """The positions layer i's queries see, None for all before."""
        return self.sliding_window if self.layer_types[i] == WINDOW else None

    def rope(self, i: int):
        """(theta, rotary_dim, yarn scaling or None) of layer i."""
        params = self.rope_parameters[self.layer_types[i]]
        rotary_dim = int(self.head_dim
                         * float(params.get("partial_rotary_factor", 1.0)))
        scaling = params if params.get("rope_type") == "yarn" else None
        return float(params["rope_theta"]), rotary_dim, scaling

    def dense(self, i: int) -> bool:
        return self.mlp_layer_types[i] == DENSE


def laguna_xs_2() -> LagunaConfig:
    return LagunaConfig()


def _attention(a, cfg: LagunaConfig, p: str, i: int):
    """Attn_i of the normalised input ``a`` [b, t, d]."""
    h, hk, dh = cfg.heads(i), cfg.num_key_value_heads, cfg.head_dim
    window = cfg.window(i)
    theta, rotary_dim, scaling = cfg.rope(i)

    def by_head(z, n):   # [b, t, n dh] -> [b, t, n, dh]
        return layers.reshape(z, [0, 0, n, dh])

    with fluid.name_scope("qkv"):
        # the layer's own width: (h + 2 hk) dh + h
        qkvg = decoder.linear(a, (h + 2 * hk) * dh + h,
                              f"{p}_attn_qkvg_colp.w")
        q, k, v, g = layers.split(qkvg, [h * dh, hk * dh, hk * dh, h],
                                  dim=-1)
        v = layers.transpose(by_head(v, hk), [0, 2, 1, 3])
    with fluid.name_scope("rope"):
        # q and k where the projection left them: the op transposes as
        # it rotates
        q, k = layers.rotary_embedding(
            by_head(q, h), by_head(k, hk), theta=theta,
            rotary_dim=rotary_dim, layout="bthd", scaling=scaling)
    with fluid.name_scope("swa" if window else "core"):
        # K and V keep their hk heads: the kernels read head q // (h / hk)
        ctx = layers.scaled_dot_product_attention(
            q, k, v, 1.0 / math.sqrt(dh), window=window,
            name=f"{p}_attn_sdpa")
    with fluid.name_scope("gate"):
        # one value a head and position, float32 through the sigmoid,
        # broadcast over the head's dh features: [b, t, h] -> [b, h, t, 1]
        g = layers.sigmoid(layers.cast(g, "float32"))
        ctx = layers.elementwise_mul(
            ctx, layers.unsqueeze(layers.transpose(g, [0, 2, 1]), [3]))
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dh])
        return decoder.linear(ctx, cfg.hidden_size, f"{p}_attn_out_rowp.w")


def decoder_layer(x, cfg: LagunaConfig, i: int):
    """(y, routing) of layer i: routing is None for a dense layer, else
    (balance loss, rows per held expert, experts chosen per token)."""
    p, eps = f"blk{i}", cfg.rms_norm_eps
    with fluid.name_scope(p):
        with fluid.name_scope("attn"):
            a = decoder.rms_norm(x, eps, f"{p}_attn_norm")
            x = layers.elementwise_add(x, _attention(a, cfg, p, i))
        if cfg.dense(i):
            with fluid.name_scope("mlp"):
                out = decoder.swiglu_mlp(
                    decoder.rms_norm(x, eps, f"{p}_mlp_norm"),
                    cfg.intermediate_size, cfg.hidden_size,
                    f"{p}_mlp_gate_colp.w", f"{p}_mlp_up_colp.w",
                    f"{p}_mlp_down_rowp.w")
                return layers.elementwise_add(x, out), None
        with fluid.name_scope("moe"):
            out, lb, _, rows, top_i = layers.topk_moe(
                decoder.rms_norm(x, eps, f"{p}_moe_norm"), cfg.num_experts,
                cfg.num_experts_per_tok, cfg.moe_intermediate_size,
                norm_topk_prob=True, name=f"{p}_moe", held=cfg.held_experts,
                shared_d_ff=cfg.shared_expert_intermediate_size,
                shared_gate=False, score="sigmoid",
                routed_scale=cfg.moe_routed_scaling_factor,
                select_bias=False)
            return layers.elementwise_add(x, out), (lb, rows, top_i)


def build(cfg: Optional[LagunaConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended across their boundaries). The
    graph has no dropout, so ``is_test`` changes nothing."""
    cfg = cfg or laguna_xs_2()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "laguna_tok_emb.w", EMBEDDING_INIT_STD)
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
    loss, lb_loss = lm_loss, None
    if lbs:   # the sum over the expert layers, as DeepSeek-V3's
        with fluid.name_scope("loss_head"):
            lb_loss = decoder.sum_of(lbs)
            loss = layers.sums([
                lm_loss,
                layers.scale(lb_loss, scale=cfg.router_aux_loss_coef)])
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
