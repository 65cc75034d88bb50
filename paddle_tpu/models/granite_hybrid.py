"""IBM Granite-4.0-H: a decoder-only hybrid of Mamba-2 and attention
layers, every layer a mixer and then a dense SwiGLU, under four scalar
multipliers (HF ``modeling_granitemoehybrid.py``; Mamba-2,
arXiv:2405.21060). As published (granite-4.0-h-micro, "3B", 40 layers;
``num_local_experts`` 0: ``shared_intermediate_size`` is the only MLP):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w                 # plain gain
    h_0      = embedding_multiplier * E[id]
    layer i  : h <- h + residual_multiplier * mixer_i(norm(h))
               h <- h + residual_multiplier * mlp(norm(h))
               mixer_i by ``layer_types[i]``: "mamba" or "attention"
    mlp(v)   = (silu(v Wg) * (v Wu)) Wd       # [Wg | Wu] one matrix, no bias

    mamba    : decoder.mamba2_mixer with ONE group (``mamba_n_groups``):
               all heads share B and C, and the gated norm's statistics
               are over all H p features
    attention: q = x Wq (h heads of dh), k, v = x Wk, x Wv (hk heads)
               o = causal softmax(attention_multiplier * q k^T) v   # the
               scale AS STATED (1 / 64 at heads of 64, not 1 / sqrt(64));
               out = o Wo.  NO positional embedding
               (``position_embedding_type`` "nope")

    LM : logits = norm(h_L) E^T / logits_scaling    (tied to the embedding)
         L = mean CE(logits_i, t_{i+1})

``first_layer`` / ``num_hidden_layers``: the layers this builder makes,
first_layer .. first_layer + num_hidden_layers - 1 of ``layer_types``,
with their published indices (a cut keeps them: the parameters' names
and the name scopes read them).

``recompute``: "layer" marks every layer's input and the last layer's
output as checkpoints (``layers.checkpoint``): the backward pass keeps
those [b, t, d] and makes a layer again where it needs its values
(backward.py); "none" marks nothing.

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/mamba2`` with ``proj``, ``conv``, ``chunks`` (the scan op),
``gate_norm`` and ``out`` under it, or ``blk<i>/attn`` with ``qkv``,
``core`` (the sdpa op) and ``out``; ``blk<i>/mlp``; ``final_norm``,
``loss_head``. A sublayer's pre-norm and its scaled residual add lie in
its scope.
"""

from __future__ import annotations

from typing import Optional, Sequence

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import (
    InverseSoftplusInitializer,
    LogUniformInitializer,
)
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401

# logits of the last positions a build offers (model["last_logits"]):
# the second check of perf/reference/granitehybrid.py. One whole chunk
# of the scan, so that the positions right behind a chunk boundary,
# where a state that was not carried shows most, are among them
LAST_POSITIONS = 128
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))
KINDS = {"mamba": "mamba2", "attention": "attn"}
RECOMPUTE = ("none", "layer")
TABLE = "granitehybrid_tok_emb.w"


class GraniteHybridConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    granite-4.0-h-micro); ``first_layer``, ``recompute``,
    ``time_step_min`` / ``time_step_max`` (the range the step size's bias
    is drawn for) and ``a_init_range`` are this builder's."""

    def __init__(
        self,
        vocab_size: int = 100352,
        hidden_size: int = 2048,
        num_hidden_layers: int = 40,
        layer_types: Sequence[str] = LAYER_TYPES,
        first_layer: int = 0,
        rms_norm_eps: float = 1e-5,
        shared_intermediate_size: int = 8192,
        # the four multipliers
        embedding_multiplier: float = 12.0,
        attention_multiplier: float = 0.015625,
        residual_multiplier: float = 0.22,
        logits_scaling: float = 8.0,
        # Mamba-2
        mamba_n_heads: int = 64,
        mamba_d_head: int = 64,
        mamba_n_groups: int = 1,
        mamba_d_state: int = 128,
        mamba_d_conv: int = 4,
        mamba_chunk_size: int = 128,
        time_step_min: float = 0.001,
        time_step_max: float = 0.1,
        a_init_range: Sequence[float] = (1.0, 16.0),
        # attention
        num_attention_heads: int = 32,
        num_key_value_heads: int = 8,
        recompute: str = "none",
    ):
        last = first_layer + num_hidden_layers
        if not (0 <= first_layer < last <= len(layer_types)):
            raise ValueError(f"layers {first_layer}..{last - 1} of "
                             f"{len(layer_types)} layer_types")
        if set(layer_types) - set(KINDS):
            raise ValueError(f"layer_types: a layer is one of {sorted(KINDS)}")
        if recompute not in RECOMPUTE:
            raise ValueError(f"recompute {recompute!r}: one of {RECOMPUTE}")
        if hidden_size % num_attention_heads:
            raise ValueError("hidden_size is num_attention_heads heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = tuple(layer_types)
        self.first_layer = first_layer
        self.rms_norm_eps = rms_norm_eps
        self.shared_intermediate_size = shared_intermediate_size
        self.embedding_multiplier = embedding_multiplier
        self.attention_multiplier = attention_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_n_groups = mamba_n_groups
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.a_init_range = tuple(a_init_range)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.recompute = recompute

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def blocks(self):
        """[(published index, scope kind)] of the layers this builder
        makes: ``layer_types`` read by published index."""
        return [(i, KINDS[self.layer_types[i]])
                for i in range(self.first_layer,
                               self.first_layer + self.num_hidden_layers)]


def granite_4_0_h_micro() -> GraniteHybridConfig:
    return GraniteHybridConfig()


def _mamba2(u, cfg: GraniteHybridConfig, p: str):
    return decoder.mamba2_mixer(
        u, p, heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
        groups=cfg.mamba_n_groups, state=cfg.mamba_d_state,
        conv_kernel=cfg.mamba_d_conv, chunk=cfg.mamba_chunk_size,
        eps=cfg.rms_norm_eps, hidden=cfg.hidden_size,
        dt_bias_init=InverseSoftplusInitializer(cfg.time_step_min,
                                                cfg.time_step_max),
        a_log_init=LogUniformInitializer(*cfg.a_init_range))


def _attention(u, cfg: GraniteHybridConfig, p: str):
    """Grouped-query attention of the normalised input u [b, t, d], no
    positional embedding, the softmax scale as the config states it."""
    return decoder.nope_attention(
        u, p, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        hidden=cfg.hidden_size, scale=cfg.attention_multiplier)


def _mlp(v, cfg: GraniteHybridConfig, p: str):
    """(silu(v Wg) * (v Wu)) Wd, [Wg | Wu] one matrix as published
    (``input_linear``)."""
    f = cfg.shared_intermediate_size
    gate, up = layers.split(
        decoder.linear(v, 2 * f, f"{p}_mlp_in_colp.w"), 2, dim=-1)
    return decoder.linear(layers.elementwise_mul(layers.silu(gate), up),
                          cfg.hidden_size, f"{p}_mlp_out_rowp.w")


def _residual(x, out, cfg):
    return layers.elementwise_add(
        x, layers.scale(out, scale=cfg.residual_multiplier))


def block(x, cfg: GraniteHybridConfig, i: int, kind: str):
    """Layer i: the mixer's sublayer, then the SwiGLU's."""
    p = f"blk{i}"
    if cfg.recompute != "none":
        layers.checkpoint(x)
    with fluid.name_scope(p):
        with fluid.name_scope(kind):
            u = decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_norm")
            mixer = _mamba2 if kind == "mamba2" else _attention
            x = _residual(x, mixer(u, cfg, p), cfg)
        with fluid.name_scope("mlp"):
            v = decoder.rms_norm(x, cfg.rms_norm_eps, f"{p}_mlp_norm")
            x = _residual(x, _mlp(v, cfg, p), cfg)
    return x


def build(cfg: Optional[GraniteHybridConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended and scanned across their
    boundaries, no state reset). The graph has no dropout, so
    ``is_test`` changes nothing."""
    cfg = cfg or granite_4_0_h_micro()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size, TABLE)
    with fluid.name_scope("embed"):
        x = layers.scale(x, scale=cfg.embedding_multiplier)
    for i, kind in cfg.blocks:
        x = block(x, cfg, i, kind)
    if cfg.recompute != "none":
        # the last layer's output too: the tail behind the last mark (the
        # final norm and the head) is not replayed, the last layer is
        layers.checkpoint(x)
    with fluid.name_scope("final_norm"):
        # logits / logits_scaling as (norm(h) / logits_scaling) E^T: the
        # division over [b, t, d], not over [b, t, vocab]
        x = layers.scale(decoder.rms_norm(x, cfg.rms_norm_eps, "final_norm"),
                         scale=1.0 / cfg.logits_scaling)
    logits, loss = decoder.tied_lm_head(x, lbl, TABLE)
    return {
        "feeds": [ids, lbl],
        "loss": loss,
        "lm_loss": loss,
        "logits": logits,
        "last_logits": decoder.last_logits(logits, LAST_POSITIONS),
        "config": cfg,
    }
