"""NVIDIA-Nemotron-3-Nano-30B-A3B: a decoder-only hybrid whose every
block is ONE mixer (HF ``modeling_nemotron_h.py``; Nemotron-H,
arXiv:2504.03624; Mamba-2, arXiv:2405.21060). As published (31.6B-A3.2B,
52 blocks):

    norm(x)   = x * rsqrt(mean(x^2) + eps) * w                # plain gain
    block i   : x <- x + mixer_i(norm_i(x))
                mixer_i by ``hybrid_override_pattern[i]``: "M" a Mamba-2
                layer, "E" an expert layer, "*" an attention layer

    M (heads H of p features in G groups, state n, conv of 4 taps):
      [z | xBC | dt] = x W_in          # H p + (H p + 2 G n) + H, no bias
      xBC = silu(conv4(xBC) + b)       # depthwise, causal
      [xs | B | C] = xBC               # H p + G n + G n
      y = mamba2_scan(xs, dt, B, C)    # layers.mamba2_scan: dt =
          softplus(dt + dt_bias); S_t = exp(-exp(A_log) dt_t) S_{t-1}
          + dt_t xs_t B_t^T; y_t = S_t C_t + D xs_t; head h reads group
          h // (H / G)
      y = norm_groups(y * silu(z)) * g # the gate FIRST, statistics over
                                       # each of G groups of H p / G
      out = y W_out

    E (experts E, top k, one group of experts):
      s = sigmoid_f32(x Wr);  chosen = top k of (s + b)
      w_j = routed_scaling_factor * s_j / (sum_chosen s + 1e-20)
      expert(x) = relu(x W_up)^2 W_down                       # NOT gated
      out = sum_j w_j expert_{e_j}(x) + expert_shared(x)      # no gate
      b [E] takes no gradient; after each step b_e += gamma *
      sign(mean(count) - count_e) (``layers.topk_moe(select_bias=True)``)
      balance loss: the sequence-wise alpha * sum_e f_e P_e

    * : q = x Wq (h heads of dh), k, v = x Wk, x Wv (hk heads)
        o = causal softmax(q k^T / sqrt(dh)) v;  out = o Wo
        NO positional embedding (HF's ``NemotronHAttention`` applies
        none; the Mamba-2 layers carry position)

    LM : logits = norm(x_L) Wout (untied);  L = mean CE(logits_i, t_{i+1})
         + alpha * balance losses

``first_layer`` / ``num_hidden_layers``: the blocks this builder makes,
first_layer .. first_layer + num_hidden_layers - 1 of the pattern, with
their published indices (a cut keeps them: the parameters' names and the
name scopes read them). ``held_experts=(first, count)`` builds one
chip's share of every expert layer (``layers.topk_moe(held=...)``).

Name scopes (README "Names in the device trace"): ``embed``,
``blk<i>/mamba2`` with ``proj``, ``conv``, ``chunks`` (the scan op),
``gate_norm`` and ``out`` under it, ``blk<i>/moe`` with ``router``,
``dispatch``, ``experts``, ``combine`` and ``shared``, ``blk<i>/attn``
with ``qkv``, ``core`` (the sdpa op) and ``out``; ``final_norm``,
``loss_head``. A block's pre-norm lies in its mixer's scope.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401
from paddle_tpu.models.phi4flash import DtBiasInitializer

# logits of the last positions a build offers (model["last_logits"]):
# the second check of perf/reference/nemotronh.py. One whole chunk of
# the scan, so that the positions right behind a chunk boundary, where a
# state that was not carried shows most, are among them
LAST_POSITIONS = 128
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}


class NemotronHConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    NVIDIA-Nemotron-3-Nano-30B-A3B); ``bias_update_rate`` (gamma) and
    ``balance_alpha`` are training settings the config does not carry,
    ``first_layer``, ``held_experts`` and ``embedding_init_std`` this
    builder's."""

    def __init__(
        self,
        vocab_size: int = 131072,
        hidden_size: int = 2688,
        num_hidden_layers: int = 52,
        hybrid_override_pattern: str = PATTERN,
        first_layer: int = 0,
        layer_norm_epsilon: float = 1e-5,
        # Mamba-2
        mamba_num_heads: int = 64,
        mamba_head_dim: int = 64,
        n_groups: int = 8,
        ssm_state_size: int = 128,
        conv_kernel: int = 4,
        chunk_size: int = 128,
        time_step_min: float = 0.001,
        time_step_max: float = 0.1,
        # attention
        num_attention_heads: int = 32,
        num_key_value_heads: int = 2,
        head_dim: int = 128,
        # experts
        n_routed_experts: int = 128,
        num_experts_per_tok: int = 6,
        moe_intermediate_size: int = 1856,
        moe_shared_expert_intermediate_size: int = 3712,
        norm_topk_prob: bool = True,
        routed_scaling_factor: float = 2.5,
        bias_update_rate: float = 0.001,
        balance_alpha: float = 1e-4,
        held_experts: Optional[Tuple[int, int]] = None,
        embedding_init_std: float = 0.02,
    ):
        last = first_layer + num_hidden_layers
        if not (0 <= first_layer < last <= len(hybrid_override_pattern)):
            raise ValueError(
                f"blocks {first_layer}..{last - 1} of a pattern of "
                f"{len(hybrid_override_pattern)}")
        if set(hybrid_override_pattern) - set(KINDS):
            raise ValueError(f"pattern {hybrid_override_pattern!r}: a block "
                             f"is one of {sorted(KINDS)}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.hybrid_override_pattern = hybrid_override_pattern
        self.first_layer = first_layer
        self.layer_norm_epsilon = layer_norm_epsilon
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = (
            moe_shared_expert_intermediate_size)
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.bias_update_rate = bias_update_rate
        self.balance_alpha = balance_alpha
        self.held_experts = tuple(held_experts) if held_experts else None
        self.embedding_init_std = embedding_init_std

    @property
    def blocks(self):
        """[(published index, kind)] of the blocks this builder makes."""
        return [(i, KINDS[self.hybrid_override_pattern[i]])
                for i in range(self.first_layer,
                               self.first_layer + self.num_hidden_layers)]

    @property
    def mamba_d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim


def nemotron_3_nano_30b_a3b() -> NemotronHConfig:
    return NemotronHConfig()


def _mamba2(u, cfg: NemotronHConfig, p: str):
    """The Mamba-2 mixer of the normalised input u [b, t, d]."""
    return decoder.mamba2_mixer(
        u, p, heads=cfg.mamba_num_heads, head_dim=cfg.mamba_head_dim,
        groups=cfg.n_groups, state=cfg.ssm_state_size,
        conv_kernel=cfg.conv_kernel, chunk=cfg.chunk_size,
        eps=cfg.layer_norm_epsilon, hidden=cfg.hidden_size,
        dt_bias_init=DtBiasInitializer(cfg.time_step_min,
                                       cfg.time_step_max))


def _attention(u, cfg: NemotronHConfig, p: str):
    """Grouped-query attention of the normalised input u [b, t, d], no
    positional embedding."""
    return decoder.nope_attention(
        u, p, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        hidden=cfg.hidden_size, scale=1.0 / math.sqrt(cfg.head_dim))


def _moe(u, cfg: NemotronHConfig, p: str):
    return layers.topk_moe(
        u, cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
        name=f"{p}_moe", held=cfg.held_experts, gated=False, act="relu2",
        shared_d_ff=cfg.moe_shared_expert_intermediate_size,
        shared_gate=False, shared_act="relu2", shared_gated=False,
        score="sigmoid", routed_scale=cfg.routed_scaling_factor,
        select_bias=True, bias_update_rate=cfg.bias_update_rate)


def block(x, cfg: NemotronHConfig, i: int, kind: str):
    """(x + mixer_i(norm_i(x)), the expert layer's (balance loss, rows
    per held expert, experts chosen per token) or None)."""
    p = f"blk{i}"
    routing = None
    with fluid.name_scope(p):
        with fluid.name_scope(kind):
            u = decoder.rms_norm(x, cfg.layer_norm_epsilon, f"{p}_norm")
            if kind == "mamba2":
                out = _mamba2(u, cfg, p)
            elif kind == "attn":
                out = _attention(u, cfg, p)
            else:
                out, lb, _, rows, top_i = _moe(u, cfg, p)
                routing = (lb, rows, top_i)
            x = layers.elementwise_add(x, out)
    return x, routing


def build(cfg: Optional[NemotronHConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended and scanned across their
    boundaries, no state reset). The graph has no dropout, so
    ``is_test`` changes nothing."""
    cfg = cfg or nemotron_3_nano_30b_a3b()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size,
                      "nemotronh_tok_emb.w", cfg.embedding_init_std)
    lbs, rows, top_i = [], [], []
    for i, kind in cfg.blocks:
        x, routing = block(x, cfg, i, kind)
        if routing:
            lbs.append(routing[0])
            rows.append(routing[1])
            top_i.append(routing[2])
    with fluid.name_scope("final_norm"):
        x = decoder.rms_norm(x, cfg.layer_norm_epsilon, "final_norm")

    logits, lm_loss = decoder.lm_head(x, lbl, cfg.vocab_size)
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
