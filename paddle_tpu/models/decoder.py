"""The frame the decoder-only builders share: what every one of them
writes the same way around its own layers. A family keeps its config
class, its per-layer decisions, its mixers, its ``topk_moe`` call and its
auxiliary-loss policy; it calls this module for the parameters' attribute,
the plain RMSNorm and the bias-free projection, the two feeds, the
embedding, the vocabulary head with its loss, the last positions' logits,
the batch of packed tokens, and the mixers two families write the same
way, multi-head latent attention (``latent_attention``), the Mamba-2
mixer (``mamba2_mixer``) and grouped-query attention without positions
(``nope_attention``), each from its sizes alone:

    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, vocab, width, "<family>_tok_emb.w")
    for i in ...: x = <the family's layer i>(x)
    with fluid.name_scope("final_norm"): x = <the family's norm>(x)
    logits, lm_loss = decoder.lm_head(x, lbl, vocab)    # or tied_lm_head
    with fluid.name_scope("loss_head"): loss = <lm_loss and the family's terms>
    last = decoder.last_logits(logits, LAST_POSITIONS)

No function here knows a family: none takes a config class or a family's
name, none branches on its caller. A helper that would need to stays in
the families. The name scopes written here (``embed``, ``loss_head``) are
keys of the per-layer metrics (README "Names in the device trace").
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import NormalInitializer, UniformInitializer
from paddle_tpu.param_attr import ParamAttr

_END = 2 ** 31 - 1   # a slice's "to the end"


def weight(name, std=0.02):
    """A matrix's attribute: its name, drawn from normal(0, std)."""
    return ParamAttr(name=name, initializer=NormalInitializer(0.0, std))


def rms_norm(x, eps, name):
    """RMSNorm with a plain gain ``<name>.scale`` (1 at the start)."""
    return layers.rms_norm(x, epsilon=eps,
                           param_attr=ParamAttr(name=f"{name}.scale"))


def linear(x, size, name):
    """x [b, t, d] W, no bias; ``name`` is W's (the ``*_colp`` /
    ``*_rowp`` names are parallel/strategy's tensor-parallel rules')."""
    return layers.fc(x, size, num_flatten_dims=2, param_attr=weight(name),
                     bias_attr=False)


def token_feeds():
    """(``input_ids``, ``labels``), each [b, t] int64: the label of a
    position is the token after it, and every position is real (packed
    documents)."""
    return (layers.data("input_ids", shape=[-1], dtype="int64"),
            layers.data("labels", shape=[-1], dtype="int64"))


def embed(ids, vocab, width, name, std=0.02):
    """The rows of table ``name`` [vocab, width] at ``ids``, under scope
    ``embed``."""
    with fluid.name_scope("embed"):
        return layers.embedding(ids, size=[vocab, width],
                                param_attr=weight(name, std))


def cross_entropy(logits, lbl):
    """Each position's cross entropy [b, t, 1] against ``lbl`` [b, t]."""
    return layers.softmax_with_cross_entropy(logits,
                                             layers.unsqueeze(lbl, [2]))


def lm_head(x, lbl, vocab, name="lm_head_colp.w"):
    """(logits [b, t, vocab] of an untied head ``name``, the mean
    next-token cross entropy), under scope ``loss_head``."""
    with fluid.name_scope("loss_head"):
        logits = linear(x, vocab, name)
        return logits, layers.mean(cross_entropy(logits, lbl))


def tied_lm_head(x, lbl, table):
    """``lm_head`` where the embedding's rows are the head's columns:
    ``table`` is the embedding's parameter name."""
    with fluid.name_scope("loss_head"):
        w = fluid.default_main_program().global_block().var(table)
        logits = layers.matmul(x, w, transpose_y=True)
        return logits, layers.mean(cross_entropy(logits, lbl))


def last_logits(logits, n):
    """The logits of a row's last ``n`` positions (what a comparison
    with a reference can hold at a full vocabulary), under scope
    ``loss_head``."""
    with fluid.name_scope("loss_head"):
        return layers.slice(logits, axes=[1], starts=[-n], ends=[_END])


def sum_of(xs):
    """The sum of a list of like tensors; one tensor is itself."""
    return xs[0] if len(xs) == 1 else layers.sums(xs)


def swiglu_mlp(x, width, out, gate_name, up_name, down_name):
    """(silu(x Wgate) * (x Wup)) Wdown with two separate projections of
    ``width``, back to ``out`` features."""
    h = layers.elementwise_mul(layers.silu(linear(x, width, gate_name)),
                               linear(x, width, up_name))
    return linear(h, out, down_name)


def _heads_first(z):   # [b, t, heads, dh] -> [b, heads, t, dh]
    return layers.transpose(z, [0, 2, 1, 3])


def latent_attention(x, p: str, *, heads: int, nope: int, rope: int, dv: int,
                     kv_lora_rank: int, hidden: int, eps: float,
                     q_lora_rank: Optional[int] = None,
                     rope_theta: Optional[float] = None,
                     rope_scaling: Optional[dict] = None,
                     softmax_scale: Optional[float] = None):
    """Multi-head latent attention (DeepSeek-V3's, arXiv:2412.19437
    2.1.1) on x [b, t, d], parameters ``<p>_attn_*``, inside the caller's
    ``attn`` scope:

        [c_kv | k_pe] = norm(x) Wkva;  [k_nope | v] = norm(c_kv) Wkvb per head
        q = norm(norm(x) Wqa) Wqb  (``q_lora_rank`` None: q = norm(x) Wq,
        no low rank and no norm of it) -> per head [q_nope | q_pe]
        q_pe, k_pe <- RoPE, pairs (2i, 2i + 1), k_pe ONE head of ``rope``
        features that all query heads share (``rope_theta`` None: NOTHING
        is rotated, the features are kept as they come; ``rope_scaling``:
        a yarn scaling of the table, ``layers.rotary_embedding(scaling=)``'s
        dict)
        o = causal softmax((q_nope k_nope^T + q_pe k_pe^T)
                           / sqrt(nope + rope)) v;  o Wo
        (``softmax_scale``: that number where 1 / sqrt(nope + rope) stands,
        as DeepSeek's yarn puts mscale^2 on it)

    The attention call takes the four parts as they are
    (``layers.scaled_dot_product_attention(q_pe=, k_pe=)``): no wide q or
    k is assembled here and the shared head is not copied.

    Scopes under the caller's: ``q_lora`` (``q`` without a low rank),
    ``kv_lora``, ``rope`` (the heads to the front, the splits and the
    rotation where there is one), ``core`` (the sdpa op), ``out``."""
    h = heads
    xn = rms_norm(x, eps, f"{p}_attn_norm")
    if q_lora_rank is None:
        with fluid.name_scope("q"):
            q = linear(xn, h * (nope + rope), f"{p}_attn_q_colp.w")
    else:
        with fluid.name_scope("q_lora"):
            c_q = rms_norm(
                linear(xn, q_lora_rank, f"{p}_attn_q_a.w"), eps,
                f"{p}_attn_q_a_norm")
            q = linear(c_q, h * (nope + rope), f"{p}_attn_q_b_colp.w")
    with fluid.name_scope("kv_lora"):
        kva = linear(xn, kv_lora_rank + rope, f"{p}_attn_kv_a.w")
        c_kv, k_rope = layers.split(kva, [kv_lora_rank, rope], dim=-1)
        kv = linear(
            rms_norm(c_kv, eps, f"{p}_attn_kv_a_norm"),
            h * (nope + dv), f"{p}_attn_kv_b_colp.w")
    with fluid.name_scope("rope"):
        q_nope, q_rope = layers.split(
            _heads_first(layers.reshape(q, [0, 0, h, nope + rope])),
            [nope, rope], dim=-1)
        k_nope, v = layers.split(
            _heads_first(layers.reshape(kv, [0, 0, h, nope + dv])),
            [nope, dv], dim=-1)
        # the shared key features are one head: [b, 1, t, rope]
        k_rope = layers.unsqueeze(k_rope, [1])
        if rope_theta is not None:
            q_rope, k_rope = layers.rotary_embedding(
                q_rope, k_rope, theta=rope_theta, interleaved=True,
                scaling=rope_scaling)
    with fluid.name_scope("core"):
        # Q, K [b, h, t, nope], QPe [b, h, t, rope], KPe [b, 1, t, rope],
        # V and Out [b, h, t, dv]
        if softmax_scale is None:
            softmax_scale = 1.0 / math.sqrt(nope + rope)
        ctx = layers.scaled_dot_product_attention(
            q_nope, k_nope, v, softmax_scale, name=f"{p}_attn_sdpa",
            q_pe=q_rope, k_pe=k_rope)
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dv])
        return linear(ctx, hidden, f"{p}_attn_out_rowp.w")


def nope_attention(u, p: str, *, heads: int, kv_heads: int, head_dim: int,
                   hidden: int, scale: float):
    """Grouped-query attention of the normalised input u [b, t, d] with NO
    positional embedding (Nemotron-H's and Granite-4.0-H's: the Mamba-2
    layers carry position), parameters ``<p>_attn_*`` (Wq | Wk | Wv one
    matrix), inside the caller's ``attn`` scope:

        q = u Wq (``heads`` of ``head_dim``), k, v = u Wk, u Wv (``kv_heads``)
        o = causal softmax(``scale`` * q k^T) v;  out = o Wo

    Scopes under the caller's: ``qkv``, ``core`` (the sdpa op), ``out``."""
    h, hk, dh = heads, kv_heads, head_dim

    def heads_first(z, n):   # [b, t, n dh] -> [b, n, t, dh]
        return layers.transpose(layers.reshape(z, [0, 0, n, dh]),
                                [0, 2, 1, 3])

    with fluid.name_scope("qkv"):
        qkv = linear(u, (h + 2 * hk) * dh, f"{p}_attn_qkv_colp.w")
        q, k, v = layers.split(qkv, [h * dh, hk * dh, hk * dh], dim=-1)
        q, k, v = heads_first(q, h), heads_first(k, hk), heads_first(v, hk)
    with fluid.name_scope("core"):
        # K and V keep their hk heads: the kernels read head q // (h / hk)
        ctx = layers.scaled_dot_product_attention(
            q, k, v, scale, name=f"{p}_attn_sdpa")
    with fluid.name_scope("out"):
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [0, 0, h * dh])
        return linear(ctx, hidden, f"{p}_attn_out_rowp.w")


def mamba2_mixer(u, p: str, *, heads: int, head_dim: int, groups: int,
                 state: int, conv_kernel: int, chunk: int, eps: float,
                 hidden: int, dt_bias_init, a_log_init=None):
    """The Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060; as HF's
    ``modeling_nemotron_h.py`` and ``modeling_granitemoehybrid.py`` write
    it) of the normalised input u [b, t, d], parameters ``<p>_mamba_*``,
    inside the caller's ``mamba2`` scope:

        [z | xBC | dt] = u W_in        # H p + (H p + 2 G n) + H, no bias
        xBC = silu(conv(xBC) + b)      # depthwise, causal, ``conv_kernel`` taps
        [xs | B | C] = xBC             # H p + G n + G n
        y = mamba2_scan(xs, dt, B, C)  # head h reads group h // (H / G)
        y = norm_groups(y * silu(z)) * g   # the gate FIRST, statistics
                                           # over each of G groups of H p / G
        out = y W_out

    ``groups`` is the B / C groups AND the gated norm's (Nemotron-3 8,
    Granite-4.0-H 1); ``dt_bias_init`` / ``a_log_init`` the initializers
    of the step size's bias and of ``A_log`` (None: log(1 .. H)).

    Scopes under the caller's: ``proj``, ``conv``, ``chunks`` (the scan
    op), ``gate_norm``, ``out``."""
    e = heads * head_dim
    gn = groups * state
    with fluid.name_scope("proj"):
        z, xbc, dt = layers.split(
            linear(u, 2 * e + 2 * gn + heads, f"{p}_mamba_in_colp.w"),
            [e, e + 2 * gn, heads], dim=-1)
    with fluid.name_scope("conv"):
        # torch's Conv1d default (HF's _init_weights re-draws Linear and
        # Embedding only): uniform(+-1 / sqrt(taps)) for the filter and
        # its bias, as the Mamba-1 builder's (models/phi4flash.py)
        bound = conv_kernel ** -0.5
        xbc = layers.causal_conv1d(
            xbc, taps=conv_kernel, act="silu",
            param_attr=ParamAttr(
                name=f"{p}_mamba_conv.w",
                initializer=UniformInitializer(-bound, bound)),
            bias_attr=ParamAttr(
                name=f"{p}_mamba_conv.b",
                initializer=UniformInitializer(-bound, bound)))
        xs, b, c = layers.split(xbc, [e, gn, gn], dim=-1)
    with fluid.name_scope("chunks"):
        y = layers.mamba2_scan(
            xs, dt, b, c, heads=heads, groups=groups, chunk=chunk,
            a_log_attr=ParamAttr(name=f"{p}_mamba_a_log",
                                 initializer=a_log_init),
            d_attr=ParamAttr(name=f"{p}_mamba_d"),
            dt_bias_attr=ParamAttr(name=f"{p}_mamba_dt.b",
                                   initializer=dt_bias_init))
    with fluid.name_scope("gate_norm"):
        y = layers.gated_rms_norm(
            y, z, epsilon=eps, gate_first=True, group_size=e // groups,
            param_attr=ParamAttr(name=f"{p}_mamba_norm.scale"))
    with fluid.name_scope("out"):
        return linear(y, hidden, f"{p}_mamba_out_rowp.w")


def make_batch(cfg, batch: int, seq_len: int,
               seed: int = 0) -> Dict[str, np.ndarray]:
    """Packed tokens below ``cfg.vocab_size``: ``seq_len + 1`` of them a
    row, inputs the first ``seq_len``, labels the same shifted by one."""
    r = np.random.RandomState(seed)
    toks = r.randint(0, cfg.vocab_size, (batch, seq_len + 1)).astype(np.int64)
    return {"input_ids": toks[:, :-1], "labels": toks[:, 1:]}
