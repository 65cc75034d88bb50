"""Phi-4-mini-flash-reasoning ("SambaY": Ren et al. 2025,
arXiv:2507.06607, laid out as HF ``modeling_phi4flash.py``): a decoder
whose first half (the self-decoder) alternates Mamba-1 state-space
layers with sliding-window attention, and whose second half (the
cross-decoder) reuses what ONE full-attention layer and ONE Mamba layer
computed: every cross-attention layer reads that full layer's keys and
values, every gated memory unit (GMU) that Mamba layer's scan output.
No positional embedding anywhere. As published (3.8B, 32 layers):

    LN(x)    = (x - mean) * rsqrt(var + eps) * w + b
    layer i  : h = x + Mixer_i(LN1(x));  y = h + MLP(LN2(h))
    MLP(u)   = (silu(g) * v) W2,  [g | v] = u W1                (no bias)
    LM       : logits = LN(y_L) E^T over the table E (tied);  loss = mean
               next-token cross entropy

    Mixer_i over the published 32 (``layer_kinds``; half = 16):
      i < half,  i % mb_per_layer == 0 : Mamba          i odd: window attention
      i == half                        : Mamba, memory source (M kept)
      i == half + 1                    : full attention, key/value source
      i > half + 1, i % mb_per_layer == 0 : GMU         i odd: cross-attention

    Mamba (u [t, d]; e = expand * d, n = d_state, r = dt_rank):
      [a | z] = u W_in;  c = silu(conv4(a) + b_conv)   (causal, depthwise)
      [dt_r | B | C] = c W_x;  dt = dt_r W_dt
      y = selective_scan(c, dt, A = -exp(A_log), B, C, D, dt_bias)
          # layers.selective_scan: Delta = softplus(dt + dt_bias) ...
      out = (y * silu(z)) W_out;   the memory source keeps M = y
    GMU:  out = (silu(u W1g) * M) W2g
    Attention (h query, hk key/value heads of dh; differential, in pairs):
      [q1 | q2 | k1 | k2 | v] = u W_qkv + b   (q1, k1 the pairs' first
          heads, q2, k2 their second: the column order is storage)
      V = v as hk / 2 heads of 2 dh;  A_j = softmax(q_j k_j^T / sqrt(dh) +
          mask), pair-head p of q_j reads pair-head p // (h / hk) of k_j
      o = rms_norm_{2 dh}(A_1 V - lambda A_2 V) * gain * (1 - lambda_init)
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
      lambda_init = 0.8 - 0.6 exp(-0.3 i);  out = o W_o + b_o
      mask: causal; a window layer's query sees the last ``sliding_window``
      positions, itself among them
    Cross-attention: [q1 | q2] = u W_q + b only; k1, k2, V are the
      key/value source's, as it computed them; its own lambda, norm, W_o

A CUT of the model (``first_layer`` > 0 or fewer layers than
``model_layers``) is built by the same code: layer i keeps its published
index (its kind, its ``lambda_init`` and its parameters' names follow
it), and a cut that holds a reader holds its source.

The two softmax maps of a layer are two ``scaled_dot_product_attention``
calls of h / 2 over hk / 2 heads, dh wide over values of 2 dh, reading
the SAME V variable: nothing is copied per map.

Name scopes (README "Names in the device trace"): ``embed``;
``blk<i>/ssm`` with ``proj`` (W_in), ``conv``, ``xproj`` (W_x, W_dt),
``sscan`` (the selective scan; not ``scan``: jax puts that word into op
names itself and a reader of the trace stops at it), ``gate`` (the
memory source's y * silu(z)) and ``out``; ``blk<i>/gmu``;
``blk<i>/attn`` with ``qkv``, the sdpa ops under ``swa`` (window),
``core`` (full) or ``cross``, ``diff`` (lambda, the subtraction, the
sub-norm, the scale) and ``out``; ``blk<i>/mlp``; ``final_norm``,
``loss_head``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.initializer import Initializer, UniformInitializer
from paddle_tpu.models import decoder
from paddle_tpu.models.decoder import make_batch  # noqa: F401
from paddle_tpu.param_attr import ParamAttr

# logits of the last positions a build offers (model["last_logits"]):
# 64 of one row (perf/reference/phi4flash.py says why)
LAST_POSITIONS = 64
# every matrix starts at normal(0, 0.02), the tied table among them (HF's
# initializer_range): as the head it gives logits of unit size
EMBEDDING_INIT_STD = 0.02
TABLE = "phi4flash_tok_emb.w"
KINDS = ("mamba", "swa", "mamba_mem", "full", "gmu", "cross")


class Phi4FlashConfig:
    """Keys as in the model's published ``config.json`` (defaults:
    Phi-4-mini-flash-reasoning); the ``mamba_*`` sizes are HF
    ``Phi4FlashConfig``'s defaults (``mamba_dt_rank`` None: ceil(hidden /
    16)); ``first_layer`` and ``model_layers`` are this builder's: the
    published index of the first layer built and the published depth
    (None: ``first_layer + num_hidden_layers``)."""

    def __init__(
        self,
        vocab_size: int = 200064,
        hidden_size: int = 2560,
        num_hidden_layers: int = 32,
        num_attention_heads: int = 40,
        num_key_value_heads: int = 20,
        intermediate_size: int = 10240,
        sliding_window: int = 512,
        layer_norm_eps: float = 1e-5,
        mb_per_layer: int = 2,
        mamba_d_state: int = 16,
        mamba_d_conv: int = 4,
        mamba_expand: int = 2,
        mamba_dt_rank: Optional[int] = None,
        first_layer: int = 0,
        model_layers: Optional[int] = None,
    ):
        assert hidden_size % num_attention_heads == 0
        assert num_attention_heads % num_key_value_heads == 0
        assert num_key_value_heads % 2 == 0, "differential attention pairs"
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.intermediate_size = intermediate_size
        self.sliding_window = sliding_window
        self.layer_norm_eps = layer_norm_eps
        self.mb_per_layer = mb_per_layer
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_d_inner = mamba_expand * hidden_size
        self.mamba_dt_rank = mamba_dt_rank or -(-hidden_size // 16)
        self.first_layer = first_layer
        self.model_layers = model_layers or first_layer + num_hidden_layers
        assert first_layer + num_hidden_layers <= self.model_layers


def phi4_mini_flash() -> Phi4FlashConfig:
    return Phi4FlashConfig()


def layer_kinds(cfg) -> List:
    """[(published index, kind)] of the layers ``cfg`` builds, a kind of
    ``KINDS``, from ``num_hidden_layers``, ``mb_per_layer``,
    ``first_layer`` and ``model_layers`` (attributes or keys)."""
    get = (cfg.get if isinstance(cfg, dict)
           else lambda k, d=None: getattr(cfg, k, d))
    first = get("first_layer", 0) or 0
    count = get("num_hidden_layers")
    half = (get("model_layers") or first + count) // 2
    out = []
    for i in range(first, first + count):
        ssm_shaped = i % get("mb_per_layer") == 0
        if i < half:
            kind = "mamba" if ssm_shaped else "swa"
        elif i == half:
            kind = "mamba_mem"
        elif i == half + 1:
            kind = "full"
        else:
            kind = "gmu" if ssm_shaped else "cross"
        out.append((i, kind))
    kinds = [k for _, k in out]
    assert "gmu" not in kinds or "mamba_mem" in kinds, kinds
    assert "cross" not in kinds or "full" in kinds, kinds
    return out


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


class DtBiasInitializer(Initializer):
    """Mamba's own: the inverse softplus of a step size drawn
    log-uniformly from [low, high], log(expm1(dt))."""

    def __init__(self, low=1e-3, high=1e-1):
        self.low, self.high = low, high

    def __call__(self, var, block):
        block.append_op(
            "uniform_random", outputs={"Out": var.name},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "min": math.log(self.low), "max": math.log(self.high),
                   "seed": 0})
        for op, attrs in (("exp", {}), ("exp", {}),
                          ("scale", {"scale": 1.0, "bias": -1.0}),
                          ("log", {})):
            block.append_op(op, inputs={"X": var.name},
                            outputs={"Out": var.name}, attrs=attrs)


def _norm(x, cfg, name):
    return layers.layer_norm(
        x, begin_norm_axis=2, epsilon=cfg.layer_norm_eps,
        param_attr=ParamAttr(name=f"{name}.scale"),
        bias_attr=ParamAttr(name=f"{name}.bias"))


def _linear(x, size, name, bias=False):
    return layers.fc(
        x, size, num_flatten_dims=2, param_attr=decoder.weight(name + ".w"),
        bias_attr=ParamAttr(name=name + ".b") if bias else False)


def _mamba(u, cfg: Phi4FlashConfig, p: str, keep_memory: bool):
    """(Mamba of the normalised input u [b, t, d], the scan's output
    before the gate where ``keep_memory``)."""
    e, n, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    with fluid.name_scope("proj"):
        a, z = layers.split(_linear(u, 2 * e, f"{p}_ssm_in_colp"), 2, dim=-1)
    with fluid.name_scope("conv"):
        # torch's Conv1d default, which is Mamba's (HF's _init_weights
        # re-draws Linear and Embedding only): uniform(+-1 / sqrt(taps))
        # for the filter and its bias. At normal(0, 0.02) the
        # convolution's output, and with it B, C and the state, is so
        # small that an untrained layer's output is D x alone and no
        # check can tell whether the recurrence ran (PERF.md section 6).
        bound = cfg.mamba_d_conv ** -0.5
        c = layers.causal_conv1d(
            a, taps=cfg.mamba_d_conv, act="silu",
            param_attr=ParamAttr(
                name=f"{p}_ssm_conv.w",
                initializer=UniformInitializer(-bound, bound)),
            bias_attr=ParamAttr(
                name=f"{p}_ssm_conv.b",
                initializer=UniformInitializer(-bound, bound)))
    with fluid.name_scope("xproj"):
        dt_r, b, cc = layers.split(
            _linear(c, r + 2 * n, f"{p}_ssm_x_rowp"), [r, n, n], dim=-1)
        dt = _linear(dt_r, e, f"{p}_ssm_dt")
    with fluid.name_scope("sscan"):
        y = layers.selective_scan(
            c, dt, b, cc, z=None if keep_memory else z, state_size=n,
            a_log_attr=ParamAttr(name=f"{p}_ssm_a_log"),
            d_attr=ParamAttr(name=f"{p}_ssm_d"),
            dt_bias_attr=ParamAttr(name=f"{p}_ssm_dt.b",
                                   initializer=DtBiasInitializer()))
    memory = None
    if keep_memory:
        memory = y
        with fluid.name_scope("gate"):
            y = layers.elementwise_mul(y, layers.silu(z))
    with fluid.name_scope("out"):
        return _linear(y, cfg.hidden_size, f"{p}_ssm_out_rowp"), memory


def _gmu(u, memory, cfg: Phi4FlashConfig, p: str):
    g = layers.silu(_linear(u, cfg.mamba_d_inner, f"{p}_gmu_in_colp"))
    return _linear(layers.elementwise_mul(g, memory), cfg.hidden_size,
                   f"{p}_gmu_out_rowp")


def _attention(u, cfg: Phi4FlashConfig, p: str, i: int, kind: str, shared):
    """Differential attention of the normalised input u [b, t, d];
    ``shared``: the key/value source's (k1, k2, V) for a cross layer.
    -> (out, the (k1, k2, V) this layer used)."""
    h, hk, dh = (cfg.num_attention_heads // 2, cfg.num_key_value_heads // 2,
                 cfg.head_dim)

    def heads_first(z, n, width):   # [b, t, n width] -> [b, n, t, width]
        return layers.transpose(layers.reshape(z, [0, 0, n, width]),
                                [0, 2, 1, 3])

    with fluid.name_scope("qkv"):
        if kind == "cross":
            q1, q2 = layers.split(
                _linear(u, 2 * h * dh, f"{p}_attn_q_colp", bias=True), 2,
                dim=-1)
            k1, k2, v = shared
        else:
            q1, q2, k1, k2, v = layers.split(
                _linear(u, 2 * (h + 2 * hk) * dh, f"{p}_attn_qkv_colp",
                        bias=True),
                [h * dh, h * dh, hk * dh, hk * dh, 2 * hk * dh], dim=-1)
            k1, k2 = heads_first(k1, hk, dh), heads_first(k2, hk, dh)
            v = heads_first(v, hk, 2 * dh)
        q1, q2 = heads_first(q1, h, dh), heads_first(q2, h, dh)
    window = cfg.sliding_window if kind == "swa" else None
    with fluid.name_scope({"swa": "swa", "full": "core",
                           "cross": "cross"}[kind]):
        def attend(j, q, k):
            # K and V keep their heads: the kernels read head q // group
            return layers.scaled_dot_product_attention(
                q, k, v, 1.0 / math.sqrt(dh), window=window,
                name=f"{p}_attn_sdpa{j}")

        o1, o2 = attend(1, q1, k1), attend(2, q2, k2)
    with fluid.name_scope("diff"):
        o = layers.diff_attention_combine(
            o1, o2, lambda_init(i), dh, epsilon=cfg.layer_norm_eps,
            lambda_attr=ParamAttr(name=f"{p}_attn_lambda"),
            param_attr=ParamAttr(name=f"{p}_attn_subln.scale"))
    with fluid.name_scope("out"):
        o = layers.reshape(layers.transpose(o, [0, 2, 1, 3]),
                           [0, 0, 2 * h * dh])
        return (_linear(o, cfg.hidden_size, f"{p}_attn_out_rowp", bias=True),
                (k1, k2, v))


def _mlp(u, cfg: Phi4FlashConfig, p: str):
    g, v = layers.split(
        _linear(u, 2 * cfg.intermediate_size, f"{p}_mlp_up_colp"), 2, dim=-1)
    return _linear(layers.elementwise_mul(layers.silu(g), v),
                   cfg.hidden_size, f"{p}_mlp_down_rowp")


def decoder_layer(x, cfg: Phi4FlashConfig, i: int, kind: str, shared: Dict):
    """Layer ``i`` (published index) of kind ``kind``; ``shared`` holds
    what crosses layers: "memory" (the memory source's scan output) and
    "kv" (the key/value source's k1, k2, V), written by their sources
    and read by the layers behind them."""
    p = f"blk{i}"
    with fluid.name_scope(p):
        if kind in ("mamba", "mamba_mem"):
            with fluid.name_scope("ssm"):
                out, memory = _mamba(_norm(x, cfg, f"{p}_mixer_norm"), cfg, p,
                                     kind == "mamba_mem")
                if memory is not None:
                    shared["memory"] = memory
                x = layers.elementwise_add(x, out)
        elif kind == "gmu":
            with fluid.name_scope("gmu"):
                x = layers.elementwise_add(x, _gmu(
                    _norm(x, cfg, f"{p}_mixer_norm"), shared["memory"], cfg,
                    p))
        else:
            with fluid.name_scope("attn"):
                out, kv = _attention(
                    _norm(x, cfg, f"{p}_mixer_norm"), cfg, p, i, kind,
                    shared.get("kv"))
                if kind == "full":
                    shared["kv"] = kv
                x = layers.elementwise_add(x, out)
        with fluid.name_scope("mlp"):
            x = layers.elementwise_add(
                x, _mlp(_norm(x, cfg, f"{p}_mlp_norm"), cfg, p))
    return x


def build(cfg: Optional[Phi4FlashConfig] = None, is_test: bool = False):
    """Language-modelling graph. Feeds: ``input_ids`` [b, t] and
    ``labels`` [b, t] (the next token of every position; every position
    is real: packed documents, attended and scanned across their
    boundaries). The graph has no dropout (``embd_pdrop`` and
    ``resid_pdrop`` are 0 as published), so ``is_test`` changes
    nothing."""
    cfg = cfg or phi4_mini_flash()
    ids, lbl = decoder.token_feeds()
    x = decoder.embed(ids, cfg.vocab_size, cfg.hidden_size, TABLE,
                      EMBEDDING_INIT_STD)
    shared: Dict = {}
    for i, kind in layer_kinds(cfg):
        x = decoder_layer(x, cfg, i, kind, shared)
    with fluid.name_scope("final_norm"):
        x = _norm(x, cfg, "final_norm")

    logits, loss = decoder.tied_lm_head(x, lbl, TABLE)
    return {
        "feeds": [ids, lbl],
        "loss": loss,
        "logits": logits,
        "last_logits": decoder.last_logits(logits, LAST_POSITIONS),
        "memory": shared.get("memory"),
        "kv": list(shared["kv"]) if "kv" in shared else None,
        "config": cfg,
    }
