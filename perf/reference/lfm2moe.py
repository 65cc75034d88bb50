"""Plain reference of LFM2-MoE language-model training (HF
``modeling_lfm2_moe.py``): forward and loss in float32 ``jax.numpy``, no
kernels, nothing fused, sorted, grouped or skipped. The gated short
convolution is a split, a product, three shifted adds and a product;
attention is explicit scores, a query head at a time; every held expert
runs on every token and the router's weights (zero for an expert a token
did not choose) pick what counts. Weights in, numbers out; gradients are
``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    block i  : h = x + Op_i(norm(x));  out = h + FF_i(norm(h))
    conv     : [B | C | u] = n W_in;  v = B * u
               c_t = sum_j w[:, j] v_{t - (L - 1) + j}  (zeros in front)
               Op = (C * c) W_out
    attention: q, k, v = n W;  q, k = norm_dh(q), norm_dh(k) per head;
               rotate-half rotary over the whole head;
               Op = (causal softmax(q k^T / sqrt(dh)) v) W_o, kv head =
               q head // group
    FF dense : (silu(n W_1) * (n W_3)) W_2
    FF expert: s = sigmoid(n W_r); chosen = top k of s + bias;
               w = s_chosen / (sum s_chosen + 1e-6) * routed_scaling_factor
               out = sum over the HELD experts among them of
               w_e (silu(n Wg_e) * (n Wu_e)) Wd_e
    LM       : logits = norm(x_L) E^T over the sliced, tied table; loss =
               mean next-token cross entropy + 1e-4 * the expert layers'
               sequence-wise balance losses

The configuration's cut is the program's: the same blocks under their
published indices (``first_layer`` on, each reading its own entry of
``layer_types`` and ``i < num_dense_layers``), the same held share of
the experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary. HF's ``+ 1e-6`` in the renormalisation is
kept HERE; the program leaves it out (four sigmoid scores sum far above
it: the configuration's ``assumed``).

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``ablate`` removes ONE new mechanism, to show
that the checks see it: "no_b_gate" (v = u), "no_c_gate" (Op = c
W_out), "last_tap" (the convolution cut to its last tap: no memory of
earlier positions), "taps_reversed" (w[:, j] meets v_{t - j}),
"no_qk_norm" (q and k as projected) and "no_select_bias" (the k largest
of s alone: shows only under a non-zero ``expert_bias``, which the
family's startup program holds: perf/families/lfm2moe.py).

The second check (perf/README.md), as the other MoE families': the loss
is a mean over 8192 positions x 8192 classes at ln(8192) and does not
resolve a lower precision, so the family also holds the LOGITS of the
sample's last 64 positions to the reference's, where program and
reference chose the same of the experts this chip holds in every layer,
and bounds the share of ALL choices that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np

BALANCE_ALPHA = 1e-4   # assumed, as joyai-llm-flash.json has it
LAST_POSITIONS = 64    # models/lfm2_moe.py
KINDS = {"conv": "sconv", "full_attention": "attn"}
ABLATIONS = ("no_b_gate", "no_c_gate", "last_tap", "taps_reversed",
             "no_qk_norm", "no_select_bias")

# The second check's limits, set between two readings on the v5e at the
# published widths (my chip run, PR 48, the review round,
# perf/tools/lfm2moe_logits_control.py; PERF.md sections 4 and 6), at
# the state a run of the cell starts from (perf/families/lfm2moe.py:
# QK-norm gains of normal(2, 0.2), expert_bias of +-0.03): the program
# (bf16 AMP) read an rms logit error of 0.0176-0.0223 of the logits'
# rms and 1.77-1.90% of the expert choices flipped over 12 seeds of the
# tool (the cell's own runs read inside both spans); the reference with
# every weight matmul's operands rounded to float8_e4m3fn, the nearest
# precision below bf16, read 0.1556-0.1603 and 13.39-13.65% over 4
# seeds (float8_e5m2: 0.407-0.422 and 31.4-31.7%) and comes out as not
# correct by either limit. Each limit is the geometric middle: 2.6
# times the program's largest, as far under the control's smallest. The
# ablations (2 seeds each, at that same state): the B gate dropped, the
# C gate dropped and the taps reversed flip 89-93% of the choices and
# leave no position or one to compare (1.42 there), the convolution cut
# to its last tap 1.16 and 83%, the QK-norm dropped 0.326-0.334 and
# 27.8-28.0%, the selection bias ignored 0.025-0.031 (INSIDE the logit
# limit, on the 31-32 positions left to compare) and 19.9%: every one
# not correct by the second check on both seeds. check_loss (1e-3) saw
# none of them this round (1.7e-5 to 9.7e-4; the QK-norm's 9.7e-4 and
# the B gate's 9.0e-4 the nearest); its own reading of the program is
# 3e-7 to 5.9e-5. (At the builder's fresh values, gains 1 and bias 0,
# the first round read the program at 0.0136-0.0181 and 1.29-1.45%, the
# control at 0.1205-0.1224 and 10.4-10.7%, the QK-norm dropped at
# 0.007-0.010, inside the limits, and the bias ignored at nothing.)
LOGIT_ERR_LIMIT = 0.059
FLIP_LIMIT = 0.050


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def blocks(cfg):
    """[(published index, mixer kind, dense FF or not)] of the
    configuration's blocks."""
    first = int(cfg.get("first_layer", 0))
    return [(i, KINDS[cfg["layer_types"][i]], i < cfg["num_dense_layers"])
            for i in range(first, first + int(cfg["num_hidden_layers"]))]


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------


def short_conv(n, w, p, cfg, round_to=None, ablate=None):
    """Op of the normalised input n [b, t, d]."""
    gate_b, gate_c, u = jnp.split(
        _mm(n, w[f"{p}_sconv_in_colp.w"], round_to), 3, axis=-1)
    v = u if ablate == "no_b_gate" else gate_b * u
    taps = w[f"{p}_sconv_conv.w"]                       # [d, L]
    if ablate == "taps_reversed":
        taps = taps[:, ::-1]
    width, t = taps.shape[1], v.shape[1]
    pad = jnp.pad(v, [(0, 0), (width - 1, 0), (0, 0)])
    first = width - 1 if ablate == "last_tap" else 0
    c = sum(pad[:, j:j + t] * taps[:, j] for j in range(first, width))
    y = c if ablate == "no_c_gate" else gate_c * c
    return _mm(y, w[f"{p}_sconv_out_rowp.w"], round_to)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def rope(x, theta):
    """x [b, t, heads, dh]: rotate-half rotary over the whole head,
    position p of the row is p."""
    t, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(n, w, p, cfg, round_to=None, ablate=None):
    """Op of the normalised input n [b, t, d]."""
    b, t, d = n.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = d // h, cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    qkv = _mm(n, w[f"{p}_attn_qkv_colp.w"], round_to)
    q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
    q, k = q.reshape(b, t, h, dh), k.reshape(b, t, hk, dh)
    if ablate != "no_qk_norm":
        q = norm(q, w[f"{p}_attn_qnorm.scale"], eps)
        k = norm(k, w[f"{p}_attn_knorm.scale"], eps)
    q = rope(q, theta).transpose(2, 0, 1, 3)            # [h, b, t, dh]
    k = rope(k, theta).transpose(0, 2, 1, 3)            # [b, hk, t, dh]
    v = v.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    visible = jnp.tril(jnp.ones((t, t), bool))

    def one(args):   # one query head: [b, t, t] float32 is live, not h
        q_h, head = args
        k_h, v_h = k[:, head // (h // hk)], v[:, head // (h // hk)]
        s = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(visible, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    o = jax.lax.map(one, (q, jnp.arange(h)))            # [h, b, t, dh]
    return _mm(o.transpose(1, 2, 0, 3).reshape(b, t, h * dh),
               w[f"{p}_attn_out_rowp.w"], round_to)


# ---------------------------------------------------------------------------
# the feed-forward branches
# ---------------------------------------------------------------------------


def swiglu(x, w1, w3, w2, round_to):
    return _mm(jax.nn.silu(_mm(x, w1, round_to)) * _mm(x, w3, round_to),
               w2, round_to)


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    count = int(cfg["num_experts"])
    return (int(cfg.get("held_first", 0)), count,
            int(cfg.get("router_experts", count)))


def route(x, wr, bias, cfg, round_to=None, ablate=None):
    """x [b, t, d] -> (top_w [n, k], top_i [n, k], the mean over the
    rows of the balance loss) over all the experts the router scores."""
    b, t, d = x.shape
    k, e = cfg["num_experts_per_tok"], wr.shape[-1]
    s = jax.nn.sigmoid(_mm(x.reshape(b * t, d), wr, round_to))
    pick = s if ablate == "no_select_bias" else s + bias
    _, top_i = jax.lax.top_k(pick, k)           # the bias: the choice only
    top_w = jnp.take_along_axis(s, top_i, -1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-6)
    top_w = top_w * cfg["routed_scaling_factor"]
    count = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    f = e / (k * t) * jnp.sum(count.reshape(b, t, e), 1)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(b, t, e), 1)
    return top_w, top_i, jnp.mean(jnp.sum(f * p, -1))


def moe(x, w, p, cfg, round_to=None, ablate=None):
    """x [b, t, d] -> (out, top_i, balance loss). Every HELD expert on
    every token, weighted by the router (zero where the token did not
    choose it); an expert held elsewhere adds nothing here."""
    b, t, d = x.shape
    first, count, e = held(cfg)
    top_w, top_i, lb = route(x, w[f"{p}_moe_router.w"],
                             w[f"{p}_moe_router.bias"], cfg, round_to, ablate)
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=x.dtype))
    weight = weight[:, first:first + count]
    xf = x.reshape(b * t, d)

    def one(acc, args):
        g, u, dn, w_e = args
        return acc + w_e[:, None] * swiglu(xf, g, u, dn, round_to), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(xf), (
        w[f"{p}_moe_gate.w"], w[f"{p}_moe_up.w"], w[f"{p}_moe_down.w"],
        weight.T))
    return out.reshape(b, t, d), top_i, lb


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def forward(w, cfg, ids, round_to=None, last=None, ablate=None):
    """{"logits": [b, t or last, V], "top_i": [per expert layer [b*t,
    k]], "lb": the balance losses' sum} of token ids [b, t]."""
    assert ablate is None or ablate in ABLATIONS, ablate
    eps = cfg["norm_eps"]
    table = w["lfm2_tok_emb.w"]
    x = table[jnp.asarray(ids)]
    top_is, lb = [], 0.0
    for i, kind, dense in blocks(cfg):
        p = f"blk{i}"
        n = norm(x, w[f"{p}_op_norm.scale"], eps)
        op = short_conv if kind == "sconv" else attention
        x = x + op(n, w, p, cfg, round_to, ablate)
        n = norm(x, w[f"{p}_ffn_norm.scale"], eps)
        if dense:
            out = swiglu(n, w[f"{p}_ffn_w1_colp.w"], w[f"{p}_ffn_w3_colp.w"],
                         w[f"{p}_ffn_w2_rowp.w"], round_to)
        else:
            out, top_i, lb_i = moe(n, w, p, cfg, round_to, ablate)
            top_is.append(top_i)
            lb = lb + lb_i
        x = x + out
    x = norm(x, w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, table.T, round_to), "top_i": top_is, "lb": lb}


def loss(w, cfg, feed, round_to=None, ablate=None):
    out = forward(w, cfg, feed["input_ids"], round_to, ablate=ablate)
    logp = jax.nn.log_softmax(out["logits"], -1)
    ce = -jnp.take_along_axis(
        logp, jnp.asarray(feed["labels"])[..., None], -1)[..., 0]
    return jnp.mean(ce) + BALANCE_ALPHA * out["lb"]


def chosen(a, n_experts):
    """[n, E] bool: which experts each token's choices ``a`` [n, k] hold
    (sets: the order of the k does not matter)."""
    a = np.asarray(a)
    out = np.zeros((a.shape[0], n_experts), bool)
    out[np.arange(a.shape[0])[:, None], a] = True
    return out


def compare(cfg, want, got_logits, got_top_i):
    """The second check's two readings of ``got`` against the
    reference's ``want`` (``forward(..., last=LAST_POSITIONS)``): the rms
    of the logit differences over the logits' rms among the last
    positions where every expert layer chose the same HELD experts, and
    the share of all (token, slot) choices that differ. The rms and not
    the largest difference, as the other MoE families' checks say: a
    differing choice at an earlier position reaches every later one
    through the convolutions and the attention; the largest is kept in
    the record, unjudged."""
    want_logits = np.asarray(want["logits"], np.float32)
    got_logits = np.asarray(got_logits, np.float32)
    b, last = want_logits.shape[:2]
    (first, count, e), k = held(cfg), cfg["num_experts_per_tok"]
    sets = [(chosen(g, e), chosen(r, e))
            for g, r in zip(got_top_i, want["top_i"])]       # [n, E] each
    diff = np.stack([(g & ~r).sum(1) for g, r in sets])      # [L, n]
    mine = slice(first, first + count)
    held_differ = sum((g[:, mine] != r[:, mine]).sum(1) for g, r in sets)
    same = (held_differ == 0).reshape(b, -1)[:, -last:]
    scale = np.sqrt(np.mean(want_logits ** 2))
    sq = ((got_logits - want_logits) ** 2).mean(-1)        # [b, last]
    worst = np.abs(got_logits - want_logits).max(-1) / scale
    return {"logit_err_over_rms": float(np.sqrt(sq[same].mean()) / scale)
            if same.any() else float("nan"),
            "logit_max_err_over_rms": float(worst[same].max())
            if same.any() else float("nan"),
            "positions_compared": int(same.sum()),
            "positions": int(same.size),
            "flipped_share": float(diff.sum() / (diff.size * k))}


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits``, ``top_i``
    and ``expert_rows`` on the sample (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids: forward(w_, cfg, ids,
                                           last=LAST_POSITIONS))(
        w, jnp.asarray(sample["input_ids"]))
    record = compare(cfg, want, fetched["last_logits"], fetched["top_i"])
    rows = np.asarray(fetched["expert_rows"], np.float64)   # [L, held]
    pairs = np.asarray(fetched["top_i"][0]).size
    record["max_expert_load"] = float(
        (rows.max(1) / np.maximum(rows.mean(1), 1e-9)).max())
    # the (token, slot) pairs on experts this chip holds, over all pairs
    record["held_row_share"] = float(rows.sum(1).mean() / pairs)
    record["limits"] = [LOGIT_ERR_LIMIT, FLIP_LIMIT]
    problems = []
    if not record["positions_compared"]:
        problems.append("no last position where program and reference "
                        "chose the same experts: nothing to compare")
    elif not record["logit_err_over_rms"] <= LOGIT_ERR_LIMIT:
        problems.append(
            f"last-position logits differ from the reference's by "
            f"{record['logit_err_over_rms']:.3g} of their rms > "
            f"{LOGIT_ERR_LIMIT}")
    if not record["flipped_share"] <= FLIP_LIMIT:
        problems.append(
            f"{100 * record['flipped_share']:.2f}% of the expert choices "
            f"differ from the reference's > {100 * FLIP_LIMIT}%")
    return problems, record
