"""Plain reference of Qwen3-Next language-model training (HF
``modeling_qwen3_next.py``; Gated DeltaNet: arXiv:2412.06464): forward
and loss in float32 ``jax.numpy``, no kernels, nothing chunked, sorted
or grouped. The delta rule runs in its RECURRENT form, one ``lax.scan``
step a position (the program runs the chunkwise algebra); attention is
explicit scores, a head at a time; every held expert runs on every
token and the router's weights (zero for an expert a token did not
choose) pick what counts. Weights in, numbers out; gradients are
``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * (1 + w)
    layer i  : h = x + Mixer_i(norm(x));  y = h + MoE(norm(h))
               full attention where (i + 1) % 4 == 0, else Gated DeltaNet
    Attention: [q | gate] per head, k, v = x W;  q, k = norm over each
               head;  RoPE (rotate-half) on the first rotary_dim features;
               o = causal softmax(q k^T / sqrt(dh)) v, kv head = q head //
               group;  out = (o * sigmoid(gate)) Wo
    DeltaNet : q, k, v, z = x Wqkvz;  b, a = x Wba;  [q|k|v] <- silu(causal
               depthwise conv, 4 taps);  beta = sigmoid(b);  g = -exp(A_log)
               * softplus(a + dt_bias);  q, k <- q / |q| / sqrt(dk), k / |k|
               per value head, S_0 = 0:  S_t = exp(g_t) S_{t-1};
               S_t += k_t (beta_t (v_t - S_t^T k_t))^T;  o_t = S_t^T q_t
               out = (rmsnorm(o) * w_n * silu(z)) Wo
    MoE      : p = softmax(x Wr) over all ``router_experts``; top k,
               renormalised;  out = sum over the HELD experts among them
               (``held_first`` .. + ``num_experts``) of p_j SwiGLU_j(x)
               + sigmoid(x w_s) * SwiGLU_shared(x)
    LM       : logits = norm(y_L) Wout over the sliced vocabulary;  loss =
               mean next-token cross entropy + 0.001 * load-balancing loss

The configuration's cut is the program's: the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in).

The second check (perf/README.md), as OLMoE's: the loss is a mean over
8192 positions x 18,992 classes at ln(18992) and does not resolve a
lower precision, so the family also holds the LOGITS of the sample's
last 64 positions to the reference's, where program and reference chose
the same of the experts this chip holds in every layer, and bounds the
share of ALL choices that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np

AUX_COEF = 0.001   # HF's router_aux_loss_coef default (assumed)
# 64 positions of the sample's one row (OLMoE: 8 of each of two rows),
# compared where program and reference chose the same HELD experts in
# every layer. On the v5e 2.5-3.1% of all choices differ (near-ties
# among 512 under a bf16 stream): held to all ten of 512 a position's 40
# choices all agree one time in three (of 8 positions one was left at
# the first seed tried, and one seed in thirty would have left none),
# and the float8 control leaves none of 64. A choice among experts other
# chips hold moves nothing computed here but the renormalised weights,
# by the near-tie's difference.
LAST_POSITIONS = 64

# The second check's limits, set between two readings on the v5e at the
# published widths (my chip runs, PR 32; PERF.md sections 4 and 6): the
# program (bf16 AMP) over 13 seeds read an rms logit error of
# 0.0273-0.0399 of the logits' rms and 2.55-3.07% of the expert choices
# flipped (five times OLMoE's share: ten of 512 leave nearer ties than
# eight of 64); over 7 seeds (perf/tools/olmoe_logits_control.py
# --workload qwen3next-train-s8192) the reference with every weight
# matmul's operands rounded to float8_e4m3fn, the nearest precision
# below bf16, read 0.200-0.249 and 14.5-16.8% (float8_e5m2: 0.461-0.609
# and 34.5-38.0%), and comes out as not correct by either limit. Each
# limit is near the geometric middle: 2.1 to 2.3 times the program's
# largest, 2.2 times under the control's smallest.
LOGIT_ERR_LIMIT = 0.09
FLIP_LIMIT = 0.065


def norm(x, w, eps):
    """Zero-centred RMSNorm over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def rope(x, theta, rotary_dim):
    """x [b, h, t, dh]: rotate-half over the first ``rotary_dim``
    features (feature i pairs with i + rotary_dim/2), the rest pass."""
    t = x.shape[-2]
    xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = xr[..., :rotary_dim // 2], xr[..., rotary_dim // 2:]
    return jnp.concatenate(
        [xr * cos + jnp.concatenate([-x2, x1], -1) * sin, xp], -1)


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def attention(x, w, p, cfg, round_to=None):
    b, t, _ = x.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rd = int(dh * cfg["partial_rotary_factor"])
    qgkv = _mm(x, w[f"{p}_attn_qgkv_colp.w"], round_to)
    qg, k, v = jnp.split(qgkv, [2 * h * dh, (2 * h + hk) * dh], axis=-1)
    q, gate = jnp.split(qg.reshape(b, t, h, 2 * dh), 2, axis=-1)
    k, v = k.reshape(b, t, hk, dh), v.reshape(b, t, hk, dh)
    q = norm(q, w[f"{p}_attn_qnorm.scale"], eps).transpose(0, 2, 1, 3)
    k = norm(k, w[f"{p}_attn_knorm.scale"], eps).transpose(0, 2, 1, 3)
    q, k, v = rope(q, theta, rd), rope(k, theta, rd), v.transpose(0, 2, 1, 3)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(args):   # a query head at a time, so that the [t, t]
        q_h, i = args     # scores of all heads never coexist
        k_h, v_h = k[:, i // (h // hk)], v[:, i // (h // hk)]
        s = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(causal, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3), jnp.arange(h)))
    o = o.transpose(1, 2, 0, 3) * jax.nn.sigmoid(gate)      # [b, t, h, dh]
    return _mm(o.reshape(b, t, h * dh), w[f"{p}_attn_out_rowp.w"], round_to)


def causal_conv(x, w):
    """x [b, t, c], w [c, taps]: y_t = sum_j w[:, j] x_{t - taps + 1 + j}."""
    taps, t = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position a step. q, k [b, t, h, dk] (already
    normalised), v [b, t, h, dv], g, beta [b, t, h] -> o [b, t, h, dv]."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        delta = (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)) * b_t[..., None]
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    b, _, h, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def delta_net(x, w, p, cfg, round_to=None):
    b, t, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz = _mm(x, w[f"{p}_gdn_qkvz_colp.w"], round_to)
    b_, a_ = jnp.split(_mm(x, w[f"{p}_gdn_ba.w"], round_to), 2, axis=-1)
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    qkv = jax.nn.silu(causal_conv(qkv, w[f"{p}_gdn_conv.w"]))
    q, k, v = jnp.split(qkv, [kd, 2 * kd], axis=-1)
    beta = jax.nn.sigmoid(b_)
    g = -jnp.exp(w[f"{p}_gdn_A_log"]) * jax.nn.softplus(
        a_ + w[f"{p}_gdn_dt_bias"])

    def unit(z):
        return z * jax.lax.rsqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)

    # key head i serves value heads i * hv/hk .. (repeat_interleave)
    q = jnp.repeat(unit(q.reshape(b, t, hk, dk)), hv // hk, axis=2) / jnp.sqrt(
        jnp.float32(dk))
    k = jnp.repeat(unit(k.reshape(b, t, hk, dk)), hv // hk, axis=2)
    o = delta_rule(q, k, v.reshape(b, t, hv, dv), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = o * w[f"{p}_gdn_onorm.scale"] * jax.nn.silu(z.reshape(b, t, hv, dv))
    return _mm(o.reshape(b, t, vd), w[f"{p}_gdn_out_rowp.w"], round_to)


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    return (int(cfg.get("held_first", 0)), int(cfg["num_experts"]),
            int(cfg.get("router_experts", cfg["num_experts"])))


def route(x, wr, k, round_to=None):
    """x [n, d] -> (top_w [n, k] renormalised, top_i [n, k],
    load-balancing loss) over all the experts the router scores."""
    probs = jax.nn.softmax(_mm(x, wr, round_to), -1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    e = wr.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    lb = e * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(probs, 0))
    return top_w, top_i, lb


def swiglu(x, wg, wu, wd, round_to):
    return _mm(jax.nn.silu(_mm(x, wg, round_to)) * _mm(x, wu, round_to),
               wd, round_to)


def moe(x, w, p, cfg, round_to=None):
    """x [n, d] -> (out [n, d], top_i, lb). Every HELD expert on every
    token, weighted by the router (zero where the token did not choose
    it); an expert held elsewhere adds nothing here."""
    first, count, e = held(cfg)
    top_w, top_i, lb = route(x, w[f"{p}_moe_router.w"],
                             cfg["num_experts_per_tok"], round_to)
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=x.dtype))
    weight = weight[:, first:first + count]

    def one(acc, args):
        g, u, dn, w_e = args
        return acc + w_e[:, None] * swiglu(x, g, u, dn, round_to), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        w[f"{p}_moe_gate.w"], w[f"{p}_moe_up.w"], w[f"{p}_moe_down.w"],
        weight.T))
    shared = swiglu(x, w[f"{p}_moe_shared_gate.w"], w[f"{p}_moe_shared_up.w"],
                    w[f"{p}_moe_shared_down.w"], round_to)
    mix = jax.nn.sigmoid(_mm(x, w[f"{p}_moe_shared_mix.w"], round_to))
    return out + mix * shared, top_i, lb


def forward(w, cfg, ids, round_to=None, last=None):
    """{"logits": [b, t or last, V], "top_i": [per layer [b*t, k]],
    "lb"} of token ids [b, t]."""
    eps = cfg["rms_norm_eps"]
    x = w["qwen3next_tok_emb.w"][jnp.asarray(ids)]
    b, t, d = x.shape
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        if (i + 1) % cfg["full_attention_interval"] == 0:
            x = x + attention(norm(x, w[f"{p}_attn_norm.scale"], eps),
                              w, p, cfg, round_to)
        else:
            x = x + delta_net(norm(x, w[f"{p}_gdn_norm.scale"], eps),
                              w, p, cfg, round_to)
        out, top_i, lb = moe(
            norm(x, w[f"{p}_moe_norm.scale"], eps).reshape(b * t, d),
            w, p, cfg, round_to)
        x = x + out.reshape(b, t, d)
        top_is.append(top_i)
        lbs.append(lb)
    x = norm(x, w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, w["lm_head_colp.w"], round_to),
            "top_i": top_is, "lb": sum(lbs) / len(lbs)}


def loss(w, cfg, feed, round_to=None):
    out = forward(w, cfg, feed["input_ids"], round_to)
    logp = jax.nn.log_softmax(out["logits"], -1)
    ce = -jnp.take_along_axis(
        logp, jnp.asarray(feed["labels"])[..., None], -1)[..., 0]
    return jnp.mean(ce) + AUX_COEF * out["lb"]


def chosen(a, n_experts):
    """[n, E] bool: which experts each token's choices ``a`` [n, k] hold
    (sets: the order of the k does not matter)."""
    a = np.asarray(a)
    out = np.zeros((a.shape[0], n_experts), bool)
    out[np.arange(a.shape[0])[:, None], a] = True
    return out


def choices_differ(a, b, n_experts):
    """Per token, how many of the experts ``a`` [n, k] chose ``b`` [n, k]
    did not."""
    return (chosen(a, n_experts) & ~chosen(b, n_experts)).sum(1)


def compare(cfg, want, got_logits, got_top_i):
    """The second check's two readings of ``got`` against the
    reference's ``want`` (``forward(..., last=LAST_POSITIONS)``): the
    rms of the logit differences over the logits' rms among the last
    positions where every layer chose the same HELD experts, and the
    share of all (token, slot) choices that differ.

    The rms and not OLMoE's largest difference: here a differing choice
    at an EARLIER position reaches every later one through the delta
    rule's state and the attention (OLMoE's one block routes after its
    only mixer), so a few logits of a few positions move by a discrete
    step that no precision bounds: the largest difference read 0.17 to
    0.56 of the rms over six seeds of the program on the v5e (the
    float8 control 1.12 to 1.53), a tail too long to set a limit under.
    It is kept in the record (``logit_max_err_over_rms``), unjudged."""
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
