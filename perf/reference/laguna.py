"""Plain reference of Laguna language-model training (poolside's
Laguna-XS.2, from the keys of its ``config.json``; what no key settles
is the configuration file's ``assumed``): forward and loss in float32
``jax.numpy``, no kernels, nothing sorted, grouped or skipped. Attention
is explicit scores with the visibility rule written out, a query head
and a block of queries at a time, each layer at its own head count;
yarn's frequencies are the formula below, in numpy at trace time; every
held expert runs on every token and the router's weights (zero for an
expert a token did not choose) pick what counts. Weights in, numbers
out; gradients are ``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    layer i  : a = norm_in(x);  h = x + Attn_i(a);  y = h + FFN_i(norm_post(h))
    Attn_i   : H = num_attention_heads_per_layer[i], hk key/value heads of dh
               q = a Wq (H dh);  k = a Wk, v = a Wv (hk dh);  g = sigmoid(a Wg) (H)
               layer_types[i] picks rope_parameters: the first dh *
               partial_rotary_factor features of q and k turn (rotate-half
               inside them), position p by p * inv_freq_j, cos and sin times
               attention_factor under yarn; the other features pass
               visible(p, s) = s <= p, and p - s < sliding_window in a
               sliding_attention layer;  o_h = softmax(q_h k^T / sqrt(dh) over
               visible) v, kv head = h // (H / hk);  out = concat_h(g_h o_h) Wo
    yarn     : over the r rotated features, f_j = theta^(-2j/r), j < r/2;
               low, high = floor, ceil of r ln(L / (2 pi beta)) / (2 ln theta) at
               beta_fast, beta_slow, clipped to [0, r - 1];  ramp_j = clip((j -
               low) / (high - low), 0, 1);  inv_freq_j = f_j / factor * ramp_j
               + f_j (1 - ramp_j)
    FFN_i    : mlp_layer_types[i] dense: (silu(z Wg) * (z Wu)) Wd;  sparse: s =
               sigmoid(z Wr) over all ``router_experts``; chosen = the k largest;
               w_j = moe_routed_scaling_factor * s_j / sum_chosen s;  out = sum
               over the HELD experts among them (``held_first`` .. +
               ``num_experts``) of w_j SwiGLU_j(z), + SwiGLU_shared(z)
               balance loss of a row: sum_e f_e P_e, f_e = E / (k T) count_e,
               P_e = mean_t s_e / sum_e' s_e'
    LM       : logits = norm(y_L) Wout over the sliced vocabulary;  loss = mean
               next-token cross entropy + ALPHA * sum over the expert layers of
               the mean over the rows of the balance loss

The configuration's cut is the program's: the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``no_window`` drops the window, ``no_gate``
the per-head gate (g = 1), ``no_yarn`` yarn (the full layers rotate
their part of a head by the plain frequencies, factor 1): the controls
that each is computed at all.

The second check (perf/README.md), as the other MoE families': the loss
is a mean over 8192 positions x 12,544 classes at ln(12544) and does not
resolve a lower precision, so the family also holds the LOGITS of the
sample's last 64 positions (each sees a full window and 8k of global
context) to the reference's, where program and reference chose the same
of the experts this chip holds in every layer, and bounds the share of
ALL choices that differ by itself."""

import math

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 1e-4   # the balance loss's weight (assumed: joyai-llm-flash's)
LAST_POSITIONS = 64
# queries a block of the explicit scores: [2048, t] float32 and not
# [t, t] is live beside the training state
QUERY_BLOCK = 2048

# The second check's limits, set between two readings on the v5e at the
# published widths (my chip runs, PR 57,
# perf/tools/laguna_logits_control.py and the cell's own runs; PERF.md
# sections 4 and 6): the program (bf16 AMP) read an rms logit error of
# 0.00716-0.00727 of the logits' rms and 0.699-0.773% of the expert
# choices flipped over 17 seeds (59 to 64 of the 64 positions compared;
# 0.769% the larger of two more, run while XLA still made the full
# layers' rotation);
# the reference with every weight matmul's operands rounded to
# float8_e4m3fn, the nearest precision below bf16, read 0.0449-0.0452
# and 4.93-5.01% over 6 seeds (float8_e5m2: 0.125-0.126 and
# 12.3-12.4%) and comes out as not correct by either limit. Each limit
# is the geometric middle: 2.5 times the program's largest, as far under
# the control's smallest. On one seed the reference with the window
# DROPPED reads 0.0809 and 6.31%, with every gate at 1 0.178 and 15.7%,
# with yarn dropped (the full layers' half heads turned by the plain
# frequencies, factor 1) 0.0293 and 4.46%: each not correct by either
# reading; check_loss (8e-6, 2e-5 and 5e-6 of its 1e-3) sees none.
LOGIT_ERR_LIMIT = 0.018
FLIP_LIMIT = 0.0195


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(r, params, no_yarn=False):
    """[r / 2] inverse frequencies of r rotated features under one entry
    of ``rope_parameters`` (numpy float64: plain numbers at trace time)."""
    theta = float(params["rope_theta"])
    j = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / r)
    if params.get("rope_type", "default") != "yarn" or no_yarn:
        return f
    length = float(params["original_max_position_embeddings"])

    def correction(beta):
        return (r * math.log(length / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(float(params["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(params["beta_slow"]))), r - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / float(params["factor"]) * ramp + f * (1.0 - ramp)


def rope(x, dh, params, no_yarn=False):
    """x [b, h, t, dh]: the first r = dh * partial_rotary_factor
    features turn, feature j with j + r/2, position p by p * inv_freq_j;
    cos and sin carry yarn's attention factor; the rest pass."""
    t = x.shape[-2]
    r = int(dh * float(params.get("partial_rotary_factor", 1.0)))
    yarn = params.get("rope_type", "default") == "yarn" and not no_yarn
    scale = float(params.get("attention_factor", 1.0)) if yarn else 1.0
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq(r, params, no_yarn), jnp.float32)[None, :])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def attention(a, w, p, cfg, i, round_to=None, no_window=False,
              no_gate=False, no_yarn=False):
    """Attn_i of the normalised input a [b, t, d]."""
    b, t, _ = a.shape
    h = int(cfg["num_attention_heads_per_layer"][i])
    hk, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][i]
    window = (int(cfg["sliding_window"])
              if kind == "sliding_attention" and not no_window else None)
    params = cfg["rope_parameters"][kind]
    qkvg = _mm(a, w[f"{p}_attn_qkvg_colp.w"], round_to)
    q, k, v, g = jnp.split(
        qkvg, [h * dh, (h + hk) * dh, (h + 2 * hk) * dh], axis=-1)
    q = rope(q.reshape(b, t, h, dh).transpose(0, 2, 1, 3), dh, params,
             no_yarn)
    k = rope(k.reshape(b, t, hk, dh).transpose(0, 2, 1, 3), dh, params,
             no_yarn)
    v = v.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0, (t, blk)
    nb = t // blk
    s_pos = jnp.arange(t)[None, :]

    def one(args):   # one query head, one block of queries
        q_blk, head, p0 = args          # [b, blk, dh]
        k_h, v_h = k[:, head // (h // hk)], v[:, head // (h // hk)]
        s = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) / jnp.sqrt(jnp.float32(dh))
        p_pos = (p0 + jnp.arange(blk))[:, None]
        visible = s_pos <= p_pos
        if window is not None:
            visible = visible & (p_pos - s_pos < window)
        s = jnp.where(visible, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    q_blocks = q.reshape(b, h, nb, blk, dh).transpose(1, 2, 0, 3, 4)
    o = jax.lax.map(one, (
        q_blocks.reshape(h * nb, b, blk, dh),
        jnp.repeat(jnp.arange(h), nb), jnp.tile(jnp.arange(nb) * blk, h)))
    o = o.reshape(h, nb, b, blk, dh).transpose(2, 1, 3, 0, 4)  # b nb blk h dh
    o = o.reshape(b, t, h, dh)
    if not no_gate:
        o = o * jax.nn.sigmoid(g)[..., None]       # one value a head
    return _mm(o.reshape(b, t, h * dh), w[f"{p}_attn_out_rowp.w"], round_to)


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    count = int(cfg["num_experts"])
    return (int(cfg.get("held_first", 0)), count,
            int(cfg.get("router_experts", count)))


def route(x, wr, cfg, round_to=None):
    """x [b, t, d] -> (top_w [n, k], top_i [n, k], the mean over the
    rows of the balance loss) over all the experts the router scores."""
    b, t, d = x.shape
    k, e = cfg["num_experts_per_tok"], wr.shape[-1]
    s = jax.nn.sigmoid(_mm(x.reshape(b * t, d), wr, round_to))
    top_w, top_i = jax.lax.top_k(s, k)
    top_w = (top_w / jnp.sum(top_w, -1, keepdims=True)
             * cfg["moe_routed_scaling_factor"])
    count = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    f = e / (k * t) * jnp.sum(count.reshape(b, t, e), 1)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(b, t, e), 1)
    return top_w, top_i, jnp.mean(jnp.sum(f * p, -1))


def swiglu(x, wg, wu, wd, round_to):
    return _mm(jax.nn.silu(_mm(x, wg, round_to)) * _mm(x, wu, round_to),
               wd, round_to)


def moe(z, w, p, cfg, round_to=None, share=None, shared=True):
    """z [b, t, d] -> (out, top_i, balance loss). Every HELD expert on
    every token, weighted by the router (zero where the token did not
    choose it); an expert held elsewhere adds nothing here; the shared
    expert whole and ungated. ``share``: another (first, count) than the
    configuration's (the share test); ``shared`` False leaves the shared
    expert out (it is counted once over the shares)."""
    b, t, d = z.shape
    first, count, e = held(cfg)
    if share is not None:
        first, count = share
    top_w, top_i, lb = route(z, w[f"{p}_moe_router.w"], cfg, round_to)
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=z.dtype))
    weight = weight[:, first:first + count]
    zf = z.reshape(b * t, d)
    out = jnp.zeros_like(zf)
    for j in range(count):     # a loop over the held experts
        out = out + weight[:, j:j + 1] * swiglu(
            zf, w[f"{p}_moe_gate.w"][j], w[f"{p}_moe_up.w"][j],
            w[f"{p}_moe_down.w"][j], round_to)
    if shared:
        out = out + swiglu(zf, w[f"{p}_moe_shared_gate.w"],
                           w[f"{p}_moe_shared_up.w"],
                           w[f"{p}_moe_shared_down.w"], round_to)
    return out.reshape(b, t, d), top_i, lb


def forward(w, cfg, ids, round_to=None, last=None, **controls):
    """{"logits": [b, t or last, V], "top_i": [per expert layer
    [b*t, k]], "lb": the sum of the layers' balance losses} of token ids
    [b, t]. ``controls``: no_window, no_gate, no_yarn."""
    eps = cfg["rms_norm_eps"]
    x = w["laguna_tok_emb.w"][jnp.asarray(ids)]
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        a = norm(x, w[f"{p}_attn_norm.scale"], eps)
        x = x + attention(a, w, p, cfg, i, round_to, **controls)
        if cfg["mlp_layer_types"][i] == "dense":
            z = norm(x, w[f"{p}_mlp_norm.scale"], eps)
            x = x + swiglu(z, w[f"{p}_mlp_gate_colp.w"],
                           w[f"{p}_mlp_up_colp.w"],
                           w[f"{p}_mlp_down_rowp.w"], round_to)
            continue
        out, top_i, lb = moe(norm(x, w[f"{p}_moe_norm.scale"], eps), w, p,
                             cfg, round_to)
        x = x + out
        top_is.append(top_i)
        lbs.append(lb)
    x = norm(x, w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, w["lm_head_colp.w"], round_to),
            "top_i": top_is, "lb": sum(lbs)}


def loss(w, cfg, feed, round_to=None, **controls):
    out = forward(w, cfg, feed["input_ids"], round_to, **controls)
    logp = jax.nn.log_softmax(out["logits"], -1)
    ce = -jnp.take_along_axis(
        logp, jnp.asarray(feed["labels"])[..., None], -1)[..., 0]
    return jnp.mean(ce) + ALPHA * out["lb"]


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
    positions where every layer chose the same HELD experts, and the
    share of all (token, slot) choices that differ. The rms and not the
    largest difference, as Qwen3-Next's check says: a differing choice at
    an earlier position reaches every later one through the attention,
    so a few logits move by a step that no precision bounds; the largest
    is kept in the record, unjudged."""
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
