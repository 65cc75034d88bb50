"""Plain reference of SmallThinker language-model training (HF
``modeling_smallthinker.py``; arXiv:2507.20984): forward and loss in
float32 ``jax.numpy``, no kernels, nothing sorted, grouped or skipped.
Attention is explicit scores with the visibility rule written out, a
query head and a block of queries at a time; every held expert runs on
every token and the router's weights (zero for an expert a token did not
choose) pick what counts. Weights in, numbers out; gradients are
``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    layer i  : a = norm_in(x);  h = x + Attn_i(a)
               y = h + MoE(router reads a, experts read norm_post(h))
    Attn_i   : q, k, v = a W;  rope_layout[i]: RoPE (rotate-half, whole
               head, angle p * theta^(-2j/dh)) on q and k, else neither
               visible(p, s) = s <= p, and p - s < sliding_window_size where
               sliding_window_layout[i];  o = softmax(q k^T / sqrt(dh) over
               visible) v, kv head = q head // group;  out = o Wo
    MoE      : l = a Wr over all ``router_experts``; chosen = top k of l;
               w = softmax over the k chosen logits (HF's order: top-k,
               THEN softmax; the program takes the softmax over all and
               renormalises the k: the agreement is part of what is
               checked);  out = sum over the HELD experts among them
               (``held_first`` .. + ``moe_num_primary_experts``) of
               w_j (relu(z Wg_j) * (z Wu_j)) Wd_j,  z = norm_post(h)
    LM       : logits = norm(y_L) Wout over the sliced vocabulary;  loss =
               mean next-token cross entropy + 0.001 * load-balancing loss

The configuration's cut is the program's: the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``no_window`` drops the window (every layer
global): the control that the window is computed at all.

The second check (perf/README.md), as the other MoE families': the loss
is a mean over 16,384 positions x 18,992 classes at ln(18992) and does
not resolve a lower precision, so the family also holds the LOGITS of
the sample's last 64 positions (each sees a full window and 16k of
global context) to the reference's, where program and reference chose
the same of the experts this chip holds in every layer, and bounds the
share of ALL choices that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np


AUX_COEF = 0.001   # assumed: config.json carries no coefficient
LAST_POSITIONS = 64
# queries a block of the explicit scores: [2048, t] float32 and not
# [t, t] is live beside the training state (at t 16,384: 134 MB, not 1 GB)
QUERY_BLOCK = 2048

# The second check's limits, set between two readings on the v5e at the
# published widths (my chip run, PR 38,
# perf/tools/smallthinker_logits_control.py; PERF.md sections 4 and 6):
# the program (bf16 AMP) read an rms logit error of 0.00494-0.00511 of
# the logits' rms and 0.243-0.269% of the expert choices flipped over 33
# seeds (six of 64 leave fewer near-ties than Qwen3-Next's ten of 512,
# and four layers of attention average a bf16 stream's rounding over up
# to 16k keys); the reference with every weight matmul's operands rounded
# to float8_e4m3fn, the nearest precision below bf16, read 0.0284-0.0288
# and 2.66-2.73% over 12 seeds (float8_e5m2: 0.0778-0.0785 and
# 6.78-6.92%) and comes out as not correct by either limit. Each limit
# is the geometric middle: 2.4 and 3.1 times the program's largest, as
# far under the control's smallest. The reference with the window
# DROPPED reads 0.105-0.111 and 1.28-1.39% (two seeds): not correct by
# either reading; check_loss (7e-5 and 3e-5 of its 1e-3) does not see it.
LOGIT_ERR_LIMIT = 0.012
FLIP_LIMIT = 0.0084


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [b, h, t, dh]: rotate-half over the whole head (feature j pairs
    with j + dh/2), position p turns the pair by p * theta^(-2j/dh)."""
    t, dh = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def layer_kind(cfg, i):
    """(window or None, rotates) of layer i."""
    sw, rl = cfg["sliding_window_layout"], cfg["rope_layout"]
    return (int(cfg["sliding_window_size"]) if sw[i % len(sw)] else None,
            bool(rl[i % len(rl)]))


def attention(a, w, p, cfg, window, rotates, round_to=None):
    """Attn of the normalised input a [b, t, d]."""
    b, t, _ = a.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    qkv = _mm(a, w[f"{p}_attn_qkv_colp.w"], round_to)
    q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
    q = q.reshape(b, t, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    if rotates:
        q, k = rope(q, float(cfg["rope_theta"])), rope(k, float(
            cfg["rope_theta"]))
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
        if window is not None:     # HF: kv_idx > q_idx - sliding_window
            visible = visible & (p_pos - s_pos < window)
        s = jnp.where(visible, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    q_blocks = q.reshape(b, h, nb, blk, dh).transpose(1, 2, 0, 3, 4)
    o = jax.lax.map(one, (
        q_blocks.reshape(h * nb, b, blk, dh),
        jnp.repeat(jnp.arange(h), nb), jnp.tile(jnp.arange(nb) * blk, h)))
    o = o.reshape(h, nb, b, blk, dh).transpose(2, 1, 3, 0, 4)  # b nb blk h dh
    return _mm(o.reshape(b, t, h * dh), w[f"{p}_attn_out_rowp.w"], round_to)


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    count = int(cfg["moe_num_primary_experts"])
    return (int(cfg.get("held_first", 0)), count,
            int(cfg.get("router_experts", count)))


def route(r, wr, k, round_to=None):
    """r [n, d] -> (top_w [n, k], top_i [n, k], load-balancing loss):
    the k largest logits, then the softmax over those k."""
    logits = _mm(r, wr, round_to)
    top_l, top_i = jax.lax.top_k(logits, k)
    top_w = jax.nn.softmax(top_l, -1)
    e = wr.shape[-1]
    chose = jnp.sum(jax.nn.one_hot(top_i, e, dtype=r.dtype), axis=1)
    lb = e * jnp.sum(jnp.mean(chose, 0)
                     * jnp.mean(jax.nn.softmax(logits, -1), 0))
    return top_w, top_i, lb


def reglu(x, wg, wu, wd, round_to):
    return _mm(jax.nn.relu(_mm(x, wg, round_to)) * _mm(x, wu, round_to),
               wd, round_to)


def moe(r, z, w, p, cfg, round_to=None):
    """r, z [n, d] -> (out [n, d], top_i, lb): the router reads r, every
    HELD expert runs on every token of z, weighted by the router (zero
    where the token did not choose it)."""
    first, count, e = held(cfg)
    top_w, top_i, lb = route(r, w[f"{p}_moe_router.w"],
                             cfg["moe_num_active_primary_experts"], round_to)
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=z.dtype))
    weight = weight[:, first:first + count]

    def one(acc, args):
        g, u, dn, w_e = args
        return acc + w_e[:, None] * reglu(z, g, u, dn, round_to), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        w[f"{p}_moe_gate.w"], w[f"{p}_moe_up.w"], w[f"{p}_moe_down.w"],
        weight.T))
    return out, top_i, lb


def forward(w, cfg, ids, round_to=None, last=None, no_window=False):
    """{"logits": [b, t or last, V], "top_i": [per layer [b*t, k]],
    "lb"} of token ids [b, t]."""
    eps = cfg["rms_norm_eps"]
    x = w["smallthinker_tok_emb.w"][jnp.asarray(ids)]
    b, t, d = x.shape
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        window, rotates = layer_kind(cfg, i)
        a = norm(x, w[f"{p}_attn_norm.scale"], eps)
        x = x + attention(a, w, p, cfg, None if no_window else window,
                          rotates, round_to)
        out, top_i, lb = moe(
            a.reshape(b * t, d),
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


def loss(w, cfg, feed, round_to=None, no_window=False):
    out = forward(w, cfg, feed["input_ids"], round_to, no_window=no_window)
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
    (first, count, e), k = held(cfg), cfg["moe_num_active_primary_experts"]
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
