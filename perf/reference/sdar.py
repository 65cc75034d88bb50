"""Plain reference of SDAR's block-diffusion training step (SDAR,
arXiv:2510.06303; the mask and the loss are block diffusion's, Arriola
et al. 2025, arXiv:2503.09573; the layer is HF's Qwen3-MoE block):
forward and loss in float32 ``jax.numpy``, no kernels, nothing sorted,
grouped or skipped. The row is the FEED's, [xt ; x0] of L tokens each:
the noise was drawn on the host, so the reference sees the very row the
program saw. Attention is explicit scores under the mask built from the
rule by index arithmetic, a query head and a block of query rows at a
time; every held expert runs on every position and the router's weights
(zero for an expert a position did not choose) pick what counts. Weights
in, numbers out; gradients are ``jax.grad`` of ``loss``. Callers run it
under ``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    row      : ids [b, 2L] = [xt ; x0];  x = E[ids];  index i is position i mod L
    layer    : h = x + Attn(norm_in(x));  y = h + MoE(norm_post(h))
    Attn     : q, k, v = a W;  q, k = norm_q(q), norm_k(k) over each head's dh
               RoPE (rotate-half, whole head, angle (i mod L) * theta^(-2j/dh))
               visible(p, s), P = p mod L, S = s mod L, bp = P // B, bs = S // B:
                 p <  L, s <  L :  bp == bs
                 p <  L, s >= L :  bs <  bp
                 p >= L, s >= L :  bs <= bp
                 p >= L, s <  L :  never
               o = softmax(q k^T / sqrt(dh) over visible) v, kv head = q head // group
    MoE      : s = softmax(z Wr) over all ``router_experts``; the k largest,
               renormalised over the k;  out = sum over the HELD experts
               among them (``held_first`` .. + ``num_experts``) of
               w_j (silu(z Wg_j) * (z Wu_j)) Wd_j
    LM       : logits_i = norm(y_L)_i Wout over the sliced vocabulary, i < L
               loss = (1 / (b L)) sum_{labels_i != ignore} CE(logits_i, labels_i)
                      * loss_weight_i  +  0.001 * load-balancing loss (the mean
                      over the layers, each from all 2L positions' routing)

The configuration's cut is the program's: the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``leak`` lets the clean half see the noised
half (p >= L, s < L: bs <= bp): the control that the mask's fourth
quadrant is shut at all.

The second check (perf/README.md), as the other MoE families': the loss
is a weighted sum over the row's masked positions at ln(18992) a
position and does not resolve a lower precision, so the family also
holds the LOGITS of the sample's last 64 MASKED positions of the noised
half (each sees its own noised block and every clean block before it)
to the reference's, where program and reference chose the same of the
experts this chip holds in every layer, and bounds the share of ALL 2L
positions' choices that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np


AUX_COEF = 0.001   # assumed: config.json carries no coefficient
IGNORE_INDEX = -100
# the program offers the logits of the noised half's last LAST_POSITIONS
# positions; the check compares the last COMPARED masked ones among them
LAST_POSITIONS = 256
COMPARED = 64
# query rows a block of the explicit scores: [1024, 2L] float32 and not
# [2L, 2L] is live beside the training state (at L 4096: 34 MB, not 268)
QUERY_BLOCK = 1024

# The second check's limits, set between two readings on the v5e at the
# published widths (perf/tools/sdar_logits_control.py; PERF.md sections
# 4 and 6): the program's largest over its seeds, and the reference's own
# smallest with every weight matmul's operands rounded to float8_e4m3fn.
LOGIT_ERR_LIMIT = 0.15
FLIP_LIMIT = 0.08


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [b, h, t, dh] at ``positions`` [t]: rotate-half over the whole
    head (feature j pairs with j + dh/2), position p turns the pair by
    p * theta^(-2j/dh)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def visible(p, s, half, block, leak=False):
    """The rule, by index arithmetic: may query index ``p`` see key index
    ``s`` of a row of two halves of ``half`` positions in blocks of
    ``block``?"""
    bp, bs = (p % half) // block, (s % half) // block
    q_noised, k_noised = p < half, s < half
    seen = ((q_noised & k_noised & (bp == bs))
            | (q_noised & ~k_noised & (bs < bp))
            | (~q_noised & ~k_noised & (bs <= bp)))
    if leak:
        seen = seen | (~q_noised & k_noised & (bs <= bp))
    return seen


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def attention(a, w, p, cfg, round_to=None, leak=False):
    """Attn of the normalised input a [b, 2L, d]."""
    b, t, _ = a.shape
    half = t // 2
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    qkv = _mm(a, w[f"{p}_attn_qkv_colp.w"], round_to)
    q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
    q = norm(q.reshape(b, t, h, dh), w[f"{p}_attn_qnorm.scale"], eps)
    k = norm(k.reshape(b, t, hk, dh), w[f"{p}_attn_knorm.scale"], eps)
    positions = jnp.arange(t) % half
    q = rope(q.transpose(0, 2, 1, 3), positions, theta)
    k = rope(k.transpose(0, 2, 1, 3), positions, theta)
    v = v.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    blk = min(QUERY_BLOCK, t)
    while t % blk:
        blk -= 1
    nb = t // blk
    s_idx = jnp.arange(t)[None, :]

    def one(args):   # one query head, one block of query rows
        q_blk, head, p0 = args          # [b, blk, dh]
        k_h, v_h = k[:, head // (h // hk)], v[:, head // (h // hk)]
        s = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) / jnp.sqrt(jnp.float32(dh))
        p_idx = (p0 + jnp.arange(blk))[:, None]
        s = jnp.where(visible(p_idx, s_idx, half, cfg["block_length"], leak),
                      s, -1e30)
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
    count = int(cfg["num_experts"])
    return (int(cfg.get("held_first", 0)), count,
            int(cfg.get("router_experts", count)))


def route(z, wr, k, round_to=None):
    """z [n, d] -> (top_w [n, k], top_i [n, k], load-balancing loss):
    the k largest of the softmax over all, renormalised over the k."""
    probs = jax.nn.softmax(_mm(z, wr, round_to), -1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    e = wr.shape[-1]
    chose = jnp.sum(jax.nn.one_hot(top_i, e, dtype=z.dtype), axis=1)
    lb = e * jnp.sum(jnp.mean(chose, 0) * jnp.mean(probs, 0))
    return top_w, top_i, lb


def moe(z, w, p, cfg, round_to=None):
    """z [n, d] -> (out [n, d], top_i, lb): every HELD expert runs on
    every position, weighted by the router (zero where the position did
    not choose it)."""
    first, count, e = held(cfg)
    top_w, top_i, lb = route(z, w[f"{p}_moe_router.w"],
                             cfg["num_experts_per_tok"], round_to)
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=z.dtype))
    weight = weight[:, first:first + count]

    def one(acc, args):
        g, u, dn, w_e = args
        hidden = jax.nn.silu(_mm(z, g, round_to)) * _mm(z, u, round_to)
        return acc + w_e[:, None] * _mm(hidden, dn, round_to), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        w[f"{p}_moe_gate.w"], w[f"{p}_moe_up.w"], w[f"{p}_moe_down.w"],
        weight.T))
    return out, top_i, lb


def forward(w, cfg, ids, round_to=None, last=None, leak=False):
    """{"logits": [b, L or last, V] of the NOISED half's positions (its
    last ``last``), "top_i": [per layer [b*2L, k]], "lb"} of the row
    ids [b, 2L] = [xt ; x0]."""
    eps = cfg["rms_norm_eps"]
    x = w["sdar_tok_emb.w"][jnp.asarray(ids)]
    b, t, d = x.shape
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        x = x + attention(norm(x, w[f"{p}_attn_norm.scale"], eps), w, p,
                          cfg, round_to, leak)
        out, top_i, lb = moe(
            norm(x, w[f"{p}_moe_norm.scale"], eps).reshape(b * t, d), w, p,
            cfg, round_to)
        x = x + out.reshape(b, t, d)
        top_is.append(top_i)
        lbs.append(lb)
    x = norm(x[:, :t // 2], w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, w["lm_head_colp.w"], round_to),
            "top_i": top_is, "lb": sum(lbs) / len(lbs)}


def loss(w, cfg, feed, round_to=None, leak=False):
    out = forward(w, cfg, feed["input_ids"], round_to, leak=leak)
    labels = jnp.asarray(feed["labels"])
    counts = labels != IGNORE_INDEX
    logp = jax.nn.log_softmax(out["logits"], -1)
    ce = -jnp.take_along_axis(
        logp, jnp.where(counts, labels, 0)[..., None], -1)[..., 0]
    weighted = jnp.where(counts, ce * jnp.asarray(feed["loss_weight"]), 0.0)
    return jnp.sum(weighted) / labels.size + AUX_COEF * out["lb"]


def chosen(a, n_experts):
    """[n, E] bool: which experts each position's choices ``a`` [n, k]
    hold (sets: the order of the k does not matter)."""
    a = np.asarray(a)
    out = np.zeros((a.shape[0], n_experts), bool)
    out[np.arange(a.shape[0])[:, None], a] = True
    return out


def compared_positions(labels, last=LAST_POSITIONS, n=COMPARED):
    """[b, last] bool: of the noised half's last ``last`` positions, each
    row's last ``n`` masked ones (all of them where there are fewer)."""
    masked = np.asarray(labels)[:, -last:] != IGNORE_INDEX
    later = np.cumsum(masked[:, ::-1], axis=1)[:, ::-1]   # masked from here on
    return masked & (later <= n)


def compare(cfg, labels, want, got_logits, got_top_i):
    """The second check's two readings of ``got`` against the
    reference's ``want`` (``forward(..., last=LAST_POSITIONS)``): the rms
    of the logit differences over the logits' rms among the compared
    positions (``compared_positions``) where every layer chose the same
    HELD experts, and the share of all (position, slot) choices that
    differ. The rms and not the largest difference, as Qwen3-Next's
    check says: a differing choice at a clean position reaches every
    later block through the attention, so a few logits move by a step
    that no precision bounds; the largest is kept in the record,
    unjudged."""
    want_logits = np.asarray(want["logits"], np.float32)
    got_logits = np.asarray(got_logits, np.float32)
    b, last = want_logits.shape[:2]
    half = np.asarray(labels).shape[1]
    (first, count, e), k = held(cfg), cfg["num_experts_per_tok"]
    sets = [(chosen(g, e), chosen(r, e))
            for g, r in zip(got_top_i, want["top_i"])]       # [b 2L, E] each
    diff = np.stack([(g & ~r).sum(1) for g, r in sets])      # [layers, b 2L]
    mine = slice(first, first + count)
    held_differ = sum((g[:, mine] != r[:, mine]).sum(1) for g, r in sets)
    # (the noised half's last positions of each row of 2L)
    same = (held_differ == 0).reshape(b, 2 * half)[:, half - last:half]
    at = same & compared_positions(labels, last)
    scale = np.sqrt(np.mean(want_logits ** 2))
    sq = ((got_logits - want_logits) ** 2).mean(-1)        # [b, last]
    worst = np.abs(got_logits - want_logits).max(-1) / scale
    return {"logit_err_over_rms": float(np.sqrt(sq[at].mean()) / scale)
            if at.any() else float("nan"),
            "logit_max_err_over_rms": float(worst[at].max())
            if at.any() else float("nan"),
            "positions_compared": int(at.sum()),
            "positions": int(compared_positions(labels, last).sum()),
            "flipped_share": float(diff.sum() / (diff.size * k))}


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits``, ``top_i``
    and ``expert_rows`` on the sample (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids: forward(w_, cfg, ids,
                                           last=LAST_POSITIONS))(
        w, jnp.asarray(sample["input_ids"]))
    record = compare(cfg, sample["labels"], want, fetched["last_logits"],
                     fetched["top_i"])
    rows = np.asarray(fetched["expert_rows"], np.float64)   # [L, held]
    pairs = np.asarray(fetched["top_i"][0]).size
    record["max_expert_load"] = float(
        (rows.max(1) / np.maximum(rows.mean(1), 1e-9)).max())
    # the (position, slot) pairs on experts this chip holds, over all pairs
    record["held_row_share"] = float(rows.sum(1).mean() / pairs)
    record["limits"] = [LOGIT_ERR_LIMIT, FLIP_LIMIT]
    problems = []
    if not record["positions_compared"]:
        problems.append("no masked position where program and reference "
                        "chose the same experts: nothing to compare")
    elif not record["logit_err_over_rms"] <= LOGIT_ERR_LIMIT:
        problems.append(
            f"masked-position logits differ from the reference's by "
            f"{record['logit_err_over_rms']:.3g} of their rms > "
            f"{LOGIT_ERR_LIMIT}")
    if not record["flipped_share"] <= FLIP_LIMIT:
        problems.append(
            f"{100 * record['flipped_share']:.2f}% of the expert choices "
            f"differ from the reference's > {100 * FLIP_LIMIT}%")
    return problems, record
