"""Plain reference of Kimi Linear language-model training ("Kimi Linear:
An Expressive, Efficient Attention Architecture", arXiv:2510.26692; HF
``modeling_kimi.py``): forward and loss in float32 ``jax.numpy``, no
kernels, nothing chunked, sorted or grouped. Kimi Delta Attention runs
its recurrence STEP BY STEP, one ``lax.scan`` step a position with the
[dk, dv] state of every head (the program runs a chunkwise algebra: a
wrong chunkwise form cannot agree with this); the three convolutions are
shifted sums; latent attention is a dense masked softmax over blocks of
query rows, with nothing rotated; every held expert runs on every token
in a loop and the router's weights (zero for an expert a token did not
choose) pick what counts; the selection bias enters the choice only.
Weights in, numbers out; gradients are ``jax.grad`` of ``loss``. Callers
run it under ``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    layer i  : h = x + Mix_i(norm(x));  y = h + FFN_i(norm(h))
               Mix_i is KDA where i + 1 is in ``kda_layers``, else MLA
    KDA      : [q | k | v] = silu(conv4(a Wqkv)) (H heads of dk);  q, k <-
               q / |q| / sqrt(dk), k / |k|;  [f | z | b] = a Wfgb;
               g = -exp(A_log[h]) softplus(f Wfb + dt_bias) [t, H, dk];
               beta = sigmoid(b) [t, H];  per head, S_0 = 0:
               S_t = Diag(exp(g_t)) S_{t-1};  S_t += k_t (beta_t (v_t -
               S_t^T k_t))^T;  o_t = S_t^T q_t;
               out = (o rsqrt(mean(o^2) + eps) w_n sigmoid(z Wgb)) Wo
    MLA      : q = a Wq -> per head [nope | pe];  [c_kv | k_pe] = a Wkva;
               [k_nope | v] = norm(c_kv) Wkvb per head;  k = [k_nope | k_pe]
               (k_pe ONE head shared by all);  o = causal softmax(q k^T /
               sqrt(nope + pe)) v;  out = o Wo. No positional embedding.
    FFN      : SwiGLU(intermediate_size) for i < first_k_dense_replace, else
               s = sigmoid(x Wr) over all ``router_experts``;  chosen = top k
               of s + b;  w_j = scale * s_j / sum_chosen s;  out = sum over
               the HELD experts among them (``held_first`` .. +
               ``num_experts``) of w_j SwiGLU_j(x) + SwiGLU_shared(x)
               balance loss of a row: sum_e f_e P_e, f_e = E/(k T) count_e,
               P_e = mean_t s_e / sum_e' s_e'
    LM       : logits = norm(y_L) Wout over the sliced vocabulary
    loss     = mean CE(logits_i, t_{i+1}) + ALPHA * sum over the expert
               layers of the mean over the rows of the balance loss

The configuration's cut is the program's: the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary. The storage this file reads is the
program's (q | k | v one matrix, [f | z | b] one): an order of columns,
no mathematics.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``rotate`` (a theta) turns the 64 shared key
features and the queries' last 64 by RoPE as DeepSeek-V3's latent
attention does: the control of a model that quietly rotates.

The second check (perf/README.md), as the other MoE families': the loss
is a mean over 4096 positions x 20,480 classes at ln(20480) and does not
resolve a lower precision, so the family also holds the LOGITS of the
sample's last positions to the reference's, where program and reference
chose the same of the experts this chip holds in every layer, and bounds
the share of ALL choices that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 1e-4       # the balance loss's weight (arXiv:2412.19437 4.2; assumed)
LAST_POSITIONS = 8
QUERY_BLOCK = 512  # rows of queries whose scores exist at a time

# The second check's limits, set between readings on the v5e at the
# published widths, at the state a run starts from (the family's
# ``build_graph``: the latent layer's queries drawn at std 0.1; my chip
# runs, PR 64; perf/tools/kimilinear_logits_control.py; PERF.md sections
# 4 and 6): the program (bf16 AMP) over 12 seeds read an rms logit error
# of 0.0169-0.0186 of the logits' rms and 1.78-1.88% of the expert
# choices flipped, 7 or 8 of the 8 positions compared; over 4 seeds this
# reference with every weight matmul's operands rounded to
# float8_e4m3fn, the nearest precision below bf16, read 0.1263-0.1292
# and 11.35-11.43% (float8_e5m2: 0.338-0.350 and 27.7-28.1%), and in
# full float32 with the 64 shared key features ROTATED 0.2266-0.2346
# and 11.6-11.9%: each comes out as not correct by both limits. Each
# limit is the geometric middle of the program's largest and the
# controls' smallest: 2.6 and 2.4 times of room on both sides. (At the
# builder's own state, every matrix at 0.02, the rotated control read
# 0.0094-0.0099, UNDER the program's 0.0153-0.0163: a near-uniform
# attention hides its positional part, which is why the family lays a
# sharper one.)
LOGIT_ERR_LIMIT = 0.048
FLIP_LIMIT = 0.046


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def causal_conv(x, w):
    """x [b, t, c], w [c, taps]: y_t = sum_j w[:, j] x_{t - taps + 1 + j},
    positions before the first zeros: shifted sums."""
    taps, t = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position a step. q, k [b, t, h, dk] (already
    normalised), v [b, t, h, dv], g [b, t, h, dk] (log decay of each
    ROW of the state), beta [b, t, h] -> o [b, t, h, dv]."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., :, None] * s
        delta = (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)) * b_t[..., None]
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    b, _, h, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(x, w, p, cfg, round_to=None):
    b, t, _ = x.shape
    la = cfg["linear_attn_config"]
    h, dh = la["num_heads"], la["head_dim"]
    qkv = jax.nn.silu(causal_conv(
        _mm(x, w[f"{p}_kda_qkv_colp.w"], round_to), w[f"{p}_kda_conv.w"]))
    q, k, v = (z.reshape(b, t, h, dh) for z in jnp.split(qkv, 3, axis=-1))
    fgb = _mm(x, w[f"{p}_kda_fgb.w"], round_to)
    f_a, g_a, b_ = fgb[..., :dh], fgb[..., dh:2 * dh], fgb[..., 2 * dh:]
    a = _mm(f_a, w[f"{p}_kda_f_b_colp.w"], round_to).reshape(b, t, h, dh)
    z = _mm(g_a, w[f"{p}_kda_g_b_colp.w"], round_to).reshape(b, t, h, dh)
    g = -jnp.exp(w[f"{p}_kda_A_log"])[:, None] * jax.nn.softplus(
        a + w[f"{p}_kda_dt_bias"])
    beta = jax.nn.sigmoid(b_)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    o = delta_rule(unit(q) / jnp.sqrt(jnp.float32(dh)), unit(k), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = o * w[f"{p}_kda_onorm.scale"] * jax.nn.sigmoid(z)
    return _mm(o.reshape(b, t, h * dh), w[f"{p}_kda_out_rowp.w"], round_to)


def rope_pairs(x, theta):
    """x [.., t, d]: features (2i, 2i + 1) of position p turned by the
    angle p * theta^(-2i/d) (the ``rotate`` control only)."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     -1).reshape(x.shape)


def latent_attention(x, w, p, cfg, round_to=None, rotate=None):
    b, t, _ = x.shape
    h, nope, pe, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, r = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    q = _mm(x, w[f"{p}_attn_q_colp.w"], round_to).reshape(
        b, t, h, nope + pe).transpose(0, 2, 1, 3)
    kva = _mm(x, w[f"{p}_attn_kv_a.w"], round_to)
    c_kv, k_pe = kva[..., :r], kva[..., r:]
    kv = _mm(norm(c_kv, w[f"{p}_attn_kv_a_norm.scale"], eps),
             w[f"{p}_attn_kv_b_colp.w"], round_to).reshape(
        b, t, h, nope + dv).transpose(0, 2, 1, 3)
    if rotate is not None:
        q = jnp.concatenate(
            [q[..., :nope], rope_pairs(q[..., nope:], rotate)], -1)
        k_pe = rope_pairs(k_pe, rotate)
    # the shared features are one head: every query head reads them
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (b, h, t, pe))], -1)
    v = kv[..., nope:]
    rows = min(QUERY_BLOCK, t)
    assert t % rows == 0, (t, rows)
    keys = jnp.arange(t)

    def block(i):   # a block of query rows at a time, all heads: the
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / jnp.sqrt(
            jnp.float32(nope + pe))         # [t, t] scores never coexist
        seen = keys[None, :] <= (i * rows + jnp.arange(rows))[:, None]
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)

    o = jax.lax.map(block, jnp.arange(t // rows))       # [n, b, h, rows, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, t, dv)
    return _mm(o.transpose(0, 2, 1, 3).reshape(b, t, h * dv),
               w[f"{p}_attn_out_rowp.w"], round_to)


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    return (int(cfg.get("held_first", 0)), int(cfg["num_experts"]),
            int(cfg.get("router_experts", cfg["num_experts"])))


def route(x, wr, bias, cfg, round_to=None):
    """x [b, t, d] -> (top_w [n, k], top_i [n, k], the mean over the
    rows of the balance loss) over all the experts the router scores."""
    b, t, d = x.shape
    k, e = cfg["num_experts_per_token"], wr.shape[-1]
    s = jax.nn.sigmoid(_mm(x.reshape(b * t, d), wr, round_to))
    _, top_i = jax.lax.top_k(s + bias, k)       # the bias: the choice only
    top_w = jnp.take_along_axis(s, top_i, -1)
    if cfg["moe_renormalize"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * cfg["routed_scaling_factor"]
    count = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    f = e / (k * t) * jnp.sum(count.reshape(b, t, e), 1)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(b, t, e), 1)
    return top_w, top_i, jnp.mean(jnp.sum(f * p, -1))


def swiglu(x, wg, wu, wd, round_to):
    return _mm(jax.nn.silu(_mm(x, wg, round_to)) * _mm(x, wu, round_to),
               wd, round_to)


def moe(x, w, p, cfg, round_to=None, share=None):
    """x [b, t, d] -> (out, top_i, balance loss). A loop over the HELD
    experts (``share``: another (first, count) than the configuration's;
    the weights ``w`` then hold that share's experts), each on every
    token, weighted by the router (zero where the token did not choose
    it); an expert held elsewhere adds nothing here; the shared expert
    whole and ungated."""
    b, t, d = x.shape
    first, count, e = held(cfg)
    if share is not None:
        first, count = share
    top_w, top_i, lb = route(x, w[f"{p}_moe_router.w"],
                             w[f"{p}_moe_router.bias"], cfg, round_to)
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=x.dtype))
    xf = x.reshape(b * t, d)
    out = jnp.zeros_like(xf)
    for j in range(count):
        out = out + weight[:, first + j, None] * swiglu(
            xf, w[f"{p}_moe_gate.w"][j], w[f"{p}_moe_up.w"][j],
            w[f"{p}_moe_down.w"][j], round_to)
    out = out + swiglu(xf, w[f"{p}_moe_shared_gate.w"],
                       w[f"{p}_moe_shared_up.w"],
                       w[f"{p}_moe_shared_down.w"], round_to)
    return out.reshape(b, t, d), top_i, lb


def layer(x, w, i, cfg, round_to=None, rotate=None):
    """(y, top_i or None, balance loss or None) of layer i (from 0)."""
    p, eps = f"blk{i}", cfg["rms_norm_eps"]
    if i + 1 in cfg["linear_attn_config"]["kda_layers"]:
        x = x + kda(norm(x, w[f"{p}_kda_norm.scale"], eps), w, p, cfg,
                    round_to)
    else:
        x = x + latent_attention(norm(x, w[f"{p}_attn_norm.scale"], eps),
                                 w, p, cfg, round_to, rotate)
    if i < cfg["first_k_dense_replace"]:
        return x + swiglu(norm(x, w[f"{p}_ffn_norm.scale"], eps),
                          w[f"{p}_ffn_gate_colp.w"], w[f"{p}_ffn_up_colp.w"],
                          w[f"{p}_ffn_down_rowp.w"], round_to), None, None
    out, top_i, lb = moe(norm(x, w[f"{p}_moe_norm.scale"], eps), w, p, cfg,
                         round_to)
    return x + out, top_i, lb


def forward(w, cfg, ids, round_to=None, last=None, rotate=None):
    """{"logits": [b, t or last, V], "top_i": [per expert layer,
    [b * t, k]], "lb": the sum of the layers' balance losses} of token
    ids [b, t]."""
    x = w["kimilinear_tok_emb.w"][jnp.asarray(ids)]
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, top_i, lb = layer(x, w, i, cfg, round_to, rotate)
        if top_i is not None:
            top_is.append(top_i)
            lbs.append(lb)
    x = norm(x, w["final_norm.scale"], cfg["rms_norm_eps"])
    logits = _mm(x if last is None else x[:, -last:], w["lm_head_colp.w"],
                 round_to)
    return {"logits": logits, "top_i": top_is, "lb": sum(lbs)}


def _ce(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def loss(w, cfg, feed, round_to=None, rotate=None):
    labels = jnp.asarray(feed["labels"])
    out = forward(w, cfg, feed["input_ids"], round_to, rotate=rotate)
    return jnp.mean(_ce(out["logits"], labels)) + ALPHA * out["lb"]


def chosen(a, n_experts):
    """[n, E] bool: which experts each token's choices ``a`` [n, k] hold
    (sets: the order of the k does not matter)."""
    a = np.asarray(a)
    out = np.zeros((a.shape[0], n_experts), bool)
    out[np.arange(a.shape[0])[:, None], a] = True
    return out


def compare(cfg, want, got_logits, got_top_i):
    """The second check's readings of ``got`` against the reference's
    ``want`` (``forward(..., last=LAST_POSITIONS)``): the rms of the
    logit differences over the logits' rms among the last positions
    where every layer chose the same HELD experts, and the share of all
    (token, slot) choices that differ. The rms and not the largest
    difference: a differing choice at an earlier position reaches every
    later one through the state and the attention, so a few logits move
    by a discrete step that no precision bounds."""
    got = np.asarray(got_logits, np.float32)
    ref = np.asarray(want["logits"], np.float32)
    (first, count, e), k = held(cfg), cfg["num_experts_per_token"]
    sets = [(chosen(g, e), chosen(r, e))
            for g, r in zip(got_top_i, want["top_i"])]       # [n, E] each
    diff = np.stack([(g & ~r).sum(1) for g, r in sets])      # [L, n]
    mine = slice(first, first + count)
    held_differ = sum((g[:, mine] != r[:, mine]).sum(1) for g, r in sets)
    same = (held_differ == 0).reshape(got.shape[0], -1)[:, -ref.shape[1]:]
    sq = ((got - ref) ** 2).mean(-1)                         # [b, last]
    return {
        "flipped_share": float(diff.sum() / (diff.size * k)),
        "logit_err_over_rms": float(
            np.sqrt(sq[same].mean() / np.mean(ref ** 2))
        ) if same.any() else float("nan"),
        "positions_compared": int(same.sum()),
        "positions": int(same.size)}


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits``, ``top_i``
    and ``expert_rows`` on the sample (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids: forward(
        w_, cfg, ids, last=LAST_POSITIONS))(
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
    err = record["logit_err_over_rms"]
    if not record["positions_compared"]:
        problems.append("no last position where program and reference "
                        "chose the same experts: nothing to compare")
    elif not err <= LOGIT_ERR_LIMIT:
        problems.append(
            f"last-position logits differ from the reference's by "
            f"{err:.3g} of their rms > {LOGIT_ERR_LIMIT}")
    if not record["flipped_share"] <= FLIP_LIMIT:
        problems.append(
            f"{100 * record['flipped_share']:.2f}% of the expert choices "
            f"differ from the reference's > {100 * FLIP_LIMIT}%")
    return problems, record
