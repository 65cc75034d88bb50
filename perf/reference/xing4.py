"""Plain reference of Xing4.0 language-model training: a model of
DeepSeek-V3's shape (arXiv:2412.19437 2.1-2.2, HF
``modeling_deepseek_v3.py``) whose residual path is manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over hyper-connections,
arXiv:2409.19606) and whose rotary table is yarn's (Peng et al.,
arXiv:2309.00071, as DeepSeek applies it). Forward and loss in float32
``jax.numpy``, no kernels, nothing sorted, grouped or token-minor: the
streams are [b, t, n, d], a token's mix an [n, n] matrix under
``jnp.sum``; latent attention is explicit scores, a head at a time, its
rotary pairs turned by an explicit 2 x 2 rotation each; every held
expert runs on every token and the router's weights pick what counts.
Weights in, numbers out; gradients are ``jax.grad`` of ``loss``. Callers
run it under ``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    n = hc_mult streams X [n, d] a token; for EACH sublayer s:
      r      = rsqrt(mean(vec(X)^2) + eps)                 over n d, no gain
      m      = (vec(X) Phi_s) * r                          [n^2 + 2n]
      H_pre  = sigmoid(alpha_s[0] m[:n] + b_s[:n])
      H_post = 2 sigmoid(alpha_s[1] m[n:2n] + b_s[n:2n])
      M      = exp(clamp(alpha_s[2] mat(m[2n:]) + mat(b_s[2n:]), lo, hi))
      hc_sinkhorn_iters x: M <- M / (rowsum(M) + hc_eps);
                           M <- M / (colsum(M) + hc_eps);      H_res = M
      h = sum_i H_pre[i] X[i];  y = F_s(norm_s(h));
      X'[j] = sum_i H_res[j, i] X[i] + H_post[j] y
    read-in: X[i] = Emb(token) for every i; read-out: x = sum_i X_L[i]
    layer i  : attention, then SwiGLU(intermediate_size) for i <
               first_k_dense_replace, else the MoE: two sublayers
    MLA      : c_q = norm(x Wqa);  q = c_q Wqb -> per head [q_nope | q_rope]
               [c_kv | k_rope] = x Wkva;  [k_nope | v] = norm(c_kv) Wkvb
               q_rope, k_rope <- pairs (2i, 2i+1) turned by p * f_i, f yarn's:
               f_i = theta^(-2i/rope) (1 - ramp_i + ramp_i / factor), ramp
               from beta_fast to beta_slow rotations over the original
               length; cos, sin times mscale(factor, mscale) /
               mscale(factor, mscale_all_dim); k_rope ONE head for all
               o = causal softmax([q_nope|q_rope] [k_nope|k_rope]^T *
                   mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)) v
               mscale(f, m) = 0.1 m ln f + 1
    MoE      : s = sigmoid(x Wr) over all ``router_experts``;  chosen = top k
               of s + b;  w_j = scale * s_j / sum_chosen s;  out = sum over the
               HELD experts among them of w_j SwiGLU_j(x) + SwiGLU_shared(x)
               balance loss of a row: sum_e f_e P_e
    LM       : logits = norm(x) Wout over the sliced vocabulary
    MTP      : h' = [norm_h(x) | norm_e(Emb(t_{i+1}))] Weh  (x BEFORE the final
               norm);  Z = read-in(h');  one layer under its own mixes;
               logits' = norm_mtp(sum_i Z'[i]) Wout
    loss     = mean CE(logits_i, t_{i+1}) + MTP_LAMBDA * mean_{i<T-1}
               CE(logits'_i, t_{i+2}) + ALPHA * sum of the balance losses

The configuration's cut is the program's: the same held share of the
experts and the same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication (the mix's projection too) to that dtype first: the
lower-precision control of the second check (float8 is the nearest
precision below the bf16 the configuration trains in). ``control`` (a
name of ``CONTROLS``) leaves one piece of the mechanism out or does it
wrong: a model that quietly did the same must fail the second check
(perf/tools/xing4_logits_control.py).

The second check (perf/README.md), as the other MoE families': the loss
is a mean over 4096 positions x 16,384 classes at ln(16384) and does not
resolve a lower precision, so the family also holds the LOGITS of the
sample's last positions to the reference's, where program and reference
chose the same of the experts this chip holds in every layer, and bounds
the share of ALL choices that differ by itself."""

import math

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 1e-4       # the balance loss's weight (arXiv:2412.19437 4.2; assumed)
MTP_LAMBDA = 0.1   # the MTP loss's weight (the paper: 0.3, then 0.1; assumed)
LAST_POSITIONS = 8

# what ``control`` may name: each a model that is NOT this one
CONTROLS = ("one_iteration",      # 1 Sinkhorn iteration for hc_sinkhorn_iters
            "no_column_step",     # rows normalised, columns never
            "post_without_2",     # H_post = sigmoid(..), not 2 sigmoid(..)
            "no_clamp",           # exp of the unclamped matrix
            "mix_from_stream_0",  # m from X[0] and Phi's first d rows alone
            "no_mscale",          # the softmax scale without mscale^2
            "no_yarn")            # the plain rotary table

# The second check's limits, one for the logits and one for the choices,
# set between two readings on the v5e at the published widths, at the
# state a run starts from (the family's ``build_graph``: gates of 1, H_res's
# bias drawn normal(0, 1), q_b at std 0.05; my chip runs, PR 67;
# perf/tools/xing4_logits_control.py; PERF.md sections 4 and 6): the
# program (bf16 AMP, bf16 streams) over 12 seeds read an rms logit error
# of 0.0229-0.0360 of the logits' rms (0.0307-0.0372 on two seeds at an
# H_res bias drawn 25 wide, where the clamp works) and 2.64-3.01% of the
# expert choices flipped (3.02-3.11%), 6 to 8 of the 8 positions
# compared; 14 more runs of the cell read inside that. Over 3 seeds this
# reference with every weight matmul's operands rounded to float8_e4m3fn,
# the nearest precision below bf16, read 0.196-0.340 and 18.96-20.00%
# (float8_e5m2: 0.412-0.645 and 38.0-39.6%), and comes out as not correct
# by both limits. Each limit is the geometric middle of the program's
# largest and the control's smallest: 2.3 times either way. The mechanism
# controls (two seeds): one Sinkhorn iteration 0.0895-0.315 and
# 10.0-10.4%; no column step 0.242-0.388 and 14.6-14.7%; H_post without
# its 2 0.373-0.388 and 28.7-29.1%; the mix from stream 0 alone
# 0.199-0.397 and 25.8%; without the clamp (bias 25 wide) 38.5-61.4% of
# the choices flipped, without mscale^2 75.3-75.5%, without the yarn table
# 83.0-83.2%, no last position left to compare: each not correct.
LOGIT_ERR_LIMIT = 0.085
FLIP_LIMIT = 0.077


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


# -- the residual path ------------------------------------------------------


def mix(xs, w, p, cfg, round_to=None, control=None):
    """(H_pre [b, t, n], H_post [b, t, n], H_res [b, t, n, n]) of the
    streams xs [b, t, n, d] under the mix ``<p>_hc*``."""
    b, t, n, d = xs.shape
    phi, bias, alpha = (w[f"{p}_hc_phi.w"], w[f"{p}_hc.bias"],
                        w[f"{p}_hc.alpha"])
    flat = xs.reshape(b, t, n * d)
    if control == "mix_from_stream_0":
        flat, phi = xs[:, :, 0], phi[:d]
    r = jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                      + cfg["rms_norm_eps"])
    m = _mm(flat, phi, round_to) * r
    h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
    h_post = jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + bias[n:2 * n])
    if control != "post_without_2":
        h_post = 2.0 * h_post
    z = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
    if control != "no_clamp":
        z = jnp.clip(z, cfg["mhc_h_res_clamp_min"],
                     cfg["mhc_h_res_clamp_max"])
    mat = jnp.exp(z)
    iters = 1 if control == "one_iteration" else cfg["hc_sinkhorn_iters"]
    for _ in range(iters):
        mat = mat / (jnp.sum(mat, -1, keepdims=True) + cfg["hc_eps"])
        if control != "no_column_step":
            mat = mat / (jnp.sum(mat, -2, keepdims=True) + cfg["hc_eps"])
    return h_pre, h_post, mat


def hyper_connected(xs, w, p, cfg, sublayer, round_to=None, control=None):
    """The streams behind ``sublayer`` (h [b, t, d] -> y or (y, more))
    -> (streams, more or None)."""
    h_pre, h_post, h_res = mix(xs, w, p, cfg, round_to, control)
    y, more = sublayer(jnp.einsum("bti,btid->btd", h_pre, xs)), None
    if isinstance(y, tuple):
        y, more = y
    return (jnp.einsum("btji,btid->btjd", h_res, xs)
            + h_post[..., None] * y[:, :, None, :]), more


# -- latent attention -------------------------------------------------------


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary_frequencies(dim, theta, scaling):
    """[dim / 2] angles a position of the pairs, yarn's where ``scaling``
    is the config's ``rope_scaling``."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return jnp.asarray(f, jnp.float32)
    orig = scaling["original_max_position_embeddings"]

    def correction(rotations):   # the pair that turns so often over orig
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(f * (1.0 - ramp) + f / scaling["factor"] * ramp,
                       jnp.float32)


def rope_pairs(x, freq, factor=1.0):
    """x [.., t, d]: features (2i, 2i + 1) of position p turned by the
    angle p * freq_i, each pair by its own 2 x 2 rotation (times
    ``factor``)."""
    t, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    rot = factor * jnp.stack(
        [jnp.stack([jnp.cos(ang), -jnp.sin(ang)], -1),
         jnp.stack([jnp.sin(ang), jnp.cos(ang)], -1)], -2)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))          # [.., t, d/2, 2]
    return jnp.einsum("tiab,...tib->...tia", rot, pairs).reshape(x.shape)


def latent_attention(x, w, p, cfg, round_to=None, control=None):
    b, t, _ = x.shape
    h, nope, rope, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, r = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    rs = cfg.get("rope_scaling")
    table, scale = 1.0, 1.0 / math.sqrt(nope + rope)
    if rs and control != "no_mscale":
        scale *= mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    if control == "no_yarn":
        rs = None
    if rs:
        table = (mscale(rs["factor"], rs["mscale"])
                 / mscale(rs["factor"], rs["mscale_all_dim"]))
    freq = rotary_frequencies(rope, float(cfg["rope_theta"]), rs)
    c_q = norm(_mm(x, w[f"{p}_attn_q_a.w"], round_to),
               w[f"{p}_attn_q_a_norm.scale"], eps)
    q = _mm(c_q, w[f"{p}_attn_q_b_colp.w"], round_to).reshape(
        b, t, h, nope + rope).transpose(0, 2, 1, 3)
    kva = _mm(x, w[f"{p}_attn_kv_a.w"], round_to)
    c_kv, k_rope = kva[..., :r], kva[..., r:]
    kv = _mm(norm(c_kv, w[f"{p}_attn_kv_a_norm.scale"], eps),
             w[f"{p}_attn_kv_b_colp.w"], round_to).reshape(
        b, t, h, nope + dv).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], freq, table)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = rope_pairs(k_rope, freq, table)    # [b, t, rope]: one head
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(args):   # a head at a time, so that the [t, t] scores
        qn, qr, kn, v_h = args   # of all heads never coexist
        s = (jnp.einsum("bqd,bkd->bqk", qn, kn)
             + jnp.einsum("bqd,bkd->bqk", qr, k_rope)) * scale
        s = jnp.where(causal, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    o = jax.lax.map(one_head, tuple(
        z.transpose(1, 0, 2, 3) for z in (q_nope, q_rope, k_nope, v)))
    o = o.transpose(1, 2, 0, 3).reshape(b, t, h * dv)
    return _mm(o, w[f"{p}_attn_out_rowp.w"], round_to)


# -- the feed-forward sublayers ----------------------------------------------


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    return (int(cfg.get("held_first", 0)), int(cfg["n_routed_experts"]),
            int(cfg.get("router_experts", cfg["n_routed_experts"])))


def route(x, wr, bias, cfg, round_to=None):
    """x [b, t, d] -> (top_w [n, k], top_i [n, k], the mean over the
    rows of the balance loss) over all the experts the router scores."""
    b, t, d = x.shape
    k, e = cfg["num_experts_per_tok"], wr.shape[-1]
    s = jax.nn.sigmoid(_mm(x.reshape(b * t, d), wr, round_to))
    _, top_i = jax.lax.top_k(s + bias, k)       # the bias: the choice only
    top_w = jnp.take_along_axis(s, top_i, -1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * cfg["routed_scaling_factor"]
    count = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    f = e / (k * t) * jnp.sum(count.reshape(b, t, e), 1)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(b, t, e), 1)
    return top_w, top_i, jnp.mean(jnp.sum(f * p, -1))


def swiglu(x, wg, wu, wd, round_to):
    return _mm(jax.nn.silu(_mm(x, wg, round_to)) * _mm(x, wu, round_to),
               wd, round_to)


def moe(x, w, p, cfg, round_to=None):
    """x [b, t, d] -> (out, top_i, balance loss). Every HELD expert on
    every token, weighted by the router (zero where the token did not
    choose it); an expert held elsewhere adds nothing here; the shared
    expert whole and ungated."""
    b, t, d = x.shape
    first, count, e = held(cfg)
    top_w, top_i, lb = route(x, w[f"{p}_moe_router.w"],
                             w[f"{p}_moe_router.bias"], cfg, round_to)
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
    out = out + swiglu(xf, w[f"{p}_moe_shared_gate.w"],
                       w[f"{p}_moe_shared_up.w"],
                       w[f"{p}_moe_shared_down.w"], round_to)
    return out.reshape(b, t, d), top_i, lb


def layer(xs, w, p, cfg, dense, round_to=None, control=None):
    """(streams, top_i or None, balance loss or None) of one decoder
    layer: two hyper-connected sublayers."""
    eps = cfg["rms_norm_eps"]
    xs, _ = hyper_connected(
        xs, w, f"{p}_attn", cfg, lambda h: latent_attention(
            norm(h, w[f"{p}_attn_norm.scale"], eps), w, p, cfg, round_to,
            control), round_to, control)
    if dense:
        xs, _ = hyper_connected(
            xs, w, f"{p}_ffn", cfg, lambda h: swiglu(
                norm(h, w[f"{p}_ffn_norm.scale"], eps),
                w[f"{p}_ffn_gate_colp.w"], w[f"{p}_ffn_up_colp.w"],
                w[f"{p}_ffn_down_rowp.w"], round_to), round_to, control)
        return xs, None, None

    def experts(h):
        out, top_i, lb = moe(norm(h, w[f"{p}_moe_norm.scale"], eps), w, p,
                             cfg, round_to)
        return out, (top_i, lb)

    xs, (top_i, lb) = hyper_connected(xs, w, f"{p}_moe", cfg, experts,
                                      round_to, control)
    return xs, top_i, lb


def read_in(x, n):
    return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n,) + x.shape[2:])


def forward(w, cfg, ids, labels, round_to=None, last=None, control=None):
    """{"logits" (and "mtp_logits" with the module): [b, t or last, V],
    "top_i": [per expert layer, the MTP module's behind the stack's,
    [b*t, k]], "lb": the sum of the layers' balance losses} of token ids
    [b, t] and their next tokens ``labels`` [b, t] (the MTP module's
    second input)."""
    assert control is None or control in CONTROLS, control
    eps, n = cfg["rms_norm_eps"], cfg["hc_mult"]
    emb = w["xing4_tok_emb.w"]
    xs = read_in(emb[jnp.asarray(ids)], n)
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        xs, top_i, lb = layer(xs, w, f"blk{i}", cfg,
                              i < cfg["first_k_dense_replace"], round_to,
                              control)
        if top_i is not None:
            top_is.append(top_i)
            lbs.append(lb)
    x = jnp.sum(xs, 2)

    def head(z):
        return _mm(z if last is None else z[:, -last:],
                   w["lm_head_colp.w"], round_to)

    out = {"logits": head(norm(x, w["final_norm.scale"], eps))}
    if cfg["num_nextn_predict_layers"]:
        merged = jnp.concatenate(
            [norm(x, w["mtp_hnorm.scale"], eps),
             norm(emb[jnp.asarray(labels)], w["mtp_enorm.scale"], eps)], -1)
        zs, top_i, lb = layer(
            read_in(_mm(merged, w["mtp_eh_proj.w"], round_to), n), w, "mtp",
            cfg, False, round_to, control)
        top_is.append(top_i)
        lbs.append(lb)
        out["mtp_logits"] = head(norm(jnp.sum(zs, 2),
                                      w["mtp_final_norm.scale"], eps))
    out.update(top_i=top_is, lb=sum(lbs))
    return out


def _ce(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def loss(w, cfg, feed, round_to=None):
    labels = jnp.asarray(feed["labels"])
    out = forward(w, cfg, feed["input_ids"], labels, round_to)
    total = jnp.mean(_ce(out["logits"], labels)) + ALPHA * out["lb"]
    if "mtp_logits" in out:
        # position i's second target is position i + 1's first; the
        # row's last position has none
        total = total + MTP_LAMBDA * jnp.mean(
            _ce(out["mtp_logits"][:, :-1], labels[:, 1:]))
    return total


# -- the second check --------------------------------------------------------


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
    difference, as the other MoE families': a differing choice at an
    earlier position reaches every later one through the attention."""
    ref = np.asarray(want["logits"], np.float32)
    got = np.asarray(got_logits, np.float32)
    b = got.shape[0]
    (first, count, e), k = held(cfg), cfg["num_experts_per_tok"]
    sets = [(chosen(g, e), chosen(r, e))
            for g, r in zip(got_top_i, want["top_i"])]       # [n, E] each
    diff = np.stack([(g & ~r).sum(1) for g, r in sets])      # [L, n]
    mine = slice(first, first + count)
    held_differ = sum((g[:, mine] != r[:, mine]).sum(1) for g, r in sets)
    same = (held_differ == 0).reshape(b, -1)[:, -ref.shape[1]:]
    sq = ((got - ref) ** 2).mean(-1)                         # [b, last]
    return {
        "flipped_share": float(diff.sum() / (diff.size * k)),
        "logit_err_over_rms": float(
            np.sqrt(sq[same].mean() / np.mean(ref ** 2))
        ) if same.any() else float("nan"),
        "positions_compared": int(same.sum()), "positions": int(same.size)}


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits``, ``top_i``
    and ``expert_rows`` on the sample (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids, lbl: forward(
        w_, cfg, ids, lbl, last=LAST_POSITIONS))(
        w, jnp.asarray(sample["input_ids"]), jnp.asarray(sample["labels"]))
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
