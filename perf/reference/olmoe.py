"""Plain reference of OLMoE language-model training (Muennighoff et al.
2024, arXiv:2409.02060; HF ``modeling_olmoe.py``): forward and loss in
float32 ``jax.numpy``, no kernels, nothing sorted or grouped: every
expert runs on every token and the router's weights (zero for an expert
a token did not choose) pick what counts. Weights in, numbers out;
gradients are ``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    h = x + Attn(RMSNorm(x));   y = h + MoE(RMSNorm(h))
    Attn: q, k, v = x Wq, x Wk, x Wv;  q, k = RMSNorm(q), RMSNorm(k) over
          the whole width;  split into heads;  RoPE (rotate-half);
          causal softmax(q k^T / sqrt(dh)) v;  out = o Wo
    MoE:  p = softmax(x Wr);  top k of p, not renormalised unless
          norm_topk_prob;  out = sum_j p_j (silu(x Wg[e_j]) * (x Wu[e_j])) Wd[e_j]
    LM:   logits = RMSNorm(y_L) Wout;  loss = mean next-token cross entropy
          + 0.01 * load-balancing loss + 0.001 * router z-loss

``round_to`` (a dtype) rounds both operands of every matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in).

The second check (perf/README.md): the loss is a mean over 8192
positions x 50304 classes at ln(50304) and does not resolve a lower
precision (a float8-rounded model passes ``check_loss``), so the family
also holds the LOGITS of the sample's last positions to the
reference's. A token whose eighth and ninth expert are a near-tie may
take the other one in bf16: that is a different, equally valid
function, so logits are compared where program and reference chose the
same experts, and the share of choices that differ is bounded by
itself."""

import jax
import jax.numpy as jnp
import numpy as np

AUX_COEF, Z_COEF = 0.01, 0.001   # the paper's weights (assumed: the config has none)
LAST_POSITIONS = 8

# The second check's limits, set between two readings on the v5e at the
# published widths (my chip runs, PR 28, PERF.md sections 4 and 6):
# over 21 seeds the program (bf16 AMP) read a largest logit error of
# 0.0414-0.0512 of the logits' rms and 0.47-0.57% of the expert choices
# flipped; over 14 seeds (perf/tools/olmoe_logits_control.py) the
# reference with every matmul operand rounded to float8_e4m3fn read
# 0.4255-0.5356 and 4.13-4.44% (float8_e5m2: 0.929-1.237 and
# 10.5-11.2%). Each limit is 2 to 2.6 times the program's largest and
# 2.7 to 4.3 times under the control's smallest.
LOGIT_ERR_LIMIT = 0.1
FLIP_LIMIT = 0.015


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [b, h, t, dh], rotate-half: feature i pairs with i + dh/2."""
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


def attention(x, wqkv, wo, q_gain, k_gain, n_head, theta, eps, round_to=None):
    b, t, d = x.shape
    q, k, v = jnp.split(_mm(x, wqkv, round_to), 3, axis=-1)
    q, k = rms_norm(q, q_gain, eps), rms_norm(k, k_gain, eps)

    def heads(z):
        return z.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)

    def one_head(qkv):   # [b, t, dh] each: a head at a time, so that
        q, k, v = qkv    # the [t, t] scores of 16 heads never coexist
        s = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(
            jnp.float32(d // n_head))
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)

    q, k, v = rope(heads(q), theta), rope(heads(k), theta), heads(v)
    o = jax.lax.map(one_head, tuple(z.transpose(1, 0, 2, 3)
                                    for z in (q, k, v)))   # [h, b, t, dh]
    return _mm(o.transpose(1, 2, 0, 3).reshape(b, t, d), wo, round_to)


def route(x, wr, k, norm_topk=False, round_to=None):
    """x [n, d] -> (top_w [n, k], top_i [n, k], load-balancing loss,
    z-loss)."""
    logits = _mm(x, wr, round_to)
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if norm_topk:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    e = wr.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    lb = e * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(probs, 0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return top_w, top_i, lb, z


def moe(x, wr, wg, wu, wd, k, norm_topk=False, round_to=None):
    """x [n, d]; wg, wu [E, d, f]; wd [E, f, d] -> (out [n, d], top_i,
    lb, z). Every expert on every token, weighted by the router."""
    top_w, top_i, lb, z = route(x, wr, k, norm_topk, round_to)
    e = wr.shape[-1]
    weight = jnp.einsum("nk,nke->ne", top_w,
                        jax.nn.one_hot(top_i, e, dtype=x.dtype))

    def one(acc, args):
        g, u, dn, w_e = args
        h = jax.nn.silu(_mm(x, g, round_to)) * _mm(x, u, round_to)
        return acc + w_e[:, None] * _mm(h, dn, round_to), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (wg, wu, wd, weight.T))
    return out, top_i, lb, z


def forward(w, cfg, ids, round_to=None, last=None):
    """{"logits": [b, t or last, V], "top_i": [per layer [b*t, k]],
    "lb", "z"} of token ids [b, t]."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    x = w["olmoe_tok_emb.w"][jnp.asarray(ids)]
    b, t, d = x.shape
    top_is, lbs, zs = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        x = x + attention(
            rms_norm(x, w[f"{p}_attn_norm.scale"], eps),
            w[f"{p}_attn_qkv_colp.w"], w[f"{p}_attn_out_rowp.w"],
            w[f"{p}_attn_qnorm.scale"], w[f"{p}_attn_knorm.scale"],
            cfg["num_attention_heads"], theta, eps, round_to)
        out, top_i, lb, z = moe(
            rms_norm(x, w[f"{p}_moe_norm.scale"], eps).reshape(b * t, d),
            w[f"{p}_moe_router.w"], w[f"{p}_moe_gate.w"],
            w[f"{p}_moe_up.w"], w[f"{p}_moe_down.w"],
            cfg["num_experts_per_tok"], cfg["norm_topk_prob"], round_to)
        x = x + out.reshape(b, t, d)
        top_is.append(top_i)
        lbs.append(lb)
        zs.append(z)
    x = rms_norm(x, w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, w["lm_head_colp.w"], round_to),
            "top_i": top_is, "lb": sum(lbs) / len(lbs),
            "z": sum(zs) / len(zs)}


def loss(w, cfg, feed, round_to=None):
    out = forward(w, cfg, feed["input_ids"], round_to)
    logp = jax.nn.log_softmax(out["logits"], -1)
    ce = -jnp.take_along_axis(
        logp, jnp.asarray(feed["labels"])[..., None], -1)[..., 0]
    return jnp.mean(ce) + AUX_COEF * out["lb"] + Z_COEF * out["z"]


def choices_differ(a, b, n_experts):
    """Per token, how many of the experts ``a`` [n, k] chose ``b`` [n, k]
    did not (sets: the order of the k does not matter)."""
    a, b = np.asarray(a), np.asarray(b)
    rows = np.arange(a.shape[0])[:, None]
    in_a = np.zeros((a.shape[0], n_experts), bool)
    in_b = np.zeros_like(in_a)
    in_a[rows, a] = True
    in_b[rows, b] = True
    return (in_a & ~in_b).sum(1)


def compare(cfg, want, got_logits, got_top_i):
    """The second check's two readings of ``got`` against the
    reference's ``want`` (``forward(..., last=LAST_POSITIONS)``):
    the largest |logit difference| over the logits' rms among the last
    positions where every layer chose the same experts, and the share
    of all (token, slot) choices that differ."""
    want_logits = np.asarray(want["logits"], np.float32)
    got_logits = np.asarray(got_logits, np.float32)
    b, last = want_logits.shape[:2]
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    diff = np.stack([choices_differ(g, r, e)
                     for g, r in zip(got_top_i, want["top_i"])])  # [L, n]
    same = (diff.sum(0) == 0).reshape(b, -1)[:, -last:]
    err = np.abs(got_logits - want_logits).max(-1) / np.sqrt(
        np.mean(want_logits ** 2))
    return {"logit_err_over_rms": float(err[same].max()) if same.any()
            else float("nan"),
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
    rows = np.asarray(fetched["expert_rows"], np.float64)       # [L, E]
    record["max_expert_load"] = float((rows.max(1) / rows.mean(1)).max())
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
