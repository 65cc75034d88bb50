"""Plain reference of JoyAI-LLM-Flash language-model training (a model
of DeepSeek-V3's shape: arXiv:2412.19437 sections 2.1-2.2, HF
``modeling_deepseek_v3.py``): forward and loss in float32 ``jax.numpy``,
no kernels, nothing sorted or grouped. Latent attention is explicit
scores, a head at a time, its rotary pairs turned by an explicit 2 x 2
rotation each (not HF's de-interleave before a rotate-half); every held
expert runs on every token and the router's weights (zero for an expert
a token did not choose) pick what counts; the selection bias enters the
choice only. Weights in, numbers out; gradients are ``jax.grad`` of
``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    layer i  : h = x + MLA(norm(x));  y = h + FFN_i(norm(h))
               FFN_i = SwiGLU(intermediate_size) for i < first_k_dense_replace,
               else the MoE
    MLA      : c_q = norm(x Wqa);  q = c_q Wqb -> per head [q_nope | q_rope]
               [c_kv | k_rope] = x Wkva;  [k_nope | v] = norm(c_kv) Wkvb per head
               q_rope, k_rope <- pairs (2i, 2i+1) turned by p * theta^(-2i/rope);
               k_rope is ONE head shared by all query heads
               o = causal softmax([q_nope|q_rope] [k_nope|k_rope]^T
                   / sqrt(nope + rope)) v;  out = o Wo
    MoE      : s = sigmoid(x Wr) over all ``router_experts``;  chosen = top k of
               s + b;  w_j = scale * s_j / sum_chosen s;  out = sum over the HELD
               experts among them (``held_first`` .. + ``n_routed_experts``) of
               w_j SwiGLU_j(x) + SwiGLU_shared(x)
               balance loss of a row: sum_e f_e P_e, f_e = E/(k T) count_e,
               P_e = mean_t s_e / sum_e' s_e'
    LM       : logits = norm(y_L) Wout over the sliced vocabulary
    MTP      : h' = [norm_h(y_L) | norm_e(Emb(t_{i+1}))] Weh;  z = Layer_mtp(h');
               logits' = norm_mtp(z) Wout
    loss     = mean CE(logits_i, t_{i+1}) + MTP_LAMBDA * mean_{i<T-1}
               CE(logits'_i, t_{i+2}) + ALPHA * sum over the expert layers
               (the MTP module's too) of the mean over the rows of the balance loss

The configuration's cut is the program's: the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in).

The second check (perf/README.md), as the two other MoE families': the
loss is a mean over 4096 positions x 16,160 classes at ln(16160), and
the MTP term weighs a tenth in it: it does not resolve a lower
precision, least of all in the MTP path. So the family also holds the
main AND the MTP logits of the sample's last positions to the
reference's, where program and reference chose the same of the experts
this chip holds in every layer, and bounds the share of ALL choices
that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 1e-4       # the balance loss's weight (arXiv:2412.19437 4.2; assumed)
MTP_LAMBDA = 0.1   # the MTP loss's weight (the paper: 0.3, then 0.1; assumed)
LAST_POSITIONS = 8

# The second check's limits, one for both sets of logits and one for the
# choices, set between two readings on the v5e at the published widths
# (my chip runs, PR 34; PERF.md sections 4 and 6): the program (bf16
# AMP) over 21 runs on 14 seeds read an rms logit error of 0.0103-0.0109
# (main) and 0.0086-0.0092 (MTP) of the logits' rms and 1.18-1.26% of
# the expert choices flipped, 7 or 8 of the 8 positions compared; over
# 6 seeds (perf/tools/joyai_logits_control.py) this reference with
# every weight matmul's operands rounded to float8_e4m3fn, the nearest
# precision below bf16, read 0.104-0.112, 0.0855-0.0913 and 10.1-10.3%
# (float8_e5m2: 0.229-0.275, 0.190-0.226 and 21.6-22.2%), and comes out
# as not correct by every limit. Each limit is the geometric middle:
# 2.75 times the program's largest, 2.85 times under the control's
# smallest. Lower on both sides than the two other MoE families': one
# row of 4096, eight of 256 experts, weights scaled by 2.5.
LOGIT_ERR_LIMIT = 0.03
FLIP_LIMIT = 0.035


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_pairs(x, theta):
    """x [.., t, d]: features (2i, 2i + 1) of position p turned by the
    angle p * theta^(-2i/d), each pair by its own 2 x 2 rotation."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    rot = jnp.stack([jnp.stack([jnp.cos(ang), -jnp.sin(ang)], -1),
                     jnp.stack([jnp.sin(ang), jnp.cos(ang)], -1)], -2)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))          # [.., t, d/2, 2]
    return jnp.einsum("tiab,...tib->...tia", rot, pairs).reshape(x.shape)


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def latent_attention(x, w, p, cfg, round_to=None):
    b, t, _ = x.shape
    h, nope, rope, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, theta, r = (cfg["rms_norm_eps"], float(cfg["rope_theta"]),
                     cfg["kv_lora_rank"])
    c_q = norm(_mm(x, w[f"{p}_attn_q_a.w"], round_to),
               w[f"{p}_attn_q_a_norm.scale"], eps)
    q = _mm(c_q, w[f"{p}_attn_q_b_colp.w"], round_to).reshape(
        b, t, h, nope + rope).transpose(0, 2, 1, 3)
    kva = _mm(x, w[f"{p}_attn_kv_a.w"], round_to)
    c_kv, k_rope = kva[..., :r], kva[..., r:]
    kv = _mm(norm(c_kv, w[f"{p}_attn_kv_a_norm.scale"], eps),
             w[f"{p}_attn_kv_b_colp.w"], round_to).reshape(
        b, t, h, nope + dv).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], theta)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = rope_pairs(k_rope, theta)        # [b, t, rope]: one head
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(args):   # a head at a time, so that the [t, t] scores
        qn, qr, kn, v_h = args   # of all heads never coexist
        s = (jnp.einsum("bqd,bkd->bqk", qn, kn)
             + jnp.einsum("bqd,bkd->bqk", qr, k_rope)
             ) / jnp.sqrt(jnp.float32(nope + rope))
        s = jnp.where(causal, s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    o = jax.lax.map(one_head, tuple(
        z.transpose(1, 0, 2, 3) for z in (q_nope, q_rope, k_nope, v)))
    o = o.transpose(1, 2, 0, 3).reshape(b, t, h * dv)
    return _mm(o, w[f"{p}_attn_out_rowp.w"], round_to)


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


def layer(x, w, p, cfg, dense, round_to):
    """(y, top_i or None, balance loss or None) of one decoder layer."""
    eps = cfg["rms_norm_eps"]
    x = x + latent_attention(norm(x, w[f"{p}_attn_norm.scale"], eps),
                             w, p, cfg, round_to)
    if dense:
        return x + swiglu(norm(x, w[f"{p}_ffn_norm.scale"], eps),
                          w[f"{p}_ffn_gate_colp.w"], w[f"{p}_ffn_up_colp.w"],
                          w[f"{p}_ffn_down_rowp.w"], round_to), None, None
    out, top_i, lb = moe(norm(x, w[f"{p}_moe_norm.scale"], eps), w, p, cfg,
                         round_to)
    return x + out, top_i, lb


def forward(w, cfg, ids, labels, round_to=None, last=None):
    """{"logits", "mtp_logits": [b, t or last, V], "top_i": [per expert
    layer, the MTP module's behind the stack's, [b*t, k]], "lb": the sum
    of the layers' balance losses} of token ids [b, t] and their next
    tokens ``labels`` [b, t] (the MTP module's second input)."""
    eps = cfg["rms_norm_eps"]
    emb = w["joyai_tok_emb.w"]
    x = emb[jnp.asarray(ids)]
    top_is, lbs = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, top_i, lb = layer(x, w, f"blk{i}", cfg,
                             i < cfg["first_k_dense_replace"], round_to)
        if top_i is not None:
            top_is.append(top_i)
            lbs.append(lb)

    def head(z):
        return _mm(z if last is None else z[:, -last:],
                   w["lm_head_colp.w"], round_to)

    out = {"logits": head(norm(x, w["final_norm.scale"], eps))}
    if cfg["num_nextn_predict_layers"]:
        merged = jnp.concatenate(
            [norm(x, w["mtp_hnorm.scale"], eps),
             norm(emb[jnp.asarray(labels)], w["mtp_enorm.scale"], eps)], -1)
        z, top_i, lb = layer(_mm(merged, w["mtp_eh_proj.w"], round_to), w,
                             "mtp", cfg, False, round_to)
        top_is.append(top_i)
        lbs.append(lb)
        out["mtp_logits"] = head(norm(z, w["mtp_final_norm.scale"], eps))
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
    where every layer chose the same HELD experts, main and MTP logits
    each (``got_logits``: the pair), and the share of all (token, slot)
    choices that differ. The rms and not the largest difference, as
    Qwen3-Next's: a differing choice at an earlier position reaches
    every later one through the attention, so a few logits move by a
    discrete step that no precision bounds."""
    b = np.asarray(got_logits[0]).shape[0]
    (first, count, e), k = held(cfg), cfg["num_experts_per_tok"]
    sets = [(chosen(g, e), chosen(r, e))
            for g, r in zip(got_top_i, want["top_i"])]       # [n, E] each
    diff = np.stack([(g & ~r).sum(1) for g, r in sets])      # [L, n]
    mine = slice(first, first + count)
    held_differ = sum((g[:, mine] != r[:, mine]).sum(1) for g, r in sets)
    record = {"flipped_share": float(diff.sum() / (diff.size * k))}
    for name, got, ref in (("logit", got_logits[0], want["logits"]),
                           ("mtp_logit", got_logits[1],
                            want["mtp_logits"])):
        ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
        same = (held_differ == 0).reshape(b, -1)[:, -ref.shape[1]:]
        sq = ((got - ref) ** 2).mean(-1)                    # [b, last]
        record[f"{name}_err_over_rms"] = float(
            np.sqrt(sq[same].mean() / np.mean(ref ** 2))
        ) if same.any() else float("nan")
    record.update(positions_compared=int(same.sum()),
                  positions=int(same.size))
    return record


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits``,
    ``mtp_last_logits``, ``top_i`` and ``expert_rows`` on the sample
    (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids, lbl: forward(
        w_, cfg, ids, lbl, last=LAST_POSITIONS))(
        w, jnp.asarray(sample["input_ids"]), jnp.asarray(sample["labels"]))
    record = compare(cfg, want, (fetched["last_logits"],
                                 fetched["mtp_last_logits"]),
                     fetched["top_i"])
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
    for name in ("logit", "mtp_logit"):
        err = record[f"{name}_err_over_rms"]
        if record["positions_compared"] and not err <= LOGIT_ERR_LIMIT:
            problems.append(
                f"last-position {name}s differ from the reference's by "
                f"{err:.3g} of their rms > {LOGIT_ERR_LIMIT}")
    if not record["flipped_share"] <= FLIP_LIMIT:
        problems.append(
            f"{100 * record['flipped_share']:.2f}% of the expert choices "
            f"differ from the reference's > {100 * FLIP_LIMIT}%")
    return problems, record
