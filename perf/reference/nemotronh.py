"""Plain reference of Nemotron-H language-model training (HF
``modeling_nemotron_h.py``; arXiv:2504.03624; Mamba-2,
arXiv:2405.21060): forward and loss in float32 ``jax.numpy``, no
kernels, nothing chunked, sorted, grouped or skipped. The Mamba-2 scan
is the recurrence position by position (one ``lax.scan`` over t, no
chunks); attention is explicit scores, a query head at a time; every
held expert runs on every token and the router's weights (zero for an
expert a token did not choose) pick what counts. Weights in, numbers
out; gradients are ``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    block i  : x <- x + mixer_i(norm_i(x)), mixer_i by
               hybrid_override_pattern[i]: "M", "E" or "*"
    M        : [z | xBC | dt] = x W_in;  xBC = silu(conv4(xBC) + b)
               [xs | B | C] = xBC;  dt = softplus(dt + dt_bias)
               S_t[h] = exp(-exp(A_log[h]) dt_t[h]) S_{t-1}[h]
                        + dt_t[h] xs_t[h] B_t[g]^T,  g = h // (H / G)
               y_t[h] = S_t[h] C_t[g] + D[h] xs_t[h]
               y = norm over each of G groups of (y * silu(z)) * gain
               out = y W_out
    E        : s = sigmoid(x Wr); chosen = top k of s + bias;
               w = 2.5 s / (sum over the chosen + 1e-20)
               out = sum over the HELD experts among them of
               w_j relu(x Wu_j)^2 Wd_j  +  relu(x Wu_s)^2 Wd_s
    *        : q, k, v = x W; o = causal softmax(q k^T / sqrt(dh)) v, kv
               head = q head // group; out = o Wo; no positions
    LM       : logits = norm(x_L) Wout over the sliced vocabulary; loss =
               mean next-token cross entropy + 1e-4 * the expert layers'
               sequence-wise balance losses

The configuration's cut is the program's: the same blocks under their
published indices (``first_layer`` on), the same held share of the
experts (nothing stands in for the experts other chips hold) and the
same slice of the vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``ablate`` removes ONE new mechanism, to show
that the check sees it: "no_carry" (the state dropped at every chunk
boundary), "gate_after_norm" (norm(y) * gain * silu(z)), "relu" (relu
for relu^2 in every expert) and "one_decay" (every head decays at the
heads' mean rate).

The second check (perf/README.md), as the other MoE families': the loss
is a mean over 4096 positions x 16,384 classes at ln(16384) and does not
resolve a lower precision, so the family also holds the LOGITS of the
sample's last 128 positions (one whole chunk of the scan: the positions
right behind a chunk boundary, where a state that was not carried shows
most, are among them) to the reference's, where program and
reference chose the same of the experts this chip holds in every layer,
and bounds the share of ALL choices that differ by itself."""

import jax
import jax.numpy as jnp
import numpy as np

BALANCE_ALPHA = 1e-4   # assumed, as joyai-llm-flash.json has it
LAST_POSITIONS = 128   # one chunk of the scan (models/nemotron_h.py)
KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}
ABLATIONS = ("no_carry", "gate_after_norm", "relu", "one_decay")

# The second check's limits, set between two readings on the v5e at the
# published widths (my chip run, PR 45,
# perf/tools/nemotronh_logits_control.py; PERF.md sections 4 and 6): the
# program (bf16 AMP) read an rms logit error of 0.0119-0.0192 of the
# logits' rms and 0.735-0.850% of the expert choices flipped over 12
# seeds; the reference with every weight matmul's operands rounded to
# float8_e4m3fn, the nearest precision below bf16, read 0.1079-0.1199
# and 7.55-7.93% over 4 seeds (float8_e5m2: 0.274-0.280 and 17.9-18.0%)
# and comes out as not correct by either limit. Each limit is the
# geometric middle: 2.4 and 3.0 times the program's largest, as far
# under the control's smallest. The ablations (3 seeds; check_loss sees
# none of them: 2e-6 to 7.4e-4 of its 1e-3): the gate behind the norm
# 0.388-0.392 and 20.1-20.6%, relu for relu^2 0.774-0.782 and
# 40.3-41.1%, one decay for all heads 0.105-0.111 and 5.3-6.6%: not
# correct by both limits on every seed. The state DROPPED at every chunk
# boundary 0.0645, 0.0562, 0.0428 and 4.2%, 3.2%, 1.4%: not correct on
# two seeds and INSIDE both limits on the third: at HF's initial decays
# (A = 1 .. 64 over the heads, dt 0.001-0.1) all but a few heads forget
# within ten positions, so a state that is not carried over a boundary
# moves the chunk behind it by 4-6% of the logits' rms, which is where
# the limit between bf16 and float8 lies. tests/test_mamba2_scan.py
# holds the kernels to the recurrence directly.
LOGIT_ERR_LIMIT = 0.045
FLIP_LIMIT = 0.025


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def blocks(cfg):
    """[(published index, kind)] of the configuration's blocks."""
    first = int(cfg.get("first_layer", 0))
    return [(i, KINDS[cfg["hybrid_override_pattern"][i]])
            for i in range(first, first + int(cfg["num_hidden_layers"]))]


# ---------------------------------------------------------------------------
# M: the Mamba-2 mixer
# ---------------------------------------------------------------------------


def causal_conv(x, w, bias):
    """x [b, t, c], w [c, taps], bias [c]: y_t = sum_j w[:, j] x_{t - taps
    + 1 + j} (zeros before the row's start) + bias."""
    taps = w.shape[1]
    t = x.shape[1]
    pad = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return sum(pad[:, j:j + t] * w[:, j] for j in range(taps)) + bias


def scan(xs, dt, a, b, c, chunk=None):
    """The recurrence, one step a position: xs [b, t, H, p], dt and the
    log decay a [b, t, H], B and C [b, t, G, n] -> y [b, t, H, p].
    ``chunk``: the state is dropped in front of every chunk-th position
    (the "no_carry" ablation)."""
    heads, groups = dt.shape[-1], b.shape[-2]
    b, c = (jnp.repeat(v, heads // groups, axis=2) for v in (b, c))

    def step(s, at):
        x_t, dt_t, a_t, b_t, c_t, keep = at
        s = (jnp.exp(a_t)[..., None, None] * s * keep
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    t = xs.shape[1]
    keep = (jnp.ones(t, bool) if chunk is None
            else jnp.arange(t) % chunk != 0).astype(jnp.float32)
    first = lambda v: jnp.moveaxis(v, 1, 0)
    s0 = jnp.zeros(xs.shape[:1] + xs.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, s0, (first(xs), first(dt), first(a), first(b),
                                   first(c), keep))
    return jnp.moveaxis(y, 0, 1)


def mamba2(u, w, p, cfg, round_to=None, ablate=None):
    """The mixer of the normalised input u [b, t, d]."""
    bsz, t, _ = u.shape
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    e, gn = heads * hp, groups * n
    proj = _mm(u, w[f"{p}_mamba_in_colp.w"], round_to)
    z, xbc, dt = jnp.split(proj, [e, 2 * e + 2 * gn], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w[f"{p}_mamba_conv.w"],
                                  w[f"{p}_mamba_conv.b"]))
    xs, b, c = jnp.split(xbc, [e, e + gn], axis=-1)
    dt = jax.nn.softplus(dt + w[f"{p}_mamba_dt.b"])
    rate = jnp.exp(w[f"{p}_mamba_a_log"])
    if ablate == "one_decay":
        rate = jnp.full_like(rate, jnp.mean(rate))
    xs = xs.reshape(bsz, t, heads, hp)
    y = scan(xs, dt, -rate * dt, b.reshape(bsz, t, groups, n),
             c.reshape(bsz, t, groups, n),
             chunk=cfg["chunk_size"] if ablate == "no_carry" else None)
    y = (y + w[f"{p}_mamba_d"][:, None] * xs).reshape(bsz, t, e)
    gate = jax.nn.silu(z)
    if ablate != "gate_after_norm":
        y = y * gate
    y = norm(y.reshape(bsz, t, groups, e // groups), 1.0,
             cfg["layer_norm_epsilon"]).reshape(bsz, t, e)
    y = y * w[f"{p}_mamba_norm.scale"]
    if ablate == "gate_after_norm":
        y = y * gate
    return _mm(y, w[f"{p}_mamba_out_rowp.w"], round_to)


# ---------------------------------------------------------------------------
# *: attention
# ---------------------------------------------------------------------------


def attention(u, w, p, cfg, round_to=None):
    """Attn of the normalised input u [b, t, d]: no positions."""
    b, t, _ = u.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    qkv = _mm(u, w[f"{p}_attn_qkv_colp.w"], round_to)
    q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
    q = q.reshape(b, t, h, dh).transpose(2, 0, 1, 3)
    k = k.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
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
# E: the experts
# ---------------------------------------------------------------------------


def held(cfg):
    """(first, count) of the experts the configuration holds, and the
    number its router scores."""
    count = int(cfg["n_routed_experts"])
    return (int(cfg.get("held_first", 0)), count,
            int(cfg.get("router_experts", count)))


def route(x, wr, bias, cfg, round_to=None):
    """x [b, t, d] -> (top_w [n, k], top_i [n, k], the mean over the
    rows of the balance loss) over all the experts the router scores."""
    b, t, d = x.shape
    k, e = cfg["num_experts_per_tok"], wr.shape[-1]
    s = jax.nn.sigmoid(_mm(x.reshape(b * t, d), wr, round_to))
    _, top_i = jax.lax.top_k(s + bias, k)       # the bias: the choice only
    top_w = jnp.take_along_axis(s, top_i, -1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    count = jnp.sum(jax.nn.one_hot(top_i, e, dtype=x.dtype), axis=1)
    f = e / (k * t) * jnp.sum(count.reshape(b, t, e), 1)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(b, t, e), 1)
    return top_w, top_i, jnp.mean(jnp.sum(f * p, -1))


def expert(x, wu, wd, round_to, ablate=None):
    h = jax.nn.relu(_mm(x, wu, round_to))
    return _mm(h if ablate == "relu" else h * h, wd, round_to)


def moe(x, w, p, cfg, round_to=None, ablate=None):
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
        u, dn, w_e = args
        return acc + w_e[:, None] * expert(xf, u, dn, round_to, ablate), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(xf), (
        w[f"{p}_moe_up.w"], w[f"{p}_moe_down.w"], weight.T))
    out = out + expert(xf, w[f"{p}_moe_shared_up.w"],
                       w[f"{p}_moe_shared_down.w"], round_to, ablate)
    return out.reshape(b, t, d), top_i, lb


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def forward(w, cfg, ids, round_to=None, last=None, ablate=None):
    """{"logits": [b, t or last, V], "top_i": [per expert layer [b*t,
    k]], "lb": the balance losses' sum} of token ids [b, t]."""
    assert ablate is None or ablate in ABLATIONS, ablate
    eps = cfg["layer_norm_epsilon"]
    x = w["nemotronh_tok_emb.w"][jnp.asarray(ids)]
    top_is, lb = [], 0.0
    for i, kind in blocks(cfg):
        p = f"blk{i}"
        u = norm(x, w[f"{p}_norm.scale"], eps)
        if kind == "mamba2":
            out = mamba2(u, w, p, cfg, round_to, ablate)
        elif kind == "attn":
            out = attention(u, w, p, cfg, round_to)
        else:
            out, top_i, lb_i = moe(u, w, p, cfg, round_to, ablate)
            top_is.append(top_i)
            lb = lb + lb_i
        x = x + out
    x = norm(x, w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, w["lm_head_colp.w"], round_to),
            "top_i": top_is, "lb": lb}


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
    through the scans and the attention; the largest is kept in the
    record, unjudged."""
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
