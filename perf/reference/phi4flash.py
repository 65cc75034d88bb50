"""Plain reference of Phi-4-mini-flash-reasoning language-model
training ("SambaY", arXiv:2507.06607; HF ``modeling_phi4flash.py``):
forward and loss in float32 ``jax.numpy``, no kernels, nothing chunked
or saved. The selective scan is the recurrence, one position after
another; attention is explicit scores with the visibility rule written
out, a pair-head and a block of queries at a time. Weights in, numbers
out; gradients are ``jax.grad`` of ``loss``. Callers run it under
``jax.default_matmul_precision("highest")``.

    LN(x)   = (x - mean) * rsqrt(var + eps) * w + b
    layer i : h = x + Mixer_i(LN1(x));  y = h + MLP(LN2(h))
    MLP(u)  = (silu(g) * v) W2,  [g | v] = u W1
    Mixer_i (published index i, half = model_layers / 2):
      i < half: Mamba (i % mb_per_layer == 0) or window attention
      i == half: Mamba, and its scan output M is kept
      i == half + 1: full attention, and its keys and values are kept
      i > half + 1: GMU (i % mb_per_layer == 0) or cross-attention
    Mamba   : [a | z] = u W_in;  c = silu(conv4(a) + b_conv)
              [dt_r | B | C] = c W_x;  Delta = softplus(dt_r W_dt + b_dt)
              s_t = exp(Delta_t A) s_{t-1} + Delta_t B_t c_t,  A = -exp(A_log)
              y_t = C_t . s_t + D c_t;  out = (y * silu(z)) W_out
    GMU     : out = (silu(u W1g) * M) W2g
    Attention: q1, q2, k1, k2, v = u W_qkv + b;  V = v in heads of 2 dh
              A_j = softmax(q_j k_j^T / sqrt(dh) over visible)
              o = rms_norm(A_1 V - lambda A_2 V) * gain * (1 - lambda_init)
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
              lambda_init = 0.8 - 0.6 exp(-0.3 i);  out = o W_o + b_o
              visible(p, s) = s <= p, and p - s < sliding_window in a
              window layer
    Cross   : q1, q2 = u W_q + b; k1, k2, V the full layer's
    LM      : logits = LN(y_L) E^T over the sliced table;  loss = mean
              next-token cross entropy

Departures from the published description, each the program's too:
W_qkv's columns are STORED [q1 | q2 | k1 | k2 | v] (q1, k1 the first
heads of the published even/odd pairs, q2, k2 the second: the published
matrix with its columns permuted, so the even/odd split is a slice);
packed rows are attended and scanned across document boundaries (no
mask, no state reset); the configuration's cut (layers 14-19, an eighth
of the table) is the program's.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication (the tied head among them) to that dtype first: the
lower-precision control of the second check (float8 is the nearest
precision below the bf16 the configuration trains in). ``no_window``
drops the window, ``no_scan`` the recurrence's memory (the state is
forgotten at every position: y_t = (C_t . B_t Delta_t + D) c_t): the
controls that the window and the scan are computed at all.

The second check (perf/README.md): the loss is a mean over 4096
positions x 25,008 classes near ln(25008) and does not resolve a lower
precision, so the family also holds the LOGITS of the sample's last 64
positions (each behind 4k positions of scan state and full attention)
to the reference's, by the rms of the differences over the logits'
rms."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from perf import flops_phi4flash

LAST_POSITIONS = 64
# queries a block of the explicit scores
QUERY_BLOCK = 1024
TABLE = "phi4flash_tok_emb.w"

# The second check's limit, set between two readings on the v5e at the
# published widths (my chip runs, PR 40,
# perf/tools/phi4flash_logits_control.py; PERF.md sections 4 and 6): the
# program (bf16 AMP) read an rms logit error of 0.02097-0.02153 of the
# logits' rms over 12 seeds (a dense model: no routing to flip, so the
# readings lie within 3% of each other); the reference with every weight
# matmul's operands rounded to float8_e4m3fn, the nearest precision
# below bf16, read 0.1773-0.1780 over 3 seeds (float8_e5m2: 0.475-0.478)
# and comes out as not correct. The limit is the geometric middle: 2.8
# times the program's largest, as far under the control's smallest. The
# reference with the window DROPPED reads 0.293-0.307 and with the
# scan's memory REMOVED 0.158-0.256 (two seeds each): not correct
# either; check_loss (2.9e-4 and 3e-7 of its 1e-3 before the
# convolution's initialisation was Mamba's) does not see them.
LOGIT_ERR_LIMIT = 0.06


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def layer_kinds(cfg):
    """[(published index, kind)] of the layers the configuration holds
    (the kinds by perf/flops_phi4flash.py, the benchmark's own count)."""
    first = int(cfg.get("first_layer", 0))
    return list(enumerate(flops_phi4flash.layer_kinds(cfg), first))


sizes = flops_phi4flash.sizes


def conv(a, w, b):
    """a [b, t, e], w [e, taps], b [e]: causal, depthwise."""
    taps, t = w.shape[1], a.shape[1]
    ap = jnp.pad(a, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(ap[:, j:j + t] * w[:, j] for j in range(taps)) + b


def scan(c, delta, a, bm, cm, d, no_scan=False):
    """The recurrence: c, delta [b, t, e], a [e, n], bm, cm [b, t, n]."""
    def step(s, at):
        c_t, d_t, b_t, c_out = at
        keep = 0.0 if no_scan else jnp.exp(d_t[..., None] * a)
        s = keep * s + (d_t * c_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_out[:, None, :], -1)

    first = lambda v: jnp.moveaxis(v, 1, 0)
    s0 = jnp.zeros((c.shape[0],) + a.shape, jnp.float32)
    _, y = jax.lax.scan(step, s0, (first(c), first(delta), first(bm),
                                   first(cm)))
    return jnp.moveaxis(y, 0, 1) + d * c


def mamba(u, w, p, cfg, round_to=None, no_scan=False):
    """-> (out, the scan's output y before the gate)."""
    z = sizes(cfg)
    az = _mm(u, w[f"{p}_ssm_in_colp.w"], round_to)
    a, gate = az[..., :z["e"]], az[..., z["e"]:]
    c = jax.nn.silu(conv(a, w[f"{p}_ssm_conv.w"], w[f"{p}_ssm_conv.b"]))
    x = _mm(c, w[f"{p}_ssm_x_rowp.w"], round_to)
    dt_r, bm, cm = (x[..., :z["r"]], x[..., z["r"]:z["r"] + z["n"]],
                    x[..., z["r"] + z["n"]:])
    delta = jax.nn.softplus(_mm(dt_r, w[f"{p}_ssm_dt.w"], round_to)
                            + w[f"{p}_ssm_dt.b"])
    y = scan(c, delta, -jnp.exp(w[f"{p}_ssm_a_log"]), bm, cm,
             w[f"{p}_ssm_d"], no_scan)
    return _mm(y * jax.nn.silu(gate), w[f"{p}_ssm_out_rowp.w"], round_to), y


def gmu(u, memory, w, p, round_to=None):
    return _mm(jax.nn.silu(_mm(u, w[f"{p}_gmu_in_colp.w"], round_to))
               * memory, w[f"{p}_gmu_out_rowp.w"], round_to)


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def heads(x, n, width):
    b, t, _ = x.shape
    return x.reshape(b, t, n, width).transpose(0, 2, 1, 3)


def attention(u, w, p, i, cfg, kind, kv, round_to=None, no_window=False):
    """-> (out, (k1, k2, V) heads first)."""
    z = sizes(cfg)
    h, hk, dh = z["h"] // 2, z["hk"] // 2, z["dh"]
    b, t, _ = u.shape
    if kind == "cross":
        q = _mm(u, w[f"{p}_attn_q_colp.w"], round_to) + w[f"{p}_attn_q_colp.b"]
        q1, q2 = q[..., :h * dh], q[..., h * dh:]
        k1, k2, v = kv
    else:
        x = (_mm(u, w[f"{p}_attn_qkv_colp.w"], round_to)
             + w[f"{p}_attn_qkv_colp.b"])
        q1, q2, k1, k2, v = jnp.split(
            x, np.cumsum([h * dh, h * dh, hk * dh, hk * dh]).tolist(), -1)
        k1, k2, v = heads(k1, hk, dh), heads(k2, hk, dh), heads(v, hk, 2 * dh)
    q1, q2 = heads(q1, h, dh), heads(q2, h, dh)
    lam = (jnp.exp(jnp.sum(w[f"{p}_attn_lambda_lq1"] * w[f"{p}_attn_lambda_lk1"]))
           - jnp.exp(jnp.sum(w[f"{p}_attn_lambda_lq2"]
                             * w[f"{p}_attn_lambda_lk2"])) + lambda_init(i))
    window = (int(cfg["sliding_window"])
              if kind == "swa" and not no_window else None)
    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0, (t, blk)
    nb = t // blk
    s_pos = jnp.arange(t)[None, :]

    def one(args):   # one pair-head, one block of queries
        q1_b, q2_b, head, p0 = args            # [b, blk, dh]
        g = head // (h // hk)
        p_pos = (p0 + jnp.arange(blk))[:, None]
        visible = s_pos <= p_pos
        if window is not None:
            visible = visible & (p_pos - s_pos < window)

        def softmax_map(q_b, k_h):
            s = jnp.einsum("bqd,bkd->bqk", q_b, k_h) / jnp.sqrt(
                jnp.float32(dh))
            return jax.nn.softmax(jnp.where(visible, s, -1e30), -1)

        a = softmax_map(q1_b, k1[:, g]) - lam * softmax_map(q2_b, k2[:, g])
        return jnp.einsum("bqk,bkd->bqd", a, v[:, g])

    def blocks(q):
        return q.reshape(b, h, nb, blk, dh).transpose(1, 2, 0, 3, 4).reshape(
            h * nb, b, blk, dh)

    o = jax.lax.map(one, (blocks(q1), blocks(q2),
                          jnp.repeat(jnp.arange(h), nb),
                          jnp.tile(jnp.arange(nb) * blk, h)))
    o = o.reshape(h, nb, b, blk, 2 * dh).transpose(2, 1, 3, 0, 4)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["layer_norm_eps"])
    o = o * w[f"{p}_attn_subln.scale"] * (1.0 - lambda_init(i))
    out = (_mm(o.reshape(b, t, 2 * h * dh), w[f"{p}_attn_out_rowp.w"],
               round_to) + w[f"{p}_attn_out_rowp.b"])
    return out, (k1, k2, v)


def mlp(u, w, p, cfg, round_to=None):
    gv = _mm(u, w[f"{p}_mlp_up_colp.w"], round_to)
    f = int(cfg["intermediate_size"])
    return _mm(jax.nn.silu(gv[..., :f]) * gv[..., f:],
               w[f"{p}_mlp_down_rowp.w"], round_to)


def forward(w, cfg, ids, round_to=None, last=None, no_window=False,
            no_scan=False):
    """{"logits": [b, t or last, V]} of token ids [b, t]."""
    eps = cfg["layer_norm_eps"]
    x = w[TABLE][jnp.asarray(ids)]
    memory = kv = None
    for i, kind in layer_kinds(cfg):
        p = f"blk{i}"
        u = layer_norm(x, w[f"{p}_mixer_norm.scale"],
                       w[f"{p}_mixer_norm.bias"], eps)
        if kind in ("mamba", "mamba_mem"):
            out, y = mamba(u, w, p, cfg, round_to, no_scan)
            if kind == "mamba_mem":
                memory = y
        elif kind == "gmu":
            out = gmu(u, memory, w, p, round_to)
        else:
            out, used = attention(u, w, p, i, cfg, kind, kv, round_to,
                                  no_window)
            if kind == "full":
                kv = used
        x = x + out
        x = x + mlp(layer_norm(x, w[f"{p}_mlp_norm.scale"],
                               w[f"{p}_mlp_norm.bias"], eps), w, p, cfg,
                    round_to)
    x = layer_norm(x, w["final_norm.scale"], w["final_norm.bias"], eps)
    if last is not None:
        x = x[:, -last:]
    return {"logits": _mm(x, w[TABLE].T, round_to)}


def loss(w, cfg, feed, round_to=None, no_window=False, no_scan=False):
    out = forward(w, cfg, feed["input_ids"], round_to, no_window=no_window,
                  no_scan=no_scan)
    logp = jax.nn.log_softmax(out["logits"], -1)
    ce = -jnp.take_along_axis(
        logp, jnp.asarray(feed["labels"])[..., None], -1)[..., 0]
    return jnp.mean(ce)


def compare(want_logits, got_logits):
    """The second check's reading of ``got`` against the reference's
    ``want``: the rms of the logit differences over the logits' rms (the
    largest difference is kept in the record, unjudged)."""
    want = np.asarray(want_logits, np.float32)
    got = np.asarray(got_logits, np.float32)
    scale = np.sqrt(np.mean(want ** 2))
    return {"logit_err_over_rms":
            float(np.sqrt(np.mean((got - want) ** 2)) / scale),
            "logit_max_err_over_rms": float(np.abs(got - want).max() / scale),
            "positions": int(want.shape[0] * want.shape[1])}


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits`` on the
    sample (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids: forward(w_, cfg, ids,
                                           last=LAST_POSITIONS))(
        w, jnp.asarray(sample["input_ids"]))
    record = compare(want["logits"], fetched["last_logits"])
    record["limits"] = [LOGIT_ERR_LIMIT]
    problems = []
    if not record["logit_err_over_rms"] <= LOGIT_ERR_LIMIT:
        problems.append(
            f"last-position logits differ from the reference's by "
            f"{record['logit_err_over_rms']:.3g} of their rms > "
            f"{LOGIT_ERR_LIMIT}")
    return problems, record
