"""Plain reference of Granite-4.0-H language-model training (HF
``modeling_granitemoehybrid.py``; Mamba-2, arXiv:2405.21060): forward
and loss in float32 ``jax.numpy``, no kernels, nothing chunked, nothing
recomputed (the loss is the same function whatever the program keeps for
its backward pass). The Mamba-2 scan is the recurrence position by
position (one ``lax.scan`` over t, no chunks); attention is explicit
scores, one query head and one block of ``QUERY_BLOCK`` queries at a
time, so that a row of 16,384 positions fits beside the program's
state. Weights in, numbers out; gradients are ``jax.grad`` of ``loss``.
Callers run it under ``jax.default_matmul_precision("highest")``.

    norm(x)  = x * rsqrt(mean(x^2) + eps) * w
    h_0      = embedding_multiplier E[id]
    layer i  : h <- h + residual_multiplier mixer_i(norm(h)), mixer_i by
               layer_types[i] ("mamba" | "attention", published index)
               h <- h + residual_multiplier (silu(v Wg) * (v Wu)) Wd,
               v = norm(h), [Wg | Wu] one matrix
    mamba    : [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC) + b)
               [xs | B | C] = xBC;  dt = softplus(dt + dt_bias)
               S_t[h] = exp(-exp(A_log[h]) dt_t[h]) S_{t-1}[h]
                        + dt_t[h] xs_t[h] B_t[g]^T,  g = h // (H / G), G = 1
               y_t[h] = S_t[h] C_t[g] + D[h] xs_t[h]
               y = norm over each of G groups of (y * silu(z)) * gain
               out = y W_out
    attention: q, k, v = u W; o = causal softmax(attention_multiplier
               q k^T) v, kv head = q head // group; out = o Wo; no positions
    LM       : logits = norm(h_L) E^T / logits_scaling over the sliced
               vocabulary (tied); loss = mean next-token cross entropy

The configuration's cut is the program's: the same layers under their
published indices (``first_layer`` on) and the same slice of the
vocabulary.

``round_to`` (a dtype) rounds both operands of every weight matrix
multiplication to that dtype first: the lower-precision control of the
second check (float8 is the nearest precision below the bf16 the
configuration trains in). ``ablate`` replaces ONE of the model's own
mechanisms by its neighbour's, to show that the check sees it:
"scale_sqrt" (the softmax scale 1 / sqrt(64) where the config states
1 / 64), "norm_groups" (the gated norm's statistics over 8 groups of
512, Nemotron-3's, where the model has one of 4096) and "no_carry" (the
state dropped at every boundary of the kernels' chunk of 128).

The second check (perf/README.md): the loss is a mean over 16,384
positions x 12,544 classes near ln(12544) and does not resolve a lower
precision, so the family also holds the LOGITS of the sample's last 128
positions (one whole chunk of the scan) to the reference's."""

import math

import jax
import jax.numpy as jnp
import numpy as np

LAST_POSITIONS = 128   # one chunk of the scan (models/granite_hybrid.py)
KERNEL_CHUNK = 128     # where the kernels save states (the file's `assumed`)
QUERY_BLOCK = 2048
KINDS = {"mamba": "mamba2", "attention": "attn"}
ABLATIONS = ("scale_sqrt", "norm_groups", "no_carry")
TABLE = "granitehybrid_tok_emb.w"

# The second check's limit, set between two groups of readings on the
# v5e at the published widths and the cell's own start state (my chip
# runs, PR 75, perf/tools/granitehybrid_logits_control.py; PERF.md
# sections 4 and 6): the program (bf16 AMP) read an rms logit error of
# 0.0181-0.0185 of the logits' rms over 12 seeds. The controls, each the
# reference changed in ONE way and judged as if it were the program (2
# seeds each; check_loss sees none of them: 0 to 3.2e-5 of its 1e-3):
# every weight matmul's operands rounded to float8_e4m3fn, the nearest
# precision below bf16, 0.128-0.130; the softmax scale 1 / sqrt(64)
# where the config states 1 / 64, 0.227-0.228 (at the start state's
# sharper queries and keys; at the builder's own the scores are flat and
# it reads under the program's rounding); the gated norm's statistics
# over 8 groups of 512 where the model has ONE of 4096, 0.326-0.331 (the
# heads' decays are drawn apart, so a group of 8 heads' mean square is
# not the layer's); the state dropped at every boundary of the kernels'
# chunk of 128, 0.114-0.178. All four come out as not correct. The
# reference with its operands rounded to bfloat16 reads 0.0113-0.0115:
# the program's own reading is its matmuls' rounding and little else.
# The limit is the geometric middle of the program's largest and the
# controls' smallest: 2.4 times the one, 2.5 times under the other.
LOGIT_ERR_LIMIT = 0.045


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def blocks(cfg):
    """[(published index, kind)] of the configuration's layers."""
    first = int(cfg.get("first_layer", 0))
    return [(i, KINDS[cfg["layer_types"][i]])
            for i in range(first, first + int(cfg["num_hidden_layers"]))]


def causal_conv(x, w, bias):
    """x [b, t, c], w [c, taps], bias [c]: y_t = sum_j w[:, j] x_{t - taps
    + 1 + j} (zeros before the row's start) + bias."""
    taps = w.shape[1]
    t = x.shape[1]
    pad = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return sum(pad[:, j:j + t] * w[:, j] for j in range(taps)) + bias


def scan(xs, dt, a, b, c, chunk=None):
    """The recurrence, one step a position: xs [b, t, G, r, p], dt and
    the log decay a [b, t, G, r], B and C [b, t, G, n] -> y like xs.
    ``chunk``: the state is dropped in front of every chunk-th position
    (the "no_carry" ablation)."""

    def step(s, at):
        x_t, dt_t, a_t, b_t, c_t, keep = at
        s = (jnp.exp(a_t)[..., None, None] * s * keep
             + (dt_t[..., None] * x_t)[..., None]
             * b_t[:, :, None, None, :])
        return s, jnp.einsum("bgrpn,bgn->bgrp", s, c_t)

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
    heads, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    e, gn, r = heads * hp, groups * n, heads // groups
    proj = _mm(u, w[f"{p}_mamba_in_colp.w"], round_to)
    z, xbc, dt = jnp.split(proj, [e, 2 * e + 2 * gn], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w[f"{p}_mamba_conv.w"],
                                  w[f"{p}_mamba_conv.b"]))
    xs, b, c = jnp.split(xbc, [e, e + gn], axis=-1)
    dt = jax.nn.softplus(dt + w[f"{p}_mamba_dt.b"])
    a = -jnp.exp(w[f"{p}_mamba_a_log"]) * dt
    xs = xs.reshape(bsz, t, groups, r, hp)
    y = scan(xs, dt.reshape(bsz, t, groups, r), a.reshape(bsz, t, groups, r),
             b.reshape(bsz, t, groups, n), c.reshape(bsz, t, groups, n),
             chunk=KERNEL_CHUNK if ablate == "no_carry" else None)
    y = (y + w[f"{p}_mamba_d"].reshape(groups, r, 1) * xs).reshape(bsz, t, e)
    y = y * jax.nn.silu(z)
    stat = 8 if ablate == "norm_groups" else groups
    y = norm(y.reshape(bsz, t, stat, e // stat), 1.0,
             cfg["rms_norm_eps"]).reshape(bsz, t, e)
    return _mm(y * w[f"{p}_mamba_norm.scale"], w[f"{p}_mamba_out_rowp.w"],
               round_to)


def attention(u, w, p, cfg, round_to=None, ablate=None):
    """Attn of the normalised input u [b, t, d]: no positions, the scale
    as the configuration states it."""
    b, t, _ = u.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // h
    scale = (1.0 / math.sqrt(dh) if ablate == "scale_sqrt"
             else float(cfg["attention_multiplier"]))
    qkv = _mm(u, w[f"{p}_attn_qkv_colp.w"], round_to)
    q, k, v = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)
    q = q.reshape(b, t, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, hk, dh).transpose(0, 2, 1, 3)
    blk = min(QUERY_BLOCK, t)
    assert t % blk == 0, (t, blk)
    nb = t // blk
    s_pos = jnp.arange(t)[None, :]

    def one(args):   # one query head, one block of queries
        q_blk, head, p0 = args          # [b, blk, dh]
        k_h, v_h = k[:, head // (h // hk)], v[:, head // (h // hk)]
        s = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) * scale
        s = jnp.where(s_pos <= (p0 + jnp.arange(blk))[:, None], s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v_h)

    q_blocks = q.reshape(b, h, nb, blk, dh).transpose(1, 2, 0, 3, 4)
    o = jax.lax.map(one, (
        q_blocks.reshape(h * nb, b, blk, dh),
        jnp.repeat(jnp.arange(h), nb), jnp.tile(jnp.arange(nb) * blk, h)))
    o = o.reshape(h, nb, b, blk, dh).transpose(2, 1, 3, 0, 4)  # b nb blk h dh
    return _mm(o.reshape(b, t, h * dh), w[f"{p}_attn_out_rowp.w"], round_to)


def mlp(v, w, p, round_to=None):
    gate, up = jnp.split(_mm(v, w[f"{p}_mlp_in_colp.w"], round_to), 2, -1)
    return _mm(jax.nn.silu(gate) * up, w[f"{p}_mlp_out_rowp.w"], round_to)


def forward(w, cfg, ids, round_to=None, last=None, ablate=None):
    """Logits [b, t or last, V] of token ids [b, t]."""
    assert ablate is None or ablate in ABLATIONS, ablate
    eps, res = cfg["rms_norm_eps"], float(cfg["residual_multiplier"])
    x = float(cfg["embedding_multiplier"]) * w[TABLE][jnp.asarray(ids)]
    for i, kind in blocks(cfg):
        p = f"blk{i}"
        u = norm(x, w[f"{p}_norm.scale"], eps)
        mixer = mamba2 if kind == "mamba2" else attention
        x = x + res * mixer(u, w, p, cfg, round_to, ablate)
        x = x + res * mlp(norm(x, w[f"{p}_mlp_norm.scale"], eps), w, p,
                          round_to)
    x = norm(x, w["final_norm.scale"], eps)
    if last is not None:
        x = x[:, -last:]
    return _mm(x, w[TABLE].T, round_to) / float(cfg["logits_scaling"])


def loss(w, cfg, feed, round_to=None, ablate=None):
    logits = forward(w, cfg, feed["input_ids"], round_to, ablate=ablate)
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(
        logp, jnp.asarray(feed["labels"])[..., None], -1)[..., 0]
    return jnp.mean(ce)


def compare(want_logits, got_logits):
    """The second check's reading of ``got`` against the reference's
    ``want`` (``forward(..., last=LAST_POSITIONS)``): the rms of the
    logit differences over the logits' rms; the largest difference is
    kept in the record, unjudged."""
    want = np.asarray(want_logits, np.float32)
    got = np.asarray(got_logits, np.float32)
    scale = np.sqrt(np.mean(want ** 2))
    return {"logit_err_over_rms": float(
                np.sqrt(np.mean((got - want) ** 2)) / scale),
            "logit_max_err_over_rms": float(np.abs(got - want).max() / scale),
            "logit_rms": float(scale),
            "positions": int(want.shape[0] * want.shape[1])}


def second_check(w, cfg, sample, fetched):
    """(problems, record) of the program's ``last_logits`` on the sample
    (perf/kinds/train.check_second)."""
    want = jax.jit(lambda w_, ids: forward(w_, cfg, ids,
                                           last=LAST_POSITIONS))(
        w, jnp.asarray(sample["input_ids"]))
    record = compare(want, fetched["last_logits"])
    record["limits"] = [LOGIT_ERR_LIMIT]
    problems = []
    if not record["logit_err_over_rms"] <= LOGIT_ERR_LIMIT:
        problems.append(
            f"last-position logits differ from the reference's by "
            f"{record['logit_err_over_rms']:.3g} of their rms > "
            f"{LOGIT_ERR_LIMIT}")
    return problems, record
