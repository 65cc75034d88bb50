"""Gated delta-rule linear attention (Gated DeltaNet: Yang, Kautz &
Hatamizadeh 2024, arXiv:2412.06464, as Qwen3-Next lays it out in HF
``modeling_qwen3_next.py``) and the small ops around it: the causal
depthwise convolution in front, the gates, the gated RMSNorm behind.

Per value head, with a state S in R^{dk x dv} that starts at zero:

    S_t = exp(g_t) S_{t-1}
    S_t += k_t (beta_t (v_t - S_t^T k_t))^T
    o_t  = S_t^T q_t

``gated_delta_rule`` runs that recurrence in its CHUNKWISE form (chunk
C = 64 by default). Inside a chunk, with G the running sum of g from the
chunk's first position and D_ij = exp(G_i - G_j) for i >= j:

    A   = strictly_lower((beta K) K^T . D)             # [C, C]
    U   = (I + A)^{-1} (beta V),   W = (I + A)^{-1} (beta K exp(G))
    per chunk, S the state it starts from:
        V' = U - W S
        O  = (Q exp(G)) S + lower(Q K^T . D) V'
        S <- exp(G_C) S + (K exp(G_C - G))^T V'

so the sequence costs t / C scan steps of matmuls, not t steps of rank-1
updates. What is float32 whatever the activation stream: g, its running
sums and every exp of them, beta, the normalisation of q and k, the
triangular solve, and the state the scan carries. Matmul OPERANDS are
the dtype Q arrives in (bf16 under AMP: core/interp.AMP_OP_TYPES casts
Q, K, V and keeps G and Beta), accumulated in float32.

The backward pass is the op's own (``gated_delta_rule_grad``): the
forward saves the state each chunk starts from (``States``, in the
operands' dtype: every use of it is a matmul operand); the grad op
recomputes the per-chunk quantities above ONCE (parallel over chunks:
no second run of the scan), walks the chunks backwards with the
hand-derived transposes of the four products of a step, and sends the
per-chunk cotangents through the transpose of the parallel part.

Two writings of that chunkwise form, chosen per call by
``parallel/gated_delta_rule.gdn_tile`` from the call's own shapes
(never by a flag): the ``gdn.rule.fwd`` / ``gdn.rule.bwd`` Pallas
kernels where it gives a tile (bf16 operands, dk = dv = 128, chunk 64,
a TPU backend, no mesh: the state stays in VMEM across the chunks, each
chunk's triangle is inverted once a pass, its 16 x 16 diagonal blocks by
substitution and the rest by two exact block merges on the MXU, nothing
is staged through HBM), and
XLA ops everywhere else (``_chunk_parts`` / ``_chunk_scan`` below, with
``jax.vjp`` of the parallel part behind a barrier): every CPU run,
float32 operands, other widths or chunks, a program under a mesh. The
kernels are custom calls, which XLA cannot CSE: the grad op recomputes
inside its one kernel and runs no kernel of the forward again.

The causal convolution in front is written twice as well, chosen per
call by ``parallel/causal_conv.conv_tile``: the ``gdn.conv.fwd`` /
``gdn.conv.bwd`` Pallas kernels (bf16 on a TPU, channels a multiple of
128, no mesh: X, Y, dY and dX cross HBM once each as bf16, float32 only
in VMEM, the earlier rows a halo; an optional bias, Mamba's, rides as a
row behind the taps) and float32 XLA ops over a padded
copy of X everywhere else (``_conv_xla``); its backward pass is its own
grad op (``causal_conv1d_grad``), which saves nothing but X;
``pt_causal_conv_dispatch_total`` records which (``kernel`` or ``xla``).

``gated_short_conv`` is the convolution as a sequence mixer of its own
(LFM2): the fused projection [b, t, 3c] = [B | C | u] in, y = C *
taps(B * u) out, no activation. A sibling op and not an attribute of
``causal_conv1d``: its output is a third as wide as its input and its
grad op returns d[B | C | u] as ONE tensor, so neither op's rule would
share more than ``_conv_xla``, which they do share, and the plain op's
lowering stays what it was byte for byte. ``sconv.gated.fwd`` /
``sconv.gated.bwd`` where ``conv_tile(..., gated=True)`` gives a tile
(the three ranges read in place through lane-offset index maps), the
composition as float32 XLA ops elsewhere; its rows in
``pt_causal_conv_dispatch_total`` carry a further label ``gated``.

``impl="recurrent"`` is the recurrence step by step (``lax.scan`` over
positions, differentiated by jax): the fallback a caller asks for, never
taken silently: ``pt_linear_attention_dispatch_total`` records the
implementation of every lowered call (``kernel``, ``chunked`` or
``recurrent``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core.registry import register_op
from paddle_tpu.parallel import causal_conv as _conv
from paddle_tpu.parallel import gated_delta_rule as _kernels

DEFAULT_CHUNK = 64

_M_DISPATCH = _monitor.counter(
    "pt_linear_attention_dispatch_total",
    "gated delta-rule calls lowered, by pass (fwd, bwd), shape (batch, "
    "positions, key heads, value heads and their widths), chunk (the "
    "positions of one scan step; 1 for the recurrent form) and impl "
    "(kernel: a gdn.* or kda.* Pallas kernel; chunked: the chunkwise form "
    "as XLA ops; recurrent: one scan step a position) and gate (head: one "
    "decay a value head and position; feature: one a key feature)")


_M_CONV_DISPATCH = _monitor.counter(
    "pt_causal_conv_dispatch_total",
    "causal_conv1d calls lowered, by pass (fwd, bwd), shape (batch, "
    "positions, channels), taps and impl (kernel: a gdn.conv.* Pallas "
    "kernel; xla: float32 XLA ops over a padded copy of X); a "
    "gated_short_conv call's rows carry a further label gated=1 "
    "(channels: one of its three ranges'; kernel: sconv.gated.*)")


def _x(ins, slot, i=0):
    v = ins.get(slot)
    return v[i] if v else None


def _by_feature(g, q):
    """Whether G is a decay a key FEATURE [b, t, hv, dk] (Kimi Delta
    Attention) and not one a head [b, t, hv]: its rank says, no flag."""
    return g.ndim == q.ndim


def _note_dispatch(direction, q, v, chunk, impl, g=None):
    # off with telemetry; build-time shape inference is not a lowering
    from paddle_tpu.core import interp

    if not _monitor.enabled() or not interp.lowering_active():
        return
    b, t, hk, dk = q.shape
    _M_DISPATCH.inc(labels={
        "pass": direction,
        "shape": f"b{b} t{t} hk{hk} hv{v.shape[2]} dk{dk} dv{v.shape[3]}",
        "chunk": str(chunk), "impl": impl,
        "gate": ("feature" if g is not None and _by_feature(g, q)
                 else "head")})


def _note_conv(direction, x, taps, impl, gated=False):
    from paddle_tpu.core import interp

    if not _monitor.enabled() or not interp.lowering_active():
        return
    shape = x.shape[:-1] + (x.shape[-1] // 3,) if gated else x.shape
    labels = {"pass": direction, "shape": " ".join(
        f"{n}{d}" for n, d in zip("btc", shape)),
        "taps": str(taps), "impl": impl}
    if gated:    # a plain call's rows have no such label
        labels["gated"] = "1"
    _M_CONV_DISPATCH.inc(labels=labels)


def _counts(counter, name_of):
    out = {}
    for row in _monitor.snapshot()[counter.name]["values"]:
        name = name_of(row["labels"])
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def conv_dispatch_counts():
    """{"impl pass shape taps<n>": calls lowered so far}: the conv's
    counter as chip_smoke.py prints it."""
    return _counts(_M_CONV_DISPATCH, lambda lb: (
        f"{lb.get('impl', '?')} {lb.get('pass', '?')} "
        f"{lb.get('shape', '?')} taps{lb.get('taps', '?')}"
        + (" gated" if lb.get("gated") else "")))


def dispatch_counts():
    """{"impl pass shape chunk<C>": calls lowered so far}: the counter
    as chip_smoke.py prints it."""
    return _counts(_M_DISPATCH, lambda lb: (
        f"{lb.get('impl', '?')} {lb.get('pass', '?')} "
        f"{lb.get('shape', '?')} chunk{lb.get('chunk', '?')}"))


# ---------------------------------------------------------------------------
# the small ops around the recurrence
# ---------------------------------------------------------------------------


def _conv_xla(x, w, act, bias=None):
    """``causal_conv1d`` as XLA ops: float32 passes over a padded X."""
    taps, t = w.shape[-1], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xf[:, j:j + t] * wf[:, j] for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if act == "silu":
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


def _conv_args(ins, attrs, direction):
    """(X, W, Bias or None, act, ``conv_tile``'s answer for the call),
    noted."""
    x, w, bias = _x(ins, "X"), _x(ins, "W"), _x(ins, "Bias")
    act = attrs.get("act", "silu")
    tile = None
    if x.ndim == 3 and w.ndim == 2 and act in ("silu", ""):
        tile = _conv.conv_tile(x.shape[1], x.shape[2], w.shape[1], x.dtype)
    _note_conv(direction, x, w.shape[-1], "kernel" if tile else "xla")
    return x, w, bias, act, tile


@register_op("causal_conv1d", diff_inputs=("X", "W", "Bias"))
def _causal_conv1d(ins, attrs):
    """X [b, t, c], W [c, taps] -> Y [b, t, c]: a depthwise convolution
    over the sequence that sees no later position,
    y_t = sum_j W[:, j] * x_{t - (taps - 1) + j} (positions before the
    first count as zeros; HF's ``Conv1d(groups=c, padding=taps - 1)`` cut
    to t), plus the optional Bias [c] (Mamba's convolution has one,
    Qwen3-Next's none), then ``act``: "silu", or "" for no activation
    (Y is the taps' sum, plus the bias). Products and the sum
    in float32, the result in X's dtype: the ``gdn.conv.fwd`` kernel
    where ``parallel/causal_conv.conv_tile`` gives the call a tile (bf16
    on a TPU, no mesh, channels a multiple of 128), XLA ops everywhere
    else."""
    x, w, bias, act, tile = _conv_args(ins, attrs, "fwd")
    if tile:
        return {"Y": [_conv.causal_conv_fwd(x, w, tile, act, bias)]}
    return {"Y": [_conv_xla(x, w, act, bias)]}


@register_op("causal_conv1d_grad", no_grad=True)
def _causal_conv1d_grad(ins, attrs):
    """The backward pass of ``causal_conv1d`` from X, W, Bias and Y's
    cotangent (nothing else is saved: the pre-activation is made again):
    the ``gdn.conv.bwd`` kernel where the call has a tile, else jax's
    vjp of the XLA form. dX in X's dtype, dW in W's, dBias in Bias's."""
    x, w, bias, act, tile = _conv_args(ins, attrs, "bwd")
    dy = _x(ins, "GRAD::Y").astype(x.dtype)
    if bias is None:
        if tile:
            dx, dw = _conv.causal_conv_bwd(x, w, dy, tile, act)
        else:
            dx, dw = jax.vjp(lambda x, w: _conv_xla(x, w, act), x, w)[1](dy)
        return {"GRAD::X": [dx], "GRAD::W": [dw.astype(w.dtype)]}
    if tile:
        dx, dw, db = _conv.causal_conv_bwd(x, w, dy, tile, act, bias)
    else:
        dx, dw, db = jax.vjp(
            lambda x, w, b: _conv_xla(x, w, act, b), x, w, bias)[1](dy)
    return {"GRAD::X": [dx], "GRAD::W": [dw.astype(w.dtype)],
            "GRAD::Bias": [db.astype(bias.dtype)]}


def _gated_conv_xla(x, w):
    """``gated_short_conv`` as XLA ops: the composition, float32
    throughout (the split, B * u, the taps over a padded copy, C * c)."""
    gate, c, u = jnp.split(x.astype(jnp.float32), 3, axis=-1)
    return (c * _conv_xla(gate * u, w, "")).astype(x.dtype)


def _gated_conv_args(ins, direction):
    """(X, W, ``conv_tile``'s answer for the gated call), noted."""
    x, w = _x(ins, "X"), _x(ins, "W")
    tile = None
    if x.ndim == 3 and w.ndim == 2 and x.shape[2] == 3 * w.shape[0]:
        tile = _conv.conv_tile(x.shape[1], w.shape[0], w.shape[1], x.dtype,
                               gated=True)
    _note_conv(direction, x, w.shape[-1], "kernel" if tile else "xla",
               gated=True)
    return x, w, tile


@register_op("gated_short_conv", diff_inputs=("X", "W"))
def _gated_short_conv(ins, attrs):
    """X [b, t, 3c] = [B | C | u] (one projection of the token, its
    thirds in that order), W [c, taps] -> Y [b, t, c] = C * conv(B * u):
    LFM2's short convolution with its two input-dependent gates, the
    convolution ``causal_conv1d``'s (depthwise, causal, zeros before the
    first position) without bias or activation. X and Y in X's dtype
    (bf16 in HBM under AMP); every product and the taps' sum in float32,
    nothing rounded between them: the ``sconv.gated.fwd`` kernel where
    ``parallel/causal_conv.conv_tile(gated=True)`` gives the call a tile
    (bf16 on a TPU, no mesh, c a multiple of 128: no slice of X reaches
    HBM), XLA ops everywhere else."""
    x, w, tile = _gated_conv_args(ins, "fwd")
    if tile:
        return {"Y": [_conv.gated_conv_fwd(x, w, tile)]}
    return {"Y": [_gated_conv_xla(x, w)]}


@register_op("gated_short_conv_grad", no_grad=True)
def _gated_short_conv_grad(ins, attrs):
    """The backward pass of ``gated_short_conv`` from X, W and Y's
    cotangent (nothing else is saved: B * u and its convolution are made
    again): dX = [dB | dC | du] as ONE [b, t, 3c] tensor in X's dtype,
    dW [c, taps] in W's. The ``sconv.gated.bwd`` kernel where the call
    has a tile, else jax's vjp of the XLA form."""
    x, w, tile = _gated_conv_args(ins, "bwd")
    dy = _x(ins, "GRAD::Y").astype(x.dtype)
    if tile:
        dx, dw = _conv.gated_conv_bwd(x, w, dy, tile)
    else:
        dx, dw = jax.vjp(_gated_conv_xla, x, w)[1](dy)
    return {"GRAD::X": [dx], "GRAD::W": [dw.astype(w.dtype)]}


@register_op("gdn_gates", diff_inputs=("B", "A", "ALog", "DtBias"))
def _gdn_gates(ins, attrs):
    """B, A [b, t, h] (two projections of the token), ALog, DtBias [h]
    -> Beta = sigmoid(B), the write strength, and G = -exp(ALog) *
    softplus(A + DtBias), the log of the state's decay (<= 0). Float32
    whatever the inputs' dtype: both are exponentiated and summed over
    a chunk. A decay a key feature (Kimi Delta Attention): A
    [b, t, h, dk] with DtBias [h, dk] and ALog still [h] -> G
    [b, t, h, dk]."""
    f32 = jnp.float32
    b, a = _x(ins, "B").astype(f32), _x(ins, "A").astype(f32)
    a_log, dt = _x(ins, "ALog").astype(f32), _x(ins, "DtBias").astype(f32)
    if a.ndim == b.ndim + 1:
        a_log = a_log[:, None]
    return {"Beta": [jax.nn.sigmoid(b)],
            "G": [-jnp.exp(a_log) * jax.nn.softplus(a + dt)]}


@register_op("gated_rms_norm", diff_inputs=("X", "Z", "Scale"))
def _gated_rms_norm(ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale * silu(Z):
    Qwen3-Next's norm behind the delta rule, over each value head's
    width, a plain gain. Statistics, gain and gate in float32, Y in X's
    dtype. ``gate_first``: Y = norm(X * silu(Z)) * Scale, the gate in
    front of the statistics (Mamba-2's); ``group_size``: the mean over
    each group of that many features of the last axis; ``gate_act``:
    "sigmoid" for sigmoid(Z) where silu(Z) stands (Kimi Delta
    Attention's output gate)."""
    f32 = jnp.float32
    x, z, scale = _x(ins, "X"), _x(ins, "Z"), _x(ins, "Scale")
    xf = x.astype(f32)
    eps = attrs.get("epsilon", 1e-6)
    act = (jax.nn.sigmoid if attrs.get("gate_act") == "sigmoid"
           else jax.nn.silu)
    if not attrs.get("gate_first") and not attrs.get("group_size"):
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        y = y * scale.astype(f32) * act(z.astype(f32))
        return {"Y": [y.astype(x.dtype)]}
    gate = act(z.astype(f32))
    if attrs.get("gate_first"):
        xf = xf * gate
    size = int(attrs.get("group_size") or x.shape[-1])
    by_group = xf.reshape(xf.shape[:-1] + (-1, size))
    y = (by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, -1, keepdims=True) + eps)
         ).reshape(xf.shape) * scale.astype(f32)
    if not attrs.get("gate_first"):
        y = y * gate
    return {"Y": [y.astype(x.dtype)]}


# ---------------------------------------------------------------------------
# the gated delta rule
# ---------------------------------------------------------------------------


def _normalised(q, k, eps):
    """float32 q / |q| / sqrt(dk) and k / |k| over the last axis (HF's
    ``l2norm``: x * rsqrt(sum(x^2) + eps))."""
    def l2(x):
        xf = x.astype(jnp.float32)
        return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)

    return l2(q) * (q.shape[-1] ** -0.5), l2(k)


def _heads_first(x, rep=1):
    """[b, t, h, ...] -> [b, h * rep, t, ...], each head ``rep`` times
    in a row (key head i serves value heads i * rep .. i * rep + rep - 1,
    HF's ``repeat_interleave``)."""
    x = jnp.moveaxis(x, 2, 1)
    return jnp.repeat(x, rep, axis=1) if rep > 1 else x


def _mm(spec, a, b, dtype):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _decayed_products(q, k, gc, dtype):
    """A decay a key feature sits INSIDE the contraction: -> (P, K')
    [.., C, C] float32, P_ij = sum_d q_id k_jd exp(G_id - G_jd) for
    i >= j and K'_ij the same of k and k for i > j, zeros elsewhere,
    from q, k, G [.., C, dk]. Every exponent that is formed is <= 0
    (``(k e^G) (k e^-G)^T`` ends at e^88, and G passes -100 inside a
    chunk at the family's initialisation): positions i > j part at ONE
    level of halving, the s with i // 2s == j // 2s and i // s ==
    j // s + 1; there r = (i // s) s, the first row of i's block, lies
    between them, j < r <= i, and
    exp(G_i - G_j) = exp(G_i - G_r) exp(G_r - G_j): two factors <= 1,
    one on each operand of that level's product, which is masked to the
    level's blocks. log2(C) products of [C, dk] x [dk, C] for one, each
    a plain matmul; the diagonal of P is q_i . k_i. Exact whatever the
    gates (no clamp, no reference further than a block away)."""
    c = q.shape[-2]
    pos = jnp.arange(c)
    p = jnp.where(jnp.eye(c, dtype=bool),
                  jnp.sum(q * k, -1)[..., :, None], 0.0)
    kk = jnp.zeros_like(p)
    s = 1
    while s < c:
        blk = pos // s
        odd = (blk % 2 == 1)[:, None]
        # rows of an odd block: down from the block's first row r; rows
        # of the even block in front of it: up to r
        e = jnp.exp(jnp.where(
            odd, gc - jnp.take(gc, blk * s, axis=-2), 0.0))
        f = jnp.exp(jnp.where(
            odd, 0.0,
            jnp.take(gc, jnp.minimum((blk + 1) * s, c - 1), axis=-2) - gc))
        level = odd & (blk[:, None] == blk[None, :] + 1)
        kf = k * f
        p = p + jnp.where(
            level, _mm("...ik,...jk->...ij", q * e, kf, dtype), 0.0)
        kk = kk + jnp.where(
            level, _mm("...ik,...jk->...ij", k * e, kf, dtype), 0.0)
        s *= 2
    return p, kk


def _feature_parts(q, k, v, g, beta, dtype):
    """``_chunk_parts`` where g is [b, h, n, C, dk], a decay a key
    feature: every exp(G) a [C, dk] array where it was a column, A and
    the chunk's attention from ``_decayed_products``, exp(G_C) [.., dk]
    (a factor a ROW of the state)."""
    c = q.shape[-2]
    gc = jnp.cumsum(g, -2)
    eg = jnp.exp(gc)
    attn, kk = _decayed_products(q, k, gc, dtype)
    rhs = jnp.concatenate(
        [v.astype(jnp.float32) * beta[..., None],
         k * beta[..., None] * eg], -1)
    sol = jax.lax.linalg.triangular_solve(
        kk * beta[..., None] + jnp.eye(c, dtype=kk.dtype), rhs,
        left_side=True, lower=True, unit_diagonal=True)
    dv = v.shape[-1]
    u, w = sol[..., :dv], sol[..., dv:]
    g_last = gc[..., -1:, :]
    return (w.astype(dtype), u, (q * eg).astype(dtype),
            (k * jnp.exp(g_last - gc)).astype(dtype), attn.astype(dtype),
            eg[..., -1, :])


def _chunk_parts(q, k, v, g, beta, dtype):
    """The part of the chunkwise form that is parallel over chunks.
    q, k [b, h, n, C, dk] float32 (normalised), v [b, h, n, C, dv], g,
    beta [b, h, n, C] float32 -> (w [.., C, dk], u [.., C, dv],
    qg [.., C, dk], kd [.., C, dk], attn [.., C, C], dec [..]): the
    module docstring's W, U, Q exp(G), K exp(G_C - G), lower(Q K^T . D)
    and exp(G_C). U (subtracted from) and exp(G_C) (the state's decay)
    are float32; the other four are only ever matmul operands and leave
    in ``dtype``. g [b, h, n, C, dk]: ``_feature_parts``."""
    if _by_feature(g, q):
        return _feature_parts(q, k, v, g, beta, dtype)
    c = q.shape[-2]
    gc = jnp.cumsum(g, -1)
    lower = jnp.tril(jnp.ones((c, c), bool))
    strictly_lower = jnp.tril(lower, -1)
    diff = gc[..., :, None] - gc[..., None, :]
    # (the inner where: exp of a masked, positive difference overflows)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(strictly_lower,
                  _mm("...ik,...jk->...ij", kb, k, dtype) * decay, 0.0)
    rhs = jnp.concatenate(
        [v.astype(jnp.float32) * beta[..., None],
         kb * jnp.exp(gc)[..., None]], -1)
    sol = jax.lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    dv = v.shape[-1]
    u, w = sol[..., :dv], sol[..., dv:]
    attn = _mm("...ik,...jk->...ij", q, k, dtype) * decay
    g_last = gc[..., -1:]
    return (w.astype(dtype), u, (q * jnp.exp(gc)[..., None]).astype(dtype),
            (k * jnp.exp(g_last - gc)[..., None]).astype(dtype),
            attn.astype(dtype), jnp.exp(g_last[..., 0]))


def _chunks_first(x):
    """[b, h, n, ...] -> [n, b, h, ...]: the scan's axis in front."""
    return jnp.moveaxis(x, 2, 0)


def _over_state(dec):
    """exp(G_C) of one chunk as a factor of the state [b, h, dk, dv]: one
    a head [b, h], or one a key feature [b, h, dk], a row of the state
    each."""
    return dec[..., None, None] if dec.ndim == 2 else dec[..., None]


def _chunk_scan(parts, dtype):
    """The scan over chunks -> (o [b, h, n, C, dv] float32, the state
    each chunk started from [n, b, h, dk, dv] in ``dtype``)."""
    w, u, qg, kd, attn, dec = parts
    b, h = w.shape[:2]
    s0 = jnp.zeros((b, h, w.shape[-1], u.shape[-1]), jnp.float32)

    def step(s, xs):
        w, u, qg, kd, attn, dec = xs
        sm = s.astype(dtype)
        vn = u - _mm("bhck,bhkv->bhcv", w, sm, dtype)
        o = (_mm("bhck,bhkv->bhcv", qg, sm, dtype)
             + _mm("bhij,bhjv->bhiv", attn, vn, dtype))
        s = (s * _over_state(dec)
             + _mm("bhck,bhcv->bhkv", kd, vn, dtype))
        return s, (o, sm)

    _, (o, states) = jax.lax.scan(
        step, s0, tuple(_chunks_first(x) for x in parts))
    return jnp.moveaxis(o, 0, 2), states


def _chunk_scan_bwd(parts, states, do, dtype):
    """Cotangents of ``parts`` for the cotangent ``do`` [b, h, n, C, dv]
    of the scan's output: the chunks in reverse, each step the
    transposes of the forward step's four products around the state it
    started from (saved) and V' (one product to make again)."""
    def step(ds, xs):
        w, u, qg, kd, attn, dec, sm, do = xs
        vn = u - _mm("bhck,bhkv->bhcv", w, sm, dtype)
        dvn = (_mm("bhij,bhiv->bhjv", attn, do, dtype)
               + _mm("bhck,bhkv->bhcv", kd, ds, dtype))
        dattn = _mm("bhiv,bhjv->bhij", do, vn, dtype)
        dqg = _mm("bhcv,bhkv->bhck", do, sm, dtype)
        dkd = _mm("bhcv,bhkv->bhck", vn, ds, dtype)
        dw = -_mm("bhcv,bhkv->bhck", dvn, sm, dtype)
        ddec = jnp.sum(ds * sm.astype(jnp.float32),
                       (-2, -1) if dec.ndim == 2 else -1)
        ds = (ds * _over_state(dec)
              + _mm("bhck,bhcv->bhkv", qg, do, dtype)
              - _mm("bhck,bhcv->bhkv", w, dvn, dtype))
        return ds, (dw.astype(dtype), dvn, dqg.astype(dtype),
                    dkd.astype(dtype), dattn.astype(dtype), ddec)

    xs = tuple(_chunks_first(x) for x in parts) + (states, _chunks_first(do))
    ds0 = jnp.zeros(states.shape[1:], jnp.float32)
    _, grads = jax.lax.scan(step, ds0, xs, reverse=True)
    return tuple(jnp.moveaxis(x, 0, 2) for x in grads)


def _chunked(x, n, c):
    """[b, h, t, ...] -> [b, h, n, c, ...], zeros behind position t."""
    t = x.shape[2]
    if n * c != t:
        x = jnp.pad(x, [(0, 0), (0, 0), (0, n * c - t)]
                    + [(0, 0)] * (x.ndim - 3))
    return x.reshape(x.shape[:2] + (n, c) + x.shape[3:])


def _chunk_inputs(q, k, v, g, beta, chunk, eps):
    """The op's inputs as the chunkwise form takes them. A sequence the
    chunk does not divide is padded behind its last position with
    zeros: beta 0 writes nothing, g 0 forgets nothing and q 0 reads
    nothing, and the padded positions come after every real one."""
    rep = v.shape[2] // q.shape[2]
    n = -(-q.shape[1] // chunk)
    qn, kn = _normalised(q, k, eps)
    return tuple(_chunked(x, n, chunk) for x in (
        _heads_first(qn, rep), _heads_first(kn, rep), _heads_first(v),
        _heads_first(g.astype(jnp.float32)),
        _heads_first(beta.astype(jnp.float32))))


def _unchunked(o, t, dtype):
    """[b, h, n, C, dv] -> [b, t, h, dv]."""
    b, h, n, c, dv = o.shape
    return jnp.moveaxis(o.reshape(b, h, n * c, dv)[:, :, :t], 1, 2).astype(
        dtype)


def recurrent_gated_delta_rule(q, k, v, g, beta, eps=1e-6):
    """The recurrence of the module docstring, one scan step a position:
    q, k [b, t, hk, dk], v [b, t, hv, dv], g, beta [b, t, hv] -> o
    [b, t, hv, dv] in v's dtype. g [b, t, hv, dk]: S_t = Diag(exp(g_t))
    S_{t-1}, a decay a ROW of the state (one broadcast apart). Float32
    throughout."""
    rep = v.shape[2] // q.shape[2]
    qn, kn = _normalised(q, k, eps)
    f32 = jnp.float32
    if not _by_feature(g, q):
        g = g[..., None]
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (
        _heads_first(qn, rep), _heads_first(kn, rep),
        _heads_first(v.astype(f32)), _heads_first(g.astype(f32)),
        _heads_first(beta.astype(f32))))       # [t, b, h, ...]

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        delta = (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)) * b_t[..., None]
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    b, _, h, dv = v.shape
    s0 = jnp.zeros((b, h, q.shape[-1], dv), f32)
    _, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _args(ins, attrs):
    return (tuple(_x(ins, s) for s in ("Q", "K", "V", "G", "Beta")),
            int(attrs.get("chunk", DEFAULT_CHUNK)),
            attrs.get("impl", "chunked"),
            float(attrs.get("epsilon", 1e-6)))


def _kernel_tile(q, k, v, g, chunk):
    """``gdn_tile``'s answer for a chunked call (``kda_tile``'s where
    the decay is one a key feature): the tile of the gdn.* or kda.*
    kernels, or None for the XLA ops below."""
    if not q.dtype == k.dtype == v.dtype:
        return None
    _, t, hk, dk = q.shape
    tile = _kernels.kda_tile if _by_feature(g, q) else _kernels.gdn_tile
    return tile(t, hk, v.shape[2], dk, v.shape[3], chunk, q.dtype)


@register_op("gated_delta_rule", diff_inputs=("Q", "K", "V", "G", "Beta"))
def _gated_delta_rule(ins, attrs):
    """Q, K [b, t, hk, dk] (normalised here), V [b, t, hv, dv] (hv a
    multiple of hk), G [b, t, hv] (log decay, <= 0; [b, t, hv, dk] for
    a decay a key feature, Kimi Delta Attention's: the rank decides)
    and Beta [b, t, hv]
    (write strength), float32 both -> Out [b, t, hv, dv] in V's dtype
    and States [n, b, hv, dk, dv], the state each chunk of ``chunk``
    positions started from, for the paired grad op (dead at inference;
    one zero for ``impl="recurrent"``). See the module docstring."""
    (q, k, v, g, beta), chunk, impl, eps = _args(ins, attrs)
    if impl == "recurrent":
        _note_dispatch("fwd", q, v, 1, impl, g)
        return {"Out": [recurrent_gated_delta_rule(q, k, v, g, beta, eps)],
                "States": [jnp.zeros((1,), q.dtype)]}
    tile = _kernel_tile(q, k, v, g, chunk)
    _note_dispatch("fwd", q, v, chunk, "kernel" if tile else impl, g)
    if tile:
        o, states = _kernels.gated_delta_rule_fwd(q, k, v, g, beta, tile,
                                                  eps)
        return {"Out": [o], "States": [states]}
    parts = _chunk_parts(*_chunk_inputs(q, k, v, g, beta, chunk, eps),
                         q.dtype)
    o, states = _chunk_scan(parts, q.dtype)
    return {"Out": [_unchunked(o, q.shape[1], v.dtype)],
            "States": [states]}


@register_op("gated_delta_rule_grad", no_grad=True)
def _gated_delta_rule_grad(ins, attrs):
    """The backward pass of ``gated_delta_rule`` from the saved States
    (module docstring): the ``gdn.rule.bwd`` kernel where the call has
    a tile; else one recomputation of the parallel part, a reverse scan
    over chunks, and jax's transpose of the parallel part.
    ``impl="recurrent"``: jax's vjp of the step-by-step scan."""
    (q, k, v, g, beta), chunk, impl, eps = _args(ins, attrs)
    do = _x(ins, "GRAD::Out")
    if impl == "recurrent":
        _note_dispatch("bwd", q, v, 1, impl, g)
        _, vjp = jax.vjp(
            lambda *a: recurrent_gated_delta_rule(*a, eps), q, k, v, g, beta)
        grads = vjp(do.astype(v.dtype))
    elif tile := _kernel_tile(q, k, v, g, chunk):
        _note_dispatch("bwd", q, v, chunk, "kernel", g)
        grads = _kernels.gated_delta_rule_bwd(
            q, k, v, g, beta, _x(ins, "States"), do, tile, eps)
    else:
        _note_dispatch("bwd", q, v, chunk, impl, g)
        dtype = q.dtype

        def parallel_part(q, k, v, g, beta):
            return _chunk_parts(
                *_chunk_inputs(q, k, v, g, beta, chunk, eps), dtype)

        # (behind a barrier, or XLA merges this recomputation with the
        # forward op's and keeps every per-chunk quantity, most of them
        # float32, from the forward pass to here: 0.8 GB a layer at
        # 8192 positions of 32 heads, compiled for a v5e)
        parts, vjp = jax.vjp(parallel_part, *jax.lax.optimization_barrier(
            (q, k, v, g, beta)))
        n = parts[0].shape[2]
        do = _chunked(_heads_first(do.astype(jnp.float32)), n, chunk)
        grads = vjp(_chunk_scan_bwd(parts, _x(ins, "States"), do, dtype))
    return {f"GRAD::{s}": [d.astype(x.dtype)] for s, d, x in zip(
        ("Q", "K", "V", "G", "Beta"), grads, (q, k, v, g, beta))}
