"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): the residual path as a parameter
of the layer. ``n`` residual streams X stand where one [b, t, d] stood,
laid side by side along the features as vec(X) [b, t, n d] (stream i is
features i d .. (i + 1) d: a [.., n, d] tensor of bf16 would be tiled
over (n, d) and every reshape of it to the projection's [T, n d] a copy);
every op carries the attribute ``n``. Around each sublayer F (a layer's
attention and its feed-forward have a mix each):

    r      = rsqrt(mean(vec(X)^2) + eps)            one a token, over n d
    m      = (vec(X) Phi) * r                        [n^2 + 2n], float32
    H_pre  = sigmoid(alpha[0] m[:n] + b[:n])                        [n]
    H_post = 2 sigmoid(alpha[1] m[n:2n] + b[n:2n])                  [n]
    M      = exp(clamp(alpha[2] mat(m[2n:]) + mat(b[2n:]), lo, hi)) [n, n]
    iters x: M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    H_res  = M                     (Sinkhorn: doubly stochastic at the limit)
    h      = sum_i H_pre[i] X[i]                     F's input
    X'[j]  = sum_i H_res[j, i] X[i] + H_post[j] F(h)

three ops: ``hc_mix`` (X -> H_pre, H_post, H_res), ``hc_pre`` (X, H_pre
-> h) and ``hc_post`` (X, y, H_res, H_post -> X'). ``mat`` is row-major:
entry (j, i) is feature 2n + j n + i; a row sum is over i.

The three H are float32 and TOKEN-MINOR, [b, n, t] and [b, n, n, t]: a
[.., t, 4] float32 tensor would spend a 128-lane tile a token. The mix
and its Sinkhorn iterations are float32 whatever the streams' dtype; the
projection's operands are the streams' dtype (bf16 under AMP, Phi
rounded as any weight is) and accumulate in float32. The stream
products of ``hc_pre`` and ``hc_post`` accumulate in float32 and round
once. Under AMP ``hc_pre`` and ``hc_post`` take X, y and the cotangents
in bf16 (core/interp.AMP_OP_TYPES) and keep the H float32
(AMP_KEEP_F32_SLOTS); ``hc_mix`` reads X as it comes.

Every backward pass is written by hand, a grad op of its own. Autodiff
of ``hc_post`` reads the streams once a product, and of the mix would
keep every iteration's matrix from the forward pass: ``hc_mix_grad``
RECOMPUTES the mix from the saved stream (mHC 4.3.2 does the same), so
nothing of it lives from the forward pass to the backward but X itself.
With M' = M / (s + hc_eps), s a row or column sum, a half-step's
backward is dM = (dM' - sum(dM' M')) / (s + hc_eps), the sum over the
same axis.

The mix's iterations are the kernels ``hc.mix.fwd`` / ``hc.mix.bwd``
(parallel/hc_mix.py) where ``hc_mix.mix_tile`` gives the call a tile (a
TPU, no mesh, whole blocks of tokens), else XLA's ops, which run them as
a chain of a fusion or two a half-step; everything around them and the
stream passes of ``hc_pre`` / ``hc_post`` are XLA's ops
(``pt_hc_dispatch_total{op, pass, impl}`` says which a call took; a mix
counts as ``kernel`` where its iterations do)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core.registry import register_op

_F32 = jnp.float32

_M_DISPATCH = _monitor.counter(
    "pt_hc_dispatch_total",
    "hyper-connection calls lowered, one row a call: op (mix, pre, post), "
    "pass (fwd, bwd) and impl (xla: the op as XLA's ops; kernel: an "
    "hc.* Pallas kernel; a mix whose Sinkhorn iterations are hc.mix.*)")


def _x(ins, slot, i=0):
    v = ins.get(slot)
    return v[i] if v else None


def _note(op, direction, impl="xla"):
    # off with telemetry; build-time shape inference is not a lowering
    from paddle_tpu.core import interp

    if _monitor.enabled() and interp.lowering_active():
        _M_DISPATCH.inc(labels={"op": op, "pass": direction, "impl": impl})


def dispatch_counts():
    """{"impl op pass": calls lowered so far}: the counter as
    chip_smoke.py prints it."""
    out = {}
    for row in _monitor.snapshot()[_M_DISPATCH.name]["values"]:
        lb = row["labels"]
        name = f"{lb.get('impl', '?')} {lb.get('op', '?')} {lb.get('pass', '?')}"
        out[name] = out.get(name, 0) + int(row["value"])
    return out


# ---------------------------------------------------------------------------
# the mix
# ---------------------------------------------------------------------------


def _mix_attrs(attrs):
    return (float(attrs.get("epsilon", 1e-6)), int(attrs.get("iters", 20)),
            float(attrs.get("hc_eps", 1e-6)),
            float(attrs.get("clamp_min", -30.0)),
            float(attrs.get("clamp_max", 30.0)))


def _projection(x, phi):
    """(x2 [T, n d] as it came, the same in float32, p [K, T] float32) of
    X [b, t, n d]: the flattened streams and their projection by Phi."""
    x2 = x.reshape(-1, x.shape[-1])
    xf = x2.astype(_F32)
    p = jnp.einsum("fk,tf->kt", phi.astype(x.dtype), x2,
                   preferred_element_type=_F32)
    return x2, xf, p


def _sinkhorn(m0, iters, hc_eps, keep=False):
    """(H_res of M0 [n, n, T], with ``keep`` every iteration's (M' behind
    the row step, 1 / (rowsum + hc_eps), M' behind the column step, 1 /
    (colsum + hc_eps)) stacked): one scan step an iteration, so that the
    program holds the iteration once."""
    def iteration(m, _):
        inv_r = 1.0 / (jnp.sum(m, axis=1) + hc_eps)     # a row: over i
        m_r = m * inv_r[:, None]
        inv_c = 1.0 / (jnp.sum(m_r, axis=0) + hc_eps)
        m_c = m_r * inv_c[None]
        return m_c, ((m_r, inv_r, m_c, inv_c) if keep else None)

    return jax.lax.scan(iteration, m0, None, length=iters)


def _sinkhorn_back(d_res, kept):
    """dM0 of dH_res through the kept iterations, last first: a
    half-step's dM = (dM' - sum(dM' M')) / (s + hc_eps), the sum over its
    axis."""
    def iteration(dm, k):
        m_r, inv_r, m_c, inv_c = k
        dm = (dm - jnp.sum(dm * m_c, axis=0)[None]) * inv_c[None]
        dm = (dm - jnp.sum(dm * m_r, axis=1)[:, None]) * inv_r[:, None]
        return dm, None

    return jax.lax.scan(iteration, d_res, kept, reverse=True)[0]


def _res_tile(z, n, direction):
    """``parallel/hc_mix.mix_tile``'s answer for the Sinkhorn iterations
    on Z [n n, T] (None: XLA's ops), noted in ``pt_hc_dispatch_total``."""
    from paddle_tpu.parallel import hc_mix

    tile = hc_mix.mix_tile(n, z.shape[-1])
    _note("mix", direction, "kernel" if tile else "xla")
    return tile


def _res(z, n, attrs):
    """H_res [n, n, T] of the logits Z [n n, T]: ``hc.mix.fwd`` where the
    call has a tile, else XLA's ops."""
    _, iters, hc_eps, lo, hi = _mix_attrs(attrs)
    tile = _res_tile(z, n, "fwd")
    if tile:
        from paddle_tpu.parallel import hc_mix

        return hc_mix.sinkhorn_fwd(z, n, iters, hc_eps, lo, hi,
                                   tile).reshape(n, n, -1)
    m0 = jnp.exp(jnp.clip(z, lo, hi)).reshape(n, n, -1)
    return _sinkhorn(m0, iters, hc_eps)[0]


def _res_grad(z, d_res, n, attrs):
    """dZ [n n, T] of dH_res [n, n, T], the iterations made again from Z
    and walked back half-step by half-step: ``hc.mix.bwd``, else XLA's
    ops."""
    _, iters, hc_eps, lo, hi = _mix_attrs(attrs)
    tile = _res_tile(z, n, "bwd")
    if tile:
        from paddle_tpu.parallel import hc_mix

        return hc_mix.sinkhorn_bwd(z, d_res.reshape(z.shape), n, iters,
                                   hc_eps, lo, hi, tile)
    m0 = jnp.exp(jnp.clip(z, lo, hi)).reshape(n, n, -1)
    dm = _sinkhorn_back(d_res, _sinkhorn(m0, iters, hc_eps, keep=True)[1])
    inside = (z > lo) & (z < hi)
    return jnp.where(inside, (dm * m0).reshape(z.shape), 0.0)


def _mix(x, phi, bias, alpha, attrs):
    """((H_pre [n, T], H_post [n, T], the n x n part's logits Z [n n, T]),
    what the backward pass needs of the way there)."""
    eps = _mix_attrs(attrs)[0]
    n = int(attrs["n"])
    x2, xf, p = _projection(x, phi)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)          # [T]
    m = p * r[None, :]                                            # [K, T]
    bias, alpha = bias.astype(_F32), alpha.astype(_F32)
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n]
                                + bias[n:2 * n, None])
    z = alpha[2] * m[2 * n:] + bias[2 * n:, None]                 # [n n, T]
    return (pre, post, z), (x2, xf, p, r, m)


def _token_minor(h, b):
    """[.., T] -> [b, .., t]."""
    h = h.reshape(h.shape[:-1] + (b, -1))
    return jnp.moveaxis(h, -2, 0)


def _token_flat(h):
    """[b, .., t] -> [.., T]."""
    h = jnp.moveaxis(h, 0, -2)
    return h.reshape(h.shape[:-2] + (-1,))


@register_op("hc_mix", diff_inputs=("X", "Phi", "Bias", "Alpha"))
def _hc_mix(ins, attrs):
    """X [b, t, n d], Phi [n d, n^2 + 2n], Bias [n^2 + 2n], Alpha [3] ->
    HPre, HPost [b, n, t] and HRes [b, n, n, t], float32 (the module's
    equations; attrs ``n``, ``epsilon``, ``iters``, ``hc_eps``,
    ``clamp_min``, ``clamp_max``)."""
    x = _x(ins, "X")
    (pre, post, z), _ = _mix(x, _x(ins, "Phi"), _x(ins, "Bias"),
                             _x(ins, "Alpha"), attrs)
    res = _res(z, int(attrs["n"]), attrs)
    b = x.shape[0]
    return {"HPre": [_token_minor(pre, b)], "HPost": [_token_minor(post, b)],
            "HRes": [_token_minor(res, b)]}


def _cot(g, like_shape):
    """A cotangent in float32, token-flat; zeros where the program gave
    none."""
    if g is None:
        return jnp.zeros(like_shape, _F32)
    return _token_flat(g.astype(_F32))


@register_op("hc_mix_grad", no_grad=True)
def _hc_mix_grad(ins, attrs):
    """GRAD::X, GRAD::Phi, GRAD::Bias, GRAD::Alpha of hc_mix from X alone:
    the mix is made again here (its matrices are read from no forward
    output), then walked back half-step by half-step."""
    # (behind a barrier: XLA would otherwise find the forward op's mix
    # in these lines and keep its forty matrices instead)
    x, phi = jax.lax.optimization_barrier(_x(ins, "X")), _x(ins, "Phi")
    bias, alpha = _x(ins, "Bias"), _x(ins, "Alpha")
    n, nd = int(attrs["n"]), x.shape[-1]
    (pre, post, z), (x2, xf, p, r, m) = _mix(x, phi, bias, alpha, attrs)
    a = alpha.astype(_F32)

    d_pre = _cot(_x(ins, "GRAD::HPre"), pre.shape)
    d_post = _cot(_x(ins, "GRAD::HPost"), post.shape)
    dz_res = _res_grad(z, _cot(_x(ins, "GRAD::HRes"), (n, n, z.shape[-1])),
                       n, attrs)
    dz_pre = d_pre * pre * (1.0 - pre)
    dz_post = d_post * post * (1.0 - 0.5 * post)
    dz = jnp.concatenate([dz_pre, dz_post, dz_res], axis=0)       # [K, T]
    d_bias = jnp.sum(dz, axis=-1)
    d_alpha = jnp.stack([jnp.sum(dz_pre * m[:n]),
                         jnp.sum(dz_post * m[n:2 * n]),
                         jnp.sum(dz_res * m[2 * n:])])
    scale = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                             jnp.full((n * n,), a[2])])
    dm = dz * scale[:, None]
    dp = dm * r[None, :]
    dr = jnp.sum(dm * p, axis=0)                                   # [T]
    dp_x = dp.astype(x.dtype)
    d_phi = jnp.einsum("tf,kt->fk", x2, dp_x, preferred_element_type=_F32)
    # (the product leaves the MXU in the streams' dtype: a float32
    # [T, n d] between it and the sum is two more stream-sized passes)
    dx = jnp.einsum("kt,fk->tf", dp_x, phi.astype(x.dtype))
    dx = dx.astype(_F32) + (dr * (-(r ** 3) / nd))[:, None] * xf
    return {"GRAD::X": [dx.astype(x.dtype).reshape(x.shape)],
            "GRAD::Phi": [d_phi.astype(phi.dtype)],
            "GRAD::Bias": [d_bias.astype(bias.dtype)],
            "GRAD::Alpha": [d_alpha.astype(alpha.dtype)]}


# ---------------------------------------------------------------------------
# the read and the write-back
# ---------------------------------------------------------------------------


def _streams(x, n):
    """X [b, t, n d] -> its n streams [b, t, d] in float32."""
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d].astype(_F32) for i in range(n)]


def _side_by_side(streams, dtype):
    """n streams [b, t, d] -> [b, t, n d] in ``dtype``."""
    return jnp.concatenate([s.astype(dtype) for s in streams], axis=-1)


def _w(h, *index):
    """One weight a token of a token-minor H, [b, t, 1]: ready to
    multiply a stream."""
    for i in index:
        h = h[:, i]
    return h.astype(_F32)[..., None]


def _dot(a, b):
    """sum over the features of a * b -> [b, t], float32."""
    return jnp.sum(a * b, axis=-1)


@register_op("hc_pre", diff_inputs=("X", "HPre"))
def _hc_pre(ins, attrs):
    """Out [b, t, d] = sum_i HPre[:, i] X_i (X_i: stream i of X
    [b, t, n d]; attr ``n``): the sublayer's input, in X's dtype."""
    x, h = _x(ins, "X"), _x(ins, "HPre")
    _note("pre", "fwd")
    xs = _streams(x, int(attrs["n"]))
    out = _w(h, 0) * xs[0]
    for i in range(1, len(xs)):
        out = out + _w(h, i) * xs[i]
    return {"Out": [out.astype(x.dtype)]}


@register_op("hc_pre_grad", no_grad=True)
def _hc_pre_grad(ins, attrs):
    """GRAD::X_i = HPre[:, i] dOut and GRAD::HPre[:, i] = sum_d dOut
    X_i."""
    x, h = _x(ins, "X"), _x(ins, "HPre")
    _note("pre", "bwd")
    do = _x(ins, "GRAD::Out").astype(_F32)
    n = int(attrs["n"])
    xs = _streams(x, n)
    dx = _side_by_side([_w(h, i) * do for i in range(n)], x.dtype)
    dh = jnp.stack([_dot(do, xs[i]) for i in range(n)], axis=1)
    return {"GRAD::X": [dx], "GRAD::HPre": [dh]}


@register_op("hc_post", diff_inputs=("X", "Y", "HRes", "HPost"))
def _hc_post(ins, attrs):
    """Out_j = sum_i HRes[:, j, i] X_i + HPost[:, j] Y: the streams
    behind the sublayer [b, t, n d], in X's dtype (attr ``n``)."""
    x, y = _x(ins, "X"), _x(ins, "Y")
    res, post = _x(ins, "HRes"), _x(ins, "HPost")
    _note("post", "fwd")
    n = int(attrs["n"])
    xs, yf = _streams(x, n), y.astype(_F32)
    outs = []
    for j in range(n):
        acc = _w(post, j) * yf
        for i in range(n):
            acc = acc + _w(res, j, i) * xs[i]
        outs.append(acc)
    # (the streams are a tensor of the program, saved for the backward
    # pass: behind a barrier, or XLA's CPU pipeline fuses this op's twenty
    # products into each of the next sublayer's three readers again and
    # a five-layer step takes five times as long to compile)
    return {"Out": [jax.lax.optimization_barrier(
        _side_by_side(outs, x.dtype))]}


@register_op("hc_post_grad", no_grad=True)
def _hc_post_grad(ins, attrs):
    """From X, Y and dOut, each read once: GRAD::X_i = sum_j HRes[:, j, i]
    dOut_j, GRAD::Y = sum_j HPost[:, j] dOut_j, GRAD::HRes[:, j, i] =
    sum_d dOut_j X_i, GRAD::HPost[:, j] = sum_d dOut_j Y."""
    x, y = _x(ins, "X"), _x(ins, "Y")
    res, post = _x(ins, "HRes"), _x(ins, "HPost")
    _note("post", "bwd")
    n = int(attrs["n"])
    xs, yf = _streams(x, n), y.astype(_F32)
    dos = _streams(_x(ins, "GRAD::Out"), n)
    dxs = []
    for i in range(n):
        acc = _w(res, 0, i) * dos[0]
        for j in range(1, n):
            acc = acc + _w(res, j, i) * dos[j]
        dxs.append(acc)
    dy = _w(post, 0) * dos[0]
    for j in range(1, n):
        dy = dy + _w(post, j) * dos[j]
    d_res = jnp.stack([jnp.stack([_dot(dos[j], xs[i]) for i in range(n)],
                                 axis=1) for j in range(n)], axis=1)
    d_post = jnp.stack([_dot(dos[j], yf) for j in range(n)], axis=1)
    return {"GRAD::X": [_side_by_side(dxs, x.dtype)],
            "GRAD::Y": [dy.astype(y.dtype)],
            "GRAD::HRes": [d_res], "GRAD::HPost": [d_post]}
