"""The state-space scan of a Mamba-2 layer (Dao & Gu 2024,
arXiv:2405.21060; as HF ``modeling_nemotron_h.py``'s mixer and the
``mamba_chunk_scan_combined`` it calls lay it out).

Per head h of p features a state of [p, n] numbers that starts at zero,
with ONE scalar decay a position (Mamba-1 has one per channel and state
index: ops/selective_scan_ops.py), and an input and an output vector of
the token that the heads of a group share:

    dt_t[h]  = softplus(Dt_t[h] + DtBias[h])
    a_t[h]   = -exp(ALog[h]) dt_t[h]                       (the log decay)
    S_t[h]   = exp(a_t[h]) S_{t-1}[h] + dt_t[h] x_t[h] B_t[g]^T    [p, n]
    y_t[h]   = S_t[h] C_t[g] + D[h] x_t[h]                 g = h // (heads / groups)

X arrives [b, t, heads * p], B and C [b, t, groups * n], Dt [b, t,
heads]. What is float32 whatever the activation stream: dt, the log
decay, its running sums and every exp, the state, D's product; X, B, C
arrive and Out leaves in the stream's dtype. HF clamps dt to
``time_step_limit``; the published limit is (0, inf), which a softplus
never leaves, so no clamp is built.

A scalar decay makes the recurrence over a CHUNK of positions four
matrix products (the paper's section 6, Listing 1): with Acum the
running sum of a inside the chunk, X~ = dt x and S the state the chunk
starts from,

    Y  = ((C B^T) o L) X~ + exp(Acum) (C S^T) + D x,   L_ij = exp(Acum_i - Acum_j), i >= j
    S' = exp(Acum_C) S + (exp(Acum_C - Acum) X~)^T B

``mamba2_scan`` runs that form: a ``lax.scan`` over chunks of ``chunk``
positions carries the state, and the forward saves the state each chunk
starts from (``States``) and nothing of size t x heads x p x n. The
backward pass is the op's own (``mamba2_scan_grad``): it walks the
chunks in reverse with the state's cotangent and makes a chunk again.

Three lowerings, chosen per call by ``parallel/mamba2_scan.mamba2_tile``
from the call's own shapes (never by a flag): the ``mamba2.chunk.fwd`` /
``mamba2.chunk.bwd`` Pallas kernels where it gives a tile (a bf16
stream, heads of 64 in even groups of any size, a state of 128, chunk
128, a TPU backend, no mesh), XLA ops everywhere else (``_chunk_fn`` below under
``lax.scan``, ``jax.vjp`` of it a chunk at a time backward): every CPU
run, a float32 stream, other sizes, a program under a mesh.
``impl="recurrent"`` is the recurrence position by position (one
``lax.scan``, differentiated by jax, which keeps [t, heads, p, n]
float32 for the backward pass): the form a test asks for, never taken
silently: ``pt_mamba2_scan_dispatch_total`` records the implementation
of every lowered call (``kernel``, ``chunked`` or ``recurrent``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.linear_attention_ops import _counts
from paddle_tpu.parallel import mamba2_scan as _kernels

DEFAULT_CHUNK = 128
SLOTS = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")
_F32 = jnp.float32

_M_DISPATCH = _monitor.counter(
    "pt_mamba2_scan_dispatch_total",
    "mamba2_scan calls lowered, by pass (fwd, bwd), shape (batch, "
    "positions, heads, a head's features, groups, state), chunk (the "
    "positions between two saved states; 1 for the recurrent form), "
    "impl (kernel: a mamba2.chunk.* Pallas kernel; chunked: a scan over "
    "chunks as XLA ops; recurrent: one scan over all positions) and tile "
    "(a kernel's grid step, hb<heads of a head block> c<chunks>: a head "
    "block is a whole group or, of a group wider than a step, a part; "
    "empty for the XLA forms)")


def _x(ins, slot):
    v = ins.get(slot)
    return v[0] if v else None


def _sizes(x, dt, b, groups):
    """(heads, a head's features, the state's size)."""
    heads = dt.shape[-1]
    return heads, x.shape[-1] // heads, b.shape[-1] // groups


def _note_dispatch(direction, x, dt, b, groups, chunk, impl, tile=None):
    # off with telemetry; build-time shape inference is not a lowering
    from paddle_tpu.core import interp

    if not _monitor.enabled() or not interp.lowering_active():
        return
    heads, p, n = _sizes(x, dt, b, groups)
    _M_DISPATCH.inc(labels={
        "pass": direction,
        "shape": f"b{x.shape[0]} t{x.shape[1]} h{heads} p{p} g{groups} n{n}",
        "chunk": str(chunk), "impl": impl,
        "tile": f"hb{2 * tile[0]} c{tile[1]}" if tile else ""})


def dispatch_counts():
    """{"impl pass shape chunk<C>": calls lowered so far}: the counter
    as chip_smoke.py prints it."""
    return _counts(_M_DISPATCH, lambda lb: (
        f"{lb.get('impl', '?')} {lb.get('pass', '?')} "
        f"{lb.get('shape', '?')} chunk{lb.get('chunk', '?')}"))


def step_sizes(dt, a_log, dt_bias):
    """(dt, a) [b, t, heads] float32 of the module docstring: the step
    size behind its softplus and the log decay -exp(ALog) dt."""
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    return dt, -jnp.exp(a_log.astype(_F32)) * dt


def _grouped(x, dt, a, b, c, groups):
    """x [.., g, r, p], dt and a [.., g, r], B and C [.., g, n], float32:
    the heads by group."""
    heads = dt.shape[-1]
    r = heads // groups
    lead = x.shape[:-1]
    f = lambda v, *tail: v.astype(_F32).reshape(lead + tail)
    return (f(x, groups, r, x.shape[-1] // heads), f(dt, groups, r),
            f(a, groups, r), f(b, groups, b.shape[-1] // groups),
            f(c, groups, c.shape[-1] // groups))


def recurrent_mamba2_scan(x, dt, a_log, b, c, d, dt_bias, groups):
    """The recurrence of the module docstring, one scan step a position:
    x [b, t, heads * p], dt [b, t, heads], a_log, d, dt_bias [heads], b,
    c [b, t, groups * n] -> y [b, t, heads * p] in x's dtype."""
    dt, a = step_sizes(dt, a_log, dt_bias)
    xg, dtg, ag, bg, cg = _grouped(x, dt, a, b, c, groups)

    def step(s, at):
        x_t, dt_t, a_t, b_t, c_t = at   # [b,g,r,p] [b,g,r] [b,g,r] [b,g,n]
        s = (jnp.exp(a_t)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return s, jnp.sum(s * c_t[:, :, None, None, :], -1)

    first = lambda v: jnp.moveaxis(v, 1, 0)
    s0 = jnp.zeros(xg.shape[:1] + xg.shape[2:] + bg.shape[-1:], _F32)
    _, y = jax.lax.scan(step, s0, tuple(first(v) for v in
                                        (xg, dtg, ag, bg, cg)))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    heads = dt.shape[-1]
    y = y + jnp.repeat(d.astype(_F32), x.shape[-1] // heads) * x.astype(_F32)
    return y.astype(x.dtype)


def _chunks_first(v, n, chunk):
    """[b, t, ...] -> [n, b, chunk, ...], zeros behind position t."""
    t = v.shape[1]
    if n * chunk != t:
        v = jnp.pad(v, [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (v.ndim - 2))
    return jnp.moveaxis(
        v.reshape((v.shape[0], n, chunk) + v.shape[2:]), 1, 0)


def _unchunked(y, t):
    """[n, b, chunk, e] -> [b, t, e]."""
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape((y.shape[0], -1) + y.shape[3:])[:, :t]


def _chunk_fn(s, per, shared, groups):
    """One chunk from the state ``s`` [b, g, r, p, n] it starts from:
    ``per`` = (x, dt_raw, b, c) of its positions, ``shared`` = (a_log, d,
    dt_bias) -> (y [b, chunk, heads * p] float32, the state behind it).
    A padded position has x = 0 and, through ``live`` (per's fifth: 1 on
    a real position), a step size and a log decay of exactly 0."""
    x, dt_raw, b, c, live = per
    a_log, d, dt_bias = shared
    dt, a = step_sizes(dt_raw, a_log, dt_bias)
    dt, a = dt * live, a * live
    xg, dtg, ag, bg, cg = _grouped(x, dt, a, b, c, groups)
    acum = jnp.cumsum(ag, axis=1)                         # [b, l, g, r]
    size = x.shape[1]
    lower = jnp.tril(jnp.ones((size, size), bool))[None, :, :, None, None]
    diff = acum[:, :, None] - acum[:, None, :]            # [b, i, j, g, r]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    cb = jnp.einsum("bign,bjgn->bijg", cg, bg)
    xdt = xg * dtg[..., None]
    y = jnp.einsum("bijgr,bjgrp->bigrp", cb[..., None] * decay, xdt)
    y = y + jnp.exp(acum)[..., None] * jnp.einsum("bign,bgrpn->bigrp", cg, s)
    a_last = acum[:, -1]                                  # [b, g, r]
    to_end = jnp.exp(a_last[:, None] - acum)[..., None]
    s = (jnp.exp(a_last)[..., None, None] * s
         + jnp.einsum("bjgrp,bjgn->bgrpn", xdt * to_end, bg))
    heads = dt.shape[-1]
    y = (y.reshape(x.shape)
         + jnp.repeat(d.astype(_F32), x.shape[-1] // heads) * x.astype(_F32))
    return y, s


def _args(ins, attrs):
    return (tuple(_x(ins, s) for s in SLOTS),
            int(attrs.get("chunk", DEFAULT_CHUNK)), int(attrs["groups"]),
            attrs.get("impl", "chunked"))


def _kernel_tile(x, dt, b, c, groups, chunk):
    """``mamba2_tile``'s answer for a chunked call: the tile of the
    mamba2.chunk.* kernels, or None for the XLA ops."""
    if not (x.dtype == b.dtype == c.dtype):
        return None
    heads, p, n = _sizes(x, dt, b, groups)
    return _kernels.mamba2_tile(x.shape[1], heads, groups, p, n, chunk,
                                x.dtype)


def _chunk_inputs(x, dt, b, c, chunk):
    n = -(-x.shape[1] // chunk)
    live = (jnp.arange(n * chunk) < x.shape[1]).astype(_F32)
    live = jnp.broadcast_to(live[None, :, None], (x.shape[0], n * chunk, 1))
    return n, tuple(_chunks_first(v, n, chunk) for v in (x, dt, b, c, live))


def _zero_state(x, dt, b, groups):
    heads, p, n = _sizes(x, dt, b, groups)
    return jnp.zeros((x.shape[0], groups, heads // groups, p, n), _F32)


@register_op("mamba2_scan", diff_inputs=SLOTS)
def _mamba2_scan(ins, attrs):
    """X [b, t, heads * p], Dt [b, t, heads] (the pre-activation of the
    step size), ALog, D, DtBias [heads], B, C [b, t, groups * n] -> Out
    [b, t, heads * p] in X's dtype and States, the state each chunk
    started from, for the paired grad op (dead at inference; one zero
    for ``impl="recurrent"``). Attrs ``groups``, ``chunk`` (128),
    ``impl``. See the module docstring."""
    (x, dt, a_log, b, c, d, dt_bias), chunk, groups, impl = _args(ins, attrs)
    if impl == "recurrent":
        _note_dispatch("fwd", x, dt, b, groups, 1, impl)
        return {"Out": [recurrent_mamba2_scan(x, dt, a_log, b, c, d,
                                              dt_bias, groups)],
                "States": [jnp.zeros((1,), _F32)]}
    if tile := _kernel_tile(x, dt, b, c, groups, chunk):
        _note_dispatch("fwd", x, dt, b, groups, chunk, "kernel", tile)
        y, states = _kernels.mamba2_scan_fwd(
            x, *step_sizes(dt, a_log, dt_bias), b, c, d, tile)
        return {"Out": [y], "States": [states]}
    _note_dispatch("fwd", x, dt, b, groups, chunk, impl)
    _, per = _chunk_inputs(x, dt, b, c, chunk)
    shared = (a_log, d, dt_bias)

    def step(s, per):
        y, s_next = _chunk_fn(s, per, shared, groups)
        return s_next, (y.astype(x.dtype), s)

    _, (y, states) = jax.lax.scan(step, _zero_state(x, dt, b, groups), per)
    return {"Out": [_unchunked(y, x.shape[1])], "States": [states]}


@register_op("mamba2_scan_grad", no_grad=True)
def _mamba2_scan_grad(ins, attrs):
    """The backward pass of ``mamba2_scan`` from the saved States (module
    docstring): the ``mamba2.chunk.bwd`` kernel where the call has a
    tile (and jax's transposes of the few XLA ops in front of it: the
    softplus and the log decay); else a reverse scan over the chunks,
    each step jax's vjp of the chunk around the state it started from.
    ``impl="recurrent"``: jax's vjp of the one scan."""
    args, chunk, groups, impl = _args(ins, attrs)
    x, dt, a_log, b, c, d, dt_bias = args
    dy = _x(ins, "GRAD::Out")
    if impl == "recurrent":
        _note_dispatch("bwd", x, dt, b, groups, 1, impl)
        _, vjp = jax.vjp(
            lambda *a: recurrent_mamba2_scan(*a, groups), *args)
        grads = vjp(dy.astype(x.dtype))
    elif tile := _kernel_tile(x, dt, b, c, groups, chunk):
        _note_dispatch("bwd", x, dt, b, groups, chunk, "kernel", tile)
        (step, a), vjp = jax.vjp(step_sizes, dt, a_log, dt_bias)
        dx, db, dc, dstep, da, dd = _kernels.mamba2_scan_bwd(
            x, step, a, b, c, d, _x(ins, "States"), dy, tile)
        ddt, da_log, dbias = vjp((dstep, da))
        grads = (dx, ddt, da_log, db, dc, dd, dbias)
    else:
        _note_dispatch("bwd", x, dt, b, groups, chunk, impl)
        nc, per = _chunk_inputs(x, dt, b, c, chunk)
        shared = (a_log, d, dt_bias)

        def step(carry, at):
            ds, dshared = carry
            s, per, dy = at
            _, vjp = jax.vjp(
                lambda s, per, shared: _chunk_fn(s, per, shared, groups),
                s, per, shared)
            ds, dper, dsh = vjp((dy, ds))
            return (ds, jax.tree_util.tree_map(jnp.add, dshared, dsh)), dper

        zeros = jax.tree_util.tree_map(
            lambda v: jnp.zeros(v.shape, v.dtype), shared)
        (_, (da_log, dd, dbias)), (dx, ddt, db, dc, _) = jax.lax.scan(
            step, (_zero_state(x, dt, b, groups), zeros),
            (_x(ins, "States"), per,
             _chunks_first(dy.astype(_F32), nc, chunk)), reverse=True)
        t = x.shape[1]
        grads = (_unchunked(dx, t), _unchunked(ddt, t), da_log,
                 _unchunked(db, t), _unchunked(dc, t), dd, dbias)
    return {f"GRAD::{s}": [g.astype(v.dtype)]
            for s, g, v in zip(SLOTS, grads, args)}
