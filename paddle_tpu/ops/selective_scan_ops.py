"""The selective scan of a state-space layer (Mamba-1's S6: Gu & Dao
2023, arXiv:2312.00752, as HF ``modeling_phi4flash.py`` and the
``selective_scan_fn`` it calls lay it out).

Per channel e a state of n numbers that starts at zero, with a decay, an
input matrix and an output matrix that depend on the token and are
diagonal in the state:

    Delta_t[e] = softplus(Dt_t[e] + DtBias[e])
    s_t[e, n]  = exp(Delta_t[e] A[e, n]) s_{t-1}[e, n] + Delta_t[e] B_t[n] x_t[e]
    y_t[e]     = sum_n C_t[n] s_t[e, n] + D[e] x_t[e]
    out_t      = y_t * silu(z_t)                      (where Z is given)

No delta rule, no heads, no matmul form (A differs per channel AND
state index): ``gated_delta_rule`` cannot express it. What is float32
whatever the activation stream: Delta, A, every exp, the state, the sum
over n and the gate; X, Dt, Z arrive and Out leaves in the stream's
dtype.

``selective_scan`` runs that recurrence in CHUNKS: a ``lax.scan`` over
chunks of ``chunk`` positions carries the state, and the forward saves
the state each chunk starts from (``States``) and nothing of size t x e
x n. The backward pass is the op's own (``selective_scan_grad``): it
walks the chunks in reverse with the state's cotangent and recomputes
inside a chunk what the forward made of it.

Two writings of that form, chosen per call by
``parallel/selective_scan.ssm_tile`` from the call's own shapes (never
by a flag): the ``ssm.scan.fwd`` / ``ssm.scan.bwd`` Pallas kernels where
it gives a tile (a bf16 stream, channels a multiple of 1024, a state of
16, a TPU backend, no mesh: the state stays in VMEM across the blocks,
nothing is staged through HBM), and XLA ops everywhere else
(``_chunk_fn`` below under ``lax.scan``, ``jax.vjp`` of it a chunk at a
time backward): every CPU run, a float32 stream, other sizes, a program
under a mesh.

``impl="recurrent"`` is the recurrence position by position over the
whole sequence (one ``lax.scan``, differentiated by jax, which keeps
[t, e, n] float32 for the backward pass): the fallback a caller asks
for, never taken silently: ``pt_selective_scan_dispatch_total`` records
the implementation of every lowered call (``kernel``, ``chunked`` or
``recurrent``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.linear_attention_ops import _counts
from paddle_tpu.parallel import selective_scan as _kernels

DEFAULT_CHUNK = 64
SLOTS = ("X", "Dt", "A", "B", "C", "D", "Z", "DtBias")

_M_DISPATCH = _monitor.counter(
    "pt_selective_scan_dispatch_total",
    "selective_scan calls lowered, by pass (fwd, bwd), shape (batch, "
    "positions, channels, state), chunk (the positions between two saved "
    "states; 1 for the recurrent form) and impl (kernel: an ssm.scan.* "
    "Pallas kernel; chunked: a scan over chunks as XLA ops; recurrent: "
    "one scan over all positions)")


def _x(ins, slot):
    v = ins.get(slot)
    return v[0] if v else None


def _note_dispatch(direction, x, n, chunk, impl):
    # off with telemetry; build-time shape inference is not a lowering
    from paddle_tpu.core import interp

    if not _monitor.enabled() or not interp.lowering_active():
        return
    b, t, e = x.shape
    _M_DISPATCH.inc(labels={
        "pass": direction, "shape": f"b{b} t{t} e{e} n{n}",
        "chunk": str(chunk), "impl": impl})


def dispatch_counts():
    """{"impl pass shape chunk<C>": calls lowered so far}: the counter
    as chip_smoke.py prints it."""
    return _counts(_M_DISPATCH, lambda lb: (
        f"{lb.get('impl', '?')} {lb.get('pass', '?')} "
        f"{lb.get('shape', '?')} chunk{lb.get('chunk', '?')}"))


def _positions(s, x, dt, a, b, c, d, z, dt_bias):
    """The recurrence over the positions of x [b, t, e] from the state s
    [b, e, n] (float32) -> (out [b, t, e] float32, the state behind the
    last position)."""
    f32 = jnp.float32
    raw = dt.astype(f32)
    if dt_bias is not None:
        raw = raw + dt_bias.astype(f32)
    delta = jax.nn.softplus(raw)
    xf, a = x.astype(f32), a.astype(f32)

    def step(s, at):
        x_t, d_t, b_t, c_t = at              # [b, e], [b, e], [b, n], [b, n]
        s = (jnp.exp(d_t[..., None] * a) * s
             + (d_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], -1)

    first = lambda v: jnp.moveaxis(v, 1, 0)
    s, y = jax.lax.scan(step, s, (first(xf), first(delta),
                                  first(b.astype(f32)), first(c.astype(f32))))
    y = jnp.moveaxis(y, 0, 1) + d.astype(f32) * xf
    if z is not None:
        y = y * jax.nn.silu(z.astype(f32))
    return y, s


def recurrent_selective_scan(x, dt, a, b, c, d, z=None, dt_bias=None):
    """The recurrence of the module docstring, one scan step a position:
    x, dt [b, t, e], a [e, n], b, c [b, t, n], d [e], z like x or None,
    dt_bias [e] or None -> out [b, t, e] in x's dtype."""
    s0 = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    return _positions(s0, x, dt, a, b, c, d, z, dt_bias)[0].astype(x.dtype)


def _chunks_first(v, n, chunk, value=0.0):
    """[b, t, ...] -> [n, b, chunk, ...], ``value`` behind position t."""
    t = v.shape[1]
    if n * chunk != t:
        v = jnp.pad(v, [(0, 0), (0, n * chunk - t)] + [(0, 0)] * (v.ndim - 2),
                    constant_values=value)
    return jnp.moveaxis(
        v.reshape((v.shape[0], n, chunk) + v.shape[2:]), 1, 0)


def _chunk_inputs(x, dt, b, c, z, chunk):
    """The per-position inputs chunks first. A sequence the chunk does
    not divide is padded behind its last position with a delta of
    exactly 0 (``parallel/selective_scan.PAD_DT``): the state neither
    decays nor is written to there, and the padded positions come after
    every real one."""
    n = -(-x.shape[1] // chunk)
    return n, (_chunks_first(x, n, chunk),
               _chunks_first(dt, n, chunk, _kernels.PAD_DT),
               _chunks_first(b, n, chunk), _chunks_first(c, n, chunk),
               None if z is None else _chunks_first(z, n, chunk))


def _unchunked(y, t):
    """[n, b, chunk, e] -> [b, t, e]."""
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape((y.shape[0], -1) + y.shape[3:])[:, :t]


def _chunk_fn(s, per, a, d, dt_bias):
    """One chunk from the state ``s`` it starts from: ``per`` = (x, dt,
    b, c, z) of its positions -> (out float32, the state behind it)."""
    x, dt, b, c, z = per
    return _positions(s, x, dt, a, b, c, d, z, dt_bias)


def _args(ins, attrs):
    return (tuple(_x(ins, s) for s in SLOTS),
            int(attrs.get("chunk", DEFAULT_CHUNK)),
            attrs.get("impl", "chunked"))


def _kernel_tile(x, dt, z, a):
    """``ssm_tile``'s answer for a chunked call: the tile of the ssm.*
    kernels, or None for the XLA ops."""
    if x.dtype != dt.dtype or (z is not None and z.dtype != x.dtype):
        return None
    return _kernels.ssm_tile(x.shape[1], x.shape[2], a.shape[1], x.dtype)


@register_op("selective_scan", diff_inputs=SLOTS)
def _selective_scan(ins, attrs):
    """X, Dt [b, t, e] (Dt the pre-activation of Delta), A [e, n] (< 0),
    B, C [b, t, n], D [e], optional Z [b, t, e] (a gate: Out = y *
    silu(Z)) and DtBias [e] -> Out [b, t, e] in X's dtype and States,
    the float32 state each chunk started from, for the paired grad op
    (dead at inference; one zero for ``impl="recurrent"``). See the
    module docstring."""
    (x, dt, a, b, c, d, z, dt_bias), chunk, impl = _args(ins, attrs)
    n = a.shape[1]
    if impl == "recurrent":
        _note_dispatch("fwd", x, n, 1, impl)
        return {"Out": [recurrent_selective_scan(x, dt, a, b, c, d, z,
                                                 dt_bias)],
                "States": [jnp.zeros((1,), jnp.float32)]}
    if tile := _kernel_tile(x, dt, z, a):
        _note_dispatch("fwd", x, n, tile[0], "kernel")
        y, states = _kernels.selective_scan_fwd(x, dt, a, b, c, d, z,
                                                dt_bias, tile)
        return {"Out": [y], "States": [states]}
    _note_dispatch("fwd", x, n, chunk, impl)
    _, per = _chunk_inputs(x, dt, b, c, z, chunk)

    def step(s, per):
        y, s_next = _chunk_fn(s, per, a, d, dt_bias)
        return s_next, (y.astype(x.dtype), s)

    s0 = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    _, (y, states) = jax.lax.scan(step, s0, per)
    return {"Out": [_unchunked(y, x.shape[1])], "States": [states]}


@register_op("selective_scan_grad", no_grad=True)
def _selective_scan_grad(ins, attrs):
    """The backward pass of ``selective_scan`` from the saved States
    (module docstring): the ``ssm.scan.bwd`` kernel where the call has a
    tile; else a reverse scan over the chunks, each step jax's vjp of
    the chunk around the state it started from.
    ``impl="recurrent"``: jax's vjp of the one scan."""
    args, chunk, impl = _args(ins, attrs)
    x, dt, a, b, c, d, z, dt_bias = args
    n = a.shape[1]
    dy = _x(ins, "GRAD::Out")
    if impl == "recurrent":
        _note_dispatch("bwd", x, n, 1, impl)
        present = {s: v for s, v in zip(SLOTS, args) if v is not None}
        _, vjp = jax.vjp(lambda p: recurrent_selective_scan(
            *(p.get(s) for s in SLOTS)), present)
        grads = [vjp(dy.astype(x.dtype))[0].get(s) for s in SLOTS]
    elif tile := _kernel_tile(x, dt, z, a):
        _note_dispatch("bwd", x, n, tile[0], "kernel")
        dx, ddt, da, db, dc, dd, dz, dbias = _kernels.selective_scan_bwd(
            x, dt, a, b, c, d, z, dt_bias, _x(ins, "States"), dy, tile)
        grads = [dx, ddt, da, db, dc, dd, dz, dbias]
    else:
        _note_dispatch("bwd", x, n, chunk, impl)
        nc, per = _chunk_inputs(x, dt, b, c, z, chunk)
        shared = (a, d, dt_bias)

        def step(carry, at):
            ds, dshared = carry
            s, per, dy = at
            _, vjp = jax.vjp(
                lambda s, per, shared: _chunk_fn(s, per, *shared),
                s, per, shared)
            ds, dper, dsh = vjp((dy, ds))
            return (ds, jax.tree_util.tree_map(jnp.add, dshared, dsh)), dper

        zeros = jax.tree_util.tree_map(
            lambda v: jnp.zeros(v.shape, v.dtype), shared)
        (_, (da, dd, dbias)), (dx, ddt, db, dc, dz) = jax.lax.scan(
            step, (jnp.zeros((x.shape[0],) + a.shape, jnp.float32), zeros),
            (_x(ins, "States"), per,
             _chunks_first(dy.astype(jnp.float32), nc, chunk)), reverse=True)
        t = x.shape[1]
        seq = lambda v: None if v is None else _unchunked(v, t)
        grads = [seq(dx), seq(ddt), da, seq(db), seq(dc), dd, seq(dz), dbias]
    return {f"GRAD::{s}": [g.astype(v.dtype)]
            for s, g, v in zip(SLOTS, grads, args) if v is not None}
