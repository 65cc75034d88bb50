"""Pallas TPU kernels for the selective scan (Mamba-1's S6: the
mathematics and the precision contract are ops/selective_scan_ops.py's
module docstring).

``ssm.scan.fwd`` and ``ssm.scan.bwd``, one call a pass. The state of a
channel is ``n`` numbers with a decay of its own each, so nothing here
is a matmul: it is VPU and EUP work, one position after another. What
decides the layout is that B_t[n] and C_t[n] are the same for every
channel:

- **The channels fill a whole vreg, B and C are scalars.** X, Dt, Z and
  Y [b, t, e] are read as [b, t, e / 128, 128]; a grid step works on
  ``rows`` positions of 8 x 128 = 1024 channels, so a position's x is
  ONE float32 vreg [8, 128] and the state of those channels is ``n``
  vregs that stay in registers over the block's loop and in a VMEM
  scratch from block to block (the grid's last axis is ``"arbitrary"``).
  B and C [b, t, n] come in through SMEM as float32 and multiply a vreg
  as scalars: no broadcast along lanes or sublanes anywhere in the loop.
- **bf16 in HBM, float32 only in VMEM**: x, dt, z, y and their
  gradients cross HBM once each in the stream's dtype; softplus(dt +
  bias), exp(delta A), the state, the sum over n and the gate are
  float32.
- **The forward saves the state each block starts from** (``States``
  [b, blocks, n, e / 128, 128] float32: e x n numbers a block, 10 MB a
  call at 4096 x 5120 x 16) and nothing of size t x e x n.
- **The backward recomputes a block's states once** into a VMEM scratch
  [rows + 1, n, 8, 128], then walks the block's positions, and the
  blocks, last to first with the state's cotangent in registers and a
  scratch. dB_t[n] and dC_t[n] are sums over ALL channels of a product
  with the state or its cotangent, 2 n x 1024 products a position and
  tile. They are reduced without a single cross-lane reduction: the
  sublanes by a butterfly inside the loop (8 vregs [8, 128] of 8
  different n become one whose row r is the sum over the sublanes of
  one of them: 7 merges of select, select, sublane roll, add), the
  lanes by the same butterfly after the loop, over positions (128 vregs
  become one whose lane l is the sum over the lanes of one of them).
  What leaves is [rows / 32, 8, 128] a block and tile, summed over the
  tiles by the wrapper (the butterfly leaves rows and lanes in their
  natural order: ``_fold_order_is_natural``).

``ssm_tile`` is the one function that says tile or the chunked XLA form
(ops/selective_scan_ops._chunk_fn under a scan), from the call's own
shapes, the dtype, the backend and the mesh;
``pt_selective_scan_dispatch_total{impl}`` records its answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as gated_delta_rule._INTERPRET: run the kernels in
# interpreter mode on the CPU so the suite reaches them.
_INTERPRET = False

_LANES = 128
_SUBLANES = 8
_TILE = _SUBLANES * _LANES      # channels of a grid step: one f32 vreg
STATE = 16                      # the state size the kernels are written for
_BLOCK_ROWS = 128               # positions of a grid step
# positions whose 2 * STATE / 8 sublane-reduced vregs fold into one
_FOLD_ROWS = _LANES // (2 * STATE // _SUBLANES)
# results of one straight-line piece of the lane fold
_FOLD_VREGS = 64
# what the backward call may keep in VMEM (its scratch is 8 MB at 128
# rows): the call raises Mosaic's scoped limit to this
_VMEM_LIMIT_BYTES = 48 * 2**20

_F32 = jnp.float32


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def ssm_tile(t, e, n, dtype, backend=None, on_mesh=None):
    """-> (rows, channels): the positions and channels one grid step of
    ``ssm.scan.*`` works on, or None where the call runs as the chunked
    XLA form: no TPU backend (``backend``: None for this process's, with
    the interpreter counting as one), a stream that is not bf16, a
    program under a mesh (a Mosaic call is not auto-partitioned),
    channels that are not a multiple of 1024 (a float32 vreg), or a
    state of another size than 16.

    The tile follows the shape, not a flag: 128 positions, or all of a
    shorter sequence rounded up to the 32 the lane fold takes, by 1024
    channels."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or t < 1 or e < 1 or e % _TILE or n != STATE):
        return None
    return min(_BLOCK_ROWS, -(-t // _FOLD_ROWS) * _FOLD_ROWS), _TILE


# ---------------------------------------------------------------------------
# the butterfly: k vregs -> one, without a cross-lane reduction
# ---------------------------------------------------------------------------


def _merge(u, v, shift, axis, roll, where, index):
    """One vreg from two: where ``index // shift`` is even it holds u +
    (u rolled by ``shift``), elsewhere v + (v rolled): each entry the
    sum of twice as many of its source's entries as before."""
    even = (index // shift) % 2 == 0
    return where(even, u, v) + roll(where(even, v, u), shift, axis)


def _fold(vs, axis, roll, where, index):
    """[v_0 .. v_{k-1}] (k a power of two that divides the axis) -> one
    array of their shape: shifts 1, 2, 4 ..; entry i along ``axis``
    holds the sum over k entries of ONE v_j: v_i's, as
    ``_fold_order_is_natural`` holds it to."""
    shift = 1
    while len(vs) > 1:
        vs = [_merge(vs[i], vs[i + 1], shift, axis, roll, where, index)
              for i in range(0, len(vs), 2)]
        shift *= 2
    return vs[0]


def _fold_order_is_natural():
    """Folding tagged arrays in numpy exactly as the kernel folds vregs:
    row r of a sublane-folded vreg holds the sum of v_r, lane l of a
    lane-folded one the sum of v_l (``_unfold`` counts on it)."""
    def fold(vs, axis):
        index = np.arange(vs[0].shape[axis]).reshape(
            (-1, 1) if axis == 0 else (1, -1))
        return _fold(vs, axis, np.roll, np.where, index)

    rows = fold([np.full((_SUBLANES, 1), 2.0 ** i) for i in range(_SUBLANES)],
                0)[:, 0]
    lanes = fold([np.full((1, _LANES), 2.0 ** i) for i in range(_LANES)],
                 1)[0]
    return ((rows == _SUBLANES * 2.0 ** np.arange(_SUBLANES)).all()
            and (lanes == _LANES * 2.0 ** np.arange(_LANES)).all())


assert _fold_order_is_natural()


def _iota(axis):
    return jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), axis)


def _fold_sublanes(vs):
    return _fold(vs, 0, pltpu.roll, jnp.where, _iota(0))


# ---------------------------------------------------------------------------
# what a position computes
# ---------------------------------------------------------------------------


def _softplus(v):
    return jnp.where(v > 20.0, v, jnp.log1p(jnp.exp(jnp.minimum(v, 20.0))))


def _delta(dt_ref, bias, t):
    """(delta, the pre-activation) of position t, float32 [8, 128]."""
    raw = dt_ref[t].astype(_F32) + bias
    return _softplus(raw), raw


def _silu_parts(z):
    sig = jax.nn.sigmoid(z)
    return z * sig, sig


def _scalars(ref, t, n):
    return [ref[t * n + i] for i in range(n)]


def _step(s, x, dt, a_ref, b):
    """s_t from s_{t-1}: n vregs."""
    dtx = dt * x
    return [jnp.exp(dt * a_ref[i]) * s[i] + b[i] * dtx
            for i in range(len(s))]


def _read_out(s, c, d, x):
    y = d * x
    for i in range(len(s)):
        y = y + c[i] * s[i]
    return y


# ---------------------------------------------------------------------------
# ssm.scan.fwd
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, n, has_z):
    (x_ref, dt_ref), refs = refs[:2], refs[2:]
    if has_z:
        z_ref, refs = refs[0], refs[1:]
    a_ref, d_ref, bias_ref, b_ref, c_ref, y_ref, states_ref, s_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    states_ref[...] = s_ref[...]
    d, bias = d_ref[...], bias_ref[...]

    def step(t, s):
        x = x_ref[t].astype(_F32)
        dt, _ = _delta(dt_ref, bias, t)
        s = _step(s, x, dt, a_ref, _scalars(b_ref, t, n))
        y = _read_out(s, _scalars(c_ref, t, n), d, x)
        if has_z:
            y = y * _silu_parts(z_ref[t].astype(_F32))[0]
        y_ref[t] = y.astype(y_ref.dtype)
        return s

    s = jax.lax.fori_loop(0, x_ref.shape[0], step,
                          [s_ref[i] for i in range(n)])
    for i in range(n):
        s_ref[i] = s[i]


def _padded(x, size, value=0.0):
    if x.shape[1] == size:
        return x
    return jnp.pad(x, [(0, 0), (0, size - x.shape[1])]
                   + [(0, 0)] * (x.ndim - 2), constant_values=value)


# The pre-activation behind a sequence's last position: delta is exactly
# 0 there (softplus(-3e4 + any bias) is), so the state stays as it is and
# nothing is written to it.
PAD_DT = -3e4


def _operands(x, dt, z, a, b, c, d, dt_bias, tile):
    """The op's inputs as the kernels take them: the sequence padded
    behind its last position to whole blocks (``PAD_DT``),
    the channels as [.., e / 128, 128], A as [n, e / 128, 128], B and C
    float32 and flat (SMEM)."""
    rows = tile[0]
    bsz, t, e = x.shape
    size = -(-t // rows) * rows
    lanes = lambda v: v.reshape(v.shape[:-1] + (e // _LANES, _LANES))
    flat = lambda v: _padded(v.astype(_F32), size).reshape(-1)
    bias = jnp.zeros((e,), _F32) if dt_bias is None else dt_bias.astype(_F32)
    return (lanes(_padded(x, size)), lanes(_padded(dt, size, PAD_DT)),
            None if z is None else lanes(_padded(z, size)),
            lanes(a.astype(_F32).T), lanes(d.astype(_F32)), lanes(bias),
            flat(b), flat(c))


def _specs(rows, n, nblk, blk):
    """BlockSpecs of (an X-like [b, t, e / 128, 128], A [n, e / 128,
    128], a row [e / 128, 128], B or C flat in SMEM, the states
    [b, blocks, n, e / 128, 128]) for a grid whose step works on batch
    ``i``, channel tile ``j`` and block of positions ``k``, as ``blk``
    reads them off the grid's indices."""
    def at(f):
        return lambda *g: f(*blk(*g))

    return (pl.BlockSpec((None, rows, _SUBLANES, _LANES),
                         at(lambda i, j, k: (i, k, j, 0))),
            pl.BlockSpec((n, _SUBLANES, _LANES),
                         at(lambda i, j, k: (0, j, 0))),
            pl.BlockSpec((_SUBLANES, _LANES), at(lambda i, j, k: (j, 0))),
            pl.BlockSpec((rows * n,), at(lambda i, j, k: (i * nblk + k,)),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, n, _SUBLANES, _LANES),
                         at(lambda i, j, k: (i, k, 0, j, 0))))


def selective_scan_fwd(x, dt, a, b, c, d, z, dt_bias, tile):
    """x, dt [b, t, e] (bf16), a [e, n], b, c [b, t, n], d [e], z like x
    or None, dt_bias [e] or None -> (y [b, t, e] in x's dtype, the state
    each block of ``tile[0]`` positions starts from [b, blocks, n,
    e / 128, 128] float32)."""
    bsz, t, e = x.shape
    n = a.shape[1]
    rows = tile[0]
    x4, dt4, z4, a3, d2, bias2, bf, cf = _operands(
        x, dt, z, a, b, c, d, dt_bias, tile)
    nblk = x4.shape[1] // rows
    x_spec, a_spec, row_spec, bc_spec, st_spec = _specs(
        rows, n, nblk, lambda i, j, k: (i, j, k))
    has_z = z is not None
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, has_z=has_z),
        name="ssm.scan.fwd",
        out_shape=(jax.ShapeDtypeStruct(x4.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (bsz, nblk, n, e // _LANES, _LANES), _F32)),
        grid=(bsz, e // _TILE, nblk),
        in_specs=([x_spec, x_spec] + [x_spec] * has_z
                  + [a_spec, row_spec, row_spec, bc_spec, bc_spec]),
        out_specs=(x_spec, st_spec),
        scratch_shapes=[pltpu.VMEM((n, _SUBLANES, _LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * x4.size * n, transcendentals=x4.size * (n + 3),
            bytes_accessed=(3 + has_z) * x4.size * x.dtype.itemsize),
        interpret=_INTERPRET,
    )(*([x4, dt4] + [z4] * has_z + [a3, d2, bias2, bf, cf]))
    return y.reshape(bsz, -1, e)[:, :t], states


# ---------------------------------------------------------------------------
# ssm.scan.bwd
# ---------------------------------------------------------------------------


def _bwd_kernel(*refs, n, has_z):
    (x_ref, dt_ref), refs = refs[:2], refs[2:]
    if has_z:
        z_ref, refs = refs[0], refs[1:]
    (dy_ref, a_ref, d_ref, bias_ref, b_ref, c_ref, states_ref), refs = (
        refs[:7], refs[7:])
    (dx_ref, ddt_ref), refs = refs[:2], refs[2:]
    if has_z:
        dz_ref, refs = refs[0], refs[1:]
    (da_ref, dd_ref, dbias_ref, dbc_ref, s_all, g_ref, r_ref) = refs
    rows = x_ref.shape[0]
    groups = n // _SUBLANES

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when((pl.program_id(2) == 0) & (pl.program_id(1) == 0))
    def _():
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    d, bias = d_ref[...], bias_ref[...]

    # the block's states again, s_all[t + 1] = s_t
    s_all[0] = states_ref[...]

    def again(t, s):
        dt, _ = _delta(dt_ref, bias, t)
        s = _step(s, x_ref[t].astype(_F32), dt, a_ref,
                  _scalars(b_ref, t, n))
        for i in range(n):
            s_all[t + 1, i] = s[i]
        return s

    jax.lax.fori_loop(0, rows, again, [states_ref[i] for i in range(n)])

    def back(j, carry):
        g, dd, dbias = carry
        t = rows - 1 - j
        x = x_ref[t].astype(_F32)
        dt, raw = _delta(dt_ref, bias, t)
        bs, cs = _scalars(b_ref, t, n), _scalars(c_ref, t, n)
        dy = dy_ref[t].astype(_F32)
        if has_z:
            z = z_ref[t].astype(_F32)
            gate, sig = _silu_parts(z)
            y = _read_out([s_all[t + 1, i] for i in range(n)], cs, d, x)
            dz_ref[t] = (dy * y * sig * (1.0 + z * (1.0 - sig))).astype(
                dz_ref.dtype)
            dy = dy * gate
        dtx = dt * x
        ddt = jnp.zeros_like(x)
        sum_gb = jnp.zeros_like(x)
        prod_c, prod_b, g_new = [], [], []
        for i in range(n):
            gi = g[i] + cs[i] * dy            # the cotangent of s_t
            prod_c.append(dy * s_all[t + 1, i])
            prod_b.append(gi * dtx)
            a_i = a_ref[i]
            decay = jnp.exp(dt * a_i)
            daa = gi * s_all[t, i] * decay
            ddt = ddt + daa * a_i
            da_ref[i] += daa * dt
            sum_gb = sum_gb + gi * bs[i]
            g_new.append(decay * gi)
        ddt = (ddt + sum_gb * x) * jax.nn.sigmoid(raw)
        dx_ref[t] = (dy * d + sum_gb * dt).astype(dx_ref.dtype)
        ddt_ref[t] = ddt.astype(ddt_ref.dtype)
        for k in range(groups):
            at = slice(k * _SUBLANES, (k + 1) * _SUBLANES)
            r_ref[t * 2 * groups + k] = _fold_sublanes(prod_c[at])
            r_ref[t * 2 * groups + groups + k] = _fold_sublanes(prod_b[at])
        return g_new, dd + dy * x, dbias + ddt

    zero = jnp.zeros((_SUBLANES, _LANES), _F32)
    g, dd, dbias = jax.lax.fori_loop(
        0, rows, back, ([g_ref[i] for i in range(n)], zero, zero))
    for i in range(n):
        g_ref[i] = g[i]
    dd_ref[...] += dd
    dbias_ref[...] += dbias

    # the lanes: r_ref's rows * 2 * groups vregs fold to rows / 32, a
    # level of the butterfly at a time and _FOLD_VREGS results a
    # straight-line piece (one merge a trip of a loop took 70 cycles: a
    # pair's loads, a lane roll and a store with nothing to overlap; my
    # chip run, PR 40). A piece writes in front of everything a later
    # piece reads.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _SUBLANES, _LANES), 2)
    count, shift = rows * 2 * groups, 1
    while shift < _LANES:
        count //= 2
        for at in range(0, count, _FOLD_VREGS):
            size = min(_FOLD_VREGS, count - at)
            r_ref[pl.ds(at, size)] = _merge(
                r_ref[pl.ds(2 * at, size, stride=2)],
                r_ref[pl.ds(2 * at + 1, size, stride=2)], shift, 2,
                pltpu.roll, jnp.where, lane)
        shift *= 2
    dbc_ref[...] = r_ref[:count]


def _unfold(dbc, t, n):
    """[b, tiles, blocks, rows / 32, 8, 128] (the kernel's folded sums:
    row r the state index r of its group, lane l = position * 2 * groups
    + group, C's groups in front of B's) -> (dB, dC [b, t, n]), the
    tiles summed."""
    groups = n // _SUBLANES
    bsz = dbc.shape[0]
    out = jnp.sum(dbc, axis=1).reshape(
        bsz, -1, _SUBLANES, _FOLD_ROWS, 2 * groups)
    out = jnp.transpose(out, (0, 1, 3, 4, 2)).reshape(
        bsz, -1, 2, groups * _SUBLANES)[:, :t]
    return out[:, :, 1], out[:, :, 0]


def selective_scan_bwd(x, dt, a, b, c, d, z, dt_bias, states, dy, tile):
    """The cotangents (dx, ddt [b, t, e] in x's dtype, da [e, n], db, dc
    [b, t, n], dd [e], dz like x or None, ddt_bias [e]; float32 but the
    first two and dz) of ``selective_scan_fwd`` for the cotangent ``dy``
    of y, from the saved ``states``."""
    bsz, t, e = x.shape
    n = a.shape[1]
    rows = tile[0]
    x4, dt4, z4, a3, d2, bias2, bf, cf = _operands(
        x, dt, z, a, b, c, d, dt_bias, tile)
    dy4 = _padded(dy.astype(x.dtype), x4.shape[1]).reshape(x4.shape)
    nblk = x4.shape[1] // rows
    tiles = e // _TILE
    x_spec, a_spec, row_spec, bc_spec, st_spec = _specs(
        rows, n, nblk, lambda j, i, k: (i, j, nblk - 1 - k))
    has_z = z is not None
    folds = rows // _FOLD_ROWS
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, has_z=has_z),
        name="ssm.scan.bwd",
        out_shape=(
            [jax.ShapeDtypeStruct(x4.shape, x.dtype)] * (2 + has_z)
            + [jax.ShapeDtypeStruct(a3.shape, _F32),
               jax.ShapeDtypeStruct(d2.shape, _F32),
               jax.ShapeDtypeStruct(d2.shape, _F32),
               jax.ShapeDtypeStruct(
                   (bsz, tiles, nblk, folds, _SUBLANES, _LANES), _F32)]),
        grid=(tiles, bsz, nblk),
        in_specs=([x_spec, x_spec] + [x_spec] * has_z
                  + [x_spec, a_spec, row_spec, row_spec, bc_spec, bc_spec,
                     st_spec]),
        out_specs=(
            [x_spec] * (2 + has_z) + [a_spec, row_spec, row_spec]
            + [pl.BlockSpec(
                (None, None, None, folds, _SUBLANES, _LANES),
                lambda j, i, k: (i, j, nblk - 1 - k, 0, 0, 0))]),
        scratch_shapes=[
            pltpu.VMEM((rows + 1, n, _SUBLANES, _LANES), _F32),
            pltpu.VMEM((n, _SUBLANES, _LANES), _F32),
            pltpu.VMEM((rows * 2 * n // _SUBLANES, _SUBLANES, _LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=30 * x4.size * n, transcendentals=x4.size * (2 * n + 6),
            bytes_accessed=(6 + 2 * has_z) * x4.size * x.dtype.itemsize),
        interpret=_INTERPRET,
    )(*([x4, dt4] + [z4] * has_z + [dy4, a3, d2, bias2, bf, cf, states]))
    (dx, ddt), outs = outs[:2], outs[2:]
    dz = None
    if has_z:
        dz, outs = outs[0], outs[1:]
    da, dd, dbias, dbc = outs
    seq = lambda v: v.reshape(bsz, -1, e)[:, :t]
    db, dc = _unfold(dbc, t, n)
    return (seq(dx), seq(ddt), da.reshape(n, e).T, db, dc, dd.reshape(e),
            None if dz is None else seq(dz), dbias.reshape(e))
