"""Pallas TPU kernels for the causal depthwise convolution over the
sequence (ops/linear_attention_ops.causal_conv1d: a few taps, every
channel its own filter, then ``act``: ``"silu"`` in front of a gated
delta rule, a selective scan or a Mamba-2 scan, ``""`` for no
activation at all, the taps' sum as it is; the mathematics and the
precision contract are that op's docstring), and for the gated short
convolution that IS a sequence mixer (``gated_short_conv``, below).

``gdn.conv.fwd`` and ``gdn.conv.bwd``, one call a pass. A grid step
works on a block of (rows of t) x (a multiple of 128 channels) of X
[b, t, c] where it lies: the channels are the lanes, the positions the
sublanes, so a tap is a shift along the sublanes.

What XLA's ops do not do and a grid step does:

- **bf16 in HBM, float32 only in VMEM.** X, Y, dY and dX cross HBM once
  each, as bf16; the cast, the products, their sum, ``silu`` and its
  derivative are float32 on ``_PASS_ROWS`` rows of the block at a time
  (a pass of the loop inside a grid step: a few vregs an array, nothing
  spilled). No padded float32 copy of X, no float32 Y, nothing between
  the taps in HBM.
- **The earlier rows are a halo, not a pad.** A second BlockSpec reads
  the 16 rows (one bf16 sublane tile) in front of the block, of which
  the last 8 count; the first block of a sequence sees zeros there. A
  pass behind the first takes its halo from the block itself. A shift by
  s rows is ``pltpu.roll`` of [halo; rows] along the sublanes and an
  aligned slice: no unaligned slice, no select of the kernel's own.
  (The other candidate, the forward's t axis sequential and a block's
  last rows carried in a VMEM scratch, read 0.500 ms a call against
  0.502 at b1 t8192 c8192: my chip run, PR 37,
  benchmarks/conv_candidates.py. The BlockSpec keeps every axis of the
  forward's grid parallel and is what the backward kernel, which walks
  the other way, needs for X in any case.)
- **One backward kernel, nothing saved but X.** It makes the
  pre-activation again, forms dpre = dY silu'(pre) in float32 and never
  rounds it: dX at row r sums W[:, j] dpre at rows r .. r + taps - 1, so
  the blocks of a sequence (and the passes of a block) are walked from
  the LAST to the first and the first 8 rows of dpre wait in a float32
  VMEM scratch for the block in front; dW [taps, c] is summed in
  float32 over the rows of a pass onto 8 sublanes (vreg adds), carried
  through the loop, and added to an output block that stays in VMEM
  over a channel block's whole walk (the grid is channels, batch,
  blocks of t: the last two sequential). The wrapper folds the 8
  sublanes and hands dW back as [c, taps].

``sconv.gated.fwd`` and ``sconv.gated.bwd`` (LFM2's mixer,
ops/linear_attention_ops.gated_short_conv): X is the fused projection
[b, t, 3c] = [B | C | u] and y = C * taps(B * u), no activation. The
same blocks, halo, passes and reversed walk; what is new is the operand
form: the three channel ranges are three BlockSpecs on the ONE operand
whose index maps differ by a lane-block offset (c a multiple of 128, so
every offset is aligned), B and u each with their halo. No slice of the
projection, no v = B * u and no c = taps(v) reaches HBM: the forward
reads [B | C | u] once and writes y; the backward reads [B | C | u] and
dy once, makes v and c again in VMEM, and writes dB, dC, du into the
three ranges of ONE [b, t, 3c] output. An output has one BlockSpec, so
the backward grid has an innermost axis of 3 over the ranges: step 0
does the block's whole work, writes dB and leaves dC and du in a VMEM
scratch, steps 1 and 2 copy them out (their input blocks are step 0's,
so nothing is fetched again).

``conv_tile`` is the one function that says tile or the XLA form
(ops/linear_attention_ops._conv_xla, the parent's five lines), from the
call's own shapes, the dtype, the backend and the mesh, for the plain
and the gated call alike (``gated=True`` counts the wider blocks);
``pt_causal_conv_dispatch_total{impl}`` records its answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as gated_delta_rule._INTERPRET: run the kernels in
# interpreter mode on the CPU so the suite reaches them.
_INTERPRET = False

_LANES = 128
_HALO = 16      # rows of the block in front: one bf16 sublane tile
_TAIL = 8       # of which a pass keeps the last: one float32 tile
# Rows of one pass of the loop inside a grid step, and the block of a
# grid step: the widest of these lane counts that divides c. Timed on a
# v5e at b1 t8192 c8192 (benchmarks/conv_candidates.py; my chip runs,
# PR 37; forward + backward ms a call): 1024 x 512 by 32 rows 0.50 +
# 0.88, 512 x 512 0.56 + 0.89, 2048 x 512 0.49 + 0.88, 1024 x 256 by 64
# 0.58 + 0.89, 512 x 128 0.83 + 1.18; passes of 64 rows 0.51 + 0.93, of
# 16 0.58 + 1.01; the XLA form 2.54 + 7.99.
_PASS_ROWS = 32
_BLOCK_ROWS = 1024
_BLOCK_LANES = (512, 256, 128)
# What a call's blocks may take of VMEM: under Mosaic's scoped default
# of 16 MiB, so no call raises it.
_VMEM_CAP_BYTES = 12 * 2**20

_F32 = jnp.float32


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _vmem_bytes(rows, lanes, taps, gated=False):
    """What one grid step of the backward kernel (the larger) keeps in
    VMEM: X, dY and dX blocks and the halo as bf16, W and the dW block
    as float32, all double-buffered, and the scratch. ``gated``: three
    ranges of X and two halos in, and the two ranges that wait in the
    scratch for their step."""
    x_blocks, halos = (3, 2) if gated else (1, 1)
    blocks = (((x_blocks + 2) * rows + halos * _HALO) * lanes * 2
              + (taps + taps * _TAIL) * lanes * 4)
    return (2 * blocks + _TAIL * lanes * 4
            + (2 * rows * lanes * 2 if gated else 0))


def conv_tile(t, c, taps, dtype, backend=None, on_mesh=None, gated=False):
    """-> (rows, lanes): the block of X one grid step of ``gdn.conv.*``
    works on, or None where the call runs as the XLA form: no TPU
    backend (``backend``: None for this process's, with the interpreter
    counting as one), X not bf16, a program under a mesh (a Mosaic call
    is not auto-partitioned), channels that are not a multiple of the
    128 lanes, more earlier rows than the 8 a pass keeps
    (``taps - 1 > 8``), or a block over the VMEM cap.

    The tile follows the shape, not a flag: 1024 rows, or all of a
    shorter sequence (padded to whole passes), by the widest lane block
    of 512, 256, 128 that divides c. ``gated``: the call is
    ``sconv.gated.*``'s, c the channels of ONE of the three ranges."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or t < 1 or c < 1 or c % _LANES or not 1 <= taps <= _TAIL + 1):
        return None
    rows = min(_BLOCK_ROWS, -(-t // _PASS_ROWS) * _PASS_ROWS)
    for lanes in _BLOCK_LANES:
        if (c % lanes == 0
                and _vmem_bytes(rows, lanes, taps, gated) <= _VMEM_CAP_BYTES):
            return rows, lanes
    return None


# ---------------------------------------------------------------------------
# what a pass computes
# ---------------------------------------------------------------------------


def _shifted(front, x, taps):
    """front [16, L], x [R, L] (bf16: the rows in front of x, and x) ->
    [x shifted down by s rows for s in 0 .. taps - 1], float32 [R, L]:
    entry s holds at row r what X holds at row r - s."""
    ext = jnp.concatenate([front.astype(_F32)[_HALO - _TAIL:],
                           x.astype(_F32)], axis=0)
    return [ext[_TAIL:]] + [pltpu.roll(ext, s, 0)[_TAIL:]
                            for s in range(1, taps)]


def _taps_sum(xs, w):
    """sum_j w[j] xs[taps - 1 - j], the oldest row first (the XLA
    form's order of float32 additions): the newest meets the last tap."""
    taps = len(xs)
    acc = xs[taps - 1] * w[0]
    for j in range(1, taps):
        acc = acc + xs[taps - 1 - j] * w[j]
    return acc


def _taps_back(dpre, behind, w, rows):
    """dx_r = sum_s w[taps - 1 - s] dpre_{r + s}: the taps walked
    backward. Shifts UP, into the rows behind: ``behind`` [8, L] holds
    dpre's first rows of the pass (or block) behind this one."""
    taps = len(w)
    ext = jnp.concatenate([dpre, behind], axis=0)
    dx = dpre * w[taps - 1]
    for s in range(1, taps):
        dx = dx + (pltpu.roll(ext, rows + _TAIL - s, 0)[:rows]
                   * w[taps - 1 - s])
    return dx


def _fold(p):
    """[R, L] -> [8, L]: the rows summed onto one sublane tile."""
    acc = p[:_TAIL]
    for r in range(_TAIL, p.shape[0], _TAIL):
        acc = acc + p[r:r + _TAIL]
    return acc


def _w_rows(w_ref, taps):
    return [w_ref[j:j + 1, :] for j in range(taps)]


def _pass_rows(p, rows):
    return pl.ds(pl.multiple_of(p * rows, rows), rows)


def _block_in_front(front_ref, first):
    """The 16 rows in front of a block; zeros for a sequence's first."""
    front = front_ref[...]
    return jnp.where(first, jnp.zeros_like(front), front)


def _rows_in_front(x_ref, p, rows):
    """The 16 rows of the block in front of pass ``p`` > 0."""
    return x_ref[pl.ds(pl.multiple_of(p * rows - _HALO, _HALO), _HALO), :]


# ---------------------------------------------------------------------------
# gdn.conv.fwd
# ---------------------------------------------------------------------------


def _bias_row(w_ref, taps, bias):
    """The bias [1, L] where the call has one: the row behind the taps
    of the W operand."""
    return w_ref[taps:taps + 1, :] if bias else None


def _fwd_passes(x_ref, front, w_ref, y_ref, *, taps, act, rows, bias):
    """Y of a block from X's block and the 16 rows in front of it."""
    w = _w_rows(w_ref, taps)
    b = _bias_row(w_ref, taps, bias)

    def one(p, front):
        at = _pass_rows(p, rows)
        pre = _taps_sum(_shifted(front, x_ref[at, :], taps), w)
        if bias:
            pre = pre + b
        if act == "silu":
            pre = pre * jax.nn.sigmoid(pre)
        y_ref[at, :] = pre.astype(y_ref.dtype)

    one(0, front)

    def later(p, carry):
        one(p, _rows_in_front(x_ref, p, rows))
        return carry

    jax.lax.fori_loop(1, x_ref.shape[0] // rows, later, None)


def _fwd_kernel(x_ref, front_ref, w_ref, y_ref, **how):
    _fwd_passes(x_ref, _block_in_front(front_ref, pl.program_id(2) == 0),
                w_ref, y_ref, **how)


def _padded(x, size):
    if x.shape[1] == size:
        return x
    return jnp.pad(x, ((0, 0), (0, size - x.shape[1]), (0, 0)))


def _specs(rows, lanes, w_rows, blk):
    """BlockSpecs of (X-like [b, t, c], the 16 rows in front of such a
    block, W [taps (+ 1: the bias), c]) for a grid whose step (i, j, k) works on batch
    ``i``, lane block ``j`` and row block ``k``, as ``blk`` reads them
    off the grid's indices."""
    per = rows // _HALO

    def at(*g):
        i, j, k = blk(*g)
        return i, k, j

    def in_front(*g):
        i, j, k = blk(*g)
        return i, jnp.maximum(k * per - 1, 0), j

    return (pl.BlockSpec((None, rows, lanes), at),
            pl.BlockSpec((None, _HALO, lanes), in_front),
            pl.BlockSpec((w_rows, lanes), lambda *g: (0, blk(*g)[1])))


def _operands(x, w, tile, bias=None):
    """X padded behind its last row to whole blocks (zeros: they come
    after every real row) and W with the channels on the lanes, the bias
    (where the call has one) a row behind the taps."""
    rows = tile[0]
    wt = w.astype(_F32).T
    if bias is not None:
        wt = jnp.concatenate([wt, bias.astype(_F32)[None]], axis=0)
    return _padded(x, -(-x.shape[1] // rows) * rows), wt


def _act_ops(act):
    return 4 if act == "silu" else 0


def causal_conv_fwd(x, w, tile, act="silu", bias=None):
    """x [b, t, c] (bf16), w [c, taps], bias [c] or None -> y [b, t, c]
    in x's dtype: y_t = act(sum_j w[:, j] x_{t - (taps - 1) + j} + bias),
    zeros before the first position. ``tile``: ``conv_tile``'s answer
    for the call."""
    b, t, c = x.shape
    taps = w.shape[-1]
    rows, lanes = tile
    x2, wt = _operands(x, w, tile, bias)
    x_spec, front_spec, w_spec = _specs(rows, lanes, wt.shape[0],
                                        lambda i, j, k: (i, j, k))
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, act=act,
                          rows=min(rows, _PASS_ROWS),
                          bias=bias is not None),
        name="gdn.conv.fwd",
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=(b, c // lanes, x2.shape[1] // rows),
        in_specs=[x_spec, front_spec, w_spec],
        out_specs=x_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=x2.size * (2 * taps + _act_ops(act)),
            transcendentals=x2.size if act == "silu" else 0,
            bytes_accessed=2 * x2.size * x.dtype.itemsize + 4 * wt.size),
        interpret=_INTERPRET,
    )(x2, x2, wt)
    return y[:, :t]


# ---------------------------------------------------------------------------
# gdn.conv.bwd
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, front_ref, dy_ref, w_ref, dx_ref, dw_ref, behind_ref,
                *, taps, act, rows, bias):
    k = pl.program_id(2)        # the blocks of a sequence, last to first
    w = _w_rows(w_ref, taps)
    b = _bias_row(w_ref, taps, bias)
    passes = x_ref.shape[0] // rows

    @pl.when(k == 0)
    def _():
        behind_ref[...] = jnp.zeros_like(behind_ref)

    @pl.when((k == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def one(p, front, carry):
        behind, dws = carry
        at = _pass_rows(p, rows)
        xs = _shifted(front, x_ref[at, :], taps)
        dpre = dy_ref[at, :].astype(_F32)
        if act == "silu":
            pre = _taps_sum(xs, w)
            if bias:
                pre = pre + b
            sig = jax.nn.sigmoid(pre)
            dpre = dpre * (sig * (1.0 + pre * (1.0 - sig)))
        dx_ref[at, :] = _taps_back(dpre, behind, w, rows).astype(
            dx_ref.dtype)
        # (the bias's gradient, where there is one, is dpre's column
        # sum: a last accumulator beside the taps')
        return dpre[:_TAIL], tuple(
            dw + _fold(x * dpre) for dw, x in zip(dws, xs)) + tuple(
            db + _fold(dpre) for db in dws[taps:])

    def earlier(i, carry):
        p = passes - 1 - i
        return one(p, _rows_in_front(x_ref, p, rows), carry)

    zeros = jnp.zeros(behind_ref.shape, _F32)
    carry = jax.lax.fori_loop(0, passes - 1, earlier,
                              (behind_ref[...], (zeros,) * (taps + bias)))
    behind, dws = one(
        0, _block_in_front(front_ref, k == pl.num_programs(2) - 1), carry)
    behind_ref[...] = behind
    for s, dw in enumerate(dws[:taps]):
        dw_ref[taps - 1 - s] += dw
    if bias:
        dw_ref[taps] += dws[taps]


def causal_conv_bwd(x, w, dy, tile, act="silu", bias=None):
    """The cotangents (dx [b, t, c] in x's dtype, dw [c, taps] float32
    and, where the call has a bias, db [c] float32) of
    ``causal_conv_fwd`` for the cotangent ``dy`` of y, from x alone (the
    pre-activation is made again)."""
    b, t, c = x.shape
    taps = w.shape[-1]
    rows, lanes = tile
    x2, wt = _operands(x, w, tile, bias)
    w_rows = wt.shape[0]
    dy2 = _padded(dy.astype(x.dtype), x2.shape[1])
    last = x2.shape[1] // rows - 1
    x_spec, front_spec, w_spec = _specs(rows, lanes, w_rows,
                                        lambda j, i, k: (i, j, last - k))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, act=act,
                          rows=min(rows, _PASS_ROWS), bias=bias is not None),
        name="gdn.conv.bwd",
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct((w_rows, _TAIL, c), _F32)),
        grid=(c // lanes, b, last + 1),
        in_specs=[x_spec, front_spec, x_spec, w_spec],
        out_specs=(x_spec, pl.BlockSpec((w_rows, _TAIL, lanes),
                                        lambda j, i, k: (0, 0, j))),
        scratch_shapes=[pltpu.VMEM((_TAIL, lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=x2.size * (6 * taps + 2 * _act_ops(act)),
            transcendentals=x2.size if act == "silu" else 0,
            bytes_accessed=3 * x2.size * x.dtype.itemsize + 8 * wt.size),
        interpret=_INTERPRET,
    )(x2, x2, dy2, wt)
    dw = jnp.sum(dw, axis=1)
    if bias is None:
        return dx[:, :t], dw.T
    return dx[:, :t], dw[:taps].T, dw[taps]


# ---------------------------------------------------------------------------
# sconv.gated.fwd / sconv.gated.bwd: y = C * taps(B * u) of [B | C | u]
# ---------------------------------------------------------------------------


def _gate(a, b):
    return a.astype(_F32) * b.astype(_F32)


def _gated_block_in_front(b_front_ref, u_front_ref, first):
    """v = B * u on the 16 rows in front of a block (zeros for a
    sequence's first), float32."""
    return _gate(_block_in_front(b_front_ref, first),
                 _block_in_front(u_front_ref, first))


def _gated_rows_in_front(b_ref, u_ref, p, rows):
    """... on the 16 rows of the block in front of pass ``p`` > 0."""
    return _gate(_rows_in_front(b_ref, p, rows),
                 _rows_in_front(u_ref, p, rows))


def _range_specs(rows, lanes, w_rows, c, blk):
    """(the block of B, of C and of u; the 16 rows in front of B's and
    of u's; W): ``_specs`` three times over the ONE operand [b, t, 3c],
    range r's lane blocks ``c // lanes`` blocks behind range r - 1's."""
    per = c // lanes

    def of_range(r):
        def blk_r(*g):
            i, j, k = blk(*g)
            return i, j + r * per, k
        return _specs(rows, lanes, w_rows, blk_r)

    (b_spec, b_front, w_spec), (c_spec, _, _), (u_spec, u_front, _) = (
        of_range(r) for r in range(3))
    return b_spec, c_spec, u_spec, b_front, u_front, w_spec


def _gated_fwd_kernel(b_ref, c_ref, u_ref, b_front_ref, u_front_ref, w_ref,
                      y_ref, *, taps, rows):
    first = pl.program_id(2) == 0
    w = _w_rows(w_ref, taps)

    def one(p, front):
        at = _pass_rows(p, rows)
        conv = _taps_sum(
            _shifted(front, _gate(b_ref[at, :], u_ref[at, :]), taps), w)
        y_ref[at, :] = (c_ref[at, :].astype(_F32) * conv).astype(y_ref.dtype)

    one(0, _gated_block_in_front(b_front_ref, u_front_ref, first))

    def later(p, carry):
        one(p, _gated_rows_in_front(b_ref, u_ref, p, rows))
        return carry

    jax.lax.fori_loop(1, b_ref.shape[0] // rows, later, None)


def gated_conv_fwd(x, w, tile):
    """x [b, t, 3c] (bf16: the fused projection [B | C | u]), w [c,
    taps] -> y [b, t, c] in x's dtype: y_t = C_t * sum_j w[:, j] v_{t -
    (taps - 1) + j}, v = B * u, zeros before the first position, no
    activation. ``tile``: ``conv_tile(t, c, taps, dtype, gated=True)``'s
    answer for the call."""
    b, t, c3 = x.shape
    c, taps = c3 // 3, w.shape[-1]
    rows, lanes = tile
    x2, wt = _operands(x, w, tile)
    b_spec, c_spec, u_spec, b_front, u_front, w_spec = _range_specs(
        rows, lanes, taps, c, lambda i, j, k: (i, j, k))
    size = b * x2.shape[1] * c
    y = pl.pallas_call(
        functools.partial(_gated_fwd_kernel, taps=taps,
                          rows=min(rows, _PASS_ROWS)),
        name="sconv.gated.fwd",
        out_shape=jax.ShapeDtypeStruct((b, x2.shape[1], c), x.dtype),
        grid=(b, c // lanes, x2.shape[1] // rows),
        in_specs=[b_spec, c_spec, u_spec, b_front, u_front, w_spec],
        out_specs=b_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=size * (2 * taps + 2), transcendentals=0,
            bytes_accessed=4 * size * x.dtype.itemsize + 4 * wt.size),
        interpret=_INTERPRET,
    )(x2, x2, x2, x2, x2, wt)
    return y[:, :t]


def _gated_bwd_kernel(b_ref, c_ref, u_ref, b_front_ref, u_front_ref, dy_ref,
                      w_ref, dx_ref, dw_ref, behind_ref, wait_ref,
                      *, taps, rows):
    k = pl.program_id(2)        # the blocks of a sequence, last to first
    r = pl.program_id(3)        # the range of dX this step writes
    passes = b_ref.shape[0] // rows
    # (read out here: the interpreter has no grid inside a branch)
    first_walked = k == 0
    first_of_lanes = first_walked & (pl.program_id(1) == 0)
    first = k == pl.num_programs(2) - 1

    @pl.when(r == 0)
    def _():
        w = _w_rows(w_ref, taps)

        @pl.when(first_walked)
        def _():
            behind_ref[...] = jnp.zeros_like(behind_ref)

        @pl.when(first_of_lanes)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        def one(p, front, carry):
            behind, dws = carry
            at = _pass_rows(p, rows)
            gate, u = b_ref[at, :].astype(_F32), u_ref[at, :].astype(_F32)
            vs = _shifted(front, gate * u, taps)
            dy = dy_ref[at, :].astype(_F32)
            # dC = dy * c, c = taps(v) made again
            wait_ref[0, at, :] = (dy * _taps_sum(vs, w)).astype(
                wait_ref.dtype)
            dc = dy * c_ref[at, :].astype(_F32)
            dv = _taps_back(dc, behind, w, rows)
            dx_ref[at, :] = (dv * u).astype(dx_ref.dtype)           # dB
            wait_ref[1, at, :] = (dv * gate).astype(wait_ref.dtype)  # du
            return dc[:_TAIL], tuple(
                dw + _fold(v * dc) for dw, v in zip(dws, vs))

        def earlier(i, carry):
            p = passes - 1 - i
            return one(p, _gated_rows_in_front(b_ref, u_ref, p, rows), carry)

        zeros = jnp.zeros(behind_ref.shape, _F32)
        carry = jax.lax.fori_loop(0, passes - 1, earlier,
                                  (behind_ref[...], (zeros,) * taps))
        behind, dws = one(
            0, _gated_block_in_front(b_front_ref, u_front_ref, first), carry)
        behind_ref[...] = behind
        for s, dw in enumerate(dws):
            dw_ref[taps - 1 - s] += dw

    for waiting in (0, 1):
        @pl.when(r == waiting + 1)
        def _(waiting=waiting):
            dx_ref[...] = wait_ref[waiting]


def gated_conv_bwd(x, w, dy, tile):
    """The cotangents (dx [b, t, 3c] = [dB | dC | du] in x's dtype, dw
    [c, taps] float32) of ``gated_conv_fwd`` for the cotangent ``dy`` of
    y, from x alone: v = B * u and c = taps(v) are made again in VMEM."""
    b, t, c3 = x.shape
    c, taps = c3 // 3, w.shape[-1]
    rows, lanes = tile
    x2, wt = _operands(x, w, tile)
    dy2 = _padded(dy.astype(x.dtype), x2.shape[1])
    last = x2.shape[1] // rows - 1
    per = c // lanes
    b_spec, c_spec, u_spec, b_front, u_front, w_spec = _range_specs(
        rows, lanes, taps, c, lambda j, i, k, r: (i, j, last - k))
    size = b * x2.shape[1] * c
    dx, dw = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, taps=taps,
                          rows=min(rows, _PASS_ROWS)),
        name="sconv.gated.bwd",
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct((taps, _TAIL, c), _F32)),
        grid=(per, b, last + 1, 3),
        in_specs=[b_spec, c_spec, u_spec, b_front, u_front, b_spec, w_spec],
        out_specs=(pl.BlockSpec((None, rows, lanes),
                                lambda j, i, k, r: (i, last - k, j + r * per)),
                   pl.BlockSpec((taps, _TAIL, lanes),
                                lambda j, i, k, r: (0, 0, j))),
        scratch_shapes=[pltpu.VMEM((_TAIL, lanes), _F32),
                        pltpu.VMEM((2, rows, lanes), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=size * (6 * taps + 6), transcendentals=0,
            bytes_accessed=7 * size * x.dtype.itemsize + 8 * wt.size),
        interpret=_INTERPRET,
    )(x2, x2, x2, x2, x2, dy2, wt)
    return dx[:, :t], jnp.sum(dw, axis=1).T
