"""Pallas TPU grouped matmuls: the experts of a dropless top-k MoE layer.

``grouped_matmul(lhs [m, k], rhs [E, k, n], group_sizes [E]) -> [m, n]``
is ``jax.lax.ragged_dot``: the rows of ``lhs`` are sorted by expert,
``group_sizes`` says how many each expert got (they sum to m: dropless,
so there is no row past the last group), and row i is multiplied by the
matrix of its expert. Every row is computed; nothing is padded, capped
or dropped. A caller that holds only some of the experts its router
scores (``live_rows``: an expert-parallel share, ops/moe_ops.py) passes
group sizes that sum to LESS than m: the rows behind the last group
belong to no expert here, no visit reaches their tiles, and the result
has zeros there, as ``ragged_dot`` has (the kernel writes its tiles INTO
zeros, ``gmm(zero_behind=True)``: the default, for any caller). A held
layer's own ops ask for less and fill nothing: ``zero_behind="tile"``
zeroes the rows behind the last group in the ONE row tile it ends in
and leaves every tile behind as the memory was (finite to the end of
the row tile the last live row lies in, not defined behind it: the
contract of every buffer such a layer hands on), ``zero_behind=False``
not even that, for a result read by windows of live rows alone.
Operands keep their dtype (bf16 under AMP), a product is
accumulated in float32 inside the kernel and returned in the operands'
dtype, as ``ragged_dot`` returns it.

Three kernels, the program's own in place of libtpu's expansion of
``ragged_dot`` (a ``ragged-dot-none`` Mosaic call whose tile no caller
sees or sets, at 45% of the v5e's FLOP roofline at OLMoE's widths):

- ``moe.gmm.fwd``: ``gmm``, the product above.
- ``moe.gmm.bwd_dx``: the same kernel for the rows' gradient, g [m, n]
  against the SAME [E, k, n] weights read transposed by the index map
  and the matmul's dimension numbers, not by a copy.
- ``moe.tgmm.bwd_dw``: ``tgmm(lhs [m, k], g [m, n]) -> [E, k, n]``, the
  matrix's gradient lhs_e^T g_e over each expert's rows, zeros for an
  expert that got none. Where the gradient goes to the matrix's Adam
  step and nowhere else (``grouped_matmul_grads(adam=)``) the same body
  is ``moe.tgmm.bwd_dw_adam``, ``tgmm_adam``: the step is taken on the
  float32 accumulator and the gradient is no array at all.

A grid step works on one VISIT: one tile of ``tm`` rows and one expert
with rows in it (``_visits``; the bookkeeping of
``jax.experimental.pallas.ops.tpu.megablox``, from scalar prefetch). A
tile that straddles a group boundary is visited once for each group in
it, and the rows of the other groups are masked: in ``gmm`` on the
write (the block stays in VMEM across consecutive visits of one tile),
in ``tgmm`` on the read. Consecutive visits of one expert repeat the
weight block's index, and Pallas copies only a block whose index
changed: with the whole contraction resident (``tk`` = k) a weight
block is fetched once a group and n tile, not once a row tile.

``gmm_tile`` is the one function that says tile or ``ragged_dot``, from
the shapes, the dtype and the backend; ``pt_moe_gmm_dispatch_total``
records its answer per lowered call, so a fallback is never silent.
XLA cannot CSE custom calls: a caller that differentiates by re-running
its forward (the generic ``*_grad``) would execute these kernels twice,
so ``ops/moe_ops.moe_experts`` saves what its backward needs.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu import monitor as _monitor

# Test hook, as flash_attention._INTERPRET: run the kernels in
# interpreter mode on the CPU so the suite reaches them.
_INTERPRET = False

# What a call's blocks may take of the v5e's 128 MiB of VMEM (Mosaic's
# default scoped limit is 16 MiB: the calls below raise it to what their
# blocks need, _vmem_bytes, and a tile over this cap is not chosen).
_VMEM_CAP_BYTES = 48 * 2**20
# Row tiles, and the rate of a visit at each, per row, against one of 512
# rows: on a v5e at OLMoE's widths with an expert's whole matrix
# resident a visit cost 22.9 / 24.1 / 27.7 ns a row at 512 / 256 / 128
# rows over the groups a router really gives (my chip run, PR 31: a
# weight tile latched in the MXU serves fewer rows at the smaller ones).
_ROW_TILE_RATE = {512: 1.0, 256: 0.95, 128: 0.83}
_WIDTH_TILES = (2048, 1024, 512, 256, 128)

_M_DISPATCH = _monitor.counter(
    "pt_moe_gmm_dispatch_total",
    "grouped matmuls of a top-k MoE layer lowered, by pass (fwd, bwd_dx, "
    "bwd_dw; bwd_dw_adam: the matrix's gradient with its Adam step "
    "inside the kernel, where bwd_dw with a tile wrote the gradient to "
    "HBM), shape (m rows, k x n an expert, E experts: the forward "
    "product's) and tile (rows, contraction and width of one grid step "
    "of the pass's moe.* Pallas kernel; empty where the call ran as "
    "jax.lax.ragged_dot)")


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _vmem_bytes(tm, tk, tn, itemsize):
    """What one grid step of the largest of the three kernels keeps in
    VMEM at this tile: the three blocks double-buffered, and a float32
    accumulator or product of the widest block beside them twice (the
    value and the store's temporary)."""
    blocks = tm * tk + tk * tn + tm * tn
    return 2 * blocks * itemsize + 8 * max(tm * tn, tk * tn)


def _vmem_limit(tm, tk, tn, itemsize):
    """Mosaic's scoped limit for a call at this tile: what the blocks
    need and half as much again, not under its default of 16 MiB."""
    return max(16 * 2**20, _vmem_bytes(tm, tk, tn, itemsize) * 3 // 2)


def gmm_tile(m, k, n, e, dtype, backend=None, on_mesh=None, live_rows=None):
    """-> (tm, tk, tn), the rows, contraction and width of one grid step
    for the product ``[m, k] x [e, k, n]``, or None where the call runs
    as ``jax.lax.ragged_dot``: no TPU backend (``backend``: None for
    this process's, with the interpreter counting as one), operands
    that are not bf16, a program under a mesh (a Mosaic call is not
    auto-partitioned, and no expert-parallel path calls this yet), a k
    or n that is no whole number of half lane tiles (64), no tile of
    ``_width_tiles`` that the VMEM cap admits, no row tile that divides
    m, or fewer rows an
    expert than the smallest tile holds (every tile would be visited by
    several experts and most of each visit masked: serving a few rows a
    step is bound by the weights' bytes and wants another kernel; not
    measured below 1024 rows an expert).

    The tile follows the shape and the VMEM the blocks need, not a flag.
    Rows: a call makes up to m / tm + e - 1 visits (a tile is visited
    once for each group in it) and a visit costs tm rows at that tile's
    rate, so the tm with the least (1 + (e - 1) tm / m) / rate: 256 at
    1024 rows an expert, 512 from about 4600, 128 under about 750.
    Widths: the contraction whole where the cap admits, then the widest
    n (a split contraction costs an accumulator pass a step; with both
    whole an expert's matrix is fetched once a group). The matrix's
    gradient runs at the same tile; the rows' gradient asks for its own
    product, ``gmm_tile(m, n, k, ...)``.

    ``live_rows``: the rows the caller expects inside groups, where the
    group sizes sum to less than m (a share of the experts: the buffer
    holds every pair the router made, the groups only the held ones').
    The visits, and so the row tile, go with the live rows, not with
    the buffer: 5,120 live rows of 81,920 over 32 experts take tm 128."""
    tm = row_tile(m, e, dtype, backend, on_mesh, live_rows)
    if tm is None or k % 64 or n % 64:
        return None
    for tk in _width_tiles(k, True):
        for tn in _width_tiles(n, False):
            if _vmem_bytes(tm, tk, tn, 2) <= _VMEM_CAP_BYTES:
                return tm, tk, tn
    return None


def row_tile(m, e, dtype, backend=None, on_mesh=None, live_rows=None):
    """``gmm_tile``'s tm alone, which no width decides: the row tile of
    every grouped matmul over m rows in e groups (of which
    ``live_rows`` are expected inside groups), or None where none runs
    as a kernel whatever its widths. What a caller that leaves rows
    unwritten (``unfilled``) has to cover: a kernel reads its lhs a
    whole row tile at a time."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    live = m if live_rows is None else max(1, min(int(live_rows), m))
    rows = [t for t in _ROW_TILE_RATE if m % t == 0]
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or not rows or live // e < min(rows)):
        return None
    return min(rows,
               key=lambda t: (1 + (e - 1) * t / live) / _ROW_TILE_RATE[t])


def _adam_vmem_bytes(tm, tk, tn, itemsize):
    """What a grid step of ``tgmm_adam`` keeps in VMEM: the two row
    blocks double-buffered, three buffers of the weight's and its
    moments' float32 [tk, tn] block (one coming in, one stepped in
    place, one going out: ``_MatrixState``), the accumulator and the
    product that is added to it."""
    return 2 * (tm * tk + tm * tn) * itemsize + 4 * (9 + 1 + 1) * tk * tn


def adam_tile(tile, k, n, e, rows):
    """-> (tm, tk, tn) for ``tgmm_adam`` where the weight-gradient call
    ``[rows, k]^T x [rows, n]`` over e experts has ``tile`` (``rows``:
    those expected inside groups), or None where the call runs as
    ``tgmm`` with the update behind it. The row tile is the call's. The
    [tk, tn] tile of the matrix is not: three buffers of its weight and
    moments ride beside the accumulator, so it is the pair of
    ``_width_tiles`` under the VMEM cap that reads the rows least often
    (lhs once a width tile, g once a contraction tile), the larger tile
    among equals: 1024 x 1024 at OLMoE's 2048 x 1024 (6.49 ms a call,
    6.95 at 1024 x 512, 7.35 at 512 x 512, 8.34 for the two passes; my
    chip run, PR 62), the matrix whole at a held share's 2048 x 512.

    None: where no tile fits; for a width off the 128 lanes (1856: the
    kernel copies the matrix's tiles itself, and Mosaic slices no memory
    reference off the lane tiling; a contraction off them is taken
    whole, as ever: it is the tile's sublanes); and where the form moves
    MORE bytes than the two passes: it saves the gradient's write and
    read, 4 bytes a parameter, and reads the rows again for every
    further tile of the matrix. SmallThinker's share (12,288 rows over 8
    experts of 2560 x 768, no tile of which is a whole side) would save
    63 MB and read 75 MB more: 1.74 ms a call for the two passes' 1.70,
    and its cell 0.45% slower in both pairs (my chip run, PR 62)."""
    tm = tile[0]
    fits = [(tk, tn) for tk in _width_tiles(k, True)
            for tn in ([] if n % 128 else _width_tiles(n, False))
            if _adam_vmem_bytes(tm, tk, tn, 2) <= _VMEM_CAP_BYTES]
    if not fits:
        return None

    def passes(t):      # over lhs's k columns and g's n, in columns read
        return k * (n // t[1]) + n * (k // t[0])

    tk, tn = min(fits, key=lambda t: (passes(t), -t[0] * t[1]))
    if 2 * rows * (passes((tk, tn)) - k - n) >= 4 * e * k * n:
        return None
    return tm, tk, tn


def _width_tiles(size, contraction):
    """The tiles ``gmm_tile`` tries for a contraction or a width of
    ``size``, widest first: the whole of it, then the powers of two that
    divide it. A size that is an odd number of lane tiles (2688 = 21 x
    128: no power of two above 128 divides it) is also tried at the
    other multiples of 128 that divide it (896, 384), where it had the
    whole and 128 alone. A size off the 128 lanes (1856 = 14.5 x 128):
    as a CONTRACTION it is taken whole or not at all (a block as wide as
    the array, its last 64 lanes masked in VMEM by Mosaic: exact against
    ``ragged_dot`` on the chip); as a WIDTH it is tiled as the next whole
    number of lane tiles would be (1920: 1920, 640, 384, 128) and the
    last block hangs over the array's edge, where what it reads only
    makes columns that are never written. (The width whole, 1856 lanes
    of accumulator, ran at a quarter of the speed: 3.4 ms for 0.9 at
    24,576 rows of which 1536 live; my chip run, PR 45.)"""
    if size % 128 and contraction:
        return [size]
    lanes = -(-size // 128)
    tiles = [t for t in (lanes * 128,) + _WIDTH_TILES
             if t <= lanes * 128 and lanes * 128 % t == 0]
    if lanes % 2:
        tiles += [128 * f for f in range(3, lanes, 2) if lanes % f == 0]
    return sorted(set(tiles), reverse=True)


def _lane_padded(n):
    """n, or for an n off the 128 lanes the next whole number of them."""
    return -(-n // 128) * 128


def tile_label(tile) -> str:
    """A tile as the dispatch counter's ``tile`` label has it:
    "tm512 tk2048 tn1024" ("" for None)."""
    return "tm%d tk%d tn%d" % tile if tile else ""


def _note_dispatch(direction, m, k, n, e, tile):
    # off with telemetry; build-time shape inference is not a lowering
    from paddle_tpu.core import interp

    if not _monitor.enabled() or not interp.lowering_active():
        return
    _M_DISPATCH.inc(labels={
        "pass": direction, "shape": f"m{m} k{k} n{n} e{e}",
        "tile": tile_label(tile)})


def gmm_dispatch_counts():
    """{"pass shape[ [tile]]": calls lowered so far}: the grouped
    matmuls' dispatch counter as chip_smoke.py prints it, the twin of
    ``attention_ops.dispatch_counts(tiles=True)``."""
    out = {}
    for row in _monitor.snapshot()[_M_DISPATCH.name]["values"]:
        lb = row["labels"]
        name = f"{lb.get('pass', '?')} {lb.get('shape', '?')}"
        if lb.get("tile"):
            name += f" [{lb['tile']}]"
        out[name] = out.get(name, 0) + int(row["value"])
    return out


# ---------------------------------------------------------------------------
# which (row tile, expert) pairs a call visits
# ---------------------------------------------------------------------------


def _visits(group_sizes, m, tm, visit_empty):
    """-> (offsets [E+1], gids [V], tids [V], nvis [1]), int32, for the
    scalar prefetch: group g holds rows offsets[g]..offsets[g+1]; visit
    v < nvis works on row tile tids[v] for expert gids[v], visits in the
    order of the experts and, inside one, of the tiles, so that the
    visits of one tile and those of one expert are consecutive. V =
    m / tm + E - 1 is the most there can be (every boundary inside a
    tile adds one); the visits past nvis repeat the last one and compute
    nothing. Group sizes that sum to less than m make fewer visits: no
    tile behind the last group's is ever one's. ``visit_empty``: an expert without rows still gets one
    visit (``tgmm`` has its zeros to write)."""
    e = group_sizes.shape[0]
    tiles_m = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if visit_empty else 0).astype(jnp.int32)
    vis_end = jnp.cumsum(count, dtype=jnp.int32)
    nvis = vis_end[-1:]
    v = jnp.minimum(jnp.arange(tiles_m + e - 1, dtype=jnp.int32),
                    nvis - 1)
    gids = jnp.minimum(
        jnp.sum(vis_end[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        e - 1)
    tids = jnp.clip(first[gids] + v - (vis_end - count)[gids],
                    0, tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, gids, tids.astype(jnp.int32), nvis


def _rows_of(offs_ref, gids_ref, tids_ref, v, tm):
    """(first row of the visit's tile, the visit's group's start and
    end)."""
    g = gids_ref[v]
    return tids_ref[v] * tm, offs_ref[g], offs_ref[g + 1]


def _row_mask(row0, start, end, tm):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return jnp.logical_and(rows >= start, rows < end)


# ---------------------------------------------------------------------------
# gmm: [m, k] x [E, k, n] (or its transpose [E, n, k]) -> [m, n]
# ---------------------------------------------------------------------------


def _gmm_kernel(offs_ref, gids_ref, tids_ref, nvis_ref, lhs_ref, rhs_ref,
                *refs, tm, tiles_k, transpose_rhs, aliased):
    # (aliased: the zeros the result is written into ride in front of it)
    out_ref, *scratch = refs[aliased:]
    v, kk = pl.program_id(1), pl.program_id(2)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def store(acc):
        row0, start, end = _rows_of(offs_ref, gids_ref, tids_ref, v, tm)
        whole = jnp.logical_and(start <= row0, end >= row0 + tm)

        @pl.when(whole)
        def _():
            out_ref[...] = acc.astype(out_ref.dtype)

        # the other groups' rows of a straddling tile keep what the
        # block holds: what their visit wrote or, before it, anything
        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[...] = jnp.where(
                _row_mask(row0, start, end, tm), acc,
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    @pl.when(v < nvis_ref[0])
    def _():
        part = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        if tiles_k == 1:
            store(part)
            return
        acc_ref, = scratch

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = part

        @pl.when(kk > 0)
        def _():
            acc_ref[...] += part

        @pl.when(kk == tiles_k - 1)
        def _():
            store(acc_ref[...])


def gmm(lhs, rhs, group_sizes, tile, *, transpose_rhs=False,
        name="moe.gmm.fwd", zero_behind=False):
    """``lhs [m, k]`` x ``rhs [E, k, n]`` -> [m, n] over the row groups;
    ``transpose_rhs``: rhs is [E, n, k] and is read transposed. ``tile``
    (tm, tk, tn) are the rows, the contraction and the result's width of
    one grid step: tm divides m and tk k; tn divides n or, for an n off
    the 128 lanes, the next whole number of lane tiles (the last block
    hangs over the edge: ``_width_tiles``). ``zero_behind``, for
    group sizes that sum to less than m: False, the result is what the
    kernel left in memory nothing filled, not defined behind the last
    group; "tile", the one tile the last group ends in (or, with no row
    in any group, the tile an idle grid still writes back) has its rows
    behind the last group zeroed afterwards: finite to the end of the
    row tile the last live row lies in, not defined behind it (a held
    share's contract, ops/moe_ops.py: one [tm, n] window written, no
    fill); True, the result is besides an array of zeros that the
    call's tiles are written into (it rides in as an operand the result
    aliases and no step reads), so the tiles no visit reaches hold
    zeros too: ``ragged_dot``'s result, at a fill of the whole [m, n]."""
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tile
    assert m % tm == 0 and k % tk == 0 and _lane_padded(n) % tn == 0, (
        lhs.shape, rhs.shape, tile)
    tiles_k = k // tk
    meta = _visits(group_sizes, m, tm, visit_empty=False)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, tk), lambda j, v, kk, o, g, t, nv: (g[v], j, kk))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, v, kk, o, g, t, nv: (g[v], kk, j))
    item = jnp.dtype(lhs.dtype).itemsize
    assert zero_behind in (False, True, "tile"), zero_behind
    zeros = ([jnp.zeros((m, n), lhs.dtype)] if zero_behind is True else [])
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs,
                          aliased=len(zeros)),
        name=name,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(-(-n // tn), m // tm + e - 1, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, v, kk, o, g, t, nv: (t[v], kk)),
                rhs_spec,
            ] + [pl.BlockSpec(memory_space=pl.ANY)] * len(zeros),
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, kk, o, g, t, nv: (t[v], j)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else []),
        ),
        input_output_aliases={6: 0} if zeros else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, tk, tn, item)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=item * (m * k * -(-n // tn) + e * k * n + m * n)),
        interpret=_INTERPRET,
    )(*meta, lhs, rhs, *zeros)
    if not zero_behind:
        return out
    live = meta[0][-1]
    r0 = jnp.minimum(live // tm * tm, m - tm)
    rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return put_rows(out, r0, jnp.where(rows < live, rows_at(out, r0, tm), 0))


# ---------------------------------------------------------------------------
# tgmm: [m, k]^T x [m, n] over each group's rows -> [E, k, n]
# ---------------------------------------------------------------------------


def _tgmm_kernel(offs_ref, gids_ref, tids_ref, nvis_ref, lhs_ref, g_ref,
                 *refs, tm, adam=None):
    # refs: the result's block and the accumulator; with ``adam`` (an
    # AdamStep's attributes) what _MatrixState takes, the accumulator
    # among it
    v, last_v = pl.program_id(2), pl.num_programs(2) - 1
    group = gids_ref[v]
    first = jnp.logical_or(
        v == 0, gids_ref[jnp.maximum(v - 1, 0)] != group)
    last = jnp.logical_or(
        v == last_v, gids_ref[jnp.minimum(v + 1, last_v)] != group)
    row0, start, end = _rows_of(offs_ref, gids_ref, tids_ref, v, tm)
    live = jnp.logical_and(v < nvis_ref[0], end > start)
    whole = jnp.logical_and(start <= row0, end >= row0 + tm)
    dims = (((0,), (0,)), ((), ()))
    if adam is None:
        out_ref, acc_ref = refs
    else:
        state = _MatrixState(group, *refs)
        acc_ref = state.acc

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if adam is not None:
            state.fetch_ahead()

    @pl.when(jnp.logical_and(live, whole))
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], g_ref[...], dims,
            preferred_element_type=jnp.float32)

    # a straddling tile: the other groups' rows of g count as zeros
    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _():
        g = jnp.where(_row_mask(row0, start, end, tm), g_ref[...],
                      jnp.zeros_like(g_ref))
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], g, dims, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        if adam is None:
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)
        else:
            state.step(**adam)


class _MatrixState:
    """The weight and its two moments inside ``tgmm_adam``: [E, k, n]
    float32 in HBM, moved by the kernel's own copies and not by the
    grid's pipeline, which would bring a block in during the one grid
    step before its first use. A BLOCK is one group's [tk, tn] tile of
    the three; the grid walks the blocks in one order (width tile, then
    contraction tile, then group: every group is visited, ``_visits``)
    and spends a group's row tiles on each. While block s is
    contracted, block s + 1 comes in (started at s's first row tile,
    awaited at s + 1's last) and block s - 1, stepped in place, goes out
    (started behind its step, awaited at s + 1's first row tile, whose
    fetch takes its buffer): three buffers, block s in buffer s % 3. The
    copies are started at the low priority: at the priority of the
    pipeline's copies of the row blocks a 6 MB block queues in front of
    the next grid step's rows and the step waits for all of it (8.9 ms a
    call for 7.0 at OLMoE's gate matrix; my chip run, PR 62). The
    results alias the operands, and a block is read before it is
    written and touched by no other."""

    def __init__(self, group, scal_ref, *refs):
        *hbm, self.acc, self.buf, self.in_sem, self.out_sem = refs
        self.scal, self.src, self.dst = scal_ref, hbm[:3], hbm[3:]
        self.tk, self.tn = self.acc.shape
        j, i = pl.program_id(0), pl.program_id(1)
        self.tiles_k, self.e = pl.num_programs(1), self.src[0].shape[0]
        self.s = (j * self.tiles_k + i) * self.e + group
        self.blocks = pl.num_programs(0) * self.tiles_k * self.e

    def _copies(self, s, inward):
        """The three copies of block ``s``, in or out of buffer s % 3."""
        ji = s // self.e
        at = (s % self.e, pl.ds(ji % self.tiles_k * self.tk, self.tk),
              pl.ds(ji // self.tiles_k * self.tn, self.tn))
        slot = s % 3
        if inward:
            return [pltpu.make_async_copy(
                ref.at[at], self.buf.at[slot, a], self.in_sem.at[slot, a])
                for a, ref in enumerate(self.src)]
        return [pltpu.make_async_copy(
            self.buf.at[slot, a], ref.at[at], self.out_sem.at[slot, a])
            for a, ref in enumerate(self.dst)]

    def fetch_ahead(self):
        """At a block's first row tile: the next block starts coming in
        (and, at the grid's first step, this one), into the buffer that
        block s - 2 has left by now."""
        s = self.s

        @pl.when(s == 0)
        def _():
            for copy in self._copies(s, True):
                copy.start(priority=1)

        @pl.when(s >= 2)
        def _():
            for copy in self._copies(s - 2, False):
                copy.wait()

        @pl.when(s + 1 < self.blocks)
        def _():
            for copy in self._copies(s + 1, True):
                copy.start(priority=1)

    def step(self, *, beta1, beta2, epsilon, decay):
        """At a block's last row tile: the accumulator is the block's
        gradient, float32 as accumulated; Adam's step on it in place
        (``ops/optimizer_ops.adam_step``, the ops' own expressions), a
        strip of rows at a time so that a strip's chain of values stays
        in registers; the block starts going out. ``scal`` (SMEM): the
        learning rate with the bias correction in it, and AdamW's
        (``decay``) learning rate times its decay."""
        from paddle_tpu.ops.optimizer_ops import adam_step

        s, slot = self.s, self.s % 3
        for copy in self._copies(s, True):
            copy.wait()
        rows = 8
        while rows * 2 * self.tn <= 8192 and self.tk % (rows * 2) == 0:
            rows *= 2
        lr_t, lr_decay = self.scal[0], self.scal[1] if decay else None

        def strip(r, carry):
            at = (pl.ds(pl.multiple_of(r * rows, rows), rows), slice(None))
            after = adam_step(
                self.buf[(slot, 0, *at)], self.acc[at],
                self.buf[(slot, 1, *at)], self.buf[(slot, 2, *at)], lr_t,
                beta1, beta2, epsilon, lr_decay)
            for a, value in enumerate(after):
                self.buf[(slot, a, *at)] = value
            return carry

        jax.lax.fori_loop(0, self.tk // rows, strip, None)
        for copy in self._copies(s, False):
            copy.start(priority=1)

        @pl.when(s == self.blocks - 1)      # the grid's last step
        def _():
            for back in range(2):
                @pl.when(s >= back)
                def _():
                    for copy in self._copies(s - back, False):
                        copy.wait()


def tgmm(lhs, g, group_sizes, tile, *, name="moe.tgmm.bwd_dw"):
    """``lhs [m, k]``, ``g [m, n]`` -> [E, k, n]: lhs_e^T g_e over the
    rows of each group e, exact zeros for a group without rows. ``tile``
    (tm, tk, tn): tm rows are contracted a grid step into a float32
    accumulator [tk, tn] that is held across a group's row tiles."""
    return _tgmm_call(lhs, g, group_sizes, tile, name, None)


def tgmm_adam(lhs, g, group_sizes, tile, adam, *,
              name="moe.tgmm.bwd_dw_adam"):
    """``tgmm`` whose result never leaves the kernel: at a group's last
    row tile the float32 accumulator IS the gradient of that [tk, tn]
    tile of the expert's matrix, and the Adam step ``adam`` (an
    ``AdamStep``) is taken on it there (``_MatrixState.step``). Its
    state, the float32 weight and its two moments [E, k, n], stays in
    HBM, is copied by the kernel a tile at a time beside the matmuls of
    a group's row tiles and written back through three results that
    alias it (no second buffer a tensor). -> (weight, moment1, moment2)
    after the step. A group without rows is visited once all the same:
    its gradient is zero and its moments decay. ``tile`` from
    ``adam_tile``: tn divides n."""
    return _tgmm_call(lhs, g, group_sizes, tile, name, adam)


def _tgmm_call(lhs, g, group_sizes, tile, name, adam):
    m, k = lhs.shape
    n = g.shape[1]
    e = group_sizes.shape[0]
    tm, tk, tn = tile
    assert m % tm == 0 and k % tk == 0 and _lane_padded(n) % tn == 0, (
        lhs.shape, g.shape, tile)
    meta = _visits(group_sizes, m, tm, visit_empty=True)
    item = jnp.dtype(lhs.dtype).itemsize
    scratch = [pltpu.VMEM((tk, tn), jnp.float32)]
    if adam is None:
        state, state_specs, kernel, aliases = [], [], None, {}
        out_shape = jax.ShapeDtypeStruct((e, k, n), lhs.dtype)
        out_specs = pl.BlockSpec(
            (None, tk, tn), lambda j, i, v, o, gi, t, nv: (gi[v], i, j))
        order = ("parallel", "parallel", "arbitrary")
        vmem, moved = _vmem_limit(tm, tk, tn, item), item * e * k * n
    else:
        decay = adam.lr_decay is not None
        # (SMEM) the learning rates, with the bias correction and times
        # AdamW's decay
        state = [jnp.stack([jnp.asarray(x, jnp.float32) for x in (
            adam.lr_t, adam.lr_decay if decay else 0.0)]), *adam.state]
        kernel = dict(beta1=adam.beta1, beta2=adam.beta2,
                      epsilon=adam.epsilon, decay=decay)
        assert n % tn == 0 and all(
            x.shape == (e, k, n) and x.dtype == jnp.float32
            for x in adam.state), (tile, [x.shape for x in adam.state])
        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        state_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + [in_hbm] * 3
        out_shape = [jax.ShapeDtypeStruct((e, k, n), jnp.float32)] * 3
        out_specs = [in_hbm] * 3
        # (operands are counted from the scalar prefetch's four)
        aliases = {7: 0, 8: 1, 9: 2}
        scratch += [pltpu.VMEM((3, 3, tk, tn), jnp.float32)]
        scratch += [pltpu.SemaphoreType.DMA((3, 3))] * 2
        # one walk over the blocks, in the grid's order (_MatrixState)
        order = ("arbitrary", "arbitrary", "arbitrary")
        vmem = max(16 * 2**20, _adam_vmem_bytes(tm, tk, tn, item) * 3 // 2)
        moved = 4 * 6 * e * k * n
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, adam=kernel),
        name=name,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(-(-n // tn), k // tk, m // tm + e - 1),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, v, o, gi, t, nv: (t[v], i)),
                pl.BlockSpec((tm, tn),
                             lambda j, i, v, o, gi, t, nv: (t[v], j)),
            ] + state_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order, vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=item * (m * k * -(-n // tn) + m * n * (k // tk))
            + moved),
        interpret=_INTERPRET,
    )(*meta, lhs, g, *state)


# ---------------------------------------------------------------------------
# the entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm_vjp(lhs, rhs, group_sizes, tile, live_rows, zero_behind):
    return gmm(lhs, rhs, group_sizes, tile, zero_behind=zero_behind)


def _gmm_vjp_fwd(lhs, rhs, group_sizes, tile, live_rows, zero_behind):
    return (gmm(lhs, rhs, group_sizes, tile, zero_behind=zero_behind),
            (lhs, rhs, group_sizes))


def _gmm_vjp_bwd(_, live_rows, zero_behind, res, g):
    return (*grouped_matmul_grads(*res, g, live_rows=live_rows), None)


_gmm_vjp.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


# ---------------------------------------------------------------------------
# passes over the rows inside groups, where they are fewer than the rows
# ---------------------------------------------------------------------------


def live_window(m, live_rows):
    """W, the rows one trip of ``over_live_rows`` works on, for a
    buffer of m rows of which an even router fills ``live_rows``: the
    largest power of two that divides m, is at most half of them (at
    least 8) and at most the kernels' largest row tile. An even routing
    takes a few trips and an uneven one as many as its rows need; a
    gather or an elementwise pass costs the same a row at any window
    from 512 to 4096 rows, XLA's scatter-add twice as much at 2048 as
    at 512 (my chip run, PR 35). 512 at 81,920 rows with 5,120 expected
    and at 32,768 with 2,048. A buffer that no window of 8 rows divides
    is one window (m: a loop of a trip per row or two is no pass)."""
    w = 1
    while m % (2 * w) == 0 and 2 * w <= min(max(int(live_rows) // 2, 8),
                                            max(_ROW_TILE_RATE)):
        w *= 2
    return w if w >= 8 else m


def over_live_rows(live, w, body, init):
    """``init`` after ``body(r0, keep, carry)`` for each window of ``w``
    rows that holds a live one: r0 = 0, w, .. < ``live``, a DEVICE
    scalar (the rows inside groups: those the step's own router put on
    the held experts), so the trip count is the data's and one compiled
    loop serves every routing; ``keep`` [w, 1] is true on the window's
    rows before ``live``. A carry that is a buffer is updated in place
    (``put_rows``); what no trip reaches keeps what ``init`` held."""
    live = jnp.asarray(live, jnp.int32)

    def trip(i, carry):
        r0 = i * w
        keep = r0 + jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) < live
        return body(r0, keep, carry)

    return jax.lax.fori_loop(0, (live + w - 1) // w, trip, init)


def rows_at(x, r0, w):
    """The window ``x[r0:r0 + w]``."""
    return jax.lax.dynamic_slice_in_dim(x, r0, w, axis=0)


def put_rows(buf, r0, rows):
    """``buf`` with ``rows`` written at r0 (in place inside a loop)."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, rows.astype(buf.dtype), r0, axis=0)


def unfilled(shape, dtype):
    """An array of ``shape`` that nothing has written: the first carry
    of a loop that writes its windows in place (``over_live_rows``)
    into a held share's row buffer, whose rows behind the last window
    no reader reads. On a TPU a Pallas call whose result stays where
    XLA allocated it and whose body touches nothing (``rows.unfilled``:
    no byte moves, where ``jnp.zeros`` writes the whole buffer at the
    HBM's rate); under the interpreter hook NaN everywhere, so that the
    CPU suite proves nobody reads behind; under a mesh (a Mosaic call
    is not auto-partitioned) and anywhere else, zeros."""
    if not kernels_enabled() or _under_mesh():
        return jnp.zeros(shape, dtype)
    if _INTERPRET:
        return jnp.full(shape, jnp.nan, dtype)
    return pl.pallas_call(
        lambda out_ref: None, name="rows.unfilled",
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY))()


def _call_tiles(lhs, rhs, live_rows=None):
    """((m, k, n, e), the call's tile, the tile of its rows' gradient):
    both tiles, or neither."""
    (m, k), (e, _, n) = lhs.shape, rhs.shape
    tile = dx_tile = None
    if rhs.dtype == lhs.dtype:
        tile = gmm_tile(m, k, n, e, lhs.dtype, live_rows=live_rows)
        dx_tile = gmm_tile(m, n, k, e, lhs.dtype, live_rows=live_rows)
    if tile is None or dx_tile is None:
        tile = dx_tile = None
    return (m, k, n, e), tile, dx_tile


def grouped_matmul(lhs, rhs, group_sizes, live_rows=None, zero_behind=True):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)``, differentiable in
    lhs and rhs: through the kernels above at the tile ``gmm_tile``
    gives the call, else ``ragged_dot`` itself. ``live_rows`` (a
    number, not traced): the group sizes may sum to less than the rows,
    about that many are expected inside groups, and the rows behind the
    last group come back as zeros; None: they sum to all of them.
    ``zero_behind="tile"``, for a caller whose readers are the kernels
    and windows of live rows (a held share, ops/moe_ops.py): zeros to
    the end of the row tile the last group ends in, NOT DEFINED behind
    it (a kernel leaves what the buffer held; ``ragged_dot``, where the
    call has no tile, what it leaves). ``zero_behind=False``, for a
    caller that reads the result by ``over_live_rows`` and nowhere else:
    not defined anywhere behind the last group."""
    dims, tile, _ = _call_tiles(lhs, rhs, live_rows)
    _note_dispatch("fwd", *dims, tile)
    if tile is None:
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes)
    else:
        out = _gmm_vjp(lhs, rhs, group_sizes, tile, live_rows,
                       live_rows is not None and zero_behind)
    return out


class AdamStep(NamedTuple):
    """One expert matrix's Adam step, for the call that makes its
    gradient (``grouped_matmul_grads(adam=)``): ``state`` the weight and
    its two moments [E, k, n], ``lr_t`` the learning rate with the bias
    correction in it, ``lr_decay`` AdamW's learning rate times its decay
    (None: Adam), and the op's attributes."""
    state: Tuple[Any, Any, Any]
    lr_t: Any
    lr_decay: Any
    beta1: float
    beta2: float
    epsilon: float

    def after(self, dw):
        """The state after the step for a gradient that is an array: the
        ``adam`` / ``adamw`` op's arithmetic, as that op would run it
        behind the gradient's call."""
        from paddle_tpu.ops.optimizer_ops import adam_step

        p, m1, m2 = self.state
        return adam_step(p, dw.astype(m1.dtype), m1, m2, self.lr_t,
                         self.beta1, self.beta2, self.epsilon, self.lr_decay)


def grouped_matmul_grads(lhs, rhs, group_sizes, g, live_rows=None,
                         zero_behind=True, adam=None):
    """(d lhs, d rhs) of ``grouped_matmul(lhs, rhs, group_sizes)`` for
    the cotangent ``g`` [m, n], in the operands' dtypes: for a caller
    that saved its forward's results and does not run it again
    (``moe_experts_grad``), and the kernels' own vjp rule. ``live_rows``
    and ``zero_behind`` as ``grouped_matmul``'s: d lhs has zeros behind
    the last group (or to its row tile's end, or is not defined there),
    and what g holds there is never read into d rhs (lhs is multiplied
    by zeros there: it has to be finite in every row tile a group
    reaches, and need not be defined in any tile behind).

    ``adam`` (an ``AdamStep``): d rhs goes to that step and nowhere
    else, and the second result is the matrix's (weight, moment1,
    moment2) after it. Where the call has a tile, ``adam_tile`` has one
    for the matrix and the state is float32, the step is taken inside
    the weight-gradient kernel on its float32 accumulator
    (``tgmm_adam``, counted as pass ``bwd_dw_adam``) and d rhs is no
    array at all; otherwise (no TPU, a mesh: a chip's partial gradient
    is summed before any step reads it) d rhs is made as ever and the
    step follows it (``AdamStep.after``)."""
    dims, tile, dx_tile = _call_tiles(lhs, rhs, live_rows)
    fused = None
    if adam is not None and tile is not None and all(
            x.dtype == jnp.float32 for x in adam.state):
        fused = adam_tile(tile, *dims[1:], live_rows or dims[0])
    _note_dispatch("bwd_dx", *dims, dx_tile)
    _note_dispatch("bwd_dw_adam" if fused else "bwd_dw", *dims,
                   fused or tile)
    g = g.astype(lhs.dtype)
    if tile is None:
        # jax's transposes of ragged_dot; the forward it traces is dead
        _, vjp = jax.vjp(
            lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), lhs, rhs)
        dx, dw = vjp(g)
    else:
        # the rows' gradient contracts n and is k wide: its own product
        dx = gmm(g, rhs, group_sizes, dx_tile, transpose_rhs=True,
                 name="moe.gmm.bwd_dx",
                 zero_behind=live_rows is not None and zero_behind)
        if fused:
            return dx, tgmm_adam(lhs, g, group_sizes, fused, adam)
        dw = tgmm(lhs, g, group_sizes, tile)
    return dx, dw if adam is None else adam.after(dw)
