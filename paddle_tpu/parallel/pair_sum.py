"""Pallas TPU kernel for the two token-major sums of a dropless top-k MoE
layer (ops/moe_ops.py): ``moe_combine``'s forward and ``moe_dispatch``'s
gradient,

    out[t] = sum_j w[t, j] * rows[slot[t, j]]     over slot[t, j] < live,

``rows`` [n * k, d] the layer's row buffer in dispatch order (bf16),
``slot`` [n, k] the row of every (token, slot) pair, ``live`` the rows
inside the held experts' groups (a device scalar: ``sum(sizes)``), ``w``
[n, k] float32 or absent (ones). Each product and the sum are float32;
the result is cast once, to the rows' dtype.

``pairs.sum.combine`` and ``pairs.sum.dispatch_grad``: one body. What
makes a kernel possible where a row gather is not (Mosaic copies no
single row out of a tiled [m, d] buffer: a slice has to be whole sublane
tiles, and two bf16 rows share every 32-bit word): ``moe_dispatch`` sorts
the pairs by expert and STABLY, so inside an expert's group the rows
ascend by token, and the rows that a tile of ``tt`` tokens needs from one
held expert are CONTIGUOUS. The wrapper makes the table of those
segments' starts ([tiles + 1, held], a cumulative count: a few small XLA
ops); a grid step works on one token tile:

- the scalar core walks the tile's segments and starts one DMA for each
  aligned group of 16 rows (one bf16 sublane tile: 16 x d, where it lies
  in HBM) that holds a row of a segment, into a staging buffer of
  ``cap`` groups (one of two: a full buffer's DMAs land while the other
  is filled and the one before it is added), and notes each group's
  place and its segment's bounds in SMEM;
- for a full buffer, and at the tile's end, the VPU builds P
  [tt, cap * 16] from the tile's ``slot`` block: P[t, c] = w[t, j] where
  slot[t, j] is the staged row c (and that row lies inside the segment
  it was fetched for: a group fetched for two segments counts once for
  each), else 0; the MXU adds P @ staged into a float32 [tt, d]
  accumulator. P is 0 / 1 without a weight, exact in bf16; with one, its
  float32 entries are split into three bf16 pieces that sum to them
  exactly (8 + 8 + 8 bits), stacked on the left operand's rows, and the
  three products are added in float32: no weight and no product is
  rounded to bf16;
- the tile's [tt, d] is written once.

No [k, n, d] gathered copy and no float32 [n, d] scatter target exists in
HBM, a pair held elsewhere (``slot >= live``) costs no fetch, and the
work goes with the live rows: a group of the buffer no segment reaches
is never read. What a staged group holds outside its segment is
multiplied by P's zeros: it has to be finite, which every row before
``live`` is (another token's row); the one group ``live`` itself lies in
has its rows behind ``live`` zeroed in VMEM, and the groups of a buffer
that is not full are zeros.

``sum_tile`` is the one function that says tile or the XLA form
(``ops/moe_ops._sum_by_token``), from the call's own shapes, the dtype,
the backend and the mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as grouped_matmul._INTERPRET: run the kernel in interpreter
# mode on the CPU so the suite reaches it.
_INTERPRET = False

_GROUP = 16         # rows of one DMA: a bf16 sublane tile
_LANES = 128
# Tokens of a grid step (the largest that divides n) and groups of one
# staging buffer, timed alone on a v5e (benchmarks/moe_sum_candidates.py;
# my chip runs, PR 41; ms a call, with a weight + without). 128 tokens:
# the MXU's work goes with tokens x staged rows, the rows staged beyond
# the live ones with 1 / tokens: at [16384, 6, 2560] with 8 held experts
# 256 tokens read 0.91 + 0.58 an eighth live and 3.51 + 1.75 all live
# where 128 read 1.04 + 0.71 and 2.50 + 1.46. 32 groups (512 rows, four
# matmul chunks): a buffer that is not full is multiplied whole, so a
# larger one costs a sparse layer (24 / 32 / 64 groups: 1.03 / 1.22 /
# 1.94 weighted an eighth live) what it saves a dense one in passes
# (2.50 / 2.28 / 2.51 all live, the first and the last before the second
# buffer); [8192, 10, 2048] with 32 held reads 0.79 + 0.52 at 32 and
# 0.86 + 0.55 at 56 a sixteenth live, 1.94 + 1.16 and 1.81 + 1.13 all.
_TOKEN_TILES = (128, 64, 32, 16, 8)
_CAP = 32
# What a call's blocks may take of VMEM (the call raises Mosaic's scoped
# default of 16 MiB to what they need, as grouped_matmul does).
_VMEM_CAP_BYTES = 48 * 2**20

_F32 = jnp.float32
_I32 = jnp.int32


def kernels_enabled() -> bool:
    """The Pallas kernel needs a TPU backend (tests reach it on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _vmem_bytes(tt, cap, d, weighted=True):
    """What a grid step keeps in VMEM: the two staging buffers, the
    float32 accumulator, the product of the (stacked) P beside it, the
    result's block double-buffered and P with its pieces."""
    s = cap * _GROUP
    pieces = 3 if weighted else 1
    return (2 * s * d * 2 + tt * d * 4 + pieces * tt * d * 4 + 2 * tt * d * 2
            + tt * s * (4 + 2 * pieces))


def sum_tile(n, k, d, dtype, backend=None, on_mesh=None):
    """-> (tt, cap): the tokens of one grid step and the groups of 16
    rows a staging buffer holds, or None where the sum runs as the XLA
    form: no TPU backend (``backend``: None for this process's, with the
    interpreter counting as one), rows that are not bf16, a program
    under a mesh (a Mosaic call is not auto-partitioned), a width off
    the 128 lanes, a buffer that is not whole groups of 16 rows, no
    token tile that divides n, or blocks over the VMEM cap. The tile
    follows the shape, not a flag: the largest of 128 .. 8 tokens that
    divides n, and 32 groups."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    tiles = [t for t in _TOKEN_TILES if n % t == 0]
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or d % _LANES or n * k % _GROUP or not tiles
            or _vmem_bytes(tiles[0], _CAP, d) > _VMEM_CAP_BYTES):
        return None
    return tiles[0], _CAP


def segment_starts(slot, sizes, tt):
    """[(tiles + 1) * held] int32: entry i * held + e is the first row
    of held expert e's group that belongs to a token of tile i or later
    (the last tile's + 1: the group's end). ``moe_dispatch`` sorts
    stably, so the rows of group e between two entries are exactly the
    tile's pairs on e, by token."""
    n, k = slot.shape
    sizes = sizes.astype(_I32)
    ends = jnp.cumsum(sizes, dtype=_I32)
    # a tile's pairs before each group's end, less those before its start
    below = jnp.sum(slot.reshape(n // tt, tt * k, 1) < ends, axis=1,
                    dtype=_I32)
    count = jnp.diff(below, axis=1, prepend=0)
    before = jnp.cumsum(count, axis=0, dtype=_I32) - count
    return jnp.concatenate([ends - sizes + before, ends[None]]).reshape(-1)


def _three_pieces(p):
    """float32 p -> three bf16 arrays that sum to it exactly."""
    hi = p.astype(jnp.bfloat16)
    rest = p - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _kernel(seg_ref, live_ref, slot_ref, *refs, tt, cap, held, k, weighted):
    if weighted:
        w_ref, *refs = refs
    rows_ref, out_ref, buf, acc, base, lo, hi, sem = refs
    i = pl.program_id(0)
    live = live_ref[0]
    staged = cap * _GROUP
    col = jax.lax.broadcasted_iota(_I32, (1, staged), 1)
    acc[...] = jnp.zeros_like(acc)

    def at(c):
        return pl.ds(pl.multiple_of(c * _GROUP, _GROUP), _GROUP)

    def copy(b, c, g):
        return pltpu.make_async_copy(rows_ref.at[at(g)], buf.at[b, at(c)],
                                     sem.at[b])

    def add_staged(b, count):
        """acc += P @ the ``count`` groups buffer ``b`` holds."""
        t0 = b * cap

        # the buffer row of every staged row; -1 outside the segment its
        # group was fetched for, and in the groups nothing filled
        def place(c, row):
            r = base[t0 + c] + col - c * _GROUP
            ok = ((col >= c * _GROUP) & (col < (c + 1) * _GROUP)
                  & (r >= lo[t0 + c]) & (r < hi[t0 + c]))
            return jnp.where(ok, r, row)

        row = jax.lax.fori_loop(0, count, place,
                                jnp.full((1, staged), -1, _I32))
        slot = slot_ref[...]
        if weighted:
            w = w_ref[...]
            p = jnp.zeros((tt, staged), _F32)
            for j in range(k):
                p = p + jnp.where(slot[:, j:j + 1] == row, w[:, j:j + 1],
                                  0.0)
            left = jnp.concatenate(_three_pieces(p), axis=0)
        else:
            hit = slot[:, 0:1] == row
            for j in range(1, k):
                hit = hit | (slot[:, j:j + 1] == row)
            left = jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)

        def wait(c, carry):
            copy(b, c, 0).wait()
            return carry

        jax.lax.fori_loop(0, count, wait, 0)

        def clear(c, carry):
            buf[b, at(c), :] = jnp.zeros((_GROUP, buf.shape[2]), buf.dtype)
            return carry

        jax.lax.fori_loop(count, cap, clear, 0)

        def behind(c, carry):
            @pl.when(base[t0 + c] + _GROUP > live)
            def _():
                r = base[t0 + c] + jax.lax.broadcasted_iota(
                    _I32, (_GROUP, 1), 0)
                buf[b, at(c), :] = jnp.where(
                    r < live, buf[b, at(c), :].astype(_F32), 0.0
                ).astype(buf.dtype)
            return carry

        jax.lax.fori_loop(0, count, behind, 0)
        got = jax.lax.dot_general(left, buf[b], (((1,), (0,)), ((), ())),
                                  preferred_element_type=_F32)
        if weighted:
            got = got[2 * tt:] + got[tt:2 * tt] + got[:tt]
        acc[...] += got

    # Two buffers, one pass of the loop a buffer: the scalar core fills
    # buffer ``b`` from where the walk of the tile's segments stands
    # (expert ``e``, its next group ``g``, -1 for its first), then the
    # buffer filled the pass before (``waiting`` groups) is added, its
    # DMAs long landed and this one's in flight under the matmul.
    def fill(b, e, g):
        def one(state):
            e, g, c = state
            first = seg_ref[i * held + e]
            end = seg_ref[(i + 1) * held + e]
            g = jnp.where(g < 0, first // _GROUP, g)
            some = (end > first) & (g * _GROUP < end)

            @pl.when(some)
            def _():
                copy(b, c, g).start()
                base[b * cap + c] = g * _GROUP
                lo[b * cap + c] = first
                hi[b * cap + c] = end

            more = some & ((g + 1) * _GROUP < end)
            return (jnp.where(more, e, e + 1), jnp.where(more, g + 1, -1),
                    c + some.astype(_I32))

        return jax.lax.while_loop(
            lambda state: (state[0] < held) & (state[2] < cap), one,
            (e, g, jnp.int32(0)))

    def a_pass(state):
        e, g, b, waiting = state
        e, g, c = fill(b, e, g)

        @pl.when(waiting > 0)
        def _():
            add_staged(1 - b, waiting)

        return e, g, 1 - b, c

    zero = jnp.int32(0)
    jax.lax.while_loop(lambda state: (state[0] < held) | (state[3] > 0),
                       a_pass, (zero, jnp.int32(-1), zero, zero))
    out_ref[...] = acc[...].astype(out_ref.dtype)


def pair_sum(rows, slot, sizes, tile, top_w=None, *, name="pairs.sum"):
    """The sum above for ``rows`` [n * k, d], ``slot`` [n, k] int32 and
    ``sizes`` [held] int32 (moe_dispatch's Rows: the held experts'
    groups lie first in the buffer, in order), at ``tile`` (tt, cap) as
    ``sum_tile`` gives it; ``top_w`` [n, k] (float32) or None. -> [n, d]
    in the rows' dtype. One jitted function a (tile, name): the expert
    layers of a model make the same call, and a step traces and lowers
    the kernel once for all of them (0.7 s a call site on a chip's
    host otherwise: my chip run, PR 41)."""
    return _pair_sum(rows, slot, sizes, top_w, tile=tuple(tile), name=name,
                     interpret=bool(_INTERPRET))


@functools.partial(jax.jit, static_argnames=("tile", "name", "interpret"))
def _pair_sum(rows, slot, sizes, top_w, *, tile, name, interpret):
    n, k = slot.shape
    m, d = rows.shape
    tt, cap = tile
    held = sizes.shape[0]
    assert m == n * k and n % tt == 0 and m % _GROUP == 0, (rows.shape,
                                                            slot.shape, tile)
    weighted = top_w is not None
    seg = segment_starts(slot, sizes, tt)
    live = jnp.sum(sizes, dtype=_I32).reshape(1)
    pairs = pl.BlockSpec((tt, k), lambda i, seg, live: (i, 0))
    operands = [slot.astype(_I32)]
    if weighted:
        operands.append(top_w.astype(_F32))
    need = _vmem_bytes(tt, cap, d, weighted)
    return pl.pallas_call(
        functools.partial(_kernel, tt=tt, cap=cap, held=held, k=k,
                          weighted=weighted),
        name=name,
        out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tt,),
            in_specs=[pairs] * len(operands)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, d), lambda i, seg, live: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, cap * _GROUP, d), rows.dtype),
                pltpu.VMEM((tt, d), _F32),
                pltpu.SMEM((2 * cap,), _I32),
                pltpu.SMEM((2 * cap,), _I32),
                pltpu.SMEM((2 * cap,), _I32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(16 * 2**20, need * 3 // 2)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * d, transcendentals=0,
            bytes_accessed=rows.dtype.itemsize * (m + n) * d + 8 * m),
        interpret=interpret,
    )(seg, live, *operands, rows)
