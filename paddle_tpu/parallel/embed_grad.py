"""Pallas TPU kernel for the dense gradient of an embedding table
(ops/tensor_ops.py, ``lookup_table_grad``),

    dW[v] = sum of g[i] over the positions i whose id is v,

``g`` [n, d] the cotangent's rows (bf16 or float32), ``keys`` [n] int32
the row of the table each position read, or a value outside the table
for a position that read none (a padding id, an id the forward did not
find). Every product and the sum are float32, as the scatter-add's are
that this stands in for; only the order of the additions differs.

``embed.grad``: a sum of rows by id is ``pair_sum.py``'s sum the other
way round, vocabulary rows in the place of tokens and ONE segment a tile
in the place of one per held expert. XLA writes it as a sort of the ids,
a gather of the rows in that order and a sorted scatter that passes over
the table (0.39 us a TABLE row of 2560, 0.05 one of 2048: PERF.md
section 6, PR 46). Here the first two stay XLA's (a stable sort of n
keys, a gather of n rows) and the pass becomes matmuls: a tile of ``tv``
vocabulary rows needs one CONTIGUOUS segment of the sorted buffer, which
lies in a few aligned groups of ``g`` rows. The wrapper lists the (tile,
group) pairs in tile order (a cumulative count a tile: a few small XLA
ops, prefetched as scalars), at most tiles + groups of them, and the
grid walks the list:

- the pipeline fetches the step's group of rows [g, d] and its ids
  [1, g] (a step on the group of the step before fetches nothing);
- at a tile's first step its float32 block [tv, d] is zeroed in VMEM;
- the VPU builds the 0 / 1 matrix P[r, c] = (id[c] == tile's first row
  + r), exact in bf16; a row of the group that belongs to a neighbouring
  tile, or to none, matches no r. The MXU adds P @ rows into the block:
  bf16 rows as they are, float32 rows as the three bf16 pieces that sum
  to them exactly (8 + 8 + 8 bits: ``pair_sum._three_pieces``), so no
  cotangent is rounded;
- the block leaves for HBM when the walk reaches the next tile: every
  tile is written ONCE, zeros where no id falls (an empty tile takes one
  step of the list for that).

No [n, vocab] one-hot and no second float32 [vocab, d] exists in HBM.
What a group holds beside the tile's own rows is multiplied by P's
zeros, so it has to be finite: a cotangent row that is not spreads over
its tile and the tile before or behind it, where the scatter-add kept it
to one row of the table.

``embed_grad_tile`` is the one function that says tile or XLA's
scatter-add, from the call's own shapes, the dtype, the backend and the
mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.parallel.pair_sum import _three_pieces

# Test hook, as pair_sum._INTERPRET: run the kernel in interpreter mode
# on the CPU so the suite reaches it.
_INTERPRET = False

_LANES = 128
# Vocabulary rows of a tile and sorted rows of a grid step (the MXU's
# contraction, and the lanes of the ids' block), timed alone on a v5e
# (benchmarks/embed_grad_candidates.py; my chip runs, PR 46; the chip's
# busy ms a call, float32 cotangent, the sort and the gather included).
# The MXU's work goes with tile x rows + table x group, the steps with
# table / tile + rows / group: 128 x 128 reads 0.486 where 256 x 128
# reads 0.519, 512 x 128 0.603 and 256 x 256 0.693 at 4096 rows into
# [25008, 2560]; 1.054 / 1.075 / 1.184 / 1.286 at 8192 into
# [50304, 2048]; 1.629 / 1.613 / 1.861 / 1.745 at 16,384 into
# [18992, 2560] (half of it the gather of 168 MB of float32 rows); only
# at 32,768 rows of 768 is 256 x 128 ahead by 6% (0.748 / 0.707). XLA's
# scatter-add at those four calls: 9.97, 3.07, 8.71, 1.18.
_TILE = (128, 128)
# What a call's blocks may take of VMEM (the call raises Mosaic's scoped
# default of 16 MiB to what they need, as pair_sum does).
_VMEM_CAP_BYTES = 48 * 2**20
# The least width that takes the kernel. XLA's sorted scatter passes over
# the TABLE's rows, and what a row costs goes with how the width factors
# (4096 rows into 16,384: 0.36 ms at 1024, 1.09 at 2048, 2.81 at 2432,
# 6.65 at 2560, 1.13 at 2688, 29.2 at 5120, where the kernel reads 0.17 /
# 0.35 / 0.36 / 0.38 / 0.40 / 1.02). From 1024 up the gain showed in
# every cell's step (+0.75% to +1.6% at 2048 and 2688, +7.6% at 2560).
# Under 1024 the kernel is ahead alone too, by 0.3-0.5 ms a call (32,768
# rows into [10000, 512]: 0.75 -> 0.38; into [30522, 768]: 1.18 -> 0.71):
# 0.2-0.3% of a step of the cells that make such calls, which their runs
# do not resolve, so those keep XLA's form and their lowered steps the
# bytes they had (PERF.md section 6, PR 46).
_MIN_WIDTH = 1024

_F32 = jnp.float32
_I32 = jnp.int32


def kernels_enabled() -> bool:
    """The Pallas kernel needs a TPU backend (tests reach it on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _vmem_bytes(tv, group, d, itemsize):
    """What a grid step keeps in VMEM: the tile's float32 block and the
    group's rows, both double-buffered, the product beside the block,
    a float32 group's three pieces and P."""
    pieces = 3 * group * d * 2 if itemsize == 4 else 0
    return (3 * tv * d * 4 + 2 * group * d * itemsize + pieces
            + tv * group * 6)


def embed_grad_tile(n, vocab, d, dtype, backend=None, on_mesh=None):
    """-> (tv, group): the vocabulary rows of a tile and the sorted rows
    of one grid step of ``embed.grad``, or None where the gradient stays
    XLA's scatter-add: no TPU backend (``backend``: None for this
    process's, with the interpreter counting as one), a cotangent that
    is neither bf16 nor float32, a program under a mesh (a Mosaic call
    is not auto-partitioned), a width off the 128 lanes or under
    ``_MIN_WIDTH``, nothing to sum, or blocks over the VMEM cap."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    dtype = jnp.dtype(dtype)
    if (not on_tpu or on_mesh or dtype not in (jnp.bfloat16, jnp.float32)
            or d % _LANES or d < _MIN_WIDTH or min(n, vocab) < 1
            or _vmem_bytes(*_TILE, d, dtype.itemsize) > _VMEM_CAP_BYTES):
        return None
    return _TILE


def work_items(keys, vocab, tv, group):
    """(tile, group, live) [tiles + groups] int32 each: the grid's steps
    for ``keys`` [n_pad] (n_pad whole groups; order does not matter
    here), tile by tile. A tile's steps are the aligned groups of the
    SORTED buffer that its segment lies in, one step with ``live`` 0
    for a tile no key falls in, and behind the last tile's steps the
    list repeats its last entry with ``live`` 0 (a step on the block of
    the step before it fetches and writes nothing)."""
    tiles, groups = pl.cdiv(vocab, tv), keys.shape[0] // group
    bases = jnp.arange(tiles + 1, dtype=_I32) * tv
    # a tile's segment [first, end) of the sorted buffer: the keys under
    # its first row, and under the next tile's
    below = jnp.sum(keys[None, :] < bases[:, None], axis=1, dtype=_I32)
    first, end = below[:-1], below[1:]
    g0 = first // group
    span = jnp.where(end > first, (end + group - 1) // group - g0, 0)
    count = jnp.maximum(span, 1)
    offset = jnp.cumsum(count, dtype=_I32) - count
    w = jnp.arange(tiles + groups, dtype=_I32)
    tile = jnp.sum(offset[None, :] <= w[:, None], axis=1, dtype=_I32) - 1
    j = w - offset[tile]
    live = (j < span[tile]).astype(_I32)
    at = g0[tile] + jnp.clip(j, 0, jnp.maximum(span[tile] - 1, 0))
    return tile, jnp.minimum(at, groups - 1), live


def _kernel(tile_ref, group_ref, live_ref, ids_ref, rows_ref, out_ref, *,
            tv):
    del group_ref   # the index maps' alone
    w = pl.program_id(0)
    tile = tile_ref[w]

    @pl.when((w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != tile))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[w] > 0)
    def _():
        row = tile * tv + jax.lax.broadcasted_iota(_I32, (tv, 1), 0)
        p = jnp.where(ids_ref[0] == row, 1.0, 0.0).astype(jnp.bfloat16)
        rows = rows_ref[...]
        parts = ((rows,) if rows.dtype == jnp.bfloat16
                 else _three_pieces(rows)[::-1])   # the smallest first
        out_ref[...] += sum(
            jax.lax.dot_general(p, part, (((1,), (0,)), ((), ())),
                                preferred_element_type=_F32)
            for part in parts)


def embed_grad(g, keys, vocab, tile):
    """The sum above for ``g`` [n, d] (bf16 or float32) and ``keys`` [n]
    int32, at ``tile`` (tv, group) as ``embed_grad_tile`` gives it.
    -> [vocab, d] float32. A key outside [0, vocab) adds nothing."""
    return _embed_grad(g, keys, vocab=int(vocab), tile=tuple(tile),
                       interpret=bool(_INTERPRET))


def sorted_rows(g, keys, vocab, tile):
    """What XLA does in front of the kernel: ((tile, group, live) as
    ``work_items`` lists the steps, the sorted keys [groups, 1, group],
    the rows in that order [n_pad, d]). A key outside the table sorts
    behind every tile; the rows that pad n to whole groups repeat g's
    last (finite, and matched by no tile)."""
    n = g.shape[0]
    tv, group = tile
    n_pad = pl.cdiv(n, group) * group
    outside = pl.cdiv(vocab, tv) * tv
    keys = keys.astype(_I32)
    keys = jnp.where((keys >= 0) & (keys < vocab), keys, outside)
    keys = jnp.concatenate([keys, jnp.full((n_pad - n,), outside, _I32)])
    keys, order = jax.lax.sort(
        (keys, jnp.arange(n_pad, dtype=_I32)), num_keys=1, is_stable=True)
    rows = g.at[jnp.minimum(order, n - 1)].get(mode="promise_in_bounds")
    return (work_items(keys, vocab, tv, group),
            keys.reshape(n_pad // group, 1, group), rows)


@functools.partial(jax.jit, static_argnames=("vocab", "tile", "interpret"))
def _embed_grad(g, keys, *, vocab, tile, interpret):
    n, d = g.shape
    tv, group = tile
    tiles, groups = pl.cdiv(vocab, tv), pl.cdiv(n, group)
    items, keys, rows = sorted_rows(g, keys, vocab, tile)
    need = _vmem_bytes(tv, group, d, g.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, tv=tv),
        name="embed.grad",
        out_shape=jax.ShapeDtypeStruct((vocab, d), _F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles + groups,),
            in_specs=[
                pl.BlockSpec((1, 1, group),
                             lambda w, tile, at, live: (at[w], 0, 0)),
                pl.BlockSpec((group, d),
                             lambda w, tile, at, live: (at[w], 0)),
            ],
            out_specs=pl.BlockSpec(
                (tv, d), lambda w, tile, at, live: (tile[w], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 * 2**20, need * 3 // 2)),
        cost_estimate=pl.CostEstimate(
            flops=2 * (tiles + groups) * tv * group * d, transcendentals=0,
            bytes_accessed=4 * vocab * d + g.dtype.itemsize * n * d),
        interpret=interpret,
    )(*items, keys, rows)
