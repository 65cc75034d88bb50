"""Pallas TPU kernels for Mamba-2's state-space scan in its chunkwise
matmul form ("SSD": Dao & Gu 2024, arXiv:2405.21060 section 6; the
mathematics and the precision contract are ops/mamba2_scan_ops.py's
module docstring; this is the same chunkwise form, chunk 128).

``mamba2.chunk.fwd`` and ``mamba2.chunk.bwd``, one call a pass. A grid
step works on one HEAD BLOCK (heads that share B and C: a whole group
where it fits the VMEM cap, Nemotron-3's 8 heads; ``_BLOCK_PAIRS`` pairs
of a wider group, Granite-4.0-H's ONE group of 64, which its head blocks
walk) and ``chunks`` chunks of the sequence; the grid is (batch, head
blocks, chunk blocks) with the last axis ``"arbitrary"``: the state of
each head is a
float32 VMEM scratch that lives from chunk to chunk and block to block
and reaches HBM only as the ``States`` the backward pass reads (the
state each chunk STARTS from; nothing of size t x heads x 64 x 128).
The backward kernel walks the blocks and the chunks inside one in
reverse with the state's cotangent in the scratch and makes a chunk
again from x, dt, B, C and ``States``.

Heads of 64 are half a lane tile, so the kernels work on PAIRS of
heads: x [b, t, heads * 64] is read in place by lane tiles of 128 (two
heads side by side), a pair's two states lie side by side as
[n, 2 x 64] float32, and a per-head factor of a row (dt, a decay) is a
``where`` over the lanes' halves. A chunk and pair is then, on the MXU:

- ``C B^T`` [128, 128], ONCE for the head block's heads (they share B
  and C; a group wider than a block makes it once a BLOCK: one
  [128, 128, 128] product beside the block's twelve, about 8% more MXU
  work than sharing it over all 64 heads of a one-group layer);
- each head's masked decay matrix times it, times the pair's ``x dt``
  [128, 128] (full width: the other head's half of the product is
  dropped, which costs the v5e's 128-wide MXU nothing);
- ``C`` times the pair's carried states [128, 128] (the chunk's start);
- ``B^T`` times the pair's ``x dt`` decayed to the chunk's end, into
  the states.

Backward the transposes of those, and the head block's ``dC`` and
``dB`` from ONE ``d(C B^T)`` summed over its heads in VMEM; where a
group is several head blocks, each writes a float32 partial and one XLA
sum over the blocks makes the group's.

float32: dt, the log decays, their running sums and exps, the decay
matrix, the state and its cotangent, every sum over a row. bf16
operands with float32 accumulation: x dt, B, C, the masked decay matrix
times C B^T, the state as an operand, the cotangents' products.

Per-position scalars (dt and the running sum of the log decay within a
chunk) arrive as ROWS [b, heads, chunks, 128] float32 (a megabyte a
layer, made by XLA ops in front, ops/mamba2_scan_ops.py) and are turned
down a column on the block (a masked sum over the lanes: no transpose
of a 1-row array); their cotangents leave as rows the same way.

``mamba2_tile`` is the one function that says tile or the chunked XLA
form (ops/mamba2_scan_ops._chunk_fn), from the call's own shapes, the
dtype, the backend and the mesh;
``pt_mamba2_scan_dispatch_total{impl}`` records its answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as grouped_matmul._INTERPRET: run the kernels in
# interpreter mode on the CPU so the suite reaches them.
_INTERPRET = False

CHUNK = 128     # the chunk the kernels are written for (a lane tile)
HEAD_DIM = 64   # a head: half a lane tile, two heads a pair
STATE = 128     # the state's size: a lane tile
_LANES = 128
# Chunks a grid step: 8 chunks are 1024 rows of x, B, C a block, and 8
# rows of dt and the decay [.., chunks, 128] are one float32 sublane tile.
_STEP_CHUNKS = 8
# The pairs of heads a step takes of a group that is too wide for the cap
# whole (the size Nemotron-3's group of 8 heads has).
_BLOCK_PAIRS = 4
# What a call's blocks and scratch may take of the v5e's 128 MiB of VMEM
# (the calls raise Mosaic's scoped limit to what they need, _vmem_limit).
_VMEM_CAP_BYTES = 48 * 2**20

_F32 = jnp.float32


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _vmem_bytes(pairs, chunks, partials=False):
    """What one grid step of the backward kernel (the larger) keeps in
    VMEM: its blocks double-buffered (x, dy, dx: bf16 rows of a head
    block's lanes; B, C, dB, dC; the states; dt, the decay and their
    gradients padded to a sublane tile; D and dD) and the scratch (the
    states' cotangent). ``partials``: dB and dC leave as float32 (a
    group of several head blocks)."""
    rows = chunks * CHUNK
    blocks = (3 * rows * pairs * _LANES * 2 + 4 * rows * STATE * 2
              + chunks * pairs * STATE * _LANES * 4
              + 4 * 2 * pairs * max(chunks, 8) * CHUNK * 4
              + 2 * 8 * pairs * _LANES * 4)
    if partials:
        blocks += 2 * rows * STATE * 2
    return 2 * blocks + pairs * STATE * _LANES * 4


def _vmem_limit(pairs, chunks, partials=False):
    """Mosaic's scoped limit for a call at this tile: the blocks and the
    scratch, and as much again for the values of a loop body, not under
    its default of 16 MiB."""
    return max(16 * 2**20, 2 * _vmem_bytes(pairs, chunks, partials))


def mamba2_tile(t, heads, groups, head_dim, state, chunk, dtype,
                backend=None, on_mesh=None):
    """-> (pairs, chunks): the pairs of heads (a head block's) and the
    chunks of one grid step of ``mamba2.chunk.*``, or None where the call
    runs as the chunked XLA form: no TPU backend (``backend``: None for
    this process's, with the interpreter counting as one), operands that
    are not bf16, a program under a mesh (a Mosaic call is not
    auto-partitioned), heads other than 64 wide or a state other than
    128 (a pair of heads and a state are a lane tile each), a chunk
    other than the 128 the kernels are written for, or groups that do
    not divide the heads into an even number each.

    The tile follows the shape, not a flag: a group's heads in a step
    (C B^T is theirs together, and dB and dC are summed over them in
    VMEM) where that fits the VMEM cap, else the largest head block of
    at most ``_BLOCK_PAIRS`` pairs that divides the group; 8 chunks a
    step, or all of a sequence that has fewer (it is padded to a
    multiple)."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or head_dim != HEAD_DIM or state != STATE or chunk != CHUNK
            or groups < 1 or heads % groups or (heads // groups) % 2
            or t < 1):
        return None
    pairs = heads // groups // 2
    chunks = min(_STEP_CHUNKS, -(-t // CHUNK))
    if _vmem_bytes(pairs, chunks) > _VMEM_CAP_BYTES:
        pairs = max(p for p in range(1, _BLOCK_PAIRS + 1) if pairs % p == 0)
    return pairs, chunks


# ---------------------------------------------------------------------------
# what a grid step computes of a chunk
# ---------------------------------------------------------------------------


def _dot(a, b, ca, cb):
    """a x b contracting a's axis ``ca`` with b's ``cb``, float32 out."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=_F32)


def _iotas():
    shape = (CHUNK, CHUNK)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _col(row, ii, jj):
    """[1, C] -> [C, 1]: the row's entries down a column (a masked sum
    over the lanes: no transpose of a 1-row array)."""
    return jnp.sum(jnp.where(ii == jj, row, 0.0), axis=1, keepdims=True)


def _as_row(col, ii, jj):
    """[C, 1] -> [1, C]."""
    return jnp.sum(jnp.where(ii == jj, col, 0.0), axis=0, keepdims=True)


def _rows(c):
    """The rows of chunk ``c`` in a block of x, B, C."""
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _row(c):
    """Chunk ``c``'s row of a block of dt or the decay [heads, chunks,
    C]."""
    return pl.ds(c, 1)


def _head(dt_row, ac_row, ii, jj):
    """A head's quantities of a chunk from its dt and the running sum of
    its log decay as rows [1, C]: dt down a column, exp(Acum) (the decay
    from the chunk's start), exp(A_C - Acum) (to its end) as columns,
    exp(A_C) [1, 1] and the decay matrix L_ij = exp(Acum_i - Acum_j) for
    i >= j (the inner where: exp of a masked, positive difference
    overflows)."""
    lower = ii >= jj
    ac = _col(ac_row, ii, jj)
    a_last = ac_row[:, CHUNK - 1:CHUNK]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, ac - ac_row, 0.0)),
                      0.0)
    return dict(dt=_col(dt_row, ii, jj), ea=jnp.exp(ac),
                eend=jnp.exp(a_last - ac), elast=jnp.exp(a_last),
                decay=decay)


def _pair(h0, h1, key, first):
    """The two heads' ``key`` side by side over a pair's lanes: a column
    [C, 1] each -> [C, 128], a [1, 1] each -> [1, 128]."""
    return jnp.where(first, h0[key], h1[key])


def _lane_halves():
    """[1, 128] true over a pair's first head."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) < HEAD_DIM


def _half_sums(v, first):
    """The sums over a row of v [rows, 128], the first head's lanes and
    the second's: two columns [rows, 1]."""
    whole = jnp.sum(v, axis=1, keepdims=True)
    one = jnp.sum(jnp.where(first, v, 0.0), axis=1, keepdims=True)
    return one, whole - one


# ---------------------------------------------------------------------------
# mamba2.chunk.fwd
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, ac_ref, d_ref, y_ref,
                states_ref, s_ref, *, pairs, chunks):
    dtype = x_ref.dtype
    ii, jj = _iotas()
    first = _lane_halves()

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def chunk(c, carry):
        rows = _rows(c)
        bc, cc = b_ref[rows, :], c_ref[rows, :]
        cb = _dot(cc, bc, 1, 1)                  # the group's C B^T
        for p in range(pairs):
            lanes = slice(p * _LANES, (p + 1) * _LANES)
            h = [_head(dt_ref[2 * p + r, _row(c), :],
                       ac_ref[2 * p + r, _row(c), :], ii, jj)
                 for r in range(2)]
            xf = x_ref[rows, lanes].astype(_F32)
            xdt = xf * _pair(*h, "dt", first)
            xb = xdt.astype(dtype)
            s = s_ref[p]
            states_ref[c, p] = s
            sb = s.astype(dtype)
            y = jnp.where(
                first, _dot((cb * h[0]["decay"]).astype(dtype), xb, 1, 0),
                _dot((cb * h[1]["decay"]).astype(dtype), xb, 1, 0))
            y = (y + _pair(*h, "ea", first) * _dot(cc, sb, 1, 0)
                 + d_ref[:, lanes] * xf)
            y_ref[rows, lanes] = y.astype(y_ref.dtype)
            xe = (xdt * _pair(*h, "eend", first)).astype(dtype)
            s_ref[p] = s * _pair(*h, "elast", first) + _dot(bc, xe, 0, 0)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def _padded(x, axis, size, value=0.0):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad, constant_values=value)


def _scalar_rows(v, n_chunks):
    """dt or the log decay [b, t, h] float32 -> [b, h, n, C], zeros
    behind t (a dt of 0 writes nothing, a log decay of 0 forgets
    nothing)."""
    v = jnp.moveaxis(v.astype(_F32), 2, 1)
    b, h, _ = v.shape
    return _padded(v, 2, n_chunks * CHUNK).reshape(b, h, n_chunks, CHUNK)


def _operands(x, dt, a, bm, cm, tile):
    """The op's inputs as the kernels' blocks read them: x, B, C with
    their heads and groups in the lanes (no copy), dt and the running
    sum of the log decay ``a`` within each chunk as rows, heads first,
    everything padded to whole grid steps with zeros (x 0 and dt 0 write
    nothing)."""
    b, t, _ = x.shape
    chunks = tile[1]
    n = -(-t // CHUNK)
    n_pad = -(-n // chunks) * chunks
    tp = n_pad * CHUNK
    dt4 = _scalar_rows(dt, n_pad)
    ac4 = jnp.cumsum(_scalar_rows(a, n_pad), axis=-1)
    return (_padded(x, 1, tp), _padded(bm, 1, tp), _padded(cm, 1, tp),
            dt4, ac4), n, n_pad


def _group_of(per_group):
    """A head block's group: itself where a block is a whole group."""
    return (lambda g: g) if per_group == 1 else (lambda g: g // per_group)


def _specs(pairs, chunks, blk, per_group):
    """BlockSpecs of (x-like [b, t, heads * 64], B or C [b, t, groups *
    128], a scalar's rows [b, heads, n, C], the states [n, b, pairs of
    all heads, 128, 128]) for a grid (batch, head block, chunk block)
    whose block index along the sequence is ``blk(c)``; ``per_group``
    head blocks read one group's B and C."""
    rows = chunks * CHUNK
    grp = _group_of(per_group)
    return (pl.BlockSpec((None, rows, pairs * _LANES),
                         lambda i, g, c: (i, blk(c), g)),
            pl.BlockSpec((None, rows, STATE),
                         lambda i, g, c: (i, blk(c), grp(g))),
            pl.BlockSpec((None, 2 * pairs, chunks, CHUNK),
                         lambda i, g, c: (i, g, blk(c), 0)),
            pl.BlockSpec((chunks, None, pairs, STATE, _LANES),
                         lambda i, g, c: (blk(c), i, g, 0, 0)))


def _head_blocks(heads, bm, pairs):
    """(head blocks of the call, head blocks a group)."""
    blocks = heads // (2 * pairs)
    return blocks, blocks // (bm.shape[2] // STATE)


def _cost(b, heads, n, passes, bytes_accessed, pairs):
    # a chunk and head: C B^T (its share of the head block's one), the
    # decay matrix's product, C S and B^T X (2 C C 64 MACs each, about),
    # times ``passes`` (1 forward, 3 backward: made again + two
    # transposes)
    flops = 2 * (CHUNK * CHUNK * STATE // (2 * pairs)
                 + CHUNK * CHUNK * HEAD_DIM
                 + 2 * CHUNK * STATE * HEAD_DIM)
    return pl.CostEstimate(
        flops=passes * b * heads * n * flops,
        transcendentals=b * heads * n * (CHUNK * CHUNK + 3 * CHUNK),
        bytes_accessed=bytes_accessed)


def _d_rows(d):
    """D [heads] -> [1, heads * 64] float32: a head's D over its lanes."""
    return jnp.repeat(d.astype(_F32), HEAD_DIM)[None, :]


def mamba2_scan_fwd(x, dt, a, bm, cm, d, tile):
    """x [b, t, heads * 64], B, C [b, t, groups * 128] (bf16), dt and
    the log decay ``a`` = A dt [b, t, heads] float32 (dt behind its
    softplus), D [heads] -> (y [b, t, heads * 64] in x's dtype, states
    [n, b, heads / 2, 128, 128] float32: a pair of heads' states side by side,
    [state, 2 x 64], as each of the n = ceil(t / 128) chunks started
    from them). ``tile``: ``mamba2_tile``'s answer for the call."""
    b, t, width = x.shape
    heads = dt.shape[2]
    pairs, chunks = tile
    blocks, per_group = _head_blocks(heads, bm, pairs)
    assert (width == heads * HEAD_DIM
            and bm.shape[2] * per_group == blocks * STATE), (
        x.shape, bm.shape, dt.shape)
    (x2, b2, c2, dt4, ac4), n, n_pad = _operands(x, dt, a, bm, cm, tile)
    x_spec, bc_spec, row_spec, st_spec = _specs(
        pairs, chunks, lambda c: c, per_group)
    d_spec = pl.BlockSpec((1, pairs * _LANES), lambda i, g, c: (0, g))
    item = jnp.dtype(x.dtype).itemsize
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, pairs=pairs, chunks=chunks),
        name="mamba2.chunk.fwd",
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (n_pad, b, heads // 2, STATE, _LANES), _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, blocks, n_pad // chunks),
            in_specs=[x_spec, bc_spec, bc_spec, row_spec, row_spec, d_spec],
            out_specs=(x_spec, st_spec),
            scratch_shapes=[pltpu.VMEM((pairs, STATE, _LANES), _F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(pairs, chunks)),
        cost_estimate=_cost(
            b, heads, n_pad, 1,
            item * (2 * x2.size + 2 * per_group * b2.size) + 8 * dt4.size
            + 4 * n_pad * b * heads * HEAD_DIM * STATE, pairs),
        interpret=_INTERPRET,
    )(x2, b2, c2, dt4, ac4, _d_rows(d))
    return y[:, :t], states[:n]


# ---------------------------------------------------------------------------
# mamba2.chunk.bwd
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, ac_ref, d_ref, states_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dac_ref, dd_ref,
                ds_ref, *, pairs, chunks):
    dtype = x_ref.dtype
    ii, jj = _iotas()
    lower = ii >= jj
    first = _lane_halves()
    last_lane = jax.lax.broadcasted_iota(
        jnp.int32, (1, CHUNK), 1) == CHUNK - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def chunk(i, carry):
        c = chunks - 1 - i       # a block's chunks as the blocks: in reverse
        rows = _rows(c)
        bc, cc = b_ref[rows, :], c_ref[rows, :]
        cb = _dot(cc, bc, 1, 1)
        dcb = jnp.zeros((CHUNK, CHUNK), _F32)    # d(C B^T), over the heads
        dc = jnp.zeros((CHUNK, STATE), _F32)
        db = jnp.zeros((CHUNK, STATE), _F32)
        for p in range(pairs):
            lanes = slice(p * _LANES, (p + 1) * _LANES)
            h = [_head(dt_ref[2 * p + r, _row(c), :],
                       ac_ref[2 * p + r, _row(c), :], ii, jj)
                 for r in range(2)]
            dt2, ea2, eend2, elast2 = (_pair(*h, key, first) for key in (
                "dt", "ea", "eend", "elast"))
            xf = x_ref[rows, lanes].astype(_F32)
            xdt = xf * dt2
            xb = xdt.astype(dtype)
            xe = xdt * eend2
            dyb = dy_ref[rows, lanes]
            dyf = dyb.astype(_F32)
            s = states_ref[c, p]
            sb = s.astype(dtype)
            ds = ds_ref[p]
            dsb = ds.astype(dtype)
            zero = jnp.zeros_like(dyb)
            dyh = (jnp.where(first, dyb, zero), jnp.where(first, zero, dyb))
            m = [(cb * h[r]["decay"]).astype(dtype) for r in range(2)]
            # through y = M X + e^A (C S) + D x and
            # S' = e^{A_C} S + (X e^{A_C - A})^T B
            b_ds = _dot(bc, dsb, 1, 0)           # B dS'  [C, 128]
            c_s = _dot(cc, sb, 1, 0)             # C S    [C, 128]
            dxdt = (jnp.where(first, _dot(m[0], dyb, 0, 0),
                              _dot(m[1], dyb, 0, 0)) + eend2 * b_ds)
            dx_ref[rows, lanes] = (dt2 * dxdt + d_ref[:, lanes] * dyf).astype(
                dx_ref.dtype)
            dd_ref[:, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
            dye = (ea2 * dyf).astype(dtype)
            dc = dc + _dot(dye, sb, 1, 1)
            db = db + _dot(xe.astype(dtype), dsb, 1, 1)
            ds_ref[p] = ds * elast2 + _dot(cc, dye, 0, 0)
            # the per-position scalars: dt, and the decays' exponents
            ddt = _half_sums(dxdt * xf, first)
            dea = _half_sums(dyf * c_s * ea2, first)         # d Acum, e^A
            deend = _half_sums(xe * b_ds, first)             # d(A_C - Acum)
            dlast = _half_sums(
                jnp.sum(ds * s, axis=0, keepdims=True) * elast2, first)
            for r in range(2):
                dm = jnp.where(lower, _dot(dyh[r], xb, 1, 1), 0.0)
                dl = dm * h[r]["decay"]
                dcb = dcb + dl
                e = dl * cb                      # d/d(Acum_i - Acum_j)
                dac = (jnp.sum(e, axis=1, keepdims=True) + dea[r]
                       - deend[r])
                d_a_last = (jnp.sum(deend[r], axis=0, keepdims=True)
                            + dlast[r])
                dac_ref[2 * p + r, _row(c), :] = (
                    _as_row(dac, ii, jj)
                    - jnp.sum(e, axis=0, keepdims=True)
                    + jnp.where(last_lane, d_a_last, 0.0))
                ddt_ref[2 * p + r, _row(c), :] = _as_row(ddt[r], ii, jj)
        dcbb = dcb.astype(dtype)
        dc_ref[rows, :] = (dc + _dot(dcbb, bc, 1, 0)).astype(dc_ref.dtype)
        db_ref[rows, :] = (db + _dot(dcbb, cc, 0, 0)).astype(db_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, None)


def mamba2_scan_bwd(x, dt, a, bm, cm, d, states, dy, tile):
    """The cotangents (dx, dB, dC in the operands' dtype; ddt and da
    [b, t, heads] float32, of dt and of the log decay ``a``; dD [heads]
    float32) of ``mamba2_scan_fwd`` for the cotangent ``dy`` of y, from
    the forward's ``states``."""
    b, t, _ = x.shape
    heads = dt.shape[2]
    pairs, chunks = tile
    blocks, per_group = _head_blocks(heads, bm, pairs)
    partials = per_group > 1
    (x2, b2, c2, dt4, ac4), n, n_pad = _operands(x, dt, a, bm, cm, tile)
    dy2 = _padded(dy.astype(x.dtype), 1, n_pad * CHUNK)
    states = _padded(states, 0, n_pad)
    last = n_pad // chunks - 1
    x_spec, bc_spec, row_spec, st_spec = _specs(
        pairs, chunks, lambda c: last - c, per_group)
    if partials:
        # a group's head blocks each write their own dB and dC, float32
        dbc_spec = pl.BlockSpec(
            (None, None, chunks * CHUNK, STATE),
            lambda i, g, c: (g % per_group, i, last - c, g // per_group))
        dbc_shape = lambda v: jax.ShapeDtypeStruct((per_group,) + v.shape,
                                                   _F32)
    else:
        dbc_spec = bc_spec
        dbc_shape = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
    d_spec = pl.BlockSpec((1, pairs * _LANES), lambda i, g, c: (0, g))
    dd_spec = pl.BlockSpec((None, 1, pairs * _LANES),
                           lambda i, g, c: (i, 0, g))
    item = jnp.dtype(x.dtype).itemsize
    dx2, db2, dc2, ddt4, dac4, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, pairs=pairs, chunks=chunks),
        name="mamba2.chunk.bwd",
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   dbc_shape(b2), dbc_shape(c2),
                   jax.ShapeDtypeStruct(dt4.shape, _F32),
                   jax.ShapeDtypeStruct(ac4.shape, _F32),
                   jax.ShapeDtypeStruct((b, 1, heads * HEAD_DIM), _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, blocks, last + 1),
            in_specs=[x_spec, bc_spec, bc_spec, row_spec, row_spec, d_spec,
                      st_spec, x_spec],
            out_specs=(x_spec, dbc_spec, dbc_spec, row_spec, row_spec,
                       dd_spec),
            scratch_shapes=[pltpu.VMEM((pairs, STATE, _LANES), _F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(pairs, chunks, partials)),
        cost_estimate=_cost(
            b, heads, n_pad, 3,
            item * (3 * x2.size + 2 * per_group * b2.size)
            + (8 if partials else 2 * item) * per_group * b2.size
            + 16 * dt4.size + states.dtype.itemsize * states.size, pairs),
        interpret=_INTERPRET,
    )(x2, b2, c2, dt4, ac4, _d_rows(d), states, dy2)

    def scalar(v):   # [b, heads, n, C] -> [b, t, heads]
        return jnp.moveaxis(v.reshape(b, heads, -1)[:, :, :t], 1, 2)

    # Acum is the running sum of a within a chunk: da_m = sum of dAcum_i
    # over the chunk's i >= m
    da4 = jnp.flip(jnp.cumsum(jnp.flip(dac4, -1), axis=-1), -1)
    if partials:
        db2, dc2 = (jnp.sum(v, axis=0).astype(like.dtype)
                    for v, like in ((db2, bm), (dc2, cm)))
    return (dx2[:, :t], db2[:, :t], dc2[:, :t], scalar(ddt4), scalar(da4),
            jnp.sum(dd.reshape(b, heads, HEAD_DIM), axis=(0, 2)))
