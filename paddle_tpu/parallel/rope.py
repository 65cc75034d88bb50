"""Pallas TPU kernels for the rotary position embedding of q and k
(ops/attention_ops.rotary_embedding; the mathematics and the precision
contract are ``_rotate``'s there: rotate-half form, float32 angles,
float32 rotation of the bf16 values, one rounding back to bf16).

``rope.fwd`` and ``rope.bwd``, one call a pass for q AND k. The rotation
and the heads-first transpose are ONE pass over bf16: a grid step reads a
block of rows of q where the projection left it, token-major
[b, t, h * dh] (a head is a block of dh lanes), and writes it head-major
[b, h, t, dh]; the backward reads the head-major cotangents and writes
token-major ones. What XLA's lowering of transpose + ``_rotate`` does
and a grid step does not: a relayout of q with t as the minor dimension
and one back, a float32 copy of q and the two negated float32 halves in
HBM (2.63 GB accessed a forward call at [1, 28 + 4, 16384, 128] for
0.30 GB of q, k and tables: the compiled module's own count, PR 42).

A grid step holds ``rows`` positions of ``hb`` heads of q; k's heads
(all of them: there are few) ride in the step of q's first head block,
their blocks' indices constant over the head axis. A pass of the loop
inside the step takes ``_PASS_ROWS`` rows: the tables' rows once, then
head after head: load [pass, dh] bf16, convert to float32 in registers,
swap the halves (a lane roll by dh / 2 on the XLU where a head is one
vreg wide, whole vregs otherwise), multiply by cos and by a sin table
that carries the sign ([-sin | +sin]; the backward's, a rotation's
transpose, [+sin | -sin]), add, round to bf16, store. The tables
[t, dh] float32 are built by XLA once a call, inside the call's jitted
function; their block's index does not change over the head axis, so
each is fetched once a block of rows.

``rope_tile`` is the one function that says tile or the XLA form
(``ops/attention_ops._rotate`` behind XLA's transpose), from the call's
own shapes, the dtype, the backend and the mesh;
``pt_rope_dispatch_total{impl}`` records its answer.

A SCALING (``Yarn``: YaRN, Peng et al. 2023, arXiv:2309.00071, as HF's
``_compute_yarn_parameters`` computes it) changes what the tables hold
and nothing else: ``cos_sin`` is the one place that makes the inverse
frequencies (plain ``theta^(-2j/dim)``, or yarn's blend of them with the
same divided by ``factor``) and the angles, and multiplies cos and sin
by the scaling's attention factor; ``tables`` (the kernels') and
``_rotate`` (the XLA form) both call it. The kernels read tables, so a
whole-head call with a scaling takes them unchanged.

A call that turns PART of a head (``rotary_dim`` < dh, with or without a
scaling) takes the kernels where a head is ONE vreg of 128 lanes wide:
the tables hold cos 1 and sin 0 on the features that pass, and the
partner of lane j is j + rotary_dim / 2 in the first half of the part
and j - rotary_dim / 2 in the second (two lane rolls and a select where
the whole head takes one roll). At another width (64 of 256) the call is
refused by ``rope_tile`` and runs as ``_rotate``, counted under
``pt_rope_dispatch_total{impl="xla", scaling=...}``.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as causal_conv._INTERPRET: run the kernels in interpreter
# mode on the CPU so the suite reaches them.
_INTERPRET = False

_LANES = 128
# Rows of one pass of the loop inside a grid step, and of the step's
# block, with all of q's heads in it. Timed alone on a v5e
# (benchmarks/rope_candidates.py; my chip runs, PR 42; forward / backward
# ms a call from the projection's [1, 16384, 4608] to head-major 28 + 4
# heads of 128 and back, the copies of q and k out of it, 0.27, included):
# 256 rows x 28 heads 0.63 / 0.64, 128 0.65 / 0.66, 64 0.68 / 0.69, but
# 512 0.91 / 0.93 and 1024 0.91 / 0.93 (the step's compute and its DMAs
# are about as long, 0.35 ms a call each, and from 512 rows up they no
# longer overlap); 4 heads a step (1 KB pieces of a row) 0.92-1.02 at
# 128 .. 2048 rows, a head a step 1.94; passes of 16 and 64 rows as 32.
# At [2, 4096, 16 + 16 heads] 64 .. 1024 rows read 0.41-0.44 / 0.27-0.30.
_PASS_ROWS = 32
_BLOCK_ROWS = (256, 128, 64, 32)
# What a call's blocks may take of VMEM (the call raises Mosaic's scoped
# default of 16 MiB to what they need, as pair_sum does).
_VMEM_CAP_BYTES = 40 * 2**20

_F32 = jnp.float32

# A yarn scaling of the rotary frequencies, plain numbers (hashable: a
# static argument of the jitted calls): the context's growth ``factor``
# over ``original_length`` positions, the rotations (``beta_fast``,
# ``beta_slow``) between which the frequencies go from extrapolated to
# interpolated, and the ``attention_factor`` on cos and sin.
Yarn = collections.namedtuple(
    "Yarn", "factor original_length beta_fast beta_slow attention_factor")


def yarn_correction_range(dim, theta, scaling):
    """(low, high): the indices j of ``theta^(-2j/dim)`` between which
    yarn's ramp runs: the dimension whose wave makes ``beta`` rotations
    over the original length, dim ln(L / (2 pi beta)) / (2 ln theta), at
    beta_fast floored and at beta_slow ceiled, clipped to [0, dim - 1]
    (HF's ``find_correction_range`` with ``truncate``)."""
    def at(beta):
        return (dim * math.log(scaling.original_length / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(at(scaling.beta_fast)), 0),
            min(math.ceil(at(scaling.beta_slow)), dim - 1))


def inv_freq(dim, theta, scaling=None):
    """[dim / 2] float32 inverse frequencies of ``dim`` rotated
    features: f_j = theta^(-2j/dim); under a yarn scaling f_j / factor
    * ramp_j + f_j * (1 - ramp_j), ramp_j = clip((j - low) / (high -
    low), 0, 1): the fast waves (j <= low) keep their frequency, the
    slow ones (j >= high) are interpolated by ``factor``."""
    f = theta ** (-jnp.arange(0, dim, 2, dtype=_F32) / dim)
    if scaling is None:
        return f
    low, high = yarn_correction_range(dim, theta, scaling)
    if low == high:
        high += 0.001   # HF: no division by zero
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_F32) - low) / (high - low),
                    0.0, 1.0)
    return f / scaling.factor * ramp + f * (1.0 - ramp)


def cos_sin(t, dim, theta, scaling=None, positions=None, sections=None):
    """(cos, sin) [t, dim / 2] float32 of positions 0 .. t - 1 turning
    ``dim`` features, each times the scaling's attention factor: the one
    place the angles are made, for the kernels' tables and ``_rotate``.
    ``positions`` [n, t] (or [t]: one row), a device value: the
    positions are the FEED's, and frequency pair i turns by row
    axis(i)'s, axis(i) the index of the run of ``sections`` (n counts
    that sum to dim / 2: Qwen2-VL's multi-axis rotary, temporal | height
    | width) that i falls in; no ``sections``: row 0 for every pair."""
    freq = inv_freq(dim, theta, scaling)
    if positions is None:
        ang = jnp.arange(t, dtype=_F32)[:, None] * freq[None, :]
    else:
        pos = jnp.atleast_2d(positions).astype(_F32)
        sections = tuple(sections or (dim // 2,))
        if sum(sections) != dim // 2 or len(sections) > pos.shape[0] or (
                pos.shape[1] != t):
            raise ValueError(
                f"rotary positions {pos.shape} with sections {sections} do "
                f"not cover {dim // 2} frequency pairs over {t} positions")
        axis = [a for a, n in enumerate(sections) for _ in range(n)]
        ang = pos[jnp.asarray(axis)].T * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.attention_factor != 1.0:
        cos, sin = (cos * scaling.attention_factor,
                    sin * scaling.attention_factor)
    return cos, sin


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _vmem_bytes(rows, hb, hk, dh, norm=False):
    """What a grid step keeps in VMEM: q's and k's blocks in and out as
    bf16 and the two tables' as float32, all double-buffered. ``norm``
    (a call with the heads' gains): the backward's third operand, the
    projection's q and k, as well, and a pass's 1 / rms in float32."""
    return (2 * ((3 if norm else 2) * rows * (hb + hk) * dh * 2
                 + 2 * rows * dh * 4)
            + (_PASS_ROWS * max(hb, hk) * dh * 4 if norm else 0))


def rope_tile(b, t, h, dh, rotary_dim, interleaved, dtype, hk=None,
              backend=None, on_mesh=None, periods=1, norm=False):
    """-> (rows, hb): the positions and the heads of q one grid step of
    ``rope.*`` works on, or None where the call runs as the XLA form: no
    TPU backend (``backend``: None for this process's, with the
    interpreter counting as one), values that are not bf16, a program
    under a mesh (a Mosaic call is not auto-partitioned), a head of
    which only a part turns (``rotary_dim``) unless it is exactly one
    vreg of 128 lanes wide and the part is whole pairs, a head whose
    pairs are neighbours (``interleaved``), a head that is not whole
    vregs of 128 lanes, a sequence no block of rows divides, or blocks
    over the VMEM cap. ``h`` and ``hk`` (None: as many) are q's and k's
    heads. ``periods``: the positions run that many times over the row
    (index i is position i mod t / periods); a block of rows then lies
    inside one run. ``norm``: the call brings the heads' gains (the
    per-head RMSNorm in the same pass), and its backward a third block
    of q and k.

    The tile follows the shape, not a flag: all of q's heads (a block of
    the token-major side is then whole rows of q, contiguous in HBM) by
    the most of 256 .. 32 rows that divide t and fit."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    hk = h if hk is None else hk
    part = rotary_dim or dh
    if part != dh and (dh != _LANES or part % 2 or not 0 < part < dh):
        return None
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or interleaved or dh % _LANES or min(b, t, h, hk) < 1):
        return None
    if t % periods:
        return None
    for rows in _BLOCK_ROWS:
        if ((t // periods) % rows == 0
                and _vmem_bytes(rows, h, hk, dh, norm) <= _VMEM_CAP_BYTES):
            return rows, h
    return None


def tables(t, dh, theta, scaling=None, rotary_dim=None, positions=None,
           sections=None):
    """(cos, signed sin) [t, dh] float32 of positions 0 .. t - 1, the
    angles as ``_rotate`` computes them (``cos_sin``): feature i and
    i + dh / 2 share angle p * theta^(-2i / dh), or the scaling's; sin
    is [-sin | +sin], the sign the forward's swapped halves take.
    ``rotary_dim`` < dh: the same over the first rotary_dim features,
    then cos 1 and sin 0 on the features that pass. ``positions``,
    ``sections``: the fed positions' (``cos_sin``)."""
    part = rotary_dim or dh
    cos, sin = cos_sin(t, part, theta, scaling, positions, sections)
    cos, sin = (jnp.concatenate([cos, cos], -1),
                jnp.concatenate([-sin, sin], -1))
    if part == dh:
        return cos, sin
    return (jnp.concatenate([cos, jnp.ones((t, dh - part), _F32)], -1),
            jnp.concatenate([sin, jnp.zeros((t, dh - part), _F32)], -1))


def _swap_halves(x, part=None):
    """[p, dh] -> the same with its two halves of lanes exchanged; with
    ``part`` < dh (a head one vreg wide) the two halves of the first
    ``part`` lanes (what lands on the lanes behind them meets sin 0)."""
    dh = x.shape[-1]
    if part is not None:   # (``_part``: None for the whole head)
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        return jnp.where(lane < part // 2, pltpu.roll(x, dh - part // 2, 1),
                         pltpu.roll(x, part // 2, 1))
    half = dh // 2
    if half % _LANES:
        return pltpu.roll(x, half, 1)
    return jnp.concatenate([x[:, half:], x[:, :half]], axis=-1)


def _kernel(q_ref, k_ref, cos_ref, sin_ref, *rest, rows, hb, hk, dh,
            tokens_in, tokens_out, sign, part=None, eps=None, grads=False):
    """A grid step. ``rest``: the results' blocks (q's, k's). With
    ``eps`` (the per-head RMSNorm in the same pass) the gains' [1, dh]
    float32 blocks come in front of them and a float32 scratch [pass
    rows, heads dh] behind; with ``grads`` (the backward of that call)
    also the blocks of the projection's q and k in the results' layout
    behind the gains, and behind the results an (8, dh) float32 tile
    each for the partial sums of the two gains' gradients."""
    if eps is None:
        qo_ref, ko_ref = rest
        q_more = k_more = ()
    elif grads:
        (qg_ref, kg_ref, xq_ref, xk_ref, qo_ref, ko_ref, dqg_ref, dkg_ref,
         r_ref) = rest
        q_more, k_more = (qg_ref, xq_ref, dqg_ref), (kg_ref, xk_ref, dkg_ref)
    else:
        qg_ref, kg_ref, qo_ref, ko_ref, r_ref = rest
        q_more, k_more = (qg_ref,), (kg_ref,)

    def head(tokens, n, at):
        """Rows ``at`` of head ``n`` of a block, as an index."""
        if tokens:
            return (0, at, pl.ds(n * dh, dh))
        return (0, n, at, slice(None))

    def lane_sum(z):
        return jnp.sum(z, axis=-1, keepdims=True)

    def inv_rms(x_ref, tokens, heads, at):
        """1 / sqrt(mean of squares over the head + eps) of every row of
        a pass and head of ``x_ref``, as ``ops/nn_ops._rms_norm`` has it,
        into ``r_ref`` [pass rows, heads dh] across each head's lanes (a
        lane sum leaves a row's statistic on every lane of its vreg). A
        STAGE of its own in front of a pass's rotations, through VMEM:
        the XLU gives its results in the order they were asked for, so a
        head that makes its trip for the sum and its trip for the
        rotation in one chain waits for every head in front of it twice
        (1.18 / 1.55 ms a forward / backward call alone at sdar's
        [1, 8192, 32 + 4, 128]; in stages 0.45 / 0.75: my chip runs,
        PR 66). Also tried: the heads' statistics gathered one a lane for
        ONE rsqrt a pass and spread again by a masked lane sum (0.49 /
        0.85 there: two trips a vreg for one, and the XLU takes a lane
        sum about every seven cycles a unit); and, by libtpu's bundle
        count alone, the sum as a matmul with ones (the weights are
        pushed again for every tile) and the next pass's statistics
        written between this pass's rotations."""
        for n in range(heads):
            x = x_ref[head(tokens, n, at)].astype(_F32)
            r_ref[:, pl.ds(n * dh, dh)] = jnp.broadcast_to(jax.lax.rsqrt(
                lane_sum(x * x) * (1.0 / dh) + eps), (_PASS_ROWS, dh))

    def turn(src, dst, heads, gain_ref=None, x_ref=None, dgain_ref=None):
        if gain_ref is not None:
            gain = jnp.broadcast_to(gain_ref[...], (_PASS_ROWS, dh))

        def a_pass(p, carry):
            at = pl.ds(pl.multiple_of(p * _PASS_ROWS, _PASS_ROWS),
                       _PASS_ROWS)
            cos = cos_ref[at, :]
            sin = sin_ref[at, :] * sign
            if x_ref is not None:
                inv_rms(x_ref, tokens_out, heads, at)
            elif gain_ref is not None:
                inv_rms(src, tokens_in, heads, at)
            for n in range(heads):
                x = src[head(tokens_in, n, at)].astype(_F32)
                if gain_ref is not None and x_ref is None:
                    # rms_norm(x) * gain, rounded to the values' dtype
                    # where the op rms_norm in front of this one rounded
                    # its Y (and, backward, the cotangent of Y where
                    # this op's grad op rounded it): the call's results
                    # are the ops' own up to the order of a float32 lane
                    # sum, so a seed's routing and the cell's readings
                    # against its reference stay the parent's, for a
                    # pack and an unpack a vreg.
                    x = (x * r_ref[:, pl.ds(n * dh, dh)] * gain).astype(
                        dst.dtype).astype(_F32)
                y = x * cos + _swap_halves(x, part) * sin
                dst[head(tokens_out, n, at)] = y.astype(dst.dtype)
            if x_ref is None:
                return carry
            # dst holds the cotangent of rms_norm's Y, rounded; a stage
            # of its own: a head's trip for the rotation and its trip
            # for the mean, one after the other for ALL heads
            for n in range(heads):
                at_n = head(tokens_out, n, at)
                r = r_ref[:, pl.ds(n * dh, dh)]
                xhat = x_ref[at_n].astype(_F32) * r
                y = dst[at_n].astype(_F32)
                carry = carry + y * xhat
                y = y * gain
                y = r * (y - xhat * (lane_sum(y * xhat) * (1.0 / dh)))
                dst[at_n] = y.astype(dst.dtype)
            return carry

        carry = jax.lax.fori_loop(
            0, rows // _PASS_ROWS, a_pass,
            0 if x_ref is None else jnp.zeros((_PASS_ROWS, dh), _F32))
        if x_ref is not None:   # float32 all the way; XLA adds the tiles
            dgain_ref[0, 0] = functools.reduce(
                jnp.add, [carry[i:i + 8] for i in range(0, _PASS_ROWS, 8)])

    turn(q_ref, qo_ref, hb, *q_more)

    @pl.when(pl.program_id(2) == 0)
    def _():
        turn(k_ref, ko_ref, hk, *k_more)


def _specs(rows, hb, hk, dh, tokens):
    """(q's, k's) BlockSpec on a side that is token-major [b, t, h dh]
    or head-major [b, h, t, dh], for the grid (batch, blocks of rows,
    blocks of q's heads)."""
    if tokens:
        return (pl.BlockSpec((1, rows, hb * dh), lambda bi, i, j: (bi, i, j)),
                pl.BlockSpec((1, rows, hk * dh), lambda bi, i, j: (bi, i, 0)))
    return (pl.BlockSpec((1, hb, rows, dh), lambda bi, i, j: (bi, j, i, 0)),
            pl.BlockSpec((1, hk, rows, dh), lambda bi, i, j: (bi, 0, i, 0)))


@functools.partial(jax.jit, static_argnames=(
    "theta", "tile", "tokens_in", "tokens_out", "sign", "name", "interpret",
    "scaling", "rotary_dim", "periods", "eps", "sections"))
def _rope(q, k, gains=None, x=None, positions=None, *, theta, tile,
          tokens_in, tokens_out, sign, name, interpret, scaling=None,
          rotary_dim=None, periods=1, eps=None, sections=None):
    """``gains`` (q's, k's: [dh] float32) with ``eps``: the per-head
    RMSNorm in the same pass; ``x`` (the projection's q and k in the
    results' layout): the backward of that call, which also returns the
    two gains' gradients. ``positions`` [n, t] with ``sections``: the
    tables are the fed positions' (``cos_sin``), device values the
    kernel reads as it reads any table."""
    rows, hb = tile
    if tokens_in:
        (b, t, h, dh), hk = q.shape, k.shape[2]
        q, k = q.reshape(b, t, h * dh), k.reshape(b, t, hk * dh)
    else:
        (b, h, t, dh), hk = q.shape, k.shape[1]
    assert (t % periods == 0 and (t // periods) % rows == 0
            and rows % _PASS_ROWS == 0 and h % hb == 0), (
        q.shape, tile, periods)
    out_shapes = [(b, t, n * dh) if tokens_out else (b, n, t, dh)
                  for n in (h, hk)]
    # the tables of ONE run of the positions, read once a run
    run = t // periods // rows
    cos, sin = tables(t // periods, dh, theta, scaling, rotary_dim,
                      positions, sections)
    table = pl.BlockSpec((rows, dh), (lambda bi, i, j: (i % run, 0))
                         if periods > 1 else (lambda bi, i, j: (i, 0)))
    moved = (h + hk) * b * t * dh
    operands = [q, k, cos, sin]
    in_specs = [*_specs(rows, hb, hk, dh, tokens_in), table, table]
    out_shape = [jax.ShapeDtypeStruct(s, z.dtype)
                 for s, z in zip(out_shapes, (q, k))]
    out_specs = list(_specs(rows, hb, hk, dh, tokens_out))
    flops, reads = 3, 1
    if gains is not None:
        # the gains' blocks do not move: fetched once
        operands += [g.astype(_F32).reshape(1, dh) for g in gains]
        in_specs += [pl.BlockSpec((1, dh), lambda bi, i, j: (0, 0))] * 2
        flops = 8
    if x is not None:
        operands += [z.reshape(s) for z, s in zip(x, out_shapes)]
        in_specs += list(_specs(rows, hb, hk, dh, tokens_out))
        # a tile of partial sums a grid step for q's gain, one a block
        # of rows for k's
        out_shape += [jax.ShapeDtypeStruct((b, t // rows, n * 8, dh), _F32)
                      for n in (h // hb, 1)]
        out_specs += [pl.BlockSpec((1, 1, 8, dh), lambda bi, i, j: (bi, i, j, 0)),
                      pl.BlockSpec((1, 1, 8, dh), lambda bi, i, j: (bi, i, 0, 0))]
        flops, reads = 16, 2
    scratch = []
    if gains is not None:   # a pass's 1 / rms, across each head's lanes
        scratch = [pltpu.VMEM((_PASS_ROWS, max(hb, hk) * dh), _F32)]
    qo, ko, *dgains = pl.pallas_call(
        functools.partial(_kernel, rows=rows, hb=hb, hk=hk, dh=dh,
                          tokens_in=tokens_in, tokens_out=tokens_out,
                          sign=sign, part=rotary_dim, eps=eps,
                          grads=x is not None),
        name=name,
        out_shape=out_shape,
        grid=(b, t // rows, h // hb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(
                16 * 2**20,
                _vmem_bytes(rows, hb, hk, dh, x is not None) * 3 // 2)),
        cost_estimate=pl.CostEstimate(
            flops=flops * moved,
            transcendentals=0 if gains is None else moved // dh,
            bytes_accessed=(1 + reads) * q.dtype.itemsize * moved
            + 8 * t * dh),
        interpret=interpret,
    )(*operands)
    if tokens_out:
        qo, ko = qo.reshape(b, t, h, dh), ko.reshape(b, t, hk, dh)
    if x is None:
        return qo, ko
    return (qo, ko, *(d.sum((0, 1, 2)) for d in dgains))


def _part(x, rotary_dim):
    """``rotary_dim`` as the jitted call's static argument: None for the
    whole head, so that a whole-head call is one function however it
    was said."""
    return None if rotary_dim in (None, x.shape[-1]) else int(rotary_dim)


def _norm(gains, eps):
    """(gains, epsilon) as the jitted call takes them: (None, None) for
    a call without gains, whatever epsilon was said, so that such a call
    is one function."""
    return (None, None) if gains is None else (tuple(gains), float(eps))


def _sections(sections):
    """``sections`` as the jitted call's static argument."""
    return None if not sections else tuple(int(n) for n in sections)


def rope_fwd(q, k, theta, tile, tokens=False, scaling=None,
             rotary_dim=None, periods=1, gains=None, eps=None,
             positions=None, sections=None):
    """(q, k) with rotary positions 0 .. t - 1 applied, head-major
    [b, h, t, dh] (k may have fewer heads), at ``tile`` as ``rope_tile``
    gives it. ``tokens``: q and k come token-major, [b, t, h, dh]. One
    jitted function a (shape, layout): the layers of a model make the
    same call, and a step traces and lowers the kernel once for all.
    ``scaling``: a ``Yarn`` or None, what the tables hold;
    ``rotary_dim``: the leading features that turn (None: the head);
    ``periods``: the positions 0 .. t / periods - 1 run that many times
    over the row (the tables hold one run). ``gains`` (q's, k's, each
    [dh] float32) and ``eps``: every head of q and of k is first
    RMS-normalised over its dh and multiplied by its gain
    (``ops/nn_ops._rms_norm``'s arithmetic in float32, rounded to the
    values' dtype as that op's result is), in the same pass.
    ``positions`` [n, t] with ``sections``: the positions are fed
    (``cos_sin``; one run of them: ``periods`` 1)."""
    gains, eps = _norm(gains, eps)
    return _rope(q, k, gains, None, positions, theta=float(theta),
                 tile=tuple(tile),
                 tokens_in=bool(tokens), tokens_out=False, sign=1.0,
                 name="rope.fwd", interpret=bool(_INTERPRET),
                 scaling=scaling, rotary_dim=_part(q, rotary_dim),
                 periods=int(periods), eps=eps,
                 sections=_sections(sections))


def rope_bwd(dq, dk, theta, tile, tokens=False, scaling=None,
             rotary_dim=None, periods=1, gains=None, eps=None, x=None,
             positions=None, sections=None):
    """The cotangents of ``rope_fwd``'s q and k from those of its
    results (head-major): the rotation's transpose, which is the
    rotation by the negated angles, written token-major where the
    forward read so. With ``gains``, ``eps`` and ``x`` (the q and k the
    forward read, in its layout): (dq, dk, q's gain's gradient, k's
    gain's gradient) of the call with the per-head norm; the statistic
    is made again from x, the gains' gradients are float32 sums."""
    gains, eps = _norm(gains, eps)
    return _rope(dq, dk, gains, None if gains is None else tuple(x),
                 positions, theta=float(theta), tile=tuple(tile),
                 tokens_in=False, tokens_out=bool(tokens), sign=-1.0,
                 name="rope.bwd", interpret=bool(_INTERPRET),
                 scaling=scaling, rotary_dim=_part(dq, rotary_dim),
                 periods=int(periods), eps=eps,
                 sections=_sections(sections))
