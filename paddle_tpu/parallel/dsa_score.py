"""Pallas TPU kernels for a lightning indexer (ops/dsa_ops.py has the
equations; the mathematics is ``dsa_ops.score_tile``'s and
``dsa_ops.loss_row``'s), and the packing of its selection:

    I[p, s] = scale sum_j w[p, j] relu(qI[p, j] . kI[s])

``dsa.score.fwd``: the scores of ONE chunk of ``cq`` queries against every
key of the row, [cq, t] float32, which is what ``dsa_select`` bisects for
the chunk's top-k. A grid step makes the [cq, ck] tile of one block of
``ck`` keys: per index head a [cq, dI] x [dI, ck] product on the MXU, the
relu, the query's weight for that head (a column broadcast along the
lanes) and the sum over the heads in registers and VMEM. What XLA's
lowering of the same lines does and a grid step does not: the
[hI, cq, ck] float32 products of a tile go to HBM and come back for the
weighted sum (16 MB a tile of 512 x 512 at 16 heads, 1056 tiles a layer
at 16,384 positions). A tile wholly above the diagonal (every key after
every query of the chunk) is written as -inf and fetches nothing: its
index map repeats the chunk's last live block. The chunk's index is a
traced value (``dsa_select`` walks the chunks under ``lax.map``), so it
rides in as a scalar-prefetch operand.

``dsa.topk.fwd``: the top-k's THRESHOLD for one chunk's rows, the k-th
largest score of each by bisection over the float32's bits, counted over
the chunk's CAUSAL PREFIX and no more. ``dsa_select``'s form in XLA's
ops counts 32 times over a [cq, t] array whatever the chunk, because a
traced chunk index cannot shape an array; a kernel's loop can end where
the chunk's keys end. The grid walks the row's key blocks as
``dsa.score.fwd`` does (a block behind the chunk's last live one fetches
nothing); a live step turns its [cq, ck] scores into sortable keys (as
signed words, a key behind the query's own position the lowest) in a
VMEM scratch that holds the whole prefix, up to 32 MB; the last step
builds the threshold bit by bit from the top, ``_ROWS`` rows at a time:
a pass is a loop over the chunk's live blocks alone, whole vregs
compared with the candidate and added into a [rows, 128] count that is
summed across the lanes once a pass. It returns each row's threshold
and how many keys reach it; the rest of the selection (the mask, the
ties' positions where a row has a surplus of them, the packing, the
logsumexp) stays ``ops/dsa_ops.choose``'s, one pass each.

``dsa.loss.bwd``: the indexer's KL loss of one batch row TOGETHER with
its gradient (the target is detached, so dL/dI needs nothing from
upstream), one call over the causal triangle's (cq, ck) tiles, the keys
of a q-chunk the inner axis. A tile is worked on TRANSPOSED, keys on the
sublanes and queries on the lanes, so that everything a query owns (its
per-head weights, the attention's logsumexp rows, the index logsumexp)
is a row broadcast down the sublanes in the layout it has in HBM, and no
product is lhs-transposed: the index products ki qI_j^T, their relu
times the weights summed over the heads, the attention's k q_h^T for
every head with exp(. - lse_h) summed into the target, the tile's part
of the loss a query, d = (softmax_S(I) - P) / n, and then per index head
g = d where the product was positive, ONCE, from which all three
gradients come as products with the small operands: dqI_j^T = c w_j
(kI^T g), dw_j = c sum_d qI_j^T . (kI^T g), dkI^T += (c w_j qI_j^T) g^T.
The index products are made twice (16 more 64-deep products a tile
instead of 16 kept [cq, ck] arrays). dqI^T and dw^T of a q-chunk gather
in their output blocks over its tiles; dkI^T stays resident in VMEM for
the whole call. XLA's form of the same lines keeps the [hI, cq, ck] and
[h, cq, ck] float32 products of a tile in HBM.

``score_tile`` / ``topk_tile`` / ``loss_tile`` say kernel or XLA's form,
from the call's shapes, the backend and the mesh; ``pt_dsa_dispatch_total{impl}``
records it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as rope._INTERPRET: run the kernel in interpreter mode on
# the CPU so the suite reaches it.
_INTERPRET = False

_F32 = jnp.float32


def kernels_enabled() -> bool:
    """The Pallas kernel needs a TPU backend (tests reach it on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def score_tile(cq, ck, heads, dim, on_mesh=None):
    """Does ``dsa.score.fwd`` take a chunk of ``cq`` queries against
    blocks of ``ck`` keys at ``heads`` index heads of ``dim``? On a TPU,
    outside a mesh (a Mosaic call is not auto-partitioned), where the
    tile is whole sublanes by whole lane tiles (cq a multiple of 8, ck of
    128) and a head's features fit a lane tile."""
    if on_mesh is None:
        from paddle_tpu.core import interp

        on_mesh = interp.spmd_ctx() is not None
    return bool(kernels_enabled() and not on_mesh and cq % 8 == 0
                and ck % 128 == 0 and 0 < dim <= 128 and heads >= 1)


def pack_rows(chosen):
    """[.., cq, t] bool -> [.., n, t] int32, a bit a pair, n = ceil(cq /
    32) word rows: row r = i n + j is bit i of word row j (ops/dsa_ops.py's
    docstring; the bits behind row cq are 0)."""
    cq, t = chosen.shape[-2:]
    n = -(-cq // 32)
    if 32 * n != cq:
        chosen = jnp.concatenate([chosen, jnp.zeros(
            chosen.shape[:-2] + (32 * n - cq, t), bool)], axis=-2)
    bits = chosen.reshape(chosen.shape[:-2] + (32, n, t))
    at = jnp.arange(32, dtype=jnp.uint32)[:, None, None]
    words = jnp.sum(jnp.left_shift(bits.astype(jnp.uint32), at), axis=-3,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def hit_rows(words, first=0, rows=None):
    """``pack_rows`` back, as integers: [.., n, t] int32 -> [.., 32 n, t]
    int32, NONZERO where the pair's bit is set, or rows ``first`` ..
    ``first + rows`` of it (both whole multiples of n: bits first / n
    and on). The words repeated down the rows (whole sublane tiles,
    nothing is shuffled), each row behind its own bit's mask: what the
    kernels run on a block as it lies in VMEM (and transpose as 32-bit
    values where they work on keys by queries)."""
    n, t = words.shape[-2:]
    rows = 32 * n if rows is None else rows
    assert first % n == 0 and rows % n == 0, (first, rows, n)
    lead = words.shape[:-2]
    repeated = jnp.broadcast_to(words[..., None, :, :],
                                lead + (rows // n, n, t)).reshape(
                                    lead + (rows, t))
    bit = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // n + first // n
    return jnp.bitwise_and(repeated, jnp.left_shift(jnp.int32(1), bit))


def unpack_rows(words, first=0, rows=None):
    """``hit_rows`` as booleans: XLA's form of a tile's mask."""
    return hit_rows(words, first, rows) != 0


def unpack(selected, cq):
    """``Selected`` [b, (t / cq) n, t] int32, packed a chunk of ``cq``
    queries in n = ceil(cq / 32) word rows -> [b, t, t] bool: the
    selection as a mask, for the dense composition and the tests."""
    b, rows, t = selected.shape
    nq = t // cq
    return unpack_rows(selected.reshape(b, nq, rows // nq, t))[
        :, :, :cq].reshape(b, t, t)


def _last_live(c, cq, ck):
    """The last block of keys that holds a key s <= some query p of
    chunk ``c``."""
    return ((c + 1) * cq - 1) // ck


def _kernel(c_ref, qi_ref, ki_ref, w_ref, o_ref, *, scale, cq, ck, heads):
    kk = pl.program_id(0)
    live = kk <= _last_live(c_ref[0], cq, ck)

    @pl.when(live)
    def _scores():
        ki = ki_ref[...]
        acc = jnp.zeros((cq, ck), _F32)
        for j in range(heads):
            pre = jax.lax.dot_general(
                qi_ref[j], ki, (((1,), (1,)), ((), ())),
                preferred_element_type=_F32)
            acc = acc + jnp.maximum(pre, 0.0) * w_ref[:, j:j + 1]
        o_ref[...] = acc * scale

    @pl.when(jnp.logical_not(live))
    def _after():
        o_ref[...] = jnp.full((cq, ck), -jnp.inf, _F32)


@functools.partial(jax.jit, static_argnames=("scale", "ck", "interpret"))
def _score_rows(c, qi, ki, w, *, scale, ck, interpret):
    heads, cq, dim = qi.shape
    t = ki.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, cq=cq, ck=ck, heads=heads),
        name="dsa.score.fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // ck,),
            in_specs=[
                pl.BlockSpec((heads, cq, dim), lambda kk, c_: (0, 0, 0)),
                pl.BlockSpec((ck, dim), lambda kk, c_: (
                    jnp.minimum(kk, _last_live(c_[0], cq, ck)), 0)),
                pl.BlockSpec((cq, heads), lambda kk, c_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((cq, ck), lambda kk, c_: (0, kk)),
        ),
        out_shape=jax.ShapeDtypeStruct((cq, t), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * cq * t * dim, transcendentals=0,
            bytes_accessed=4 * cq * t + qi.dtype.itemsize * (
                heads * cq * dim + t * dim)),
        interpret=interpret,
    )(jnp.asarray(c, jnp.int32).reshape(1), qi, ki, w.astype(_F32))


def score_rows(c, qi, ki, w, scale, ck, interpret=None):
    """I of chunk ``c``'s queries against every key, [cq, t] float32:
    qi [hI, cq, dI] (the chunk's index queries), ki [t, dI] (the ONE
    index key head), w [cq, hI] (the chunk's per-head weights); a block
    of ``ck`` keys wholly after the chunk's queries reads -inf. One
    jitted function a shape: a model's layers make the same call.
    ``interpret``: the test hook's value as a jitted caller keyed its
    own trace on it (None: as it stands)."""
    return _score_rows(c, qi, ki, w, scale=float(scale), ck=int(ck),
                       interpret=bool(_INTERPRET if interpret is None
                                      else interpret))


# dsa.topk.fwd's row block: a pass's candidate, count and running
# threshold of this many rows stay in vector registers
_ROWS = 64
_INT_MIN = -2 ** 31


def topk_tile(cq, ck, t, on_mesh=None):
    """Does ``dsa.topk.fwd`` find the thresholds of a chunk of ``cq``
    queries over a row of ``t`` keys in blocks of ``ck``? Where
    ``dsa.score.fwd`` takes the tile (a TPU, no mesh, whole lane tiles of
    keys), the chunk is whole row blocks and a chunk's keys fit the VMEM
    scratch (32 MB)."""
    if on_mesh is None:
        from paddle_tpu.core import interp

        on_mesh = interp.spmd_ctx() is not None
    return bool(kernels_enabled() and not on_mesh and cq % _ROWS == 0
                and ck % 128 == 0 and t % ck == 0
                and 4 * cq * t <= 32 * 2**20)


def _topk_kernel(c_ref, s_ref, o_ref, keys_ref, *, cq, ck, topk):
    kk, c = pl.program_id(0), c_ref[0]
    last = _last_live(c, cq, ck)

    @pl.when(kk <= last)
    def _keys():
        # float32 -> words in the same order under a SIGNED compare
        # (dsa_ops._sortable's keys less the top bit); a key after the
        # query's own position is the lowest word
        u = jax.lax.bitcast_convert_type(s_ref[...], jnp.int32)
        key = u ^ (jnp.right_shift(u, 31) & jnp.int32(0x7FFFFFFF))
        p_at = c * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
        s_at = kk * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
        keys_ref[kk] = jnp.where(s_at <= p_at, key, jnp.int32(_INT_MIN))

    @pl.when(kk == pl.num_programs(0) - 1)
    def _bisect():
        for r0 in range(0, cq, _ROWS):
            held = c * cq + r0 + 1 + jax.lax.broadcasted_iota(
                jnp.int32, (_ROWS, 128), 0)
            want = jnp.minimum(held, topk)

            def bit(i, at, r0=r0, want=want):
                thr, reach = at
                cand = thr | jnp.left_shift(jnp.int32(1),
                                            (31 - i).astype(jnp.int32))
                signed = cand ^ jnp.int32(_INT_MIN)

                def block(j, n):
                    for l0 in range(0, ck, 128):
                        n = n + (keys_ref[j, r0:r0 + _ROWS, l0:l0 + 128]
                                 >= signed).astype(jnp.int32)
                    return n

                n = jax.lax.fori_loop(0, last + 1, block,
                                      jnp.zeros((_ROWS, 128), jnp.int32))
                n = jnp.broadcast_to(jnp.sum(
                    n, axis=1, keepdims=True, dtype=jnp.int32), (_ROWS, 128))
                keep = n >= want
                return jnp.where(keep, cand, thr), jnp.where(keep, n, reach)

            thr, reach = jax.lax.fori_loop(
                0, 32, bit, (jnp.zeros((_ROWS, 128), jnp.int32), held))
            o_ref[0, r0:r0 + _ROWS] = thr
            o_ref[1, r0:r0 + _ROWS] = reach


@functools.partial(jax.jit, static_argnames=("topk", "ck", "interpret"))
def _threshold_rows(c, scores, *, topk, ck, interpret):
    cq, t = scores.shape
    out = pl.pallas_call(
        functools.partial(_topk_kernel, cq=cq, ck=ck, topk=topk),
        name="dsa.topk.fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // ck,),
            in_specs=[pl.BlockSpec((cq, ck), lambda kk, c_: (
                0, jnp.minimum(kk, _last_live(c_[0], cq, ck))))],
            out_specs=pl.BlockSpec((2, cq, 128), lambda kk, c_: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((t // ck, cq, ck), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((2, cq, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * cq * t + 16 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=32 * cq * t, transcendentals=0,
            bytes_accessed=4 * cq * t),
        interpret=interpret,
    )(jnp.asarray(c, jnp.int32).reshape(1), scores)
    return (jax.lax.bitcast_convert_type(out[0, :, 0], jnp.uint32),
            out[1, :, 0])


def threshold_rows(c, scores, topk, ck, interpret=None):
    """Chunk ``c``'s rows of scores [cq, t] float32 (``score_rows``'s) ->
    (each row's threshold [cq] uint32: the min(p + 1, topk)-th largest
    of its keys s <= p = c cq + r as ``dsa_ops._sortable`` orders them,
    and how many of them are at or over it [cq] int32): what
    ``dsa_ops.choose``'s 32 counting passes give, counted over the
    chunk's causal prefix alone. ``interpret``: as ``score_rows``'s."""
    return _threshold_rows(c, scores, topk=int(topk), ck=int(ck),
                           interpret=bool(_INTERPRET if interpret is None
                                          else interpret))


def loss_tile(cq, ck, heads, dim, on_mesh=None):
    """Does ``dsa.loss.bwd`` take a row's loss pass in tiles of ``cq``
    queries by ``ck`` keys at ``heads`` index heads of ``dim``? On a TPU,
    outside a mesh, where a tile is whole lane tiles both ways (a tile
    is worked on transposed) and a chunk's packed selection whole
    sublane tiles of words (cq a multiple of 256)."""
    if on_mesh is None:
        from paddle_tpu.core import interp

        on_mesh = interp.spmd_ctx() is not None
    return bool(kernels_enabled() and not on_mesh and cq % 256 == 0
                and ck % 128 == 0 and 0 < dim <= 128 and dim % 8 == 0
                and heads >= 1)


_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T


def _loss_kernel(qit_ref, ki_ref, kit_ref, rows_ref, q_ref, k_ref, sel_ref,
                 loss_ref, dqit_ref, dkit_ref, dwt_ref, *, scale, attn_scale,
                 inv_n, cq, ck):
    """One (q-chunk c, k-block kk) tile, transposed: [ck, cq] arrays.
    ``rows_ref`` [hI + h + 1, cq]: a query's per-head weights, the
    attention's logsumexp a head, the index logsumexp."""
    c, kk = pl.program_id(0), pl.program_id(1)
    hi, h, hk = qit_ref.shape[0], q_ref.shape[0], k_ref.shape[0]

    @pl.when(jnp.logical_and(c == 0, kk == 0))
    def _first():
        dkit_ref[...] = jnp.zeros(dkit_ref.shape, _F32)

    @pl.when(kk == 0)
    def _chunk():
        loss_ref[...] = jnp.zeros(loss_ref.shape, _F32)
        dqit_ref[...] = jnp.zeros(dqit_ref.shape, _F32)
        dwt_ref[...] = jnp.zeros(dwt_ref.shape, _F32)

    @pl.when(kk <= _last_live(c, cq, ck))
    def _tile():
        ki, kit = ki_ref[...], kit_ref[...]
        chosen = hit_rows(sel_ref[...]).T != 0

        def pre(j):     # kI qI_j^T [ck, cq]
            return jax.lax.dot_general(ki, qit_ref[j], _NN,
                                       preferred_element_type=_F32)

        index = jnp.zeros((ck, cq), _F32)
        for j in range(hi):
            index = index + jnp.maximum(pre(j), 0.0) * rows_ref[j:j + 1, :]
        log_q = index * scale - rows_ref[hi + h:hi + h + 1, :]
        p = jnp.zeros((ck, cq), _F32)
        for head in range(h):
            s = jax.lax.dot_general(k_ref[head // (h // hk)], q_ref[head],
                                    _NT, preferred_element_type=_F32)
            p = p + jnp.exp(s * attn_scale
                            - rows_ref[hi + head:hi + head + 1, :])
        p = jnp.where(chosen, p * (1.0 / h), 0.0)
        some = p > 0.0
        loss_ref[0] += jnp.sum(jnp.where(
            some, p * (jnp.log(jnp.where(some, p, 1.0)) - log_q), 0.0),
            axis=0, keepdims=True)
        d = jnp.where(chosen, jnp.exp(log_q) - p, 0.0) * inv_n
        for j in range(hi):
            g = jnp.where(pre(j) > 0.0, d, 0.0).astype(ki.dtype)
            cw = scale * rows_ref[j:j + 1, :]                   # [1, cq]
            qit = qit_ref[j].astype(_F32)                       # [dI, cq]
            kg = jax.lax.dot_general(kit, g, _NN,
                                     preferred_element_type=_F32)
            dqit_ref[j] += cw * kg
            dwt_ref[j:j + 1, :] += scale * jnp.sum(qit * kg, axis=0,
                                                   keepdims=True)
            dkit_ref[kk] += jax.lax.dot_general(
                (qit * cw).astype(ki.dtype), g, _NT,
                preferred_element_type=_F32)


@functools.partial(jax.jit, static_argnames=(
    "scale", "attn_scale", "inv_n", "cq", "ck", "interpret"))
def _loss_rows(qi, ki, w, q, k, lse, selected, ilse, *, scale, attn_scale,
               inv_n, cq, ck, interpret):
    hi, t, di = qi.shape
    h, hk, dh = q.shape[0], k.shape[0], q.shape[2]
    nq, nk = t // cq, t // ck
    rows = jnp.concatenate([w.T.astype(_F32), lse.astype(_F32),
                            ilse[None].astype(_F32)])

    def key(c, kk):     # (a dead tile repeats the chunk's last live block)
        return jnp.minimum(kk, _last_live(c, cq, ck))

    tiles = nq * (nq + 1) // 2 * (cq // ck) if cq >= ck else nq * nk
    loss, dqit, dkit, dwt = pl.pallas_call(
        functools.partial(_loss_kernel, scale=scale, attn_scale=attn_scale,
                          inv_n=inv_n, cq=cq, ck=ck),
        name="dsa.loss.bwd",
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((hi, di, cq), lambda c, kk: (0, 0, c)),
            pl.BlockSpec((ck, di), lambda c, kk: (key(c, kk), 0)),
            pl.BlockSpec((di, ck), lambda c, kk: (0, key(c, kk))),
            pl.BlockSpec((hi + h + 1, cq), lambda c, kk: (0, c)),
            pl.BlockSpec((h, cq, dh), lambda c, kk: (0, c, 0)),
            pl.BlockSpec((hk, ck, dh), lambda c, kk: (0, key(c, kk), 0)),
            pl.BlockSpec((cq // 32, ck), lambda c, kk: (c, key(c, kk))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cq), lambda c, kk: (c, 0, 0)),
            pl.BlockSpec((hi, di, cq), lambda c, kk: (0, 0, c)),
            pl.BlockSpec((nk, di, ck), lambda c, kk: (0, 0, 0)),
            pl.BlockSpec((hi, cq), lambda c, kk: (0, c)),
        ],
        out_shape=[jax.ShapeDtypeStruct((nq, 1, cq), _F32),
                   jax.ShapeDtypeStruct((hi, di, t), _F32),
                   jax.ShapeDtypeStruct((nk, di, ck), _F32),
                   jax.ShapeDtypeStruct((hi, t), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=2 * tiles * cq * ck * (4 * hi * di + h * dh),
            transcendentals=tiles * cq * ck * (h + 2),
            bytes_accessed=q.dtype.itemsize * (
                q.size + nq * k.size // 2 + 2 * qi.size)
            + 4 * (hi * di * t + t * di) + t * t // 8),
        interpret=interpret,
    )(jnp.swapaxes(qi, 1, 2), ki, ki.T, rows, q, k, selected)
    return (jnp.sum(loss) * inv_n, jnp.swapaxes(dqit, 1, 2),
            jnp.swapaxes(dkit, 1, 2).reshape(t, di), dwt.T)


def loss_rows(qi, ki, w, q, k, lse, selected, ilse, scale, attn_scale, inv_n,
              cq, ck):
    """``dsa_ops.loss_row`` as the kernel ``dsa.loss.bwd``: one batch
    row's part of L_I and of its gradient, qi [hI, t, dI], ki [t, dI], w
    [t, hI]; the attention's q [h, t, dh], k [hk, t, dh] and logsumexp
    rows lse [h, t]; selected [t / 32, t] int32 (``pack_rows`` a chunk
    of ``cq``), ilse [t] -> (loss, dqi [hI, t, dI], dki [t, dI], dw
    [t, hI]) float32, each already times ``inv_n``."""
    return _loss_rows(qi, ki, w, q, k, lse, selected, ilse,
                      scale=float(scale), attn_scale=float(attn_scale),
                      inv_n=float(inv_n), cq=int(cq), ck=int(ck),
                      interpret=bool(_INTERPRET))
