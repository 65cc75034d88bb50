"""Pallas TPU kernels for the chunkwise gated delta rule (the mathematics
and the precision contract are ops/linear_attention_ops.py's module
docstring; this is the same chunkwise form, chunk 64).

``gdn.rule.fwd`` and ``gdn.rule.bwd``, one call a pass. A grid step
works on one key head's group of value heads and ``chunks`` chunks of
the sequence; the grid is (batch, key heads, chunk blocks) with the
last axis ``"arbitrary"``: the state S [dk, dv] of each value head is a
float32 VMEM scratch that lives from chunk to chunk and block to block
and reaches HBM only as the bf16 ``States`` the backward pass reads.
The backward kernel walks the blocks and the chunks inside one in
reverse with dS in the scratch, and recomputes what the forward made of
a chunk from q, k, v, g, beta and ``States``: nothing else is kept
between the passes and no kernel runs twice. What a grid step makes of
its chunks lives in VMEM scratch and never in HBM.

What XLA's ops cannot do and a grid step does:

- **No copy in front.** A head's 128 features are one lane tile: q, k
  [b, t, hk * dk] and v [b, t, hv * dv] are read in place by a block of
  (rows, 128) at lane-block index ``head``; a key head serves its group
  through the index map and dq, dk are summed over the group in VMEM.
  The L2 normalisation, the running sum of g, every exp and mask happen
  on the block, in float32. o alone leaves heads first, [b, hv, t, dv],
  and is handed on as its transpose: XLA keeps the gated norm behind it
  in that layout (as it did behind the XLA form) and the transpose costs
  nothing, where an o [b, t, hv * dv] paid a relayout either side of the
  norm (qwen3next-train-s8192: 24,396 -> 26,049 tokens/s for this alone;
  dO, dq, dk, dv heads first as well: no gain or a loss; my chip runs,
  PR 33).
- **One inversion a chunk, in blocks, applied as products.**
  T = (I + A)^-1 in float32, for all the chunks and heads of the grid
  step at once, two value heads side by side over the 128 lanes. The
  four 16 x 16 diagonal blocks of a triangle invert on their own: by
  forward substitution on the VPU (row j of a block's inverse is final
  after step j - 1 and is subtracted, times A[i, j], from every row
  i > j), eight blocks across the lanes, 15 steps (``_substitute``;
  until PR 47 the whole [64, 64] went this way, 63 steps on half-empty
  lanes, 2.36 ms of a pass's 5.36 / 8.61 alone at the cell's call, 0.55
  now: my chip runs, PR 47).
  Then two merges, 16 -> 32 -> 64, each the exact inverse of a 2 x 2
  block triangle, [[T11, 0], [-T22 (A21 T11), T22]], as ``HIGHEST``
  products on the MXU against a block diagonal (``_merge``). Exact to
  float32 rounding whatever the keys (no power of A or of a block is
  formed). Then
  U = T (beta V), W = T (beta K e^G) and, backward, with dU = dV' and
  dW = -dV' S^T: d(beta V) = T^T dU, d(beta K e^G) = T^T dW and
  dA = -(T^T dU) U^T - (T^T dW) W^T: no transposed solve.
- **Each per-chunk quantity is made once, in passes no chunk waits
  in.** A grid step (one key head's group, 8 chunks) is three parts
  where it was one loop until PR 56:
  1. the *pre-pass* (``_prepare``, ``_invert``, ``_apply``), for all the
     chunks at once [chunks, C, .] and not a loop: the normalised q, k,
     K K^T and Q K^T once a key head (one product against K^T); the
     gates once a value head and chunk; A, T; [U | W] = T [beta V |
     beta K e^G] as one batched product a head; the chunk's attention,
     [W; Q e^G] and K e^{G_C - G} as matmul operands and e^{G_C}: all to
     VMEM scratch (``_parts``: 2.1 MB a grid step of the cell's
     forward, 8.6 MB backward, where the gates, q, k and their
     products stay for the pass behind the loop);
  2. the *state loop*, all that is sequential: forward three products a
     head, [W; Q e^G] S (one push of S), V' = U - W S, o = (Q e^G) S +
     attn V' and S <- e^{G_C} S + (K e^{G_C - G})^T V'; backward their
     transposes around the saved state and V' (made again), dQ e^G and
     dW as one product against S^T and dS as one contraction of 128
     over [W; Q e^G]; it leaves dV' | dW and the cotangents of the
     operands in scratch. A ``fori_loop`` of ``_UNROLL`` chunks a body;
  3. backward, the *pass behind it*, again all the chunks at once: T^T
     [dV' | dW] and dA (one contraction of 256) as batched products a
     head, then what multiplies beta, G, q, k and v, with two sums
     over the lanes a head and chunk where there were six.
  What each part gave, alone at b1 t8192 hk16 hv32 on a v5e (my chip
  runs, PR 56; ms a call, forward / backward): the parent's one loop
  3.69 / 6.95; the gates, Q K^T, K K^T and the norms once and the
  passes apart, each a loop over the chunks, 3.34 / 6.50; the lane sums
  merged and the pass behind the loop one straight line 3.30 / 5.16;
  the pre-pass for all the chunks at once 3.08 / 4.97; 4 chunks a body
  of the state loop **2.80 / 4.75** (in the cell **2.34 / 3.86** of the
  parent's 3.23 / 6.07). A product that shares an operand with its
  neighbour (T against 256 lanes, dA over 256) costs what the two
  cost: a v5e's MXU pops every 8 x 128 of a result once a pass
  whatever the depth (benchmarks/gdn_candidates.py, ``*.apart``). What
  did not pay: the products of chunk c + 1 written into the state
  loop's body (an MXU takes its products in the order written, so the
  chain waits behind them: 3.18-3.40 / 6.17-6.33); 8 chunks a body
  (2.70 / 4.75, and a third more to trace).

float32: g, its running sums and their exps, beta, the normalisation,
A, T, its merges and its application (``precision=HIGHEST``), U, the
state and dS.
bf16 operands with float32 accumulation where the chunked XLA form has
them: K K^T, Q K^T, W, Q e^G, K e^{G_C - G}, the chunk's attention, V',
the state as an operand, and the cotangents' products.

``gdn_tile`` is the one function that says tile or the chunked XLA form
(ops/linear_attention_ops._chunk_parts / _chunk_scan, unchanged), from
the call's own shapes, the dtype, the backend and the mesh;
``pt_linear_attention_dispatch_total{impl}`` records its answer.

**A decay a key feature** (Kimi Delta Attention, arXiv:2510.26692: g
[b, t, hv, dk], S_t = Diag(exp(g_t)) S_{t-1}; the RANK of g decides):
the same two calls under the names ``kda.rule.fwd`` / ``kda.rule.bwd``
(family ``kda``), where ``kda_tile`` gives a tile (``gdn_tile``'s
conditions and hk == hv: every head has keys of its own). The inversion
(``_substitute``, ``_merge``, ``_invert``), ``_apply`` and the state
chain are the ONE copy above; a grid step takes two heads, so that the
inversion's two triangles side by side over the lanes are two heads of
different keys. What is the vector rule's own:

- *the pre-pass* (``_prepare_kda``): g is read in place, a float32 block
  [rows, heads * 128] beside q and k; its running sum down a chunk is
  six sublane rolls (``_running``). With a decay a feature the factor
  exp(G_id - G_jd) sits INSIDE the contraction over d, and
  ``(K e^G)(K e^-G)^T`` ends at e^88 where G passes -100 inside a chunk
  at the family's initialisation. ``_levels`` halves the chunk instead:
  rows i > j part at the one block size s with i // 2s == j // 2s and
  i // s == j // s + 1, the first row r of i's block lies between them,
  and exp(G_i - G_j) = exp(G_i - G_r) exp(G_r - G_j) puts a factor <= 1
  on each operand: six products [Q E; K E] (K F)^T a head and chunk,
  masked to their level's blocks, give P = lower(Q K^T . D) and
  A / beta at once, exactly, whatever the gates (no clamp; G_r reaches
  its block by log2(s) sublane rolls). Q . e^G, beta K . e^G and
  K . e^{G_C - G} are element-wise products; e^{G_C} multiplies the ROWS
  of the state and is kept as a [dk, dv] block (``_down_rows``: one
  transpose a chunk in the pre-pass, none in the chain);
- *the pass behind the backward loop* (``_behind_kda``): two products a
  level give dq and dk through the decays, and dG = q . dq + k .
  (dk_row - dk_col) over the same terms needs no third; the element-wise
  operands give theirs feature by feature; dg [b, t, hv * dk] float32
  is dG's running sum up the chunk.

The forward saves ``States`` and nothing of size t x C x dk; the
backward recomputes once, as ``gdn.rule.bwd`` does. Alone at
kimilinear-train-s4096's call on a v5e (b1 t4096, 32 heads of 128: 256
grid steps of 2 heads x 8 chunks; benchmarks/kda_rule_time.py, my chip
run, PR 64; ms a call, forward / backward): ``kda.rule.*`` **2.28 /
4.44**, beside ``gdn.rule.*`` at the same shape with one decay a head
(hk = hv = 32) 2.15 / 3.32 and the chunked XLA form of the vector rule
16.8 forward; both kernels within 0.5% of the float32 recurrence's
largest entry, Out and the five gradients, with G at -574 inside a
chunk and with mild gates.
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

CHUNK = 64      # the chunk the kernels are written for (gdn_chunk)
_LANES = 128    # dk and dv: a head is one lane tile
_BLOCK = 16     # T's diagonal blocks by substitution, the rest by merges
# Chunks a grid step: 8 chunks are 512 rows of q, k, v a block, and 8
# rows of g and beta [.., chunks, 64] are one float32 sublane tile.
_STEP_CHUNKS = 8
# Chunks of the state chain a loop body: the next chunk's loads and casts
# fill the slots its products' latencies leave (my chip runs, PR 56:
# 1 / 2 / 4 / 8 a body 3.08 / 2.90 / 2.80 / 2.70 ms a forward call and
# 4.99 / 4.84 / 4.75 / 4.75 backward; a chunk more in a body is 48
# equations more to trace forward and 87 backward, every layer).
_UNROLL = 4
# What a call's blocks and scratch may take of the v5e's 128 MiB of VMEM
# (the calls raise Mosaic's scoped limit to what they need, _vmem_limit).
_VMEM_CAP_BYTES = 48 * 2**20

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _under_mesh() -> bool:
    from paddle_tpu.core import interp

    return interp.spmd_ctx() is not None


def _parts(heads, chunks, dk, dv, dtype, backward, vector=False):
    """name -> (shape, dtype) of what a grid step makes ONCE of its
    chunks and keeps in VMEM for the loops behind (the module
    docstring's pre-pass), one entry a matrix (``_mat``) or a chunk:
    ``uw`` [beta V | beta K e^G], then [U | W] in its place; the chunk's
    attention, Q e^G and K e^{G_C - G} as matmul operands; e^{G_C} over
    a sublane tile. The backward pass also keeps the decay, beta, e^G
    and e^{G_C - G} (over all the lanes: no lane broadcast at a use),
    the normalised q, k and 1 / |x|, K K^T and Q K^T, and what its state
    loop leaves for the pass behind it: ``dd`` [dV' | dW], then
    [T^T dV' | T^T dW] in its place, dA, and the cotangents of the
    chunk's attention, Q e^G, K e^{G_C - G} and e^{G_C}.

    ``vector`` (``kda.rule.*``: a decay a key feature, every head its
    own keys): e^{G_C} is a factor a ROW of the state and is kept over
    the whole [dk, dv]; backward, the running sum G itself (the levels'
    factors are made again from it), K' = A / beta where K K^T . D
    stood, q, k and their norms a head, and ds . S whole (its sum over
    a row is the pass behind the loop's)."""
    mats = heads * chunks
    mat, tri = (mats, CHUNK, _LANES), (mats, CHUNK, CHUNK)
    whole = (mats, dk, dv) if vector else (mats, 8, _LANES)
    parts = dict(uw=((mats, CHUNK, dv + dk), _F32), attn=(tri, dtype),
                 wq=((mats, 2 * CHUNK, dk), dtype),
                 kd=((mats, CHUNK, dk), dtype), dec=(whole, _F32))
    if backward and vector:
        parts.update(
            beta=(mat, _F32), eg=(mat, _F32), ekd=(mat, _F32),
            gc=(mat, _F32), kn=(mat, _F32), yq=(mat, _F32), rk=(mat, _F32),
            rq=(mat, _F32), kk=(tri, _F32),
            dd=((mats, CHUNK, dv + dk), _F32), da=(tri, _F32),
            dattn=(tri, _F32), dqg=((mats, CHUNK, dk), _F32),
            dkd=((mats, CHUNK, dk), _F32), ddec=(whole, _F32))
    elif backward:
        row = (chunks, CHUNK, _LANES)
        parts.update(
            decay=(tri, _F32), beta=(mat, _F32), eg=(mat, _F32),
            ekd=(mat, _F32), kn=(row, _F32), yq=(row, _F32), rk=(row, _F32),
            rq=(row, _F32), kk=((chunks, CHUNK, CHUNK), _F32),
            qk=((chunks, CHUNK, CHUNK), _F32),
            dd=((mats, CHUNK, dv + dk), _F32), da=(tri, _F32),
            dattn=(tri, _F32), dqg=((mats, CHUNK, dk), _F32),
            dkd=((mats, CHUNK, dk), _F32), ddec=((mats, 8, _LANES), _F32))
    return parts


def _tiled_bytes(shape, dtype):
    """What an array takes of VMEM: its last axis padded to the 128
    lanes, the one before to a sublane tile (8 rows of 32 bits)."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    tile = 8 * 4 // item
    n = -(-rows // tile) * tile * -(-lanes // _LANES) * _LANES * item
    for d in lead:
        n *= d
    return n


def _vmem_bytes(heads, chunks, dk, dv, vector=False):
    """What one grid step of the backward kernel (the larger) keeps in
    VMEM: its blocks double-buffered (q, k, dq, dk; v, dO, dv; the
    states; g, beta and their gradients padded to a sublane tile) and
    the scratch (``_scratch``). ``vector``: q, k and their gradients
    ``heads`` wide, g and dg float32 blocks as wide."""
    rows = chunks * CHUNK
    blocks = (4 * rows * dk * 2 + 3 * rows * heads * dv * 2
              + chunks * heads * dk * dv * 2
              + 4 * heads * max(chunks, 8) * _LANES * 4)
    if vector:
        blocks += 4 * rows * (heads - 1) * dk * 2 + 2 * rows * heads * dk * 4
    scratch = sum(_tiled_bytes(x.shape, x.dtype) for x in _scratch(
        heads, chunks, dk, dv, jnp.bfloat16, True, vector))
    return 2 * blocks + scratch


def _vmem_limit(heads, chunks, dk, dv, vector=False):
    """Mosaic's scoped limit for a call at this tile: the blocks and the
    scratch, and as much again for the values of a loop body, not under
    its default of 16 MiB."""
    return max(16 * 2**20, 2 * _vmem_bytes(heads, chunks, dk, dv, vector))


def gdn_tile(t, hk, hv, dk, dv, chunk, dtype, backend=None, on_mesh=None):
    """-> (heads, chunks): the value heads (one key head's group) and
    the chunks of one grid step of ``gdn.rule.*``, or None where the
    call runs as the chunked XLA form: no TPU backend (``backend``: None
    for this process's, with the interpreter counting as one), operands
    that are not bf16, a program under a mesh (a Mosaic call is not
    auto-partitioned), dk or dv other than the 128 lanes, a chunk other
    than the 64 the kernels are written for, value heads that are not a
    multiple of the key heads, or a group too large for the VMEM cap.

    The tile follows the shape, not a flag: a key head's whole group in
    a step (its normalised q, k and their products are shared, dq and dk
    summed in VMEM, and the heads' state chains interleave), 8 chunks a
    step, or all of a sequence that has fewer (it is padded to a
    multiple)."""
    on_tpu = kernels_enabled() if backend is None else backend == "tpu"
    if on_mesh is None:
        on_mesh = _under_mesh()
    if (not on_tpu or on_mesh or jnp.dtype(dtype) != jnp.bfloat16
            or dk != _LANES or dv != _LANES or chunk != CHUNK
            or hk < 1 or hv % hk or t < 1):
        return None
    heads = hv // hk
    chunks = min(_STEP_CHUNKS, -(-t // CHUNK))
    if _vmem_bytes(heads, chunks, dk, dv) > _VMEM_CAP_BYTES:
        return None
    return heads, chunks


def kda_tile(t, hk, hv, dk, dv, chunk, dtype, backend=None, on_mesh=None):
    """``gdn_tile`` for a decay a key feature (g [b, t, hv, dk]): ->
    (heads, chunks) of one grid step of ``kda.rule.*``, or None for the
    chunked XLA form, on ``gdn_tile``'s conditions and one more: every
    value head has keys of its own (hk == hv; Kimi Delta Attention has
    no group). With no group to share a key head, a grid step takes TWO
    heads where the count is even: the inversion works on two triangles
    side by side over the 128 lanes (``_slot``), and here they are two
    heads of different keys."""
    if hk != hv or gdn_tile(t, hk, hv, dk, dv, chunk, dtype, backend,
                            on_mesh) is None:
        return None
    heads = 2 if hv % 2 == 0 else 1
    chunks = min(_STEP_CHUNKS, -(-t // CHUNK))
    if _vmem_bytes(heads, chunks, dk, dv, True) > _VMEM_CAP_BYTES:
        return None
    return heads, chunks


# ---------------------------------------------------------------------------
# what a grid step computes of a chunk
# ---------------------------------------------------------------------------


def _dot(a, b, ca, cb, precision=None):
    """a x b contracting a's axis ``ca`` with b's ``cb``, float32 out."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=precision,
                               preferred_element_type=_F32)


def _bdot(a, b, ca, cb, precision=None):
    """``_dot`` of every pair a[i], b[i] (the axes count the leading
    one)."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                               precision=precision,
                               preferred_element_type=_F32)


def _iotas():
    shape = (CHUNK, CHUNK)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _col(row, ii, jj):
    """[.., 1, C] -> [.., C, 1]: the row's entries down a column (a
    masked sum over the lanes: no transpose of a 1-row array)."""
    return jnp.sum(jnp.where(ii == jj, row, 0.0), axis=-1, keepdims=True)


def _l2(x, eps):
    """float32 x, x / |x| and 1 / |x| over the last axis (HF's l2norm:
    x * rsqrt(sum(x^2) + eps))."""
    xf = x.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * r, r


def _gates(g_row, b_row, ii, jj):
    """From the chunks' g and beta as rows [n, 1, C]: the running sum of
    g down a column, beta down a column, D = exp(G_i - G_j) for i >= j
    (the inner where: exp of a masked, positive difference overflows),
    exp(G), exp(G_C - G) and exp(G_C) [n, 1, 1]."""
    lower = ii >= jj
    gc = jnp.sum(jnp.where(lower, g_row, 0.0), axis=-1, keepdims=True)
    gc_row = jnp.sum(jnp.where(ii <= jj, _col(g_row, ii, jj), 0.0),
                     axis=-2, keepdims=True)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gc - gc_row, 0.0)),
                      0.0)
    g_last = jnp.sum(g_row, axis=-1, keepdims=True)
    return dict(beta=_col(b_row, ii, jj), decay=decay, eg=jnp.exp(gc),
                ekd=jnp.exp(g_last - gc), dec=jnp.exp(g_last))


def _rows(c):
    """The rows of chunk ``c`` in a block of q, k, v."""
    if isinstance(c, int):
        return pl.ds(c * CHUNK, CHUNK)
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _slot(r, c, chunks):
    """Where value head ``r``'s chunk ``c`` lies: (the pair's index in
    the scratch of A, its half of the lanes), which is T's index too. A
    key head's value heads go two and two, an odd last one alone."""
    return r // 2 * chunks + c, r % 2


def _mat(r, c, chunks):
    """Value head ``r``'s chunk ``c`` among a grid step's matrices
    (``_parts``' leading axis): a head's chunks side by side."""
    return r * chunks + c


def _mats(r, chunks):
    """All of value head ``r``'s matrices (``_mat``)."""
    return slice(r * chunks, (r + 1) * chunks)


def _pairs(r, chunks):
    """The pairs that hold value head ``r``'s chunks (``_slot``)."""
    first = _slot(r, 0, chunks)[0]
    return slice(first, first + chunks)


def _by_chunk(ref, chunks, cols=slice(None)):
    """A block of q, k or v [chunks * C, .] -> [chunks, C, .]."""
    x = ref[:, cols]
    return x.reshape(chunks, CHUNK, x.shape[-1])


def _gate_rows_of(ref, r, chunks):
    """Head ``r``'s rows of a block of g or beta [heads, chunks, C] ->
    [chunks, 1, C]."""
    return ref[r].reshape(chunks, 1, CHUNK)


def _prepare(q_ref, k_ref, v_ref, g_ref, beta_ref, a_ref, p, *, heads,
             chunks, eps, scale):
    """The pre-pass: what depends on no state, for all the chunks of
    the grid step at once [chunks, C, .]: once a key head the normalised
    q, k, K K^T and Q K^T (one product against K^T); once a value head
    the gates, then a_ref[pair] <- A = strictly_lower(beta_i (k_i . k_j)
    D_ij), two value heads side by side over the 128 lanes (``_slot``;
    zeros beside an odd last head), and ``_parts``' operands of the
    loops behind. No chunk waits for another and nothing is a loop."""
    dtype = q_ref.dtype
    dv = v_ref.shape[-1] // heads
    ii, jj = _iotas()
    backward = "decay" in p

    def wide(col):
        return jnp.broadcast_to(col, (chunks, CHUNK, _LANES))

    yq, rq = _l2(_by_chunk(q_ref, chunks), eps)
    kn, rk = _l2(_by_chunk(k_ref, chunks), eps)
    qn = yq * scale
    kb = kn.astype(dtype)
    both = _bdot(jnp.concatenate([qn.astype(dtype), kb], axis=1), kb, 2, 2)
    qk, kk = both[:, :CHUNK], both[:, CHUNK:]
    if backward:
        p["kn"][...], p["yq"][...] = kn, yq
        p["rk"][...], p["rq"][...] = wide(rk), wide(rq)
        p["kk"][...], p["qk"][...] = kk, qk
    for r0 in range(0, heads, 2):
        halves = []
        for r in range(r0, min(r0 + 2, heads)):
            ms = _mats(r, chunks)
            gt = _gates(_gate_rows_of(g_ref, r, chunks),
                        _gate_rows_of(beta_ref, r, chunks), ii, jj)
            beta, decay, eg, ekd = (gt[x] for x in (
                "beta", "decay", "eg", "ekd"))
            halves.append(jnp.where(ii > jj, beta * kk * decay, 0.0))
            # (the decay is 0 above the diagonal: no mask)
            p["attn"][ms] = (qk * decay).astype(dtype)
            p["wq"][ms, CHUNK:] = (qn * eg).astype(dtype)
            p["kd"][ms] = (kn * ekd).astype(dtype)
            v = _by_chunk(v_ref, chunks, slice(r * dv, (r + 1) * dv))
            p["uw"][ms] = jnp.concatenate(
                [beta * v.astype(_F32), (beta * eg) * kn], axis=2)
            p["dec"][ms] = jnp.broadcast_to(gt["dec"], (chunks, 8, _LANES))
            if backward:
                p["decay"][ms] = decay
                p["beta"][ms], p["eg"][ms] = wide(beta), wide(eg)
                p["ekd"][ms] = wide(ekd)
        halves += [jnp.zeros_like(kk)] * (2 - len(halves))
        a_ref[_pairs(r0, chunks)] = jnp.concatenate(halves, axis=2)


def _positions(chunks):
    """Each row's position in its chunk, [chunks, C, 128]."""
    return jax.lax.broadcasted_iota(jnp.int32, (chunks, CHUNK, _LANES), 1)


def _running(x, pos, back=False):
    """x [chunks, C, 128] -> its running sum down a chunk's rows
    (``back``: up them, row i the sum of rows i ..): six sublane rolls,
    float32 adds in the order of a tree."""
    s = 1
    while s < CHUNK:
        if back:
            x = x + jnp.where(pos < CHUNK - s,
                              pltpu.roll(x, CHUNK - s, axis=1), 0.0)
        else:
            x = x + jnp.where(pos >= s, pltpu.roll(x, s, axis=1), 0.0)
        s *= 2
    return x


def _levels(gc, pos, ii, jj):
    """The halving of ``ops/linear_attention_ops._decayed_products`` on
    the running sum G [chunks, C, 128] of a head's chunks: for each
    block size s = 1, 2, .. C / 2 -> (E, F [chunks, C, 128], the level's
    mask [C, C]). Rows i > j part at ONE level, the s with i // 2s ==
    j // 2s and i // s == j // s + 1 (i in an odd block, j in the even
    one in front); with r the first row of i's block, j < r <= i and
    exp(G_i - G_j) = E_i F_j, E_i = exp(G_i - G_r), F_j = exp(G_r - G_j):
    no exponent is above 0 whatever the gates (rows a level does not
    use get exp(0)). G_r reaches the rows of its block by log2(s)
    sublane rolls and the block in front by one more."""
    out, k = [], 0
    while 1 << k < CHUNK:
        s = 1 << k
        odd = (pos >> k) & 1 == 1
        first = jnp.where(pos & (s - 1) == 0, gc, 0.0)
        d = 1
        while d < s:
            first = first + pltpu.roll(first, d, axis=1)
            d *= 2
        e = jnp.exp(jnp.where(odd, gc - first, 0.0))
        f = jnp.exp(jnp.where(
            odd, 0.0, pltpu.roll(first, CHUNK - s, axis=1) - gc))
        level = ((ii >> k) & 1 == 1) & (ii >> k == (jj >> k) + 1)
        out.append((e, f, level))
        k += 1
    return out


def _down_rows(row):
    """[chunks, 1, 128] -> [chunks, 128, 128]: entry d of the row all
    along ROW d (a factor a row of the state [dk, dv])."""
    wide = jnp.broadcast_to(row, (row.shape[0], _LANES, _LANES))
    return jnp.swapaxes(wide, 1, 2)


def _prepare_kda(q_ref, k_ref, v_ref, g_ref, beta_ref, a_ref, p, *, heads,
                 chunks, eps, scale):
    """``_prepare`` for a decay a key feature: g_ref [chunks * C, heads *
    dk] float32 read in place beside q and k, every head its own q and
    k. Per head: the running sum G [chunks, C, 128] (``_running``), then
    P_ij = sum_d q_id k_jd exp(G_id - G_jd) (i >= j) and K' the same of
    k, k (i > j) one level of halving at a time (``_levels``: a product
    of [Q E; K E] against K F a level, masked to the level's blocks; the
    diagonal of P is q_i . k_i), a_ref[pair] <- A = beta K', two heads
    side by side, and ``_parts``' operands with every exp(G) a [C, dk]
    array: Q . e^G, beta K . e^G, K . e^{G_C - G}, and e^{G_C} down the
    rows of a [dk, dv] block."""
    dtype = q_ref.dtype
    dk, dv = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    ii, jj = _iotas()
    pos = _positions(chunks)
    backward = "gc" in p
    for r0 in range(0, heads, 2):
        halves = []
        for r in range(r0, min(r0 + 2, heads)):
            ms = _mats(r, chunks)
            cols = slice(r * dk, (r + 1) * dk)
            yq, rq = _l2(_by_chunk(q_ref, chunks, cols), eps)
            kn, rk = _l2(_by_chunk(k_ref, chunks, cols), eps)
            qn = yq * scale
            gc = _running(_by_chunk(g_ref, chunks, cols), pos)
            beta = _col(_gate_rows_of(beta_ref, r, chunks), ii, jj)
            attn = jnp.where(
                ii == jj, jnp.sum(qn * kn, axis=-1, keepdims=True), 0.0)
            kk = jnp.zeros_like(attn)
            for e, f, level in _levels(gc, pos, ii, jj):
                both = _bdot(
                    jnp.concatenate([qn * e, kn * e], axis=1).astype(dtype),
                    (kn * f).astype(dtype), 2, 2)
                attn = attn + jnp.where(level, both[:, :CHUNK], 0.0)
                kk = kk + jnp.where(level, both[:, CHUNK:], 0.0)
            eg = jnp.exp(gc)
            g_last = gc[:, CHUNK - 1:, :]
            ekd = jnp.exp(g_last - gc)
            halves.append(beta * kk)
            p["attn"][ms] = attn.astype(dtype)
            p["wq"][ms, CHUNK:] = (qn * eg).astype(dtype)
            p["kd"][ms] = (kn * ekd).astype(dtype)
            v = _by_chunk(v_ref, chunks, slice(r * dv, (r + 1) * dv))
            p["uw"][ms] = jnp.concatenate(
                [beta * v.astype(_F32), (beta * eg) * kn], axis=2)
            p["dec"][ms] = _down_rows(jnp.exp(g_last))
            if backward:
                shape = (chunks, CHUNK, _LANES)
                p["kn"][ms], p["yq"][ms], p["gc"][ms] = kn, yq, gc
                p["rk"][ms] = jnp.broadcast_to(rk, shape)
                p["rq"][ms] = jnp.broadcast_to(rq, shape)
                p["beta"][ms] = jnp.broadcast_to(beta, shape)
                p["eg"][ms], p["ekd"][ms], p["kk"][ms] = eg, ekd, kk
        halves += [jnp.zeros_like(halves[0])] * (2 - len(halves))
        a_ref[_pairs(r0, chunks)] = jnp.concatenate(halves, axis=2)


def _columns(d):
    """d [pairs, rows, 128] -> 16 arrays: column j of every block of 16
    lanes over all of its block's lanes. A lane broadcast cannot stop
    at a block's edge, and one column at a time costs five lane rolls a
    vreg; halving the distance, each array gives the two that hold the
    lower and the upper half of its columns (its neighbours 8, 4, 2, 1
    lanes away, either side): two rolls an array made."""
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 2)
    cols, s = [d], _BLOCK // 2
    while s:
        low = lane % (2 * s) < s
        cols = [y for x in cols for y in (
            jnp.where(low, x, pltpu.roll(x, s, axis=2)),
            jnp.where(low, pltpu.roll(x, _LANES - s, axis=2), x))]
        s //= 2
    return cols


def _substitute(a_ref, x_ref):
    """x_ref[pair] [16, 128] <- the inverses of I + the eight diagonal
    16 x 16 blocks of a_ref[pair] (block b of the left matrix at lanes
    16 b, of the right at 64 + 16 b), by forward substitution on every
    block of the grid step at once: row j of a block's inverse, final
    since step j - 1, times A[i, j] leaves every row i > j. A's column
    j, which each block needs across its own 16 lanes, comes from
    ``_columns`` (the XLU's work; nothing of it waits for the
    substitution). Rows above the sublane tile of row j hold
    A[i, j] = 0: skipped whole."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 2)
    x_ref[...] = (rows == lane % _BLOCK).astype(_F32)
    d = a_ref[:, :_BLOCK, :]
    for b in range(1, CHUNK // _BLOCK):
        d = jnp.where(lane // _BLOCK % (CHUNK // _BLOCK) == b,
                      a_ref[:, b * _BLOCK:(b + 1) * _BLOCK, :], d)
    for j, col in enumerate(_columns(d)[:-1]):
        r0 = j // 8 * 8
        x_ref[:, r0:, :] = (x_ref[:, r0:, :]
                            - col[:, r0:, :] * x_ref[:, j:j + 1, :])


def _diagonal(y, size, below):
    """y [pairs, size, 128] -> [pairs, 128, 128]: each y's even blocks
    of ``size`` lanes down the diagonal (``below``: each one block of
    rows further down), zeros elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 2) // size
    zeros = jnp.zeros_like(y)
    rows = [jnp.where(lane == q, y, zeros) if q % 2 == 0 else zeros
            for q in range(_LANES // size)]
    if below:
        rows = rows[-1:] + rows[:-1]
    return jnp.concatenate(rows, axis=1)


def _times_blocks(x, y, size, below):
    """x, y [pairs, size, 128]: x times y's even blocks of ``size``
    lanes, each its own product: the lanes of even block q of the
    result are x's lanes of block q (``below``: of block q + 1) times
    y's block q. One product a pair against ``_diagonal``, float32 as
    every application of T."""
    return jax.lax.dot_general(
        x, _diagonal(y, size, below), (((2,), (1,)), ((0,), (0,))),
        precision=_HIGHEST, preferred_element_type=_F32)


def _merge(a_ref, y, size):
    """y [pairs, size, 128]: the inverses of I + the diagonal blocks of
    ``size`` of each pair's two triangles, block q at lanes size q ->
    [pairs, 2 size, 128]: of the blocks of 2 size. The inverse of a
    2 x 2 block triangle is exact in its blocks, [[T11, 0], [-T22 (A21
    T11), T22]] (no power of A is formed), and in this layout T11, A21
    (A's rows under each T11, at its lanes) and the result share their
    lanes and T22 sits one block on: no lane moves. Every pair's first
    product, then every pair's second: an MXU takes its products in
    the order they are written, and a pair's second waits for its first
    (a pair at a time the inversion takes 1.19 ms a call for 0.55:
    benchmarks/gdn_candidates.py, my chip run, PR 47)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 2) // size
    a21 = a_ref[:, size:2 * size, :]
    for i in range(1, CHUNK // (2 * size)):     # the i-th pair of blocks
        a21 = jnp.where(lane // 2 % (CHUNK // (2 * size)) == i,
                        a_ref[:, (2 * i + 1) * size:(2 * i + 2) * size, :],
                        a21)
    x = _times_blocks(a21, y, size, False)                  # A21 T11
    x = _times_blocks(y, x, size, True)                     # T22 (A21 T11)
    even = lane % 2 == 0
    return jnp.concatenate([jnp.where(even, y, 0.0),
                            jnp.where(even, -x, y)], axis=1)


def _invert(a_ref, t_ref, x_ref):
    """t_ref[pair, half] <- (I + A)^-1 for each of the two strictly
    lower A [C, C] of every a_ref[pair] [C, 2 C]: the four diagonal
    blocks of 16 by substitution (``_substitute``), then two merges,
    16 -> 32 -> 64 (``_merge``)."""
    _substitute(a_ref, x_ref)
    t, size = x_ref[...], _BLOCK
    while size < CHUNK:
        t, size = _merge(a_ref, t, size), 2 * size
    for half in range(2):
        t_ref[:, half] = t[:, :, half * CHUNK:(half + 1) * CHUNK]


def _apply(t_ref, p, *, heads, chunks):
    """[U | W] = T [beta V | beta K e^G] in its place for every matrix
    of the grid step (two right-hand sides side by side): a batched
    product a head over its chunks, float32 as every application of T,
    written for all the matrices at once so that none waits for its
    neighbour's (``_merge``); W as a matmul operand over Q e^G."""
    dv = p["uw"].shape[-1] - p["wq"].shape[-1]
    for r in range(heads):
        ms = _mats(r, chunks)
        uw = _bdot(_t_of(t_ref, r, chunks), p["uw"][ms], 2, 1, _HIGHEST)
        p["uw"][ms] = uw
        p["wq"][ms, :CHUNK] = uw[:, :, dv:].astype(p["wq"].dtype)


def _t_of(t_ref, r, chunks):
    """Head ``r``'s T of every chunk [chunks, C, C] (``_slot``)."""
    return t_ref[_pairs(r, chunks), r % 2]


def _in_turn(n, body):
    """``body(i)`` for i in 0 .. n - 1, in turn: a ``fori_loop`` whose
    body holds ``_UNROLL`` of them (the last iterations of an n that is
    no multiple stand behind it)."""
    whole = n // _UNROLL
    if whole > 1:
        def step(i, carry):
            for j in range(_UNROLL):
                body(_UNROLL * i + j)
            return carry

        jax.lax.fori_loop(0, whole, step, None)
    else:
        whole = 0
    for i in range(whole * _UNROLL, n):
        body(i)


def _through_t(t, dd):
    """T^T [dV' | dW] of every matrix [mats, C, .]: d(beta V) and
    d(beta K e^G) side by side, float32 as every application of T."""
    return _bdot(t, dd, 1, 1, _HIGHEST)


def _d_triangle(dr, uw):
    """dA = -(T^T dV') U^T - (T^T dW) W^T of every matrix as ONE
    contraction over [. | .] and [U | W], 256 deep (the mask is its
    reader's)."""
    return -_bdot(dr, uw, 2, 2, _HIGHEST)


# ---------------------------------------------------------------------------
# gdn.rule.fwd
# ---------------------------------------------------------------------------


def _decay_of(p, m, vector):
    """e^{G_C} of matrix ``m`` as the state's factor: one a head over a
    row of lanes, or (``vector``) one a row of the state [dk, dv]."""
    return p["dec"][m] if vector else p["dec"][m, :1]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                s_ref, a_ref, t_ref, x_ref, *parts, heads, chunks, eps,
                scale, vector=False):
    dtype = q_ref.dtype
    dv = v_ref.shape[-1] // heads
    dk = q_ref.shape[-1] // heads if vector else q_ref.shape[-1]
    p = dict(zip(_parts(heads, chunks, dk, dv, dtype, False, vector), parts))

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    (_prepare_kda if vector else _prepare)(
        q_ref, k_ref, v_ref, g_ref, beta_ref, a_ref, p, heads=heads,
        chunks=chunks, eps=eps, scale=scale)
    _invert(a_ref, t_ref, x_ref)

    def chain(c):               # the state chain: three products a head
        rows = _rows(c)
        for r in range(heads):
            m = _mat(r, c, chunks)
            s = s_ref[r]
            sb = s.astype(dtype)
            states_ref[c, r] = sb
            ws_qs = _dot(p["wq"][m], sb, 1, 0)      # W S over (Q e^G) S
            vn = (p["uw"][m, :, :dv] - ws_qs[:CHUNK]).astype(dtype)
            o = ws_qs[CHUNK:] + _dot(p["attn"][m], vn, 1, 0)
            o_ref[r, rows, :] = o.astype(o_ref.dtype)
            s_ref[r] = (s * _decay_of(p, m, vector)
                        + _dot(p["kd"][m], vn, 0, 0))

    _apply(t_ref, p, heads=heads, chunks=chunks)
    _in_turn(chunks, chain)


def _padded(x, axis, size):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


def _gate_rows(x, n_chunks):
    """g or beta [b, t, hv] -> float32 [b, hv, n, C], zeros behind t."""
    x = jnp.moveaxis(x.astype(_F32), 2, 1)
    b, hv, _ = x.shape
    return _padded(x, 2, n_chunks * CHUNK).reshape(b, hv, n_chunks, CHUNK)


def _operands(q, k, v, g, beta, tile):
    """The op's inputs as the kernels' blocks read them: the heads
    folded into the lanes (no copy), the gates chunked and heads first
    (a megabyte; a decay a key feature [b, t, hv, dk] folded as q is,
    float32, and read in place), everything padded to whole grid steps
    with zeros (beta 0 writes nothing, g 0 forgets nothing, q 0 reads
    nothing)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    chunks = tile[1]
    n = -(-t // CHUNK)
    n_pad = -(-n // chunks) * chunks
    tp = n_pad * CHUNK
    def gates(g):
        if g.ndim == q.ndim:
            return _padded(g.astype(_F32), 1, tp).reshape(b, tp, hv * dk)
        return _gate_rows(g, n_pad)

    return (_padded(q, 1, tp).reshape(b, tp, hk * dk),
            _padded(k, 1, tp).reshape(b, tp, hk * dk),
            _padded(v, 1, tp).reshape(b, tp, hv * dv),
            gates(g), _gate_rows(beta, n_pad)), n, n_pad


def _specs(heads, chunks, dk, dv, blk, key_heads=1):
    """BlockSpecs of (q or k [b, t, hk * dk], v-like [b, t, hv * dv], o
    [b, hv, t, dv], gate-like [b, hv, n, C], states) for a grid (batch,
    key head, chunk block) whose block index along the sequence is
    ``blk(c)``. ``key_heads``: the key heads of a grid step (kda.rule.*:
    as many as value heads, and its g a block like q's)."""
    rows = chunks * CHUNK
    return (pl.BlockSpec((None, rows, key_heads * dk),
                         lambda i, h, c: (i, blk(c), h)),
            pl.BlockSpec((None, rows, heads * dv),
                         lambda i, h, c: (i, blk(c), h)),
            pl.BlockSpec((None, heads, rows, dv),
                         lambda i, h, c: (i, h, blk(c), 0)),
            pl.BlockSpec((None, heads, chunks, CHUNK),
                         lambda i, h, c: (i, h, blk(c), 0)),
            pl.BlockSpec((chunks, None, heads, dk, dv),
                         lambda i, h, c: (blk(c), i, h, 0, 0)))


def _scratch(heads, chunks, dk, dv, dtype, backward, vector=False):
    """S or dS; A of the block's chunks and pairs of heads (``_slot``),
    T of each matrix, the pairs' diagonal blocks (``_substitute``); then
    ``_parts``, in its order."""
    pairs = -(-heads // 2) * chunks
    return [pltpu.VMEM((heads, dk, dv), _F32),
            pltpu.VMEM((pairs, CHUNK, 2 * CHUNK), _F32),
            pltpu.VMEM((pairs, 2, CHUNK, CHUNK), _F32),
            pltpu.VMEM((pairs, _BLOCK, _LANES), _F32)] + [
        pltpu.VMEM(shape, dt) for shape, dt in _parts(
            heads, chunks, dk, dv, dtype, backward, vector).values()]


def _cost(b, hv, n, dk, dv, passes, bytes_accessed):
    # a chunk and head: K K^T, Q K^T, T's two products, and the four of
    # the state pass (2 dk dv + C (dk + dv) MACs a row, about), times
    # ``passes`` (1 forward, 3 backward: recomputed + two transposes)
    flops = 2 * CHUNK * (2 * CHUNK * dk + CHUNK * (dk + dv)
                         + 3 * dk * dv + CHUNK * dv)
    # T's merges, once a pass: four products of half of a pair's rows
    # (16 + 16 + 32 + 32) against a block diagonal [128, 128]
    merges = 2 * (2 * _BLOCK + CHUNK) * _LANES * _LANES // 2
    return pl.CostEstimate(
        flops=b * hv * n * (passes * flops + merges),
        transcendentals=b * hv * n * (CHUNK * CHUNK + 3 * CHUNK),
        bytes_accessed=bytes_accessed)


def gated_delta_rule_fwd(q, k, v, g, beta, tile, eps=1e-6):
    """q, k [b, t, hk, dk], v [b, t, hv, dv] (bf16), g, beta [b, t, hv]
    -> (o [b, t, hv, dv] in v's dtype, states [n, b, hv, dk, dv] in q's:
    the state each of the n = ceil(t / 64) chunks started from).
    ``tile``: ``gdn_tile``'s answer for the call. g [b, t, hv, dk] (a
    decay a key feature) with ``kda_tile``'s: the call ``kda.rule.fwd``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    heads, chunks = tile
    vector = g.ndim == q.ndim
    assert (hk == hv and not hv % heads if vector else heads * hk == hv
            ) and dk == _LANES and dv == _LANES, (q.shape, v.shape, g.shape)
    (q2, k2, v2, g4, b4), n, n_pad = _operands(q, k, v, g, beta, tile)
    qk_spec, v_spec, o_spec, gate_spec, st_spec = _specs(
        heads, chunks, dk, dv, lambda c: c, heads if vector else 1)
    g_spec = qk_spec if vector else gate_spec
    item = jnp.dtype(q.dtype).itemsize
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, chunks=chunks, eps=eps,
                          scale=dk ** -0.5, vector=vector),
        name="kda.rule.fwd" if vector else "gdn.rule.fwd",
        out_shape=(jax.ShapeDtypeStruct((b, hv, n_pad * CHUNK, dv), v.dtype),
                   jax.ShapeDtypeStruct((n_pad, b, hv, dk, dv), q.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, hv // heads, n_pad // chunks),
            in_specs=[qk_spec, qk_spec, v_spec, g_spec, gate_spec],
            out_specs=(o_spec, st_spec),
            scratch_shapes=_scratch(heads, chunks, dk, dv, q.dtype, False,
                                    vector)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(heads, chunks, dk, dv, vector)),
        cost_estimate=_cost(
            b, hv, n_pad, dk, dv, 1,
            item * (2 * q2.size + 2 * v2.size + n_pad * b * hv * dk * dv)
            + 4 * g4.size + 4 * b4.size),
        interpret=_INTERPRET,
    )(q2, k2, v2, g4, b4)
    return jnp.moveaxis(o[:, :, :t], 1, 2), states[:n]


# ---------------------------------------------------------------------------
# gdn.rule.bwd
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                ds_ref, a_ref, t_ref, x_ref, *parts, heads, chunks, eps,
                scale, vector=False):
    dtype = q_ref.dtype
    dv = v_ref.shape[-1] // heads
    dk = q_ref.shape[-1] // heads if vector else q_ref.shape[-1]
    p = dict(zip(_parts(heads, chunks, dk, dv, dtype, True, vector), parts))
    masks = None if vector else _behind_masks()

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    (_prepare_kda if vector else _prepare)(
        q_ref, k_ref, v_ref, g_ref, beta_ref, a_ref, p, heads=heads,
        chunks=chunks, eps=eps, scale=scale)
    _invert(a_ref, t_ref, x_ref)

    def chain(c):
        # the state pass backwards: the transposes of its products
        # around the saved state and V' (made again)
        rows = _rows(c)
        for r in range(heads):
            m = _mat(r, c, chunks)
            sb = states_ref[c, r]
            ds = ds_ref[r]
            dsb = ds.astype(dtype)
            dob = do_ref[rows, r * dv:(r + 1) * dv]
            wq, kd, attn = p["wq"][m], p["kd"][m], p["attn"][m]
            vn = (p["uw"][m, :, :dv] - _dot(wq[:CHUNK], sb, 1, 0)
                  ).astype(dtype)
            dvn = _dot(attn, dob, 0, 0) + _dot(kd, dsb, 1, 0)
            minus = (-dvn).astype(dtype)
            p["dattn"][m] = _dot(dob, vn, 1, 1)
            # d(Q e^G) over dW = -dV' S^T: one product against S^T
            dqg_dw = _dot(jnp.concatenate([dob, minus], axis=0), sb, 1, 1)
            p["dqg"][m] = dqg_dw[:CHUNK]
            p["dkd"][m] = _dot(vn, dsb, 1, 1)
            p["dd"][m] = jnp.concatenate([dvn, dqg_dw[CHUNK:]], axis=1)
            if vector:      # summed over each row behind the loop
                p["ddec"][m] = ds * sb.astype(_F32)
            else:
                p["ddec"][m] = jnp.broadcast_to(jnp.sum(
                    ds * sb.astype(_F32), axis=0, keepdims=True),
                    (8, _LANES))
            # dS: (Q e^G)^T dO - W^T dV', one contraction of 128
            ds_ref[r] = ds * _decay_of(p, m, vector) + _dot(
                wq, jnp.concatenate([minus, dob], axis=0), 0, 0)

    # a block's chunks as the blocks: in reverse
    _apply(t_ref, p, heads=heads, chunks=chunks)
    _in_turn(chunks, lambda i: chain(chunks - 1 - i))

    # through U = T (beta V), W = T (beta K e^G), T = (I + A)^-1, every
    # matrix of the grid step at once
    for r in range(heads):
        ms = _mats(r, chunks)
        p["dd"][ms] = _through_t(_t_of(t_ref, r, chunks), p["dd"][ms])
    for r in range(heads):
        ms = _mats(r, chunks)
        p["da"][ms] = _d_triangle(p["dd"][ms], p["uw"][ms])
    if vector:
        _behind_kda(v_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, p,
                    heads=heads, chunks=chunks, scale=scale)
    else:
        _behind(v_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, p, masks,
                heads=heads, chunks=chunks, scale=scale)


def _rowsum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _half(x):                   # [.., C, C] -> [.., C, 128], zeros beside
    return jnp.concatenate([x, jnp.zeros_like(x)], axis=-1)


def _behind_masks():
    """(ii, jj, i >= j, the chunk's last row): ``_behind``'s masks, made
    at the head of the kernel."""
    ii, jj = _iotas()
    return ii, jj, ii >= jj, jax.lax.broadcasted_iota(
        jnp.int32, (CHUNK, 1), 0) == CHUNK - 1


def _behind(v_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, p, masks, *,
            heads, chunks, scale):
    """gdn.rule.bwd's pass behind the state loop: from what the loop and
    the products through T left in ``p`` to the cotangents of beta, g,
    v and (summed over the key head's group) q and k."""
    dtype = dq_ref.dtype
    dv = v_ref.shape[-1] // heads
    ii, jj, lower, last_row = masks

    # what is left, all the chunks of the grid step at once [chunks, C,
    # .], a value head at a time: no chunk waits for another and nothing
    # is a loop, one straight line under the products above
    kn, yq, kk, qk = (p[x][...] for x in ("kn", "yq", "kk", "qk"))
    qn = yq * scale
    kb = kn.astype(dtype)
    dqn = dkn = dqk = dkk = 0.0
    for r in range(heads):
        ms = _mats(r, chunks)
        cols = slice(r * dv, (r + 1) * dv)
        beta_w, eg_w, ekd_w = (p[x][ms] for x in ("beta", "eg", "ekd"))
        beta = beta_w[:, :, :1]
        decay = p["decay"][ms]
        dd = p["dd"][ms]
        dru, drw = dd[:, :, :dv], dd[:, :, dv:]
        dqg, dkd = p["dqg"][ms], p["dkd"][ms]
        vf = _by_chunk(v_ref, chunks, cols).astype(_F32)
        da = jnp.where(ii > jj, p["da"][ms], 0.0)
        dattn = jnp.where(lower, p["dattn"][ms], 0.0)
        f = da * kk * decay
        e = beta * f + dattn * qk * decay       # d/d(G_i - G_j), i >= j
        # the sums over a row's 128 features, two a head: what beta and
        # what G_i multiply (f and e ride in their lower lanes)
        drw_kn, dkd_kn = drw * kn, ekd_w * (dkd * kn)
        to_beta = dru * vf + eg_w * drw_kn
        to_g = eg_w * (beta_w * drw_kn + dqg * qn) - dkd_kn
        dbeta = _rowsum(to_beta + _half(f))
        dgc = (_rowsum(to_g + _half(e))
               - _col(jnp.sum(e, axis=1, keepdims=True), ii, jj))
        dg_last = _rowsum(jnp.sum(dkd_kn, axis=1, keepdims=True)
                         + p["ddec"][ms, :1] * p["dec"][ms, :1, :1])
        dgc = dgc + jnp.where(last_row, dg_last, 0.0)
        # G is g's running sum: dg_m = sum of dG_i over i >= m
        dg = jnp.sum(jnp.where(lower, dgc, 0.0), axis=1, keepdims=True)
        db = jnp.sum(jnp.where(ii == jj, dbeta, 0.0), axis=1, keepdims=True)
        dvs = (beta_w * dru).astype(dv_ref.dtype)
        dg_ref[r] = dg.reshape(chunks, CHUNK)
        dbeta_ref[r] = db.reshape(chunks, CHUNK)
        dv_ref[:, cols] = dvs.reshape(chunks * CHUNK, dv)
        # dQ K^T and dK K^T summed over the group before their products
        # (in float32: a cast a key head, not a value head)
        dqk = dqk + dattn * decay
        dkk = dkk + da * (beta * decay)
        dqn = dqn + dqg * eg_w
        dkn = dkn + dkd * ekd_w + (beta_w * eg_w) * drw
    dqk, dkk = dqk.astype(dtype), dkk.astype(dtype)
    dqn = dqn + _bdot(dqk, kb, 2, 1)
    dkn = (dkn + _bdot(dqk, qn.astype(dtype), 1, 1) + _bdot(dkk, kb, 2, 1)
           + _bdot(dkk, kb, 1, 1))
    # through y = x rsqrt(|x|^2 + eps): dx = r (dy - y (y . dy))
    dyq = dqn * scale
    dq_ref[...] = (p["rq"][...] * (dyq - yq * _rowsum(yq * dyq))).astype(
        dq_ref.dtype).reshape(dq_ref.shape)
    dk_ref[...] = (p["rk"][...] * (dkn - kn * _rowsum(kn * dkn))).astype(
        dk_ref.dtype).reshape(dk_ref.shape)


def _behind_kda(v_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, p, *,
                heads, chunks, scale):
    """kda.rule.bwd's pass behind the state loop, a head at a time, all
    its chunks at once. With M = lower(dP) and N = beta . strictly_lower
    (dA), the cotangents of ``_prepare_kda``'s two decayed products, a
    level of the halving gives back, through the factors E, F it was
    made of (``_levels``, made again from G),

        X = [M; N] (K F)        dq += X_M . E,   dk_row += X_N . E
        Y = [M; N]^T [Q E; K E] dk_col += Y . F

    two products a level, and the decays' own cotangent needs no third:
    G_i enters a product only as exp(G_id) beside q_id or k_id and G_j
    as exp(-G_jd) beside k_jd, so dG = q . dq + k . (dk_row - dk_col)
    over these terms (the diagonal of P, q_i . k_i, cancels in it). The
    element-wise operands Q . e^G, beta K . e^G, K . e^{G_C - G} and the
    state's row factors e^{G_C} give theirs feature by feature, where
    gdn.rule.bwd sums over a row's lanes; dg is dG's running sum up the
    chunk."""
    dtype = dq_ref.dtype
    dk, dv = dq_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    ii, jj = _iotas()
    pos = _positions(chunks)
    last_row = pos == CHUNK - 1
    ones = jnp.ones((chunks, 8, dv), _F32)
    for r in range(heads):
        ms = _mats(r, chunks)
        cols, kcols = slice(r * dv, (r + 1) * dv), slice(r * dk, (r + 1) * dk)
        beta_w, eg, ekd, gc, kn, yq = (p[x][ms] for x in (
            "beta", "eg", "ekd", "gc", "kn", "yq"))
        qn = yq * scale
        beta = beta_w[:, :, :1]
        dd = p["dd"][ms]
        dru, drw = dd[:, :, :dv], dd[:, :, dv:]
        dqg, dkd = p["dqg"][ms], p["dkd"][ms]
        vf = _by_chunk(v_ref, chunks, cols).astype(_F32)
        da = jnp.where(ii > jj, p["da"][ms], 0.0)
        m = jnp.where(ii >= jj, p["dattn"][ms], 0.0)
        mn = jnp.concatenate([m, beta * da], axis=1)      # [chunks, 2C, C]
        # the diagonal of P: q_i . k_i
        on_diag = jnp.sum(jnp.where(ii == jj, m, 0.0), axis=-1,
                          keepdims=True)
        dq_p, dk_row, dk_col = on_diag * kn, 0.0, on_diag * qn
        for e, f, level in _levels(gc, pos, ii, jj):
            lv = jnp.where(jnp.concatenate([level, level], axis=0), mn,
                           0.0).astype(dtype)
            x = _bdot(lv, (kn * f).astype(dtype), 2, 1)
            y = _bdot(lv, jnp.concatenate(
                [qn * e, kn * e], axis=1).astype(dtype), 1, 1)
            dq_p = dq_p + x[:, :CHUNK] * e
            dk_row = dk_row + x[:, CHUNK:] * e
            dk_col = dk_col + y * f
        drw_kn, dkd_kn = drw * kn, ekd * (dkd * kn)
        dbeta = _rowsum(dru * vf + eg * drw_kn + _half(da * p["kk"][ms]))
        dgc = (eg * (beta_w * drw_kn + dqg * qn) - dkd_kn
               + qn * dq_p + kn * (dk_row - dk_col))
        # G_C: K e^{G_C - G} and the state's row factors e^{G_C}, the
        # sum over each row of ds . S as a row of lanes (one product)
        on_rows = _bdot(ones, p["ddec"][ms] * p["dec"][ms], 2, 2, _HIGHEST)
        dg_last = jnp.sum(dkd_kn, axis=1, keepdims=True) + on_rows[:, :1]
        dgc = dgc + jnp.where(last_row, dg_last, 0.0)
        db = jnp.sum(jnp.where(ii == jj, dbeta, 0.0), axis=1, keepdims=True)
        dbeta_ref[r] = db.reshape(chunks, CHUNK)
        # G is g's running sum: dg_m = sum of dG_i over i >= m
        dg_ref[:, kcols] = _running(dgc, pos, back=True).reshape(
            chunks * CHUNK, dk)
        dv_ref[:, cols] = (beta_w * dru).astype(dv_ref.dtype).reshape(
            chunks * CHUNK, dv)
        # through y = x rsqrt(|x|^2 + eps): dx = r (dy - y (y . dy))
        dyq = (dqg * eg + dq_p) * scale
        dkn = dkd * ekd + (beta_w * eg) * drw + dk_row + dk_col
        dq_ref[:, kcols] = (p["rq"][ms] * (dyq - yq * _rowsum(yq * dyq))
                            ).astype(dtype).reshape(chunks * CHUNK, dk)
        dk_ref[:, kcols] = (p["rk"][ms] * (dkn - kn * _rowsum(kn * dkn))
                            ).astype(dtype).reshape(chunks * CHUNK, dk)


def gated_delta_rule_bwd(q, k, v, g, beta, states, do, tile, eps=1e-6):
    """The cotangents (dq, dk, dv in the operands' dtype, dg, dbeta
    float32, each shaped as its input) of ``gated_delta_rule_fwd`` for
    the cotangent ``do`` of o, from the forward's ``states``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    heads, chunks = tile
    vector = g.ndim == q.ndim
    (q2, k2, v2, g4, b4), n, n_pad = _operands(q, k, v, g, beta, tile)
    do2 = _padded(do.astype(v.dtype), 1, n_pad * CHUNK).reshape(v2.shape)
    states = _padded(states, 0, n_pad)
    last = n_pad // chunks - 1
    qk_spec, v_spec, _, gate_spec, st_spec = _specs(
        heads, chunks, dk, dv, lambda c: last - c, heads if vector else 1)
    g_spec = qk_spec if vector else gate_spec
    item = jnp.dtype(q.dtype).itemsize
    dq2, dk2, dv2, dg4, db4 = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, chunks=chunks, eps=eps,
                          scale=dk ** -0.5, vector=vector),
        name="kda.rule.bwd" if vector else "gdn.rule.bwd",
        out_shape=(jax.ShapeDtypeStruct(q2.shape, q.dtype),
                   jax.ShapeDtypeStruct(k2.shape, k.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype),
                   jax.ShapeDtypeStruct(g4.shape, _F32),
                   jax.ShapeDtypeStruct(b4.shape, _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, hv // heads, last + 1),
            in_specs=[qk_spec, qk_spec, v_spec, g_spec, gate_spec,
                      st_spec, v_spec],
            out_specs=(qk_spec, qk_spec, v_spec, g_spec, gate_spec),
            scratch_shapes=_scratch(heads, chunks, dk, dv, q.dtype, True,
                                    vector)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(heads, chunks, dk, dv, vector)),
        cost_estimate=_cost(
            b, hv, n_pad, dk, dv, 3,
            item * (4 * q2.size + 3 * v2.size + n_pad * b * hv * dk * dv)
            + 8 * g4.size + 8 * b4.size),
        interpret=_INTERPRET,
    )(q2, k2, v2, g4, b4, states, do2)

    def gate(x):   # [b, hv, n, C] -> [b, t, hv]
        return jnp.moveaxis(x.reshape(b, hv, -1)[:, :, :t], 1, 2)

    return (dq2[:, :t].reshape(q.shape), dk2[:, :t].reshape(k.shape),
            dv2[:, :t].reshape(v.shape),
            dg4[:, :t].reshape(g.shape) if vector else gate(dg4), gate(db4))

