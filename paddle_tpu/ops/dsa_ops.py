"""Learned sparse attention's indexer: DeepSeek Sparse Attention's
lightning indexer (DeepSeek-V3.2-Exp's technical report, equations 1-4,
and its public inference code) as two ops beside
``scaled_dot_product_attention(Selected=, Live=)``. A small network scores
every (query, key) pair, each query keeps its ``topk`` best keys, the main
attention reads only those, and the indexer learns from a loss of its own.
For a row of t positions, ``hI`` index heads of ``dI`` features, ONE index
key head:

    I[p, s] = c0 sum_j w[p, j] relu(qI[p, j] . kI[s])        s <= p, float32
              c0 = hI^-1/2 dI^-1/2 (the op's ``scale``)
    S_p     = the min(p + 1, topk) positions s <= p of largest I[p, s],
              ties to the LOWER s
    P[p, s] = 1/h sum_head softmax_{s in S_p}(attention's scores)[s]
              (detached; made again from the attention's q, k and the
              logsumexp rows its forward pass saved)
    L_I     = mean_p sum_{s in S_p} P[p, s] (log P[p, s]
                                             - log softmax_{s in S_p}(I[p, .])[s])

``dsa_select`` (no gradient: a top-k passes none) makes S as the device
value the attention kernels read, ``Selected`` [b, t / 32, t] int32, one
BIT a pair (``dsa_score.pack_rows``), beside ``Live`` [b, t / cq, t / ck] int32
(nonzero where a block of ``q_chunk`` x ``kv_chunk`` holds a selected
pair) and ``IndexLse`` [b, t], the logsumexp of I over S_p, which the
loss reads. The selection lives from the forward pass to the backward:
a bit a pair it weighs t^2 / 8 bytes a layer (34 MB at 16,384, where an
int8 a pair weighed 268 MB and the cell's peak stood at 96.5% of the
chip). The packing is a q-chunk's own, so that a kernel unpacks a block
with shifts of whole sublane tiles: of chunk c's ``cq`` queries, query
r = i n + j is bit i of word row c n + j, n = cq / 32 (a chunk that is
no multiple of 32 takes ceil(cq / 32) word rows, the bits behind its
last query 0: the tests' sizes; the kernels take whole words); a reader
takes ``cq`` from ``Live``'s shape.

The scores are made a ``q_chunk`` of queries at a time against
``kv_chunk`` keys a tile ([hI, cq, ck] float32 is the largest value that
exists), tiles above the diagonal not at all, and never held whole. The
top-k is no sort: a row's threshold is found by BISECTION over the float32
bit pattern (32 counting passes: the k-th largest value exactly), and,
only in a chunk where some row has MORE keys equal to its threshold than
it still needs, the lowest positions among those by a second bisection
over the position (log2 t passes), so the tie rule is the reference's
(``lax.top_k``'s) bit for bit; elsewhere the keys at or over the
threshold are the answer. The chunks whose last query has at most
``topk`` earlier keys make no pass at all (every valid key is chosen: a
``lax.map`` of their own; the dense stage's every chunk). Where
``dsa_score.topk_tile`` takes the call the 32 passes are the kernel
``dsa.topk.fwd``'s, which counts over the chunk's CAUSAL PREFIX and no
more (the keys behind are -inf that nobody need count); XLA's ops count
over the chunk's whole [cq, t] row. ``pt_dsa_topk_columns_total{kind}``
counts, a lowered call, the columns those passes read and the causal
prefixes of the chunks that make them.

``dsa_index_loss`` walks the same tiles once and returns L_I TOGETHER
with its gradient: the target is detached, so dL/dI = (softmax_S(I) - P)
/ (b t) needs nothing from upstream but a scalar, and the op writes
``DQI``, ``DKI``, ``DW`` (through the relu and the per-head weights, by
hand) in the pass that makes the loss; ``dsa_index_loss_grad`` scales
them by the loss's cotangent. The per-head probabilities are made again
a tile at a time: no [h, t, t] tensor exists.

Matmul operands keep the dtype they come in (bf16 under AMP) and
accumulate in float32; I, the top-k, both softmaxes and L_I are float32.
A chunk's scores are the kernel ``dsa.score.fwd``
(parallel/dsa_score.py) where ``dsa_score.score_tile`` takes the call (a
TPU, no mesh, tiles on the lanes), else XLA's ops a tile; the loss pass
is the kernel ``dsa.loss.bwd`` where ``dsa_score.loss_tile`` takes it,
else XLA's ops a tile under ``lax.scan``; the top-k's thresholds are the
kernel ``dsa.topk.fwd`` where ``dsa_score.topk_tile`` takes them, the
rest of it XLA's ops.
``pt_dsa_dispatch_total{op, pass, impl, shape}`` counts the lowered
calls and says which."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core.registry import register_op
from paddle_tpu.parallel.dsa_score import pack_rows, unpack, unpack_rows

_F32 = jnp.float32
_NEG_INF = -jnp.inf

_M_DISPATCH = _monitor.counter(
    "pt_dsa_dispatch_total",
    "sparse-attention indexer calls lowered, one row a call: op (select: "
    "the index scores and the top-k; loss: the indexer's KL loss with its "
    "gradient), pass (fwd, bwd), impl (xla: XLA's ops a tile under "
    "lax.scan; kernel: a select whose scores are dsa.score.fwd, a loss "
    "pass that is dsa.loss.bwd, parallel/dsa_score.py) and shape (b, t, index heads hI of dI, topk "
    "k, the tiles cq x ck)")


_M_COLUMNS = _monitor.counter(
    "pt_dsa_topk_columns_total",
    "score columns of dsa_select calls lowered, over the q-chunks that "
    "make a top-k (a chunk whose queries have at most topk keys makes no "
    "pass): kind walked (the columns the threshold's 32 counting passes "
    "read: the chunk's live key blocks where they are dsa.topk.fwd's, the "
    "row's t as XLA's ops) and causal (the keys the chunk's last query can "
    "choose from, (c + 1) cq)")


def _x(ins, slot, i=0):
    v = ins.get(slot)
    return v[i] if v else None


def _lowering():
    # off with telemetry; build-time shape inference is not a lowering
    from paddle_tpu.core import interp

    return _monitor.enabled() and interp.lowering_active()


def _note(op, direction, shape, impl="xla"):
    if _lowering():
        _M_DISPATCH.inc(labels={"op": op, "pass": direction, "impl": impl,
                                "shape": shape})


def dispatch_counts():
    """{"impl op pass shape": calls lowered so far}: the counter as
    chip_smoke.py prints it."""
    out = {}
    for row in _monitor.snapshot()[_M_DISPATCH.name]["values"]:
        lb = row["labels"]
        name = " ".join(lb.get(k, "?") for k in ("impl", "op", "pass",
                                                 "shape"))
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def topk_columns():
    """{"walked": .., "causal": ..}: ``pt_dsa_topk_columns_total`` over
    the ``dsa_select`` calls lowered so far."""
    return {row["labels"]["kind"]: int(row["value"])
            for row in _monitor.snapshot()[_M_COLUMNS.name]["values"]}


def chunk(t, want):
    """The tile along a row of ``t`` positions: ``want`` where it cuts
    the row in whole parts, else the largest divisor of t under it."""
    c = max(min(int(want), t), 1)
    while t % c:
        c -= 1
    return c


def _shape(qi, topk, cq, ck):
    b, h, t, d = qi.shape
    return f"b{b} t{t} hI{h} dI{d} k{topk} cq{cq} ck{ck}"


def _tiles(x, c):
    """[.., t, d] -> [t / c, .., c, d]: the row's tiles to the front."""
    t = x.shape[-2]
    y = x.reshape(x.shape[:-2] + (t // c, c, x.shape[-1]))
    return jnp.moveaxis(y, -3, 0)


def _pre(qi, ki):
    """qI . kI of a tile: qi [hI, cq, dI], ki [ck, dI] -> [hI, cq, ck]
    float32."""
    return jnp.einsum("jqd,kd->jqk", qi, ki, preferred_element_type=_F32)


def score_tile(qi, ki, w, scale):
    """I of a tile [cq, ck] float32: w [cq, hI] float32."""
    return scale * jnp.sum(jnp.maximum(_pre(qi, ki), 0.0)
                           * w.T[:, :, None], axis=0)


def _tile_live(c, kk, cq, ck):
    """Does tile (q-chunk c, k-chunk kk) hold a pair s <= p?"""
    return kk * ck <= (c + 1) * cq - 1


def _sortable(x):
    """float32 -> uint32 keys in the same order (negative values' bits
    inverted, the others' sign bit set)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def columns(t, topk, cq, ck, kernel):
    """{"walked", "causal"}: a row's score columns over the q-chunks that
    make a top-k (``pt_dsa_topk_columns_total``'s two kinds): what the
    threshold's counting passes read (``kernel``: ``dsa.topk.fwd``'s, a
    chunk's live key blocks; else the row's t) and the chunks' causal
    prefixes."""
    chunks = range(min(topk // cq, t // cq), t // cq)
    return {"walked": sum(min(-(-(c + 1) * cq // ck) * ck, t) if kernel
                          else t for c in chunks),
            "causal": sum((c + 1) * cq for c in chunks)}


def choose(scores, valid, k, threshold=None):
    """[n, t] bool: each row's min(valid count, k) valid entries of
    largest ``scores`` [n, t] float32, ties to the lower index: what
    ``lax.top_k`` over the valid entries would list, without a sort.
    The row's threshold T (its k-th largest key) is built bit by bit
    from the top: a bit stays where at least k keys reach the candidate
    (32 counting passes over the [n, t] keys). Where every row has just
    as many keys at or over T as it wants, those are the answer. Where
    some row has more (a surplus of keys EQUAL to T), of those the first
    ``need`` = k - count(key > T) are taken, up to the position P found
    the same way over the index (one pass and log2 t more, for every row
    of the call: never more passes than 33 + log2 t). ``threshold``: (T
    [n] uint32, the count of keys at or over it [n] int32) where the
    caller has them (``dsa_score.threshold_rows``), so the 32 passes are
    not made here."""
    n, t = scores.shape
    keys = jnp.where(valid, _sortable(scores.astype(_F32)), jnp.uint32(0))
    held = jnp.sum(valid, axis=1, dtype=jnp.int32)
    want = jnp.minimum(held, k)

    def count(m):
        return jnp.sum(m, axis=1, dtype=jnp.int32)

    def value_bit(i, at):
        thr, reach = at         # reach: the keys at or over thr
        cand = thr | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        # (an entry that is not valid is key 0, under every candidate)
        reach_c = count(keys >= cand[:, None])
        keep = reach_c >= want
        return jnp.where(keep, cand, thr), jnp.where(keep, reach_c, reach)

    thr, reach = threshold or jax.lax.fori_loop(
        0, 32, value_bit, (jnp.zeros((n,), jnp.uint32), held))

    def lowest_tied():
        above = jnp.logical_and(valid, keys > thr[:, None])
        tied = jnp.logical_and(valid, keys == thr[:, None])
        need = want - count(above)
        at = jnp.arange(t, dtype=jnp.int32)[None, :]
        bits = max(int(t - 1).bit_length(), 1)

        def index_bit(i, pos):
            cand = pos | jnp.left_shift(jnp.int32(1), (bits - 1 - i))
            keep = count(jnp.logical_and(tied, at < cand[:, None])) < need
            return jnp.where(keep, cand, pos)

        # the need-th tied entry lies at ``pos``: fewer than need lie before
        pos = jax.lax.fori_loop(0, bits, index_bit,
                                jnp.zeros((n,), jnp.int32))
        return jnp.logical_or(above,
                              jnp.logical_and(tied, at <= pos[:, None]))

    chosen = jax.lax.cond(
        jnp.any(reach > want), lowest_tied,
        lambda: jnp.logical_and(valid, keys >= thr[:, None]))
    return jnp.logical_and(chosen, (want > 0)[:, None])


def choose_by_sort(scores, valid, k):
    """``choose`` by ``lax.top_k`` over the same sortable keys (as signed
    words; ties to the lower index): what the checks hold it to
    (chip_smoke.py, benchmarks/dsa_topk_candidates.py), a sort a row."""
    n, t = scores.shape
    keys = jnp.where(valid, _sortable(scores.astype(_F32)), jnp.uint32(0))
    _, idx = jax.lax.top_k(jax.lax.bitcast_convert_type(
        keys ^ jnp.uint32(0x80000000), jnp.int32), min(k, t))
    want = jnp.minimum(jnp.sum(valid, axis=1, dtype=jnp.int32), k)
    listed = jnp.arange(idx.shape[1], dtype=jnp.int32)[None, :] < want[:, None]
    return jnp.zeros((n, t), jnp.int32).at[
        jnp.arange(n)[:, None], idx].add(listed.astype(jnp.int32)) > 0


@functools.partial(jax.jit, static_argnames=(
    "scale", "topk", "cq", "ck", "top", "kernel", "thresholds", "interpret"))
def _select_run(chunks, qi, ki, w, *, scale, topk, cq, ck, top, kernel,
                thresholds, interpret):
    """The q-chunks ``chunks`` [n] int32 of a row: their index queries
    qi [n, hI, cq, dI] and weights w [n, cq, hI] float32 against the
    row's keys ki [t, dI] -> (``pack_rows`` a chunk [n, words, t] int32,
    live [n, t / ck] int32, the logsumexp of I over the selected keys
    [n, cq] float32). ``top``: a top-k is made (else every valid key is
    chosen); ``kernel``, ``thresholds``: the scores are
    ``dsa.score.fwd``'s, the top-k's thresholds ``dsa.topk.fwd``'s;
    ``interpret``: the kernels' test hook, part of the trace. One jitted
    function a shape: a model's layers make the same calls."""
    from paddle_tpu.parallel import dsa_score

    t = ki.shape[0]
    nk = t // ck
    s_at = jnp.arange(t, dtype=jnp.int32)[None, :]

    def xla_scores(c, qi_c, w_c):
        def tile(at):
            kk, ki_k = at
            return jax.lax.cond(
                _tile_live(c, kk, cq, ck),
                lambda: score_tile(qi_c, ki_k, w_c, scale),
                lambda: jnp.full((cq, ck), _NEG_INF, _F32))

        tiles = jax.lax.map(tile, (jnp.arange(nk), _tiles(ki, ck)))
        return jnp.moveaxis(tiles, 0, 1).reshape(cq, t)

    def rows(args):
        c, qi_c, w_c = args                 # [hI, cq, dI], [cq, hI]
        scores = (dsa_score.score_rows(c, qi_c, ki, w_c, scale, ck, interpret)
                  if kernel else xla_scores(c, qi_c, w_c))
        p_at = c * cq + jnp.arange(cq, dtype=jnp.int32)[:, None]
        valid = s_at <= p_at
        if not top:
            chosen = valid
        else:
            chosen = choose(scores, valid, topk, dsa_score.threshold_rows(
                c, scores, topk, ck, interpret) if thresholds else None)
        lse = jax.scipy.special.logsumexp(
            jnp.where(chosen, scores, _NEG_INF), axis=1)
        live = jnp.any(chosen.reshape(cq, nk, ck), axis=(0, 2))
        return pack_rows(chosen), live.astype(jnp.int32), lse

    return jax.lax.map(rows, (chunks, qi, w))


def select_row(qi, ki, w, scale, topk, cq, ck, kernel=False,
               thresholds=False):
    """One batch row: qi [hI, t, dI], ki [t, dI], w [t, hI] float32 ->
    (selected [(t / cq) n, t] int32 (``pack_rows`` a chunk: n word
    rows), live [t / cq, t / ck] int32, the logsumexp of I over the
    selected keys [t] float32). The chunks whose last query has at most
    ``topk`` earlier keys go first, without a top-k; then the others.
    ``kernel``: a chunk's scores are ``dsa.score.fwd``'s
    (parallel/dsa_score.py), else XLA's ops a tile; ``thresholds``: the
    top-k's thresholds are ``dsa.topk.fwd``'s, else XLA's ops."""
    from paddle_tpu.parallel import dsa_score

    t = qi.shape[1]
    nq = t // cq
    whole = min(topk // cq, nq)
    qi_t, w_t = _tiles(qi, cq), _tiles(w, cq)
    selected, live, lse = (jnp.concatenate(part) for part in zip(*(
        _select_run(jnp.arange(first, behind), qi_t[first:behind], ki,
                    w_t[first:behind], scale=float(scale), topk=int(topk),
                    cq=cq, ck=ck, top=top, kernel=bool(kernel),
                    thresholds=bool(top and thresholds),
                    interpret=bool(dsa_score._INTERPRET))
        for first, behind, top in ((0, whole, False), (whole, nq, True))
        if behind > first)))
    return selected.reshape(-1, t), live, lse.reshape(t)


def _select_attrs(attrs, t):
    topk = int(attrs.get("topk", 0)) or t
    return (float(attrs["scale"]), min(topk, t),
            chunk(t, attrs.get("q_chunk", 512)),
            chunk(t, attrs.get("kv_chunk", 512)))


@register_op("dsa_select", no_grad=True)
def _dsa_select(ins, attrs):
    """QI [b, hI, t, dI], KI [b, 1, t, dI] (ONE index key head), W
    [b, t, hI] -> Selected [b, t / 32, t] int32 (a bit a pair, set:
    query p reads key s; ``pack_rows`` a chunk of cq queries), Live
    [b, t / cq, t / ck] int32, IndexLse [b, t] float32. Attributes
    ``scale`` (c0), ``topk`` (0 or absent: every s <= p, the dense
    stage), ``q_chunk``, ``kv_chunk`` (512: the tiles, ``chunk``). The
    module's docstring has the equations and the top-k."""
    from paddle_tpu.parallel import dsa_score

    qi, ki, w = _x(ins, "QI"), _x(ins, "KI"), _x(ins, "W")
    scale, topk, cq, ck = _select_attrs(attrs, qi.shape[2])
    kernel = dsa_score.score_tile(cq, ck, qi.shape[1], qi.shape[3])
    thresholds = dsa_score.topk_tile(cq, ck, qi.shape[2])
    _note("select", "fwd", _shape(qi, topk, cq, ck),
          "kernel" if kernel else "xla")
    if _lowering():
        for kind, n in columns(qi.shape[2], topk, cq, ck, thresholds).items():
            _M_COLUMNS.inc(qi.shape[0] * n, labels={"kind": kind})
    selected, live, lse = jax.lax.map(
        lambda a: select_row(a[0], a[1][0], a[2].astype(_F32), scale, topk,
                             cq, ck, kernel, thresholds), (qi, ki, w))
    return {"Selected": [selected], "Live": [live], "IndexLse": [lse]}


@register_op("dsa_selected_rows", no_grad=True)
def _dsa_selected_rows(ins, attrs):
    """Selected [b, t / 32, t] int32 and Live (``dsa_select``'s: its
    shape says the chunk) -> Out [b, t, t] int8, 1 where query p reads
    key s, or the ``last`` rows of it (all of a row shorter than that)."""
    selected, live = _x(ins, "Selected"), _x(ins, "Live")
    t, nq = selected.shape[2], live.shape[1]
    cq, last = t // nq, int(attrs.get("last", 0))
    if not last or last > cq:   # (all of it, or a short row's)
        return {"Out": [unpack(selected, cq)[:, -last:].astype(jnp.int8)]}
    rows = unpack_rows(selected[:, -(selected.shape[1] // nq):])
    return {"Out": [rows[:, cq - last:cq].astype(jnp.int8)]}


def loss_row(qi, ki, w, q, k, lse, selected, ilse, scale, attn_scale, inv_n,
             cq, ck):
    """One batch row's part of L_I and of its gradient: qi [hI, t, dI],
    ki [t, dI], w [t, hI] float32; the attention's q [h, t, dh], k
    [hk, t, dh] and logsumexp rows lse [h, t]; selected [t / 32, t] int32
    (``pack_rows`` a chunk), ilse [t] -> (loss, dqi [hI, t, dI] f32, dki [t, dI] f32, dw [t, hI] f32),
    each already times ``inv_n`` = 1 / (b t)."""
    h_i, t, d_i = qi.shape
    h, hk = q.shape[0], k.shape[0]
    nq, nk = t // cq, t // ck
    q = q.reshape(hk, h // hk, t, q.shape[-1])
    lse = lse.reshape(hk, h // hk, t)
    ki_t, k_t = _tiles(ki, ck), _tiles(k, ck)    # [nk, ck, dI], [nk, hk, ck, dh]
    sel_t = selected.reshape(nq, -1, nk, ck).transpose(0, 2, 1, 3)

    def rows(dki, args):
        c, qi_c, w_c, q_c, lse_c, ilse_c, sel_c = args
        w_t = w_c.T[:, :, None]                            # [hI, cq, 1]

        def tile(carry, at):
            kk, ki_k, k_k, sel = at

            def work():
                loss, dqi, dw = carry
                chosen = unpack_rows(sel)[:cq]
                pre = _pre(qi_c, ki_k)
                act = jnp.maximum(pre, 0.0)
                log_q = scale * jnp.sum(act * w_t, axis=0) - ilse_c[:, None]
                s = jnp.einsum("gmqd,gkd->gmqk", q_c, k_k,
                               preferred_element_type=_F32) * attn_scale
                p = jnp.where(chosen, jnp.sum(
                    jnp.exp(s - lse_c[..., None]), axis=(0, 1)) / h, 0.0)
                some = p > 0.0
                loss = loss + jnp.sum(jnp.where(
                    some, p * (jnp.log(jnp.where(some, p, 1.0)) - log_q),
                    0.0))
                d_i_ = jnp.where(chosen, jnp.exp(log_q) - p, 0.0) * inv_n
                dw = dw + scale * jnp.sum(d_i_[None] * act, axis=2).T
                dpre = jnp.where(pre > 0.0, d_i_[None] * (scale * w_t),
                                 0.0).astype(qi.dtype)
                dqi = dqi + jnp.einsum("jqk,kd->jqd", dpre, ki_k,
                                       preferred_element_type=_F32)
                dki_k = jnp.einsum("jqk,jqd->kd", dpre, qi_c,
                                   preferred_element_type=_F32)
                return (loss, dqi, dw), dki_k

            return jax.lax.cond(
                _tile_live(c, kk, cq, ck), work,
                lambda: (carry, jnp.zeros((ck, d_i), _F32)))

        zero = (jnp.zeros((), _F32), jnp.zeros((h_i, cq, d_i), _F32),
                jnp.zeros((cq, h_i), _F32))
        (loss, dqi, dw), dki_c = jax.lax.scan(
            tile, zero, (jnp.arange(nk), ki_t, k_t, sel_c))
        return dki + dki_c.reshape(t, d_i), (loss, dqi, dw)

    dki, (loss, dqi, dw) = jax.lax.scan(
        rows, jnp.zeros((t, d_i), _F32),
        (jnp.arange(nq), _tiles(qi, cq), _tiles(w, cq),
         _tiles(q, cq), jnp.moveaxis(lse.reshape(hk, h // hk, nq, cq), 2, 0),
         ilse.reshape(nq, cq), sel_t))
    return (jnp.sum(loss) * inv_n,
            jnp.moveaxis(dqi, 0, 1).reshape(h_i, t, d_i), dki,
            dw.reshape(t, h_i))


@register_op("dsa_index_loss", diff_inputs=("QI", "KI", "W"))
def _dsa_index_loss(ins, attrs):
    """QI, KI, W (``dsa_select``'s operands), the attention's Q
    [b, h, t, dh], K [b, hk, t, dh] and Lse [b, h, t, 1] (its forward
    pass's logsumexp rows under the selection), Selected and IndexLse
    (``dsa_select``'s) -> Loss, a float32 scalar, L_I over the batch's rows,
    and its gradient DQI, DKI (the operands' dtype) and DW (float32) at
    a cotangent of 1. Attributes ``scale`` (c0), ``attn_scale`` (the
    attention's), ``q_chunk``, ``kv_chunk``. Only QI, KI and W get a
    gradient: the target is detached."""
    from paddle_tpu.parallel import dsa_score

    qi, ki, w = _x(ins, "QI"), _x(ins, "KI"), _x(ins, "W")
    q, k, lse = _x(ins, "Q"), _x(ins, "K"), _x(ins, "Lse")
    b, _, t, _ = qi.shape
    scale, _, cq, ck = _select_attrs(attrs, t)
    kernel = dsa_score.loss_tile(cq, ck, qi.shape[1], qi.shape[3])
    _note("loss", "fwd", _shape(qi, 0, cq, ck),
          "kernel" if kernel else "xla")
    row = dsa_score.loss_rows if kernel else loss_row
    inv_n = 1.0 / (b * t)
    loss, dqi, dki, dw = jax.lax.map(
        lambda a: row(a[0], a[1][0], a[2].astype(_F32), a[3], a[4],
                      a[5][..., 0], a[6], a[7], scale,
                      float(attrs["attn_scale"]), inv_n, cq, ck),
        (qi, ki, w, q, k, lse, _x(ins, "Selected"), _x(ins, "IndexLse")))
    return {"Loss": [jnp.sum(loss)],
            "DQI": [dqi.astype(qi.dtype)],
            "DKI": [dki[:, None].astype(ki.dtype)], "DW": [dw]}


@register_op("dsa_index_loss_grad", no_grad=True)
def _dsa_index_loss_grad(ins, attrs):
    """GRAD::QI, GRAD::KI, GRAD::W: the gradient the forward op made at
    a cotangent of 1 (DQI, DKI, DW), times GRAD::Loss."""
    g = _x(ins, "GRAD::Loss").astype(_F32).reshape(())
    qi = _x(ins, "QI")
    _note("loss", "bwd", _shape(qi, 0, *_select_attrs(attrs, qi.shape[2])[2:]))
    return {f"GRAD::{slot}": [(g * _x(ins, d).astype(_F32)).astype(
        _x(ins, slot).dtype)]
        for slot, d in (("QI", "DQI"), ("KI", "DKI"), ("W", "DW"))}


def index_scale(heads, dim):
    """c0 = heads^-1/2 dim^-1/2."""
    return 1.0 / math.sqrt(heads * dim)
