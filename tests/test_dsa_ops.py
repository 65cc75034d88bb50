"""The sparse-attention indexer's ops (ops/dsa_ops.py) and the attention
under a selection, at tiny sizes on the CPU: ``dsa_select`` against a
dense ``lax.top_k`` with its tie rule (exact ties included); the BHTD
kernels under a selection and a live table, through the interpreter,
against the dense composition, forward and backward; ``dsa_index_loss``'s
hand-written gradient against autodiff of the plain equations, and the
kernel ``dsa.loss.bwd`` against it; a
selection of every s <= p IS causal attention; rotary positions that are
fed against the implicit ones and against the plain rotation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from model_test import highest
from paddle_tpu import layers
from paddle_tpu.backward import append_backward
from paddle_tpu.ops import attention_ops, dsa_ops
from paddle_tpu.parallel import dsa_score
from paddle_tpu.parallel import flash_attention as fa
from paddle_tpu.parallel import rope


def plain_scores(qi, ki, w):
    """I [b, t, t] float32 of qi [b, hI, t, dI], ki [b, 1, t, dI], w
    [b, t, hI]: the equation, whole."""
    pre = jnp.einsum("bjqd,bkd->bjqk", qi, ki[:, 0])
    c0 = dsa_ops.index_scale(qi.shape[1], qi.shape[3])
    return c0 * jnp.einsum("bjqk,bqj->bqk", jax.nn.relu(pre), w)


def plain_choice(scores, k):
    """[t, t] bool by ``lax.top_k`` a row over the valid entries (the
    others -inf, under every valid one, and dropped again)."""
    t = scores.shape[0]
    valid = np.tril(np.ones((t, t), bool))
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(k, t))
    out = np.zeros((t, t), bool)
    np.put_along_axis(out, np.asarray(idx), True, axis=1)
    return out & valid


def indexer(seed, b=2, hi=3, t=64, di=8, ties=False, flat=(),
            positive=False):
    """``flat``: the (row, chunk, chunk size) whose queries weigh every
    head 0, so that each of them scores all its keys the same;
    ``positive``: no product is under 0, so no score is the relu's exact
    0 (an eighth of them are, at three heads: ties of their own)."""
    r = np.random.RandomState(seed)
    qi, ki = r.randn(b, hi, t, di), r.randn(b, 1, t, di)
    if positive:
        qi, ki = np.abs(qi), np.abs(ki)
    if ties is True:    # values on a coarse grid: many exact ties a row
        qi, ki = np.round(qi), np.round(ki)
    w = np.round(r.rand(b, t, hi) * 4) / 4 if ties is True else r.rand(
        b, t, hi)
    for row, c, cq in flat:
        w[row, c * cq:(c + 1) * cq] = 0.0
    return (jnp.asarray(x, jnp.float32) for x in (qi, ki, w))


def surplus_ties(scores, want, cq):
    """[t / cq] bool: has a chunk a row with more keys equal to its
    threshold than the selection ``want`` [t, t] took of them? (The
    top-k's second bisection runs for such a chunk alone.)"""
    t = scores.shape[0]
    s = np.where(np.tril(np.ones((t, t), bool)), np.asarray(scores), np.nan)
    thr = np.where(want, s, np.inf).min(1, keepdims=True)
    return ((s == thr) & ~want).any(1).reshape(t // cq, cq).any(1)


# topk, cq, ck, ties [, t]; ties: True a coarse grid, "some" the
# queries of two chunks flat (a surplus tie there and in no other
# chunk), "all" every query flat (every valid key equal). The rows of 128
# and 192 span 8 and 12 chunks, topk under, at and over a chunk's prefix
# (chunks with and without a top-k), and there no score is the relu's 0,
# so the only ties are the case's.
_SELECT_CASES = [
    (topk, cq, ck, ties) for ties in (False, True)
    for topk, cq, ck in ((8, 16, 16), (16, 32, 16), (0, 16, 64),
                         (100, 64, 8))] + [
    (8, 16, 16, False, 128), (16, 16, 32, "some", 128),
    (40, 16, 16, "some", 128), (48, 16, 16, "all", 128),
    (40, 16, 8, True, 128), (24, 16, 32, "some", 192),
    (64, 16, 16, False, 192), (100, 16, 64, "all", 192),
    (0, 16, 16, "some", 192)]


@pytest.mark.parametrize(
    "case", _SELECT_CASES, ids=lambda c: "-".join(str(x) for x in c))
def test_select_is_top_k_a_row_with_its_tie_rule(case):
    topk, cq, ck, ties, t = (case + (64,))[:5]
    flat = {"some": [(0, 2, cq), (1, 2, cq), (0, t // cq - 3, cq)],
            "all": [(row, 0, t) for row in (0, 1)]}.get(ties, ())
    qi, ki, w = indexer(3, t=t, ties=ties, flat=flat, positive=t > 64)
    out = dsa_ops._dsa_select(
        {"QI": [qi], "KI": [ki], "W": [w]},
        {"scale": dsa_ops.index_scale(3, 8), "topk": topk, "q_chunk": cq,
         "kv_chunk": ck})
    live, lse = (np.asarray(out[s][0]) for s in ("Live", "IndexLse"))
    # (a chunk of 16 takes a word row, half its bits)
    assert out["Selected"][0].shape == (2, t // cq * -(-cq // 32), t)
    selected = np.asarray(dsa_ops.unpack(out["Selected"][0], cq))
    scores = plain_scores(qi, ki, w)
    k = topk or t
    for row in range(2):
        want = plain_choice(scores[row], k)
        if ties is True:    # the case holds ties AT the threshold
            assert any((np.asarray(scores[row, p, :p + 1])
                        == np.asarray(scores[row, p])[want[p]].min()).sum()
                       > 1 for p in range(k, t)) or k >= t
        surplus = surplus_ties(scores[row], want, cq)
        if ties == "some" and k < t:   # both branches of the top-k ran
            assert surplus.any() and not surplus[-1] and not surplus[0]
        elif ties == "all":
            assert surplus[-(-k // cq):].all()
        elif not ties and t > 64:
            assert not surplus.any()
        np.testing.assert_array_equal(selected[row], want)
        masked = np.where(want, np.asarray(scores[row]), -np.inf)
        np.testing.assert_allclose(
            lse[row], jax.scipy.special.logsumexp(masked, axis=1), rtol=1e-6)
        blocks = want.reshape(t // cq, cq, t // ck, ck).any((1, 3))
        np.testing.assert_array_equal(live[row] != 0, blocks)
    # rows below and above k both occur
    counts = selected.sum(-1)
    assert (counts == np.minimum(np.arange(t) + 1, k)).all()
    # and the rows a check reads: the op that unpacks
    rows = dsa_ops._dsa_selected_rows(
        {"Selected": out["Selected"], "Live": out["Live"]}, {"last": 8})
    np.testing.assert_array_equal(rows["Out"][0], selected[:, -8:])
    assert rows["Out"][0].dtype == jnp.int8


@pytest.mark.parametrize("t,topk,cq,ck,kernel,want", [
    # the cell's row: chunks 4 .. 31 make a top-k; dsa.topk.fwd's passes
    # read their live key blocks, XLA's ops the row's 16,384 each
    (16384, 2048, 512, 512, True, (265216, 265216)),
    (16384, 2048, 512, 512, False, (28 * 16384, 265216)),
    # the dense stage: no chunk makes a pass
    (16384, 16384, 512, 512, True, (0, 0)),
    # key blocks that cut a chunk: the prefix in whole blocks
    (192, 24, 16, 64, True, (64 * 3 + 128 * 4 + 192 * 4, 16 * 77)),
    # a row shorter than a chunk, under and over topk
    (48, 8, 48, 16, False, (48, 48)), (64, 100, 64, 8, True, (0, 0))])
def test_the_columns_a_top_k_walks(t, topk, cq, ck, kernel, want):
    assert dsa_ops.columns(t, topk, cq, ck, kernel) == dict(
        zip(("walked", "causal"), want))


def test_the_columns_counter_holds_a_lowered_call():
    from paddle_tpu import flags, monitor

    t, cq = 192, 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qi, ki, w = (layers.data(n, shape=list(shape), dtype="float32",
                                 append_batch_size=False)
                     for n, shape in (("qi", (2, 3, t, 8)),
                                      ("ki", (2, 1, t, 8)),
                                      ("w", (2, t, 3))))
        outs = layers.dsa_select(qi, ki, w, 24, q_chunk=cq, kv_chunk=32)
    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        assert dsa_ops.topk_columns() == {}     # (building lowers nothing)
        a, b, c = indexer(0, t=t)
        fluid.Executor().run(main, feed={"qi": a, "ki": b, "w": c},
                             scope=fluid.Scope(), fetch_list=list(outs))
        got = dsa_ops.topk_columns()
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    # two rows; chunks 1 .. 11 make a top-k (the first has 16 keys for a
    # topk of 24), here as XLA's ops: 192 columns a chunk for prefixes of
    # 32 .. 192 keys
    assert got == {"walked": 2 * 11 * 192, "causal": 2 * 16 * 77}


@pytest.mark.parametrize("ties", [True, "all"])
def test_the_selects_kernels_are_xlas_ops(monkeypatch, ties):
    """``dsa.score.fwd`` and ``dsa.topk.fwd`` through the interpreter (8
    chunks of 128, the first without a top-k): the op's three outputs
    are those of XLA's ops, to the bit (the inputs lie on a grid on which
    every sum is exact, ties included; "all": every valid key equal, so
    every chunk takes the position passes behind the kernel's
    thresholds)."""
    qi, ki, w = indexer(11, b=1, t=1024, ties=True, flat=[
        (0, 0, 1024)] if ties == "all" else ())
    ins = {"QI": [qi], "KI": [ki], "W": [w]}
    attrs = {"scale": dsa_ops.index_scale(3, 8), "topk": 200,
             "q_chunk": 128, "kv_chunk": 128}
    assert not dsa_score.score_tile(128, 128, 3, 8)
    assert not dsa_score.topk_tile(128, 128, 1024)
    want = dsa_ops._dsa_select(ins, attrs)
    monkeypatch.setattr(dsa_score, "_INTERPRET", True)
    assert dsa_score.score_tile(128, 128, 3, 8)
    assert dsa_score.topk_tile(128, 128, 1024)
    assert not dsa_score.topk_tile(96, 128, 1024)       # (row blocks of 64)
    assert not dsa_score.topk_tile(512, 512, 32768)     # (32 MB of keys)
    got = dsa_ops._dsa_select(ins, attrs)
    for slot in ("Selected", "Live", "IndexLse"):
        np.testing.assert_array_equal(got[slot][0], want[slot][0])
    assert np.asarray(dsa_ops.unpack(got["Selected"][0], 128)).sum(-1)[
        0].tolist() == np.minimum(np.arange(1024) + 1, 200).tolist()


@pytest.mark.parametrize("c", [3, 7])     # (a row's every key; 2048 of 4096)
def test_the_threshold_kernel_at_the_cells_chunk(monkeypatch, c):
    """``dsa.topk.fwd`` through the interpreter at the cell's chunk of
    512 queries and key blocks of 512, a row of 4096: chunk ``c``'s
    thresholds and the keys that reach them are those ``choose`` counts
    with XLA's ops over the whole row (scores with ties, a negative
    zero and both infinities' neighbours among them)."""
    monkeypatch.setattr(dsa_score, "_INTERPRET", True)
    assert dsa_score.topk_tile(512, 512, 4096)
    r = np.random.RandomState(c)
    scores = np.round(r.randn(512, 4096) * 8) / 8
    scores[:, 5], scores[:, 9], scores[3] = -0.0, 3e38, 0.125
    scores[:, 11] = -3e38
    scores = jnp.asarray(scores, jnp.float32)
    valid = jnp.arange(4096)[None, :] <= c * 512 + jnp.arange(512)[:, None]
    thr, reach = dsa_score.threshold_rows(c, scores, 2048, 512)
    assert thr.dtype == jnp.uint32 and reach.dtype == jnp.int32
    np.testing.assert_array_equal(
        dsa_ops.choose(scores, valid, 2048, (thr, reach)),
        dsa_ops.choose(scores, valid, 2048))
    keys = np.where(valid, np.asarray(dsa_ops._sortable(scores)), 0)
    want = np.minimum(c * 512 + np.arange(512) + 1, 2048)
    kth = -np.sort(-keys.astype(np.int64), axis=1)[np.arange(512), want - 1]
    np.testing.assert_array_equal(np.asarray(thr).astype(np.int64), kth)
    np.testing.assert_array_equal(
        reach, (keys.astype(np.int64) >= kth[:, None]).sum(1))


def test_choose_counts_exact_ties_by_position():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 3.0, 0.5, 3.0, -2.0, 3.0]])
    valid = jnp.ones((1, 8), bool)
    for k, want in ((1, [1]), (3, [1, 2, 3]), (4, [1, 2, 3, 5]),
                    (6, [0, 1, 2, 3, 5, 7]), (8, list(range(8)))):
        got = np.flatnonzero(np.asarray(dsa_ops.choose(scores, valid, k))[0])
        assert got.tolist() == want, k
    none = dsa_ops.choose(scores, jnp.zeros((1, 8), bool), 3)
    assert not np.asarray(none).any()


def test_a_selection_is_packed_a_bit_a_pair_and_comes_back():
    r = np.random.RandomState(0)
    mask = r.rand(3, 64, 96) < 0.3
    mask[0, 63], mask[1, :, 5] = True, False    # the sign bit's row too
    words = dsa_score.pack_rows(jnp.asarray(mask))
    assert words.shape == (3, 2, 96) and words.dtype == jnp.int32
    np.testing.assert_array_equal(dsa_score.unpack_rows(words), mask)
    # rows 16 .. 48 alone: a slab of a block, as the backward walks it
    np.testing.assert_array_equal(dsa_score.unpack_rows(words, 16, 32),
                                  mask[:, 16:48])
    # a row of two chunks of 32: each chunk's rows in words of its own
    whole = dsa_score.unpack(words[:, :, :64].reshape(3, 2, 64), 32)
    np.testing.assert_array_equal(
        whole, np.concatenate([dsa_score.unpack_rows(words[:, i:i + 1, :64])
                               for i in (0, 1)], axis=1))
    # a chunk of 24 queries, no multiple of 32: one word row, 24 bits
    odd = dsa_score.pack_rows(jnp.asarray(mask[:, :24]))
    assert odd.shape == (3, 1, 96)
    np.testing.assert_array_equal(dsa_score.unpack_rows(odd)[:, :24],
                                  mask[:, :24])
    assert not np.asarray(dsa_score.unpack_rows(odd)[:, 24:]).any()


def test_a_dead_block_fetches_a_live_neighbour():
    live = jnp.asarray([[[1, 0, 0, 0], [0, 1, 0, 0], [1, 0, 1, 0],
                         [0, 1, 0, 1]]])
    # along a q-row's k-blocks (the forward): the next live one, behind
    # the last the last
    assert np.asarray(fa._fetched(live, 2))[0].tolist() == [
        [0, 0, 0, 0], [1, 1, 1, 1], [0, 2, 2, 2], [1, 1, 3, 3]]
    # along a k-row's q-blocks (the backward); -1: nobody reads the row
    none = live.at[0, :, 2].set(0)
    assert np.asarray(fa._fetched(none, 1))[0].T.tolist() == [
        [0, 2, 2, 2], [1, 1, 3, 3], [-1, -1, -1, -1], [3, 3, 3, 3]]


# --- the attention under a selection ---------------------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def packed(mask, cq, ck):
    """A [b, t, t] mask as ``dsa_select`` hands it on: (selected
    [b, t / 32, t] int32, live [b, t / cq, t / ck] int32)."""
    b, t, _ = mask.shape
    blocks = jnp.asarray(mask).reshape(b, t // cq, cq, t // ck, ck)
    return (dsa_score.pack_rows(blocks.reshape(b, t // cq, cq, t)).reshape(
        b, t // 32, t), blocks.any((2, 4)).astype(jnp.int32))


def attention_case(seed, t=512, h=4, hk=2, dh=128, k=40, dead="a block"):
    """-> (q, k, v, cotangent, the selection as a mask, packed with its
    live table a block of t / 4)."""
    r = np.random.RandomState(seed)
    q, g = (jnp.asarray(r.randn(1, h, t, dh), jnp.float32) for _ in range(2))
    kk, v = (jnp.asarray(r.randn(1, hk, t, dh), jnp.float32)
             for _ in range(2))
    valid = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    sel = dsa_ops.choose(jnp.asarray(r.randn(t, t), jnp.float32), valid, k)
    n = t // 4
    if dead == "a block":
        # nothing is chosen in the third quarter of the queries of the
        # first quarter of the keys
        sel = sel.at[2 * n:3 * n, :n].set(False)
    else:       # "a k-row": nobody reads the second quarter of the keys
        sel = sel.at[:, n:2 * n].set(False)
    sel = sel[None]
    return q, kk, v, g, sel, *packed(sel, n, n)


@pytest.mark.parametrize("dead", ["a block", "a k-row"])
def test_kernels_read_the_selection_as_the_composition_masks(interpreted,
                                                             dead):
    q, k, v, g, mask, sel, live = attention_case(0, dead=dead)
    assert fa.bhtd_selected(4, 512, 512, 128, 128, dh=128, group=2,
                            blocks=(4, 4))
    table = np.asarray(live)[0]
    assert (not table[2, 0] and table[3].all() if dead == "a block"
            else not table[:, 1].any())
    assert np.asarray(mask).sum(-1).min() > 0
    scale = 1.0 / np.sqrt(128)
    out, lse = fa.flash_attention_fwd(q, k, v, None, None, None, 0.0, 128,
                                      128, True, selected=sel, live=live)
    want, want_lse = fa._reference_attention_with_lse(
        q, k, v, None, scale, causal=True, selected=mask)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse, want_lse, atol=2e-6)
    grads = fa.flash_attention_bwd(q, k, v, None, None, out, lse, g, None,
                                   0.0, 128, 128, True, selected=sel,
                                   live=live)
    _, vjp = jax.vjp(lambda *a: fa._reference_attention(
        *a, None, scale, causal=True, selected=mask), q, k, v)
    for got, ref in zip(grads, vjp(g)):
        np.testing.assert_allclose(got, ref, atol=2e-5)


def test_a_call_the_kernels_do_not_take_runs_the_composition(interpreted):
    """Blocks of 64 make the backward the split pair, which carries no
    selection: the entry points run the dense composition."""
    q, k, v, g, mask, sel, live = attention_case(1, t=256, k=24)
    assert not fa.bhtd_selected(4, 256, 256, 64, 64, dh=128, group=2,
                                blocks=(4, 4))
    # nor a tile whose blocks are not the live table's (the packing's)
    assert not fa.bhtd_selected(4, 512, 512, 128, 128, dh=128, group=2,
                                blocks=(2, 4))
    out, lse = fa.flash_attention_fwd(q, k, v, None, None, None, 0.0, 64, 64,
                                      True, selected=sel, live=live)
    want, want_lse = fa._reference_attention_with_lse(
        q, k, v, None, 1.0 / np.sqrt(128), causal=True, selected=mask)
    np.testing.assert_allclose(out, want, atol=1e-6)
    np.testing.assert_allclose(lse, want_lse, atol=1e-6)
    with pytest.raises(ValueError, match="a selection is"):
        fa._selection(mask.astype(jnp.int8), live, q, k, fa._seed_arr(None),
                      2)


def sdpa_program(selected, t=64, h=4, hk=2, dh=8, seed=5):
    """(out, lse, q@GRAD, k@GRAD, v@GRAD) of the op on the CPU, with or
    without a selection (a [2, t, t] mask, fed packed a chunk of t)."""
    r = np.random.RandomState(seed)
    arrs = {"q": r.randn(2, h, t, dh), "k": r.randn(2, hk, t, dh),
            "v": r.randn(2, hk, t, dh)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        vs = {n: layers.data(n, shape=list(a.shape), dtype="float32",
                             append_batch_size=False)
              for n, a in arrs.items()}
        for v in vs.values():
            v.stop_gradient = False
        kw, fed = {}, {}
        if selected is not None:
            fed = dict(zip(("selected", "live"), packed(selected, t, t)))
            kw = {n: layers.data(n, shape=list(a.shape), dtype="int32",
                                 append_batch_size=False)
                  for n, a in fed.items()}
        out, lse = layers.scaled_dot_product_attention(
            vs["q"], vs["k"], vs["v"], 0.3, with_lse=True, **kw)
        append_backward(layers.reduce_sum(layers.square(out)))
    feed = {n: a.astype(np.float32) for n, a in arrs.items()}
    feed.update({n: np.asarray(a) for n, a in fed.items()})
    return fluid.Executor().run(main, feed=feed, scope=fluid.Scope(),
                                fetch_list=[out, lse, "q@GRAD", "k@GRAD",
                                            "v@GRAD"])


def test_a_selection_of_every_earlier_key_is_causal_attention():
    """k >= t: the op under the selection gives the bits of the call
    without one, forward and backward."""
    t = 64
    every = np.tril(np.ones((t, t), np.int8))[None].repeat(2, 0)
    under, plain = sdpa_program(every), sdpa_program(None)
    np.testing.assert_array_equal(under[0], plain[0])
    for a, b in zip(under[2:], plain[2:]):
        np.testing.assert_array_equal(a, b)
    # and the logsumexp rows are real under a selection (the dense
    # family's placeholder is zeros)
    assert np.abs(under[1]).max() > 0 and not np.abs(plain[1]).any()
    fewer = every.copy()
    fewer[:, 40:, :10] = 0
    assert np.abs(sdpa_program(fewer)[0] - plain[0]).max() > 1e-3


def test_the_dispatch_counter_says_who_read_the_selection():
    from paddle_tpu import flags, monitor

    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        sdpa_program(np.tril(np.ones((64, 64), np.int8))[None].repeat(2, 0))
        rows = attention_ops.dispatch_counts(sels=True)
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    assert rows == {"dense fwd b2 tq64 tk64 h4 kv2 dh8 sel=dense": 1,
                    "dense bwd b2 tq64 tk64 h4 kv2 dh8 sel=dense": 1}


# --- the indexer's loss ------------------------------------------------------


def plain_index_loss(qi, ki, w, q, k, selected, attn_scale):
    """L_I by the equations, whole [t, t] tensors, autodiff's to take."""
    index = plain_scores(qi, ki, w)
    chosen = selected != 0
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, 1)) * attn_scale
    prob = jax.nn.softmax(jnp.where(chosen[:, None], s, -1e30), -1)
    target = jax.lax.stop_gradient(jnp.mean(prob, 1))
    log_i = jax.nn.log_softmax(jnp.where(chosen, index, -1e30), -1)
    some = chosen & (target > 0)
    kl = jnp.where(some, target * (jnp.log(jnp.where(some, target, 1.0))
                                   - log_i), 0.0)
    return jnp.sum(kl) / (index.shape[0] * index.shape[1])


@pytest.mark.parametrize("topk", [8, 0])
def test_index_loss_and_its_hand_written_gradient(topk):
    qi, ki, w = indexer(7)
    r = np.random.RandomState(8)
    q = jnp.asarray(r.randn(2, 4, 64, 8), jnp.float32)
    k = jnp.asarray(r.randn(2, 2, 64, 8), jnp.float32)
    attrs = {"scale": dsa_ops.index_scale(3, 8), "topk": topk, "q_chunk": 16,
             "kv_chunk": 32, "attn_scale": 0.4}
    sel = dsa_ops._dsa_select({"QI": [qi], "KI": [ki], "W": [w]}, attrs)
    selected, ilse = sel["Selected"][0], sel["IndexLse"][0]
    mask = dsa_ops.unpack(selected, 16)
    _, lse = fa._reference_attention_with_lse(
        q, k, k, None, 0.4, causal=True, selected=mask)
    ins = {"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
           "Lse": [lse], "Selected": [selected], "IndexLse": [ilse]}
    out = highest(lambda: dsa_ops._dsa_index_loss(ins, attrs))()
    want, grads = highest(jax.value_and_grad(
        lambda a, b, c: plain_index_loss(a, b, c, q, k, mask, 0.4),
        argnums=(0, 1, 2)))(qi, ki, w)
    np.testing.assert_allclose(out["Loss"][0], want, rtol=2e-6)
    assert out["Loss"][0].shape == ()
    assert float(want) > 0.05
    for slot, ref in zip(("DQI", "DKI", "DW"), grads):
        np.testing.assert_allclose(out[slot][0], ref, rtol=2e-4,
                                   atol=2e-6 * float(jnp.abs(ref).max()))
    # the grad op scales the saved gradient by the loss's cotangent
    scaled = dsa_ops._dsa_index_loss_grad(
        {**ins, **{s: out[s] for s in ("DQI", "DKI", "DW")},
         "GRAD::Loss": [jnp.asarray([0.5], jnp.float32)]}, attrs)
    np.testing.assert_allclose(scaled["GRAD::KI"][0], 0.5 * out["DKI"][0],
                               rtol=1e-6)


@pytest.mark.parametrize("topk", [40, 0])
def test_the_loss_kernel_is_xlas_ops_a_tile(topk, monkeypatch):
    """``dsa.loss.bwd`` through the interpreter (tiles of 256 x 128 over
    a row of 512, grouped heads, rows below and above k) against
    ``loss_row``'s scan: the loss and the three gradients."""
    r = np.random.RandomState(0)
    qi, ki, w = indexer(9, b=1, t=512, di=16)
    q = jnp.asarray(r.randn(1, 4, 512, 32), jnp.float32)
    k = jnp.asarray(r.randn(1, 2, 512, 32), jnp.float32)
    attrs = {"scale": dsa_ops.index_scale(3, 16), "topk": topk,
             "q_chunk": 256, "kv_chunk": 128, "attn_scale": 0.2}
    sel = dsa_ops._dsa_select({"QI": [qi], "KI": [ki], "W": [w]}, attrs)
    _, lse = fa._reference_attention_with_lse(
        q, k, k, None, 0.2, causal=True,
        selected=dsa_ops.unpack(sel["Selected"][0], 256))
    ins = {"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
           "Lse": [lse], "Selected": sel["Selected"],
           "IndexLse": sel["IndexLse"]}
    assert not dsa_score.loss_tile(256, 128, 3, 16)
    want = dsa_ops._dsa_index_loss(ins, attrs)
    monkeypatch.setattr(dsa_score, "_INTERPRET", True)
    assert dsa_score.loss_tile(256, 128, 3, 16)
    got = dsa_ops._dsa_index_loss(ins, attrs)
    assert float(want["Loss"][0]) > 0.05
    for slot in ("Loss", "DQI", "DKI", "DW"):
        np.testing.assert_allclose(
            got[slot][0], want[slot][0], rtol=1e-5,
            atol=1e-6 * float(jnp.abs(want[slot][0]).max()))


def test_chunk_cuts_a_row_in_whole_parts():
    assert [dsa_ops.chunk(t, 512) for t in (16384, 640, 64, 997)] == [
        512, 320, 64, 1]


# --- fed rotary positions ---------------------------------------------------


def test_fed_positions_turn_each_pair_by_its_section():
    t, dh = 16, 16
    r = np.random.RandomState(2)
    pos = np.stack([np.arange(t), r.randint(0, 9, t), r.randint(0, 9, t)])
    cos, sin = rope.cos_sin(t, dh, 1e4, positions=jnp.asarray(pos),
                            sections=(2, 3, 3))
    freq = 1e4 ** (-np.arange(0, dh, 2) / dh)
    axis = [0, 0, 1, 1, 1, 2, 2, 2]
    np.testing.assert_allclose(cos, np.cos(pos[axis].T * freq), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(pos[axis].T * freq), rtol=1e-5,
                               atol=1e-6)
    # three equal rows 0 .. t - 1 are the implicit positions, bit for bit
    same = rope.cos_sin(t, dh, 1e4, positions=jnp.tile(jnp.arange(t), (3, 1)),
                        sections=(2, 3, 3))
    for a, b in zip(same, rope.cos_sin(t, dh, 1e4)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="do not cover"):
        rope.cos_sin(t, dh, 1e4, positions=jnp.asarray(pos), sections=(2, 3))


@pytest.mark.parametrize("kernel", [False, True])
def test_the_rotary_op_at_fed_positions(kernel, monkeypatch):
    """The op (XLA's form, and ``rope.fwd`` / ``rope.bwd`` through the
    interpreter with the per-head norm) at unequal position rows against
    the plain rotation, forward and backward."""
    if kernel:
        monkeypatch.setattr(rope, "_INTERPRET", True)
    b, t, h, hk, dh = 1, 32, 4, 2, 128
    dt = jnp.bfloat16 if kernel else jnp.float32
    r = np.random.RandomState(4)
    q = jnp.asarray(r.randn(b, t, h, dh), dt)
    k = jnp.asarray(r.randn(b, t, hk, dh), dt)
    pos = jnp.asarray(np.stack([np.arange(t), r.randint(0, 9, t),
                                r.randint(0, 9, t)]), jnp.int32)
    gains = [jnp.asarray(1 + 0.1 * r.randn(dh), jnp.float32)
             for _ in range(2)]
    attrs = {"theta": 1e7, "layout": "bthd", "mrope_section": [16, 24, 24],
             "norm_epsilon": 1e-6}
    ins = {"Q": [q], "K": [k], "Positions": [pos], "QScale": [gains[0]],
           "KScale": [gains[1]]}
    assert (attention_ops._rope_tile(q, k, attrs, "fwd", norm=True)
            is not None) == kernel
    out = attention_ops._rotary_embedding(ins, attrs)

    def plain(x, gain):
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        x = jnp.swapaxes((x * gain).astype(dt).astype(jnp.float32), 1, 2)
        freq = 1e7 ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        axis = np.repeat(np.arange(3), [16, 24, 24])
        ang = pos.astype(jnp.float32)[axis].T * freq
        cos, sin = (jnp.concatenate([f(ang)] * 2, -1)
                    for f in (jnp.cos, jnp.sin))
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    tol = dict(rtol=2e-2, atol=2e-2) if kernel else dict(rtol=1e-5,
                                                         atol=1e-5)
    np.testing.assert_allclose(out["QOut"][0].astype(jnp.float32),
                               plain(q, gains[0]), **tol)
    np.testing.assert_allclose(out["KOut"][0].astype(jnp.float32),
                               plain(k, gains[1]), **tol)
    if not kernel:
        return
    # the backward is the rotation by the negated angles at the SAME
    # positions: ``rope.bwd`` against the XLA form's vjp in float32
    gq = jnp.asarray(r.randn(b, h, t, dh), dt)
    gk = jnp.asarray(r.randn(b, hk, t, dh), dt)
    got = attention_ops._rotary_embedding_grad(
        {**ins, "GRAD::QOut": [gq], "GRAD::KOut": [gk]}, attrs)
    f32 = lambda x: x.astype(jnp.float32)
    _, vjp = jax.vjp(
        lambda q_, k_, a, b_: tuple(attention_ops._rotary_xla(
            {"Q": [q_], "K": [k_], "Positions": [pos], "QScale": [a],
             "KScale": [b_]}, attrs)[s][0] for s in ("QOut", "KOut")),
        f32(q), f32(k), *gains)
    for slot, want in zip(("GRAD::Q", "GRAD::K", "GRAD::QScale",
                           "GRAD::KScale"), vjp((f32(gq), f32(gk)))):
        np.testing.assert_allclose(
            f32(got[slot][0]), want, rtol=3e-2,
            atol=3e-2 * float(jnp.abs(want).max()))
