"""Causal attention that also FORGETS (a sliding window: a query sees
the last ``window`` positions, itself among them) through the BHTD
Pallas kernels (interpreter mode on the CPU) and the dense composition,
against explicit float32 scores: forward and the three gradients, with
an edge block worked on whole and walked in sub-tiles;
``window=None`` and a window as long as the row give the causal call bit
for bit; the live-step predicate, both index maps, the sub-tiles'
predicates and ``bhtd_pairs`` against a brute-force table of visible
pairs; the sdpa op's dispatch row."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags, monitor
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel import flash_attention as fa


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


@pytest.fixture(params=[None, 64, 32], ids=["whole", "sub64", "sub32"])
def edge_sub(request, monkeypatch):
    """An edge block worked on whole (the blocks here are under the
    program's sub-tile), or walked in sub-tiles of that side: the helper
    reached at small blocks."""
    if request.param:
        monkeypatch.setattr(fa, "_EDGE_SUB", request.param)
    return request.param


def qkv(h, hk, t, dh=16, seed=0, b=1):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, h, t, dh) * 0.5, jnp.float32),
            jnp.asarray(r.randn(b, hk, t, dh) * 0.5, jnp.float32),
            jnp.asarray(r.randn(b, hk, t, dh), jnp.float32),
            jnp.asarray(r.randn(b, h, t, dh), jnp.float32))


def visible(t, window):
    """[t, t] bool: query p sees key s (HF: kv_idx > q_idx - window)."""
    p, s = np.arange(t)[:, None], np.arange(t)[None, :]
    return (s <= p) & (p - s < (window or t))


def explicit(q, k, v, window):
    """(out, lse) by explicit float32 scores, every query head reading
    key/value head q // group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(visible(q.shape[2], window), s, -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.scipy.special.logsumexp(s, -1, keepdims=True))


# (query heads, key/value heads, t, block, window): windows smaller than,
# equal to and larger than a block, one that no block divides and that
# does not divide the row, one a position short of the row; groups 1, 7
# and 8; at blocks of 128 (the ONE backward call; of 64 the pair, whose
# edge blocks stay whole) a window of a block, of two, one no sub-tile
# divides, one under a sub-tile (a row's first live sub-tile then holds
# no key it sees: rows 32.. of a block under w20 start in keys 0..31),
# 1000 (none: the plain causal call)
CASES = [
    (2, 2, 256, 64, 16), (2, 2, 256, 64, 64), (2, 2, 256, 64, 100),
    (7, 1, 256, 64, 96), (8, 1, 256, 128, 129), (14, 2, 192, 64, 191),
    (2, 2, 384, 128, 1), (7, 1, 320, 64, 200),
    (2, 2, 512, 128, 128), (2, 1, 512, 128, 256), (7, 1, 384, 128, 100),
    (1, 1, 256, 128, 20), (8, 1, 384, 128, 1000),
]


@functools.cache
def forward_and_explicit(h, hk, t, blk, window):
    """(the forward kernel's out and lse, the explicit scores' out, lse
    and three gradients) of a case, made once: neither walks an edge
    block in sub-tiles, so the three ``edge_sub`` of a case share them.
    Called under ``interpreted``."""
    q, k, v, g = qkv(h, hk, t)

    @jax.jit
    def scores(q, k, v, g):
        want, vjp = jax.vjp(lambda q, k, v: explicit(q, k, v, window),
                            q, k, v)
        return want, vjp((g, jnp.zeros_like(want[1])))

    with jax.default_matmul_precision("highest"):
        return (fa.flash_attention_fwd(q, k, v, causal=True, window=window,
                                       q_block=blk, k_block=blk),
                *scores(q, k, v, g))


@pytest.mark.parametrize("h,hk,t,blk,window", CASES)
def test_kernels_agree_with_explicit_scores(h, hk, t, blk, window,
                                            interpreted, edge_sub):
    q, k, v, g = qkv(h, hk, t)
    tile = fa.bhtd_tile(h, t, t, blk, blk, dh=16, group=h // hk)
    assert tile is not None
    assert fa.bhtd_edge_tile(tile, True) == (
        (edge_sub, edge_sub) if edge_sub and edge_sub < blk else None)
    (out, lse), want, wants = forward_and_explicit(h, hk, t, blk, window)
    with jax.default_matmul_precision("highest"):
        grads = fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=True, window=window,
            q_block=blk, k_block=blk)
    np.testing.assert_allclose(out, want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want[1], rtol=1e-5, atol=1e-5)
    for a, b, name in zip(grads, wants, ("dq", "dk", "dv")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


# (query heads, key/value heads, t, block, window, the kernel's layout):
# a band of two blocks, of one under a group of 7, and blocks of 64,
# which cannot be cut from a row
@pytest.mark.parametrize("h,hk,t,blk,window,form", [
    (2, 2, 512, 128, 200, "rows"), (7, 1, 384, 128, 100, "rows"),
    (2, 1, 256, 64, 100, "column")])
def test_windowed_forward_lse_as_rows_or_a_column(h, hk, t, blk, window,
                                                  form, interpreted):
    """The band's forward writes its logsumexp as rows (a dead step of a
    short band repeats the row's last live block and the last step
    still writes) or, at blocks of 64, as the column: the explicit
    scores' to float32 rounding either way."""
    q, k, v, _ = qkv(h, hk, t)
    kw = dict(causal=True, window=window, q_block=blk, k_block=blk)
    tile = fa.bhtd_tile(h, t, t, blk, blk, dh=16, group=h // hk)
    assert fa.bhtd_stats_form(tile, t) == form
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, **kw)
        want_out, want_lse = explicit(q, k, v, window)
    assert lse.shape == (1, h, t, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, want_lse, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h,hk,t,window", [(2, 2, 48, 7), (7, 1, 40, 40),
                                           (8, 1, 33, 12)])
def test_dense_composition_agrees_with_explicit_scores(h, hk, t, window):
    """Off the TPU (no interpreter) the call IS the dense composition."""
    q, k, v, g = qkv(h, hk, t, seed=2)
    assert fa.bhtd_tile(h, t, t, dh=16, group=h // hk) is None
    with jax.default_matmul_precision("highest"):
        (out, lse), vjp = jax.vjp(
            lambda q, k, v: fa.flash_attention_with_lse(
                q, k, v, None, None, None, 0.0, None, None, True, window),
            q, k, v)
        want, want_vjp = jax.vjp(lambda q, k, v: explicit(q, k, v, window),
                                 q, k, v)
    np.testing.assert_allclose(out, want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want[1], rtol=1e-5, atol=1e-5)
    zero = jnp.zeros_like(lse)
    for a, b in zip(vjp((g, zero)), want_vjp((g, zero))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("window", [None, 256, 1000])
@pytest.mark.parametrize("hk,blk", [(2, 64), (1, 64), (1, 128)])
def test_no_window_is_the_causal_call_bit_for_bit(window, hk, blk,
                                                  interpreted, edge_sub):
    q, k, v, g = qkv(2, hk, 256, seed=4)
    kw = dict(causal=True, q_block=blk, k_block=blk)
    (out, lse), grads, plain = the_causal_call(hk, blk, edge_sub)
    got = fa.flash_attention_fwd(q, k, v, window=window, **kw)
    assert bool((got[0] == out).all()) and bool((got[1] == lse).all())
    got = fa.flash_attention_bwd(q, k, v, None, None, out, lse, g,
                                 window=window, **kw)
    assert all(bool((a == b).all()) for a, b in zip(got, grads))
    # and lowers the same program: no band, the sequence's own grid
    assert forward_text(q, k, v, window, kw) == plain


def forward_text(q, k, v, window, kw):
    return jax.jit(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, window=window, **kw)).lower(q, k, v).as_text()


@functools.cache
def the_causal_call(hk, blk, edge_sub):
    """((out, lse), the three gradients, the forward's lowered text) of
    the call WITHOUT a window, made once for the three windows it is
    held against. Called under ``interpreted`` and ``edge_sub``."""
    q, k, v, g = qkv(2, hk, 256, seed=4)
    kw = dict(causal=True, q_block=blk, k_block=blk)
    out, lse = fa.flash_attention_fwd(q, k, v, **kw)
    return ((out, lse), fa.flash_attention_bwd(
        q, k, v, None, None, out, lse, g, **kw),
        forward_text(q, k, v, None, kw))


def test_a_window_needs_causal_self_attention(interpreted):
    q, k, v, _ = qkv(2, 2, 128)
    with pytest.raises(ValueError, match="window=16"):
        fa.flash_attention_fwd(q, k, v, window=16)
    with pytest.raises(ValueError, match="window=16"):
        fa.flash_attention_fwd(q[:, :, :64], k, v, causal=True, window=16)


@pytest.mark.parametrize("t,bq,bk,window", [
    (512, 64, 64, 100), (512, 64, 64, 64), (512, 128, 64, 130),
    (512, 64, 128, 200), (384, 128, 128, 1), (640, 128, 128, 511),
    (16384, 512, 512, 4096)])
def test_band_geometry_against_a_table_of_visible_pairs(t, bq, bk, window):
    """Block (j, kk) is live iff it holds a visible pair; a row's live
    blocks are contiguous, from ``_first_k`` to the causal bound (a
    k-row's: from the diagonal to ``_last_q``); the inner axis is as
    long as the widest row's band; both index maps hand a live step its
    own block and a dead one its row's last live block; a block takes
    the mask iff it holds a pair that is NOT visible."""
    nq, nk = t // bq, t // bk
    if t <= 1024:
        seen = visible(t, window).reshape(nq, bq, nk, bk)
        live = seen.any((1, 3))
        edge = live & ~seen.all((1, 3))
    else:   # by the blocks' corners: the band is convex
        j, kk = np.arange(nq)[:, None], np.arange(nk)[None, :]
        live = (kk * bk <= (j + 1) * bq - 1) & (
            j * bq - ((kk + 1) * bk - 1) < window)
        edge = live & (((kk + 1) * bk - 1 > j * bq)
                       | ((j + 1) * bq - 1 - kk * bk >= window))
    k_steps = fa._k_steps(window, nq, nk, bq, bk)
    q_steps = fa._q_steps(window, nq, nk, bq, bk)
    assert k_steps == live.sum(1).max() and q_steps == live.sum(0).max()
    at_k = fa._step_blocks(True, True, bq, bk, nq, window=window,
                           steps=k_steps)
    at_q = fa._step_blocks(True, False, bq, bk, nq, window=window,
                           steps=q_steps)
    for j in range(nq):
        first = int(fa._first_k(j, bq, bk, window))
        cols = np.flatnonzero(live[j])
        assert (cols == np.arange(first, first + len(cols))).all()
        for r in range(k_steps):
            kk = first + r
            is_live = bool(fa._causal_live(j, kk, bq, bk))
            assert is_live == (kk < nk and bool(live[j, kk]))
            reads = int(at_k(0, 0, j, r)[3])
            assert reads == (kk if is_live else cols[-1])
            if is_live:
                assert bool(fa._on_edge(j, kk, bq, bk, window)) \
                    == bool(edge[j, kk])
    for kk in range(nk):
        rows = np.flatnonzero(live[:, kk])
        first = (kk * bk) // bq
        assert (rows == np.arange(first, first + len(rows))).all()
        last = min(int(fa._last_q(kk, bq, bk, window)), nq - 1)
        assert last == rows[-1]
        for r in range(q_steps):
            reads = int(at_q(0, 0, kk, r)[2])
            assert reads == (first + r if first + r <= last else last)
    if t == 16384:   # the cell's call: 252 of the triangle's 528 blocks
        assert (live.sum(), k_steps, q_steps) == (252, 9, 9)
        assert np.tril(np.ones((nq, nk), bool)).sum() == 528


@pytest.mark.parametrize("t,bq,bk,sub,window", [
    (512, 128, 128, (64, 64), 128), (512, 128, 128, (32, 32), 256),
    (512, 128, 128, (32, 32), 100), (384, 128, 128, (64, 32), 1),
    (512, 128, 64, (32, 64), 129), (512, 128, 128, (64, 64), None),
    (640, 128, 128, (32, 64), 20), (1024, 512, 512, (256, 256), 512),
    (1024, 512, 512, (128, 128), 1000)])
def test_sub_tile_geometry_against_a_table_of_visible_pairs(
        t, bq, bk, sub, window, monkeypatch):
    """A sub-tile of an edge block is dead, plain or edge by the block's
    own predicates at its own corners, as the table of visible pairs
    has it; the slabs of an edge block (one a query sub-tile: what the
    ONE backward call walks) hold exactly its live sub-tiles, each once,
    and take the mask iff they hold a pair that is not visible; every
    edge block of the call has its kind; ``bhtd_pairs`` counts the pairs
    of plain blocks and of slabs (``form=None``: of whole blocks, the
    forward's)."""
    monkeypatch.setattr(fa, "_edge_tile", lambda *_: sub)
    nq, nk, (sq, sk) = t // bq, t // bk, sub
    na, nc = bq // sq, bk // sk
    table = visible(t, window)
    slabs = fa._edge_slabs(t, t, bq, bk, sub, window)
    computed = whole = 0
    for j in range(nq):
        for kk in range(nk):
            block = table[j * bq:(j + 1) * bq, kk * bk:(kk + 1) * bk]
            live, full = block.any(), block.all()
            assert bool(fa._band_live(j, kk, bq, bk, window)) == live
            if live:
                assert bool(fa._on_edge(j, kk, bq, bk, window)) == (not full)
            whole += bq * bk * live
            if not live or full:
                computed += bq * bk * live
                continue
            alive = np.zeros((bq, bk), bool)
            for a in range(na):
                for c in range(nc):
                    part = block[a * sq:(a + 1) * sq, c * sk:(c + 1) * sk]
                    place = (j * na + a, kk * nc + c, sq, sk, window)
                    assert bool(fa._band_live(*place)) == part.any()
                    if part.any():
                        assert bool(fa._on_edge(*place)) == (not part.all())
                    alive[a * sq:(a + 1) * sq,
                          c * sk:(c + 1) * sk] = part.any()
            walked = np.zeros((bq, bk), int)
            for (q0, rows, k0, cols), masked in slabs[j * bq - kk * bk]:
                assert (rows, q0 % sq) == (sq, 0)    # one a query sub-tile
                walked[q0:q0 + rows, k0:k0 + cols] += 1
                assert masked == (
                    not block[q0:q0 + rows, k0:k0 + cols].all())
            assert (walked == alive).all()
            computed += alive.sum()
    assert fa.bhtd_edge_tile((1, bq, bk), True) == sub
    assert fa.bhtd_pairs(t, t, (1, bq, bk), True, window) \
        == (computed, table.sum())
    assert fa.bhtd_pairs(t, t, (1, bq, bk), True, window, form=None) \
        == (whole, table.sum())
    assert fa.bhtd_pairs(t, t, (1, bq, bk), False) == (t * t, t * t)


@pytest.mark.parametrize("t,window,whole,walked", [
    (8192, 512, 8126464, {256: 6094848, 128: 5079040}),
    (16384, 4096, 66060288, {256: 62390272, 128: 60555264}),
    (4096, None, 9437184, {256: 8912896, 128: 8650752})])
def test_pairs_of_the_cells_geometries(t, window, whole, walked,
                                       monkeypatch):
    """The three geometries the decoder cells run at blocks of 512:
    laguna's band of one block (every block an edge: half of what a
    whole block computes is dead), smallthinker's of eight, the plain
    triangle at 4096; the live pairs by rows. The backward walks its
    edge blocks in sub-tiles, the forward works on them whole."""
    tile = (1, 512, 512)
    live = sum(min(p + 1, window or t) for p in range(t))
    assert fa.bhtd_edge_tile(tile, True) == (fa._EDGE_SUB,) * 2
    assert fa.bhtd_pairs(t, t, tile, True, window) \
        == (walked[fa._EDGE_SUB], live)
    assert fa.bhtd_pairs(t, t, tile, True, window, form=None) == (whole, live)
    assert fa.bhtd_pairs(t, t, tile, True, window, form="split") \
        == (whole, live)
    for side, want in walked.items():
        monkeypatch.setattr(fa, "_EDGE_SUB", side)
        assert fa.bhtd_pairs(t, t, tile, True, window) == (want, live)


def test_the_sub_tile_follows_the_blocks_shape():
    """``_EDGE_SUB`` on a side where the block is square and that cuts
    its side in whole parts: one rule, from the block's shape alone;
    nothing to walk where the block is not square or no larger than a
    sub-tile, without ``causal``, in the forward and in the split pair.
    A call of square blocks has the diagonal's kind of edge block and
    the far edge's one or two."""
    sub = fa._EDGE_SUB
    assert fa._edge_tile(2 * sub, 2 * sub) == (sub, sub)
    assert fa._edge_tile(4 * sub, 4 * sub) == (sub, sub)
    assert fa._edge_tile(2 * sub, sub) is None
    assert fa._edge_tile(sub, 4 * sub) is None
    assert fa._edge_tile(sub, sub) is None
    assert fa._edge_tile(sub // 2, sub // 2) is None
    assert fa._edge_tile(sub + sub // 2, sub + sub // 2) is None
    tile, t = (1, 2 * sub, 2 * sub), 8 * sub
    assert fa.bhtd_edge_tile(tile, True) == (sub, sub)
    assert fa.bhtd_edge_tile(tile, False) is None
    assert fa.bhtd_edge_tile(tile, True, form=None) is None
    assert fa.bhtd_edge_tile(tile, True, form="split") is None
    assert fa.bhtd_edge_tile(None, True) is None
    assert fa.bhtd_edge_tile((1, 4 * sub, sub), True) is None
    for window, kinds in ((None, [0]), (2 * sub, [0, 2 * sub]),
                          (3 * sub + 1, [0, 2 * sub, 4 * sub]),
                          (1, [0])):
        assert sorted(fa._edge_slabs(t, t, 2 * sub, 2 * sub, (sub, sub),
                                     window)) == kinds
    assert fa.edge_label((sub, sub)) == f"{sub}x{sub}"
    assert fa.edge_label(None) == ""


def test_grouped_dkv_index_map_walks_each_heads_band():
    """Group 7: the dk/dv grid's inner axis walks 7 heads x the band's
    steps; step r reads query head kv * 7 + r // steps."""
    bq = bk = 64
    nq, window = 8, 100
    steps = fa._q_steps(window, nq, nq, bq, bk)
    at = fa._step_blocks(True, False, bq, bk, nq, 7, window=window,
                         steps=steps)
    for kv in range(2):
        for kk in range(nq):
            for r in range(7 * steps):
                _, g, j, got_kk = (int(x) for x in at(0, kv, kk, r))
                assert got_kk == kk and g == kv * 7 + r // steps
                last = min(int(fa._last_q(kk, bq, bk, window)), nq - 1)
                assert j == min(kk + r % steps, last)


def test_sdpa_op_takes_the_window_and_names_it(interpreted):
    q, k, v, g = qkv(7, 1, 256, dh=128, seed=3)
    attrs = {"layout": "bhtd", "causal": True, "is_test": True,
             "window": 96}
    from paddle_tpu.core import interp

    monitor.reset()
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)
    try:
        out = attention_ops._sdpa({"Q": [q], "K": [k], "V": [v]}, attrs)
        grads = attention_ops._sdpa_grad(
            {"Q": [q], "K": [k], "V": [v], "Out": out["Out"],
             "Lse": out["Lse"], "GRAD::Out": [g]}, attrs)
        attention_ops._sdpa({"Q": [q], "K": [k], "V": [v]},
                            dict(attrs, window=256))     # the whole row
        attention_ops._sdpa({"Q": [q], "K": [k], "V": [v]},
                            dict(attrs, use_pallas=False))
        rows = monitor.snapshot()["pt_attention_dispatch_total"]["values"]
        counts = attention_ops.dispatch_counts(tiles=True)
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
    shape = "b1 tq256 tk256 h7 kv1 dh128"
    assert counts == {
        f"bhtd fwd {shape} w96 [hb1 bq256 bk256]": 1,
        f"bhtd bwd {shape} w96 [hb1 bq256 bk256]": 1,
        f"bhtd fwd {shape} [hb1 bq256 bk256]": 1,   # the parent's row
        f"dense fwd {shape} w96": 1}
    bands = {(r["labels"]["family"], r["labels"].get("band")) for r in rows}
    assert bands == {("bhtd", "skip"), ("bhtd", None), ("dense", "dense")}
    # blocks of 256 are under the program's sub-tile: worked on whole
    assert {(r["labels"]["family"], r["labels"].get("edge"))
            for r in rows} == {("bhtd", ""), ("dense", None)}
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(lambda q, k, v: explicit(q, k, v, 96)[0],
                            q, k, v)
    np.testing.assert_allclose(out["Out"][0], want, rtol=2e-2, atol=2e-3)
    for s, b in zip("QKV", vjp(g)):
        np.testing.assert_allclose(grads[f"GRAD::{s}"][0], b, rtol=2e-2,
                                   atol=5e-3)
    with pytest.raises(NotImplementedError, match="layout='bhtd'"):
        attention_ops._sdpa(
            {"Q": [jnp.swapaxes(q, 1, 2)[:, :, :1]],
             "K": [jnp.swapaxes(k, 1, 2)], "V": [jnp.swapaxes(v, 1, 2)]},
            dict(attrs, layout="bthd"))


def test_dispatch_rows_name_the_sub_tile(interpreted, monkeypatch):
    """A BHTD row's ``edge``: the sub-tiles in which the call walks its
    edge blocks, "" where it works on them whole (the forward; no
    sub-tile cuts the blocks, or nothing is causal);
    ``dispatch_counts(edges=True)`` appends it; with telemetry off
    nothing is counted."""
    from paddle_tpu.core import interp

    monkeypatch.setattr(fa, "_EDGE_SUB", 128)
    q, k, v, g = qkv(2, 1, 256, dh=128, seed=5)
    ins = {"Q": [q], "K": [k], "V": [v]}
    attrs = {"layout": "bhtd", "causal": True, "is_test": True}

    def lower(attrs):
        out = attention_ops._sdpa(ins, attrs)
        attention_ops._sdpa_grad(
            dict(ins, Out=out["Out"], Lse=out["Lse"], **{"GRAD::Out": [g]}),
            attrs)

    monitor.reset()
    tok = interp.set_amp_active(False)
    try:
        lower(attrs)                            # telemetry off: silent
        assert "pt_attention_dispatch_total" not in monitor.snapshot() or \
            not monitor.snapshot()["pt_attention_dispatch_total"]["values"]
        flags.set_flags({"telemetry": True})
        lower(attrs)
        lower(dict(attrs, window=100))
        lower(dict(attrs, causal=False))
        counts = attention_ops.dispatch_counts(forms=True, edges=True)
        rows = monitor.snapshot()["pt_attention_dispatch_total"]["values"]
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
    shape = "b1 tq256 tk256 h2 kv1 dh128"
    assert counts == {
        f"bhtd fwd {shape}": 2, f"bhtd fwd {shape} w100": 1,
        f"bhtd bwd {shape} form=fused edge=128x128": 1,
        f"bhtd bwd {shape} w100 form=fused edge=128x128": 1,
        f"bhtd bwd {shape} form=fused": 1}
    assert all("edge" in r["labels"] for r in rows)
    assert sorted((r["labels"]["pass"], r["labels"]["edge"])
                  for r in rows) == [("bwd", ""), ("bwd", "128x128"),
                                     ("bwd", "128x128"), ("fwd", ""),
                                     ("fwd", "")]


def test_dispatch_rows_name_the_statistics_layout(interpreted, monkeypatch):
    """A BHTD ``fwd`` row's ``stats``: the layout in which the call's
    logsumexp leaves the kernel (``fa.bhtd_stats_form``), rows at the
    cells' shapes and wherever a q block is whole lane tiles of a row,
    column where the kernels' q block is 64;
    ``dispatch_counts(stats=True)`` appends it; no ``bwd`` row and no
    row of another family carries the label."""
    from paddle_tpu.core import interp

    q, k, v, g = qkv(2, 2, 256, dh=128, seed=5)
    ins = {"Q": [q], "K": [k], "V": [v]}
    attrs = {"layout": "bhtd", "causal": True, "is_test": True}

    def lower(attrs):
        out = attention_ops._sdpa(ins, attrs)
        attention_ops._sdpa_grad(
            dict(ins, Out=out["Out"], Lse=out["Lse"], **{"GRAD::Out": [g]}),
            attrs)

    monitor.reset()
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)
    try:
        lower(attrs)
        lower(dict(attrs, use_pallas=False))
        # laguna's window layers and joyai's latent call, as shapes
        attention_ops._note_dispatch(
            "bhtd", "fwd", (1, 8192, 8192, 64, 128, 8, 128), window=512,
            causal=True)
        attention_ops._note_dispatch(
            "bhtd", "fwd", (1, 4096, 4096, 32, 192, 32, 128), causal=True)
        attention_ops._note_dispatch("bthd_small", "fwd",
                                     (64, 256, 256, 8, 64))
        attention_ops._note_dispatch("ring", "fwd", (1, 256, 256, 2, 128))
        with monkeypatch.context() as m:
            m.setattr(fa, "DEFAULT_Q_BLOCK", 64)    # as a caller's q_block
            assert fa.bhtd_tile(2, 256, 256, dh=128) == (2, 64, 256)
            lower(attrs)
        counts = attention_ops.dispatch_counts(stats=True)
        plain = attention_ops.dispatch_counts()
        rows = monitor.snapshot()["pt_attention_dispatch_total"]["values"]
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
    shape = "b1 tq256 tk256 h2 dh128"
    assert counts == {
        f"bhtd fwd {shape} stats=rows": 1, f"bhtd fwd {shape} stats=column": 1,
        f"bhtd bwd {shape}": 2,
        f"dense fwd {shape}": 1, f"dense bwd {shape}": 1,
        "bhtd fwd b1 tq8192 tk8192 h64 kv8 dh128 w512 stats=rows": 1,
        "bhtd fwd b1 tq4096 tk4096 h32 dk192 dv128 stats=rows": 1,
        "bthd_small fwd b64 tq256 tk256 h8 dh64": 1,
        f"ring fwd {shape}": 1}
    # (the keys without the label are the parent's)
    assert plain[f"bhtd fwd {shape}"] == 2
    assert sorted({(r["labels"]["family"], r["labels"]["pass"],
                    r["labels"].get("stats")) for r in rows}) == sorted({
        ("bhtd", "fwd", "rows"), ("bhtd", "fwd", "column"),
        ("bhtd", "bwd", None), ("dense", "fwd", None),
        ("dense", "bwd", None), ("bthd_small", "fwd", None),
        ("ring", "fwd", None)}, key=str)
