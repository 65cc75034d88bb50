"""Causal attention that also FORGETS (a sliding window: a query sees
the last ``window`` positions, itself among them) through the BHTD
Pallas kernels (interpreter mode on the CPU) and the dense composition,
against explicit float32 scores: forward and the three gradients;
``window=None`` and a window as long as the row give the causal call bit
for bit; the live-step predicate and both index maps against a
brute-force table of visible pairs; the sdpa op's dispatch row."""

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
# and 8
CASES = [
    (2, 2, 256, 64, 16), (2, 2, 256, 64, 64), (2, 2, 256, 64, 100),
    (7, 1, 256, 64, 96), (8, 1, 256, 128, 129), (14, 2, 192, 64, 191),
    (2, 2, 384, 128, 1), (7, 1, 320, 64, 200),
]


@pytest.mark.parametrize("h,hk,t,blk,window", CASES)
def test_kernels_agree_with_explicit_scores(h, hk, t, blk, window,
                                            interpreted):
    q, k, v, g = qkv(h, hk, t)
    assert fa.bhtd_tile(h, t, t, blk, blk, dh=16, group=h // hk) is not None
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(
            q, k, v, causal=True, window=window, q_block=blk, k_block=blk)
        want, vjp = jax.vjp(lambda q, k, v: explicit(q, k, v, window),
                            q, k, v)
        grads = fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, causal=True, window=window,
            q_block=blk, k_block=blk)
        wants = vjp((g, jnp.zeros_like(lse)))
    np.testing.assert_allclose(out, want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want[1], rtol=1e-5, atol=1e-5)
    for a, b, name in zip(grads, wants, ("dq", "dk", "dv")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


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
@pytest.mark.parametrize("hk", [2, 1])
def test_no_window_is_the_causal_call_bit_for_bit(window, hk, interpreted):
    q, k, v, g = qkv(2, hk, 256, seed=4)
    kw = dict(causal=True, q_block=64, k_block=64)
    out, lse = fa.flash_attention_fwd(q, k, v, **kw)
    got = fa.flash_attention_fwd(q, k, v, window=window, **kw)
    assert bool((got[0] == out).all()) and bool((got[1] == lse).all())
    grads = fa.flash_attention_bwd(q, k, v, None, None, out, lse, g, **kw)
    got = fa.flash_attention_bwd(q, k, v, None, None, out, lse, g,
                                 window=window, **kw)
    assert all(bool((a == b).all()) for a, b in zip(got, grads))
    # and lowers the same program: no band, the sequence's own grid
    def text(w):
        return jax.jit(lambda q, k, v: fa.flash_attention_fwd(
            q, k, v, window=w, **kw)).lower(q, k, v).as_text()
    assert text(window) == text(None)


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
