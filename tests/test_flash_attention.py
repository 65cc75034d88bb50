"""Blocked flash-attention kernel correctness (Pallas interpret mode).

Runs the actual K-blocked online-softmax kernels (fwd + dq + dkv) through
the Pallas interpreter on CPU and checks them against the dense
composition — the TPU analog of the reference's CPU-vs-GPU kernel
cross-checks (SURVEY.md section 4.7). In-kernel dropout needs the
hardware PRNG (``prng_seed`` has no CPU lowering): those cases live in
tests/test_flash_attention_tpu.py and run on the chip.
"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as fa
from test_attention_compiles_for_v5e import _CELL_CALLS


@pytest.fixture(autouse=True)
def _interpret_mode():
    fa._INTERPRET = True
    yield
    fa._INTERPRET = False


def _rand(shape, seed, dtype=np.float32):
    return np.random.RandomState(seed).randn(*shape).astype(dtype)


def _make_qkv(b=2, h=2, tq=256, tk=256, dh=64):
    q = _rand((b, h, tq, dh), 0) * 0.3
    k = _rand((b, h, tk, dh), 1) * 0.3
    v = _rand((b, h, tk, dh), 2) * 0.3
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _pad_bias(b, tk, n_pad):
    mask = np.ones((b, tk), np.float32)
    mask[:, tk - n_pad:] = 0.0
    bias = (1.0 - mask) * -1e9
    return jnp.asarray(bias[:, None, None, :])


def _causal_bias(b, t):
    causal = np.triu(np.full((t, t), -1e9, np.float32), k=1)
    return jnp.asarray(np.broadcast_to(causal, (b, 1, t, t)).copy())


def test_forward_matches_reference_no_bias():
    q, k, v = _make_qkv()
    out = fa.flash_attention(q, k, v, q_block=128, k_block=128)
    ref = fa._reference_attention(q, k, v, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_matches_reference_pad_bias():
    q, k, v = _make_qkv()
    bias = _pad_bias(2, 256, 17)
    out = fa.flash_attention(q, k, v, bias=bias, q_block=128, k_block=128)
    ref = fa._reference_attention(q, k, v, bias, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_matches_reference_causal_bias():
    q, k, v = _make_qkv(tq=256, tk=256)
    bias = _causal_bias(2, 256)
    out = fa.flash_attention(q, k, v, bias=bias, q_block=128, k_block=128)
    ref = fa._reference_attention(q, k, v, bias, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_backward_matches_reference():
    q, k, v = _make_qkv(b=1, h=2, tq=256, tk=256, dh=64)
    bias = _causal_bias(1, 256)
    scale = 1.0 / np.sqrt(64)

    def f_flash(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, bias=bias, q_block=128, k_block=128)
            * jnp.cos(jnp.arange(64, dtype=jnp.float32))
        )

    def f_ref(q, k, v):
        return jnp.sum(
            fa._reference_attention(q, k, v, bias, scale)
            * jnp.cos(jnp.arange(64, dtype=jnp.float32))
        )

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5,
            err_msg=f"d{name} mismatch"
        )


def test_uneven_blocks_fall_back_dense():
    """tq=100 does not divide the block size -> dense path, still correct."""
    q, k, v = _make_qkv(tq=100, tk=100)
    out = fa.flash_attention(q, k, v)
    ref = fa._reference_attention(q, k, v, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --- BTHD single-block fast path (layout [b, t, h, dh]) ---


def _make_qkv_bthd(b=4, h=2, tq=128, tk=128, dh=64):
    q = _rand((b, tq, h, dh), 0) * 0.3
    k = _rand((b, tk, h, dh), 1) * 0.3
    v = _rand((b, tk, h, dh), 2) * 0.3
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_bthd_forward_matches_reference():
    q, k, v = _make_qkv_bthd()
    out, lse = fa.flash_attention_bthd_fwd(q, k, v)
    ref = fa._reference_attention_bthd(q, k, v, None, 1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # lse sanity: logsumexp of scores, [b, tq, h, 1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
    ref_lse = jax.nn.logsumexp(s, axis=-1)[..., None].transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5)


def test_bthd_forward_with_pad_and_causal_bias():
    q, k, v = _make_qkv_bthd()
    for bias in (_pad_bias(4, 128, 17), _causal_bias(4, 128)):
        out, _ = fa.flash_attention_bthd_fwd(q, k, v, bias=bias)
        ref = fa._reference_attention_bthd(q, k, v, bias, 1.0 / np.sqrt(64))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_bthd_backward_matches_reference():
    q, k, v = _make_qkv_bthd()
    bias = _causal_bias(4, 128)

    def f_flash(q, k, v):
        out, _ = fa.flash_attention_bthd_with_lse(q, k, v, bias)
        return jnp.sum(out * jnp.cos(jnp.arange(64, dtype=jnp.float32)))

    def f_ref(q, k, v):
        return jnp.sum(
            fa._reference_attention_bthd(q, k, v, bias, 1.0 / np.sqrt(64))
            * jnp.cos(jnp.arange(64, dtype=jnp.float32)))

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_bthd_cross_attention_shapes():
    """tq != tk (decoder cross attention)."""
    q, _, _ = _make_qkv_bthd(tq=64)
    _, k, v = _make_qkv_bthd(tk=128)
    out, _ = fa.flash_attention_bthd_fwd(q, k, v)
    ref = fa._reference_attention_bthd(q, k, v, None, 1.0 / np.sqrt(64))
    assert out.shape == (4, 64, 2, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_bthd_non_cq_multiple_tq_falls_back_dense():
    """tq=192 does not divide the 128-row chunk -> dense fallback (the
    grid would truncate and leave rows 128+ unwritten)."""
    q, _, _ = _make_qkv_bthd(tq=192)
    _, k, v = _make_qkv_bthd(tk=128)
    out, _ = fa.flash_attention_bthd_fwd(q, k, v)
    ref = fa._reference_attention_bthd(q, k, v, None, 1.0 / np.sqrt(64))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --- K-blocked BTHD path (512 < tk <= _KB_T_MAX, no transposes) ---


import pytest as _pytest


@_pytest.mark.parametrize("tk", [768, 1024])   # nk=3 @256 and nk=2 @512
def test_bthd_kblock_forward_matches_reference(tk):
    b, tq, h, dh = 1, 16, 2, 32
    q = jnp.asarray(_rand((b, tq, h, dh), 3) * 0.3)
    k = jnp.asarray(_rand((b, tk, h, dh), 4) * 0.3)
    v = jnp.asarray(_rand((b, tk, h, dh), 5) * 0.3)
    assert fa.bthd_family(tq, tk, h, dh) == "bthd_kblock"
    out, lse = fa.flash_attention_bthd_fwd(q, k, v)
    ref = fa._reference_attention_bthd(q, k, v, None, 1.0 / np.sqrt(dh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert np.isfinite(np.asarray(lse)).all()


@_pytest.mark.parametrize("tk", [768, 1024])
def test_bthd_kblock_backward_matches_reference(tk):
    b, tq, h, dh = 1, 16, 2, 32
    q = jnp.asarray(_rand((b, tq, h, dh), 6) * 0.3)
    k = jnp.asarray(_rand((b, tk, h, dh), 7) * 0.3)
    v = jnp.asarray(_rand((b, tk, h, dh), 8) * 0.3)
    g = jnp.asarray(_rand((b, tq, h, dh), 9) * 0.3)
    bias = _pad_bias(b, tk, 21)
    out, lse = fa.flash_attention_bthd_fwd(q, k, v, bias)
    dq, dk, dv = fa.flash_attention_bthd_bwd(q, k, v, bias, None, out, lse,
                                             g)

    def f(q, k, v):
        return jnp.sum(
            fa._reference_attention_bthd(q, k, v, bias, 1.0 / np.sqrt(dh))
            * g)

    rdq, rdk, rdv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=3e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=3e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=3e-5)


def test_native_causal_fwd_matches_causal_bias():
    """causal=True (in-kernel position mask + dead-block skip) must be
    numerically identical to the old [t, t] causal-bias formulation,
    WITHOUT any [t, t] tensor existing (VERDICT r5: the O(t) HBM claim
    now holds for decoder self-attention too)."""
    q, k, v = _make_qkv(tq=256, tk=256)
    out = fa.flash_attention(q, k, v, q_block=128, k_block=128,
                             causal=True)
    ref = fa.flash_attention(q, k, v, bias=_causal_bias(2, 256),
                             q_block=128, k_block=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_native_causal_with_pad_bias_bwd_matches():
    """fwd+bwd parity of native causal + pad bias vs the combined-bias
    dense reference, through the blocked kernels."""
    q, k, v = _make_qkv(tq=256, tk=256)
    pad = _pad_bias(2, 256, 9)
    combined = pad + _causal_bias(2, 256)

    def f_native(q, k, v):
        return fa.flash_attention(q, k, v, bias=pad, q_block=128,
                                  k_block=128, causal=True).sum()

    def f_ref(q, k, v):
        return fa._reference_attention(
            q, k, v, combined, 1.0 / np.sqrt(64)).sum()

    o1, g1 = jax.value_and_grad(f_native, argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(o1), float(o2), rtol=1e-4)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_bthd_native_causal_matches_combined_bias():
    """BTHD entry with causal=True routes every sub-path (small,
    k-blocked, long-context BHTD) to the same math as the combined
    causal bias."""
    for tq, tk in ((256, 256), (1024, 1024)):
        b, h, dh = 1, 2, 64
        q = jnp.asarray(_rand((b, tq, h, dh), 3) * 0.3)
        k = jnp.asarray(_rand((b, tk, h, dh), 4) * 0.3)
        v = jnp.asarray(_rand((b, tk, h, dh), 5) * 0.3)
        out, _ = fa.flash_attention_bthd_fwd(q, k, v, causal=True)
        ref = fa._reference_attention_bthd(
            q, k, v, fa._combined_causal_bias(None, tq, tk),
            1.0 / np.sqrt(dh))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, err_msg=f"t={tq}")


def test_bthd_kb_native_causal_backward_matches():
    """k-blocked (t=1024) native-causal backward: dq/dk/dv parity vs
    the dense combined-bias vjp (dead q/k block pairs SKIPPED in-kernel
    must still produce exact gradients)."""
    b, tq, tk, h, dh = 1, 1024, 1024, 2, 64
    q = jnp.asarray(_rand((b, tq, h, dh), 6) * 0.3)
    k = jnp.asarray(_rand((b, tk, h, dh), 7) * 0.3)
    v = jnp.asarray(_rand((b, tk, h, dh), 8) * 0.3)
    out, lse = fa.flash_attention_bthd_fwd(q, k, v, causal=True)
    g = jnp.asarray(_rand((b, tq, h, dh), 9) * 0.1)
    dq, dk, dv = fa.flash_attention_bthd_bwd(
        q, k, v, None, None, out, lse, g, causal=True)

    def f(q, k, v):
        return fa._reference_attention_bthd(
            q, k, v, fa._combined_causal_bias(None, tq, tk),
            1.0 / np.sqrt(dh))

    _, vjp = jax.vjp(f, q, k, v)
    rq, rk, rv = vjp(g)
    for a, r, name in ((dq, rq, "dq"), (dk, rk, "dk"), (dv, rv, "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-5, err_msg=name)


def test_lse_cotangent_flows_through_blocked_backward():
    """The lse OUTPUT is a real differentiated quantity (the ring merge
    weights blocks by exp(lse_blk - lse_comb)); its cotangent folds into
    the blocked backward as delta - phi. Checked against the dense
    (out, lse) vjp through the interpret-mode kernels."""
    q, k, v = _make_qkv(tq=256, tk=256)

    def loss_wrapper(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, None, None,
                                               None, 0.0)
        return out.sum() + (lse * jnp.linspace(
            0.1, 1.0, lse.shape[2])[None, None, :, None]).sum()

    def loss_dense(q, k, v):
        s = fa._reference_scores(q, k, None, 1.0 / np.sqrt(64), False)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
        return out.sum() + (lse * jnp.linspace(
            0.1, 1.0, lse.shape[2])[None, None, :, None]).sum()

    g1 = jax.grad(loss_wrapper, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, err_msg=name)


# --- heads on the grid: a tile of hb < h heads (PR 29) ---


def _live_table(nq, nk, bq, bk):
    return np.array([[bool(fa._causal_live(j, kk, bq, bk))
                      for kk in range(nk)] for j in range(nq)])


@pytest.mark.parametrize("nq,nk,bq,bk", [
    (8, 8, 512, 512),     # OLMoE's tile at 4096
    (4, 8, 256, 128),     # q blocks twice the k blocks
    (8, 4, 128, 256),     # and half
    (2, 6, 128, 128),     # tq < tk: the last k-rows have no live step
    (6, 2, 64, 128),      # tq > tk: the last q-rows have no dead step
    (3, 5, 192, 64),      # a ratio that is no power of two
])
def test_a_dead_step_reads_its_rows_nearest_live_block(nq, nk, bq, bk):
    """The index maps of the operands that move along the inner grid
    axis: a live step reads its own block, a dead one the block the
    nearest live step of its row read (so Pallas copies nothing), and no
    live step is skipped."""
    live = _live_table(nq, nk, bq, bk)
    for j in range(nq):          # forward and dq: k is the inner axis
        reads = [int(fa._live_k(j, kk, bq, bk)) for kk in range(nk)]
        alive = [kk for kk in range(nk) if live[j, kk]]
        assert alive and alive == list(range(len(alive)))  # a dead tail
        for kk in range(nk):
            assert reads[kk] == (kk if live[j, kk] else alive[-1])
        assert set(alive) <= set(reads)
    for kk in range(nk):         # dk/dv: q is the inner axis
        reads = [int(fa._live_q(j, kk, bq, bk)) for j in range(nq)]
        alive = [j for j in range(nq) if live[j, kk]]
        if not alive:            # the caller bounds it by nq - 1
            assert min(reads) >= nq
            continue
        assert alive == list(range(alive[0], nq))          # a dead head
        for j in range(nq):
            assert reads[j] == (j if live[j, kk] else alive[0])
        assert set(alive) <= set(reads)
    # what the grid steps read through the specs' own index function
    for k_inner in (True, False):
        at = fa._step_blocks(True, k_inner, bq, bk, nq)
        for j in range(nq):
            for kk in range(nk):
                ids = (0, 1, j, kk) if k_inner else (0, 1, kk, j)
                i, g, jj, kkk = (int(x) for x in at(*ids, None))
                assert (i, g) == (0, 1) and 0 <= jj < nq and 0 <= kkk < nk
                if live[j, kk]:
                    assert (jj, kkk) == (j, kk)
                elif live[:, kk].any() or k_inner:
                    assert live[jj, kkk]
    at = fa._step_blocks(False, True, bq, bk, nq)
    assert tuple(at(1, 0, 2, 3, None)) == (1, 0, 2, 3)


# (h, dh): what trips a cap at blocks of 128 — the k/v cap (24 * h * 128 *
# dh over 8 MB) or the score cap (4 * h * 128 * 128 over 1.5 MB) — and
# the heads a step keeps
_GRID_HEAD_SHAPES = [(4, 768, 2), (3, 1024, 1), (32, 16, 16)]


def _assert_kernels_match_dense(b, h, t, dh, q_block, k_block, causal,
                                bias_kind):
    """Forward, dq / dk / dv and the lse cotangent of the kernels (at the
    caller's blocks or, None, their own) against the dense
    composition."""
    q, k, v = _make_qkv(b=b, h=h, tq=t, tk=t, dh=dh)
    bias = _pad_bias(b, t, 19)
    if bias_kind == "per_head":
        bias = bias + jnp.asarray(_rand((b, h, t, t), 11))
    scale = float(1.0 / np.sqrt(dh))
    w = jnp.asarray(_rand((b, h, t, dh), 12))
    w_lse = jnp.linspace(0.1, 1.0, t)[None, None, :, None]

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            return (out * w).sum() + (lse * w_lse).sum()
        return f

    def kernels(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, bias, None, scale, 0.0,
                                           q_block, k_block, causal)

    def dense(q, k, v):
        return fa._reference_attention_with_lse(q, k, v, bias, scale,
                                                causal=causal)

    o1, l1 = kernels(q, k, v)
    o2, l2 = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=3e-5)
    g1 = jax.grad(loss(kernels), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, r, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=1e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("bias_kind", ["pad", "per_head"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,dh,hb", _GRID_HEAD_SHAPES)
def test_heads_on_the_grid_match_dense(h, dh, hb, causal, bias_kind):
    """A call whose heads went onto the grid (hb < h), through 2 x 2
    blocks."""
    assert fa._pick_tile(h, 256, 256, 128, 128, dh) == (hb, 128, 128)
    _assert_kernels_match_dense(2, h, 256, dh, 128, 128, causal, bias_kind)


@pytest.mark.parametrize("t,q_block,tile", [
    (768, None, (4, 256, 256)),   # 512 does not divide t: 3 x 3 of 256
    (256, 64, (8, 64, 128)),      # a q block off the 128 lanes: dk/dv
])                                # takes lse and delta as columns
def test_blocks_that_512_does_not_give_match_dense(t, q_block, tile):
    k_block = q_block and 128
    assert fa._pick_tile(8, t, t, q_block, k_block, 64) == tile
    _assert_kernels_match_dense(1, 8, t, 64, q_block, k_block, True, "pad")


# (h, dh, t): the tile before PR 29 (all h heads in a step, blocks shrunk
# by the caps) and the tile now. Where no cap shrank the blocks the tile
# is what it was; where one did, the heads went onto the grid instead.
# ``before`` None: the blocks did not divide t and the call ran dense.
_TILES = [
    (2, 64, 128, (2, 128, 128), (2, 128, 128)),
    (2, 64, 256, (2, 256, 256), (2, 256, 256)),
    (2, 64, 512, (2, 256, 256), (2, 256, 256)),
    (2, 64, 1024, (2, 256, 256), (2, 256, 256)),
    (8, 64, 128, (8, 128, 128), (8, 128, 128)),
    (8, 64, 256, (8, 128, 256), (4, 256, 256)),     # ring blocks of 256
    (8, 64, 512, (8, 128, 256), (1, 512, 512)),
    (8, 64, 1024, (8, 128, 256), (1, 512, 512)),
    (8, 64, 4096, (8, 128, 256), (1, 512, 512)),    # chip_smoke's bhtd case
    (16, 128, 4096, (16, 128, 128), (1, 512, 512)),  # OLMoE
    (16, 64, 4096, (16, 64, 256), (1, 512, 512)),
    (4, 256, 1024, (4, 256, 256), (4, 256, 256)),
    # 512 does not divide t: the blocks halve until they do, and the heads
    # a step keeps follow the blocks
    (8, 64, 768, (8, 128, 256), (4, 256, 256)),     # a ring's 3072 / 4
    (8, 64, 1280, (8, 128, 256), (4, 256, 256)),
    (8, 64, 1792, (8, 128, 256), (4, 256, 256)),
    (12, 64, 768, (12, 128, 256), (6, 256, 256)),
    (16, 128, 640, (16, 128, 128), (16, 128, 128)),
    (16, 128, 768, (16, 128, 128), (4, 256, 256)),
    (16, 128, 1280, (16, 128, 128), (4, 256, 256)),
    (16, 128, 1792, (16, 128, 128), (4, 256, 256)),
    (16, 128, 6400, (16, 128, 128), (4, 256, 256)),
    (8, 64, 384, None, (2, 384, 384)),
    (8, 64, 640, None, (8, 128, 128)),
]


@pytest.mark.parametrize("h,dh,t,before,now", _TILES)
def test_tile_by_shape(h, dh, t, before, now):
    assert fa._pick_tile(h, t, t, None, None, dh) == now
    hb, bq, bk = now
    assert h % hb == 0 and fa._tile_fits(hb, bq, bk, dh)
    # the kernels take the call: every shape they took before, they take
    assert fa.bhtd_tile(h, t, t, dh=dh) == now
    assert fa.bhtd_family(h, t, t, dh=dh) == "bhtd"
    assert fa.tile_label(now) == "hb%d bq%d bk%d" % now
    if before is None:
        pass
    elif fa._tile_fits(*before, dh) and before[1:] == (min(256, t),) * 2:
        assert now == before     # no cap had shrunk it: untouched
    else:
        assert t % before[1] == 0 and t % before[2] == 0
        assert now == before or (
            hb < h and bq * bk >= before[1] * before[2])
    # a caller's bound holds the blocks down, whatever the heads do
    assert fa._pick_tile(h, t, t, 64, 128, dh)[1:] == (64, 128)


def test_no_kernels_no_tile(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", False)    # the CPU backend
    assert fa.bhtd_tile(16, 4096, 4096, dh=128) is None
    assert fa.tile_label(None) == ""
    assert fa.bhtd_family(16, 4096, 4096, dh=128) == "dense"
    monkeypatch.setattr(fa, "_INTERPRET", True)
    assert fa.bhtd_tile(8, 700, 700, dh=64) is None    # no block tiles 700


# --- one backward call: attn.bhtd.bwd (PR 39) ---
#
# A live block's scores, exp and dp are computed once and dq, dk and dv
# taken from them, against the split pair (bwd_dq + bwd_dkv) and against
# jax.vjp of the dense composition.

# (query heads, key/value heads, tq, tk, dh, dv, block, causal, window,
# bias, a cotangent for lse)
_FUSED_CASES = {
    "causal": (1, 1, 512, 512, 16, 16, 128, True, None, None, False),
    "causal_one_block": (1, 1, 128, 128, 16, 16, 128, True, None, None,
                         False),
    # a band three blocks wide over five: dead steps behind the last
    # rows' bands, edge blocks on the diagonal and on the far side
    "band": (1, 1, 640, 640, 16, 16, 128, True, 200, None, False),
    "band_group7": (7, 1, 640, 640, 16, 16, 128, True, 200, None, False),
    "band_group8": (8, 1, 512, 512, 16, 16, 128, True, 129, None, True),
    "window_of_the_row": (2, 1, 384, 384, 16, 16, 128, True, 384, None,
                          False),
    "group7": (7, 1, 384, 384, 16, 16, 128, True, None, None, False),
    "group8_two_kv_heads": (16, 2, 256, 256, 16, 16, 128, True, None, None,
                            False),
    "dk192_dv128": (1, 1, 256, 256, 192, 128, 128, True, None, None, False),
    "lse_cotangent": (2, 1, 384, 384, 16, 16, 128, True, None, None, True),
    "non_causal": (1, 1, 384, 384, 16, 16, 128, False, None, None, False),
    "cross_tq_tk": (2, 1, 256, 512, 16, 16, 128, False, None, None, True),
    "pad_bias": (1, 1, 384, 384, 16, 16, 128, True, None, "pad", False),
    "row_bias_per_head": (2, 1, 256, 256, 16, 16, 128, False, None, "rows",
                          False),
}


def _fused_case(case):
    (h, hk, tq, tk, dh, dv, blk, causal, window, bias_kind,
     with_g_lse) = _FUSED_CASES[case]
    r = np.random.RandomState(len(case))

    def rand(*shape, s=1.0):
        return jnp.asarray(r.randn(*shape) * s, jnp.float32)

    q, k = rand(1, h, tq, dh, s=0.5), rand(1, hk, tk, dh, s=0.5)
    v, g = rand(1, hk, tk, dv), rand(1, h, tq, dv)
    bias = None
    if bias_kind == "pad":
        bias = _pad_bias(1, tk, 37)
    elif bias_kind == "rows":
        bias = rand(1, h, tq, tk)
    g_lse = rand(1, h, tq, 1) if with_g_lse else None
    kw = dict(causal=causal, window=window, q_block=blk, k_block=blk)
    return (q, k, v, bias, g, g_lse), kw


# the cases with an edge block, again with the edge blocks of the ONE
# call walked in sub-tiles of 64 x 64: the forward and the pair keep
# their edge blocks whole
_WALKED = ["causal", "band", "band_group7", "band_group8", "group7",
           "group8_two_kv_heads", "dk192_dv128", "lse_cotangent",
           "pad_bias"]


@functools.cache
def _fused_case_against(case):
    """(out, lse, the pair's gradients, the composition's) of a case:
    what the ONE call is held to, the same whatever sub-tiles it walks
    its edge blocks in (the forward and the pair keep theirs whole), so
    made once a case."""
    (q, k, v, bias, g, g_lse), kw = _fused_case(case)
    h, hk, tq, tk, dh, dv, blk = _FUSED_CASES[case][:7]
    window = fa._band(kw["window"], kw["causal"], tq, tk)

    @jax.jit
    def composition(q, k, v, g, g_lse):
        _, vjp = jax.vjp(
            lambda q, k, v: fa._reference_attention_with_lse(
                q, k, v, bias, dh ** -0.5, causal=kw["causal"],
                window=window), q, k, v)
        return vjp((g, g_lse))

    with jax.default_matmul_precision("highest"), \
            pytest.MonkeyPatch.context() as patch:
        out, lse = fa.flash_attention_fwd(q, k, v, bias, **kw)
        # no room for a resident row: the pair
        patch.setattr(fa, "_BWD_VMEM_CAP_BYTES", 0)
        assert fa.bhtd_bwd_form(h, tq, tk, blk, blk, dh=dh, group=h // hk,
                                dv=dv, itemsize=4) == "split"
        pair = fa.flash_attention_bwd(q, k, v, bias, None, out, lse, g,
                                      g_lse=g_lse, **kw)
        want = composition(
            q, k, v, g, jnp.zeros_like(lse) if g_lse is None else g_lse)
    return out, lse, pair, want


@pytest.mark.parametrize("case,edge_sub", [
    *((c, None) for c in sorted(_FUSED_CASES)), *((c, 64) for c in _WALKED),
    ("band", 32), ("causal", 32)])
def test_fused_backward_matches_the_pair_and_the_composition(case, edge_sub,
                                                             monkeypatch):
    (q, k, v, bias, g, g_lse), kw = _fused_case(case)
    h, hk, tq, tk, dh, dv, blk = _FUSED_CASES[case][:7]
    out, lse, pair, want = _fused_case_against(case)
    if edge_sub:
        monkeypatch.setattr(fa, "_EDGE_SUB", edge_sub)
    assert fa.bhtd_edge_tile((1, blk, blk), kw["causal"]) == (
        (edge_sub, edge_sub) if edge_sub else None)
    form = dict(dh=dh, group=h // hk, dv=dv, itemsize=4)
    assert fa.bhtd_tile(h, tq, tk, blk, blk, dh=dh, group=h // hk,
                        dv=dv) == (1, blk, blk)
    assert fa.bhtd_bwd_form(h, tq, tk, blk, blk, **form) == "fused"
    with jax.default_matmul_precision("highest"):
        got = fa.flash_attention_bwd(q, k, v, bias, None, out, lse, g,
                                     g_lse=g_lse, **kw)
    for a, p, w, name in zip(got, pair, want, ("dq", "dk", "dv")):
        assert a.shape == w.shape and a.dtype == w.dtype
        # the pair's arithmetic in another order of float32 additions
        np.testing.assert_allclose(a, p, rtol=1e-5, atol=2e-6, err_msg=name)
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=2e-5, err_msg=name)


def _pallas_calls(f, *args):
    """[(name, kernel jaxpr, eqn)] of the pallas_calls ``f`` lowers."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], eqn.params["jaxpr"], eqn))
                continue
            for v in eqn.params.values():
                for x in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(x, "jaxpr", x)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


def _count(jaxpr, primitive):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    n += _count(inner, primitive)
    return n


@pytest.mark.parametrize("case,edge_sub,branches", [
    ("non_causal", None, 1), ("causal", None, 2), ("band_group7", None, 2),
    ("pad_bias", None, 2), ("non_causal", 64, 1), ("causal", 64, 3),
    ("band_group7", 32, 12)])
def test_fused_backward_is_one_call_of_five_matmuls_and_one_exp(
        case, edge_sub, branches, monkeypatch):
    """ONE Mosaic call named attn.bhtd.bwd; the body of a live step
    holds five dot_generals and one exp (a causal call has two such
    bodies, masked for an edge block and plain inside, of which a step
    runs one; with sub-tiles the plain block's body and one for each
    slab of each kind of edge block: a query sub-tile's live key
    sub-tiles, two on the diagonal at sub-tiles of half a block; four,
    four and three for the diagonal and the two far-edge kinds of a
    band of 200 at sub-tiles of a quarter); its only results are dq, dk
    and dv."""
    if edge_sub:
        monkeypatch.setattr(fa, "_EDGE_SUB", edge_sub)
    (q, k, v, bias, g, _), kw = _fused_case(case)
    out, lse = jax.eval_shape(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, bias, **kw), q, k, v)
    calls = _pallas_calls(
        lambda q, k, v, g: fa.flash_attention_bwd(
            q, k, v, bias, None, jnp.zeros(out.shape), jnp.zeros(lse.shape),
            g, **kw), q, k, v, g)
    ((name, kernel, eqn),) = calls
    assert name == "attn.bhtd.bwd"
    assert _count(kernel, "dot_general") == 5 * branches
    assert _count(kernel, "exp") == branches
    assert [x.aval.shape for x in eqn.outvars] == [q.shape, k.shape, v.shape]


def test_bhtd_bwd_form_follows_the_call(monkeypatch):
    """Fused where the resident rows fit the cap, one head a step, no
    dropout, statistics cut from rows; the pair elsewhere, and it runs."""
    cells = dict(smallthinker=(28, 16384, 128, 128, 7),
                 qwen3next=(16, 8192, 256, 256, 8),
                 joyai=(32, 4096, 192, 128, 1), olmoe=(16, 4096, 128, 128, 1))
    for h, t, dh, dv, group in cells.values():
        assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=group, dv=dv) == "fused"
    # a row of 64k at heads of 128: 78 MB of rows and blocks
    assert fa._bwd_vmem_bytes(65536, 65536, 128, 128, 1, 512, 512, 2) \
        > fa._BWD_VMEM_CAP_BYTES
    assert fa.bhtd_bwd_form(16, 65536, 65536, dh=128) == "split"
    # twice smallthinker's row under its group of 7
    assert fa.bhtd_bwd_form(28, 32768, 32768, dh=128, group=7) == "split"
    assert fa.bhtd_bwd_form(16, 32768, 32768, dh=128) == "fused"
    # heads batched in a step, dropout, a q block off the lanes
    assert fa.bhtd_tile(2, 256, 256, dh=64) == (2, 256, 256)
    assert fa.bhtd_bwd_form(2, 256, 256, dh=64) == "split"
    assert fa.bhtd_bwd_form(16, 4096, 4096, dh=128, p_drop=0.1) == "split"
    assert fa.bhtd_bwd_form(1, 256, 256, 64, 128, dh=16) == "split"
    # no tile, no form: the dense composition
    assert fa.bhtd_bwd_form(2, 100, 100, 64, 64, dh=16) is None
    monkeypatch.setattr(fa, "_INTERPRET", False)
    assert fa.bhtd_bwd_form(16, 4096, 4096, dh=128) is None
    monkeypatch.setattr(fa, "_INTERPRET", True)

    # the pair is what a "split" call lowers
    (q, k, v, bias, g, _), kw = _fused_case("group7")
    monkeypatch.setattr(fa, "_BWD_VMEM_CAP_BYTES", 2**16)
    names = [name for name, _, _ in _pallas_calls(
        lambda q, k, v, g: fa.flash_attention_bwd(
            q, k, v, None, None, jnp.zeros(g.shape),
            jnp.zeros(g.shape[:3] + (1,)), g, **kw), q, k, v, g)]
    assert names == ["attn.bhtd.bwd_dq", "attn.bhtd.bwd_dkv"]


def test_a_window_as_long_as_the_row_lowers_the_plain_fused_call():
    (q, k, v, _, g, _), kw = _fused_case("window_of_the_row")
    out, lse = fa.flash_attention_fwd(q, k, v, **kw)

    def text(window):
        return jax.jit(lambda q, k, v, g: fa.flash_attention_bwd(
            q, k, v, None, None, out, lse, g, **dict(kw, window=window))
        ).lower(q, k, v, g).as_text()

    assert text(384) == text(None) == text(1000)
    assert text(383) != text(None)


# --- the forward's logsumexp in the layout the backward reads (PR 60) ---
# (h, t, dh, q_block, k_block, the tile, bhtd_stats_form's answer): one
# head a step, two, sixteen (the kernel writes a row a head); blocks of
# the whole of a row that is no whole number of lane tiles; a caller's q
# block of 64, which cannot be cut from a row
_STATS_CASES = {
    "hb1": (3, 256, 1024, 128, 128, (1, 128, 128), "rows"),
    "hb2": (4, 256, 768, 128, 128, (2, 128, 128), "rows"),
    "hb16": (32, 256, 16, 128, 128, (16, 128, 128), "rows"),
    "the_whole_row": (2, 200, 16, None, None, (2, 200, 200), "rows"),
    "q_block_64": (8, 256, 64, 64, 128, (8, 64, 128), "column"),
}


def _kernel_results(*args, **kw):
    """The result shapes of the ONE call ``flash_attention_fwd`` lowers."""
    ((name, _, eqn),) = _pallas_calls(
        lambda *a: fa.flash_attention_fwd(*a, **kw), *args)
    assert name == "attn.bhtd.fwd"
    return [x.aval.shape for x in eqn.outvars]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", sorted(_STATS_CASES))
def test_forward_lse_as_rows_or_a_column_is_the_references(case, causal):
    """``attn.bhtd.fwd`` writes the logsumexp as [b, h, 1, tq] rows where
    a q block can be cut from a row and as the [b, h, tq, 1] column where
    it cannot; ``flash_attention_fwd`` returns [b, h, tq, 1] float32
    either way, the reference's to float32 rounding."""
    h, t, dh, q_block, k_block, tile, form = _STATS_CASES[case]
    assert fa.bhtd_tile(h, t, t, q_block, k_block, dh=dh) == tile
    assert fa.bhtd_stats_form(tile, t) == form
    b = 2
    q, k, v = _make_qkv(b=b, h=h, tq=t, tk=t, dh=dh)
    bias = _pad_bias(b, t, 19)
    kw = dict(q_block=q_block, k_block=k_block, causal=causal)
    assert _kernel_results(q, k, v, bias, **kw) == [
        (b, h, t, dh), (b, h, 1, t) if form == "rows" else (b, h, t, 1)]
    with jax.default_matmul_precision("highest"):
        out, lse = fa.flash_attention_fwd(q, k, v, bias, **kw)
        ref_out, ref_lse = fa._reference_attention_with_lse(
            q, k, v, bias, dh ** -0.5, causal=causal)
    assert lse.shape == (b, h, t, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, ref_lse, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["hb1", "hb16", "q_block_64"])
def test_lse_cotangent_through_rows_and_through_the_column(case):
    """``flash_attention_with_lse`` under a loss that weighs the lse:
    the gradient the dense (out, lse) vjp gives, whichever layout the
    statistic crossed HBM in (one call reading rows, the pair reading
    rows, the pair reading the column)."""
    h, t, dh, q_block, k_block, _, _ = _STATS_CASES[case]
    _assert_kernels_match_dense(1, h, t, dh, q_block, k_block, True, "pad")


@pytest.mark.parametrize("call", sorted(_CELL_CALLS))
def test_every_cells_call_writes_its_statistics_as_rows(call):
    """The eight decoder cells' BHTD calls (perf/configs; the table the
    compile tests hold) all take blocks of 512: rows, and the ONE
    backward call that reads them."""
    _, h, hk, t, dh, dv, _ = _CELL_CALLS[call]
    tile = fa.bhtd_tile(h, t, t, dh=dh, group=h // hk, dv=dv)
    assert tile == (1, 512, 512)
    assert fa.bhtd_stats_form(tile, t) == "rows"
    # the one test behind the backward's form too
    assert fa.bhtd_bwd_form(h, t, t, dh=dh, group=h // hk, dv=dv) == "fused"
    assert fa.bhtd_stats_form((1, 64, 512), t) == "column"
    assert fa.bhtd_stats_form(None, t) is None


# --- the forward's own tile (PR 74) -----------------------------------
# Two query heads a grid step where the heads are on the grid: the two
# heads of one key head's group over ONE fetched block of K, V and of a
# selection's words; a key head a query head, two blocks of K and V. The
# blocks are the backward's tile's; a head's arithmetic is the one-head
# step's, so Out and Lse are that step's to the bit.

# the decoder cells' forward calls (perf/configs): (query heads,
# key/value heads, t, the whole head's width, dv, what else the tile
# goes by) -> the query heads of a step
_FWD_CALLS = {
    "keye_sel": ((32, 4, 16384, 128, 128, dict(selected=True)), 2),
    "qwen3next_d256": ((16, 2, 8192, 256, 256, {}), 2),
    "lfm2moe_d64": ((32, 8, 8192, 64, 64, {}), 2),
    "laguna_full": ((48, 8, 8192, 128, 128, {}), 2),
    "laguna_w512": ((64, 8, 8192, 128, 128, {}), 2),
    "sdar_bd": ((32, 4, 8192, 128, 128, dict(block_diffusion=4)), 2),
    "phi4flash_pairs": ((20, 10, 4096, 64, 128, {}), 2),
    "nemotron3nano": ((32, 2, 4096, 128, 128, {}), 2),
    "olmoe": ((16, 16, 4096, 128, 128, {}), 2),
    # (joyai, xing4, kimilinear: 32 x (128 | 64) over 128, ONE rotary
    # key head)
    "latent_parts": ((32, 32, 4096, 192, 128, dict(pe_group=32)), 2),
    # 28 heads on 4: heads 6 and 7 read two key heads, so one a step
    "smallthinker_g7": ((28, 4, 16384, 128, 128, {}), 1),
}


@pytest.mark.parametrize("call", sorted(_FWD_CALLS))
def test_the_forwards_own_tile_at_the_cells_calls(call):
    (h, hk, t, dh, dv, kw), hq = _FWD_CALLS[call]
    group = h // hk
    tile = fa.bhtd_fwd_tile(h, t, t, dh=dh, group=group, dv=dv, **kw)
    assert tile == (hq, 512, 512)
    # the blocks are the backward's, whose tile stays one head a step
    assert fa.bhtd_tile(h, t, t, dh=dh, group=group, dv=dv,
                        block_diffusion=kw.get("block_diffusion")) == (
        1, 512, 512)
    # (K and V blocks a step: ONE where the step's heads share a key
    # head, a head each at a key head a query head)
    held = hq if group == 1 else 1
    assert fa._kv_blocks(hq, group) == held
    kept = fa._fwd_vmem_bytes(hq, held, 512, 512, dh, dv, 2,
                              selected=kw.get("selected", False))
    limit = fa._fwd_vmem_limit(hq, held, 512, 512, dh, dv, 2,
                               selected=kw.get("selected", False))
    # (a count within Mosaic's default of 16 MiB asks for nothing; one
    # head a step always is: the parent's call)
    assert kept <= fa._FWD_VMEM_CAP_BYTES
    assert limit == (kept * 5 // 4 if kept * 5 // 4 > 16 * 2**20 else None)
    assert fa._fwd_vmem_limit(1, 1, 512, 512, dh, dv, 2) is None


def test_what_keeps_the_forward_at_one_head_a_step():
    call = dict(dh=128, group=8, dv=128)
    assert fa.bhtd_fwd_tile(32, 4096, 4096, **call) == (2, 512, 512)
    # attention dropout: a block's mask is keyed by the step's head
    # group, and the backward draws it again at one head a step
    assert fa.bhtd_fwd_tile(16, 4096, 4096, dh=128, p_drop=0.1) == (
        1, 512, 512)
    assert fa.bhtd_fwd_tile(16, 4096, 4096, dh=128) == (2, 512, 512)
    # an odd number of heads; an odd group; a rotary key head that an
    # odd number of query heads share
    assert fa.bhtd_fwd_tile(15, 4096, 4096, dh=128)[0] == 1
    assert fa.bhtd_fwd_tile(12, 4096, 4096, dh=128, group=3)[0] == 1
    assert fa.bhtd_fwd_tile(12, 4096, 4096, dh=192, dv=128,
                            pe_group=3)[0] == 1
    # blocks under the side the chip timed (a caller's, or a sequence
    # that 512 does not divide: 768 takes blocks of 256)
    assert fa.bhtd_fwd_tile(32, 4096, 4096, 256, 256, **call) == (
        1, 256, 256)
    assert fa.bhtd_fwd_tile(32, 768, 768, **call) == (1, 256, 256)
    assert fa.bhtd_fwd_tile(12, 4096, 4096, dh=192, dv=128,
                            pe_group=1)[0] == 2
    # heads that share a step already (a short row) stay as they are,
    # and no tile is no tile
    assert fa.bhtd_fwd_tile(2, 256, 256, dh=64) == (2, 256, 256)
    assert fa.bhtd_fwd_tile(8, 700, 700, dh=64) is None
    # a float bias is counted, not refused: a block a head and row of it
    # at float32 is 4 MB of a step's 13
    assert fa.bhtd_fwd_tile(16, 4096, 4096, dh=128,
                            bias=(1, 16, 4096, 4096)) == (2, 512, 512)
    assert fa._fwd_vmem_bytes(2, 2, 512, 512, 128, 128, 2,
                              bias=(1, 16, 4096, 4096)) \
        - fa._fwd_vmem_bytes(2, 2, 512, 512, 128, 128, 2) == 4 * 2**20
    # the cap is the forward's own: it passes two heads and refuses a
    # whole group of seven a step (39 MB)
    assert fa._fwd_vmem_bytes(7, 1, 512, 512, 128, 128, 2) \
        > fa._FWD_VMEM_CAP_BYTES
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "_FWD_VMEM_CAP_BYTES", 8 * 2**20)
        assert fa.bhtd_fwd_tile(32, 4096, 4096, **call) == (1, 512, 512)


def _fwd_variant(variant):
    """(operands of ``flash_attention_fwd``, the query heads its step
    takes) of a variant of the kernel, at blocks of 128 in bf16."""
    r = np.random.RandomState(len(variant))

    def rand(*shape, s=1.0):
        return jnp.asarray(r.randn(*shape) * s, jnp.bfloat16)

    h, hk, t, dh, dv, hq = 4, 2, 512, 128, 128, 2
    kw = dict(causal=True, q_block=128, k_block=128)
    if variant == "window":
        kw["window"] = 200
    elif variant == "block_diffusion":
        kw.update(causal=False, block_diffusion=32)
    elif variant == "group7":   # (heads 6 and 7 read two key heads)
        h, hk, hq = 14, 2, 1
    elif variant == "heads_of_64":
        dh = dv = 64
    elif variant == "a_key_head_a_query_head":
        hk = h
    elif variant == "pad_bias":
        kw["bias"] = _pad_bias(1, t, 37)
    elif variant == "two_parts":
        hk = h
    args = dict(q=rand(1, h, t, dh, s=0.5), k=rand(1, hk, t, dh, s=0.5),
                v=rand(1, hk, t, dv))
    if variant == "two_parts":
        args.update(q_pe=rand(1, h, t, 64, s=0.5),
                    k_pe=rand(1, 1, t, 64, s=0.5))
    if variant == "selection":
        from test_dsa_ops import attention_case

        *_, sel, live = attention_case(0, dead="a block")
        assert not np.asarray(live)[0, 2, 0]    # a dead block in the table
        args.update(selected=sel, live=live)
    return args, kw, hq


@pytest.mark.parametrize("variant", [
    "plain", "window", "block_diffusion", "selection", "two_parts",
    "group7", "heads_of_64", "a_key_head_a_query_head", "pad_bias"])
def test_forward_at_its_own_tile_is_one_head_a_step_to_the_bit(variant,
                                                               monkeypatch):
    # (a short row's heads onto the grid, as a long row's are: one
    # head's K and V blocks of 128 rows fit the cap, two do not; and
    # two heads a step at the blocks of 128 the interpreter can afford)
    monkeypatch.setattr(fa, "_KV_VMEM_BYTES", 12 * 128 * 320)
    monkeypatch.setattr(fa, "_FWD_PAIR_BLOCK", 128)
    args, kw, hq = _fwd_variant(variant)

    def tile_and_results():
        ((name, _, eqn),) = _pallas_calls(
            lambda: fa.flash_attention_fwd(**args, **kw))
        assert name == "attn.bhtd.fwd"
        return (eqn.params["grid_mapping"].grid,
                fa.flash_attention_fwd(**args, **kw))

    h = args["q"].shape[1]
    grid, (out, lse) = tile_and_results()
    assert grid[:2] == (1, h // hq)
    monkeypatch.setattr(fa, "_FWD_HEADS", 1)
    grid, (want, want_lse) = tile_and_results()
    assert grid[:2] == (1, h)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(lse, want_lse)


def test_a_call_with_dropout_keeps_one_head_a_step_and_its_masks_key():
    """The forward of a call with attention dropout lowers one head a
    step over the grid (b, h, nq, nk), the grid whose head axis
    ``_seed_step`` mixes into a block's key, and the backward (the pair)
    walks the same head axis: both draw a block's mask from (seed, row,
    head, q-block, k-block)."""
    q, k, v = (jnp.zeros((1, 16, 1024, 128), jnp.bfloat16),) * 3
    seed = jnp.int32(3)

    def both(q, k, v):
        out, lse = fa.flash_attention_fwd(q, k, v, None, seed, p_drop=0.1,
                                          causal=True)
        return fa.flash_attention_bwd(q, k, v, None, seed, out, lse, out,
                                      p_drop=0.1, causal=True)

    assert fa.bhtd_fwd_tile(16, 1024, 1024, dh=128, p_drop=0.1) == (
        1, 512, 512)
    grids = {name: eqn.params["grid_mapping"].grid
             for name, _, eqn in _pallas_calls(both, q, k, v)}
    assert grids == {"attn.bhtd.fwd": (1, 16, 2, 2),
                     "attn.bhtd.bwd_dq": (1, 16, 2, 2),
                     "attn.bhtd.bwd_dkv": (1, 16, 2, 2)}
    # without dropout the same call's forward takes two heads a step
    ((_, _, eqn),) = _pallas_calls(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, causal=True),
        q, k, v)
    assert eqn.params["grid_mapping"].grid == (1, 8, 2, 2)


# --- BTHD-small: the score block is passed over once (PR 49) ---
# The scale on q where it is a power of two, 1 / (l * p_keep) behind P.V,
# dropout as one select, delta made in the backward kernel. The TPU's
# PRNG has no CPU lowering, so the dropout cases swap the kernels' ONE
# source of kept positions (``fa._small_dropout``) for a pattern the
# interpreter can make; tests/test_flash_attention_tpu.py holds the real
# stream to the same composition on the chip.


def _stand_in_keep(seed_ref, i, jc, hi, shape, p_drop):
    """About 1 - p_drop of (row, col) kept, another set for every
    (seed, batch row, 128-row block, head), as the PRNG's keys give."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    x = (r * 7 + c * 13 + seed_ref[0] + (i + seed_ref[1]) * 5 + jc * 11
         + hi * 3)
    return x % 10 >= int(round(p_drop * 10))


def _small_case(kind, dh, cq):
    """q, k, v, g, bias at a call that walks tq in blocks of ``cq``
    rows, forward and backward."""
    b, h = 2, 2
    tq, tk = (cq, 128 if cq == 256 else 256) if kind == "cross" \
        else (cq, cq)
    assert fa.bthd_family(tq, tk, h, dh) == "bthd_small"
    assert fa._pick_cq(tq, tk, h) == cq
    q, g = (jnp.asarray(_rand((b, tq, h, dh), s) * 0.3) for s in (11, 14))
    k, v = (jnp.asarray(_rand((b, tk, h, dh), s) * 0.3) for s in (12, 13))
    bias = {"none": None, "pad": _pad_bias(b, tk, 17),
            "cross": _pad_bias(b, tk, 5)}.get(kind)
    if kind == "causal":
        bias = _pad_bias(b, tk, 9) + _causal_bias(b, tq)
    return q, k, v, g, bias


def _dense_bthd(q, k, v, bias, masks):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * np.float32(q.shape[-1] ** -0.5)
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    if masks is not None:               # [b, tq, h, tk]: keep / p_keep
        p = p * jnp.swapaxes(masks, 1, 2)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("cq", [128, 256])
@pytest.mark.parametrize("dh", [64, 32], ids=["scale_pow2", "scale_other"])
@pytest.mark.parametrize("p_drop", [0.0, 0.1])
@pytest.mark.parametrize("kind", ["none", "pad", "causal", "cross"])
def test_bthd_small_matches_dense_fed_its_own_masks(kind, p_drop, dh, cq,
                                                    monkeypatch):
    monkeypatch.setattr(fa, "_small_dropout", _stand_in_keep)
    q, k, v, g, bias = _small_case(kind, dh, cq)
    (b, tq, h, _), tk = q.shape, k.shape[1]
    assert (math.frexp(dh ** -0.5)[0] == 0.5) == (dh == 64)
    seed = jnp.int32(5) if p_drop else None
    masks = fa.bthd_dropout_masks(b, tq, tk, h, dh, p_drop, seed) \
        if p_drop else None
    if p_drop:      # float32, keep / p_keep, about p_keep of them kept
        kept = np.asarray(masks) > 0
        assert masks.dtype == jnp.float32 and 0.85 < kept.mean() < 0.95
        np.testing.assert_array_equal(
            np.asarray(masks)[kept], np.float32(1.0 / (1.0 - p_drop)))
    out, lse = fa.flash_attention_bthd_fwd(q, k, v, bias, seed,
                                           p_drop=p_drop)
    dq, dk, dv = fa.flash_attention_bthd_bwd(q, k, v, bias, seed, out, lse,
                                             g, p_drop=p_drop)
    want, vjp = jax.vjp(lambda q, k, v: _dense_bthd(q, k, v, bias, masks),
                        q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for got, ref, name in zip((dq, dk, dv), vjp(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-5, err_msg=name)


def test_bthd_small_forward_and_backward_keep_the_masks_positions(
        monkeypatch):
    """Read the kept positions back out of each pass: with q = 0 every
    p is 1 / tk, so v = I shows the forward's in out and do = I the
    backward's in dv; both are ``bthd_dropout_masks``'s."""
    monkeypatch.setattr(fa, "_small_dropout", _stand_in_keep)
    b, t, h, p_drop, seed = 2, 128, 2, 0.1, jnp.int32(3)
    eye = jnp.broadcast_to(jnp.eye(t, dtype=jnp.float32)[None, :, None, :],
                           (b, t, h, t))
    q = jnp.zeros_like(eye)
    kept = np.asarray(fa.bthd_dropout_masks(b, t, t, h, t, p_drop, seed)) > 0
    out, lse = fa.flash_attention_bthd_fwd(q, q, eye, None, seed,
                                           p_drop=p_drop)
    np.testing.assert_array_equal(np.asarray(out) > 0, kept)
    _, _, dv = fa.flash_attention_bthd_bwd(q, q, eye, None, seed, out, lse,
                                           eye, p_drop=p_drop)
    # dv[b, k, h, d] = sum_q keep[q, k] p do[q, d] = keep[d, k] / tk / p_keep
    np.testing.assert_array_equal(
        np.asarray(dv).transpose(0, 3, 2, 1) > 0, kept)


@pytest.mark.parametrize("family,tk", [("bthd_small", 256),
                                       ("bthd_kblock", 1024)])
def test_bthd_backward_makes_delta_in_the_kernel(family, tk):
    """Nothing of g * out is reduced outside the ONE Mosaic call: it
    takes ``out`` as an operand and makes delta from the blocks it
    holds."""
    b, tq, h, dh = 2, 128, 2, 64
    assert fa.bthd_family(tq, tk, h, dh) == family
    q, out, g = (jnp.zeros((b, tq, h, dh), jnp.float32) for _ in range(3))
    k = v = jnp.zeros((b, tk, h, dh), jnp.float32)
    lse = jnp.zeros((b, tq, h, 1), jnp.float32)

    def bwd(q, k, v, out, lse, g):
        return fa.flash_attention_bthd_bwd(q, k, v, None, None, out, lse, g)

    jaxpr = jax.make_jaxpr(bwd)(q, k, v, out, lse, g).jaxpr
    calls = _pallas_calls(bwd, q, k, v, out, lse, g)
    assert [name for name, _, _ in calls] == [f"attn.{family}.bwd"]
    outside = [e.primitive.name for e in jaxpr.eqns
               if e.primitive.name != "pallas_call"]
    assert not {"reduce_sum", "mul", "dot_general"} & set(outside), outside
    shapes = [tuple(x.aval.shape) for x in calls[0][2].invars]
    assert shapes.count((b, tq, h * dh)) == 3       # q, do, out
