"""The grouped-matmul kernels (paddle_tpu/parallel/grouped_matmul.py) on
the CPU through the Pallas interpreter, at small shapes, against
``jax.lax.ragged_dot`` and its own vjp; and the table of which call
gets which tile. The chip's run of the cell's shapes is
tests/test_grouped_matmul_tpu.py.

Dropless: the group sizes of every case sum to m. A row past
``sum(group_sizes)`` does not exist in ``layers.topk_moe`` (every chosen
(token, expert) pair is a row of exactly one group), ``ragged_dot``
would write zeros there and these kernels visit no such row, so no case
has one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import grouped_matmul as gm


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(gm, "_INTERPRET", True)


M = 512
# the multiset a freshly initialised router really gives is skewed: a
# few experts take several times the mean (PERF.md section 6, PR 28)
GROUPS = {
    "even": [128, 128, 128, 128],
    "skewed": [37, 301, 5, 169],
    "some_empty": [300, 0, 12, 200],
    "first_and_last_empty": [0, 255, 257, 0],
    "one_holds_everything": [0, 0, 512, 0],
    "no_tile_divides": [1, 254, 129, 128],
    "eight_groups": [64, 3, 0, 190, 61, 1, 193, 0],
}
# (tm, tk, tn) of the forward's [M, K] x [E, K, N]: a contraction in
# two steps and in one, a width in two tiles, rows of 128 and 256
TILES = [(128, 128, 128), (128, 256, 128), (256, 256, 256)]
TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       # bf16 results of sums over up to 512 products: one rounding of
       # the result (2**-8 relative) and the accumulation order
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def operands(sizes, k, n, dtype, seed=0):
    r = np.random.RandomState(seed)
    e = len(sizes)
    return (jnp.asarray(r.randn(M, k), dtype),
            jnp.asarray(r.randn(e, k, n) * 0.1, dtype),
            jnp.asarray(r.randn(M, n), dtype),
            jnp.asarray(sizes, jnp.int32))


def ragged(lhs, rhs, gs):
    return jax.lax.ragged_dot(lhs, rhs, gs)


def close(got, want, dtype):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("tile", TILES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_gmm_and_tgmm_against_ragged_dot(groups, dtype, tile, interpreted):
    """Forward, the rows' gradient (the weights read transposed: the
    other orientation) and the matrix's gradient, each kernel alone."""
    sizes = GROUPS[groups]
    k, n = 256, 256
    lhs, rhs, g, gs = operands(sizes, k, n, dtype)
    want, vjp = jax.vjp(lambda a, b: ragged(a, b, gs), lhs, rhs)
    want_dx, want_dw = vjp(g)
    tm, tk, tn = tile
    close(gm.gmm(lhs, rhs, gs, tile), want, dtype)
    close(gm.gmm(g, rhs, gs, (tm, tn, tk), transpose_rhs=True), want_dx,
          dtype)
    dw = gm.tgmm(lhs, g, gs, tile)
    close(dw, want_dw, dtype)
    for e, rows in enumerate(sizes):
        if rows == 0:    # exact zeros, not what the block held before
            assert not np.asarray(dw[e], np.float32).any(), e


@pytest.mark.parametrize("k,n", [(256, 128), (128, 256)],
                         ids=["narrowing", "widening"])
@pytest.mark.parametrize("groups", ["skewed", "some_empty"])
def test_custom_vjp_against_ragged_dots_own(groups, k, n, interpreted):
    """``grouped_matmul`` under ``jax.grad`` in both of the layer's
    orientations ([d -> f] and [f -> d]), through ``gmm_tile``'s own
    tile, against ``ragged_dot``'s."""
    lhs, rhs, g, gs = operands(GROUPS[groups], k, n, jnp.bfloat16, seed=1)
    assert gm.gmm_tile(M, k, n, len(GROUPS[groups]), jnp.bfloat16) == (
        128, k, n)

    def loss(f):
        return lambda a, b: jnp.sum(
            f(a, b, gs).astype(jnp.float32) * g.astype(jnp.float32))

    got = jax.value_and_grad(loss(gm.grouped_matmul), (0, 1))(lhs, rhs)
    want = jax.value_and_grad(loss(ragged), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-2)
    for a, b in zip(got[1], want[1]):
        close(a, b, jnp.bfloat16)
    dx, dw = gm.grouped_matmul_grads(lhs, rhs, gs, g)
    close(dx, want[1][0], jnp.bfloat16)
    close(dw, want[1][1], jnp.bfloat16)


def test_off_the_chip_it_is_ragged_dot_itself():
    lhs, rhs, g, gs = operands(GROUPS["skewed"], 256, 128, jnp.bfloat16)
    assert gm.gmm_tile(M, 256, 128, 4, jnp.bfloat16) is None
    text = str(jax.make_jaxpr(gm.grouped_matmul)(lhs, rhs, gs))
    assert "ragged_dot" in text and "pallas_call" not in text
    np.testing.assert_array_equal(gm.grouped_matmul(lhs, rhs, gs),
                                  ragged(lhs, rhs, gs))
    dx, dw = gm.grouped_matmul_grads(lhs, rhs, gs, g)
    _, vjp = jax.vjp(lambda a, b: ragged(a, b, gs), lhs, rhs)
    np.testing.assert_array_equal(dx, vjp(g)[0])
    np.testing.assert_array_equal(dw, vjp(g)[1])


@pytest.mark.parametrize("groups", sorted(GROUPS))
@pytest.mark.parametrize("tm", [128, 256])
def test_visits_cover_every_row_once(groups, tm):
    """The bookkeeping alone: the live visits' (tile, group) pairs are
    exactly the pairs that share a row, in the order of the groups; for
    ``tgmm`` every empty group gets one visit more; the visits past
    ``nvis`` repeat the last live one."""
    sizes = np.array(GROUPS[groups])
    ends = np.cumsum(sizes)
    owner = np.repeat(np.arange(len(sizes)), sizes)      # row -> group
    want = sorted({(int(g), r // tm) for r, g in enumerate(owner)})
    for visit_empty in (False, True):
        offs, gids, tids, nvis = (np.asarray(a) for a in gm._visits(
            jnp.asarray(sizes, jnp.int32), M, tm, visit_empty))
        assert offs.tolist() == [0, *ends] and offs.dtype == np.int32
        assert len(gids) == len(tids) == M // tm + len(sizes) - 1
        nv = int(nvis[0])
        live = list(zip(gids[:nv].tolist(), tids[:nv].tolist()))
        empty = [g for g in range(len(sizes)) if not sizes[g]]
        assert [p for p in live if sizes[p[0]]] == want
        assert [g for g, _ in live if not sizes[g]] == (
            empty if visit_empty else [])
        assert live == sorted(live)
        assert all(p == live[-1] for p in zip(gids[nv:].tolist(),
                                              tids[nv:].tolist()))


BF, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("m,k,n,e,dtype,backend,on_mesh,want", [
    # olmoe-train-s4096's three products (and, with k and n exchanged,
    # their rows' gradients): 1024 rows an expert, so rows of 256, and
    # the expert's whole matrix resident (PERF.md section 6, PR 31, has
    # the candidates' times)
    (65536, 2048, 1024, 64, BF, "tpu", False, (256, 2048, 1024)),
    (65536, 1024, 2048, 64, BF, "tpu", False, (256, 1024, 2048)),
    # the row tile follows the rows an expert: 8192, 256 and 128
    (65536, 2048, 1024, 8, BF, "tpu", False, (512, 2048, 1024)),
    (524288, 2048, 1024, 64, BF, "tpu", False, (512, 2048, 1024)),
    (16384, 2048, 1024, 64, BF, "tpu", False, (128, 2048, 1024)),
    (8192, 2048, 1024, 64, BF, "tpu", False, (128, 2048, 1024)),
    # a matrix the VMEM cap does not admit whole: the contraction stays
    # whole and the width narrows
    (65536, 4096, 4096, 8, BF, "tpu", False, (512, 4096, 512)),
    (65536, 8192, 2048, 8, BF, "tpu", False, (512, 8192, 256)),
    # Moonlight's experts (64 of 2048 x 1408: 1408 = 11 x 128)
    (65536, 2048, 1408, 64, BF, "tpu", False, (256, 2048, 1408)),
    # serving: 8 rows a step, or a decode batch of 64 x top-8 = 512 rows
    # over 64 experts: under a tile of rows an expert, ragged_dot
    (64, 2048, 1024, 64, BF, "tpu", False, None),
    (512, 2048, 1024, 64, BF, "tpu", False, None),
    # rows no tile divides, a width off the lanes (the TINY sizes)
    (65000, 2048, 1024, 64, BF, "tpu", False, None),
    (128, 32, 16, 8, BF, "tpu", False, None),
    (65536, 2048, 1000, 64, BF, "tpu", False, None),
    # float32 operands, no TPU, a program under a mesh
    (65536, 2048, 1024, 64, F32, "tpu", False, None),
    (65536, 2048, 1024, 64, BF, "cpu", False, None),
    (65536, 2048, 1024, 64, BF, "tpu", True, None),
])
def test_gmm_tile_by_shape(m, k, n, e, dtype, backend, on_mesh, want):
    assert gm.gmm_tile(m, k, n, e, dtype, backend, on_mesh) == want
    if want:
        tm, tk, tn = want
        assert m % tm == 0 and k % tk == 0 and n % tn == 0
        assert gm._vmem_bytes(tm, tk, tn, 2) <= gm._VMEM_CAP_BYTES


def test_gmm_tile_reads_backend_and_mesh_itself(monkeypatch):
    assert gm.gmm_tile(65536, 2048, 1024, 64, BF) is None    # the CPU
    monkeypatch.setattr(gm, "_INTERPRET", True)
    assert gm.gmm_tile(65536, 2048, 1024, 64, BF) == (256, 2048, 1024)
    monkeypatch.setattr(gm, "_under_mesh", lambda: True)
    assert gm.gmm_tile(65536, 2048, 1024, 64, BF) is None
