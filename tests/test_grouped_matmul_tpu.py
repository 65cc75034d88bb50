"""Hardware grouped-matmul checks: the real ``moe.*`` Pallas kernels on
a real TPU at olmoe-train-s4096's shapes, against libtpu's own
``jax.lax.ragged_dot`` on the same device. Skipped on CPU backends (the
interpreter's run at small shapes is tests/test_grouped_matmul.py). Run
on the chip in one pytest process:

    PT_TEST_TPU=1 python -m pytest tests/test_grouped_matmul_tpu.py -q
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import grouped_matmul as gm

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs a real TPU backend")

M, E = 65536, 64
# the cell's own expert_rows on its correctness sample at seed
# 3000000002 (my chip run, PR 31): the fullest expert 2.98 x the mean
ROUTED = [2642, 1190, 644, 724, 1196, 662, 645, 899, 746, 1410, 822, 678,
          1232, 586, 1005, 485, 934, 1448, 829, 661, 1462, 1446, 1080, 619,
          844, 1649, 1174, 1236, 1065, 1456, 475, 772, 449, 917, 819, 2030,
          408, 1217, 129, 580, 470, 657, 615, 1132, 1418, 1822, 415, 626,
          1129, 867, 785, 411, 943, 1468, 919, 642, 718, 3048, 1102, 1821,
          1620, 1953, 996, 694]


def _groups(which):
    if which == "routed":
        return ROUTED
    if which == "even":
        return [M // E] * E
    # empties: the eight smallest experts' rows go to the fullest, and
    # the first and the last expert are among the empty ones
    sizes = np.array(ROUTED)
    order = [0, E - 1, *np.argsort(sizes)[:6]]
    sizes[int(np.argmax(sizes))] += sizes[order].sum()
    sizes[order] = 0
    return sizes.tolist()


# max |kernel - ragged_dot| over max |ragged_dot|: both take bf16
# operands, accumulate in float32 and round the result to bf16 once; the
# order of a sum over 1024..3048 products differs
REL_TOL = 0.01


def _ragged_with_grads(lhs, rhs, gs, g):
    out, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, gs), lhs, rhs)
    return (out, *vjp(g))


def _rel(a, b):
    a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
    assert bool(jnp.isfinite(a).all())
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("which", ["routed", "even", "empties"])
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)],
                         ids=["gate_up", "down"])
def test_the_cells_shapes_against_ragged_dot(k, n, which):
    sizes = _groups(which)
    assert sum(sizes) == M
    r = np.random.RandomState(k + len(which))
    lhs = jnp.asarray(r.randn(M, k), jnp.bfloat16)
    rhs = jnp.asarray(r.randn(E, k, n) * 0.02, jnp.bfloat16)
    g = jnp.asarray(r.randn(M, n), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    assert gm.gmm_tile(M, k, n, E, jnp.bfloat16) == (256, k, n)

    # (every array an argument: a closed-over one is compiled into the
    # executable as a constant of a quarter of a gigabyte)
    want, want_dx, want_dw = jax.jit(_ragged_with_grads)(lhs, rhs, gs, g)
    got = jax.jit(gm.grouped_matmul)(lhs, rhs, gs)
    dx, dw = jax.jit(gm.grouped_matmul_grads)(lhs, rhs, gs, g)
    assert got.dtype == dx.dtype == dw.dtype == jnp.bfloat16
    assert _rel(got, want) <= REL_TOL
    assert _rel(dx, want_dx) <= REL_TOL
    assert _rel(dw, want_dw) <= REL_TOL
    for e, rows in enumerate(sizes):
        if rows == 0:
            assert not bool(jnp.any(dw[e] != 0)), e
    # and under jax.grad, through the custom vjp
    dx2, dw2 = jax.jit(jax.grad(
        lambda a, b, s, c: jnp.sum(
            gm.grouped_matmul(a, b, s).astype(jnp.float32)
            * c.astype(jnp.float32)), (0, 1)))(lhs, rhs, gs, g)
    assert _rel(dx2, want_dx) <= REL_TOL and _rel(dw2, want_dw) <= REL_TOL


# One chip's share of Qwen3-Next's expert layer (32 of 512 experts, top
# 10 of 8192 tokens): a buffer of 81,920 rows of which the held experts'
# groups fill the front, 160 rows an expert on an even router
HELD_M, HELD_E = 81920, 32


@pytest.mark.parametrize("behind", [True, "tile"],
                         ids=["zeros_behind", "zeros_to_the_tiles_end"])
@pytest.mark.parametrize("which", ["even_160", "skewed", "all_rows", "none"])
@pytest.mark.parametrize("k,n", [(2048, 512), (512, 2048)],
                         ids=["gate_up", "down"])
def test_a_held_share_against_ragged_dot(k, n, which, behind):
    """``zero_behind=True`` (any caller's default): zeros behind the
    last group, ``ragged_dot``'s result. ``"tile"`` (a held layer's own
    ops, which fill no buffer): zeros to the end of the row tile the
    last group ends in and nothing promised behind it; NaN in lhs and g
    behind that tile (what memory nothing filled may hold) changes no
    product and neither gradient."""
    r = np.random.RandomState(k + len(which))
    sizes = {"even_160": [160] * HELD_E,
             "skewed": (r.multinomial(5120, r.dirichlet([0.3] * HELD_E))
                        ).tolist(),
             "all_rows": [HELD_M // HELD_E] * HELD_E,
             "none": [0] * HELD_E}[which]
    live = sum(sizes)
    bf = jnp.bfloat16
    lhs = jnp.asarray(r.randn(HELD_M, k), bf)
    rhs = jnp.asarray(r.randn(HELD_E, k, n) * 0.02, bf)
    g = jnp.asarray(r.randn(HELD_M, n), bf)
    gs = jnp.asarray(sizes, jnp.int32)
    assert gm.gmm_tile(HELD_M, k, n, HELD_E, bf, live_rows=5120) \
        == (128, k, n)
    end = HELD_M
    if behind == "tile":    # of the last group's row tile (tile 0: none)
        end = max(-(-live // 128), 1) * 128
        rows = jnp.arange(HELD_M)[:, None] < end
        lhs, g = jnp.where(rows, lhs, jnp.nan), jnp.where(rows, g, jnp.nan)
    got = jax.jit(lambda a, b, s: gm.grouped_matmul(
        a, b, s, live_rows=5120, zero_behind=behind))(lhs, rhs, gs)
    dx, dw = jax.jit(lambda a, b, s, c: gm.grouped_matmul_grads(
        a, b, s, c, live_rows=5120, zero_behind=behind))(lhs, rhs, gs, g)
    assert not bool(jnp.any(got[live:end] != 0))
    assert not bool(jnp.any(dx[live:end] != 0))
    assert bool(jnp.isfinite(dw.astype(jnp.float32)).all())
    if not live:
        assert not bool(jnp.any(dw != 0))
        return
    want, want_dx, want_dw = jax.jit(_ragged_with_grads)(
        lhs[:live], rhs, gs, g[:live])
    assert _rel(got[:live], want) <= REL_TOL
    assert _rel(dx[:live], want_dx) <= REL_TOL
    assert _rel(dw, want_dw) <= REL_TOL


def test_the_lowered_calls_are_the_programs_kernels():
    lhs = jax.ShapeDtypeStruct((M, 2048), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((E, 2048, 1024), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((M, 1024), jnp.bfloat16)
    gs = jax.ShapeDtypeStruct((E,), jnp.int32)
    text = jax.jit(lambda a, b, s, c: (
        gm.grouped_matmul(a, b, s), gm.grouped_matmul_grads(a, b, s, c))
    ).lower(lhs, rhs, gs, g).compile().as_text()
    for name in ("moe.gmm.fwd", "moe.gmm.bwd_dx", "moe.tgmm.bwd_dw"):
        assert name in text, name
    assert "ragged-dot" not in text
