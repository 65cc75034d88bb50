"""The token-major sums' kernel (paddle_tpu/parallel/pair_sum.py:
``pairs.sum.*``) through the Pallas interpreter on the CPU, as
tests/test_grouped_matmul.py runs the ``moe.*`` kernels: against a
float32 numpy sum, weighted and not, at k 6 / 8 / 10 and at live counts
on every edge (none, one, one short of a group of 16 rows, a whole
buffer), with NaN behind the last live row; against the walk by token
(``ops/moe_ops._sum_by_token``), the one XLA form left beside it; what
``sum_tile`` takes and refuses; and a held layer's and an unheld one's
gradients against the float32 reference with the kernel in the sums."""

import jax.numpy as jnp
import numpy as np
import pytest

import test_moe_live_rows as base
from paddle_tpu.ops import moe_ops
from paddle_tpu.parallel import pair_sum as ps

N, D = 64, 128
SCORED, FIRST, HELD = 96, 8, 12
BF16 = jnp.bfloat16


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(ps, "_INTERPRET", True)


@pytest.fixture
def calls(monkeypatch):
    """The names ``pair_sum`` is called under, in order."""
    names, real = [], ps.pair_sum
    monkeypatch.setattr(ps, "pair_sum", lambda *a, **kw: (
        names.append(kw["name"]), real(*a, **kw))[1])
    return names


def bf16_values(a):
    return np.asarray(jnp.asarray(a, BF16).astype(jnp.float32))


def routing(k, live, seed=0):
    """Slot [N, k], Rows [HELD] and the row buffer's order for a routing
    with exactly ``live`` pairs on the held experts (k distinct experts
    a token), as ``moe_dispatch`` sorts them."""
    r = np.random.RandomState(seed)
    elsewhere = np.r_[0:FIRST, FIRST + HELD:SCORED]
    top_i = np.stack([r.permutation(elsewhere)[:k] for _ in range(N)])
    for pair in r.permutation(N * k)[:live]:
        t, j = divmod(int(pair), k)
        top_i[t, j] = FIRST + (t + j) % HELD
    local = top_i.reshape(-1) - FIRST
    flat = np.where((local >= 0) & (local < HELD), local, HELD)
    order = np.argsort(flat, kind="stable")
    slot = np.argsort(order).reshape(N, k).astype(np.int32)
    sizes = np.bincount(flat, minlength=HELD + 1)[:HELD].astype(np.int32)
    assert sizes.sum() == live
    return slot, sizes


def float32_sum(rows, slot, live, w):
    out = np.zeros((slot.shape[0], rows.shape[1]), np.float32)
    for t, row in enumerate(slot):
        for j, r in enumerate(row):
            if r < live:
                out[t] += (1.0 if w is None else w[t, j]) * rows[r]
    return out


def within_one_bf16_cast(got, want):
    """``got`` (bf16) is ``want`` (float32) rounded once: half a unit in
    the last of bf16's 8 bits, and the float32 sum's own rounding."""
    got = np.asarray(jnp.asarray(got).astype(jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_array_less(
        np.abs(got - want), np.abs(want) * 2.0 ** -8 + 1e-6)


LIVE = {"none": 0, "one": 1, "one_short_of_a_group": 3 * ps._GROUP - 1,
        "a_tile_and_a_row": None, "all": -1}


@pytest.mark.parametrize("behind", ["zeros", "nan"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("case", list(LIVE))
@pytest.mark.parametrize("k", [6, 8, 10])
def test_the_kernel_is_the_float32_sum(k, case, weighted, behind,
                                       interpreter):
    live = {None: 16 * k + 1, -1: N * k}.get(LIVE[case], LIVE[case])
    slot, sizes = routing(k, live, seed=k)
    r = np.random.RandomState(1)
    rows = bf16_values(r.randn(N * k, D))
    w = r.rand(N, k).astype(np.float32) if weighted else None
    buf = rows.copy()
    buf[live:] = 0 if behind == "zeros" else np.nan
    # two token tiles and a buffer of two groups: segments that end in a
    # group another begins in, and more than one pass a tile
    got = ps.pair_sum(jnp.asarray(buf, BF16), jnp.asarray(slot),
                      jnp.asarray(sizes), (32, 2),
                      None if w is None else jnp.asarray(w))
    assert got.shape == (N, D) and got.dtype == BF16
    within_one_bf16_cast(got, float32_sum(rows, slot, live, w))
    if live == 0:
        assert not np.asarray(got.astype(jnp.float32)).any()


# (the cases that held the scatter-add by live row to the walk by token
# before PR 41 took the scatter-add out: the kernel against that walk)
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("share", ["few_live", "half_live", "all_live"])
def test_the_kernel_is_the_walk_by_token(share, weighted, interpreter,
                                         calls, monkeypatch):
    """``_add_into_tokens`` with the kernel and, with ``sum_tile``
    giving no tile, as the walk by token: one result, each cast once."""
    k = 8
    live = {"few_live": 37, "half_live": N * k // 2 + 3,
            "all_live": N * k}[share]
    slot, sizes = routing(k, live, seed=3)
    r = np.random.RandomState(2)
    rows = jnp.asarray(np.where(np.arange(N * k)[:, None] < live,
                                r.randn(N * k, D), 0), BF16)
    w = jnp.asarray(r.rand(N, k), jnp.float32) if weighted else None
    args = (("moe_combine", "sum_pairs"), rows, jnp.asarray(slot),
            jnp.asarray(sizes), moe_ops.live_window(N * k, N * k // 8), w)
    by_kernel = moe_ops._add_into_tokens(*args)
    assert calls == ["pairs.sum.combine"]
    monkeypatch.setattr(ps, "sum_tile", lambda *a, **kw: None)
    walk = moe_ops._add_into_tokens(*args)
    assert calls == ["pairs.sum.combine"] and walk.dtype == by_kernel.dtype
    want = moe_ops._sum_by_token(rows, jnp.asarray(slot), live, w)
    within_one_bf16_cast(by_kernel, np.asarray(want))
    np.testing.assert_array_equal(walk, want.astype(BF16))


def test_sum_tile_follows_the_shape_the_dtype_the_backend_and_the_mesh():
    tile = ps.sum_tile(16384, 6, 2560, BF16, backend="tpu", on_mesh=False)
    assert tile == (128, 32)
    # a short row count takes the largest tile that divides it
    assert ps.sum_tile(48, 8, 128, BF16, backend="tpu",
                       on_mesh=False) == (16, 32)
    for refused in (
            dict(backend="cpu"), dict(on_mesh=True), dict(dtype=jnp.float32),
            dict(d=2000), dict(n=12, k=6), dict(n=4100), dict(d=16384)):
        kw = {"n": 4096, "k": 8, "d": 2048, "dtype": BF16,
              "backend": "tpu", "on_mesh": False, **refused}
        assert ps.sum_tile(kw.pop("n"), kw.pop("k"), kw.pop("d"),
                           kw.pop("dtype"), **kw) is None, refused
    # this process has no TPU and no interpreter: no tile
    assert ps.sum_tile(4096, 8, 2048, BF16) is None


def test_segment_starts_bound_each_tile_s_rows_of_each_group():
    k, tt = 6, 16
    slot, sizes = routing(k, 100, seed=5)
    seg = np.asarray(ps.segment_starts(
        jnp.asarray(slot), jnp.asarray(sizes), tt)).reshape(-1, HELD)
    ends = np.cumsum(sizes)
    assert (seg[0] == ends - sizes).all() and (seg[-1] == ends).all()
    for i in range(N // tt):
        mine = np.sort(slot[i * tt:(i + 1) * tt].reshape(-1))
        mine = mine[mine < ends[-1]]
        spans = np.concatenate([np.arange(a, b)
                                for a, b in zip(seg[i], seg[i + 1])])
        assert (np.sort(spans) == mine).all(), i


@pytest.mark.parametrize("layer", ["held", "holds_every_expert"])
def test_a_layer_s_gradients_with_the_kernel_in_its_sums(
        layer, interpreter, calls, monkeypatch):
    """tests/test_moe_live_rows.py's layer and float32 reference at a
    width the kernel takes, tokens and cotangent in bf16 (as the AMP
    stream hands them over): a held layer's two sums are the kernel's;
    a layer that holds every expert (a buffer all live) takes it for the
    tokens' gradient and keeps moe_combine's gather, which its grad op
    reads again."""
    monkeypatch.setattr(base, "D", D)
    k = 8
    if layer == "held":
        attrs, live = base.ATTRS, base.N * k // 2 + 5
        top_i, top_w = base.routing(k, live)
    else:
        # (moe_combine's grad op is the generic one here: its slots)
        attrs, live = {"num_experts": base.COUNT, "fwd_input_slots": [
            "Ys", "TopW", "Order", "Slot", "Like", "Rows"],
            "fwd_output_slots": ["Out"]}, base.N * k
        monkeypatch.setattr(base, "ROUTER_E", base.COUNT)
        monkeypatch.setattr(base, "FIRST", 0)
        r = np.random.RandomState(0)
        top_i = jnp.asarray(np.stack([
            r.permutation(base.COUNT)[:k] for _ in range(base.N)]),
            jnp.int32)
        top_w = jnp.asarray(r.rand(base.N, k), jnp.float32)
    v = base.weights()
    v["x"] = v["x"].astype(BF16)
    v["g"] = v["g"].astype(BF16)
    got = base.layer(v, top_w, top_i, True, attrs)
    assert int(got["rows"].sum()) == live
    assert calls == ["pairs.sum.combine"] * (layer == "held") + [
        "pairs.sum.dispatch_grad"]
    want = base.reference(
        {key: a.astype(jnp.float32) for key, a in v.items()}, top_w, top_i)
    for key in want:
        scale = float(np.abs(np.asarray(want[key])).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(got[key]) / scale, np.asarray(want[key]) / scale,
            rtol=3e-2, atol=3e-2, err_msg=key)
