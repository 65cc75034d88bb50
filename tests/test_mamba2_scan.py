"""Mamba-2's scan (ops/mamba2_scan_ops.py) on the CPU at small sizes:
the chunked XLA writing against the position-by-position recurrence,
forward and the gradient of every input, in float32 and bf16, at a row
the chunk does not divide and with heads in groups of 8; the
``mamba2.chunk.*`` Pallas kernels through the interpreter at their own
tile against the recurrence; the tile picker; the dispatch counter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import flags, layers
from paddle_tpu.ops import mamba2_scan_ops as S
from paddle_tpu.parallel import mamba2_scan as K

F32, BF = jnp.float32, jnp.bfloat16


def operands(b, t, heads, p, groups, n, dtype, seed=0):
    r = np.random.RandomState(seed)
    ins = {"X": jnp.asarray(r.randn(b, t, heads * p), dtype),
           "Dt": jnp.asarray(r.randn(b, t, heads) - 1.0, dtype),
           "ALog": jnp.asarray(np.log(1.0 + 3 * r.rand(heads)), F32),
           "B": jnp.asarray(r.randn(b, t, groups * n) * 0.5, dtype),
           "C": jnp.asarray(r.randn(b, t, groups * n) * 0.5, dtype),
           "D": jnp.asarray(r.randn(heads), F32),
           "DtBias": jnp.asarray(r.randn(heads) * 0.5, F32)}
    return ins, jnp.asarray(r.randn(b, t, heads * p), dtype)


def op(ins, dy, **attrs):
    """(Out, {GRAD::slot}, States) of the op and its grad op: one jitted
    computation (a fresh one a call: the hooks are read as it is traced)."""
    def both(ins, dy):
        wrapped = {k: [v] for k, v in ins.items()}
        out = S._mamba2_scan(wrapped, attrs)
        grads = S._mamba2_scan_grad(
            {**wrapped, "Out": out["Out"], "States": out["States"],
             "GRAD::Out": [dy]}, attrs)
        return (out["Out"][0], {k: v[0] for k, v in grads.items()},
                out["States"][0])

    return jax.jit(both)(ins, dy)


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)


@pytest.mark.parametrize("heads,groups", [(4, 1), (4, 2), (16, 2)],
                         ids=["one_group", "pairs", "groups_of_8"])
@pytest.mark.parametrize("t,chunk", [(48, 16), (50, 16), (7, 128)])
def test_chunked_matches_the_recurrence_float32(t, chunk, heads, groups):
    ins, dy = operands(2, t, heads, 4, groups, 8, F32, seed=t)
    y0, g0, _ = op(ins, dy, impl="recurrent", groups=groups)
    y1, g1, states = op(ins, dy, impl="chunked", chunk=chunk, groups=groups)
    assert states.shape == (-(-t // chunk), 2, groups, heads // groups, 4, 8)
    assert rel(y1, y0) < 1e-5
    assert set(g1) == set(g0) == {f"GRAD::{s}" for s in ins}
    for k in g0:
        assert g1[k].shape == ins[k[6:]].shape and g1[k].dtype == ins[
            k[6:]].dtype
        assert rel(g1[k], g0[k]) < 2e-5, k


def test_chunked_matches_the_recurrence_bf16():
    # a bf16 stream: x, dt, B, C arrive and Out leaves in bf16; the
    # state, dt, the decay and every exp are float32 either way
    ins, dy = operands(1, 40, 4, 8, 2, 8, BF, seed=3)
    y0, g0, _ = op(ins, dy, impl="recurrent", groups=2)
    y1, g1, _ = op(ins, dy, impl="chunked", chunk=16, groups=2)
    assert y1.dtype == BF and rel(y1, y0) < 1e-2
    for k in g0:
        assert g1[k].dtype == ins[k[6:]].dtype
        assert rel(g1[k], g0[k]) < 2e-2, k


def test_the_recurrence_is_the_docstrings():
    heads, p, groups, n = 4, 3, 2, 5
    ins, _ = operands(1, 6, heads, p, groups, n, F32, seed=9)
    x, dt, a_log, b, c, d, bias = (np.asarray(ins[s], np.float64)
                                   for s in S.SLOTS)
    s = np.zeros((heads, p, n))
    want = np.zeros((6, heads * p))
    for t in range(6):
        step = np.log1p(np.exp(dt[0, t] + bias))
        for h in range(heads):
            g = h // (heads // groups)
            xh = x[0, t, h * p:(h + 1) * p]
            s[h] = (np.exp(-np.exp(a_log[h]) * step[h]) * s[h]
                    + step[h] * np.outer(xh, b[0, t, g * n:(g + 1) * n]))
            want[t, h * p:(h + 1) * p] = (
                s[h] @ c[0, t, g * n:(g + 1) * n] + d[h] * xh)
    got = S.recurrent_mamba2_scan(*(ins[s] for s in S.SLOTS), groups)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5,
                               atol=1e-6)


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(K, "_INTERPRET", True)


# (t, heads, groups): an uneven last chunk; more chunks than a grid
# step holds and groups of 8 heads (four pairs); two blocks, two groups
@pytest.mark.parametrize("t,heads,groups", [(200, 2, 1), (1100, 8, 1),
                                            (1280, 4, 2)])
def test_kernels_match_the_recurrence(interpreter, t, heads, groups):
    ins, dy = operands(1, t, heads, K.HEAD_DIM, groups, K.STATE, BF, seed=t)
    tile = K.mamba2_tile(t, heads, groups, K.HEAD_DIM, K.STATE, K.CHUNK, BF)
    assert tile == (heads // groups // 2, min(8, -(-t // K.CHUNK)))
    yk, gk, states = op(ins, dy, groups=groups)
    assert states.shape == (-(-t // K.CHUNK), 1, heads // 2, K.STATE, 128)
    assert states.dtype == F32
    # the recurrence in float32 on the same bf16 numbers
    wide = {k: v.astype(F32) for k, v in ins.items()}
    y0, g0, _ = op(wide, dy.astype(F32), impl="recurrent", groups=groups)
    assert yk.dtype == BF and rel(yk, y0) < 1.5e-2
    assert set(gk) == set(g0)
    for k in g0:
        assert gk[k].shape == g0[k].shape and gk[k].dtype == ins[k[6:]].dtype
        assert rel(gk[k], g0[k]) < 3e-2, k


# ONE group wider than a grid step (Granite-4.0-H: 64 heads share one B
# and C): head blocks of _BLOCK_PAIRS pairs walk it, each writes its own
# float32 dB and dC, one sum makes the group's. 16 heads fit the cap as
# one block; with the block cut to 2 pairs they are four. (A short row's
# blocks are small, so even 64 heads fit the cap whole: the cases that
# want head blocks lower the cap too.)
@pytest.mark.parametrize("t,heads,block_pairs,tile", [
    (300, 16, None, (8, 3)), (300, 16, 2, (2, 3)), (140, 64, 4, (4, 2))],
    ids=["16_whole", "16_in_blocks_of_4", "64_in_blocks_of_8"])
def test_one_group_wider_than_a_step(interpreter, monkeypatch, t, heads,
                                     block_pairs, tile):
    if block_pairs:
        monkeypatch.setattr(K, "_BLOCK_PAIRS", block_pairs)
        monkeypatch.setattr(K, "_VMEM_CAP_BYTES", 2**20)
    ins, dy = operands(1, t, heads, K.HEAD_DIM, 1, K.STATE, BF, seed=heads)
    assert K.mamba2_tile(t, heads, 1, K.HEAD_DIM, K.STATE, K.CHUNK,
                         BF) == tile
    yk, gk, states = op(ins, dy, groups=1)
    assert states.shape == (-(-t // K.CHUNK), 1, heads // 2, K.STATE, 128)
    wide = {k: v.astype(F32) for k, v in ins.items()}
    y0, g0, _ = op(wide, dy.astype(F32), impl="recurrent", groups=1)
    y1, g1, _ = op(wide, dy.astype(F32), impl="chunked", groups=1)
    assert rel(y1, y0) < 1e-5
    assert yk.dtype == BF and rel(yk, y0) < 1.5e-2
    for k in g0:
        assert rel(g1[k], g0[k]) < 1e-4, k
        assert gk[k].shape == g0[k].shape and gk[k].dtype == ins[k[6:]].dtype
        assert rel(gk[k], g0[k]) < 3e-2, k


def test_tile_follows_the_call():
    tile = K.mamba2_tile
    on = dict(backend="tpu", on_mesh=False)
    assert tile(4096, 64, 8, 64, 128, 128, BF, **on) == (4, 8)
    assert tile(200, 2, 1, 64, 128, 128, BF, **on) == (1, 2)
    assert tile(4096, 64, 8, 64, 128, 128, BF, backend="cpu",
                on_mesh=False) is None
    assert tile(4096, 64, 8, 64, 128, 128, BF, backend="tpu",
                on_mesh=True) is None
    assert tile(4096, 64, 8, 64, 128, 128, F32, **on) is None
    assert tile(4096, 64, 8, 64, 128, 64, BF, **on) is None     # the chunk
    assert tile(4096, 64, 8, 128, 128, 128, BF, **on) is None   # a head
    assert tile(4096, 64, 8, 64, 16, 128, BF, **on) is None     # the state
    assert tile(4096, 24, 8, 64, 128, 128, BF, **on) is None    # 3 a group
    # a group wider than the VMEM cap takes: head blocks walk it
    assert tile(16384, 64, 1, 64, 128, 128, BF, **on) == (4, 8)
    assert tile(16384, 16, 1, 64, 128, 128, BF, **on) == (8, 8)
    assert tile(16384, 44, 1, 64, 128, 128, BF, **on) == (2, 8)  # 22 pairs


def run_layer(impl, t=12):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[t, 8], dtype="float32")
        dt = layers.data("dt", shape=[t, 4], dtype="float32")
        bc = layers.data("bc", shape=[t, 12], dtype="float32")
        b, c = layers.split(bc, 2, dim=-1)
        y = layers.mamba2_scan(x, dt, b, c, heads=4, groups=2, chunk=8,
                               impl=impl)
        loss = layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {k: r.randn(2, t, w).astype("float32")
            for k, w in (("x", 8), ("dt", 4), ("bc", 12))}
    out = [exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]
           for _ in range(3)]
    # (A_log, D, dt_bias: in the order the layer made them)
    return np.asarray(out), [np.asarray(scope.find_var(p.name))
                             for p in main.all_parameters()]


def test_the_layer_trains_either_way_and_counts_its_calls():
    flags.set_flags({"telemetry": True})
    try:
        before = S.dispatch_counts()
        chunked, params = run_layer("chunked")
        recurrent, params_r = run_layer("recurrent")
        after = S.dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
    np.testing.assert_allclose(chunked, recurrent, rtol=1e-4, atol=1e-6)
    assert chunked[2] != chunked[0]          # A_log, D, dt_bias move
    assert len(params) == 3
    # A_log started at log(1 .. heads): three small steps on
    np.testing.assert_allclose(params[0], np.log(np.arange(1.0, 5.0)),
                               atol=0.1)
    for got, want in zip(params, params_r):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    new = {k: v - before.get(k, 0) for k, v in after.items()
           if v - before.get(k, 0)}
    shape = "b2 t12 h4 p2 g2 n3"
    assert new == {f"chunked fwd {shape} chunk8": 1,
                   f"chunked bwd {shape} chunk8": 1,
                   f"recurrent fwd {shape} chunk1": 1,
                   f"recurrent bwd {shape} chunk1": 1}, new
    with pytest.raises(ValueError):
        run_layer("associative")
    with pytest.raises(ValueError):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = layers.data("x", shape=[4, 8], dtype="float32")
            dt = layers.data("dt", shape=[4, 4], dtype="float32")
            layers.mamba2_scan(x, dt, x, x, heads=4, groups=3)
