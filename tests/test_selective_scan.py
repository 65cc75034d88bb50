"""The selective scan (ops/selective_scan_ops.py) on the CPU at tiny
sizes: the chunked XLA writing against the position-by-position
recurrence, forward and the gradient of every input, in float32 and
bf16 and at a row the chunk does not divide; the ``ssm.scan.*`` Pallas
kernels through the interpreter against the chunked writing; the tile
picker; the dispatch counter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import flags, layers
from paddle_tpu.ops import selective_scan_ops as S
from paddle_tpu.parallel import selective_scan as K

F32, BF = jnp.float32, jnp.bfloat16


def operands(b, t, e, n, dtype, gated=True, bias=True, seed=0):
    r = np.random.RandomState(seed)
    ins = {"X": jnp.asarray(r.randn(b, t, e), dtype),
           "Dt": jnp.asarray(r.randn(b, t, e) - 2.0, dtype),
           "A": -jnp.asarray(np.exp(r.rand(e, n) * 2), F32),
           "B": jnp.asarray(r.randn(b, t, n), dtype),
           "C": jnp.asarray(r.randn(b, t, n), dtype),
           "D": jnp.asarray(r.randn(e), F32)}
    if gated:
        ins["Z"] = jnp.asarray(r.randn(b, t, e), dtype)
    if bias:
        ins["DtBias"] = jnp.asarray(r.randn(e) * 0.5, F32)
    return ins, jnp.asarray(r.randn(b, t, e), dtype)


def op(ins, dy, **attrs):
    """(Out, {GRAD::slot}, States) of the op and its grad op: one jitted
    computation (a fresh one a call: the hooks are read as it is traced)."""
    def both(ins, dy):
        wrapped = {k: [v] for k, v in ins.items()}
        out = S._selective_scan(wrapped, attrs)
        grads = S._selective_scan_grad(
            {**wrapped, "Out": out["Out"], "States": out["States"],
             "GRAD::Out": [dy]}, attrs)
        return (out["Out"][0], {k: v[0] for k, v in grads.items()},
                out["States"][0])

    return jax.jit(both)(ins, dy)


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("bias", [True, False])
@pytest.mark.parametrize("t,chunk", [(48, 16), (50, 16), (7, 64)])
def test_chunked_matches_the_recurrence_float32(t, chunk, gated, bias):
    ins, dy = operands(2, t, 8, 4, F32, gated, bias, seed=t)
    y0, g0, _ = op(ins, dy, impl="recurrent")
    y1, g1, states = op(ins, dy, impl="chunked", chunk=chunk)
    assert states.shape == (-(-t // chunk), 2, 8, 4)
    assert rel(y1, y0) < 1e-5
    assert set(g1) == set(g0) == {f"GRAD::{s}" for s in ins}
    for k in g0:
        assert g1[k].shape == ins[k[6:]].shape and g1[k].dtype == ins[
            k[6:]].dtype
        assert rel(g1[k], g0[k]) < 1e-5, k


def test_chunked_matches_the_recurrence_bf16():
    # a bf16 stream: x, dt, z, B, C arrive and Out leaves in bf16, the
    # state, Delta and every exp are float32 either way
    ins, dy = operands(1, 40, 16, 4, BF, seed=3)
    y0, g0, _ = op(ins, dy, impl="recurrent")
    y1, g1, _ = op(ins, dy, impl="chunked", chunk=16)
    assert y1.dtype == BF and rel(y1, y0) < 1e-2
    for k in g0:
        assert g1[k].dtype == ins[k[6:]].dtype
        assert rel(g1[k], g0[k]) < 2e-2, k


def test_the_recurrence_is_the_docstrings():
    ins, _ = operands(1, 5, 3, 2, F32, seed=9)
    x, dt, a, b, c, d, z, bias = (np.asarray(ins[s], np.float64)
                                  for s in S.SLOTS)
    s = np.zeros((3, 2))
    want = np.zeros((5, 3))
    for t in range(5):
        delta = np.log1p(np.exp(dt[0, t] + bias))
        s = (np.exp(delta[:, None] * a) * s
             + (delta * x[0, t])[:, None] * b[0, t][None, :])
        y = (s * c[0, t][None, :]).sum(-1) + d * x[0, t]
        want[t] = y * z[0, t] / (1 + np.exp(-z[0, t]))
    got = S.recurrent_selective_scan(*(ins[s] for s in S.SLOTS))
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5,
                               atol=1e-6)


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(K, "_INTERPRET", True)


@pytest.mark.parametrize("t,gated,bias", [(64, True, True), (40, False, True),
                                          (160, True, False)])
def test_kernels_match_the_chunked_writing(interpreter, monkeypatch, t,
                                           gated, bias):
    ins, dy = operands(1, t, 1024, K.STATE, BF, gated, bias, seed=t)
    assert K.ssm_tile(t, 1024, K.STATE, BF) == (
        min(128, -(-t // 32) * 32), 1024)
    yk, gk, states = op(ins, dy)
    rows = K.ssm_tile(t, 1024, K.STATE, BF)[0]
    assert states.shape == (1, -(-t // rows), K.STATE, 8, 128)
    assert states.dtype == F32
    monkeypatch.setattr(K, "_INTERPRET", False)
    yc, gc, _ = op(ins, dy, chunk=16)
    assert rel(yk, yc) < 1e-2
    assert set(gk) == set(gc)
    for k in gc:
        assert gk[k].shape == gc[k].shape and gk[k].dtype == gc[k].dtype
        assert rel(gk[k], gc[k]) < 2e-2, k


def test_the_fold_sums_every_entry_once():
    # the butterfly the backward kernel reduces dB and dC with, in
    # numpy: row r of the folded array is the sum of v_r's rows
    r = np.random.RandomState(0)
    vs = [r.randn(8, 128) for _ in range(8)]
    index = np.arange(8).reshape(-1, 1)
    got = K._fold(vs, 0, np.roll, np.where, index)
    for row in range(8):
        np.testing.assert_allclose(got[row], vs[row].sum(0), rtol=1e-12)
    assert K._fold_order_is_natural()


def test_tile_follows_the_call():
    tile = K.ssm_tile
    assert tile(4096, 5120, 16, BF, backend="tpu", on_mesh=False) == (
        128, 1024)
    assert tile(40, 1024, 16, BF, backend="tpu", on_mesh=False) == (64, 1024)
    assert tile(4096, 5120, 16, BF, backend="cpu", on_mesh=False) is None
    assert tile(4096, 5120, 16, BF, backend="tpu", on_mesh=True) is None
    assert tile(4096, 5120, 16, F32, backend="tpu", on_mesh=False) is None
    assert tile(4096, 5000, 16, BF, backend="tpu", on_mesh=False) is None
    assert tile(4096, 5120, 8, BF, backend="tpu", on_mesh=False) is None


def run_layer(impl, t=12):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[t, 8], dtype="float32")
        dt = layers.data("dt", shape=[t, 8], dtype="float32")
        bc = layers.data("bc", shape=[t, 8], dtype="float32")
        b, c = layers.split(bc, 2, dim=-1)
        y = layers.selective_scan(x, dt, b, c, z=x, state_size=4, chunk=8,
                                  impl=impl)
        loss = layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {k: r.randn(2, t, 8).astype("float32") for k in ("x", "dt", "bc")}
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
    np.testing.assert_allclose(chunked, recurrent, rtol=1e-5)
    assert chunked[2] != chunked[0]          # A_log, D, dt_bias move
    assert len(params) == 3
    # A_log started at log(1 .. n) in every channel: three small steps on
    np.testing.assert_allclose(
        params[0], np.tile(np.log(np.arange(1.0, 5.0)), (8, 1)), atol=0.1)
    for got, want in zip(params, params_r):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    new = {k: v - before.get(k, 0) for k, v in after.items()
           if v - before.get(k, 0)}
    assert new == {"chunked fwd b2 t12 e8 n4 chunk8": 1,
                   "chunked bwd b2 t12 e8 n4 chunk8": 1,
                   "recurrent fwd b2 t12 e8 n4 chunk1": 1,
                   "recurrent bwd b2 t12 e8 n4 chunk1": 1}, new
    with pytest.raises(ValueError):
        run_layer("associative")
