"""The delta rule with a decay a key FEATURE (Kimi Delta Attention: G
[b, t, hv, dk], S_t = Diag(exp(g_t)) S_{t-1}; ops/linear_attention_ops.py
and parallel/gated_delta_rule.py's ``kda.rule.*``) on the CPU: the
chunked XLA form and the kernels through the Pallas interpreter against
the step-by-step float32 recurrence, Out and all five gradients, at rows
of one, two and three chunks and one the chunk does not divide, with
gates drawn so that G falls below -200 inside a chunk (A = 16, dt = 0.5:
``(K e^G)(K e^-G)^T`` ends at e^88 and is not finite there); a decay
whose dk values are all equal against the scalar-gate call; causality;
the gates op and the gated norm's sigmoid; the op through a Program.
The chip's run: benchmarks/kda_rule_time.py, chip_smoke.py's ``kda``
phase."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from op_test import delta_rule_op
from op_test import delta_rule_recurrence as recurrence
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.parallel import gated_delta_rule as gdr

BF, F32 = jnp.bfloat16, jnp.float32
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(gdr, "_INTERPRET", True)


def operands(t, h, seed=0, dtype=F32, dt=0.5, width=16, b=1):
    """q, k, v [b, t, h, width], g [b, t, h, width] = -16 softplus(x)
    with softplus(x) near ``dt`` (A = 16: about -8 a position at 0.5,
    below -200 within half a chunk of 64), beta, dO."""
    r = np.random.RandomState(seed)
    q, k, v, do = (jnp.asarray(r.randn(b, t, h, width), dtype)
                   for _ in "qkvd")
    x = r.randn(b, t, h, width) * 0.3 + np.log(np.expm1(dt))
    g = -16.0 * jax.nn.softplus(jnp.asarray(x, F32))
    return q, k, v, g, jax.nn.sigmoid(jnp.asarray(r.randn(b, t, h), F32)), do


def through_the_op(q, k, v, g, beta, do, **attrs):
    return delta_rule_op(**attrs)(q, k, v, g, beta, do)


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def steepest(g, chunk):
    """The lowest running sum of g inside a chunk."""
    g = np.asarray(g)
    t = g.shape[1] // chunk * chunk
    return float(np.cumsum(g[:, :t].reshape(
        g.shape[0], -1, chunk, *g.shape[2:]), 2).min())


# one, two and three chunks of 64, and a row the chunk does not divide
ROWS = [64, 128, 192, 150]


@pytest.mark.parametrize("t", ROWS)
def test_chunked_xla_form_is_the_recurrence_below_minus_200(t):
    """float32 operands: the same mathematics in another order, to
    float32's rounding, however steep the gates."""
    args = operands(t, 2, seed=t)
    assert steepest(args[3], 64) < -200
    got, states = through_the_op(*args, chunk=64)
    assert states.shape == (-(-t // 64), 1, 2, 16, 16)
    for name, a, b in zip(NAMES, got, recurrence(*args)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 2e-5, (name, rel(a, b))


def test_the_obvious_factoring_is_not_finite_there():
    """What the halving is for: (K e^G)(K e^-G)^T overflows float32
    where G passes -88, and the levels' factors never do."""
    q, k, _, g, _, _ = operands(64, 1, seed=3)
    gc = jnp.cumsum(jnp.moveaxis(g, 2, 1), 2)             # [b, h, C, dk]
    kh = jnp.moveaxis(k, 2, 1)
    naive = jnp.einsum("...ik,...jk->...ij", kh * jnp.exp(gc),
                       kh * jnp.exp(-gc))
    assert not np.isfinite(np.asarray(jnp.tril(naive))).all()
    p, kk = L._decayed_products(jnp.moveaxis(q, 2, 1), kh, gc, F32)
    direct = jnp.einsum("...id,...jd,...ijd->...ij", kh, kh, jnp.exp(
        jnp.minimum(gc[..., :, None, :] - gc[..., None, :, :], 0.0)))
    np.testing.assert_allclose(kk, jnp.tril(direct, -1), rtol=1e-5,
                               atol=1e-7)
    assert np.isfinite(np.asarray(p)).all()


@pytest.mark.parametrize("chunk", [8, 12])
def test_any_chunk_halves(chunk):
    """A chunk that is no power of two: the levels still part every
    pair once."""
    args = operands(40, 2, seed=chunk, dt=0.05)
    got, _ = through_the_op(*args, chunk=chunk)
    for name, a, b in zip(NAMES, got, recurrence(*args)):
        assert rel(a, b) < 2e-5, (name, rel(a, b))


@pytest.mark.parametrize("dt", [0.5, 0.002], ids=["steep", "mild"])
@pytest.mark.parametrize("t,h", [(64, 2), (128, 2), (192, 1), (150, 3),
                                 (600, 2)])
def test_kernels_are_the_recurrence(t, h, dt, interpreted):
    """``kda.rule.fwd`` / ``kda.rule.bwd`` through the interpreter: bf16
    operands, so to bf16's rounding of the chunk's matmuls (0.003-0.006
    of the largest entry, as the scalar rule's kernels read); two heads
    a grid step, one where the count is odd; 600 positions are two grid
    steps (the state and dS cross a block). Mild gates carry the state
    from chunk to chunk; steep ones take G below -200 inside one."""
    args = operands(t, h, seed=t + h, dtype=BF, dt=dt, width=128)
    assert (steepest(args[3], 64) < -200) == (dt == 0.5)
    assert gdr.kda_tile(t, h, h, 128, 128, 64, BF) == (
        2 - h % 2, min(8, -(-t // 64)))
    got, states = through_the_op(*args, chunk=64)
    assert states.shape == (-(-t // 64), 1, h, 128, 128)
    assert states.dtype == BF and got[0].dtype == BF
    assert got[4].dtype == F32 and got[4].shape == args[3].shape
    for name, a, b in zip(NAMES, got, recurrence(*args)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 0.012, (name, rel(a, b))


def test_kernels_and_the_chunked_form_save_the_same_states(interpreted,
                                                           monkeypatch):
    args = operands(200, 2, seed=5, dtype=BF, dt=0.002, width=128)
    got, states = through_the_op(*args, chunk=64)
    monkeypatch.setattr(gdr, "_INTERPRET", False)      # no tile: XLA ops
    want, want_states = through_the_op(*args, chunk=64)
    assert rel(states, want_states) < 0.01
    for name, a, b in zip(NAMES, got, want):
        assert rel(a, b) < 0.012, (name, rel(a, b))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernel"])
def test_a_decay_equal_over_the_features_is_the_scalar_rule(kernels,
                                                            monkeypatch):
    """G whose dk values are all equal against the scalar-gate call on
    one of them. Not bit for bit: the scalar form multiplies K K^T by
    exp(G_i - G_j) from outside, ONE rounding of the product, where the
    decay inside the contraction rounds each operand's factor (to bf16
    in the kernels, to float32 in the XLA form) before the product and
    sums 6 levels. So to float32's rounding in float32 and to bf16's
    between the kernels."""
    monkeypatch.setattr(gdr, "_INTERPRET", kernels)
    q, k, v, g, beta, do = operands(
        150, 2, seed=8, dtype=BF if kernels else F32, dt=0.01, width=128)
    g = jnp.broadcast_to(g[..., :1], g.shape)
    wide, _ = through_the_op(q, k, v, g, beta, do, chunk=64)
    flat, _ = through_the_op(q, k, v, g[..., 0], beta, do, chunk=64)
    tol = 0.012 if kernels else 2e-5
    for name, a, b in zip(NAMES, wide, flat):
        if name == "dg":
            a = jnp.sum(a, -1)
        assert rel(a, b) < tol, (name, rel(a, b))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernel"])
def test_a_late_token_changes_nothing_before_it(kernels, monkeypatch):
    monkeypatch.setattr(gdr, "_INTERPRET", kernels)
    dtype, width = (BF, 128) if kernels else (F32, 16)
    q, k, v, g, beta, do = operands(150, 2, seed=4, dtype=dtype, dt=0.01,
                                    width=width)
    at = 100
    edit = [x.at[:, at].set(x[:, at] * 0.5 + 0.25) for x in (q, k, v, g)]
    run = delta_rule_op(chunk=64)
    a, _ = run(q, k, v, g, beta, do)
    b, _ = run(*edit, beta.at[:, at].set(0.9), do)
    np.testing.assert_array_equal(np.asarray(a[0][:, :at], np.float32),
                                  np.asarray(b[0][:, :at], np.float32))
    assert rel(a[0][:, at:], b[0][:, at:]) > 1e-3


def test_picker_needs_keys_of_every_heads_own():
    assert gdr.kda_tile(4096, 32, 32, 128, 128, 64, BF, "tpu", False) == (2, 8)
    assert gdr.kda_tile(100, 3, 3, 128, 128, 64, BF, "tpu", False) == (1, 2)
    for refused in (dict(hk=16), dict(dtype=F32), dict(dk=64), dict(chunk=32),
                    dict(backend="cpu"), dict(on_mesh=True)):
        kw = dict(t=4096, hk=32, hv=32, dk=128, dv=128, chunk=64, dtype=BF,
                  backend="tpu", on_mesh=False)
        kw.update(refused)
        assert gdr.kda_tile(**kw) is None, refused
    assert (gdr._vmem_bytes(2, 8, 128, 128, True)
            <= gdr._VMEM_CAP_BYTES)


def test_gates_op_takes_a_projection_a_feature():
    r = np.random.RandomState(0)
    b, a = r.randn(2, 5, 3), r.randn(2, 5, 3, 4)
    a_log, dt = r.randn(3), r.randn(3, 4)
    out = L._gdn_gates({"B": [jnp.asarray(b)], "A": [jnp.asarray(a)],
                        "ALog": [jnp.asarray(a_log)],
                        "DtBias": [jnp.asarray(dt)]}, {})
    want = -np.exp(a_log)[:, None] * np.log1p(np.exp(a + dt))
    np.testing.assert_allclose(out["G"][0], want, rtol=1e-5)
    assert out["G"][0].shape == (2, 5, 3, 4) and (want <= 0).all()
    np.testing.assert_allclose(out["Beta"][0], 1 / (1 + np.exp(-b)),
                               rtol=1e-5)


def test_gated_norm_takes_a_sigmoid_and_says_so_only_then():
    r = np.random.RandomState(1)
    x, z = r.randn(2, 3, 8).astype("float32"), r.randn(2, 3, 8).astype(
        "float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=[3, 8], dtype="float32")
        zv = layers.data("z", shape=[3, 8], dtype="float32")
        plain = layers.gated_rms_norm(xv, zv, epsilon=1e-5)
        sig = layers.gated_rms_norm(xv, zv, epsilon=1e-5,
                                    gate_act="sigmoid")
        with pytest.raises(ValueError):
            layers.gated_rms_norm(xv, zv, gate_act="tanh")
    ops = [op for op in main.global_block().ops
           if op.type == "gated_rms_norm"]
    assert "gate_act" not in ops[0].attrs
    assert ops[1].attrs["gate_act"] == "sigmoid"
    exe = fluid.Executor()
    exe.run(startup)
    got_plain, got_sig = exe.run(main, feed={"x": x, "z": z},
                                 fetch_list=[plain, sig])
    normed = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)
    sigmoid = 1 / (1 + np.exp(-z))
    np.testing.assert_allclose(got_sig, normed * sigmoid, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got_plain, normed * z * sigmoid, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("impl", ["chunked", "recurrent"])
def test_layer_through_a_program_counts_its_gate(impl):
    """layers.gdn_gates with a projection a feature and
    layers.gated_delta_rule through append_backward: the rank of g
    decides, the dispatch rows carry ``gate=feature`` beside ``impl``,
    and ``dispatch_counts()`` keeps its keys."""
    b, t, h, d = 2, 24, 2, 8
    r = np.random.RandomState(2)
    feed = {n: r.randn(b, t, h, d).astype("float32") for n in "qkva"}
    feed["b"] = r.randn(b, t, h).astype("float32")
    flags.set_flags({"telemetry": True})
    monitor.reset()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ins = {n: layers.data(n, shape=list(x.shape[1:]),
                                  dtype="float32") for n, x in feed.items()}
            for v in ins.values():
                v.stop_gradient = False
            beta, g = layers.gdn_gates(ins["b"], ins["a"])
            assert tuple(g.shape)[1:] == (t, h, d)
            o = layers.gated_delta_rule(ins["q"], ins["k"], ins["v"], g,
                                        beta, chunk=8, impl=impl)
            append_backward(layers.reduce_sum(layers.square(o)))
        dt_bias = [p for p in main.all_parameters() if p.shape == (h, d)]
        assert len(dt_bias) == 1
        exe = fluid.Executor()
        exe.run(startup)
        out, dg = exe.run(main, feed=feed, fetch_list=[o, g.name + "@GRAD"])
        assert out.shape == (b, t, h, d) and dg.shape == (b, t, h, d)
        assert np.isfinite(out).all() and np.abs(dg).max() > 0
        rows = monitor.snapshot()[L._M_DISPATCH.name]["values"]
        assert {(x["labels"]["gate"], x["labels"]["impl"]) for x in rows} \
            == {("feature", impl)}
        chunk = 8 if impl == "chunked" else 1
        assert L.dispatch_counts() == {
            f"{impl} {p} b2 t24 hk2 hv2 dk8 dv8 chunk{chunk}": 1
            for p in ("fwd", "bwd")}
    finally:
        monitor.reset()
        flags.set_flags({"telemetry": False})
