"""``causal_conv1d`` with a bias in front of its activation (Mamba's
convolution has one): both writings against a plain numpy convolution
and against each other, Y, dX, dW and dBias; and a call WITHOUT a bias
lowering as it did before the operand existed (the text of the XLA
writing's lowering against the parent's five lines, the kernels' W
operand still [taps, c])."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.parallel import causal_conv as cc

BF, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(cc, "_INTERPRET", True)


def operands(b, t, c, taps, seed=0, dtype=BF):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, t, c), dtype),
            jnp.asarray(r.randn(c, taps) * 0.5, F32),
            jnp.asarray(r.randn(c), F32),
            jnp.asarray(r.randn(b, t, c), dtype))


def through_the_op(x, w, bias, dy, act):
    ins = {"X": [x], "W": [w], "Bias": [bias]}
    y = L._causal_conv1d(ins, {"act": act})["Y"][0]
    grads = L._causal_conv1d_grad({**ins, "Y": [y], "GRAD::Y": [dy]},
                                  {"act": act})
    return (y, *(grads[f"GRAD::{s}"][0] for s in ("X", "W", "Bias")))


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))


@pytest.mark.parametrize("act", ["silu", ""])
def test_xla_writing_is_the_plain_convolution_plus_the_bias(act):
    x, w, bias, dy = operands(2, 9, 4, 4, dtype=F32)
    y, dx, dw, db = through_the_op(x, w, bias, dy, act)
    xn, wn = np.asarray(x, np.float64), np.asarray(w, np.float64)
    pre = np.zeros_like(xn)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                pre[:, t] += wn[:, j] * xn[:, t - 3 + j]
    pre += np.asarray(bias, np.float64)
    want = pre / (1 + np.exp(-pre)) if act else pre
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-6)
    # the bias's gradient is the pre-activation's, summed over rows
    _, vjp = jax.vjp(lambda b: L._conv_xla(x, w, act, b), bias)
    np.testing.assert_allclose(np.asarray(db), np.asarray(vjp(dy)[0]),
                               rtol=1e-6)
    if not act:
        np.testing.assert_allclose(np.asarray(db),
                                   np.asarray(dy).sum((0, 1)), rtol=1e-5)
    assert db.shape == bias.shape and dw.shape == w.shape


@pytest.mark.parametrize("b,t,c,taps,act", [
    (1, 64, 128, 4, "silu"), (2, 1100, 256, 4, "silu"), (1, 40, 128, 3, ""),
    (1, 2100, 640, 4, "silu")])
def test_kernels_with_a_bias_match_the_xla_writing(interpreted, monkeypatch,
                                                   b, t, c, taps, act):
    x, w, bias, dy = operands(b, t, c, taps, seed=t)
    assert cc.conv_tile(t, c, taps, BF) is not None
    got = through_the_op(x, w, bias, dy, act)
    monkeypatch.setattr(cc, "_INTERPRET", False)
    want = through_the_op(x, w, bias, dy, act)
    for name, g, wnt in zip(("Y", "dX", "dW", "dBias"), got, want):
        assert g.shape == wnt.shape and g.dtype == wnt.dtype, name
        assert rel(g, wnt) < 2e-2, name


def parents_conv_xla(x, w, act):
    """ops/linear_attention_ops._conv_xla as the parent commit had it."""
    taps, t = w.shape[-1], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xf[:, j:j + t] * wf[:, j] for j in range(taps))
    if act == "silu":
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


def test_a_call_without_a_bias_lowers_as_before(interpreted, monkeypatch):
    x, w, _, dy = operands(1, 64, 128, 4)

    def op(x, w):
        return L._causal_conv1d({"X": [x], "W": [w]}, {"act": "silu"})["Y"][0]

    def grad(x, w, dy):
        g = L._causal_conv1d_grad({"X": [x], "W": [w], "GRAD::Y": [dy]},
                                  {"act": "silu"})
        assert set(g) == {"GRAD::X", "GRAD::W"}
        return g["GRAD::X"][0], g["GRAD::W"][0]

    # the kernels: W goes in as [taps, c], dW comes out [taps, 8, c]
    jaxpr = str(jax.make_jaxpr(op)(x, w))
    assert "gdn.conv.fwd" in jaxpr and "f32[4,128]" in jaxpr
    assert "f32[5,128]" not in jaxpr
    jaxpr = str(jax.make_jaxpr(grad)(x, w, dy))
    assert "gdn.conv.bwd" in jaxpr and "f32[4,8,128]" in jaxpr
    assert "f32[5,8,128]" not in jaxpr
    # the XLA writing: the lowered text is the parent's, byte for byte
    monkeypatch.setattr(cc, "_INTERPRET", False)
    text = lambda f, *a: jax.jit(f).lower(*a).as_text()
    assert text(lambda x, w: op(x, w), x, w) == text(
        lambda x, w: parents_conv_xla(x, w, "silu"), x, w)
    assert text(lambda x, w, dy: grad(x, w, dy), x, w, dy) == text(
        lambda x, w, dy: tuple(
            g.astype(v.dtype) for g, v in zip(jax.vjp(
                lambda x, w: parents_conv_xla(x, w, "silu"), x, w)[1](dy),
                (x, w))), x, w, dy)


def test_the_layer_trains_its_bias():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[12, 8], dtype="float32")
        y = layers.causal_conv1d(x, taps=4, act="silu",
                                 param_attr=ParamAttr(name="conv.w"),
                                 bias_attr=ParamAttr(name="conv.b"))
        plain = layers.causal_conv1d(x, taps=4, act="silu",
                                     param_attr=ParamAttr(name="plain.w"))
        loss = layers.mean(layers.elementwise_add(y, plain))
        fluid.optimizer.SGD(0.5).minimize(loss)
    names = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert names == {"conv.w": (8, 4), "conv.b": (8,), "plain.w": (8, 4)}
    conv_ops = [op for op in main.global_block().ops
                if op.type == "causal_conv1d"]
    assert [sorted(op.inputs) for op in conv_ops] == [
        ["Bias", "W", "X"], ["W", "X"]]
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    assert not np.asarray(scope.find_var("conv.b")).any()
    feed = {"x": np.random.RandomState(0).randn(2, 12, 8).astype("float32")}
    first = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]
    second = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0]
    assert np.asarray(scope.find_var("conv.b")).all() and second < first
