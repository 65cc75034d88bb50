"""The three ops of manifold-constrained hyper-connections
(paddle_tpu/ops/hc_ops.py: ``hc_mix``, ``hc_pre``, ``hc_post``) against
the plain equations in float32 ``jax.numpy`` (token-major, ``jnp.sum``
over a matrix's axes, nothing kept) and against ``jax.vjp`` of them:
every backward pass of the ops is written by hand. Then what the
mechanism promises: H_res doubly stochastic, the clamp, and n = 1 with
H fixed to 1 as the plain residual; the layers' parameters; the rows of
the dispatch counter; bf16 streams under AMP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.ops import hc_ops

ATTRS = {"n": 4, "epsilon": 1e-6, "iters": 20, "hc_eps": 1e-6, "clamp_min": -30.0,
         "clamp_max": 30.0}


def plain_mix(x, phi, bias, alpha, attrs=ATTRS):
    """(H_pre [b, t, n], H_post [b, t, n], H_res [b, t, n, n]): the
    module docstring's equations, token-major."""
    b, t, n, d = x.shape
    flat = x.reshape(b, t, n * d)
    r = jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                      + attrs["epsilon"])
    m = (flat @ phi) * r
    pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + bias[n:2 * n])
    z = (alpha[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
    mat = jnp.exp(jnp.clip(z, attrs["clamp_min"], attrs["clamp_max"]))
    for _ in range(attrs["iters"]):
        mat = mat / (mat.sum(-1, keepdims=True) + attrs["hc_eps"])
        mat = mat / (mat.sum(-2, keepdims=True) + attrs["hc_eps"])
    return pre, post, mat


def plain_pre(x, pre):
    return jnp.einsum("bti,btid->btd", pre, x)


def plain_post(x, y, res, post):
    return (jnp.einsum("btji,btid->btjd", res, x)
            + post[..., None] * y[:, :, None, :])


def flat(x):    # [b, t, n, d] -> the ops' vec(X) [b, t, n d]
    return x.reshape(x.shape[:2] + (-1,))


def minor(h):   # [b, t, ...] -> [b, ..., t]: the ops' token-minor H
    return jnp.moveaxis(h, 1, -1)


def major(h):   # and back
    return jnp.moveaxis(h, -1, 1)


def operands(n=4, d=8, b=2, t=5, seed=0, spread=1.0):
    r = np.random.RandomState(seed)
    k = n * n + 2 * n
    return dict(
        x=jnp.asarray(r.randn(b, t, n, d), jnp.float32),
        phi=jnp.asarray(0.3 * r.randn(n * d, k), jnp.float32),
        bias=jnp.asarray(spread * r.randn(k), jnp.float32),
        alpha=jnp.asarray(0.5 + 0.3 * r.rand(3), jnp.float32),
        y=jnp.asarray(r.randn(b, t, d), jnp.float32))


def mix_op(o, attrs=ATTRS):
    out = hc_ops._hc_mix({"X": [flat(o["x"])], "Phi": [o["phi"]],
                          "Bias": [o["bias"]], "Alpha": [o["alpha"]]}, attrs)
    return out["HPre"][0], out["HPost"][0], out["HRes"][0]


def test_mix_is_the_plain_equations_token_minor_and_float32():
    o = operands()
    got = mix_op(o)
    want = plain_mix(o["x"], o["phi"], o["bias"], o["alpha"])
    assert [g.shape for g in got] == [(2, 4, 5), (2, 4, 5), (2, 4, 4, 5)]
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(g, minor(w), rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("spread", [1.0, 25.0], ids=["inside", "clamped"])
def test_mix_backward_by_hand_is_autodiff_of_the_plain_equations(spread):
    """At ``clamped`` a part of the n x n entries sits outside -30 .. 30:
    no gradient passes those, by hand as by autodiff."""
    o = operands(seed=1, spread=spread)
    r = np.random.RandomState(2)
    cots = [jnp.asarray(r.randn(*s), jnp.float32)
            for s in ((2, 5, 4), (2, 5, 4), (2, 5, 4, 4))]
    _, vjp = jax.vjp(plain_mix, o["x"], o["phi"], o["bias"], o["alpha"])
    want = vjp(tuple(cots))
    got = hc_ops._hc_mix_grad(
        {"X": [flat(o["x"])], "Phi": [o["phi"]], "Bias": [o["bias"]],
         "Alpha": [o["alpha"]], "GRAD::HPre": [minor(cots[0])],
         "GRAD::HPost": [minor(cots[1])], "GRAD::HRes": [minor(cots[2])]},
        ATTRS)
    if spread > 20:
        z = o["alpha"][2] * 0 + o["bias"][8:]
        assert (np.abs(np.asarray(z)) > 30).any()
    for slot, w in zip(("X", "Phi", "Bias", "Alpha"), want):
        g = got[f"GRAD::{slot}"][0].reshape(w.shape)
        assert g.dtype == w.dtype
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(np.abs(w).max() + 1e-9))


def test_mix_backward_takes_an_absent_cotangent_as_zeros():
    o = operands(seed=3)
    cot = jnp.asarray(np.random.RandomState(4).randn(2, 5, 4), jnp.float32)
    _, vjp = jax.vjp(plain_mix, o["x"], o["phi"], o["bias"], o["alpha"])
    want = vjp((cot, jnp.zeros((2, 5, 4), jnp.float32),
                jnp.zeros((2, 5, 4, 4), jnp.float32)))
    got = hc_ops._hc_mix_grad(
        {"X": [flat(o["x"])], "Phi": [o["phi"]], "Bias": [o["bias"]],
         "Alpha": [o["alpha"]], "GRAD::HPre": [minor(cot)]}, ATTRS)
    np.testing.assert_allclose(got["GRAD::X"][0], flat(want[0]), rtol=2e-4,
                               atol=1e-7)


@pytest.mark.parametrize("iters,limit", [(20, 1e-5), (1, None)],
                         ids=["twenty", "one"])
def test_h_res_is_doubly_stochastic_after_twenty_iterations(iters, limit):
    """And is NOT after one: the iterations are what makes it so. (The
    iteration converges linearly, the faster the nearer exp of the
    matrix is to a constant: at entries of exp(N(0, 0.6)) twenty reach
    1e-6, at exp(N(0, 2)) they leave 2e-4.)"""
    o = operands(seed=5, t=64, spread=0.5)
    o["alpha"] = 0.3 * o["alpha"]
    res = np.asarray(mix_op(o, dict(ATTRS, iters=iters))[2])   # [b,n,n,t]
    rows, cols = res.sum(2), res.sum(1)      # over i; over j
    assert (res > 0).all()
    np.testing.assert_allclose(cols, 1.0, atol=1e-5)   # the last half-step
    off = np.abs(rows - 1.0).max()
    if limit is None:
        assert off > 1e-2
    else:
        assert off < limit


def test_the_clamp_bounds_the_matrix_before_the_iterations():
    """A bias of +-100 on two entries of one row: clamped, both are
    exp(+-30) and the row's mass goes to the large one; unclamped, exp
    overflows float32."""
    o = operands(seed=6)
    bias = np.zeros(24, np.float32)
    bias[8], bias[9] = 100.0, -100.0
    o["bias"] = jnp.asarray(bias)
    o["alpha"] = jnp.zeros(3, jnp.float32)
    res = np.asarray(mix_op(o)[2])
    assert np.isfinite(res).all()
    want = plain_mix(o["x"], o["phi"], o["bias"], o["alpha"])[2]
    np.testing.assert_allclose(res, minor(want), rtol=1e-5, atol=1e-30)
    loose = np.asarray(mix_op(o, dict(ATTRS, clamp_min=-1e9,
                                      clamp_max=1e9))[2])
    assert not np.isfinite(loose).all()


def test_pre_and_post_are_the_plain_sums_and_their_backward_autodiff():
    o = operands(seed=7)
    r = np.random.RandomState(8)
    pre, post, res = (jnp.asarray(r.rand(*s), jnp.float32)
                      for s in ((2, 5, 4), (2, 5, 4), (2, 5, 4, 4)))
    x, y, n = o["x"], o["y"], {"n": 4}
    h = hc_ops._hc_pre({"X": [flat(x)], "HPre": [minor(pre)]}, n)["Out"][0]
    np.testing.assert_allclose(h, plain_pre(x, pre), rtol=1e-6, atol=1e-6)
    out = hc_ops._hc_post({"X": [flat(x)], "Y": [y], "HRes": [minor(res)],
                           "HPost": [minor(post)]}, n)["Out"][0]
    np.testing.assert_allclose(out, flat(plain_post(x, y, res, post)),
                               rtol=1e-6, atol=1e-6)

    dh = jnp.asarray(r.randn(2, 5, 8), jnp.float32)
    want = jax.vjp(plain_pre, x, pre)[1](dh)
    got = hc_ops._hc_pre_grad({"X": [flat(x)], "HPre": [minor(pre)],
                               "GRAD::Out": [dh]}, n)
    np.testing.assert_allclose(got["GRAD::X"][0], flat(want[0]), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(major(got["GRAD::HPre"][0]), want[1],
                               rtol=1e-5, atol=1e-5)

    dout = jnp.asarray(r.randn(2, 5, 4, 8), jnp.float32)
    want = jax.vjp(plain_post, x, y, res, post)[1](dout)
    got = hc_ops._hc_post_grad(
        {"X": [flat(x)], "Y": [y], "HRes": [minor(res)],
         "HPost": [minor(post)], "GRAD::Out": [flat(dout)]}, n)
    for slot, w, back in (("X", flat(want[0]), None), ("Y", want[1], None),
                          ("HRes", want[2], major), ("HPost", want[3],
                                                     major)):
        g = got[f"GRAD::{slot}"][0]
        np.testing.assert_allclose(back(g) if back else g, w, rtol=1e-5,
                                   atol=1e-5)


def test_one_stream_with_h_fixed_to_one_is_the_plain_residual():
    r = np.random.RandomState(9)
    x = jnp.asarray(r.randn(2, 5, 8), jnp.float32)
    y = jnp.asarray(r.randn(2, 5, 8), jnp.float32)
    one = jnp.ones((2, 1, 5), jnp.float32)
    h = hc_ops._hc_pre({"X": [x], "HPre": [one]}, {"n": 1})["Out"][0]
    np.testing.assert_array_equal(h, x)
    out = hc_ops._hc_post({"X": [x], "Y": [y], "HRes": [one[:, None]],
                           "HPost": [one]}, {"n": 1})["Out"][0]
    np.testing.assert_array_equal(out, x + y)


def _program(amp=False, n=4, d=8):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[-1, n * d], dtype="float32")
        x.stop_gradient = False
        with fluid.name_scope("hc"):
            pre, post, res = layers.hc_mix(x, n, name="s_hc")
            h = layers.hc_pre(x, pre)
        y = layers.fc(h, d, num_flatten_dims=2, bias_attr=False)
        out = layers.hc_post(x, y, res, post)
        loss = layers.mean(layers.elementwise_mul(out, out))
        fluid.append_backward(loss)
    main._amp = amp
    return main, startup, (pre, post, res, h, out, loss)


def test_layers_create_three_parameters_and_start_at_a_plain_residual():
    """Phi normal, Bias at H_pre = 1 / n, H_post = 1 and H_res near the
    identity, Alpha 0.01: the layer starts as a pre-norm residual over
    the streams' mean."""
    main, startup, (pre, post, res, h, out, _) = _program()
    params = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert [s for n, s in params.items() if "hc" in n] == [
        (32, 24), (24,), (3,)]
    ops = [op.type for op in main.global_block().ops]
    assert ops[:3] == ["hc_mix", "hc_pre", "mul"] and "hc_post" in ops
    for grad in ("hc_mix_grad", "hc_pre_grad", "hc_post_grad"):
        assert ops.count(grad) == 1, grad
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    x = np.random.RandomState(0).randn(2, 6, 4, 8).astype(np.float32)
    got = exe.run(main, feed={"x": flat(x)}, scope=scope,
                  fetch_list=[pre, post, res, h])
    assert got[0].shape == (2, 4, 6) and got[2].shape == (2, 4, 4, 6)
    np.testing.assert_allclose(got[0], 0.25, atol=2e-3)
    np.testing.assert_allclose(got[1], 1.0, atol=5e-3)
    np.testing.assert_allclose(
        got[2], np.broadcast_to(np.eye(4)[None, :, :, None], got[2].shape),
        atol=2e-3)
    np.testing.assert_allclose(got[3], x.mean(2), atol=1e-2)


def test_the_programs_gradients_are_autodiff_of_the_plain_equations():
    main, startup, (*_, loss) = _program()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(1)
    names = [p.name for p in main.all_parameters()]
    for n in names:   # away from the start: every H depends on the token
        shape = np.shape(scope.find_var(n))
        scope.set(n, jnp.asarray(0.4 * r.randn(*shape), jnp.float32))
    w = {n: np.asarray(scope.find_var(n)) for n in names}
    phi, bias, alpha, fc = (w[n] for n in names)
    x = r.randn(2, 6, 4, 8).astype(np.float32)

    def plain(x_, phi_, bias_, alpha_, fc_):
        pre, post, res = plain_mix(x_, phi_, bias_, alpha_)
        out = plain_post(x_, plain_pre(x_, pre) @ fc_, res, post)  # [.., n, d]
        return jnp.mean(out * out)

    want = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(x, phi, bias, alpha, fc)
    got = exe.run(main, feed={"x": flat(x)}, scope=scope, fetch_list=[
        "x@GRAD", *(f"{n}@GRAD" for n in names)])
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g.reshape(w_.shape), w_, rtol=5e-4,
                                   atol=1e-5 * float(np.abs(w_).max()))


def test_under_amp_the_streams_are_bf16_and_the_mixes_float32():
    main, startup, (pre, post, res, h, out, _) = _program(amp=True)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    x = np.random.RandomState(2).randn(2, 6, 32).astype(np.float32)
    got = exe.run(main, feed={"x": x}, scope=scope, return_numpy=False,
                  fetch_list=[pre, post, res, h, out, "x@GRAD"])
    assert [str(g.dtype) for g in got[:3]] == ["float32"] * 3
    assert str(got[3].dtype) == str(got[4].dtype) == "bfloat16"
    ref = exe.run(_program()[0], feed={"x": x}, scope=scope,
                  fetch_list=[out.name])[0]
    np.testing.assert_allclose(np.asarray(got[4], np.float32), ref,
                               rtol=0.05, atol=0.05)


def test_the_dispatch_counter_has_a_row_a_lowered_call():
    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        main, startup, (*_, loss) = _program()
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.ones((1, 3, 32), np.float32)},
                scope=scope, fetch_list=[loss])
        assert hc_ops.dispatch_counts() == {
            f"xla {op} {way}": 1 for op in ("mix", "pre", "post")
            for way in ("fwd", "bwd")}
    finally:
        monitor.reset()
        flags.set_flags({"telemetry": False})


# --- the Sinkhorn kernels (parallel/hc_mix.py) through the interpreter -----


@pytest.fixture
def interpreted(monkeypatch):
    from paddle_tpu.parallel import hc_mix

    monkeypatch.setattr(hc_mix, "_INTERPRET", True)
    return hc_mix


def _logits(tokens, seed=0):
    r = np.random.RandomState(seed)
    z = 2.0 * r.randn(16, tokens)
    z[3, :100], z[9, 50:300] = 40.0, -35.0       # outside the clamp
    return (jnp.asarray(z, jnp.float32),
            jnp.asarray(r.randn(4, 4, tokens), jnp.float32))


@pytest.mark.parametrize("iters", [3, 20])
def test_mix_kernel_forward_is_the_xla_form(iters, interpreted):
    z, _ = _logits(2048)
    attrs = dict(ATTRS, iters=iters)
    assert interpreted.mix_tile(4, 2048) == 8
    got = jax.jit(lambda z_: hc_ops._res(z_, 4, attrs))(z)
    interpreted._INTERPRET = False
    assert interpreted.mix_tile(4, 2048) is None
    want = hc_ops._res(z, 4, attrs)
    assert got.shape == want.shape == (4, 4, 2048)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)


def test_mix_kernel_backward_is_the_xla_form_and_autodiff(interpreted):
    z, d = _logits(1024, seed=1)
    attrs = dict(ATTRS, iters=5)
    got = jax.jit(lambda z_, d_: hc_ops._res_grad(z_, d_, 4, attrs))(z, d)
    interpreted._INTERPRET = False
    want = hc_ops._res_grad(z, d, 4, attrs)

    def plain(z_):
        mat = jnp.exp(jnp.clip(z_, -30.0, 30.0)).reshape(4, 4, -1)
        for _ in range(5):
            mat = mat / (mat.sum(1, keepdims=True) + 1e-6)
            mat = mat / (mat.sum(0, keepdims=True) + 1e-6)
        return mat

    auto = jax.vjp(plain, z)[1](d)[0]
    scale = float(np.abs(auto).max())
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale)
    np.testing.assert_allclose(got, auto, rtol=2e-4, atol=2e-5 * scale)
    # no gradient passes a clamped entry
    assert not np.asarray(got)[3, :100].any()


@pytest.mark.parametrize("tokens,want", [(4096, 8), (1000, None),
                                         (1024, 8), (512, None)])
def test_mix_tile_takes_whole_blocks_of_tokens(tokens, want, interpreted):
    assert interpreted.mix_tile(4, tokens) == want


def test_the_op_takes_the_kernel_and_says_so(interpreted):
    """hc_mix and its grad op with the kernels on (1024 tokens: one
    block) against the same ops on XLA's forms, and the dispatch rows."""
    o = operands(t=512, seed=11)
    ins = {"X": [flat(o["x"])], "Phi": [o["phi"]], "Bias": [o["bias"]],
           "Alpha": [o["alpha"]]}
    cots = {f"GRAD::{s}": [jnp.ones(shape, jnp.float32)] for s, shape in (
        ("HPre", (2, 4, 512)), ("HPost", (2, 4, 512)),
        ("HRes", (2, 4, 4, 512)))}
    attrs = dict(ATTRS, iters=3)
    got = jax.jit(lambda: (hc_ops._hc_mix(ins, attrs),
                           hc_ops._hc_mix_grad({**ins, **cots}, attrs)))()
    interpreted._INTERPRET = False
    want = (hc_ops._hc_mix(ins, attrs),
            hc_ops._hc_mix_grad({**ins, **cots}, attrs))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5,
                                   atol=2e-6 * float(np.abs(w).max()))
