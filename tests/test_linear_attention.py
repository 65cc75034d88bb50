"""The gated delta rule (ops/linear_attention_ops.py) and the small ops
around it, on the CPU at small sizes: the chunkwise form against the
step-by-step recurrence, forward and every gradient, at several chunk
counts and with a last chunk that is not full; through the Program
(layer, grad op, AMP's casts); and the dispatch counter. Gradients of
the recurrence are ``jax.grad`` of ``lax.scan``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.param_attr import ParamAttr
from perf.reference import qwen3next as ref

# float32 on both sides, the same recurrence as another algebra (a
# triangular solve and matmuls over a chunk against rank-1 updates):
# rounding differs in the last bits of sums over up to 64 terms
TOL = dict(rtol=2e-4, atol=2e-5)


def operands(t, b=2, hk=2, hv=4, dk=16, dv=8, seed=0):
    r = np.random.RandomState(seed)
    f = jnp.float32
    return (jnp.asarray(r.randn(b, t, hk, dk), f),
            jnp.asarray(r.randn(b, t, hk, dk), f),
            jnp.asarray(r.randn(b, t, hv, dv), f),
            -jnp.asarray(r.rand(b, t, hv) * 2, f),     # log decay <= 0
            jnp.asarray(r.rand(b, t, hv), f),
            jnp.asarray(r.randn(b, t, hv, dv), f))


def op_ins(q, k, v, g, beta):
    return {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}


# (positions, chunk): one chunk, several, a last chunk of 6 and of 22
# positions, a sequence shorter than one chunk
@pytest.mark.parametrize("t,chunk", [(64, 64), (128, 16), (96, 32),
                                     (150, 64), (150, 16), (11, 64)])
def test_chunked_form_is_the_recurrence(t, chunk):
    q, k, v, g, beta, do = operands(t)

    @jax.jit    # (op by op each case is some hundreds of executables)
    def both(q, k, v, g, beta, do):
        out = L._gated_delta_rule(op_ins(q, k, v, g, beta), {"chunk": chunk})
        want, vjp = jax.vjp(L.recurrent_gated_delta_rule, q, k, v, g, beta)
        grads = L._gated_delta_rule_grad(
            {**op_ins(q, k, v, g, beta), "States": out["States"],
             "GRAD::Out": [do]}, {"chunk": chunk})
        return out, want, grads, vjp(do)

    with jax.default_matmul_precision("highest"):
        out, want, grads, wants = both(q, k, v, g, beta, do)
    # a ragged last chunk is PADDED (zeros behind the last position
    # write, forget and read nothing): one state a chunk, the last too
    assert out["States"][0].shape[0] == -(-t // chunk)
    np.testing.assert_allclose(out["Out"][0], want, **TOL)
    for slot, w in zip(("Q", "K", "V", "G", "Beta"), wants):
        np.testing.assert_allclose(grads[f"GRAD::{slot}"][0], w,
                                   err_msg=slot, **TOL)


def test_recurrence_is_the_references():
    """The op's fallback and perf/reference's scan are two writings of
    the module docstring's recurrence (the reference normalises outside
    and repeats the key heads itself)."""
    q, k, v, g, beta, _ = operands(40)
    qn, kn = L._normalised(q, k, 1e-6)
    want = ref.delta_rule(jnp.repeat(qn, 2, 2), jnp.repeat(kn, 2, 2), v,
                          g, beta)
    np.testing.assert_allclose(
        L.recurrent_gated_delta_rule(q, k, v, g, beta), want,
        rtol=1e-5, atol=1e-6)


def test_strong_decay_and_long_chunks_stay_finite():
    """g down to -21 a position (A = 16, the initialiser's largest):
    exp of the running sum underflows to 0 inside a chunk and the
    masked, positive differences never reach an exp."""
    q, k, v, g, beta, do = operands(128, seed=3)
    g = g * 10.5
    out = L._gated_delta_rule(op_ins(q, k, v, g, beta), {"chunk": 64})
    grads = L._gated_delta_rule_grad(
        {**op_ins(q, k, v, g, beta), "States": out["States"],
         "GRAD::Out": [do]}, {"chunk": 64})
    assert all(bool(jnp.isfinite(x[0]).all())
               for x in [out["Out"], *grads.values()])
    np.testing.assert_allclose(
        out["Out"][0], L.recurrent_gated_delta_rule(q, k, v, g, beta),
        rtol=1e-3, atol=1e-5)


def _layer_program(impl, chunk, amp):
    t, hk, hv, dk, dv = 48, 2, 4, 16, 8
    q, k, v, g, beta, probe = operands(t, 1, hk, hv, dk, dv, seed=5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        def data(name, x):
            var = layers.data(name, shape=list(x.shape), dtype="float32",
                              append_batch_size=False)
            var.stop_gradient = False
            return var

        o = layers.gated_delta_rule(
            data("q", q), data("k", k), data("v", v), data("g", g),
            data("beta", beta), chunk=chunk, impl=impl)
        loss = layers.reduce_sum(layers.elementwise_mul(o, data("p", probe)))
        append_backward(loss)
    main._amp = amp
    feed = dict(q=q, k=k, v=v, g=g, beta=beta, p=probe)
    got = fluid.Executor().run(
        main, feed={n: np.asarray(x) for n, x in feed.items()},
        scope=fluid.Scope(),
        fetch_list=[o] + [f"{n}@GRAD" for n in ("q", "k", "v", "g", "beta")])
    return got


def test_layer_and_grad_op_through_the_program():
    want = _layer_program("recurrent", 1, amp=False)
    got = _layer_program("chunked", 16, amp=False)
    for a, b, name in zip(got, want, ("o", "q", "k", "v", "g", "beta")):
        np.testing.assert_allclose(a, b, err_msg=name, rtol=1e-3, atol=1e-4)


def test_amp_casts_the_operands_and_keeps_the_gates_float32():
    """Under AMP Q, K, V reach the op as bf16 (its matmuls' operands)
    and G, Beta stay float32 (AMP_KEEP_F32_SLOTS); the result is the
    float32 program's to bf16's rounding of sums over a chunk."""
    from paddle_tpu.core import interp

    assert "gated_delta_rule" in interp.AMP_OP_TYPES
    assert {"G", "Beta"} <= interp.AMP_KEEP_F32_SLOTS
    want = _layer_program("chunked", 16, amp=False)
    got = _layer_program("chunked", 16, amp=True)
    assert got[0].dtype == np.dtype("bfloat16") or got[0].dtype.itemsize == 2
    assert got[4].dtype == np.float32 and got[5].dtype == np.float32
    for a, b, name in zip(got, want, ("o", "q", "k", "v", "g", "beta")):
        scale = np.abs(b).max()
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   err_msg=name, rtol=5e-2, atol=3e-2 * scale)


def test_dispatch_counter_names_the_implementation():
    flags.set_flags({"telemetry": True})
    try:
        _layer_program("chunked", 16, amp=False)
        _layer_program("recurrent", 1, amp=False)
        counts = L.dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    shape = "b1 t48 hk2 hv4 dk16 dv8"
    assert counts == {f"chunked fwd {shape} chunk16": 1,
                      f"chunked bwd {shape} chunk16": 1,
                      f"recurrent fwd {shape} chunk1": 1,
                      f"recurrent bwd {shape} chunk1": 1}
    with pytest.raises(ValueError):
        layers.gated_delta_rule(*[None] * 5, impl="quadratic")


def test_causal_conv_sees_no_later_position():
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(2, 12, 6), jnp.float32)
    w = jnp.asarray(r.randn(6, 4), jnp.float32)
    y = L._causal_conv1d({"X": [x], "W": [w]}, {"act": "silu"})["Y"][0]
    np.testing.assert_allclose(
        y, jax.nn.silu(ref.causal_conv(x, w)), rtol=1e-6, atol=1e-6)
    # HF's Conv1d(groups=c, padding=taps - 1)[..., :t], by hand at t = 5
    want5 = sum(w[:, j] * x[:, 2 + j] for j in range(4))
    np.testing.assert_allclose(
        L._causal_conv1d({"X": [x], "W": [w]}, {"act": ""})["Y"][0][:, 5],
        want5, rtol=1e-6, atol=1e-6)
    later = x.at[:, 7:].set(0.0)
    y2 = L._causal_conv1d({"X": [later], "W": [w]}, {"act": "silu"})["Y"][0]
    assert bool((y2[:, :7] == y[:, :7]).all())


def test_gates_and_gated_norm():
    r = np.random.RandomState(2)
    b, a = (jnp.asarray(r.randn(2, 5, 4), jnp.bfloat16) for _ in range(2))
    a_log = jnp.log(jnp.asarray([0.5, 1.0, 4.0, 16.0]))
    dt = jnp.ones(4)
    out = L._gdn_gates({"B": [b], "A": [a], "ALog": [a_log],
                        "DtBias": [dt]}, {})
    beta, g = out["Beta"][0], out["G"][0]
    assert beta.dtype == g.dtype == jnp.float32 and bool((g <= 0).all())
    np.testing.assert_allclose(
        g, -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + 1.0),
        rtol=1e-6)
    x, z = (jnp.asarray(r.randn(3, 8), jnp.float32) for _ in range(2))
    s = jnp.asarray(1 + 0.1 * r.randn(8), jnp.float32)
    y = L._gated_rms_norm({"X": [x], "Z": [z], "Scale": [s]},
                          {"epsilon": 1e-6})["Y"][0]
    want = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
            * s * jax.nn.silu(z))
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)


def test_log_uniform_initializer_and_zero_centred_gain():
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 4
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[5, 64], dtype="float32")
        beta, g = layers.gdn_gates(x, x, ParamAttr(name="a_log"),
                                   ParamAttr(name="dt"))
        y = layers.rms_norm(x, zero_centered=True,
                            param_attr=ParamAttr(name="gain"))
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    a = np.exp(np.asarray(scope.find_var("a_log")))
    assert a.shape == (64,) and (a > 0).all() and (a < 16).all() \
        and a.max() > 8
    assert (np.asarray(scope.find_var("dt")) == 1).all()
    assert (np.asarray(scope.find_var("gain")) == 0).all()
    xv = np.random.RandomState(0).randn(2, 5, 64).astype(np.float32)
    got = exe.run(main, feed={"x": xv}, fetch_list=[y], scope=scope)[0]
    np.testing.assert_allclose(
        got, ref.norm(jnp.asarray(xv), jnp.zeros(64), 1e-5), rtol=1e-5,
        atol=1e-6)
