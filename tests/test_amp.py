"""bf16 AMP correctness: activation stream runs in bf16, master weights
stay f32, and the loss trajectory tracks the f32 run.

Covers the trace-time cast policy in core/lowering.py (AMP_OP_TYPES /
AMP_FLOW_OP_TYPES) that otherwise only executes on the TPU bench host.
"""

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.models import transformer as T


CFG = T.TransformerConfig(
    src_vocab_size=64, trg_vocab_size=64, d_model=32, d_inner=64,
    n_head=4, n_layer=2, max_length=32, dropout=0.0,
)


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = T.build(CFG, is_test=False)
        fluid.optimizer.Adam(1e-3).minimize(model["loss"])
    return main, startup, model


def _run(amp, n_steps=4):
    main, startup, model = _build()
    main._amp = amp
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    losses = []
    for i in range(n_steps):
        feed = T.make_batch(CFG, batch=8, src_len=16, trg_len=16, seed=i)
        out = exe.run(main, feed=feed, fetch_list=[model["loss"]],
                      scope=scope)
        losses.append(float(out[0]))
    return losses, scope, main, model


import pytest


@pytest.fixture(scope="module")
def amp_run():
    # one bf16 compile+run shared by the trajectory and master-weight
    # tests (each _run costs a full transformer compile on the CPU
    # backend)
    return _run(amp=True)


def test_amp_loss_tracks_f32(amp_run):
    f32, _, _, _ = _run(amp=False)
    bf16, _, _, _ = amp_run
    assert all(np.isfinite(bf16)), bf16
    # same trajectory within bf16 noise
    np.testing.assert_allclose(f32, bf16, rtol=0.05, atol=0.05)
    assert bf16[-1] < bf16[0]  # still learning


def test_amp_master_weights_stay_f32(amp_run):
    _, scope, main, _ = amp_run
    for p in main.all_parameters():
        v = scope.find_var(p.name)
        assert v is not None
        assert jnp.asarray(v).dtype == jnp.float32, p.name


def test_amp_stream_is_bf16():
    """The lowered computation must actually contain bf16 matmuls — guards
    against a flow op silently promoting the stream back to f32."""
    main, startup, model = _build()
    main._amp = True
    feed = T.make_batch(CFG, batch=8, src_len=16, trg_len=16, seed=0)
    feed_names = sorted(feed.keys())
    lowered = lowering.lower_block(main, 0, feed_names, [model["loss"].name])
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    state = {n: np.asarray(scope.find_var(n)) for n in lowered.state_in_names}
    jaxpr = jax.make_jaxpr(lowered.fn)(state, feed, jax.random.PRNGKey(0))
    text = str(jaxpr)
    # bf16 dot_generals present (the activation stream), f32 params in state
    assert "bf16" in text
    n_bf16_dots = text.count("preferred_element_type=bfloat16")
    n_dots = text.count("dot_general")
    assert n_dots > 0
    # the bulk of matmuls consume/produce bf16: look for bf16 dot operands
    assert text.count(":bf16") > 50, "bf16 stream missing from lowered jaxpr"


# --------------------------------------------------------------------------
# dynamic loss scaling: the first direct tests of the grow/shrink/skip
# state machine (amp.decorate(use_dynamic_loss_scaling=True) compiles it
# in-graph; these drive it through the Executor step by step)
# --------------------------------------------------------------------------

from paddle_tpu import amp, flags, layers, monitor


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags({"telemetry": False, "numerics": False})
    yield
    flags.set_flags({"telemetry": False, "numerics": False})


def _scaler_setup(init_scale, incr_every_n=1000, decr_every_n=1,
                  incr_ratio=2.0, decr_ratio=0.5):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, 2, bias_attr=False))
        opt = amp.decorate(
            fluid.optimizer.SGD(0.1), init_loss_scaling=init_scale,
            use_dynamic_loss_scaling=True,
            incr_every_n_steps=incr_every_n,
            decr_every_n_nan_or_inf=decr_every_n,
            incr_ratio=incr_ratio, decr_ratio=decr_ratio)
        opt.minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, loss, opt, scope, exe


def _scale(scope, opt):
    return float(np.asarray(scope.find_var(opt.loss_scaling_name))[0])


_OK_FEED = {"x": np.ones((2, 4), np.float32)}
# scaled by >=1e30 the fc gradients overflow f32
_HUGE_FEED = {"x": np.full((2, 4), 1e10, np.float32)}


def test_loss_scale_grows_after_n_good_steps():
    main, loss, opt, scope, exe = _scaler_setup(
        init_scale=4.0, incr_every_n=2)
    with fluid.scope_guard(scope):
        scales = []
        for _ in range(5):
            exe.run(main, feed=_OK_FEED, fetch_list=[loss])
            scales.append(_scale(scope, opt))
    # grows 2x on every 2nd clean step, counter resets after each growth
    assert scales == [4.0, 8.0, 8.0, 16.0, 16.0]


def test_overflow_skips_update_and_shrinks_scale():
    main, loss, opt, scope, exe = _scaler_setup(init_scale=1e30)
    pname = main.all_parameters()[0].name
    with fluid.scope_guard(scope):
        before = np.asarray(scope.find_var(pname)).copy()
        out = exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])
        after = np.asarray(scope.find_var(pname))
        # the skip contract: parameters bit-unchanged on overflow, the
        # (unscaled) loss fetch itself stays finite
        np.testing.assert_array_equal(before, after)
        assert np.isfinite(out[0]).all()
        assert _scale(scope, opt) == pytest.approx(5e29)
        # recovery: the next finite step updates normally
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])
        assert not np.array_equal(after,
                                  np.asarray(scope.find_var(pname)))


def test_overflow_resets_growth_counter():
    main, loss, opt, scope, exe = _scaler_setup(
        init_scale=1e30, incr_every_n=2)
    with fluid.scope_guard(scope):
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])   # good: 1
        exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])  # overflow
        s_after_bad = _scale(scope, opt)
        assert s_after_bad == pytest.approx(5e29)
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])   # good: 1 again
        assert _scale(scope, opt) == pytest.approx(s_after_bad)
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])   # good: 2 -> grow
        assert _scale(scope, opt) == pytest.approx(s_after_bad * 2)


def test_decr_every_n_requires_consecutive_overflows():
    main, loss, opt, scope, exe = _scaler_setup(
        init_scale=1e30, decr_every_n=2)
    with fluid.scope_guard(scope):
        exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])  # bad: 1
        assert _scale(scope, opt) == pytest.approx(1e30)  # not yet
        exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])  # bad: 2
        assert _scale(scope, opt) == pytest.approx(5e29)


def test_overflow_skip_counter_and_scale_gauge_exported():
    flags.set_flags({"telemetry": True, "numerics": True})
    main, loss, opt, scope, exe = _scaler_setup(init_scale=1e30)
    with fluid.scope_guard(scope):
        exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])
    assert monitor.counter("pt_amp_overflow_skips_total").value() == 1
    assert monitor.gauge("pt_amp_loss_scale").value() == pytest.approx(
        5e29)
    # the step log carries the aux values too
    rec = monitor.recent_steps()[-1]
    assert rec["numerics"]["aux"]["amp_loss_scale"] == pytest.approx(5e29)
    assert rec["numerics"]["aux"]["amp_found_inf"] == 0.0


def test_skip_counter_exact_under_sampled_decode():
    """The skip count rides a cumulative in-graph var decoded as deltas,
    so overflows on UNSAMPLED steps still reach the counter."""
    flags.set_flags({"telemetry": True, "numerics": True,
                     "numerics_every_n_steps": 4})
    main, loss, opt, scope, exe = _scaler_setup(init_scale=1e30)
    with fluid.scope_guard(scope):
        # steps 1..3 (none lands on the every-4 sampling grid): two
        # overflows happen entirely between decodes
        exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])
        exe.run(main, feed=_HUGE_FEED, fetch_list=[loss])
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])
        assert monitor.counter(
            "pt_amp_overflow_skips_total").value() == 0  # not decoded yet
        exe.run(main, feed=_OK_FEED, fetch_list=[loss])  # step 4: decode
    assert monitor.counter("pt_amp_overflow_skips_total").value() == 2
    flags.set_flags({"numerics_every_n_steps": 1})


def test_scale_growth_guarded_against_f32_overflow():
    """A scale whose next doubling would overflow f32 must stay put —
    an inf scale would flag every later step as overflow and silently
    freeze training forever."""
    main, loss, opt, scope, exe = _scaler_setup(
        init_scale=1e38, incr_every_n=1)
    pname = main.all_parameters()[0].name
    # small activations keep the scaled loss/grads finite even at the
    # clamp, so only the growth guard is exercised
    tiny = {"x": np.full((2, 4), 1e-3, np.float32)}
    with fluid.scope_guard(scope):
        for _ in range(4):  # 2e38 is representable; 4e38 is not
            exe.run(main, feed=tiny, fetch_list=[loss])
        assert _scale(scope, opt) == pytest.approx(2e38, rel=1e-6)
        assert np.isfinite(_scale(scope, opt))
        # training still updates parameters at the clamped scale
        before = np.asarray(scope.find_var(pname)).copy()
        exe.run(main, feed=tiny, fetch_list=[loss])
        assert not np.array_equal(before,
                                  np.asarray(scope.find_var(pname)))


def test_dynamic_decorate_rejects_split_apply_gradients():
    opt = amp.decorate(fluid.optimizer.SGD(0.1),
                       use_dynamic_loss_scaling=True)
    with pytest.raises(RuntimeError, match="minimize"):
        opt.apply_gradients([])


def test_static_decorate_still_marks_amp_only():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, 2))
        amp.decorate(fluid.optimizer.SGD(0.1)).minimize(loss)
    assert main._amp
    # no scaling machinery was built
    assert not hasattr(main, "_amp_scale_vars")
    assert not any(op.type == "isfinite"
                   for op in main.global_block().ops)
