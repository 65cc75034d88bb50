"""Gradient clipping accounting (paddle_tpu/clip.py): global-norm clip
math against ground truth (triggered vs not), the reported pre/post
norms, the numerics-plane clip instruments, param_list scoping, and the
by-value / by-norm variants — previously untested and metric-less."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import clip as clip_mod
from paddle_tpu import flags, layers, monitor, numerics


@pytest.fixture(autouse=True)
def _clean():
    clip_mod.set_gradient_clip.__globals__["_clip_attr"] = None
    clip_mod.set_gradient_clip.__globals__["_clip_param_names"] = None
    flags.set_flags({"telemetry": False, "numerics": False,
                     "numerics_vars": ""})
    yield
    clip_mod.set_gradient_clip.__globals__["_clip_attr"] = None
    clip_mod.set_gradient_clip.__globals__["_clip_param_names"] = None
    flags.set_flags({"telemetry": False, "numerics": False,
                     "numerics_vars": ""})


def _build_and_run(clip_norm, x_val, lr=1.0):
    """One param w [4] with loss = sum(w * x): grad_w == x exactly, so
    the global norm is ||x|| — ground truth without model noise.
    Returns (w_before, w_after, grad, clip_attr)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        w = layers.create_parameter([4], "float32", name="clip_w")
        loss = layers.reduce_sum(layers.elementwise_mul(x, w))
        attr = clip_mod.GradientClipByGlobalNorm(clip_norm)
        clip_mod.set_gradient_clip(attr)
        fluid.optimizer.SGD(lr).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        before = np.asarray(scope.find_var("clip_w")).copy()
        exe.run(main, feed={"x": x_val[None, :].astype(np.float32)},
                fetch_list=[loss])
        after = np.asarray(scope.find_var("clip_w"))
    return before, after, x_val, attr


def test_global_norm_clip_triggered_scales_to_clip_norm():
    flags.set_flags({"telemetry": True, "numerics": True})
    grad = np.array([3.0, 4.0, 0.0, 0.0])  # ||g|| = 5
    before, after, _g, attr = _build_and_run(clip_norm=2.5, x_val=grad)
    # scale = 2.5 / max(5, 2.5) = 0.5 -> update = g * 0.5
    np.testing.assert_allclose(before - after, grad * 0.5, rtol=1e-5)
    # the in-graph norm/scale vars are registered + exported
    assert attr.global_norm_name is not None
    assert monitor.gauge("pt_grad_global_norm").value() == pytest.approx(
        5.0, rel=1e-5)
    assert monitor.gauge("pt_grad_clip_ratio").value() == pytest.approx(
        0.5, rel=1e-5)
    assert monitor.counter("pt_grad_clips_total").value() == 1
    # post-clip norm = pre * scale = the clip bound
    post = monitor.gauge("pt_grad_global_norm").value() * \
        monitor.gauge("pt_grad_clip_ratio").value()
    assert post == pytest.approx(2.5, rel=1e-5)


def test_global_norm_clip_not_triggered_reports_ratio_one():
    flags.set_flags({"telemetry": True, "numerics": True})
    grad = np.array([3.0, 4.0, 0.0, 0.0])  # ||g|| = 5 < 100
    before, after, _g, _attr = _build_and_run(clip_norm=100.0, x_val=grad)
    np.testing.assert_allclose(before - after, grad, rtol=1e-5)
    assert monitor.gauge("pt_grad_global_norm").value() == pytest.approx(
        5.0, rel=1e-5)
    assert monitor.gauge("pt_grad_clip_ratio").value() == pytest.approx(
        1.0, rel=1e-5)
    assert monitor.counter("pt_grad_clips_total").value() == 0


def test_global_norm_clip_math_without_telemetry():
    """The clip itself never depends on the observability plane."""
    grad = np.array([6.0, 8.0, 0.0, 0.0])  # ||g|| = 10
    before, after, _g, _attr = _build_and_run(clip_norm=5.0, x_val=grad)
    np.testing.assert_allclose(before - after, grad * 0.5, rtol=1e-5)
    assert monitor.counter("pt_grad_clips_total").value() == 0  # tele off


def test_set_gradient_clip_param_list_scopes_clipping():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        wa = layers.create_parameter([4], "float32", name="scoped_a")
        wb = layers.create_parameter([4], "float32", name="scoped_b")
        loss = layers.reduce_sum(
            layers.elementwise_add(layers.elementwise_mul(x, wa),
                                   layers.elementwise_mul(x, wb)))
        clip_mod.set_gradient_clip(
            clip_mod.GradientClipByGlobalNorm(2.5), param_list=["scoped_a"])
        assert clip_mod.clip_applies_to("scoped_a")
        assert not clip_mod.clip_applies_to("scoped_b")
        fluid.optimizer.SGD(1.0).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    grad = np.array([3.0, 4.0, 0.0, 0.0], np.float32)  # per-param ||g||=5
    with fluid.scope_guard(scope):
        exe.run(startup)
        a0 = np.asarray(scope.find_var("scoped_a")).copy()
        b0 = np.asarray(scope.find_var("scoped_b")).copy()
        exe.run(main, feed={"x": grad[None, :]}, fetch_list=[loss])
        a1 = np.asarray(scope.find_var("scoped_a"))
        b1 = np.asarray(scope.find_var("scoped_b"))
    # only scoped_a is clipped (its own norm 5 -> scale 0.5)
    np.testing.assert_allclose(a0 - a1, grad * 0.5, rtol=1e-5)
    np.testing.assert_allclose(b0 - b1, grad, rtol=1e-5)


def test_clip_by_value_and_by_norm_variants():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        w = layers.create_parameter([4], "float32", name="val_w")
        loss = layers.reduce_sum(layers.elementwise_mul(x, w))
        clip_mod.set_gradient_clip(clip_mod.GradientClipByValue(1.0))
        fluid.optimizer.SGD(1.0).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    grad = np.array([3.0, -4.0, 0.5, 0.0], np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.find_var("val_w")).copy()
        exe.run(main, feed={"x": grad[None, :]}, fetch_list=[loss])
        w1 = np.asarray(scope.find_var("val_w"))
    np.testing.assert_allclose(
        w0 - w1, np.clip(grad, -1.0, 1.0), rtol=1e-5)


def test_clip_norm_vars_ride_the_numerics_bundle():
    """With the full pass applied, the clip's norm/scale ride the SAME
    single bundle as the tensor stats (no extra transfers)."""
    flags.set_flags({"telemetry": True, "numerics": True})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        w = layers.create_parameter([4], "float32", name="bundle_w")
        loss = layers.reduce_sum(layers.elementwise_mul(x, w))
        clip_mod.set_gradient_clip(clip_mod.GradientClipByGlobalNorm(2.5))
        fluid.optimizer.SGD(1.0).minimize(loss)
    plan = numerics.instrument(main)
    kinds = [k for k, _v in plan.aux]
    assert "grad_global_norm" in kinds and "grad_clip_scale" in kinds
    assert plan.bundle_size == (
        len(plan.entries) * len(numerics.STAT_FIELDS) + len(plan.aux))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    grad = np.array([3.0, 4.0, 0.0, 0.0], np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": grad[None, :]}, fetch_list=[loss])
    aux = numerics.latest_stats()[main._uid]["aux"]
    assert aux["grad_global_norm"] == pytest.approx(5.0, rel=1e-5)
    assert aux["grad_clip_scale"] == pytest.approx(0.5, rel=1e-5)
