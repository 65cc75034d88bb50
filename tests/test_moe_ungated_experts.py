"""Experts that are not gated units (``layers.topk_moe(gated=False,
act="relu2")``: relu(x WUp)^2 WDown, two matrices an expert, and a
shared expert of the same form): the layer through Program ->
append_backward -> Executor against its mathematics written out, whole
and as a held share, output and every parameter's gradient; the
experts' grouped matmuls at a width off the 128 lanes through the
kernels' interpreter; what the rows counter calls the new passes; and
that a gated layer's program has today's text."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.ops import moe_ops
from paddle_tpu.parallel import grouped_matmul as gm

N, D, F, E, K = 48, 16, 24, 8, 3


def build(held, gated=False, amp=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        kw = (dict(gated=False, act="relu2", shared_act="relu2",
                   shared_gated=False) if not gated else {})
        out, _, _, rows, top_i = layers.topk_moe(
            x, E, K, F, norm_topk_prob=True, name="m", held=held,
            shared_d_ff=2 * F, shared_gate=False, score="sigmoid",
            routed_scale=2.5, select_bias=True, **kw)
        g = layers.data("g", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(out, g))
        grads = append_backward(loss)
    return main, startup, out, top_i, grads


def reference(w, x, held):
    """out [N, D] float32 of the layer above."""
    s = jax.nn.sigmoid(x @ w["m_router.w"])
    _, top_i = jax.lax.top_k(s + w["m_router.bias"], K)
    top_s = jnp.take_along_axis(s, top_i, 1)
    top_w = 2.5 * top_s / (jnp.sum(top_s, 1, keepdims=True) + 1e-20)
    first, count = held or (0, E)
    out = jnp.square(jax.nn.relu(x @ w["m_shared_up.w"])) @ w[
        "m_shared_down.w"]
    for e in range(count):
        y = jnp.square(jax.nn.relu(x @ w["m_up.w"][e])) @ w["m_down.w"][e]
        weight = jnp.sum(jnp.where(top_i == first + e, top_w, 0.0), 1)
        out = out + weight[:, None] * y
    return out


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "held_2_6"])
def test_layer_is_its_mathematics(held):
    main, startup, out, top_i, grads = build(held)
    names = [p.name for p, _ in grads]
    assert sorted(names) == ["m_down.w", "m_router.w", "m_shared_down.w",
                             "m_shared_up.w", "m_up.w"]
    assert not any("gate" in p.name for p in main.all_parameters())
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {"x": r.randn(N, D).astype("float32"),
            "g": r.randn(N, D).astype("float32")}
    block = main.global_block()
    dx = block.var(fluid.framework.grad_var_name("x"))
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[out, dx, *(g for _, g in grads)])
    w = {p.name: jnp.asarray(np.asarray(scope.find_var(p.name)))
         for p in main.all_parameters()}
    count = held[1] if held else E
    assert w["m_up.w"].shape == (count, D, F)
    assert w["m_down.w"].shape == (count, F, D)
    with jax.default_matmul_precision("highest"):
        want = reference(w, jnp.asarray(feed["x"]), held)
        want_g = jax.grad(lambda w, x: jnp.sum(
            reference(w, x, held) * feed["g"]), (0, 1))(
                w, jnp.asarray(feed["x"]))
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want_g[1], rtol=2e-4, atol=1e-5)
    for name, g in zip(names, got[2:]):
        scale = float(np.abs(want_g[0][name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, want_g[0][name], rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=name)


def test_the_new_passes_have_names_of_their_own():
    flags.set_flags({"telemetry": True})
    try:
        monitor.reset()
        main, startup, out, *_ = build((2, 4))
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        exe.run(main, feed={k: np.ones((N, D), np.float32) for k in "xg"},
                scope=scope, fetch_list=[out])
        counts = moe_ops.rows_dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
    mine = {k.rsplit(" ", 2)[0] for k in counts if k.startswith("moe_experts")}
    assert mine == {"moe_experts act windowed",
                    "moe_experts_grad gather_xs windowed",
                    "moe_experts_grad act windowed",
                    "moe_experts_grad act_grad windowed"}, counts


def op_texts(program):
    return [(op.type, sorted(op.inputs), sorted(op.outputs),
             sorted((k, v) for k, v in op.attrs.items()
                    if isinstance(v, (int, float, str, bool))))
            for op in program.global_block().ops]


def test_a_gated_layers_program_is_unchanged():
    """No new attribute, input or output on a gated layer's ops: the
    defaults lower today's programs byte for byte."""
    main, *_ = build((2, 4), gated=True)
    experts = [op for op in main.global_block().ops
               if op.type == "moe_experts"]
    assert len(experts) == 1
    assert sorted(experts[0].inputs) == ["Order", "Rows", "WDown", "WGate",
                                         "WUp", "X", "Xs"]
    assert sorted(experts[0].outputs) == ["Gate", "Up", "Ys"]
    assert "gated" not in experts[0].attrs and "act" not in experts[0].attrs
    assert any(p.name == "m_shared_gate.w" for p in main.all_parameters())
    for wrong in (dict(act="relu2"), dict(gated=False, act="silu"),
                  dict(shared_d_ff=F, shared_act="relu2")):
        with pytest.raises(ValueError):
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                layers.topk_moe(
                    layers.data("y", shape=[N, D], dtype="float32"), E, K,
                    F, **wrong)


# ---------------------------------------------------------------------------
# a width off the 128 lanes through the kernels
# ---------------------------------------------------------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(gm, "_INTERPRET", True)


def test_gmm_tile_at_a_width_off_the_lanes():
    bf, on = jnp.bfloat16, dict(backend="tpu", on_mesh=False)
    # Nemotron-3-Nano's experts: 2688 -> 1856 -> 2688, 8 of 128 held.
    # As a width 1856 is tiled as 1920 would be (three blocks of 640,
    # the last over the edge) under the whole contraction; as a
    # contraction it is taken whole
    m, live = 4096 * 6, 1536
    up = gm.gmm_tile(m, 2688, 1856, 8, bf, live_rows=live, **on)
    down = gm.gmm_tile(m, 1856, 2688, 8, bf, live_rows=live, **on)
    assert up == (128, 2688, 640) and down == (128, 1856, 896)
    # no whole number of half lane tiles: ragged_dot
    assert gm.gmm_tile(m, 2688, 1848, 8, bf, live_rows=live, **on) is None
    # a size on the lanes with a power of two above 128 in it keeps the
    # tiles it had, as a width and as a contraction
    for contraction in (True, False):
        assert gm._width_tiles(2560, contraction) == [2560, 512, 256, 128]
        assert gm._width_tiles(768, contraction) == [768, 256, 128]
        assert gm._width_tiles(2688, contraction) == [2688, 896, 384, 128]
    assert gm._width_tiles(1856, True) == [1856]
    assert gm._width_tiles(1856, False) == [1920, 640, 384, 128]


@pytest.mark.parametrize("k,n", [(256, 192), (192, 256)],
                         ids=["width_off_lanes", "contraction_off_lanes"])
def test_off_lane_products_through_the_kernels(k, n, interpreted):
    """192 = 1.5 x 128, as 1856 = 14.5 x 128: the last lane tile is
    half of one; forward, the rows' gradient and the matrix's against
    ragged_dot's, with rows behind the last group."""
    r = np.random.RandomState(k)
    sizes = jnp.asarray([130, 0, 254, 128], jnp.int32)     # 512 of 768
    m, e, bf = 768, 4, jnp.bfloat16
    lhs = jnp.asarray(r.randn(m, k), bf)
    rhs = jnp.asarray(r.randn(e, k, n) * 0.2, bf)
    g = jnp.asarray(r.randn(m, n), bf)
    tile = gm.gmm_tile(m, k, n, e, bf, live_rows=512)
    dx_tile = gm.gmm_tile(m, n, k, e, bf, live_rows=512)
    # (the width of 192 as one block of 256 that hangs over the edge)
    assert tile == (128, k, 256) and dx_tile == (128, n, 256)
    got = gm.grouped_matmul(lhs, rhs, sizes, live_rows=512)
    dx, dw = gm.grouped_matmul_grads(lhs, rhs, sizes, g, live_rows=512)
    f32 = jnp.float32
    want, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                        lhs.astype(f32), rhs.astype(f32))
    want_dx, want_dw = vjp(g.astype(f32))
    for a, b in ((got, want), (dx, want_dx), (dw, want_dw)):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= 2e-2 * np.abs(b).max()
    assert not np.asarray(got, np.float32)[512:].any()
    assert not np.asarray(dx, np.float32)[512:].any()
