"""The decoder-only MoE block (models/olmoe.py) and the ops it brought
(rms_norm, rotary_embedding, the four top-k MoE ops) against the plain
float32 reference (perf/reference/olmoe.py), forward and gradient, at
tiny sizes on the CPU. Gradients of the reference are ``jax.grad`` of
its functions; the program's come from ``append_backward``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from model_test import snapshot
from paddle_tpu import analysis, layers
from paddle_tpu.backward import append_backward
from paddle_tpu.models import olmoe as M
from paddle_tpu.param_attr import ParamAttr
from perf.reference import olmoe as ref

# float32 on both sides, the same mathematics in another order of
# operations (sorted groups against a dense loop, fused qkv): rounding
# differs in the last bits of sums over 16..64 terms
TOL = dict(rtol=2e-5, atol=2e-6)


def run_graph(build, feed, seed=3):
    """(values fetched, {param: value}, {param: gradient}) of a graph
    built by ``build() -> (loss, [fetches])`` in float32."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        loss, fetches = build()
        grads = append_backward(loss)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    w = snapshot(scope)
    out = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[loss, *fetches, *(g for _, g in grads)])
    n = 1 + len(fetches)
    return (out[:n], w, {p.name: g for (p, _), g in zip(grads, out[n:])})


def data(name, shape, dtype="float32"):
    v = layers.data(name, shape=list(shape), dtype=dtype,
                    append_batch_size=False)
    v.stop_gradient = False
    return v


def test_rms_norm_forward_and_gradient():
    r = np.random.RandomState(0)
    x = r.randn(3, 5, 16).astype(np.float32)
    probe = r.randn(3, 5, 16).astype(np.float32)

    def build():
        xv = data("x", x.shape)
        y = layers.rms_norm(xv, epsilon=1e-5,
                            param_attr=ParamAttr(
                                name="g", initializer=fluid.initializer
                                .NormalInitializer(1.0, 0.3)))
        loss = layers.reduce_sum(layers.elementwise_mul(y, data("p", x.shape)))
        return loss, [y, "x@GRAD"]

    (loss, y, gx), w, g = run_graph(build, {"x": x, "p": probe})
    want = ref.rms_norm(jnp.asarray(x), w["g"], 1e-5)
    np.testing.assert_allclose(y, want, **TOL)
    gg = jax.grad(lambda gain: jnp.sum(
        ref.rms_norm(jnp.asarray(x), gain, 1e-5) * probe))(jnp.asarray(w["g"]))
    np.testing.assert_allclose(g["g"], gg, **TOL)
    np.testing.assert_allclose(gx, jax.grad(lambda z: jnp.sum(
        ref.rms_norm(z, w["g"], 1e-5) * probe))(jnp.asarray(x)), **TOL)


def test_rms_norm_keeps_bf16_streams_and_f32_statistics():
    from paddle_tpu.core.registry import get_op_def

    x = (np.random.RandomState(1).randn(4, 256) * 30).astype(np.float32)
    gain = np.ones(256, np.float32)
    y = get_op_def("rms_norm").compute(
        {"X": [jnp.asarray(x, jnp.bfloat16)], "Scale": [jnp.asarray(gain)]},
        {"epsilon": 1e-5})["Y"][0]
    assert y.dtype == jnp.bfloat16
    want = ref.rms_norm(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                        gain, 1e-5)
    # one rounding to bf16 of an f32 result (2**-8 relative), not a
    # mean of 256 squares accumulated in bf16
    np.testing.assert_allclose(np.asarray(y, np.float32), want, rtol=2 ** -7)


def test_rotary_embedding_forward_and_gradient():
    r = np.random.RandomState(2)
    q = r.randn(2, 3, 12, 8).astype(np.float32)
    k = r.randn(2, 3, 12, 8).astype(np.float32)
    pq, pk = r.randn(*q.shape).astype(np.float32), \
        r.randn(*k.shape).astype(np.float32)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        qv, kv = data("q", q.shape), data("k", k.shape)
        qo, ko = layers.rotary_embedding(qv, kv, theta=10000.0)
        loss = layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(qo, data("pq", q.shape))),
            layers.reduce_sum(layers.elementwise_mul(ko, data("pk", k.shape))))
        append_backward(loss)
    out = fluid.Executor().run(
        main, feed={"q": q, "k": k, "pq": pq, "pk": pk},
        fetch_list=[qo, ko, "q@GRAD", "k@GRAD"], scope=fluid.Scope())
    np.testing.assert_allclose(out[0], ref.rope(jnp.asarray(q), 10000.0), **TOL)
    np.testing.assert_allclose(out[1], ref.rope(jnp.asarray(k), 10000.0), **TOL)
    gq = jax.grad(lambda z: jnp.sum(ref.rope(z, 10000.0) * pq))(jnp.asarray(q))
    gk = jax.grad(lambda z: jnp.sum(ref.rope(z, 10000.0) * pk))(jnp.asarray(k))
    np.testing.assert_allclose(out[2], gq, **TOL)
    np.testing.assert_allclose(out[3], gk, **TOL)
    # position 0 is not turned, and a turn keeps a pair's length
    np.testing.assert_allclose(out[0][:, :, 0], q[:, :, 0], **TOL)
    np.testing.assert_allclose(np.linalg.norm(out[0], axis=-1),
                               np.linalg.norm(q, axis=-1), rtol=1e-5)


N, D, F, E, K = 48, 16, 8, 8, 2


def ref_moe_loss(x, w, probe):
    out, _, lb, z = ref.moe(x, w["m_router.w"], w["m_gate.w"], w["m_up.w"],
                            w["m_down.w"], K)
    return jnp.sum(out * probe) + 0.3 * lb + 0.2 * z


def test_topk_moe_forward_and_gradient_against_the_dense_reference():
    r = np.random.RandomState(4)
    x = r.randn(N, D).astype(np.float32)
    probe = r.randn(N, D).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        xv = data("x", x.shape)
        out, lb, z, rows, top_i = layers.topk_moe(xv, E, K, F, name="m")
        loss = layers.sums([
            layers.reduce_sum(layers.elementwise_mul(out, data("p", x.shape))),
            layers.scale(lb, scale=0.3), layers.scale(z, scale=0.2)])
        grads = append_backward(loss)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    # (a wider router than the 0.02 initialisation: weights that differ)
    scope.set("m_router.w", jnp.asarray(
        r.randn(D, E).astype(np.float32)))
    w = snapshot(scope)
    got = exe.run(main, feed={"x": x, "p": probe}, scope=scope,
                  fetch_list=[loss, out, lb, z, rows, top_i, "x@GRAD",
                              *(g for _, g in grads)])
    want_out, want_i, want_lb, want_z = ref.moe(
        jnp.asarray(x), w["m_router.w"], w["m_gate.w"], w["m_up.w"],
        w["m_down.w"], K)
    np.testing.assert_allclose(got[1], want_out, **TOL)
    np.testing.assert_allclose(got[2], want_lb, rtol=1e-6)
    np.testing.assert_allclose(got[3], want_z, rtol=1e-6)
    assert (np.sort(got[5], -1) == np.sort(np.asarray(want_i), -1)).all()
    assert got[4].sum() == N * K
    assert (got[4] == np.bincount(got[5].ravel(), minlength=E)).all()
    gx, gw = jax.grad(ref_moe_loss, argnums=(0, 1))(jnp.asarray(x), w, probe)
    np.testing.assert_allclose(got[6], gx, **TOL)
    for (p, _), g in zip(grads, got[7:]):
        np.testing.assert_allclose(g, gw[p.name], err_msg=p.name, **TOL)
    assert {p.name for p, _ in grads} == {
        "m_router.w", "m_gate.w", "m_up.w", "m_down.w"}


def test_every_chosen_pair_contributes_under_skewed_routing():
    # one expert takes EVERY token's first choice and one more every
    # second choice: 48 rows each where an even load is 12. A
    # capacity of 1.25 x 12 = 15 would drop 33 of them. Nothing is
    # dropped or padded: the output is the dense reference's, and
    # removing any one pair changes it.
    r = np.random.RandomState(6)
    x = np.abs(r.randn(N, D)).astype(np.float32) + 0.5
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        xv = data("x", x.shape)
        out, lb, z, rows, top_i = layers.topk_moe(xv, E, K, F, name="m")
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    wr = r.randn(D, E).astype(np.float32) * 0.01
    wr[:, 3] += 0.08     # x > 0: expert 3 wins every token
    wr[:, 5] += 0.04     # and expert 5 comes second
    scope.set("m_router.w", jnp.asarray(wr))
    # (experts that differ by more than the 0.02 initialisation)
    for n in ("m_gate.w", "m_up.w", "m_down.w"):
        scope.set(n, jnp.asarray(
            r.randn(*np.shape(scope.find_var(n))).astype(np.float32) * 0.3))
    w = snapshot(scope)
    got_out, got_rows, got_i = exe.run(
        main, feed={"x": x}, fetch_list=[out, rows, top_i], scope=scope)
    assert got_rows[3] == N and got_rows[5] == N and got_rows.sum() == N * K
    want, _, _, _ = ref.moe(jnp.asarray(x), w["m_router.w"], w["m_gate.w"],
                            w["m_up.w"], w["m_down.w"], K)
    np.testing.assert_allclose(got_out, want, **TOL)
    # each of the 96 pairs carries weight: the output of a token is not
    # what either of its experts gives alone
    for e in (3, 5):
        alone = (jax.nn.silu(x @ w["m_gate.w"][e]) * (x @ w["m_up.w"][e])) \
            @ w["m_down.w"][e]
        p = jax.nn.softmax(jnp.asarray(x) @ w["m_router.w"], -1)[:, e:e + 1]
        assert np.abs(got_out - p * alone).max(-1).min() > 1e-3


TINY = dict(vocab_size=50, hidden_size=32, intermediate_size=16,
            num_hidden_layers=2, num_attention_heads=4, num_experts=8,
            num_experts_per_tok=2)
REF_CFG = dict(TINY, norm_topk_prob=False, rms_norm_eps=1e-5,
               rope_theta=10000.0)


def test_model_loss_logits_and_a_gradient_of_every_kind():
    cfg = M.OlmoeConfig(**TINY)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        grads = append_backward(model["loss"])
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(12)
    for n in scope.var_names():   # gains and routers that differ from 1 / 0.02
        if n.endswith(".scale"):
            scope.set(n, jnp.asarray(
                1 + 0.2 * r.randn(*np.shape(scope.find_var(n))), jnp.float32))
        if n.endswith("_router.w"):
            scope.set(n, jnp.asarray(
                r.randn(*np.shape(scope.find_var(n))), jnp.float32))
    w = snapshot(scope)
    names = [p.name for p, _ in grads]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["last_logits"], model["lb_loss"],
        model["z_loss"], *model["top_i"], *(g for _, g in grads)])
    # (op by op: one fused computation rounds the table's gradient 2e-8
    # off the tolerance below)
    want = ref.forward(w, REF_CFG, feed["input_ids"], last=M.LAST_POSITIONS)
    want_loss, want_g = jax.value_and_grad(
        lambda w_: ref.loss(w_, REF_CFG, feed))(w)
    np.testing.assert_allclose(got[0], want_loss, rtol=1e-6)
    np.testing.assert_allclose(got[1], want["logits"], **TOL)
    np.testing.assert_allclose(got[2], want["lb"], rtol=1e-6)
    np.testing.assert_allclose(got[3], want["z"], rtol=1e-6)
    for layer in range(2):
        assert (np.sort(got[4 + layer], -1)
                == np.sort(np.asarray(want["top_i"][layer]), -1)).all()
    g = dict(zip(names, got[6:]))
    kinds = ["olmoe_tok_emb.w", "lm_head_colp.w", "final_norm.scale"]
    for i in range(2):
        kinds += [f"blk{i}_{s}" for s in (
            "attn_norm.scale", "attn_qkv_colp.w", "attn_qnorm.scale",
            "attn_knorm.scale", "attn_out_rowp.w", "moe_norm.scale",
            "moe_router.w", "moe_gate.w", "moe_up.w", "moe_down.w")]
    assert sorted(names) == sorted(kinds)
    for n in names:
        # the loss is a mean over 32 positions at ln(50): gradients of
        # 1e-6..1e-2, so the absolute part is tighter than TOL's
        np.testing.assert_allclose(g[n], want_g[n], rtol=5e-5, atol=1e-8,
                                   err_msg=n)


def test_model_trains_under_amp_with_the_router_in_float32():
    cfg = M.OlmoeConfig(**dict(TINY, num_hidden_layers=1))
    feed = M.make_batch(cfg, 4, 16, seed=1)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 2
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        evalp = main.clone(for_test=True)
        fluid.optimizer.Adam(3e-3).minimize(model["loss"])
    main._amp = evalp._amp = True
    # the zoo's programs lint clean under defaults (tests/test_analysis.py)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    losses = [float(exe.run(main, feed=feed, fetch_list=[model["loss"]],
                            scope=scope)[0]) for _ in range(30)]
    assert losses[-1] < losses[0] - 0.5 and np.isfinite(losses).all()
    # the routing of the eval clone under bf16 AMP is the float32
    # reference's on the same bf16 stream: what the router sees is
    # rounded, how it decides is not
    router = next(op for op in evalp.global_block().ops
                  if op.type == "moe_router")
    top_w, top_i = exe.run(evalp, feed=feed, scope=scope, fetch_list=[
        router.output("TopW")[0], model["top_i"][0]])
    assert top_w.dtype == np.float32 and top_i.dtype == np.int32
    assert top_w.shape == (64, 2) and (top_w[:, 0] >= top_w[:, 1]).all()


def test_expert_rows_reach_the_monitor_with_telemetry_on():
    from paddle_tpu import flags, monitor
    from paddle_tpu.ops import moe_ops

    rows = np.array([5, 0, 11, 0], np.int32)
    moe_ops.record_expert_rows("blk0", rows)       # telemetry off: nothing
    c = monitor.counter("pt_moe_rows_total")
    assert c.value(labels={"layer": "blk0", "expert": "2"}) == 0
    flags.set_flags({"telemetry": True})
    try:
        moe_ops.record_expert_rows("blk0", rows)
        moe_ops.record_expert_rows("blk0", rows)
        assert c.value(labels={"layer": "blk0", "expert": "2"}) == 22
        assert c.value(labels={"layer": "blk0", "expert": "0"}) == 10
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


@pytest.mark.parametrize("h,dh,want", [
    # where all heads at blocks of 256 pass a VMEM cap (PR 28: 16 x 128
    # overflowed the dk/dv kernel), the heads go onto the grid and the
    # blocks grow (PR 29); tests/test_flash_attention.py has the table
    (8, 64, (1, 512, 512)),
    (16, 128, (1, 512, 512)),     # OLMoE
    (2, 64, (2, 256, 256)),       # no cap reached: all heads in a step
])
def test_bhtd_tile_counts_the_head_width(h, dh, want):
    from paddle_tpu.parallel import flash_attention as fa

    assert fa._pick_tile(h, 4096, 4096, None, None, dh) == want


def test_topk_moe_kernel_path_agrees_with_the_ragged_dot_path(monkeypatch):
    """The layer under bf16 AMP at a size ``gmm_tile`` takes (512 rows
    over 4 experts of 128 x 128): forward and every gradient through the
    interpreted ``moe.*`` kernels against the same program through
    ``jax.lax.ragged_dot``. Both accumulate in float32 and round the
    result to bf16 once; the order of the sums differs. And the counter
    names the tile of each of the nine calls, with telemetry on only."""
    from paddle_tpu import flags, monitor
    from paddle_tpu.parallel import grouped_matmul as gm

    n, d, f, e, k = 256, 128, 128, 4, 2
    r = np.random.RandomState(11)
    x = r.randn(n, d).astype(np.float32)
    probe = r.randn(n, d).astype(np.float32)
    wr = r.randn(d, e).astype(np.float32) * 0.3

    def run():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            xv = data("x", x.shape)
            out, lb, z, rows, _ = layers.topk_moe(xv, e, k, f, name="m")
            loss = layers.reduce_sum(
                layers.elementwise_mul(out, data("p", x.shape)))
            grads = append_backward(loss)
        main._amp = True
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        scope.set("m_router.w", jnp.asarray(wr))
        for name in ("m_gate.w", "m_up.w", "m_down.w"):
            scope.set(name, jnp.asarray(r2.randn(
                *np.shape(scope.find_var(name))).astype(np.float32) * 0.1))
        got = exe.run(main, feed={"x": x, "p": probe}, scope=scope,
                      fetch_list=[out, rows, "x@GRAD",
                                  *(g for _, g in grads)])
        return got, [p.name for p, _ in grads]

    r2 = np.random.RandomState(12)
    want, names = run()
    assert gm.gmm_dispatch_counts() == {}          # telemetry off
    monkeypatch.setattr(gm, "_INTERPRET", True)
    flags.set_flags({"telemetry": True})
    try:
        r2 = np.random.RandomState(12)
        got, _ = run()
        counts = gm.gmm_dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    tiles = {"m512 k128 n128 e4": "[tm128 tk128 tn128]"}
    assert counts == {f"{p} {s} {t}": 3 for s, t in tiles.items()
                      for p in ("fwd", "bwd_dx", "bwd_dw")}
    assert (got[1] == want[1]).all() and got[1].sum() == n * k
    # outputs of 0.1..1 rounded to bf16 (2**-8); a gradient sums up to
    # 512 such products
    np.testing.assert_allclose(got[0], want[0], rtol=2e-2, atol=2e-2)
    for name, a, b in zip(["x", *names], got[2:], want[2:]):
        scale = np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2 * scale,
                                   err_msg=name)
