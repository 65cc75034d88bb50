"""The window / full hybrid decoder with a head count a layer kind
(models/laguna.py: window layers of more query heads than the full
layers', plain rotary positions beside yarn over half a head, a sigmoid
gate a head, a dense layer 0 in front of sigmoid-routed held experts
beside a shared one) against the plain float32 reference
(perf/reference/laguna.py, the file the benchmark's ``correct`` is
decided by), forward and gradient, at tiny sizes on the CPU; yarn in the
rotary op against the formula by hand; the per-head gate's gradient; the
controls that a reference without the gate, the window or yarn is
another model; the expert layer as one chip's share. Gradients of the
reference are ``jax.grad`` of its functions; the program's come from
``append_backward``."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, highest, moved, reference, snapshot
from paddle_tpu import analysis, flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.models import laguna as M
from paddle_tpu.ops import attention_ops as ao
from paddle_tpu.parallel import rope
from perf.reference import laguna as ref

# 16 positions, a window of 5; 3 and 4 query heads a key/value head;
# yarn over 8 of a head's 16 features, its ramp 0, 0.2, 0.4, 0.6
ROPE = {
    "full_attention": {
        "rope_theta": 100, "rope_type": "yarn", "factor": 4,
        "original_max_position_embeddings": 8, "beta_slow": 0.01,
        "beta_fast": 1, "attention_factor": 1.1386294361119891,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
TINY = dict(vocab_size=50, hidden_size=32, intermediate_size=64,
            num_hidden_layers=5, num_attention_heads=6,
            num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
            num_experts_per_tok=3, moe_intermediate_size=16,
            shared_expert_intermediate_size=16,
            moe_routed_scaling_factor=2.5, sliding_window=5,
            rope_parameters=ROPE,
            layer_types=["full_attention"] + ["sliding_attention"] * 3
            + ["full_attention"],
            mlp_layer_types=["dense"] + ["sparse"] * 4,
            num_attention_heads_per_layer=[6, 8, 8, 8, 6])
# experts 2..5 of the 16 the router scores are this chip's
HELD = (2, 4)
REF_CFG = dict(TINY, num_experts=HELD[1], held_first=HELD[0],
               router_experts=16)
ATTN = ["attn_norm.scale", "attn_qkvg_colp.w", "attn_out_rowp.w"]
DENSE = ATTN + ["mlp_norm.scale", "mlp_gate_colp.w", "mlp_up_colp.w",
                "mlp_down_rowp.w"]
SPARSE = ATTN + ["moe_norm.scale", "moe_router.w", "moe_gate.w", "moe_up.w",
                 "moe_down.w", "moe_shared_gate.w", "moe_shared_up.w",
                 "moe_shared_down.w"]
YARN = rope.Yarn(64.0, 4096.0, 64.0, 1.0, 1.4158883083359672)


# gains and routers away from their initial 1 / 0.02, so that every
# parameter matters and the routing has no near-ties; the attention
# projections larger, so that what a query sees (and how its head is
# gated) moves its output
PERTURB = [((".scale",), moved(0.2)), (("_router.w",), drawn()),
           (("_attn_qkvg_colp.w",), drawn(0.3))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, optimizer=None, **overrides):
    cfg = M.LagunaConfig(**dict(TINY, **overrides), num_experts=16,
                         held_experts=HELD)
    return (cfg, *model_test.built(M, cfg, seed, optimizer))


@functools.cache
def run_against_reference():
    """The one run the gradient test and the three controls read: the
    program built at seed 11 on the batch of seed 9, and the reference
    on the same weights."""
    cfg, main, startup, model, grads = built(11)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["logits"], model["lb_loss"], *model["top_i"],
        *model["expert_rows"], *(g for _, g in grads)])
    return (grads, feed, w, got, *reference(ref, w, REF_CFG, feed))


# --- the model against the reference ---------------------------------------


def test_model_loss_logits_and_every_parameters_gradient():
    grads, _, w, got, want, want_loss, want_g = run_against_reference()
    names = [p.name for p, _ in grads]
    # float32 on both sides; the same mathematics in another order
    np.testing.assert_allclose(got[0], want_loss, rtol=2e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[2], want["lb"], rtol=1e-6)
    pairs = 0
    for layer in range(4):      # the four expert layers; layer 0 is dense
        top_i = np.asarray(want["top_i"][layer])
        assert (np.sort(got[3 + layer], -1) == np.sort(top_i, -1)).all()
        rows = got[7 + layer]
        assert rows.shape == (4,)
        assert (rows == [(top_i == HELD[0] + e).sum() for e in range(4)]).all()
        pairs += rows.sum()
    assert 0 < pairs < 4 * 32 * 3           # a share: some pairs, not all
    kinds = ["laguna_tok_emb.w", "lm_head_colp.w", "final_norm.scale"]
    kinds += [f"blk0_{s}" for s in DENSE]
    kinds += [f"blk{i}_{s}" for i in range(1, 5) for s in SPARSE]
    assert sorted(names) == sorted(kinds)
    assert w["blk1_moe_gate.w"].shape == (4, 32, 16)      # held, not 16
    assert w["blk1_moe_router.w"].shape == (32, 16)       # scored: all
    # a layer's q|k|v|g and o at ITS head count: 6 heads of 16 in a full
    # layer, 8 in a window layer, 2 key/value heads, a gate column a head
    assert w["blk0_attn_qkvg_colp.w"].shape == (32, (6 + 4) * 16 + 6)
    assert w["blk1_attn_qkvg_colp.w"].shape == (32, (8 + 4) * 16 + 8)
    assert w["blk4_attn_qkvg_colp.w"].shape == (32, (6 + 4) * 16 + 6)
    assert w["blk0_attn_out_rowp.w"].shape == (96, 32)
    assert w["blk2_attn_out_rowp.w"].shape == (128, 32)
    g = dict(zip(names, got[11:]))
    for n in names:     # q|k|v|g, Wo, router, held experts, shared, dense
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=2e-3,
                                   atol=1e-4 * scale + 1e-9, err_msg=n)
    # the gate's columns (the last h of q|k|v|g) take a gradient of
    # their own, a value a head
    for i, h in ((0, 6), (1, 8)):
        gate_g = g[f"blk{i}_attn_qkvg_colp.w"][:, -h:]
        assert np.abs(gate_g).max() > 1e-3 * np.abs(
            g[f"blk{i}_attn_qkvg_colp.w"]).max()


def test_ops_of_a_layer_by_kind():
    """Every layer rotates: the full layers half a head under yarn, the
    window layers the whole head plainly; the sdpa op carries the window
    under ``swa`` and none under ``core``, at the layer's own heads; the
    gate is a cast, a sigmoid and a product under ``gate``; layer 0 has
    a dense MLP and no router."""
    _, main, _, _, _ = built(3)
    ops = main.global_block().ops
    fwd = [op for op in ops if op.role != "backward"
           and not op.type.endswith("_grad")]
    rot = {op.namescope: op.attrs for op in fwd
           if op.type == "rotary_embedding"}
    assert sorted(rot) == [f"blk{i}/attn/rope" for i in range(5)]
    for i in (0, 4):
        attrs = rot[f"blk{i}/attn/rope"]
        assert attrs["rotary_dim"] == 8 and attrs["theta"] == 100.0
        assert (attrs["yarn_factor"], attrs["yarn_original_length"],
                attrs["yarn_beta_fast"], attrs["yarn_beta_slow"]) \
            == (4.0, 8.0, 1.0, 0.01)
        assert attrs["yarn_attention_factor"] == pytest.approx(
            0.1 * math.log(4) + 1)
    for i in (1, 2, 3):
        attrs = rot[f"blk{i}/attn/rope"]
        assert "rotary_dim" not in attrs and "yarn_factor" not in attrs
        assert attrs["theta"] == 10000.0
    sdpa = [(op.namescope, op.attrs.get("window"),
             main.global_block().var(op.inputs["Q"][0]).shape[1])
            for op in fwd if op.type == "scaled_dot_product_attention"]
    assert sdpa == [("blk0/attn/core", None, 6), ("blk1/attn/swa", 5, 8),
                    ("blk2/attn/swa", 5, 8), ("blk3/attn/swa", 5, 8),
                    ("blk4/attn/core", None, 6)]
    for i in range(5):
        gate = [op.type for op in fwd if op.namescope == f"blk{i}/attn/gate"]
        assert gate.count("sigmoid") == 1 and gate.count("cast") == 1
        assert gate.count("elementwise_mul") == 1
        routers = [op for op in fwd if op.type == "moe_router"
                   and op.namescope == f"blk{i}/moe/router"]
        dense = [op for op in fwd if op.namescope == f"blk{i}/mlp"
                 and op.type == "mul"]
        assert (len(routers), len(dense)) == ((0, 3) if i == 0 else (1, 0))
        if routers:
            assert routers[0].attrs["score"] == "sigmoid"
            assert routers[0].attrs["routed_scale"] == 2.5
            shared = [op for op in fwd
                      if op.namescope == f"blk{i}/moe/shared"]
            assert shared
    types = [op.type for op in ops]
    assert types.count("scaled_dot_product_attention_grad") == 5
    assert types.count("rotary_embedding_grad") == 5
    assert types.count("moe_experts_grad") == 4


@pytest.mark.parametrize("control", ["no_gate", "no_window", "no_yarn"])
def test_a_reference_without_one_mechanism_is_another_model(control):
    """The controls: the reference with the gate at 1, the window
    dropped or yarn dropped must not agree with the program, by loss and
    by logits."""
    _, feed, w, got, _, _, _ = run_against_reference()
    other = highest(lambda w_: ref.forward(
        w_, REF_CFG, feed["input_ids"], **{control: True}))(w)
    other_loss = float(highest(lambda w_: ref.loss(
        w_, REF_CFG, feed, **{control: True}))(w))
    logit_err = np.abs(got[1] - np.asarray(other["logits"])).max()
    assert logit_err > 100 * 2e-5 and logit_err > 1e-2 * np.abs(got[1]).max()
    assert abs(float(got[0]) - other_loss) > 100 * 2e-6 * float(got[0])


def test_model_trains_under_amp():
    cfg, main, startup, model, _ = built(
        2, lambda: fluid.optimizer.Adam(3e-3))
    feed = M.make_batch(cfg, 4, 16, seed=1)
    main._amp = True
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    losses = [float(exe.run(main, feed=feed, scope=scope,
                            fetch_list=[model["loss"]])[0])
              for _ in range(30)]
    assert losses[-1] < losses[0] - 0.5 and np.isfinite(losses).all()


def test_config_refuses_heads_that_do_not_group_and_short_lists():
    with pytest.raises(ValueError, match="key/value heads"):
        M.LagunaConfig(**dict(TINY, num_attention_heads_per_layer=[
            6, 7, 8, 8, 6]))
    with pytest.raises(ValueError, match="entries for 5 layers"):
        M.LagunaConfig(**dict(TINY, layer_types=["full_attention"] * 4))
    pub = M.laguna_xs_2()
    assert [pub.heads(i) for i in range(5)] == [48, 64, 64, 64, 48]
    assert [pub.window(i) for i in range(5)] == [None, 512, 512, 512, None]
    assert [pub.dense(i) for i in range(3)] == [True, False, False]
    theta, rotary_dim, scaling = pub.rope(0)
    assert (theta, rotary_dim, scaling["factor"]) == (5e5, 64, 64.0)
    assert pub.rope(1) == (1e4, 128, None)


# --- yarn in the rotary op ---------------------------------------------------


def test_yarns_tables_at_the_published_numbers_by_hand():
    """Laguna-XS.2's full layers: 64 rotated features, theta 5e5, factor
    64 over 4096, beta 64 and 1."""
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (low, high) == (pytest.approx(5.66, abs=0.01),
                           pytest.approx(15.80, abs=0.01))
    assert rope.yarn_correction_range(64, 5e5, YARN) == (5, 16)
    assert YARN.attention_factor == pytest.approx(0.1 * math.log(64) + 1)
    assert YARN.attention_factor == pytest.approx(1.41589, abs=1e-5)
    f = 5e5 ** (-2 * np.arange(32) / 64)
    ramp = np.clip((np.arange(32) - 5) / 11, 0, 1)
    want = f / 64 * ramp + f * (1 - ramp)
    got = np.asarray(rope.inv_freq(64, 5e5, YARN))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the six fastest waves keep their frequency, from the 17th on they
    # are interpolated 64 times, between them the ramp
    np.testing.assert_allclose(got[:6], f[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], f[16:] / 64, rtol=1e-6)
    assert got[10] == pytest.approx(f[10] * (1 - 5 / 11 * 63 / 64), rel=1e-6)
    # and the reference, written from the formula on its own, agrees
    np.testing.assert_allclose(
        ref.inv_freq(64, M.ROPE_PARAMETERS["full_attention"]), want,
        rtol=1e-12)
    # the tables: cos and sin of p * inv_freq, times the attention factor
    cos, sin = rope.cos_sin(40, 64, 5e5, YARN)
    ang = np.arange(40)[:, None] * want[None, :]
    np.testing.assert_allclose(cos, 1.4158883083359672 * np.cos(ang),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sin, 1.4158883083359672 * np.sin(ang),
                               rtol=1e-4, atol=1e-5)
    k_cos, k_sin = rope.tables(40, 64, 5e5, YARN)
    np.testing.assert_array_equal(k_cos, np.concatenate([cos, cos], -1))
    np.testing.assert_array_equal(k_sin, np.concatenate([-sin, sin], -1))
    # without a scaling the plain law, bit for bit what it was
    np.testing.assert_array_equal(
        rope.inv_freq(128, 1e4),
        1e4 ** (-jnp.arange(0, 128, 2, dtype=jnp.float32) / 128))


def by_hand(x, r, theta, scaling):
    """x [b, h, t, dh] rotated from the formula: numpy float64."""
    x = np.asarray(x, np.float64)
    t = x.shape[-2]
    j = np.arange(r // 2)
    f = theta ** (-2.0 * j / r)
    scale = 1.0
    if scaling is not None:
        low, high = rope.yarn_correction_range(r, theta, scaling)
        ramp = np.clip((j - low) / (high - low), 0, 1)
        f = f / scaling.factor * ramp + f * (1 - ramp)
        scale = scaling.attention_factor
    ang = np.arange(t)[:, None] * f[None, :]
    cos, sin = scale * np.cos(ang), scale * np.sin(ang)
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., r:]], -1)


@pytest.mark.parametrize("rotary_dim", [None, 64], ids=["whole", "64of128"])
@pytest.mark.parametrize("layout", ["bhtd", "bthd"])
def test_rotary_embedding_with_a_scaling_forward_and_gradient(rotary_dim,
                                                              layout):
    """The layer with ``scaling=`` against ``_rotate`` and against the
    formula by hand, forward and gradient, the whole head and 64 of
    128."""
    r = np.random.RandomState(5)
    b, h, hk, t, dh = 2, 3, 1, 24, 128
    q = r.randn(b, h, t, dh).astype(np.float32)
    k = r.randn(b, hk, t, dh).astype(np.float32)
    gq = r.randn(b, h, t, dh).astype(np.float32)
    gk = r.randn(b, hk, t, dh).astype(np.float32)
    scaling = M.ROPE_PARAMETERS["full_attention"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        shape = (lambda n: [b, t, n, dh]) if layout == "bthd" \
            else (lambda n: [b, n, t, dh])
        qv = layers.data("q", shape=shape(h), dtype="float32",
                         append_batch_size=False)
        kv = layers.data("k", shape=shape(hk), dtype="float32",
                         append_batch_size=False)
        gqv = layers.data("gq", shape=[b, h, t, dh], dtype="float32",
                          append_batch_size=False)
        gkv = layers.data("gk", shape=[b, hk, t, dh], dtype="float32",
                          append_batch_size=False)
        qv.stop_gradient = kv.stop_gradient = False
        qo, ko = layers.rotary_embedding(qv, kv, theta=5e5,
                                         rotary_dim=rotary_dim,
                                         layout=layout, scaling=scaling)
        append_backward(layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(qo, gqv)),
            layers.reduce_sum(layers.elementwise_mul(ko, gkv))))
    fed = (lambda a: a.transpose(0, 2, 1, 3)) if layout == "bthd" \
        else (lambda a: a)
    got = fluid.Executor().run(
        main, feed={"q": fed(q), "k": fed(k), "gq": gq, "gk": gk},
        fetch_list=[qo, ko, "q@GRAD", "k@GRAD"], scope=fluid.Scope())
    rd = rotary_dim or dh
    for x, g, out, grad in ((q, gq, got[0], got[2]), (k, gk, got[1], got[3])):
        want = by_hand(x, rd, 5e5, YARN)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            out, ao._rotate(jnp.asarray(x), 5e5, rotary_dim, False, YARN),
            rtol=1e-6, atol=1e-6)
        # the rotation is linear: its gradient is its transpose, the
        # rotation by the negated angles (times the same factor)
        want_g, = jax.vjp(lambda z: ao._rotate(z, 5e5, rotary_dim, False,
                                               YARN), jnp.asarray(x))[1](
            jnp.asarray(g))
        np.testing.assert_allclose(fed(np.asarray(want_g)), grad, rtol=1e-5,
                                   atol=1e-5)
        # norms grow by the attention factor on the features that turn
        np.testing.assert_allclose(
            np.linalg.norm(out[..., :rd]),
            YARN.attention_factor * np.linalg.norm(x[..., :rd]), rtol=1e-4)
        np.testing.assert_array_equal(out[..., rd:], x[..., rd:])


def test_rope_dispatch_counter_names_the_scaling():
    """``rope_tile``'s answer for a call is counted with the new label:
    on the CPU every call is the XLA form, the full layers' under
    ``scaling=yarn``, the window layers' under ``scaling=none``."""
    monitor.reset()
    flags.set_flags({"telemetry": True})
    try:
        _, main, startup, model, _ = built(3)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        cfg = M.LagunaConfig(**TINY, num_experts=16, held_experts=HELD)
        exe.run(main, feed=M.make_batch(cfg, 1, 16), scope=scope,
                fetch_list=[model["loss"]])
        rows = {}
        for row in monitor.snapshot()["pt_rope_dispatch_total"]["values"]:
            lb = row["labels"]
            key = (lb["impl"], lb["pass"], lb["scaling"])
            rows[key] = rows.get(key, 0) + int(row["value"])
        assert rows == {("xla", "fwd", "yarn"): 2, ("xla", "bwd", "yarn"): 2,
                        ("xla", "fwd", "none"): 3, ("xla", "bwd", "none"): 3}
        attn = monitor.snapshot()["pt_attention_dispatch_total"]["values"]
        windowed = [r["labels"] for r in attn if r["labels"].get("band")]
        assert windowed and {lb["heads"] for lb in windowed} == {"8"}
        assert all("heads" not in r["labels"] for r in attn
                   if not r["labels"].get("band"))
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_both_of_the_cells_calls_as_rope_tile_sees_them():
    """The window layers' whole head and the full layers' 64 of 128
    under yarn both go through the kernels (they read tables); a part of
    a head that is not one vreg wide stays XLA's."""
    bf16 = jnp.bfloat16
    assert rope.rope_tile(1, 8192, 64, 128, None, False, bf16, hk=8,
                          backend="tpu", on_mesh=False) == (256, 64)
    assert rope.rope_tile(1, 8192, 48, 128, 64, False, bf16, hk=8,
                          backend="tpu", on_mesh=False) == (256, 48)
    assert rope.rope_tile(1, 8192, 16, 256, 64, False, bf16, hk=2,
                          backend="tpu", on_mesh=False) is None
    cos, sin = rope.tables(8, 128, 5e5, YARN, 64)
    assert cos.shape == sin.shape == (8, 128)
    assert (cos[:, 64:] == 1).all() and (sin[:, 64:] == 0).all()
    part_cos, part_sin = rope.tables(8, 64, 5e5, YARN)
    np.testing.assert_array_equal(cos[:, :64], part_cos)
    np.testing.assert_array_equal(sin[:, :64], part_sin)


@pytest.mark.parametrize("tokens", [True, False],
                         ids=["token_major", "head_major"])
@pytest.mark.parametrize("rotary_dim,scaling", [
    (None, YARN), (64, YARN), (64, None), (32, None)],
    ids=["whole_yarn", "64of128_yarn", "64of128_plain", "32of128_plain"])
def test_the_kernels_under_yarn_and_on_part_of_a_head(monkeypatch, tokens,
                                                      rotary_dim, scaling):
    """``rope.fwd`` / ``rope.bwd`` through the interpreter with yarn's
    tables and on the first features of a head one vreg wide:
    ``_rotate`` with the same arguments, and its vjp, to bf16 rounding;
    the features that pass come back bit for bit."""
    monkeypatch.setattr(rope, "_INTERPRET", True)
    r = np.random.RandomState(2)
    q = jnp.asarray(r.randn(1, 64, 3, 128), jnp.bfloat16)   # token-major
    k = jnp.asarray(r.randn(1, 64, 1, 128), jnp.bfloat16)
    qh, kh = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    gq = jnp.asarray(r.randn(1, 3, 64, 128), jnp.bfloat16)
    gk = jnp.asarray(r.randn(1, 1, 64, 128), jnp.bfloat16)
    tile = rope.rope_tile(1, 64, 3, 128, rotary_dim, False, jnp.bfloat16,
                          hk=1)
    assert tile == (64, 3)
    kw = dict(tokens=tokens, scaling=scaling, rotary_dim=rotary_dim)
    outs = rope.rope_fwd(*((q, k) if tokens else (qh, kh)), 5e5, tile, **kw)
    grads = rope.rope_bwd(gq, gk, 5e5, tile, **kw)
    f32 = lambda a: np.asarray(a, np.float32)   # noqa: E731
    for x, g, out, grad in ((qh, gq, outs[0], grads[0]),
                            (kh, gk, outs[1], grads[1])):
        turn = lambda z: ao._rotate(z, 5e5, rotary_dim, False,  # noqa: E731
                                    scaling)
        want, vjp = jax.vjp(turn, x.astype(jnp.float32))
        np.testing.assert_allclose(f32(out), want, rtol=1e-2, atol=2e-2)
        want_g, = vjp(g.astype(jnp.float32))
        if tokens:
            want_g = jnp.swapaxes(want_g, 1, 2)
        np.testing.assert_allclose(f32(grad), want_g, rtol=1e-2, atol=2e-2)
        if rotary_dim:
            np.testing.assert_array_equal(f32(out)[..., rotary_dim:],
                                          f32(x)[..., rotary_dim:])


# --- the per-head gate -------------------------------------------------------


def test_the_per_head_gates_gradient():
    """g = sigmoid(a Wg), one value a head and position, times the
    head's context: the program's gradient of q|k|v|g's gate columns is
    the reference's, and a reference without the gate gives another."""
    cfg, main, startup, model, grads = built(7, num_hidden_layers=2)
    feed = M.make_batch(cfg, 2, 16, seed=3)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 5)
    w = snapshot(scope)
    names = [p.name for p, _ in grads]
    got = dict(zip(names, exe.run(main, feed=feed, scope=scope,
                                  fetch_list=[g for _, g in grads])))
    ref_cfg = dict(REF_CFG, num_hidden_layers=2)
    want = highest(jax.grad(lambda w_: ref.loss(w_, ref_cfg, feed)))(w)
    ungated = highest(jax.grad(lambda w_: ref.loss(
        w_, ref_cfg, feed, no_gate=True)))(w)
    for i, h in ((0, 6), (1, 8)):
        n = f"blk{i}_attn_qkvg_colp.w"
        gate, gate_want = got[n][:, -h:], np.asarray(want[n])[:, -h:]
        scale = np.abs(gate_want).max()
        assert scale > 0
        np.testing.assert_allclose(gate, gate_want, rtol=2e-3,
                                   atol=1e-4 * scale)
        assert np.abs(np.asarray(ungated[n])[:, -h:]).max() == 0
        # and the gate scales what reaches Wo
        o = f"blk{i}_attn_out_rowp.w"
        assert np.abs(np.asarray(ungated[o]) - got[o]).max() \
            > 0.05 * np.abs(got[o]).max()


# --- one chip's share of an expert layer ---------------------------------

N, D, F, E, K = 15, 8, 6, 32, 4
KW = dict(norm_topk_prob=True, shared_gate=False, score="sigmoid",
          routed_scale=2.5, select_bias=False)


def moe_layer(held, shared, x, weights=None, seed=3):
    """(out, rows, {param: value}) of a topk_moe layer as models/laguna
    builds it; ``weights``: the uncut layer's, cut to the held share."""
    return model_test.moe_layer(
        E, K, F, held, x, weights, seed, name="p_moe",
        shared_d_ff=shared, **KW)


def test_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """E = 32 as SIXTEEN shares of 2 (the deployment's sixteen chips):
    what the shares give for their routed experts, plus the ungated
    shared expert counted once, is the uncut layer's output, which is
    the uncut reference's; the reference's own shares sum to it too;
    every (token, slot) pair is a row of exactly one share."""
    r = np.random.RandomState(0)
    x = r.randn(3, 5, D).astype(np.float32)
    _, _, w = moe_layer(None, F, x)
    w = dict(w, **{"p_moe_router.w": r.randn(D, E).astype(np.float32)})
    full, rows, w = moe_layer(None, F, x, w)
    assert rows.shape == (E,) and rows.sum() == N * K
    assert "p_moe_shared_mix.w" not in w and "p_moe_shared_down.w" in w
    cfg = dict(num_experts=E, router_experts=E, num_experts_per_tok=K,
               moe_routed_scaling_factor=2.5)
    total, ref_total, held_rows = 0.0, 0.0, []
    for i in range(16):
        share = (2 * i, 2)
        out, r_, _ = moe_layer(share, F if i == 0 else None, x, w)
        assert (r_ == rows[2 * i:2 * i + 2]).all()
        held_rows.append(r_.sum())
        total = total + out
        w_share = dict(w, **{k: w[k][2 * i:2 * i + 2] for k in (
            "p_moe_gate.w", "p_moe_up.w", "p_moe_down.w")})
        with jax.default_matmul_precision("highest"):
            ref_total = ref_total + ref.moe(
                jnp.asarray(x), w_share, "p", cfg, share=share,
                shared=i == 0)[0]
    assert sum(held_rows) == N * K
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-7)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.moe(jnp.asarray(x), w, "p", cfg)
    np.testing.assert_allclose(full, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(ref_total, want, rtol=1e-5, atol=1e-7)
