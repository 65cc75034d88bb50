"""LFM2-MoE (paddle_tpu/models/lfm2_moe.py) on the CPU at tiny sizes.

The gated short convolution's op (ops/linear_attention_ops.
gated_short_conv and its grad op) against its composition from
``causal_conv1d`` and two multiplies, forward and every gradient; its
Pallas kernels (parallel/causal_conv.py ``sconv.gated.*``) through the
interpreter against the XLA form; the model's loss, logits and every
parameter's gradient against the plain reference
(perf/reference/lfm2moe.py) on seeded weights, for the cut the benchmark
runs (layers 1-5 of 40, a share of the experts held) and for a whole
tiny model; that the held shares ADD UP to the uncut layer at LFM2's
router; that each of the reference's ablations is another model. The
program's gradients come from ``append_backward``. The chip's run is
chip_smoke.py's ``sconv`` phase."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, moved, reference, snapshot
from paddle_tpu import analysis, flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.models import lfm2_moe as M
from paddle_tpu.ops import linear_attention_ops as L
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.parallel import causal_conv as cc
from perf import flops_lfm2moe
from perf.reference import lfm2moe as ref

BF, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(cc, "_INTERPRET", True)


def operands(b, t, c, taps, seed=0, dtype=BF):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, t, 3 * c), dtype),
            jnp.asarray(r.randn(c, taps) * 0.5, F32),
            jnp.asarray(r.randn(b, t, c), dtype))


def through_the_op(x, w, dy):
    """(Y, dX, dW): the registered op and its grad op, as the Program
    runs them."""
    ins = {"X": [x], "W": [w]}
    y = L._gated_short_conv(ins, {})["Y"][0]
    grads = L._gated_short_conv_grad({**ins, "Y": [y], "GRAD::Y": [dy]}, {})
    return y, grads["GRAD::X"][0], grads["GRAD::W"][0]


def composed(x, w, dy):
    """The mixer composed from what the repo had: a split, a product,
    ``causal_conv1d`` without activation, a product; jax's vjp."""
    def f(x, w):
        gate_b, gate_c, u = jnp.split(x, 3, axis=-1)
        conv = L._causal_conv1d({"X": [gate_b * u], "W": [w]},
                                {"act": ""})["Y"][0]
        return gate_c * conv

    y, vjp = jax.vjp(f, x, w)
    return (y, *vjp(dy))


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    assert np.isfinite(a).all()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))


# ---------------------------------------------------------------------------
# the op against its composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(F32, 2e-6), (BF, 3e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("taps", [3, 4])
def test_op_is_its_composition_forward_and_every_gradient(dtype, tol, taps):
    """Batch 2, t not a multiple of any block. In float32 the same
    mathematics; in bf16 the composition rounds B * u, the convolution
    and its cotangents to bf16 between the ops and the one op does not:
    they agree to a few bf16 roundings of a result."""
    x, w, dy = operands(2, 77, 40, taps, seed=taps, dtype=dtype)
    got, want = through_the_op(x, w, dy), composed(x, w, dy)
    assert got[0].shape == (2, 77, 40) and got[0].dtype == dtype
    assert got[1].shape == x.shape and got[1].dtype == dtype
    assert got[2].shape == w.shape and got[2].dtype == F32
    for g, v, name in zip(got, want, ("Y", "dX", "dW")):
        assert rel(g, v) < tol, name
    # each range of dX by itself: dB, dC, du
    for i, name in enumerate(("dB", "dC", "du")):
        at = slice(i * 40, (i + 1) * 40)
        assert rel(got[1][..., at], want[1][..., at]) < 2 * tol, name
    # and the composition written out: y_t = C_t sum_j w_j (B u)_{t-2+j}
    xf = np.asarray(x, np.float32)
    v = xf[..., :40] * xf[..., 80:]
    pad = np.pad(v, [(0, 0), (taps - 1, 0), (0, 0)])
    y = xf[..., 40:80] * sum(pad[:, j:j + 77] * np.asarray(w)[:, j]
                             for j in range(taps))
    assert rel(got[0], y) < max(tol, 1e-5)


def test_the_first_rows_see_zeros_and_no_later_position():
    x, w, _ = operands(1, 12, 8, 3, dtype=F32)
    y = L._gated_short_conv({"X": [x], "W": [w]}, {})["Y"][0]
    # position 0: only the last tap, on v_0
    v0 = x[0, 0, :8] * x[0, 0, 16:]
    np.testing.assert_allclose(y[0, 0], x[0, 0, 8:16] * w[:, 2] * v0,
                               rtol=1e-5)
    # a later position's input does not move an earlier output
    moved = x.at[0, 7].add(1.0)
    y2 = L._gated_short_conv({"X": [moved], "W": [w]}, {})["Y"][0]
    np.testing.assert_array_equal(y[0, :7], y2[0, :7])
    assert np.abs(np.asarray(y[0, 7:10] - y2[0, 7:10])).min() > 0
    np.testing.assert_array_equal(y[0, 10:], y2[0, 10:])


# ---------------------------------------------------------------------------
# the kernels through the interpreter
# ---------------------------------------------------------------------------

# (batch, positions, channels a range, taps) -> the tile: one pass; two
# lane blocks of 128 over three row blocks, the last ragged (the halo
# crosses two block boundaries); 1024 x 256 over two lane blocks of a
# wider range and a batch of 2; four taps
KERNEL_CASES = {
    "one_pass": ((1, 32, 128, 3), (32, 128)),
    "ragged_blocks_lanes2": ((1, 2100, 384, 3), (1024, 128)),
    "batch2_lanes256": ((2, 1100, 512, 3), (1024, 256)),
    "taps4": ((1, 1200, 128, 4), (1024, 128)),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_are_the_xla_form(case, interpreted):
    (b, t, c, taps), tile = KERNEL_CASES[case]
    x, w, dy = operands(b, t, c, taps, seed=t)
    assert cc.conv_tile(t, c, taps, BF, gated=True) == tile
    y = cc.gated_conv_fwd(x, w, tile)
    dx, dw = cc.gated_conv_bwd(x, w, dy, tile)
    want_y, vjp = jax.vjp(L._gated_conv_xla, x, w)
    want_dx, want_dw = vjp(dy)
    assert y.shape == (b, t, c) and y.dtype == BF
    assert dx.shape == (b, t, 3 * c) and dx.dtype == BF
    assert dw.shape == (c, taps) and dw.dtype == F32
    assert rel(y, want_y) < 1e-2
    for i, name in enumerate(("dB", "dC", "du")):
        at = slice(i * c, (i + 1) * c)
        assert rel(dx[..., at], want_dx[..., at]) < 1e-2, name
    assert rel(dw, want_dw) < 1e-4


def test_a_block_takes_its_first_rows_from_the_block_in_front(interpreted):
    """Rows 1024 and 1025 read v at 1022 and 1023 (the halo), dv at 1022
    and 1023 reads dc at 1024 and 1025 (the scratch the reversed walk
    carries): zeroing the block in front moves exactly those."""
    x, w, dy = operands(1, 2048, 128, 3, seed=5)
    tile = cc.conv_tile(2048, 128, 3, BF, gated=True)
    assert tile == (1024, 128)
    y = np.asarray(cc.gated_conv_fwd(x, w, tile), np.float32)
    cut = x.at[:, :1024].set(0)
    y_cut = np.asarray(cc.gated_conv_fwd(cut, w, tile), np.float32)
    assert (np.abs(y - y_cut)[0, 1024:1026].max(-1) > 0).all()
    np.testing.assert_array_equal(y[0, 1026:], y_cut[0, 1026:])
    dx = np.asarray(cc.gated_conv_bwd(x, w, dy, tile)[0], np.float32)
    dy_cut = dy.at[:, 1024:].set(0)
    dx_cut = np.asarray(cc.gated_conv_bwd(x, w, dy_cut, tile)[0], np.float32)
    assert (np.abs(dx - dx_cut)[0, 1022:1024, :128].max(-1) > 0).all()
    np.testing.assert_array_equal(dx[0, :1022], dx_cut[0, :1022])


def test_picker_says_kernel_or_xla_from_the_call_alone():
    pick = lambda *a, **k: cc.conv_tile(*a, backend="tpu", on_mesh=False,
                                        gated=True, **k)
    # the cell's call: 8192 positions, 2048 channels a range, 3 taps
    assert pick(8192, 2048, 3, BF) == (1024, 256)
    assert cc._vmem_bytes(1024, 256, 3, True) <= cc._VMEM_CAP_BYTES \
        < cc._vmem_bytes(1024, 512, 3, True)
    # the plain call's tile is what it was
    assert cc.conv_tile(8192, 8192, 4, BF, backend="tpu",
                        on_mesh=False) == (1024, 512)
    assert pick(8192, 2048, 3, F32) is None
    assert pick(8192, 2000, 3, BF) is None
    assert cc.conv_tile(8192, 2048, 3, BF, backend="cpu", on_mesh=False,
                        gated=True) is None
    assert cc.conv_tile(8192, 2048, 3, BF, backend="tpu", on_mesh=True,
                        gated=True) is None


def test_op_and_grad_op_in_a_program_under_amp(interpreted):
    """The layer under AMP through ``append_backward``: its own grad op,
    which takes X, W and Y's cotangent; the kernels (interpreter) and
    the XLA form give the same gradients; the counter's rows carry
    ``gated``, a plain call's do not."""
    def run(interpret):
        cc._INTERPRET = interpret
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 3
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[2, 64, 32], dtype="float32",
                            append_batch_size=False)
            bcu = layers.fc(x, 3 * 128, num_flatten_dims=2, bias_attr=False,
                            param_attr=ParamAttr(name="in.w"))
            y = layers.short_conv_gate(bcu, taps=3,
                                       param_attr=ParamAttr(name="conv.w"))
            plain = layers.causal_conv1d(y, taps=3, act=None,
                                         param_attr=ParamAttr(name="p.w"))
            loss = layers.mean(layers.elementwise_mul(plain, plain))
            grads = append_backward(loss)
        main._amp = True
        types = [op.type for op in main.global_block().ops]
        assert "gated_short_conv" in types
        assert "gated_short_conv_grad" in types
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        feed = {"x": np.random.RandomState(1).randn(2, 64, 32).astype(
            "float32")}
        out = exe.run(main, feed=feed, scope=scope,
                      fetch_list=[loss] + [g for _, g in grads])
        return {p.name: np.asarray(o) for (p, _), o in zip(grads, out[1:])}

    monitor.reset()
    flags.set_flags({"telemetry": True})
    try:
        kernel = run(True)
        counts = L.conv_dispatch_counts()
        assert counts == {
            "kernel fwd b2 t64 c128 taps3 gated": 1,
            "kernel bwd b2 t64 c128 taps3 gated": 1,
            "kernel fwd b2 t64 c128 taps3": 1,
            "kernel bwd b2 t64 c128 taps3": 1}
        rows = monitor.snapshot()["pt_causal_conv_dispatch_total"]["values"]
        assert sorted("gated" in r["labels"] for r in rows) \
            == [False, False, True, True]
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    xla = run(False)
    assert set(kernel) == {"in.w", "conv.w", "p.w"}
    for name in kernel:
        assert rel(kernel[name], xla[name]) < 2e-2, name


def test_layer_refuses_channels_that_are_not_three_ranges():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", shape=[2, 8, 10], dtype="float32",
                        append_batch_size=False)
        with pytest.raises(ValueError):
            layers.short_conv_gate(x)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

TINY = dict(vocab_size=50, hidden_size=32, intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=3)
CUT = dict(num_hidden_layers=5, first_layer=1, held_experts=(2, 2))
WHOLE = dict(num_hidden_layers=4, num_dense_layers=1,
             layer_types=("conv", "full_attention", "conv", "conv"))
REF_BASE = dict(
    {k: v for k, v in TINY.items() if k != "num_experts"},
    norm_eps=1e-5, conv_L_cache=3, norm_topk_prob=True,
    routed_scaling_factor=1, num_dense_layers=2,
    layer_types=list(M.LAYER_TYPES),
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"})


def ref_cfg(layout):
    cfg = dict(REF_BASE, **{k: v for k, v in layout.items()
                            if k != "held_experts"})
    first, count = layout.get("held_experts", (0, 8))
    cfg.update(held_first=first, num_experts=count, router_experts=8)
    return cfg


# gains and the routers' selection biases away from their initial
# values, so that every parameter matters; the projections larger, so
# that what a query sees and what the taps keep move the output
PERTURB = [((".scale",), moved(0.2)), (("_router.bias",), drawn(0.1)),
           (("_colp.w", "_rowp.w", "_conv.w", "_gate.w", "_up.w", "_down.w",
             "_router.w", "_tok_emb.w"), drawn(0.3))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, **layout):
    cfg = M.Lfm2MoeConfig(**TINY, **layout)
    return (cfg, *model_test.built(M, cfg, seed))


def block_parameters(kind, dense):
    mixer = {"sconv": ["sconv_in_colp.w", "sconv_conv.w", "sconv_out_rowp.w"],
             "attn": ["attn_qkv_colp.w", "attn_qnorm.scale",
                      "attn_knorm.scale", "attn_out_rowp.w"]}[kind]
    ff = (["ffn_w1_colp.w", "ffn_w3_colp.w", "ffn_w2_rowp.w"] if dense
          else ["moe_router.w", "moe_gate.w", "moe_up.w", "moe_down.w"])
    return ["op_norm.scale", "ffn_norm.scale"] + mixer + ff


@pytest.mark.parametrize("layout,blocks", [
    (CUT, [(1, "sconv", True), (2, "attn", False), (3, "sconv", False),
           (4, "sconv", False), (5, "sconv", False)]),
    (WHOLE, [(0, "sconv", True), (1, "attn", False), (2, "sconv", False),
             (3, "sconv", False)]),
], ids=["layers-1-5-of-40-held-2-of-8", "a-whole-model-of-4"])
def test_model_loss_logits_and_every_parameters_gradient(layout, blocks):
    cfg, main, startup, model, grads = built(11, **layout)
    assert cfg.blocks == blocks
    rcfg = ref_cfg(layout)
    assert ref.blocks(rcfg) == blocks
    assert flops_lfm2moe.blocks(rcfg) == [(k, d) for _, k, d in blocks]
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    feed = M.make_batch(cfg, 2, 16, seed=9)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["logits"], *model["top_i"],
        *(g for _, g in grads)])
    want, want_loss, want_g = reference(ref, w, rcfg, feed)
    names = [p.name for p, _ in grads]
    expected = [M.TABLE, "final_norm.scale"]      # the tied table: once
    expected += [f"blk{i}_{s}" for i, k, dense in blocks
                 for s in block_parameters(k, dense)]
    assert sorted(names) == sorted(expected)
    held = layout.get("held_experts", (0, 8))[1]
    moe_at = next(i for i, _, dense in blocks if not dense)
    assert w[f"blk{moe_at}_moe_up.w"].shape == (held, 32, 16)
    assert w[f"blk{moe_at}_moe_router.w"].shape == (32, 8)
    assert w[f"blk{moe_at}_moe_router.bias"].shape == (8,)
    # float32 on both sides; the same mathematics in another order
    n_moe = sum(not dense for _, _, dense in blocks)
    for a, b in zip(got[2:2 + n_moe], want["top_i"]):
        assert (np.sort(a, -1) == np.sort(np.asarray(b), -1)).all()
    np.testing.assert_allclose(got[0], want_loss, rtol=5e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=5e-4, atol=5e-5)
    g = dict(zip(names, got[2 + n_moe:]))
    for n in names:
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=3e-3,
                                   atol=2e-4 * scale + 1e-9, err_msg=n)


def test_defaults_are_the_published_model():
    cfg = M.lfm2_24b_a2b()
    kinds = [k for _, k, _ in cfg.blocks]
    assert len(kinds) == 40 and kinds.count("sconv") == 30
    assert [i for i, k, _ in cfg.blocks if k == "attn"] == list(
        range(2, 40, 4))
    assert [i for i, _, dense in cfg.blocks if dense] == [0, 1]
    assert cfg.head_dim == 64 and cfg.conv_L_cache == 3
    assert M.Lfm2MoeConfig(num_hidden_layers=5, first_layer=1).blocks == [
        (1, "sconv", True), (2, "attn", False), (3, "sconv", False),
        (4, "sconv", False), (5, "sconv", False)]
    with pytest.raises(ValueError):
        M.Lfm2MoeConfig(num_hidden_layers=5, first_layer=38)
    with pytest.raises(ValueError):
        M.Lfm2MoeConfig(layer_types=("conv", "mamba"), num_hidden_layers=2)
    with pytest.raises(NotImplementedError):
        M.Lfm2MoeConfig(conv_bias=True)


def test_scopes_carry_the_published_indices():
    _, main, _, _, _ = built(3, **CUT)
    from paddle_tpu.framework import OP_NAMESCOPE_ATTR

    scopes = {op.attrs.get(OP_NAMESCOPE_ATTR, "").strip("/")
              for op in main.global_block().ops}
    for want in ("embed", "blk1/sconv/in_proj", "blk1/sconv/gconv",
                 "blk1/sconv/out_proj", "blk1/ffn", "blk2/attn/qkv",
                 "blk2/attn/qk_norm", "blk2/attn/rope", "blk2/attn/core",
                 "blk2/attn/out", "blk2/moe/router", "blk2/moe/dispatch",
                 "blk2/moe/experts", "blk2/moe/combine", "blk5/sconv/gconv",
                 "final_norm", "loss_head"):
        assert any(s == want or s.startswith(want + "/") for s in scopes), \
            (want, sorted(scopes))
    assert not any(s.startswith(("blk0", "blk6")) for s in scopes)
    conv = [op for op in main.global_block().ops
            if op.type.startswith("gated_short_conv")]
    assert len(conv) == 8                       # four mixers, each way


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

N, D, F, E, K = 24, 16, 12, 8, 4


def moe_layer(held):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        out, *_ = layers.topk_moe(
            x, E, K, F, norm_topk_prob=True, name="m", held=held,
            score="sigmoid", routed_scale=1.0, select_bias=True)
    return main, startup, out


def test_the_four_held_shares_add_up_to_the_uncut_layer():
    """Two experts a chip, four chips, at LFM2's router (sigmoid
    scores, a non-zero selection bias, the top 4 renormalised): the
    shares' outputs, summed, are the layer that holds all eight, and
    that layer is the reference's."""
    r = np.random.RandomState(0)
    x = r.randn(N, D).astype("float32")
    w = {"m_router.w": r.randn(D, E) * 0.5, "m_router.bias": r.randn(E) * 0.1,
         "m_gate.w": r.randn(E, D, F) * 0.3, "m_up.w": r.randn(E, D, F) * 0.3,
         "m_down.w": r.randn(E, F, D) * 0.3}
    w = {k: v.astype("float32") for k, v in w.items()}

    def run(held):
        main, startup, out = moe_layer(held)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        for name, value in w.items():
            if held is not None and name in ("m_gate.w", "m_up.w",
                                             "m_down.w"):
                value = value[held[0]:held[0] + held[1]]
            assert np.asarray(scope.find_var(name)).shape == value.shape
            scope.set(name, jnp.asarray(value))
        return np.asarray(exe.run(main, feed={"x": x}, scope=scope,
                                  fetch_list=[out])[0])

    whole = run(None)
    shares = [run((first, 2)) for first in (0, 2, 4, 6)]
    assert all(np.abs(s).max() > 1e-3 for s in shares)
    np.testing.assert_allclose(sum(shares), whole, rtol=1e-4, atol=1e-5)
    cfg = dict(num_experts=E, router_experts=E, num_experts_per_tok=K,
               norm_topk_prob=True, routed_scaling_factor=1)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.moe(jnp.asarray(x)[None], {
            f"blk_moe_{k[2:]}": jnp.asarray(v) for k, v in w.items()},
            "blk", cfg)
    np.testing.assert_allclose(whole, np.asarray(want)[0], rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the reference's ablations
# ---------------------------------------------------------------------------


@functools.cache
def unablated():
    """(weights, ids, the reference's logits) every ablation is held
    against: one startup and one forward for all of them."""
    cfg, _, startup, _, _ = built(5, **CUT)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 4)
    w = snapshot(scope)
    ids = M.make_batch(cfg, 2, 16, seed=1)["input_ids"]
    with jax.default_matmul_precision("highest"):
        return w, ids, np.asarray(ref.forward(w, ref_cfg(CUT), ids)["logits"])


@pytest.mark.parametrize("ablation", ref.ABLATIONS)
def test_an_ablated_reference_is_another_model(ablation):
    w, ids, want = unablated()
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.forward(w, ref_cfg(CUT), ids,
                                       ablate=ablation)["logits"])
    scale = np.sqrt(np.mean(want ** 2))
    assert np.sqrt(np.mean((other - want) ** 2)) > 0.02 * scale
    if ablation in ("last_tap", "taps_reversed"):
        # position 0 sees only itself: the last tap either way... but
        # reversed, it meets the FIRST tap's weight
        same = ablation == "last_tap"
        first = np.abs(other[:, 0] - want[:, 0]).max() < 1e-4 * scale
        assert first == same
    with pytest.raises(AssertionError):
        ref.forward(w, ref_cfg(CUT), ids, ablate="no_such")
