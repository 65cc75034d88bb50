"""The hyper-connected latent-attention decoder (models/xing4.py: four
residual streams under a Sinkhorn-projected mix a token and a sublayer,
latent attention under a yarn table with DeepSeek's softmax scale, dense
layers, sigmoid-routed held experts beside an ungated shared expert, one
multi-token-prediction module under streams of its own) against the
plain float32 reference (perf/reference/xing4.py, the file the
benchmark's ``correct`` is decided by), forward and gradient, at tiny
sizes on the CPU, with and without the MTP module; the yarn numbers by
hand; a few steps of Adam under AMP; the expert layer as one chip's
share of eight. Gradients of the reference are ``jax.grad`` of its
functions; the program's come from ``append_backward``, whose hc grad
ops are written by hand (tests/test_hc_ops.py holds each alone)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import model_test
from model_test import drawn, highest, moved, reference, snapshot
from paddle_tpu import analysis
from paddle_tpu.models import xing4 as M
from paddle_tpu.parallel import rope
from perf.reference import xing4 as ref

# a yarn table whose original length (8) the tests' 16 positions pass
YARN = dict(M.YARN, factor=4, original_max_position_embeddings=8)
TINY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, intermediate_size=64,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=10000.0, rope_scaling=YARN, rms_norm_eps=1e-6,
            num_experts_per_tok=3, moe_intermediate_size=16,
            n_shared_experts=1, norm_topk_prob=True,
            routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20,
            hc_eps=1e-6, mhc_h_res_clamp_min=-30.0,
            mhc_h_res_clamp_max=30.0)
# experts 4..7 of the 16 the router scores are this chip's
HELD = (4, 4)
MLA = ["attn_norm.scale", "attn_q_a.w", "attn_q_a_norm.scale",
       "attn_q_b_colp.w", "attn_kv_a.w", "attn_kv_a_norm.scale",
       "attn_kv_b_colp.w", "attn_out_rowp.w"]
MOE = ["moe_norm.scale", "moe_router.w", "moe_gate.w", "moe_up.w",
       "moe_down.w", "moe_shared_gate.w", "moe_shared_up.w",
       "moe_shared_down.w"]
HC = ["hc_phi.w", "hc.bias", "hc.alpha"]


def ref_cfg(mtp):
    return dict(TINY, num_nextn_predict_layers=mtp, n_routed_experts=HELD[1],
                held_first=HELD[0], router_experts=16)


# gains, routers, selection biases and every mix away from their start
# (1 / 0.02 / 0 / gates of 0.01), so that every parameter matters, the
# routing has no near-ties and each H depends on the token
PERTURB = [((".scale",), moved(0.2)), (("_router.w",), drawn()),
           (("_router.bias",), drawn(0.3)),
           (("_hc.alpha",), lambda v, r: 0.5 + 0.2 * r.randn(*v.shape)),
           (("_hc.bias",), lambda v, r: 0.3 * v + 0.5 * r.randn(*v.shape)),
           (("_hc_phi.w",), drawn(0.2))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, mtp, optimizer=None):
    cfg = M.Xing4Config(**TINY, num_nextn_predict_layers=mtp,
                        n_routed_experts=16, held_experts=HELD)
    return (cfg, *model_test.built(M, cfg, seed, optimizer))


@pytest.mark.parametrize("mtp", [1, 0], ids=["with_mtp", "without_mtp"])
def test_model_loss_logits_and_every_parameters_gradient(mtp):
    cfg, main, startup, model, grads = built(11, mtp)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    names = [p.name for p, _ in grads]
    n_moe = 2 + mtp      # two expert layers of the stack, the module's
    fetch = [model["loss"], model["last_logits"], model["lb_loss"],
             *model["top_i"], *model["expert_rows"], *(g for _, g in grads)]
    if mtp:
        fetch += [model["mtp_last_logits"], model["mtp_loss"]]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=fetch)
    want, want_loss, want_g = reference(
        ref, w, ref_cfg(mtp), feed, feed["labels"], last=M.LAST_POSITIONS)
    # float32 on both sides; the same mathematics in another order
    # (token-minor mixes written as adds of slices against jnp.sum over a
    # token's matrix, sorted groups against a dense loop, one score
    # product against its two parts)
    np.testing.assert_allclose(got[0], want_loss, rtol=2e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[2], want["lb"], rtol=1e-6)
    if mtp:
        np.testing.assert_allclose(got[-2], want["mtp_logits"], rtol=2e-4,
                                   atol=2e-5)
        assert 0.05 * float(got[-1]) > 100 * 2e-6 * float(got[0])
    else:
        assert "mtp_logits" not in want and "mtp_loss" not in model
    for layer in range(n_moe):
        top_i = np.asarray(want["top_i"][layer])
        assert (np.sort(got[3 + layer], -1) == np.sort(top_i, -1)).all()
        rows = got[3 + n_moe + layer]
        assert (rows == [(top_i == HELD[0] + e).sum() for e in range(4)]).all()

    kinds = ["xing4_tok_emb.w", "lm_head_colp.w", "final_norm.scale"]
    kinds += [f"blk0_{s}" for s in MLA + [
        "ffn_norm.scale", "ffn_gate_colp.w", "ffn_up_colp.w",
        "ffn_down_rowp.w"]]
    kinds += [f"blk0_{sub}_{s}" for sub in ("attn", "ffn") for s in HC]
    blocks = ["blk1", "blk2"] + (["mtp"] if mtp else [])
    for p in blocks:
        kinds += [f"{p}_{s}" for s in MLA + MOE]
        kinds += [f"{p}_{sub}_{s}" for sub in ("attn", "moe") for s in HC]
    if mtp:
        kinds += ["mtp_hnorm.scale", "mtp_enorm.scale", "mtp_eh_proj.w",
                  "mtp_final_norm.scale"]
    # the selection biases are state and no parameter of the loss
    assert sorted(names) == sorted(kinds)
    assert w["blk1_attn_hc_phi.w"].shape == (4 * 32, 24)
    assert w["blk1_moe_gate.w"].shape == (4, 32, 16)      # held, not 16
    g = dict(zip(names, got[3 + 2 * n_moe:]))
    for n in names:
        # the loss is a mean over 32 positions at ln(50): gradients of
        # 1e-7..1e-2; seven sublayers deep the order of the sums shows in
        # the fifth digit of the largest entry of a tensor
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=2e-3,
                                   atol=1e-4 * scale + 1e-9, err_msg=n)


def test_every_reference_control_is_another_model():
    """Each piece of the mechanism that ``ref.CONTROLS`` leaves out
    moves the logits: the model computes it, and a model that did not
    would not agree."""
    cfg, main, startup, model, _ = built(5, 0)
    feed = M.make_batch(cfg, 2, 16, seed=3)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 6)
    # sharp attention (scores of a few units, as a trained layer's: at
    # normal(0, 0.02) every softmax is nearly uniform and its scale and
    # table move nothing), and
    # a wide H_res bias in every sublayer, so that the clamp is reached
    for name in scope.var_names():
        if name.endswith("_attn_q_b_colp.w"):
            scope.set(name, 50.0 * jnp.asarray(scope.find_var(name)))
    for i, name in enumerate(n for n in scope.var_names()
                             if n.endswith("_hc.bias")):
        bias = np.asarray(scope.find_var(name)).copy()
        bias[8:] = 25.0 * np.random.RandomState(7 + i).randn(16)
        scope.set(name, jnp.asarray(bias))
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[model["last_logits"]])[0]
    rc = ref_cfg(0)
    # (op by op: the seven models share their ops' executables, where
    # one jitted computation a control compiles the forward seven times)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(w, rc, feed["input_ids"], feed["labels"],
                           last=M.LAST_POSITIONS)["logits"]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        rms = float(np.sqrt(np.mean(np.asarray(want) ** 2)))
        for control in ref.CONTROLS:
            other = ref.forward(w, rc, feed["input_ids"], feed["labels"],
                                last=M.LAST_POSITIONS,
                                control=control)["logits"]
            err = float(np.sqrt(np.mean((np.asarray(other) - got) ** 2)))
            assert err > 0.01 * rms, (control, err / rms)


def test_the_yarn_table_and_the_softmax_scale_as_published():
    """factor 64 over 4096, beta 32 / 1, mscale = mscale_all_dim = 1:
    the table's factor is 1, the softmax scale (0.1 ln 64 + 1)^2 /
    sqrt(192); the op carries both, and the frequencies are yarn's by
    hand."""
    cfg = M.xing4_0_29b()
    table, scale = M.rope_table_and_scale(cfg)
    assert table["attention_factor"] == 1.0
    assert scale == pytest.approx((0.1 * math.log(64) + 1) ** 2
                                  / math.sqrt(192))
    assert scale == pytest.approx(0.14467, rel=1e-4)
    plain = M.Xing4Config()       # no rope_scaling: DeepSeek-V3's own
    assert M.rope_table_and_scale(plain) == (None, 1 / math.sqrt(192))

    _, main, _, _, _ = built(1, 0)
    ops = main.global_block().ops
    (sdpa, *_), (rot, *_) = (
        [op for op in ops if op.type == kind]
        for kind in ("scaled_dot_product_attention", "rotary_embedding"))
    assert sdpa.attrs["scale"] == pytest.approx(
        (0.1 * math.log(4) + 1) ** 2 / math.sqrt(24))
    assert rot.attrs["interleaved"] and rot.attrs["yarn_factor"] == 4.0
    assert rot.attrs["yarn_original_length"] == 8.0
    assert rot.attrs["yarn_attention_factor"] == 1.0

    # the published table by hand: pairs that turn more than 32 times
    # over 4096 positions keep their frequency, those under once are
    # divided by 64, a linear ramp between
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    turns = 4096 * f / (2 * np.pi)
    got = np.asarray(rope.inv_freq(64, 10000.0, rope.Yarn(64, 4096, 32, 1,
                                                          1.0)))
    np.testing.assert_allclose(got[turns > 33], f[turns > 33], rtol=1e-6)
    np.testing.assert_allclose(got[turns < 0.9], f[turns < 0.9] / 64,
                               rtol=1e-6)
    mid = (turns < 30) & (turns > 1.1)
    assert mid.sum() > 8 and ((got[mid] < f[mid])
                              & (got[mid] > f[mid] / 64)).all()
    np.testing.assert_allclose(
        got, ref.rotary_frequencies(64, 10000.0, M.YARN), rtol=1e-6)


def test_the_hyper_connections_sit_under_their_sublayers_scope():
    _, main, _, _, _ = built(2, 1)
    scopes = {}
    for op in main.global_block().ops:
        if op.type.startswith("hc_"):
            scopes.setdefault(op.type, set()).add(op.namescope)
    subs = ([("blk0", "attn"), ("blk0", "ffn")]
            + [(b, s) for b in ("blk1", "blk2", "blk_mtp")
               for s in ("attn", "moe")])
    for kind, leaf in (("hc_mix", "mix"), ("hc_pre", "pre"),
                       ("hc_post", "post")):
        want = {f"{b}/{s}/hc/{leaf}" for b, s in subs}
        assert scopes[kind] == scopes[f"{kind}_grad"] == want
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("hc_mix") == kinds.count("hc_post_grad") == 8


def test_model_trains_under_amp_on_bf16_streams():
    cfg, main, startup, model, _ = built(
        2, 1, lambda: fluid.optimizer.Adam(3e-3))
    feed = M.make_batch(cfg, 4, 16, seed=1)
    main._amp = True
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    before = snapshot(scope)
    losses = []
    for _ in range(30):
        loss, top_i = exe.run(main, feed=feed, scope=scope,
                              fetch_list=[model["loss"], model["top_i"][0]])
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5 and np.isfinite(losses).all()
    after = snapshot(scope)
    # every mix moved: Phi, its bias and its gates take a gradient
    for p in ("blk0_attn", "blk0_ffn", "blk2_moe", "mtp_attn"):
        for s in HC:
            assert np.abs(after[f"{p}_{s}"] - before[f"{p}_{s}"]).max() > 0
    # the streams between the sublayers are bf16
    block = main.global_block()
    post = [op for op in block.ops if op.type == "hc_post"][3]
    out = exe.run(main, feed=feed, scope=scope, return_numpy=False,
                  fetch_list=[post.outputs["Out"][0]])[0]
    assert str(out.dtype) == "bfloat16" and out.shape == (4, 16, 4 * 32)


def test_one_step_of_adam_is_the_references_gradient_step():
    """Adam's first step moves every parameter by lr * sign(gradient)
    (m / sqrt(v) is +-1 at step one): the program's update against the
    reference's gradient, the mixes' parameters among them."""
    lr = 1e-3
    cfg, main, startup, model, _ = built(
        8, 0, lambda: fluid.optimizer.Adam(lr, epsilon=1e-12))
    feed = M.make_batch(cfg, 2, 16, seed=4)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 9)
    before = snapshot(scope)
    exe.run(main, feed=feed, scope=scope, fetch_list=[model["loss"]])
    after = snapshot(scope)
    grad = highest(jax.grad(
        lambda w_: ref.loss(w_, ref_cfg(0), feed)))(before)
    for n in ("blk0_attn_hc_phi.w", "blk1_moe_hc.bias", "blk2_attn_hc.alpha",
              "blk1_attn_q_b_colp.w", "final_norm.scale"):
        g = np.asarray(grad[n])
        sure = np.abs(g) > 1e-3 * np.abs(g).max()
        np.testing.assert_allclose(
            (after[n] - before[n])[sure], -lr * np.sign(g[sure]), rtol=1e-3,
            err_msg=n)


# --- one chip's share of an expert layer ---------------------------------

D, F, E, K, N = 8, 6, 16, 4, 15
KW = dict(norm_topk_prob=True, score="sigmoid", routed_scale=2.0,
          select_bias=True, shared_gate=False)


def moe_layer(held, shared, x, weights=None, seed=3):
    """(out, rows, {param: value}) of the model's expert layer
    (``layers.topk_moe`` as ``xing4._moe`` calls it); ``weights``: the
    uncut layer's, cut to the held share."""
    return model_test.moe_layer(
        E, K, F, held, x, weights, seed, shared_d_ff=shared, **KW)


def test_the_eight_shares_add_up_to_the_uncut_references_layer():
    """The test that ties the cut to the model: 16 experts as EIGHT
    shares of 2 (the cell's eight chips), top-4, scaling 2. What the
    shares give for their routed experts, with the ungated shared expert
    counted once, is the uncut reference's output for the whole layer;
    every (token, slot) pair is a row of exactly one share."""
    r = np.random.RandomState(0)
    x = r.randn(3, 5, D).astype(np.float32)
    _, _, w = moe_layer(None, F, x)
    w = dict(w, **{"m_router.w": r.randn(D, E).astype(np.float32),
                   "m_router.bias": 0.3 * r.randn(E).astype(np.float32)})
    cfg = dict(n_routed_experts=E, router_experts=E, num_experts_per_tok=K,
               norm_topk_prob=True, routed_scaling_factor=2.0)
    with jax.default_matmul_precision("highest"):
        want, top_i, _ = ref.moe(
            jnp.asarray(x), {f"p_moe_{k[2:]}": v for k, v in w.items()},
            "p", cfg)
    total, pairs = 0.0, 0
    for share in range(8):
        out, rows, _ = moe_layer((2 * share, 2), F if share == 0 else None,
                                 x, w)
        assert (rows == [(np.asarray(top_i) == 2 * share + e).sum()
                         for e in range(2)]).all()
        pairs += rows.sum()
        total = total + out
    assert pairs == N * K
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-7)
    # one share alone is not the layer
    assert np.abs(out - want).max() > 0.1 * np.abs(want).max()
