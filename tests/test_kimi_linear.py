"""The Kimi Linear decoder (models/kimi_linear.py: Kimi Delta Attention
layers, latent attention with no positional embedding, a dense first
layer, then sigmoid-routed held experts beside a shared one) against the
plain float32 reference (perf/reference/kimilinear.py, the file the
benchmark's ``correct`` is decided by), loss, logits and every
parameter's gradient, at tiny sizes on the CPU; the mixers read from the
two published lists; the 32 shares of an expert layer; the moved latent
block; the initialisers. Gradients of the reference are ``jax.grad`` of
its functions; the program's come from ``append_backward``."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, moved, reference, snapshot
from paddle_tpu import analysis, layers
from paddle_tpu.models import decoder, joyai_flash
from paddle_tpu.models import kimi_linear as M
from perf.reference import kimilinear as ref

# published layers 1-5 in small: KDA + dense, KDA, KDA, MLA, KDA
LINEAR = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
          "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4}
TINY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=5,
            first_k_dense_replace=1, intermediate_size=64,
            num_attention_heads=4, q_lora_rank=None, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rms_norm_eps=1e-5, linear_attn_config=LINEAR,
            num_experts_per_token=3, moe_intermediate_size=16,
            num_shared_experts=1, moe_renormalize=True,
            routed_scaling_factor=2.446)
# experts 4..7 of the 16 the router scores are this chip's
HELD = (4, 4)
REF_CFG = dict(TINY, num_experts=HELD[1], held_first=HELD[0],
               router_experts=16)
KDA = ["kda_norm.scale", "kda_qkv_colp.w", "kda_fgb.w", "kda_f_b_colp.w",
       "kda_g_b_colp.w", "kda_conv.w", "kda_A_log", "kda_dt_bias",
       "kda_onorm.scale", "kda_out_rowp.w"]
MLA = ["attn_norm.scale", "attn_q_colp.w", "attn_kv_a.w",
       "attn_kv_a_norm.scale", "attn_kv_b_colp.w", "attn_out_rowp.w"]
MOE = ["moe_norm.scale", "moe_router.w", "moe_gate.w", "moe_up.w",
       "moe_down.w", "moe_shared_gate.w", "moe_shared_up.w",
       "moe_shared_down.w"]


# gains, taps, routers and selection biases away from their initial 1 /
# 0.02 / 0, so that every parameter matters, the routing has no
# near-ties and the bias moves some choices; the low-rank pairs' second
# matrices large enough for the decay and the gate to move
PERTURB = [((".scale",), moved(0.2)),
           (("_router.w", "_b_colp.w"), drawn()),
           (("_router.bias",), drawn(0.3)),
           (("_conv.w", "_kda_fgb.w"), drawn(0.5))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, optimizer=None, **over):
    cfg = M.KimiLinearConfig(**dict(TINY, **over), num_experts=16,
                             held_experts=HELD, kda_chunk=8)
    return (cfg, *model_test.built(M, cfg, seed, optimizer))


def test_model_loss_logits_and_every_parameters_gradient():
    cfg, main, startup, model, grads = built(11)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    names = [p.name for p, _ in grads]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["last_logits"], model["lb_loss"],
        *model["top_i"], *model["expert_rows"], *(g for _, g in grads)])
    want, want_loss, want_g = reference(ref, w, REF_CFG, feed,
                                        last=M.LAST_POSITIONS)
    # float32 on both sides; the same mathematics in another order (the
    # chunkwise rule with its halved decays against the recurrence,
    # sorted groups against a dense loop, fused projections)
    np.testing.assert_allclose(got[0], want_loss, rtol=2e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[2], want["lb"], rtol=1e-6)
    pairs = 0
    for layer in range(4):
        top_i = np.asarray(want["top_i"][layer])
        assert (np.sort(got[3 + layer], -1) == np.sort(top_i, -1)).all()
        rows = got[7 + layer]
        assert (rows == [(top_i == HELD[0] + e).sum() for e in range(4)]).all()
        pairs += rows.sum()
    assert 0 < pairs < 4 * 32 * 3       # a share: some pairs, not all
    kinds = ["kimilinear_tok_emb.w", "lm_head_colp.w", "final_norm.scale"]
    for i in range(5):
        kinds += [f"blk{i}_{s}" for s in (MLA if i == 3 else KDA) + (
            ["ffn_norm.scale", "ffn_gate_colp.w", "ffn_up_colp.w",
             "ffn_down_rowp.w"] if i == 0 else MOE)]
    assert sorted(names) == sorted(kinds)     # the bias takes no gradient
    assert w["blk1_moe_gate.w"].shape == (4, 32, 16)      # held, not 16
    assert w["blk1_moe_router.w"].shape == (32, 16)       # scored: all
    assert w["blk0_kda_dt_bias"].shape == (4, 8)          # a feature
    assert w["blk0_kda_A_log"].shape == (4,)              # a head
    g = dict(zip(names, got[11:]))
    for n in names:
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=2e-3,
                                   atol=1e-4 * scale + 1e-9, err_msg=n)


def test_mixers_come_from_the_published_lists_and_nothing_rotates():
    cfg, main, _, _, _ = built(1, optimizer=lambda: fluid.optimizer.Adam(
        1e-3))
    assert [cfg.is_kda(i) for i in range(5)] == [True, True, True, False,
                                                 True]
    # the published tail is no period: 25 and 26 KDA, 27 latent attention
    pub = M.kimi_linear_48b_a3b()
    assert [pub.is_kda(i) for i in (23, 24, 25, 26)] == [False, True, True,
                                                         False]
    assert sum(pub.is_kda(i) for i in range(27)) == 20
    with pytest.raises(AssertionError):       # a layer in neither list
        M.KimiLinearConfig(num_hidden_layers=3, linear_attn_config=dict(
            LINEAR, kda_layers=[1, 3], full_attn_layers=[]))
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("gated_delta_rule") == 4 \
        and kinds.count("gated_delta_rule_grad") == 4 \
        and kinds.count("causal_conv1d") == 4 \
        and kinds.count("scaled_dot_product_attention") == 1
    assert not [k for k in kinds if "rotary" in k or "rope" in k]
    scopes = {op.attrs.get("op_namescope") for op in main.global_block().ops}
    scopes = {s.strip("/") for s in scopes if s}
    for want in ("embed", "blk0/kda/proj", "blk0/kda/conv", "blk0/kda/rule",
                 "blk0/kda/gate_norm", "blk0/kda/out", "blk0/ffn",
                 "blk3/attn/q", "blk3/attn/kv_lora", "blk3/attn/rope",
                 "blk3/attn/core", "blk3/attn/out", "blk4/moe/router",
                 "blk4/moe/experts", "final_norm", "loss_head"):
        assert any(s.endswith(want) for s in scopes), (want, sorted(scopes))
    assert not any("q_lora" in s for s in scopes)
    gates = [op for op in main.global_block().ops
             if op.type == "gated_rms_norm"]
    assert {op.attrs.get("gate_act") for op in gates} == {"sigmoid"}


def test_model_trains_under_amp_and_moves_the_bias_without_a_gradient():
    cfg, main, startup, model, _ = built(
        2, optimizer=lambda: fluid.optimizer.Adam(3e-3))
    feed = M.make_batch(cfg, 4, 16, seed=1)
    main._amp = True
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    bias = np.asarray(scope.find_var("blk1_moe_router.bias")).copy()
    losses = [float(exe.run(main, feed=feed, fetch_list=[model["loss"]],
                            scope=scope)[0]) for _ in range(30)]
    assert losses[-1] < losses[0] - 0.5 and np.isfinite(losses).all()
    moved = np.asarray(scope.find_var("blk1_moe_router.bias")) - bias
    assert np.abs(moved).max() > 0


def test_initialisers_are_the_familys():
    """A_log = log U(1, 16) a head; dt_bias the inverse softplus of a
    step size log-uniform in [1e-3, 0.1] a feature."""
    cfg, main, startup, _, _ = built(3, linear_attn_config=dict(
        LINEAR, num_heads=16, head_dim=32), num_attention_heads=4)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    a = np.exp(np.asarray(scope.find_var("blk0_kda_A_log")))
    dt = np.log1p(np.exp(np.asarray(scope.find_var("blk0_kda_dt_bias"))))
    assert a.shape == (16,) and dt.shape == (16, 32)
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.max() > 4 * a.min()
    assert 0.99e-3 <= dt.min() < 3e-3 and 0.03 < dt.max() <= 0.101
    # log-uniform: the median near the geometric middle, 0.01
    assert 0.005 < np.median(dt) < 0.02


# --- one chip's share of an expert layer ---------------------------------

N, D, F, E, K = 40, 8, 6, 256, 8
KW = dict(norm_topk_prob=True, score="sigmoid", routed_scale=2.446,
          select_bias=True, shared_gate=False)


def moe_layer(held, shared, x, weights=None, seed=3):
    """(out, rows, {param: value}) of the family's topk_moe layer;
    ``weights``: the uncut layer's, cut to the held share."""
    return model_test.moe_layer(
        E, K, F, held, x, weights, seed, shared_d_ff=shared, **KW)


def test_the_32_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """The deployment in small: 256 experts as 32 shares of 8, 8 a
    token. What the shares give for their routed experts, with the
    ungated shared expert counted once, adds up to the uncut layer's
    output; every (token, slot) pair is a row of exactly one share; and
    the uncut layer is the reference's, whose loop over the held experts
    takes the same (first, count)."""
    r = np.random.RandomState(0)
    x = r.randn(N, D).astype(np.float32)
    _, _, w = moe_layer(None, F, x)
    w = dict(w, **{"m_router.w": r.randn(D, E).astype(np.float32),
                   "m_router.bias": 0.3 * r.randn(E).astype(np.float32)})
    full, rows, w = moe_layer(None, F, x, w)
    assert rows.shape == (E,) and rows.sum() == N * K
    total, held_rows = 0.0, []
    for i in range(32):
        out, r_, _ = moe_layer((8 * i, 8), F if i == 0 else None, x, w)
        assert (r_ == rows[8 * i:8 * i + 8]).all()
        held_rows.append(r_.sum())
        total = total + out
    assert sum(held_rows) == N * K
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-8)
    cfg = dict(num_experts=E, router_experts=E, num_experts_per_token=K,
               moe_renormalize=True, routed_scaling_factor=2.446)
    named = {f"p_moe_{k[2:]}": jnp.asarray(v) for k, v in w.items()}
    want, _, _ = ref.moe(jnp.asarray(x)[None], named, "p", cfg)
    np.testing.assert_allclose(full, want[0], rtol=1e-5, atol=1e-8)
    # one share through the reference: the same (first, count)
    share, _, _ = ref.moe(jnp.asarray(x)[None], {
        k: (v[24:32] if v.ndim == 3 and v.shape[0] == E else v)
        for k, v in named.items()}, "p", cfg, share=(24, 8))
    out, _, _ = moe_layer((24, 8), F, x, w)
    np.testing.assert_allclose(out, share[0], rtol=1e-5, atol=1e-8)


# --- the latent block both families take -------------------------------------


def test_there_is_one_latent_attention_builder():
    models = os.path.dirname(M.__file__)
    for name in ("kimi_linear", "joyai_flash"):
        with open(os.path.join(models, f"{name}.py")) as f:
            tree = ast.parse(f.read())
        fn = next(n for n in tree.body if isinstance(n, ast.FunctionDef)
                  and n.name == "_latent_attention")
        calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)]
        assert len(calls) == 1 and ast.unparse(calls[0].func) == \
            "decoder.latent_attention", name
    assert joyai_flash.decoder is decoder


def latent_ops(**kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16, 32], dtype="float32")
        decoder.latent_attention(
            x, "blk0", heads=4, nope=16, rope=8, dv=16, kv_lora_rank=16,
            hidden=32, eps=1e-5, **kw)
    return main


def test_no_low_rank_and_no_rotation_leave_the_ops_out():
    kinds = [op.type for op in latent_ops().global_block().ops]
    with_both = [op.type for op in latent_ops(
        q_lora_rank=24, rope_theta=1e4).global_block().ops]
    assert "rotary_embedding" in with_both \
        and "rotary_embedding" not in kinds
    # no rotation: the same three splits (kva, q, kv: since PR 70 the
    # sdpa op takes q and k in two parts either way), and neither form
    # copies the shared head or assembles a wide q or k
    assert with_both.count("split") == kinds.count("split") == 3
    assert not {"concat", "expand"} & set(with_both + kinds)
    # no low rank: one projection and one norm fewer
    assert with_both.count("mul") - kinds.count("mul") == 1
    assert with_both.count("rms_norm") - kinds.count("rms_norm") == 1
    params = {p.name for p in latent_ops().all_parameters()}
    assert params == {f"blk0_{s}" for s in MLA}


def test_reference_rotation_control_moves_the_logits():
    """A model that quietly applied RoPE to the 64 shared features would
    not be this one: the reference's ``rotate`` control differs from the
    reference at once."""
    cfg, _, startup, _, _ = built(5)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 6)
    w = snapshot(scope)
    ids = M.make_batch(cfg, 2, 16, seed=3)["input_ids"]
    with jax.default_matmul_precision("highest"):
        plain = ref.forward(w, REF_CFG, ids)["logits"]
        turned = ref.forward(w, REF_CFG, ids, rotate=1e4)["logits"]
    # position 0 is turned by the angle 0: the same; later rows differ
    np.testing.assert_allclose(plain[:, 0], turned[:, 0], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(plain[:, 4:] - turned[:, 4:]).max() > 1e-3
