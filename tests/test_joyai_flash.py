"""The latent-attention decoder (models/joyai_flash.py: MLA with wider
queries and keys than values, a dense first layer, sigmoid-routed held
experts with a selection bias beside an ungated shared expert, one
multi-token-prediction module that reuses the embedding and the head)
against the plain float32 reference (perf/reference/joyai.py, the file
the benchmark's ``correct`` is decided by), forward and gradient, at
tiny sizes on the CPU; the sigmoid router, the bias's step, and the
expert layer as one chip's share of an expert-parallel layer. Gradients
of the reference are ``jax.grad`` of its functions; the program's come
from ``append_backward``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, highest, moved, reference, snapshot
from paddle_tpu import analysis, flags, monitor
from paddle_tpu.models import joyai_flash as M
from paddle_tpu.ops import moe_ops
from perf.reference import joyai as ref

TINY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, intermediate_size=64,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=3.2e7, rms_norm_eps=1e-6, num_experts_per_tok=3,
            moe_intermediate_size=16, n_shared_experts=1,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            num_nextn_predict_layers=1)
# experts 4..7 of the 16 the router scores are this chip's
HELD = (4, 4)
REF_CFG = dict(TINY, n_routed_experts=HELD[1], held_first=HELD[0],
               router_experts=16)
MLA = ["attn_norm.scale", "attn_q_a.w", "attn_q_a_norm.scale",
       "attn_q_b_colp.w", "attn_kv_a.w", "attn_kv_a_norm.scale",
       "attn_kv_b_colp.w", "attn_out_rowp.w"]
MOE = ["moe_norm.scale", "moe_router.w", "moe_gate.w", "moe_up.w",
       "moe_down.w", "moe_shared_gate.w", "moe_shared_up.w",
       "moe_shared_down.w"]


# gains, routers and selection biases away from their initial 1 / 0.02 /
# 0, so that every parameter matters, the routing has no near-ties and
# the bias moves some choices
PERTURB = [((".scale",), moved(0.2)), (("_router.w",), drawn()),
           (("_router.bias",), drawn(0.3))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, optimizer=None):
    cfg = M.JoyaiFlashConfig(**TINY, n_routed_experts=16, held_experts=HELD)
    return (cfg, *model_test.built(M, cfg, seed, optimizer))


def test_model_loss_both_logits_and_every_parameters_gradient():
    cfg, main, startup, model, grads = built(11)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    names = [p.name for p, _ in grads]
    n_moe = 3       # two expert layers of the stack and the MTP module's
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["last_logits"], model["mtp_last_logits"],
        model["lb_loss"], model["mtp_loss"], *model["top_i"],
        *model["expert_rows"], *(g for _, g in grads)])
    want, want_loss, want_g = reference(
        ref, w, REF_CFG, feed, feed["labels"], last=M.LAST_POSITIONS)
    # float32 on both sides; the same mathematics in another order
    # (sorted groups against a dense loop, one 24-wide score product
    # against its two parts, rotate-by-reshape against a 2 x 2 rotation
    # a pair): sums over 8..64 terms
    np.testing.assert_allclose(got[0], want_loss, rtol=2e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[2], want["mtp_logits"], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(got[3], want["lb"], rtol=1e-6)
    # the loss holds the MTP term at its weight (it is no rounding of
    # the main loss: dropping it moves the loss by a tenth of ln(50))
    assert 0.05 * float(got[4]) > 100 * 2e-6 * float(got[0])
    pairs = 0
    for layer in range(n_moe):
        top_i = np.asarray(want["top_i"][layer])
        assert (np.sort(got[5 + layer], -1) == np.sort(top_i, -1)).all()
        rows = got[5 + n_moe + layer]
        assert rows.shape == (4,)
        assert (rows == [(top_i == HELD[0] + e).sum() for e in range(4)]).all()
        pairs += rows.sum()
    assert 0 < pairs < n_moe * 32 * 3       # a share: some pairs, not all
    kinds = ["joyai_tok_emb.w", "lm_head_colp.w", "final_norm.scale",
             "mtp_hnorm.scale", "mtp_enorm.scale", "mtp_eh_proj.w",
             "mtp_final_norm.scale"]
    kinds += [f"blk0_{s}" for s in MLA + [
        "ffn_norm.scale", "ffn_gate_colp.w", "ffn_up_colp.w",
        "ffn_down_rowp.w"]]
    for p in ("blk1", "blk2", "mtp"):
        kinds += [f"{p}_{s}" for s in MLA + MOE]
    # the selection biases are state and no parameter of the loss
    assert sorted(names) == sorted(kinds)
    assert all(f"{p}_moe_router.bias" in w and f"{p}_moe_shared_mix.w"
               not in w for p in ("blk1", "blk2", "mtp"))
    assert w["blk1_moe_gate.w"].shape == (4, 32, 16)      # held, not 16
    assert w["blk1_moe_router.w"].shape == (32, 16)       # scored: all
    g = dict(zip(names, got[5 + 2 * n_moe:]))
    for n in names:
        # the loss is a mean over 32 positions at ln(50): gradients of
        # 1e-7..1e-2; four blocks deep the order of the sums shows in
        # the fifth digit of the largest entry of a tensor
        scale = np.abs(want_g[n]).max()
        np.testing.assert_allclose(g[n], want_g[n], rtol=2e-3,
                                   atol=1e-4 * scale + 1e-9, err_msg=n)

    # the table and the head are ONE parameter with two uses each: the
    # gradient is the sum of both uses' (each alone is not it)
    def weighted(w_, main_w, mtp_w):
        out = ref.forward(w_, REF_CFG, feed["input_ids"], feed["labels"])
        lbl = jnp.asarray(feed["labels"])
        return (main_w * jnp.mean(ref._ce(out["logits"], lbl))
                + mtp_w * ref.MTP_LAMBDA * jnp.mean(
                    ref._ce(out["mtp_logits"][:, :-1], lbl[:, 1:])))

    part = highest(jax.grad(weighted))      # (one executable for both)
    only_main, only_mtp = part(w, 1.0, 0.0), part(w, 0.0, 1.0)
    for n in ("joyai_tok_emb.w", "lm_head_colp.w"):
        both = only_main[n] + only_mtp[n]
        scale = np.abs(both).max()
        assert np.abs(only_mtp[n]).max() > 0.01 * scale, n
        # (the balance loss reaches the table through the routers: its
        # 1e-4 is inside the tolerance's floor)
        np.testing.assert_allclose(g[n], both, rtol=2e-3,
                                   atol=2e-3 * scale, err_msg=n)
        assert np.abs(g[n] - only_main[n]).max() > 0.01 * scale, n


def test_model_trains_under_amp_and_moves_the_bias_without_a_gradient():
    cfg, main, startup, model, _ = built(
        2, lambda: fluid.optimizer.Adam(3e-3))
    feed = M.make_batch(cfg, 4, 16, seed=1)
    main._amp = True
    ops = main.global_block().ops
    updates = [op for op in ops if op.type == "moe_bias_update"]
    assert [(op.role, op.namescope) for op in updates] == [
        ("opt", f"{b}/moe/router") for b in ("blk1", "blk2", "blk_mtp")]
    # only the router (and its grad op, which runs it again) reads a
    # bias, only its update writes one, and no gradient of one exists
    for op in ops:
        read = [n for v in op.inputs.values() for n in v]
        wrote = [n for v in op.outputs.values() for n in v]
        assert not any("_router.bias@GRAD" in n for n in read + wrote)
        if op.type not in ("moe_bias_update", "moe_router",
                           "moe_router_grad"):
            assert not any("_router.bias" in n for n in read), op.type
        if op.type != "moe_bias_update":
            assert not any("_router.bias" in n for n in wrote), op.type
    kinds = [op.type for op in ops]
    assert kinds.count("scaled_dot_product_attention") == 4 \
        and kinds.count("scaled_dot_product_attention_grad") == 4 \
        and kinds.count("moe_router") == 3
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(30):
        before = np.asarray(scope.find_var("blk1_moe_router.bias")).copy()
        loss, top_i = exe.run(main, feed=feed, scope=scope,
                              fetch_list=[model["loss"], model["top_i"][0]])
        losses.append(float(loss))
        count = np.bincount(np.asarray(top_i).ravel(), minlength=16)
        np.testing.assert_allclose(
            np.asarray(scope.find_var("blk1_moe_router.bias")),
            before + cfg.bias_update_rate * np.sign(count.mean() - count),
            atol=1e-7)
    assert losses[-1] < losses[0] - 0.5 and np.isfinite(losses).all()


def test_the_eval_clone_never_moves_the_bias():
    cfg = M.JoyaiFlashConfig(**TINY, n_routed_experts=16, held_experts=HELD)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 4
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        evalp = main.clone(for_test=True)
        # a training clone keeps the updates its optimizer will append
        assert len(main.clone()._step_updates) == 3
        fluid.optimizer.Adam(1e-3).minimize(model["loss"])
    assert evalp._step_updates == [] == main._step_updates
    assert "moe_bias_update" not in [op.type for op in
                                     evalp.global_block().ops]
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    exe.run(evalp, feed=M.make_batch(cfg, 2, 16), scope=scope,
            fetch_list=[model["loss"]])
    assert not np.asarray(scope.find_var("mtp_moe_router.bias")).any()


# --- the sigmoid router ------------------------------------------------------

N, D, F, E, K = 15, 8, 6, 16, 4


def router(x, w, bias=None, **attrs):
    ins = {"X": [jnp.asarray(x)], "W": [jnp.asarray(w)]}
    if bias is not None:
        ins["Bias"] = [jnp.asarray(bias)]
    out = moe_ops._moe_router(ins, {"k": K, **attrs})
    return {k: np.asarray(v[0]) for k, v in out.items()}


def test_sigmoid_router_bias_moves_the_choice_and_never_the_weight():
    r = np.random.RandomState(0)
    x = r.randn(3, 5, D).astype(np.float32)
    w = r.randn(D, E).astype(np.float32)
    bias = np.zeros(E, np.float32)
    bias[[2, 9]] = 5.0          # above any sigmoid: always chosen
    kw = dict(score="sigmoid", norm_topk=True, routed_scale=2.5)
    plain, lifted = router(x, w, **kw), router(x, w, bias, **kw)
    s = np.asarray(jax.nn.sigmoid(x.reshape(-1, D) @ w))
    for got, pick in ((plain, s), (lifted, s + bias)):
        want_i = np.argsort(-pick, -1)[:, :K]
        assert (np.sort(got["TopI"], -1) == np.sort(want_i, -1)).all()
        chosen = np.take_along_axis(s, got["TopI"], -1)     # s, not s + b
        np.testing.assert_allclose(
            got["TopW"], 2.5 * chosen / chosen.sum(-1, keepdims=True),
            rtol=1e-5)
        np.testing.assert_allclose(got["TopW"].sum(-1), 2.5, rtol=1e-5)
    assert all({2, 9} <= set(row) for row in lifted["TopI"])
    assert not all({2, 9} <= set(row) for row in plain["TopI"])
    # without the renormalisation the weights are the scores, scaled
    raw = router(x, w, bias, score="sigmoid", routed_scale=2.0)
    np.testing.assert_allclose(
        raw["TopW"], 2.0 * np.take_along_axis(s, raw["TopI"], -1), rtol=1e-5)
    # the sequence-wise balance loss, a row of x at a time
    count = np.stack([np.bincount(row.ravel(), minlength=E) for row in
                      lifted["TopI"].reshape(3, 5 * K)])
    p = (s / s.sum(-1, keepdims=True)).reshape(3, 5, E).mean(1)
    np.testing.assert_allclose(
        lifted["LBLoss"], ((E / (K * 5)) * count * p).sum(-1).mean(),
        rtol=1e-5)
    # one group of experts is no group step; more are not built
    one = router(x, w, bias, n_group=1, topk_group=1, **kw)
    assert all((one[k] == lifted[k]).all() for k in one)
    with pytest.raises(NotImplementedError, match="n_group"):
        router(x, w, bias, n_group=4, topk_group=2, **kw)


def test_softmax_router_is_as_it_was():
    r = np.random.RandomState(1)
    x = r.randn(N, D).astype(np.float32)
    w = r.randn(D, E).astype(np.float32)
    got = router(x, w, norm_topk=True)
    probs = np.asarray(jax.nn.softmax(x @ w, -1))
    top_i = np.argsort(-probs, -1)[:, :K]
    assert (got["TopI"] == top_i).all()
    top_w = np.take_along_axis(probs, top_i, -1)
    np.testing.assert_allclose(got["TopW"],
                               top_w / top_w.sum(-1, keepdims=True), rtol=1e-5)
    count = np.bincount(top_i.ravel(), minlength=E)
    np.testing.assert_allclose(
        got["LBLoss"], E * (count / N * probs.mean(0)).sum(), rtol=1e-5)
    with pytest.raises(ValueError, match="Bias"):
        router(x, w, np.zeros(E, np.float32))


def test_router_dispatch_counter_names_the_form():
    flags.set_flags({"telemetry": True})
    try:
        cfg, main, startup, model, _ = built(5)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        exe.run(main, feed=M.make_batch(cfg, 1, 16), scope=scope,
                fetch_list=[model["loss"]])
        rows = monitor.snapshot()["pt_moe_router_dispatch_total"]["values"]
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    assert {tuple(sorted(r["labels"].items())) for r in rows} == {
        (("bias", "1"), ("experts", "16"), ("input", "own"), ("k", "3"),
         ("score", "sigmoid"))}
    assert sum(r["value"] for r in rows) >= 3


def test_bias_update_is_the_papers_step_and_has_no_gradient():
    from paddle_tpu.core.registry import get_op_def

    r = np.random.RandomState(3)
    bias = r.randn(E).astype(np.float32)
    top_i = r.randint(0, E, (N, K)).astype(np.int32)
    top_i[:, 0] = 5             # expert 5 overloaded, some never chosen
    got = np.asarray(moe_ops._moe_bias_update(
        {"Bias": [jnp.asarray(bias)], "TopI": [jnp.asarray(top_i)]},
        {"gamma": 0.001})["BiasOut"][0])
    count = np.bincount(top_i.ravel(), minlength=E)
    np.testing.assert_allclose(
        got, bias + 0.001 * np.sign(count.mean() - count), atol=1e-7)
    assert got[5] < bias[5] and (got[count == 0] > bias[count == 0]).all()
    assert get_op_def("moe_bias_update").no_grad


# --- one chip's share of an expert layer ---------------------------------

KW = dict(norm_topk_prob=True, score="sigmoid", routed_scale=2.5,
          select_bias=True, shared_gate=False)


def moe_layer(held, shared, x, weights=None, seed=3):
    """(out, rows, d loss / d x, {param: value}) of a sigmoid-routed
    topk_moe layer; ``weights``: the uncut layer's, cut to the held
    share."""
    return model_test.moe_layer(
        E, K, F, held, x, weights, seed, grad=True,
        shared_d_ff=shared, **KW)


def test_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """E = 16 as four shares of 4: what the shares give for their routed
    experts, plus the ungated shared expert once, is the uncut layer's
    output; every (token, slot) pair is a row of exactly one share."""
    r = np.random.RandomState(0)
    x = r.randn(3, 5, D).astype(np.float32)
    _, _, _, w = moe_layer(None, F, x)
    w = dict(w, **{"m_router.w": r.randn(D, E).astype(np.float32),
                   "m_router.bias": 0.3 * r.randn(E).astype(np.float32)})
    full, rows, _, w = moe_layer(None, F, x, w)
    assert rows.shape == (E,) and rows.sum() == N * K
    assert "m_shared_mix.w" not in w and "m_shared_down.w" in w
    total, held_rows = 0.0, []
    for i in range(4):
        out, r_, _, _ = moe_layer((4 * i, 4), F if i == 0 else None, x, w)
        assert (r_ == rows[4 * i:4 * i + 4]).all()
        held_rows.append(r_.sum())
        total = total + out
    assert sum(held_rows) == N * K and min(held_rows) > 0
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-8)
    # and the uncut layer is the reference's
    cfg = dict(n_routed_experts=E, router_experts=E, num_experts_per_tok=K,
               norm_topk_prob=True, routed_scaling_factor=2.5)
    want, _, _ = ref.moe(jnp.asarray(x), {f"p_moe_{k[2:]}": v
                                          for k, v in w.items()}, "p", cfg)
    np.testing.assert_allclose(full, want, rtol=1e-5, atol=1e-8)


def test_all_pairs_held_drops_no_token():
    """A selection bias that sends EVERY pair to held experts (all N * K
    rows of the buffer are live): the layer is the reference's, output
    and the tokens' gradient."""
    r = np.random.RandomState(4)
    x = r.randn(N, D).astype(np.float32)
    _, _, _, w = moe_layer((8, 4), None, x)
    bias = np.zeros(E, np.float32)
    bias[8:12] = 3.0
    w = dict(w, **{"m_router.w": r.randn(D, E).astype(np.float32),
                   "m_router.bias": bias})
    out, rows, gx, w = moe_layer((8, 4), None, x, w)
    assert rows.sum() == N * K
    cfg = dict(n_routed_experts=4, held_first=8, router_experts=E,
               num_experts_per_tok=K, norm_topk_prob=True,
               routed_scaling_factor=2.5)
    zero = np.zeros((D, F), np.float32)
    wr = {f"p_moe_{k[2:]}": v for k, v in w.items()}
    wr.update({"p_moe_shared_gate.w": zero, "p_moe_shared_up.w": zero,
               "p_moe_shared_down.w": zero.T})

    def layer(xs):
        return ref.moe(xs[None], wr, "p", cfg)[0][0]

    np.testing.assert_allclose(out, layer(jnp.asarray(x)), rtol=1e-5,
                               atol=1e-8)
    want_gx = jax.grad(lambda xs: jnp.sum(layer(xs) ** 2))(jnp.asarray(x))
    np.testing.assert_allclose(gx, want_gx, rtol=1e-4, atol=1e-8)


# --- the latent block lives in models/decoder.py (PR 64) ----------------


def test_the_moved_latent_block_builds_the_program_it_built():
    """``_latent_attention`` is ``decoder.latent_attention`` with the
    family's query low rank and rotation: the block's ops, their scopes
    and their order are what this file's builder wrote before the move
    (the cell's compiled step is keyed by the program), and the family's
    tiny program still digests to what it did at the parent commit."""
    _, main, startup, _, _ = built(1)
    block = [(op.attrs.get("op_namescope").strip("/"), op.type)
             for op in main.global_block().ops
             if "blk0/attn" in (op.attrs.get("op_namescope") or "")
             and not op.type.endswith("_grad")]
    p = "blk0/attn"
    assert block == [
        (p, "rms_norm"), (f"{p}/q_lora", "mul"), (f"{p}/q_lora", "rms_norm"),
        (f"{p}/q_lora", "mul"), (f"{p}/kv_lora", "mul"),
        (f"{p}/kv_lora", "split"), (f"{p}/kv_lora", "rms_norm"),
        (f"{p}/kv_lora", "mul"), (f"{p}/rope", "reshape2"),
        (f"{p}/rope", "transpose2"), (f"{p}/rope", "split"),
        (f"{p}/rope", "reshape2"), (f"{p}/rope", "transpose2"),
        (f"{p}/rope", "split"), (f"{p}/rope", "unsqueeze2"),
        (f"{p}/rope", "rotary_embedding"),
        # (since PR 70 the sdpa op takes q and k in two parts: no concat
        # of a wide q, no expand of the shared key head, no concat of k)
        (f"{p}/core", "scaled_dot_product_attention"),
        (f"{p}/out", "transpose2"), (f"{p}/out", "reshape2"),
        (f"{p}/out", "mul"), (p, "elementwise_add")]
    rope = next(op for op in main.global_block().ops
                if op.type == "rotary_embedding")
    assert rope.attrs["theta"] == TINY["rope_theta"] \
        and rope.attrs["interleaved"]
    assert {q.name for q in main.all_parameters()
            if q.name.startswith("blk0_attn")} == {
        f"blk0_{s}" for s in MLA}
    assert M._latent_attention.__code__.co_names[:2] == (
        "decoder", "latent_attention")
