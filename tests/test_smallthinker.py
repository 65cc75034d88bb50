"""The sliding-window / global hybrid decoder (models/smallthinker.py:
window layers with rotary positions to one global layer without any,
28 / 4-style grouped heads, a router that reads the attention's input,
held ReGLU experts) against the plain float32 reference
(perf/reference/smallthinker.py, the file the benchmark's ``correct`` is
decided by), forward and gradient, at tiny sizes on the CPU; the control
that a program without the window does not agree; ReGLU experts and a
router on another input as ops; the expert layer as one chip's share.
Gradients of the reference are ``jax.grad`` of its functions; the
program's come from ``append_backward``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, highest, moved, reference, snapshot
from paddle_tpu import analysis, flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.models import smallthinker as M
from paddle_tpu.ops import moe_ops
from perf.reference import smallthinker as ref

# 16 positions, a window of 5: window layers forget from position 5 on
TINY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=7, num_key_value_heads=1, head_dim=8,
            rope_theta=1.5e6, rms_norm_eps=1e-6, sliding_window_size=5,
            sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
            moe_num_active_primary_experts=3, moe_ffn_hidden_size=16,
            norm_topk_prob=True)
# experts 2..5 of the 16 the router scores are this chip's
HELD = (2, 4)
REF_CFG = dict(TINY, moe_num_primary_experts=HELD[1], held_first=HELD[0],
               router_experts=16)
LAYER = ["attn_norm.scale", "attn_qkv_colp.w", "attn_out_rowp.w",
         "moe_norm.scale", "moe_router.w", "moe_gate.w", "moe_up.w",
         "moe_down.w"]


# gains and routers away from their initial 1 / 0.02, so that every
# parameter matters and the routing has no near-ties; the attention
# projections larger, so that what a query sees moves its output
PERTURB = [((".scale",), moved(0.2)), (("_router.w",), drawn()),
           (("_attn_qkv_colp.w",), drawn(0.3))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def built(seed, optimizer=None, **overrides):
    cfg = M.SmallThinkerConfig(**dict(TINY, **overrides),
                               moe_num_primary_experts=16, held_experts=HELD)
    return (cfg, *model_test.built(M, cfg, seed, optimizer))


def run_against_reference(main, startup, model, grads, feed):
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    perturb(scope, 12)
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["logits"], model["lb_loss"], *model["top_i"],
        *model["expert_rows"], *(g for _, g in grads)])
    return (w, got, *reference(ref, w, REF_CFG, feed))


def test_model_loss_logits_and_every_parameters_gradient(monkeypatch):
    cfg, main, startup, model, grads = built(11)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    w, got, want, want_loss, want_g = run_against_reference(
        main, startup, model, grads, feed)
    names = [p.name for p, _ in grads]
    # float32 on both sides; the same mathematics in another order
    # (sorted groups against a dense loop, softmax over all then
    # renormalised against top-k then softmax): sums over 8..64 terms
    np.testing.assert_allclose(got[0], want_loss, rtol=2e-6)
    np.testing.assert_allclose(got[1], want["logits"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[2], want["lb"], rtol=1e-6)
    pairs = 0
    for layer in range(4):
        top_i = np.asarray(want["top_i"][layer])
        assert (np.sort(got[3 + layer], -1) == np.sort(top_i, -1)).all()
        rows = got[7 + layer]
        assert rows.shape == (4,)
        assert (rows == [(top_i == HELD[0] + e).sum() for e in range(4)]).all()
        pairs += rows.sum()
    assert 0 < pairs < 4 * 32 * 3           # a share: some pairs, not all
    kinds = ["smallthinker_tok_emb.w", "lm_head_colp.w", "final_norm.scale"]
    kinds += [f"blk{i}_{s}" for i in range(4) for s in LAYER]
    assert sorted(names) == sorted(kinds)
    assert w["blk1_moe_gate.w"].shape == (4, 32, 16)      # held, not 16
    assert w["blk1_moe_router.w"].shape == (32, 16)       # scored: all
    # q and o are heads x head_dim wide (56), not the hidden size (32)
    assert w["blk0_attn_qkv_colp.w"].shape == (32, 56 + 2 * 8)
    assert w["blk0_attn_out_rowp.w"].shape == (56, 32)
    g = dict(zip(names, got[11:]))
    for n in names:
        scale = np.abs(want_g[n]).max()
        assert scale > 0, n
        np.testing.assert_allclose(g[n], want_g[n], rtol=2e-3,
                                   atol=1e-4 * scale + 1e-9, err_msg=n)

    # the router's gradient reaches norm_in's gain: with the router's
    # input cut off from the gradient the reference gives another one
    route = ref.route
    monkeypatch.setattr(ref, "route", lambda r, *a, **k: route(
        jax.lax.stop_gradient(r), *a, **k))
    cut_g = highest(jax.grad(lambda w_: ref.loss(w_, REF_CFG, feed)))(w)
    for i in range(4):
        n = f"blk{i}_attn_norm.scale"
        scale = np.abs(want_g[n]).max()
        # (a hundredth of the gain's gradient at these sizes: thirty
        # times the floor the program's gradient was just held to)
        assert np.abs(np.asarray(cut_g[n]) - g[n]).max() > 3e-3 * scale, n


def test_ops_of_a_layer_by_kind():
    """Global layers append no rotary op and an sdpa without a window;
    window layers rotate and carry the window; every router reads the
    attention's normalised input, dispatch and the experts another."""
    _, main, _, _, _ = built(3)
    ops = main.global_block().ops
    fwd = [op for op in ops if op.role != "backward"
           and not op.type.endswith("_grad")]
    rot = [op.namescope for op in fwd if op.type == "rotary_embedding"]
    assert rot == [f"blk{i}/attn/rope" for i in (1, 2, 3)]
    sdpa = [(op.namescope, op.attrs.get("window"))
            for op in fwd if op.type == "scaled_dot_product_attention"]
    assert sdpa == [("blk0/attn/core", None), ("blk1/attn/swa", 5),
                    ("blk2/attn/swa", 5), ("blk3/attn/swa", 5)]
    for i in range(4):
        router = next(op for op in fwd if op.type == "moe_router"
                      and op.namescope == f"blk{i}/moe/router")
        dispatch = next(op for op in fwd if op.type == "moe_dispatch"
                        and op.namescope == f"blk{i}/moe/dispatch")
        experts = next(op for op in fwd if op.type == "moe_experts"
                       and op.namescope == f"blk{i}/moe/experts")
        assert router.inputs["X"] != dispatch.inputs["X"]
        assert router.attrs["input"] == "other"
        assert experts.attrs["act"] == "relu"
        qkv = next(op for op in fwd if op.type == "mul"
                   and op.namescope == f"blk{i}/attn/qkv")
        assert router.inputs["X"] == qkv.inputs["X"]
    grads = [op.type for op in ops]
    assert grads.count("scaled_dot_product_attention_grad") == 4
    assert grads.count("moe_experts_grad") == 4


def test_a_program_without_the_window_fails_against_the_reference():
    """The control: four GLOBAL layers (the window dropped, all else the
    same) must not agree with the reference, by loss and by logits; and
    the reference with its window dropped agrees with THAT program."""
    cfg, main, startup, model, grads = built(
        11, sliding_window_layout=(0, 0, 0, 0))
    feed = M.make_batch(cfg, 2, 16, seed=9)
    w, got, want, want_loss, _ = run_against_reference(
        main, startup, model, grads, feed)
    logit_err = np.abs(got[1] - np.asarray(want["logits"])).max()
    assert logit_err > 100 * 2e-5 and logit_err > 1e-2 * np.abs(got[1]).max()
    assert abs(float(got[0]) - float(want_loss)) > 100 * 2e-6 * float(got[0])
    dropped = highest(lambda w_: ref.forward(
        w_, REF_CFG, feed["input_ids"], no_window=True))(w)
    np.testing.assert_allclose(got[1], dropped["logits"], rtol=2e-4,
                               atol=2e-5)


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


# --- ReGLU experts and the router's input as ops --------------------------

N, D, F, E, K = 15, 8, 6, 16, 4


def experts_ops(act, held, seed=0):
    """(forward outputs, grad outputs, the plain form's (ys, vjp)) of the
    moe_experts op pair on sorted rows."""
    r = np.random.RandomState(seed)
    e = held[1] if held else E
    m = N * K
    rows = np.zeros(e, np.int32)
    live = m if not held else 22
    for i in r.randint(0, e, live):
        rows[i] += 1
    xs = r.randn(m, D).astype(np.float32)
    xs[live:] = 0.0
    wg, wu = (r.randn(e, D, F).astype(np.float32) for _ in range(2))
    wd = r.randn(e, F, D).astype(np.float32)
    g = r.randn(m, D).astype(np.float32)
    g[live:] = 0.0
    attrs = {} if act is None else {"act": act}
    ins = {"Xs": [jnp.asarray(xs)], "Rows": [jnp.asarray(rows)],
           "WGate": [jnp.asarray(wg)], "WUp": [jnp.asarray(wu)],
           "WDown": [jnp.asarray(wd)]}
    if held:
        attrs.update(num_experts=E, held_first=held[0], held_count=e)
        # the grad op gathers Xs again from the tokens: rows r < live of
        # pair order[r]; here every row is its own token
        ins.update(X=[jnp.asarray(xs)],
                   Order=[jnp.arange(m, dtype=jnp.int32)])
    out = moe_ops._moe_experts(ins, attrs)
    grad = moe_ops._moe_experts_grad(
        {**ins, "Gate": out["Gate"], "Up": out["Up"],
         "GRAD::Ys": [jnp.asarray(g)]}, attrs)
    expert = np.repeat(np.arange(e), rows)
    fn = {"relu": jax.nn.relu, "silu": jax.nn.silu, None: jax.nn.silu}[act]

    def plain(xs_, wg_, wu_, wd_):
        x = xs_[:live]
        h = fn(jnp.einsum("md,mdf->mf", x, wg_[expert])) * jnp.einsum(
            "md,mdf->mf", x, wu_[expert])
        ys = jnp.einsum("mf,mfd->md", h, wd_[expert])
        return jnp.concatenate([ys, jnp.zeros((m - live, D), jnp.float32)])

    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(plain, *(jnp.asarray(a)
                                     for a in (xs, wg, wu, wd)))
        wants = vjp(jnp.asarray(g))
    return out, grad, want, wants


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["whole", "held"])
@pytest.mark.parametrize("act", ["relu", "silu"])
def test_gated_experts_forward_and_grad_against_the_plain_form(act, held):
    with jax.default_matmul_precision("highest"):
        out, grad, want, wants = experts_ops(act, held)
    np.testing.assert_allclose(out["Ys"][0], want, rtol=1e-5, atol=1e-5)
    for slot, b in zip(("Xs", "WGate", "WUp", "WDown"), wants):
        np.testing.assert_allclose(grad[f"GRAD::{slot}"][0], b, rtol=1e-4,
                                   atol=1e-4, err_msg=slot)


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["whole", "held"])
def test_silu_and_no_router_input_lower_todays_ops(held):
    """``act="silu"`` (or none) and ``router_input=None`` are the ops as
    they were: the same values bit for bit and the same lowered text."""
    a, ga, _, _ = experts_ops(None, held)
    b, gb, _, _ = experts_ops("silu", held)
    for x, y in ((a, b), (ga, gb)):
        for k in x:
            assert bool((x[k][0] == y[k][0]).all()), k

    def program(**kw):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[N, D], dtype="float32",
                            append_batch_size=False)
            out, *_ = layers.topk_moe(x, E, K, F, name="m", held=held, **kw)
        # (slots, not names: temporaries are numbered by the process)
        return [(op.type, sorted(op.attrs.items()), sorted(op.inputs))
                for op in main.global_block().ops]

    assert program() == program(act="silu", router_input=None)
    with pytest.raises(ValueError, match="act="):
        program(act="gelu")


def moe_layer(held, x, r_in=None, weights=None, seed=3):
    """(out, rows, d loss / d x, d loss / d r, {param: value}) of a
    ReGLU topk_moe layer whose router reads ``r_in``; ``weights``: the
    uncut layer's, cut to the held share."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        rv = None
        if r_in is not None:
            rv = layers.data("r", shape=list(x.shape), dtype="float32",
                             append_batch_size=False)
            rv.stop_gradient = False
        out, lb, _, rows, _ = layers.topk_moe(
            xv, E, K, F, name="m", held=held, norm_topk_prob=True,
            act="relu", router_input=rv)
        append_backward(layers.sums([
            layers.reduce_sum(layers.square(out)), lb]))
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    for n, v in (weights or {}).items():
        if n in scope.var_names():
            if held and v.ndim == 3 and v.shape[0] == E:
                v = v[held[0]:held[0] + held[1]]
            scope.set(n, jnp.asarray(v))
    w = snapshot(scope)
    feed = {"x": x} if r_in is None else {"x": x, "r": r_in}
    fetch = [out, rows, "x@GRAD"] + (["r@GRAD"] if r_in is not None else [])
    got = exe.run(main, feed=feed, scope=scope, fetch_list=fetch)
    return (*got, *([None] if r_in is None else []), w)


def ref_layer(w, cfg):
    wr = {f"p_moe_{k[2:]}": v for k, v in w.items()}

    def f(r, z):
        out, _, lb = ref.moe(r, z, wr, "p", cfg)
        return out, lb
    return f


def test_router_input_routes_by_one_tensor_and_multiplies_another():
    """The router's choices and weights follow ``router_input``; the
    experts multiply ``input``; each tensor's gradient is its own path's
    (the router's, through the weights and the balance loss, lands on
    the router's input)."""
    r = np.random.RandomState(0)
    x = r.randn(N, D).astype(np.float32)
    r_in = r.randn(N, D).astype(np.float32)
    _, _, _, _, w = moe_layer(None, x, r_in)
    w = dict(w, **{"m_router.w": r.randn(D, E).astype(np.float32)})
    out, rows, gx, gr, w = moe_layer(None, x, r_in, w)
    cfg = dict(moe_num_primary_experts=E, router_experts=E,
               moe_num_active_primary_experts=K)
    layer = ref_layer(w, cfg)

    def loss(r_, z_):
        o, lb = layer(r_, z_)
        return jnp.sum(o ** 2) + lb

    with jax.default_matmul_precision("highest"):
        want, _ = layer(jnp.asarray(r_in), jnp.asarray(x))
        want_gr, want_gx = jax.grad(loss, (0, 1))(jnp.asarray(r_in),
                                                  jnp.asarray(x))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx, want_gx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gr, want_gr, rtol=1e-4, atol=1e-5)
    assert np.abs(gr).max() > 0
    # routed by its own input the layer is another function
    own, *_ = moe_layer(None, x, None, w)
    assert np.abs(own - out).max() > 0.1 * np.abs(out).max()


def test_router_dispatch_counter_names_the_input():
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
        (("bias", "0"), ("experts", "16"), ("input", "other"), ("k", "3"),
         ("score", "softmax"))}


# --- one chip's share of an expert layer ---------------------------------

def test_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """E = 16 as eight shares of 2 (the deployment's eight chips): what
    the shares give for their routed experts sums to the uncut layer's
    output, which is the reference's; every (token, slot) pair is a row
    of exactly one share."""
    r = np.random.RandomState(0)
    x = r.randn(3, 5, D).astype(np.float32)
    r_in = r.randn(3, 5, D).astype(np.float32)
    _, _, _, _, w = moe_layer(None, x, r_in)
    w = dict(w, **{"m_router.w": r.randn(D, E).astype(np.float32)})
    full, rows, _, _, w = moe_layer(None, x, r_in, w)
    assert rows.shape == (E,) and rows.sum() == N * K
    total, held_rows = 0.0, []
    for i in range(8):
        out, r_, _, _, _ = moe_layer((2 * i, 2), x, r_in, w)
        assert (r_ == rows[2 * i:2 * i + 2]).all()
        held_rows.append(r_.sum())
        total = total + out
    assert sum(held_rows) == N * K
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-7)
    cfg = dict(moe_num_primary_experts=E, router_experts=E,
               moe_num_active_primary_experts=K)
    with jax.default_matmul_precision("highest"):
        want, _ = ref_layer(w, cfg)(jnp.asarray(r_in).reshape(N, D),
                                    jnp.asarray(x).reshape(N, D))
    np.testing.assert_allclose(full.reshape(N, D), want, rtol=1e-5,
                               atol=1e-7)


def test_all_pairs_held_drops_no_token():
    """A router input that sends EVERY pair to held experts (all N * K
    rows of the buffer are live): the layer is the reference's, output
    and both gradients."""
    r = np.random.RandomState(4)
    x = r.randn(N, D).astype(np.float32)
    r_in = np.abs(r.randn(N, D)).astype(np.float32)
    _, _, _, _, w = moe_layer((8, 4), x, r_in)
    wr = 0.1 * r.randn(D, E).astype(np.float32)
    wr[:, 8:12] += 2.0          # positive inputs: experts 8..11 win
    w = dict(w, **{"m_router.w": wr})
    out, rows, gx, gr, w = moe_layer((8, 4), x, r_in, w)
    assert rows.sum() == N * K
    cfg = dict(moe_num_primary_experts=4, held_first=8, router_experts=E,
               moe_num_active_primary_experts=K)
    layer = ref_layer(w, cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = layer(jnp.asarray(r_in), jnp.asarray(x))
        want_gx = jax.grad(lambda z: jnp.sum(layer(jnp.asarray(r_in), z)[0]
                                             ** 2))(jnp.asarray(x))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gx, want_gx, rtol=1e-4, atol=1e-6)
