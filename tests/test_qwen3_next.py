"""The hybrid decoder (models/qwen3_next.py: Gated DeltaNet layers,
gated grouped-query attention, held experts with a shared expert)
against the plain float32 reference (perf/reference/qwen3next.py, the
file the benchmark's ``correct`` is decided by), forward and gradient, at
tiny sizes on the CPU; the expert layer as one chip's share of an
expert-parallel layer; and grouped matmuls whose groups do not fill
their rows. Gradients of the reference are ``jax.grad`` of its
functions; the program's come from ``append_backward``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, moved, reference, snapshot
from paddle_tpu import analysis, flags, layers, monitor
from paddle_tpu.backward import append_backward
from paddle_tpu.models import qwen3_next as M
from paddle_tpu.parallel import grouped_matmul as gm
from perf.reference import qwen3next as ref

TINY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=4,
            full_attention_interval=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
            rope_theta=1e7, rms_norm_eps=1e-6, linear_conv_kernel_dim=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            linear_num_key_heads=2, linear_num_value_heads=4,
            num_experts_per_tok=3, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, norm_topk_prob=True)
# experts 4..7 of the 16 the router scores are this chip's
HELD = (4, 4)
REF_CFG = dict(TINY, num_experts=HELD[1], held_first=HELD[0],
               router_experts=16)


# gains, gates and routers away from their initial 0 / 1 / 0.02, so that
# every parameter matters and the routing has no near-ties
PERTURB = [((".scale", "_dt_bias"), moved(0.2)), (("_router.w",), drawn()),
           (("_conv.w", "_shared_mix.w"), drawn(0.5))]


def perturb(scope, seed):
    model_test.perturb(scope, seed, PERTURB)


def test_model_loss_logits_and_every_parameters_gradient():
    cfg = M.Qwen3NextConfig(**TINY, num_experts=16, held_experts=HELD,
                            gdn_chunk=8)
    feed = M.make_batch(cfg, 2, 16, seed=9)
    main, startup, model, grads = model_test.built(M, cfg, 11)
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
    # chunkwise delta rule against the recurrence, sorted groups against
    # a dense loop, fused projections): sums over 8..64 terms
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
    assert 0 < pairs < 4 * 32 * 3       # a share: some pairs, not all
    kinds = ["qwen3next_tok_emb.w", "lm_head_colp.w", "final_norm.scale"]
    for i in range(4):
        mixer = (["attn_norm.scale", "attn_qgkv_colp.w", "attn_qnorm.scale",
                  "attn_knorm.scale", "attn_out_rowp.w"] if i == 3 else
                 ["gdn_norm.scale", "gdn_qkvz_colp.w", "gdn_ba.w",
                  "gdn_conv.w", "gdn_A_log", "gdn_dt_bias",
                  "gdn_onorm.scale", "gdn_out_rowp.w"])
        kinds += [f"blk{i}_{s}" for s in mixer + [
            "moe_norm.scale", "moe_router.w", "moe_gate.w", "moe_up.w",
            "moe_down.w", "moe_shared_gate.w", "moe_shared_up.w",
            "moe_shared_down.w", "moe_shared_mix.w"]]
    assert sorted(names) == sorted(kinds)
    assert w["blk0_moe_gate.w"].shape == (4, 32, 16)      # held, not 16
    assert w["blk0_moe_router.w"].shape == (32, 16)       # scored: all
    g = dict(zip(names, got[11:]))
    for n in names:
        # the loss is a mean over 32 positions at ln(50): gradients of
        # 1e-7..1e-2; four layers deep the order of the sums shows in
        # the fifth digit of the largest entry of a tensor
        scale = np.abs(want_g[n]).max()
        np.testing.assert_allclose(g[n], want_g[n], rtol=2e-3,
                                   atol=1e-4 * scale + 1e-9, err_msg=n)


def test_model_trains_under_amp():
    cfg = M.Qwen3NextConfig(**TINY, num_experts=16, held_experts=HELD,
                            gdn_chunk=8)
    feed = M.make_batch(cfg, 4, 16, seed=1)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 2
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        fluid.optimizer.Adam(3e-3).minimize(model["loss"])
    main._amp = True
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    losses = [float(exe.run(main, feed=feed, fetch_list=[model["loss"]],
                            scope=scope)[0]) for _ in range(30)]
    assert losses[-1] < losses[0] - 0.5 and np.isfinite(losses).all()
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("gated_delta_rule") == 3 \
        and kinds.count("gated_delta_rule_grad") == 3 \
        and kinds.count("scaled_dot_product_attention") == 1


# --- one chip's share of an expert layer ---------------------------------

N, D, F, E, K = 15, 8, 6, 16, 4


def moe_layer(held, shared, x, weights=None, seed=3):
    """(out, rows, d loss / d x, {param: value}) of a topk_moe layer;
    ``weights``: the uncut layer's, cut to the held share."""
    return model_test.moe_layer(
        E, K, F, held, x, weights, seed, grad=True,
        shared_d_ff=shared, norm_topk_prob=True)


def test_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """E = 16 as four shares of 4: what the shares give for their routed
    experts, plus the shared expert once, is the uncut layer's output;
    every (token, slot) pair is a row of exactly one share."""
    x = np.random.RandomState(0).randn(3, 5, D).astype(np.float32)
    full, rows, _, w = moe_layer(None, F, x)
    assert rows.shape == (E,) and rows.sum() == N * K
    total, held_rows = 0.0, []
    for i in range(4):
        out, r, _, _ = moe_layer((4 * i, 4), F if i == 0 else None, x, w)
        assert (r == rows[4 * i:4 * i + 4]).all()
        held_rows.append(r.sum())
        total = total + out
    assert sum(held_rows) == N * K and min(held_rows) > 0
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("case", ["all_pairs_held", "no_pair_held"])
def test_no_routing_drops_a_token(case):
    """A router that sends EVERY pair to held experts (all N * K rows of
    the buffer are live) and one that sends none (the groups are empty
    and the layer adds nothing): the layer is the reference's in both,
    output and the tokens' gradient."""
    r = np.random.RandomState(4)
    x = r.randn(N, D).astype(np.float32)
    x[:, 0] = 3.0
    _, _, _, w = moe_layer((8, 4), None, x)
    router = 0.1 * r.randn(D, E).astype(np.float32)
    # feature 0 is constant: +30 on the logits of experts 8..11 (or of
    # the twelve others) decides every token's top 4
    cols = np.arange(8, 12) if case == "all_pairs_held" else np.r_[0:8, 12:16]
    router[0, cols] += 10.0
    w = dict(w, **{"m_router.w": router})
    out, rows, gx, w = moe_layer((8, 4), None, x, w)
    assert rows.sum() == (N * K if case == "all_pairs_held" else 0)
    cfg = dict(num_experts=4, held_first=8, router_experts=E,
               num_experts_per_tok=K)

    def routed(xs):
        x_ = jnp.asarray(xs)
        top_w, top_i, _ = ref.route(x_, w["m_router.w"], K)
        weight = jnp.einsum("nk,nke->ne", top_w,
                            jax.nn.one_hot(top_i, E))[:, 8:12]
        return sum(weight[:, e:e + 1] * ref.swiglu(
            x_, w["m_gate.w"][e], w["m_up.w"][e], w["m_down.w"][e], None)
            for e in range(4))

    assert ref.held(cfg) == (8, 4, E)
    np.testing.assert_allclose(out, routed(x), rtol=1e-5, atol=1e-8)
    want_gx = jax.grad(lambda xs: jnp.sum(routed(xs) ** 2))(x)
    np.testing.assert_allclose(gx, want_gx, rtol=1e-4, atol=1e-8)
    if case == "no_pair_held":
        assert not out.any() and not gx.any()


# --- grouped matmuls whose groups do not fill their rows --------------------

ROWS = 512
PARTIAL = {"a_sixteenth": [9, 0, 16, 7], "half": [100, 28, 0, 128],
           "one_row_short": [128, 128, 128, 127], "none": [0, 0, 0, 0]}


@pytest.mark.parametrize("behind", [True, "tile"],
                         ids=["zeros_behind", "zeros_to_the_tiles_end"])
@pytest.mark.parametrize("kernels", [False, True], ids=["ragged_dot",
                                                        "kernels"])
@pytest.mark.parametrize("groups", sorted(PARTIAL))
def test_grouped_matmul_with_rows_behind_the_last_group(groups, kernels,
                                                        behind, monkeypatch):
    """Group sizes that sum to less than the rows: ``ragged_dot``'s
    semantics (zeros behind the last group, in the product and in the
    rows' gradient; the matrix's gradient never reads them), through
    the interpreted kernels and through the fallback. A held layer's
    own ops ask for ``zero_behind="tile"``: zeros to the end of the row
    tile the last group ends in (tile 0 where no group has a row), and
    behind it what the memory held (NaN under the interpreter: nothing
    filled it), which neither gradient reads from g or lhs."""
    monkeypatch.setattr(gm, "_INTERPRET", kernels)
    sizes = jnp.asarray(PARTIAL[groups], jnp.int32)
    live = int(sizes.sum())
    r = np.random.RandomState(1)
    bf = jnp.bfloat16
    lhs = jnp.asarray(r.randn(ROWS, 128), bf)
    rhs = jnp.asarray(r.randn(4, 128, 256) * 0.1, bf)
    g = jnp.asarray(r.randn(ROWS, 256), bf)
    # the row tile goes with the live rows the caller expects
    tile = gm.gmm_tile(ROWS, 128, 256, 4, bf, live_rows=ROWS)
    assert (tile is not None) == kernels
    assert gm.gmm_tile(8192 * 10, 2048, 512, 32, bf, "tpu", False,
                       live_rows=5120) == (128, 2048, 512)
    assert gm.gmm_tile(8192 * 10, 2048, 512, 32, bf, "tpu", False) \
        == (256, 2048, 512)
    end = ROWS
    if behind == "tile" and kernels:    # of the last group's row tile
        end = max(-(-live // tile[0]), 1) * tile[0]
        rows = jnp.arange(ROWS)[:, None] < end
        lhs, g = jnp.where(rows, lhs, jnp.nan), jnp.where(rows, g, jnp.nan)
    out = gm.grouped_matmul(lhs, rhs, sizes, live_rows=ROWS,
                            zero_behind=behind)
    dx, dw = gm.grouped_matmul_grads(lhs, rhs, sizes, g, live_rows=ROWS,
                                     zero_behind=behind)
    # (a last group that ends ON a tile's edge: the tile behind it is
    # the one zeroed, whole)
    nothing_wrote = end + tile[0] * (live % tile[0] == 0) if kernels else end
    assert np.isnan(np.asarray(out[nothing_wrote:], np.float32)).all()
    assert np.isnan(np.asarray(dx[nothing_wrote:], np.float32)).all()
    assert np.isfinite(np.asarray(dw, np.float32)).all()
    out, dx = out[:end], dx[:end]
    want = jax.lax.ragged_dot(lhs[:live], rhs, sizes)
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                     lhs[:live], rhs)
    want_dx, want_dw = vjp(g[:live])
    f = np.float32
    assert not np.asarray(out[live:], f).any()
    assert not np.asarray(dx[live:], f).any()
    tol = dict(rtol=2e-2, atol=2e-2)     # bf16 results, sums over <= 256
    np.testing.assert_allclose(np.asarray(out[:live], f),
                               np.asarray(want, f), **tol)
    np.testing.assert_allclose(np.asarray(dx[:live], f),
                               np.asarray(want_dx, f), **tol)
    np.testing.assert_allclose(np.asarray(dw, f), np.asarray(want_dw, f),
                               rtol=2e-2, atol=2e-2 * 12)


def test_held_layer_kernel_path_agrees_with_the_ragged_dot_path(monkeypatch):
    """The held layer under bf16 AMP at a size ``gmm_tile`` takes (1024
    pairs, 512 expected on 4 of 8 experts of 128 x 128), forward and
    gradients, through the interpreted ``moe.*`` kernels against
    ``ragged_dot``; the dispatch counter names the tile the LIVE rows
    chose."""
    n, d, f, e, k = 512, 128, 128, 8, 2
    r = np.random.RandomState(11)
    x = r.randn(n, d).astype(np.float32)
    weights = {"m_router.w": r.randn(d, e).astype(np.float32) * 0.3}

    def run():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            xv = layers.data("x", shape=[n, d], dtype="float32",
                             append_batch_size=False)
            xv.stop_gradient = False
            out, _, _, rows, _ = layers.topk_moe(xv, e, k, f, name="m",
                                                 held=(2, 4))
            grads = append_backward(layers.reduce_sum(layers.square(out)))
        main._amp = True
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        scope.set("m_router.w", jnp.asarray(weights["m_router.w"]))
        return exe.run(main, feed={"x": x}, scope=scope, fetch_list=[
            out, rows, "x@GRAD", *(g for _, g in grads)])

    want = run()
    monkeypatch.setattr(gm, "_INTERPRET", True)
    flags.set_flags({"telemetry": True})
    try:
        got = run()
        counts = gm.gmm_dispatch_counts()
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
    assert counts == {f"{p} m1024 k128 n128 e4 [tm128 tk128 tn128]": 3
                      for p in ("fwd", "bwd_dx", "bwd_dw")}
    assert (got[1] == want[1]).all() and 0 < got[1].sum() < n * k
    for a, b in zip(got[:1] + got[2:], want[:1] + want[2:]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=3e-2,
                                   atol=3e-2 * np.abs(b).max() + 1e-12)
