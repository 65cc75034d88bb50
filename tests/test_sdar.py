"""SDAR's block-diffusion training step (paddle_tpu.models.sdar) at tiny
sizes on the CPU: the model against the plain reference on seeded
weights (loss and gradients), the mask's three no-leak properties by
changing tokens and comparing hidden states, the BHTD kernels under the
block mask through the interpreter against the dense rule (forward and
the ONE backward call, grouped queries, one to three tiles a half), the
walks' index maps and ``bhtd_pairs`` against a brute-force table, the
rotary op's ``periods``, the per-head QK-norm inside the rotary op (every
parameter's gradient against the reference, the startup program's
parameters in the parent's order, the model at heads of 128 through the
``rope.*`` kernels against the XLA form), the weighted loss head, and
the held shares' sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from model_test import highest, reference, snapshot
from paddle_tpu import flags, layers, monitor
from paddle_tpu.models import sdar as M
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel import flash_attention as fa
from paddle_tpu.parallel import rope
from perf.reference import sdar as ref

L, B = 24, 4


def tiny(**kw):
    base = dict(vocab_size=50, hidden_size=32, num_hidden_layers=3,
                num_attention_heads=8, num_key_value_heads=1, head_dim=8,
                num_experts=8, num_experts_per_tok=3,
                moe_intermediate_size=16, mask_token_id=49, block_length=B)
    base.update(kw)
    return M.SdarConfig(**base)


def as_file(cfg):
    """The configuration as perf/reference/sdar.py reads it."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
        "num_experts_per_tok", "block_length")}
    first, count = cfg.held_experts or (0, cfg.num_experts)
    out.update(router_experts=cfg.num_experts, num_experts=count,
               held_first=first)
    return out


def built(cfg, seed=3, train=False, fetch_hidden=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        if train:
            fluid.optimizer.SGD(0.0).minimize(model["loss"])
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    return main, model, scope, exe


# --- the model against the reference ---------------------------------------


@pytest.mark.parametrize("held", [None, (2, 3)])
def test_loss_and_gradients_agree_with_the_plain_reference(held):
    cfg = tiny(held_experts=held)
    main, model, scope, exe = built(cfg, train=True)
    feed = M.make_batch(cfg, 2, L, seed=5)
    names = ["sdar_tok_emb.w", "blk0_attn_qkv_colp.w", "blk1_moe_router.w",
             "blk2_moe_down.w", "blk1_attn_knorm.scale", "lm_head_colp.w",
             "final_norm.scale", "blk2_attn_out_rowp.w"]
    w = snapshot(scope)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["lm_loss"]] + [f"{n}@GRAD" for n in names])
    want, grads = highest(jax.value_and_grad(
        lambda w_: ref.loss(w_, as_file(cfg), feed)))(w)
    assert float(got[0]) == pytest.approx(float(want), rel=2e-5)
    assert float(got[1]) > 1.0     # ln(50) a masked position, 1 / p each
    for name, g in zip(names, got[2:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(grads[name]),
                                   rtol=2e-3, atol=2e-6, err_msg=name)
    # the clean half's last layer reaches the loss through its keys and
    # values alone: its q, o and experts take no gradient from it, the
    # table's clean-only rows do
    assert np.abs(np.asarray(grads["blk2_attn_out_rowp.w"])).max() > 0


def test_the_feed_is_the_noise():
    cfg = tiny()
    feed = M.make_batch(cfg, 4, L, seed=1)
    xt, x0 = feed["input_ids"][:, :L], feed["input_ids"][:, L:]
    masked = xt == cfg.mask_token_id
    assert (x0 < cfg.mask_token_id).all() and 0 < masked.mean() < 1
    assert (feed["labels"][masked] == x0[masked]).all()
    assert (feed["labels"][~masked] == M.IGNORE_INDEX).all()
    assert (xt[~masked] == x0[~masked]).all()
    # one weight a block, 1 / p with p in [P_MIN, 1]
    w = feed["loss_weight"].reshape(4, L // B, B)
    assert (w == w[:, :, :1]).all() and w.min() >= 1.0 \
        and w.max() <= 1.0 / M.P_MIN * (1 + 1e-6)
    again = M.make_batch(cfg, 4, L, seed=1)
    assert all((feed[k] == again[k]).all() for k in feed)


# --- the mask's no-leak properties ------------------------------------------


def hidden_states(cfg, ids):
    """Each layer's output [b, 2L, d] and the noised half's logits, from
    the reference's own forward on the program's weights, and the
    program's logits beside them."""
    main, model, scope, exe = built(cfg)
    w = snapshot(scope)
    file = as_file(cfg)
    feed = {"input_ids": ids,
            "labels": np.full((ids.shape[0], L), M.IGNORE_INDEX, np.int64),
            "loss_weight": np.ones((ids.shape[0], L), np.float32)}
    # the program's own hidden states: every layer's residual stream
    block = main.global_block()
    adds = [op.outputs["Out"][0] for op in block.ops
            if op.type == "elementwise_add"][1::2][:cfg.num_hidden_layers]
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=adds + [model["last_logits"]])
    return [np.asarray(g) for g in got], w, file


def changed(cfg, ids, at):
    out = ids.copy()
    out[:, at] = (out[:, at] + 7) % cfg.mask_token_id
    return out


@pytest.fixture(scope="module")
def base_states():
    cfg = tiny()
    ids = M.make_batch(cfg, 1, L, seed=2)["input_ids"]
    return cfg, ids, hidden_states(cfg, ids)[0]


def test_a_change_in_the_noised_half_moves_no_clean_position(base_states):
    cfg, ids, base = base_states
    blk = 2                                  # noised block 2: 8 .. 11
    got = hidden_states(cfg, changed(cfg, ids, slice(blk * B, blk * B + B)))[0]
    for layer, (a, b) in enumerate(zip(base[:-1], got[:-1])):
        # no clean position in any layer
        assert (a[:, L:] == b[:, L:]).all(), layer
        # no noised position of another block
        other = np.ones(L, bool)
        other[blk * B:(blk + 1) * B] = False
        assert (a[:, :L][:, other] == b[:, :L][:, other]).all(), layer
        assert (a[:, blk * B:(blk + 1) * B]
                != b[:, blk * B:(blk + 1) * B]).any(), layer
    # the last-position logits (a row of 24: all of it) move in block 2 alone
    moved = (base[-1] != got[-1]).any(-1)[0]
    assert moved[blk * B:(blk + 1) * B].all() and moved.sum() == B


def test_a_change_in_a_clean_block_moves_nothing_at_or_before_it(base_states):
    cfg, ids, base = base_states
    blk = 2
    got = hidden_states(
        cfg, changed(cfg, ids, slice(L + blk * B, L + blk * B + B)))[0]
    for layer, (a, b) in enumerate(zip(base[:-1], got[:-1])):
        # no clean position of the blocks before it
        assert (a[:, L:L + blk * B] == b[:, L:L + blk * B]).all(), layer
        # no noised position of blocks <= b
        assert (a[:, :(blk + 1) * B] == b[:, :(blk + 1) * B]).all(), layer
    moved = (base[-1] != got[-1]).any(-1)[0]
    assert not moved[:(blk + 1) * B].any()
    # ... and it DOES move noised block b + 1 (and every later one)
    assert moved[(blk + 1) * B:].all()
    for a, b in zip(base[:-1], got[:-1]):
        assert (a[:, (blk + 1) * B:L] != b[:, (blk + 1) * B:L]).any()
        assert (a[:, L + blk * B:] != b[:, L + blk * B:]).any()


# --- the kernels under the block mask ---------------------------------------


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def qkv(h, hk, t, dh=16, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(1, h, t, dh) * 0.5, jnp.float32),
            jnp.asarray(r.randn(1, hk, t, dh) * 0.5, jnp.float32),
            jnp.asarray(r.randn(1, hk, t, dh), jnp.float32),
            jnp.asarray(r.randn(1, h, t, dh), jnp.float32))


def rule(t, block):
    """[t, t] bool by the rule, a loop over index pairs."""
    half, out = t // 2, np.zeros((t, t), bool)
    for p in range(t):
        for s in range(t):
            bp, bs = (p % half) // block, (s % half) // block
            if p < half and s < half:
                out[p, s] = bp == bs
            elif p < half:
                out[p, s] = bs < bp
            elif s >= half:
                out[p, s] = bs <= bp
    return out


@pytest.mark.parametrize("t,block", [(16, 4), (48, 4), (64, 16), (24, 2)])
def test_the_dense_rule_and_the_reference_say_the_same(t, block):
    want = rule(t, block)
    assert (np.asarray(fa.bd_visible(t, block)) == want).all()
    p, s = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    assert (np.asarray(ref.visible(p, s, t // 2, block)) == want).all()
    assert want.sum() == (t // 2) ** 2 + block * (t // 2)
    leak = np.asarray(ref.visible(p, s, t // 2, block, leak=True))
    assert leak[t // 2:, :t // 2].any() and (leak[:t // 2] == want[:t // 2]).all()


# (half a row in tiles of 128, block length, query heads, key/value heads)
KERNEL_CASES = [(1, 4, 8, 1), (2, 4, 8, 1), (3, 4, 8, 1), (2, 16, 8, 1),
                (3, 16, 16, 2), (1, 16, 8, 1), (2, 128, 2, 1), (2, 3, 2, 1)]


@pytest.mark.parametrize("tiles,block,h,hk", KERNEL_CASES)
def test_kernels_agree_with_the_dense_rule(tiles, block, h, hk, interpreted):
    half = 128 * tiles
    if half % block:
        half = 384           # (blocks of 3: a length no power of two)
    t = 2 * half
    q, k, v, g = qkv(h, hk, t)
    kw = dict(q_block=128, k_block=128, block_diffusion=block)
    tile = fa.bhtd_tile(h, t, t, 128, 128, dh=16, group=h // hk,
                        block_diffusion=block, itemsize=4)
    if 128 % block:
        # blocks that are no whole part of a tile: the dense composition
        assert tile is None
        return
    assert tile == (1, 128, 128)
    assert fa.bhtd_bwd_form(h, t, t, 128, 128, dh=16, group=h // hk,
                            itemsize=4, block_diffusion=block) == "fused"
    out, lse = jax.jit(lambda q, k, v: fa.flash_attention_fwd(
        q, k, v, **kw))(q, k, v)
    grads = jax.jit(lambda *a: fa.flash_attention_bwd(
        *a[:3], None, None, *a[3:], **kw))(q, k, v, out, lse, g)

    def dense(q, k, v):
        group = q.shape[1] // k.shape[1]
        s = fa._reference_scores(q, jnp.repeat(k, group, 1), None,
                                 q.shape[-1] ** -0.5, False,
                                 block_diffusion=block)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                          jnp.repeat(v, group, 1)), \
            jax.scipy.special.logsumexp(s, -1, keepdims=True)

    (want, want_lse), vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse, want_lse, atol=2e-6)
    for a, b in zip(grads, vjp((g, jnp.zeros_like(want_lse)))):
        np.testing.assert_allclose(a, b, atol=2e-5)
    # what the mask lets through, and the blocks the two walks compute
    live = int(rule(t, block).sum())
    assert live == half * half + block * half
    n = tiles
    for form in (None, "fused"):
        assert fa.bhtd_pairs(t, t, tile, False, form=form,
                             block_diffusion=block) \
            == (n * (n + 2) * 128 * 128, live)
    assert fa.bhtd_edge_tile(tile, False) is None


def test_a_call_the_kernels_do_not_take_runs_dense_and_is_counted(
        interpreted, monkeypatch):
    # heads batched in a step (the pair, not the ONE backward call), a
    # half that is no whole number of tiles, blocks of 3
    assert fa.bhtd_tile(2, 256, 256, 128, 128, dh=16,
                        block_diffusion=4) is None
    assert fa.bhtd_tile(8, 384, 384, 128, 128, dh=16, group=8,
                        block_diffusion=4) is None
    assert fa.bhtd_family(8, 512, 512, 128, 128, dh=16, group=8,
                          block_diffusion=3) == "dense"
    assert fa.bhtd_family(8, 512, 512, 128, 128, dh=16, group=8,
                          block_diffusion=4) == "bhtd"
    for bad in (dict(causal=True), dict(causal=True, window=8)):
        with pytest.raises(ValueError, match="block_diffusion"):
            fa.flash_attention_fwd(*qkv(2, 2, 64)[:3], block_diffusion=4,
                                   **bad)
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention_fwd(*qkv(2, 2, 60)[:3], block_diffusion=4)


@pytest.mark.parametrize("nh", [1, 2, 3, 8])
def test_both_walks_visit_each_live_block_once_and_fetch_no_dead_one(nh):
    bq, bd = 128, (4, 128 * nh)
    nq = 2 * nh
    live = rule(2 * nh * 8, 4).reshape(nq, 8, nq, 8).any((1, 3))
    whole = rule(2 * nh * 8, 4).reshape(nq, 8, nq, 8).all((1, 3))
    at_k = fa._step_blocks(False, True, bq, bq, nq, bd=bd)
    at_q = fa._step_blocks(False, False, bq, bq, nq, bd=bd)
    seen_k, seen_q = np.zeros((nq, nq), int), np.zeros((nq, nq), int)
    for j in range(nq):
        for r in range(nh + 1):
            kk, alive, edge = (int(x) for x in fa._bd_k_step(j, r, bq, bd))
            fetched = int(at_k(0, 0, j, r)[3])
            assert live[j, fetched], "a dead block is fetched"
            if alive:
                assert fetched == kk and live[j, kk]
                assert bool(edge) == (not whole[j, kk])
                seen_k[j, kk] += 1
            if r == 0:
                assert alive and kk == j     # a row's own block first
    for kk in range(nq):
        for r in range(nq):
            j, alive, edge = (int(x) for x in fa._bd_q_step(kk, r, bq, bd))
            fetched = int(at_q(0, 0, kk, r)[2])
            assert live[fetched, kk], "a dead block is fetched"
            if alive:
                assert fetched == j and live[j, kk]
                assert bool(edge) == (not whole[j, kk])
                seen_q[j, kk] += 1
    assert (seen_k == live).all() and (seen_q == live).all()
    # no clean q-row ever fetches a noised block
    for j in range(nh, nq):
        assert all(int(at_k(0, 0, j, r)[3]) >= nh for r in range(nh + 1))


def test_the_op_counts_the_block_masked_call(interpreted):
    flags.set_flags({"telemetry": True})
    monitor.reset()
    try:
        for t, want in ((1024, "bhtd"), (96, "dense")):
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                q = layers.data("q", shape=[8, t, 16], dtype="float32")
                k = layers.data("k", shape=[1, t, 16], dtype="float32")
                q.stop_gradient = k.stop_gradient = False
                out = layers.scaled_dot_product_attention(
                    q, k, k, 0.25, block_diffusion=4)
                loss = layers.mean(out)
                fluid.backward.append_backward(loss)
            a, b, _, _ = qkv(8, 1, t)
            got = fluid.Executor().run(
                main, feed={"q": np.asarray(a), "k": np.asarray(b)},
                fetch_list=[out, "q@GRAD"])
            want_out = fa._reference_attention(a, b, b, None, 0.25,
                                               block_diffusion=4)
            np.testing.assert_allclose(got[0], want_out, atol=2e-6)
            assert np.abs(got[1]).max() > 0
            rows = attention_ops.dispatch_counts(masks=True, forms=True)
            mine = {k: v for k, v in rows.items() if f"tq{t} " in k}
            band = "skip" if want == "bhtd" else "dense"
            assert sorted(mine) == sorted([
                f"{want} fwd b1 tq{t} tk{t} h8 kv1 dh16 "
                f"mask=block_diffusion block=4 band={band}",
                f"{want} bwd b1 tq{t} tk{t} h8 kv1 dh16"
                + (" form=fused" if want == "bhtd" else "")
                + f" mask=block_diffusion block=4 band={band}"]), mine
            # the keys without the label are what they were
            plain = attention_ops.dispatch_counts()
            assert f"{want} fwd b1 tq{t} tk{t} h8 kv1 dh16" in plain
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


# --- the positions run twice ------------------------------------------------


def test_rotary_periods_turn_both_halves_by_the_same_angles(monkeypatch):
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(1, 3, 64, 16), jnp.float32)
    twice = attention_ops._rotate(x, 1e4, periods=2)
    np.testing.assert_array_equal(
        twice[:, :, 32:], attention_ops._rotate(x[:, :, 32:], 1e4))
    np.testing.assert_array_equal(
        twice[:, :, :32], attention_ops._rotate(x[:, :, :32], 1e4))
    with pytest.raises(ValueError, match="runs"):
        attention_ops._rotate(x[:, :, :63], 1e4, periods=2)
    # the kernels read one run's tables twice
    monkeypatch.setattr(rope, "_INTERPRET", True)
    q = jnp.asarray(r.randn(1, 128, 4, 128), jnp.bfloat16)
    k = jnp.asarray(r.randn(1, 128, 2, 128), jnp.bfloat16)
    tile = rope.rope_tile(1, 128, 4, 128, None, False, jnp.bfloat16, hk=2,
                          periods=2)
    assert tile == (64, 4)      # a block of rows lies inside one run
    assert rope.rope_tile(1, 96, 4, 128, None, False, jnp.bfloat16, hk=2,
                          periods=2) is None
    qo, ko = rope.rope_fwd(q, k, 1e6, tile, tokens=True, periods=2)
    for got, src in ((qo, q), (ko, k)):
        want = attention_ops._rotate(jnp.swapaxes(src, 1, 2), 1e6, periods=2)
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=0.04)
    back = rope.rope_bwd(qo, ko, 1e6, tile, tokens=True, periods=2)
    np.testing.assert_allclose(back[0].astype(jnp.float32),
                               q.astype(jnp.float32), atol=0.08)


# --- the per-head QK-norm inside the rotary op ---------------------------------

# the parameters the startup program creates for two layers, in its
# order, as the tree before PR 66 created them (two rms_norm layers
# between the projection and the rotary op): a run's draws from --seed
# go by this order, and perf/families/sdar.build_graph finds the gains
# by their suffix
PARAMETERS = [
    "sdar_tok_emb.w",
    "blk0_attn_norm.scale", "blk0_attn_qkv_colp.w", "blk0_attn_qnorm.scale",
    "blk0_attn_knorm.scale", "blk0_attn_out_rowp.w", "blk0_moe_norm.scale",
    "blk0_moe_router.w", "blk0_moe_gate.w", "blk0_moe_up.w",
    "blk0_moe_down.w",
    "blk1_attn_norm.scale", "blk1_attn_qkv_colp.w", "blk1_attn_qnorm.scale",
    "blk1_attn_knorm.scale", "blk1_attn_out_rowp.w", "blk1_moe_norm.scale",
    "blk1_moe_router.w", "blk1_moe_gate.w", "blk1_moe_up.w",
    "blk1_moe_down.w",
    "final_norm.scale", "lm_head_colp.w"]


@pytest.mark.parametrize("is_test", [False, True], ids=["train", "eval"])
def test_the_qk_norm_is_the_rotary_op_s_and_the_parameters_keep_their_place(
        is_test):
    """One rotary op a layer with QScale and KScale, no ``qk_norm``
    scope, the pre-norm the one ``rms_norm`` of a block's attention; the
    startup program fills the same parameters in the same order with
    the same ops as the parent's (a constant 1 for every gain)."""
    cfg = tiny(num_hidden_layers=2)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        M.build(cfg, is_test=is_test)
    made = [(op.type, op.output_arg_names[0])
            for op in startup.global_block().ops]
    assert [name for _, name in made] == PARAMETERS
    assert all((kind == "fill_constant") == name.endswith(".scale")
               for kind, name in made)
    assert [p.name for p in main.all_parameters()] == PARAMETERS
    for p in main.all_parameters():
        if p.name.endswith(("qnorm.scale", "knorm.scale")):
            assert tuple(p.shape) == (cfg.head_dim,) and p.dtype == "float32"
    ops = main.global_block().ops
    rotary = [op for op in ops if op.type == "rotary_embedding"]
    assert [(op.namescope, op.inputs["QScale"], op.inputs["KScale"],
             op.attrs["norm_epsilon"], op.attrs["periods"],
             op.attrs["layout"]) for op in rotary] == [
        (f"blk{i}/attn/rope", [f"blk{i}_attn_qnorm.scale"],
         [f"blk{i}_attn_knorm.scale"], cfg.rms_norm_eps, 2, "bthd")
        for i in range(2)]
    assert not any("qk_norm" in op.namescope for op in ops)
    assert [op.namescope for op in ops if op.type == "rms_norm"
            and "/attn" in op.namescope] == ["blk0/attn", "blk1/attn"]


def test_every_parameter_s_gradient_agrees_with_the_plain_reference():
    """The logits and ALL the gradients, the gains' among them, against
    perf/reference/sdar.py (which norms and rotates as two steps)."""
    cfg = tiny(num_hidden_layers=2)
    main, model, scope, exe = built(cfg, train=True)
    feed = M.make_batch(cfg, 2, L, seed=6)
    w = snapshot(scope)
    # gains off 1, as a run's start state has them
    r = np.random.RandomState(0)
    for name in PARAMETERS:
        if name.endswith(("qnorm.scale", "knorm.scale")):
            w[name] = (2.0 + 0.2 * r.randn(*w[name].shape)).astype(np.float32)
            scope.set(name, w[name])
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["last_logits"]]
        + [f"{n}@GRAD" for n in PARAMETERS])
    want_out, want, grads = reference(ref, w, as_file(cfg), feed)
    logits = want_out["logits"]
    assert float(got[0]) == pytest.approx(float(want), rel=2e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(logits),
                               rtol=2e-3, atol=2e-5)
    for name, g in zip(PARAMETERS, got[2:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(grads[name]),
                                   rtol=2e-3, atol=2e-6, err_msg=name)
        assert np.abs(np.asarray(g)).max() > 0, name


def test_the_model_through_the_rope_kernels_is_the_model_through_xla(
        monkeypatch):
    """The tiny model at heads of 128 under AMP, a row of 2 x 64
    positions: the rotary op with the gains through ``rope.fwd`` /
    ``rope.bwd`` (the interpreter), against the same Program where
    ``rope_tile`` gives no tile (rms_norm's lines, ``_rotate`` and their
    vjp): the loss, the logits and every parameter's gradient."""
    cfg = tiny(num_hidden_layers=2, head_dim=128, num_attention_heads=4,
               num_key_value_heads=2)

    def run():
        main, model, scope, exe = built(cfg, train=True)
        main._amp = True
        r = np.random.RandomState(0)
        for name in PARAMETERS:
            if name.endswith(("qnorm.scale", "knorm.scale")):
                scope.set(name, (2.0 + 0.2 * r.randn(128)).astype(np.float32))
        got = exe.run(main, feed=M.make_batch(cfg, 1, 64, seed=7),
                      scope=scope, fetch_list=[
                          model["loss"], model["last_logits"]]
                      + [f"{n}@GRAD" for n in PARAMETERS])
        return [np.asarray(g, np.float32) for g in got]

    monkeypatch.setattr(rope, "_INTERPRET", True)
    made = []
    for fn in (rope.rope_fwd, rope.rope_bwd):
        def spy(*a, _fn=fn, **kw):
            made.append((_fn.__name__, kw.get("gains") is not None))
            return _fn(*a, **kw)
        monkeypatch.setattr(rope, fn.__name__, spy)
    got = run()
    assert made == [("rope_fwd", True)] * 2 + [("rope_bwd", True)] * 2
    monkeypatch.setattr(rope, "rope_tile", lambda *a, **kw: None)
    want = run()
    assert len(made) == 4
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    np.testing.assert_allclose(got[1], want[1], atol=3e-2 * np.abs(
        want[1]).max())
    for name, g, w in zip(PARAMETERS, got[2:], want[2:]):
        scale = float(np.abs(w).max()) or 1.0
        np.testing.assert_allclose(g / scale, w / scale, atol=3e-2,
                                   err_msg=name)


# --- the held shares ---------------------------------------------------------


def test_the_eight_shares_expert_outputs_add_up_to_the_uncut_layer():
    """One expert layer of 16 experts whole, and as eight shares of two:
    the shares' outputs, summed, are the whole layer's."""
    r = np.random.RandomState(0)
    x = r.randn(2, 12, 32).astype(np.float32)

    def layer(held):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            xin = layers.data("x", shape=[12, 32], dtype="float32")
            out, _, _, rows, _ = layers.topk_moe(
                xin, 16, 4, 24, norm_topk_prob=True, name="m", held=held)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        return main, scope, exe, out, rows

    main, scope, exe, out, rows = layer(None)
    whole, whole_rows = exe.run(main, feed={"x": x}, scope=scope,
                                fetch_list=[out, rows])
    w = {n: np.asarray(scope.find_var(n)) for n in scope.var_names()}
    total, counted = 0.0, 0
    for share in range(8):
        main, scope, exe, out, rows = layer((2 * share, 2))
        for n in scope.var_names():
            full = w[n]
            part = (full[2 * share:2 * share + 2]
                    if n.endswith(("_gate.w", "_up.w", "_down.w")) else full)
            scope.set(n, jnp.asarray(part))
        got, got_rows = exe.run(main, feed={"x": x}, scope=scope,
                                fetch_list=[out, rows])
        assert (np.asarray(got_rows)
                == np.asarray(whole_rows)[2 * share:2 * share + 2]).all()
        total, counted = total + np.asarray(got), counted + got_rows.sum()
    assert counted == 2 * 12 * 4
    np.testing.assert_allclose(total, np.asarray(whole), atol=1e-5)
