"""Keye-VL-2.0's language model (models/keye.py: grouped-query attention
that reads the keys a lightning indexer chose, the indexer's own KL
loss, fed multi-axis rotary positions, held SwiGLU experts) against the
plain float32 reference (perf/reference/keye.py, the file the
benchmark's ``correct`` is decided by) at tiny sizes on the CPU: loss,
logits, the selection and every gradient, in both stages, at UNEQUAL
position rows; the two gradient paths are disjoint; a step of Adam; the
eight shares' expert-layer outputs add up to the uncut reference's.
Gradients of the reference are ``jax.grad`` of its functions; the
program's come from ``append_backward``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_test
import paddle_tpu as fluid
from model_test import drawn, highest, moved, snapshot
from paddle_tpu import analysis
from paddle_tpu.models import keye as M
from perf.reference import keye as ref

# rows of 64 positions, 8 keys a query: rows below and above k; tiles of 16
TINY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            mrope_section=(2, 3, 3), num_experts_per_tok=2,
            moe_intermediate_size=16, indexer_num_heads=4,
            indexer_head_dim=8, topk=8, q_chunk_size=16, kv_chunk_size=16,
            indexer_rope_dim=4)
# experts 2..5 of the 16 the router scores are this chip's
HELD = (2, 4)
REF_CFG = dict(
    vocab_size=50, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    rope_theta=1e7, rms_norm_eps=1e-6, num_experts_per_tok=2,
    moe_intermediate_size=16, num_experts=HELD[1], held_first=HELD[0],
    router_experts=16, rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=8, topk=8),
    indexer_rope_dim=4)
INDEXER = ("_idx_q.w", "_idx_k.w", "_idx_w.w", "_idx_knorm.scale",
           "_idx_knorm.bias")

# gains and routers away from their initial 1 / 0.02, so that every
# parameter matters and the routing has no near-ties; the attention's and
# the indexer's projections larger, so that what a query sees, and which
# keys it chooses, moves its output
PERTURB = [((".scale", ".bias"), moved(0.2)), (("_router.w",), drawn()),
           (("_attn_qkv_colp.w",), drawn(0.3)),
           (("_idx_q.w", "_idx_k.w", "_idx_w.w"), drawn(0.5))]


def feed_of(cfg, seed=1):
    """A batch whose three position rows differ (an image's patch grid
    would: a test's, no tower is built)."""
    feed = M.make_batch(cfg, 2, 64, seed=seed)
    r = np.random.RandomState(5)
    feed["position_ids"] = np.stack([
        np.arange(64), np.sort(r.randint(0, 20, 64)),
        r.randint(0, 20, 64)]).astype(np.int64)
    return feed


@functools.cache
def against_reference(stage):
    """(names, the program's fetches, the reference's forward, loss and
    gradients) of one stage, made once."""
    cfg = M.KeyeConfig(**TINY, num_experts=16, held_experts=HELD, stage=stage)
    main, startup, model, grads = model_test.built(M, cfg, 3)
    assert analysis.lint(main) == [] and analysis.lint(startup) == []
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    model_test.perturb(scope, 12, PERTURB)
    w, feed = snapshot(scope), feed_of(cfg)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        model["loss"], model["index_loss"], model["logits"],
        *model["selections"], *(g for _, g in grads)])
    ref.QUERY_BLOCK = 16
    select = {"select": "dense"} if stage == "warmup" else {}
    want = highest(lambda w_: ref.forward(
        w_, REF_CFG, feed["input_ids"], feed["position_ids"], keep=64,
        **select))(w)
    loss, want_g = highest(jax.value_and_grad(
        lambda w_: ref.loss(w_, REF_CFG, feed, stage=stage)))(w)
    return [p.name for p, _ in grads], got, want, loss, want_g


@pytest.mark.parametrize("stage", ["sparse", "warmup"])
def test_model_loss_logits_selection_and_every_gradient(stage):
    names, got, want, want_loss, want_g = against_reference(stage)
    # float32 on both sides; the same mathematics in another order
    np.testing.assert_allclose(np.ravel(got[0])[0], want_loss, rtol=3e-6)
    np.testing.assert_allclose(np.ravel(got[1])[0], want["index_loss"],
                               rtol=3e-6)
    assert np.shape(got[0]) == np.shape(got[1]) == ()
    assert float(want["index_loss"]) > 0.1
    np.testing.assert_allclose(got[2], want["logits"], atol=2e-6)
    for layer in range(2):
        mine = np.asarray(want["kept"][layer][0])
        np.testing.assert_array_equal(got[3 + layer] != 0, mine)
        k = 64 if stage == "warmup" else 8
        assert (mine.sum(-1) == np.minimum(np.arange(64) + 1, k)).all()
    for name, g in zip(names, got[5:]):
        size = float(jnp.abs(want_g[name]).max())
        assert size > 0, name
        np.testing.assert_allclose(g, want_g[name], atol=3e-5 * size,
                                   err_msg=name)
    # the warm-up stage freezes the model: the indexer alone is trained
    silent = [n for n, g in want_g.items() if not float(jnp.abs(g).max())]
    if stage == "warmup":
        assert sorted(names) == sorted(
            n for n in want_g if n.endswith(INDEXER))
        assert sorted(silent) == sorted(set(want_g) - set(names))
    else:
        assert sorted(names) == sorted(want_g) and not silent


def test_the_two_gradient_paths_are_disjoint():
    """The model's loss gives the indexer's parameters a gradient of
    exactly zero (none at all: no gradient variable exists), and L_I
    gives exactly zero to every other parameter."""
    from paddle_tpu.backward import append_backward

    def owners(loss_key):
        cfg = M.KeyeConfig(**TINY, num_experts=16, held_experts=HELD)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            model = M.build(cfg)
            pairs = append_backward(model[loss_key])
        return {p.name for p, _ in pairs}, {
            p.name for p in main.all_parameters()}

    index, every = owners("index_loss")
    lm, _ = owners("lm_loss")
    assert index == {n for n in every if n.endswith(INDEXER)}
    assert lm == every - index and lm and index
    # and the reference agrees: its two terms' gradients do not overlap
    cfg = M.KeyeConfig(**TINY, num_experts=16, held_experts=HELD)
    feed = feed_of(cfg)
    _, _, _, _, want_g = against_reference("sparse")
    w = {k: jnp.asarray(v) for k, v in want_g.items()}   # any weights
    g_index = highest(jax.grad(lambda w_: ref.forward(
        w_, REF_CFG, feed["input_ids"], feed["position_ids"])["index_loss"]
    ))(w)
    for name, g in g_index.items():
        assert bool(jnp.abs(g).max() > 0) == name.endswith(INDEXER), name


def test_one_step_of_adam_moves_every_parameter():
    cfg = M.KeyeConfig(**TINY, num_experts=16, held_experts=HELD)
    main, startup, model, _ = model_test.built(
        M, cfg, 4, optimizer=lambda: fluid.optimizer.Adam(1e-2))
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    model_test.perturb(scope, 12, PERTURB)
    before, feed = snapshot(scope), feed_of(cfg, seed=2)
    names = [p.name for p in main.all_parameters()]
    first = exe.run(main, feed=feed, scope=scope,
                    fetch_list=[model["loss"], model["index_loss"]])
    after = snapshot(scope)
    for n in names:
        assert np.abs(after[n] - before[n]).max() > 1e-4, n
    for _ in range(8):
        last = exe.run(main, feed=feed, scope=scope,
                       fetch_list=[model["loss"], model["index_loss"]])
    # both terms fall on a batch seen nine times
    assert last[0] < first[0] and last[1] < first[1]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each of eight chips holds two of the sixteen experts the router
    scores; their parts of the layer's output add up to what the
    reference gives for the whole layer."""
    r = np.random.RandomState(3)
    x = r.randn(2, 16, 32).astype(np.float32)
    _, _, w = model_test.moe_layer(16, 2, 16, None, x, name="blk0_moe")
    whole = dict(REF_CFG, num_experts=16, held_first=0)
    want, _, _ = highest(lambda w_: ref.moe(
        jnp.asarray(x).reshape(32, 32), w_, "blk0", whole))(w)
    total = np.zeros((2, 16, 32), np.float32)
    for share in range(8):
        out, rows, _ = model_test.moe_layer(
            16, 2, 16, (2 * share, 2), x, weights=w, name="blk0_moe",
            norm_topk_prob=True)
        total += out
        part, _, _ = highest(lambda w_: ref.moe(
            jnp.asarray(x).reshape(32, 32), w_, "blk0",
            dict(REF_CFG, num_experts=2, held_first=2 * share)))(
                {n: (v[2 * share:2 * share + 2] if v.ndim == 3 else v)
                 for n, v in w.items()})
        np.testing.assert_allclose(out.reshape(32, 32), part, atol=2e-6)
    np.testing.assert_allclose(total.reshape(32, 32), want, atol=5e-6)


def test_names_scopes_and_the_config_defaults():
    cfg = M.keye_vl2_30b_a3b()
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.topk,
            cfg.mrope_section, cfg.rope_theta) == (16, 64, 2048,
                                                   (16, 24, 24), 1e7)
    with pytest.raises(AssertionError):
        M.KeyeConfig(mrope_section=(16, 24, 23))
    tiny = M.KeyeConfig(**TINY, num_experts=16, held_experts=HELD)
    main, _, model, _ = model_test.built(M, tiny, 1)
    scopes = {op.namescope for op in main.global_block().ops
              if op.namescope and "blk0/attn/dsa" in op.namescope}
    assert scopes == {f"/blk0/attn/dsa/{s}/" for s in ("proj", "select",
                                                       "loss")} or all(
        any(s in n for n in scopes) for s in ("proj", "select", "loss"))
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS
    assert ref.AUX_COEF == tiny.router_aux_loss_coef
    assert ref.INDEX_COEF == tiny.index_loss_coef
    assert len(model["last_selected"]) == 2
