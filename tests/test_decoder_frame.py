"""The frame the decoder builders share (models/decoder.py) and the
layer of the attention op they call (layers.scaled_dot_product_attention):
the op the layer appends and its result and gradients against the float32
composition; each piece of the frame once; and the guard of the seam: a
decoder builder spells none of the frame for itself."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import decoder

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "models")
BUILDERS = ("olmoe", "qwen3_next", "joyai_flash", "smallthinker",
            "phi4flash", "nemotron_h", "lfm2_moe", "laguna",
            "kimi_linear", "xing4")
# functions of these names that are NOT the frame's: a zero-centred
# RMSNorm, a LayerNorm and a projection with a bias
OWN = {("qwen3_next", "_norm"), ("phi4flash", "_norm"),
       ("phi4flash", "_linear")}


def composition(q, k, v, scale, window):
    """softmax(scale q k^T) v by explicit float32 scores: causal, a query
    sees its last ``window`` positions, query head i reads key/value head
    i // group."""
    group, t = q.shape[1] // k.shape[1], q.shape[2]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    p, s = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (s <= p) & (p - s < (window or t))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1),
                      v)


@pytest.mark.parametrize("window", [None, 5], ids=["causal", "window"])
@pytest.mark.parametrize("h,hk", [(4, 4), (4, 2)], ids=["h_eq_hk", "grouped"])
@pytest.mark.parametrize("dk,dv", [(8, 8), (12, 8)],
                         ids=["dv_eq_dk", "dv_ne_dk"])
def test_sdpa_layer_is_one_op_and_the_float32_composition(window, h, hk,
                                                          dk, dv):
    b, t, scale = 2, 16, dk ** -0.5
    r = np.random.RandomState(3)
    feed = {"q": r.randn(b, h, t, dk), "k": r.randn(b, hk, t, dk),
            "v": r.randn(b, hk, t, dv), "w": r.randn(b, h, t, dv)}
    feed = {n: a.astype(np.float32) for n, a in feed.items()}

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ins = {n: layers.data(n, shape=list(a.shape[1:]), dtype="float32")
               for n, a in feed.items()}
        for n in "qkv":
            ins[n].stop_gradient = False
        before = len(main.global_block().ops)
        out = layers.scaled_dot_product_attention(
            ins["q"], ins["k"], ins["v"], scale, window=window,
            name="blk7_attn_sdpa")
        ops = main.global_block().ops[before:]
        loss = layers.reduce_sum(layers.elementwise_mul(out, ins["w"]))
        fluid.append_backward(loss, parameter_list=[])

    # ONE op, with the attrs every decoder builder wrote by hand
    assert [op.type for op in ops] == ["scaled_dot_product_attention"]
    (op,) = ops
    assert sorted(op.inputs) == ["K", "Q", "V"]
    assert sorted(op.outputs) == ["Lse", "Out"]
    want = {"scale": scale, "dropout_prob": 0.0, "is_test": True,
            "layout": "bhtd", "causal": True}
    if window:
        want["window"] = window
    got = {k: v for k, v in op.attrs.items() if k in want or k == "window"}
    assert got == want
    # temporaries named from ``name``, as LayerHelper(name) counts them
    assert op.outputs["Out"] == [out.name] == ["blk7_attn_sdpa_0.tmp_0"]
    assert op.outputs["Lse"] == ["blk7_attn_sdpa_0.tmp_1"]
    lse = main.global_block().var("blk7_attn_sdpa_0.tmp_1")
    assert str(lse.dtype) == "float32" and lse.stop_gradient
    assert not out.stop_gradient

    exe = fluid.Executor()
    exe.run(startup)
    got_out, dq, dk_, dv_ = exe.run(
        main, feed=feed, fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD"])
    ref, vjp = jax.vjp(
        lambda q, k, v: composition(q, k, v, scale, window),
        *(jnp.asarray(feed[n]) for n in "qkv"))
    np.testing.assert_allclose(got_out, ref, rtol=2e-5, atol=2e-5)
    for got_g, ref_g in zip((dq, dk_, dv_), vjp(jnp.asarray(feed["w"]))):
        np.testing.assert_allclose(got_g, ref_g, rtol=2e-4, atol=2e-5)


def _program():
    return fluid.program_guard(fluid.Program(), fluid.Program())


def _scopes(ops):
    return {op.attrs.get("op_namescope") for op in ops}


def test_embed_is_one_lookup_under_its_scope():
    with _program():
        ids, lbl = decoder.token_feeds()
        assert (ids.name, lbl.name) == ("input_ids", "labels")
        assert str(ids.dtype) == str(lbl.dtype) == "int64"
        x = decoder.embed(ids, 50, 16, "fam_tok_emb.w", 1.0)
        main = fluid.default_main_program()
        ops = main.global_block().ops
        assert [op.type for op in ops] == ["lookup_table"]
        assert _scopes(ops) == {"embed"}
        assert main.global_block().var("fam_tok_emb.w").shape == (50, 16)
        assert tuple(x.shape)[-1] == 16
        init = fluid.default_startup_program().global_block().ops[-1]
        assert init.type == "gaussian_random" and init.attrs["std"] == 1.0


def test_lm_head_is_logits_and_the_mean_cross_entropy_under_loss_head():
    with _program():
        ids, lbl = decoder.token_feeds()
        x = decoder.embed(ids, 50, 16, "t.w")
        n = len(fluid.default_main_program().global_block().ops)
        logits, loss = decoder.lm_head(x, lbl, 50)
        main = fluid.default_main_program()
        ops = main.global_block().ops[n:]
        assert _scopes(ops) == {"loss_head"}
        assert [op.type for op in ops if op.type != "unsqueeze"][-2:] == [
            "softmax_with_cross_entropy", "mean"]
        assert main.global_block().var("lm_head_colp.w").shape == (16, 50)
        # the tied head reads the table and makes no parameter
        params = {p.name for p in main.all_parameters()}
        tied, tied_loss = decoder.tied_lm_head(x, lbl, "t.w")
        assert {p.name for p in main.all_parameters()} == params
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        batch = decoder.make_batch(type("C", (), {"vocab_size": 50}), 2, 8)
        lo, ls, tl, tls = exe.run(main, feed=batch,
                                  fetch_list=[logits, loss, tied, tied_loss])
    assert lo.shape == tl.shape == (2, 8, 50)
    for lg, got in ((lo, ls), (tl, tls)):
        logp = jax.nn.log_softmax(jnp.asarray(lg, jnp.float32), -1)
        want = -np.take_along_axis(np.asarray(logp),
                                   batch["labels"][..., None], -1).mean()
        np.testing.assert_allclose(np.asarray(got).reshape(()), want,
                                   rtol=1e-5)


def test_last_logits_is_the_rows_last_positions_under_loss_head():
    with _program():
        x = layers.data("x", shape=[8, 5], dtype="float32")
        last = decoder.last_logits(x, 3)
        main = fluid.default_main_program()
        (op,) = main.global_block().ops
        assert op.type == "slice" and _scopes([op]) == {"loss_head"}
        a = np.arange(2 * 8 * 5, dtype=np.float32).reshape(2, 8, 5)
        (got,) = fluid.Executor().run(main, feed={"x": a}, fetch_list=[last])
    np.testing.assert_array_equal(got, a[:, -3:])


def test_sum_of_one_tensor_is_itself_and_of_more_one_op():
    with _program():
        xs = [layers.data(f"x{i}", shape=[3], dtype="float32")
              for i in range(3)]
        ops = fluid.default_main_program().global_block().ops
        assert decoder.sum_of(xs[:1]) is xs[0] and not ops
        total = decoder.sum_of(xs)
        assert [op.type for op in ops] == ["sum"]
        feed = {f"x{i}": np.full((2, 3), float(i + 1), np.float32)
                for i in range(3)}
        (got,) = fluid.Executor().run(fluid.default_main_program(),
                                      feed=feed, fetch_list=[total])
    np.testing.assert_array_equal(got, np.full((2, 3), 6.0, np.float32))


def test_make_batch_shifts_the_labels_by_one_and_is_the_builders_own():
    cfg = type("C", (), {"vocab_size": 11})
    batch = decoder.make_batch(cfg, 3, 7, seed=5)
    ids, lbl = batch["input_ids"], batch["labels"]
    assert ids.shape == lbl.shape == (3, 7) and ids.dtype == np.int64
    np.testing.assert_array_equal(ids[:, 1:], lbl[:, :-1])
    assert 0 <= min(ids.min(), lbl.min()) and max(ids.max(), lbl.max()) < 11
    np.testing.assert_array_equal(
        decoder.make_batch(cfg, 3, 7, seed=5)["labels"], lbl)
    import importlib

    for name in BUILDERS:
        mod = importlib.import_module(f"paddle_tpu.models.{name}")
        assert mod.make_batch is decoder.make_batch, name


@pytest.mark.parametrize("name", BUILDERS)
def test_a_decoder_builder_spells_none_of_the_frame_itself(name):
    """The seam's guard: the parameters' attribute, the plain norm and
    projection, the batch and the attention op's append live in
    models/decoder.py and layers/nn.py, once."""
    with open(os.path.join(MODELS, f"{name}.py")) as f:
        tree = ast.parse(f.read())
    defined = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    assert "_w" not in defined, "decoder.weight is the one copy"
    assert "make_batch" not in defined, "decoder.make_batch is the one copy"
    for fn, shared in (("_norm", "rms_norm"), ("_linear", "linear")):
        assert fn not in defined or (name, fn) in OWN, (
            f"{fn}: decoder.{shared} is the one copy")
    literals = {n.value for n in ast.walk(tree)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    assert "scaled_dot_product_attention" not in literals, (
        "layers.scaled_dot_product_attention appends the op")
    imported = {a.name for n in tree.body if isinstance(n, ast.ImportFrom)
                and n.module == "paddle_tpu.models.decoder" for a in n.names}
    assert "make_batch" in imported


def test_the_frame_is_a_leaf_and_knows_no_family():
    with open(os.path.join(MODELS, "decoder.py")) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert not (node.module or "").startswith("paddle_tpu.models")
        if isinstance(node, ast.Import):
            assert not any(a.name.startswith("paddle_tpu.models")
                           for a in node.names)
        if isinstance(node, ast.FunctionDef):
            args = {a.arg for a in node.args.args + node.args.kwonlyargs}
            assert not args & {"kind", "family", "model"}, node.name
