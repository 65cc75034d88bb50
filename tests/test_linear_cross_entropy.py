"""linear_cross_entropy and its own grad op (paddle_tpu/ops/nn_ops.py):
the vocabulary projection and the loss over the rows whose label counts.
Through Program -> Executor against ``jax.vjp`` of what it replaces in
BERT's head (mul + softmax_with_cross_entropy + the multiply that zeroes
the rows without a label), for every live count the loop's trip count
can take; the eager engine's gradient; what the op may not hold (an
array of [rows, vocab]); BERT's program against the parent graph rebuilt
from fc + softmax_with_cross_entropy; the dispatch counter's rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers
from paddle_tpu.core import autodiff
from paddle_tpu.core.registry import OpDef
from paddle_tpu.models import bert as B
from paddle_tpu.ops import math_ops, nn_ops
from paddle_tpu.param_attr import ParamAttr

CHUNK = nn_ops._ROWS_CHUNK
ROWS, WIDTH = 2 * CHUNK + 256, 32
VOCAB = 3052            # BERT's 30522 scaled down: off the 128 lanes
# name: rows of ROWS whose label counts
# ("one", "chunk_and_a_row", "every_row": the last trip is the short one;
# "a_short_trip_and_a_row": one row too many for it)
SHORT = CHUNK // nn_ops._SHORT_TRIP
LIVE = {"none": 0, "one": 1, "one_chunk": CHUNK, "chunk_and_a_row": CHUNK + 1,
        "every_row": ROWS, "random_15_percent": None,
        "chunk_and_a_short_trip": CHUNK + SHORT,
        "a_short_trip_and_a_row": SHORT + 1}


def _operands(live, ignore_index, seed=0):
    r = np.random.RandomState(seed)
    x = r.randn(ROWS, WIDTH).astype(np.float32)
    label = r.randint(0, VOCAB, (ROWS, 1)).astype(np.int64)
    if ignore_index >= 0:
        label[label == ignore_index] += 1
    if LIVE[live] is None:
        skipped = r.rand(ROWS) >= 0.15
    else:
        skipped = np.ones(ROWS, bool)
        skipped[r.choice(ROWS, LIVE[live], replace=False)] = False
    label[skipped] = ignore_index
    g = r.uniform(0.1, 1.0, (ROWS, 1)).astype(np.float32)
    return x, label, g, int((~skipped).sum())


def old_head(x, w, label, ignore_index):
    """What the op replaces, by the ops it replaces: every row projected,
    scored against a label made safe, and the rows that do not count
    multiplied by zero."""
    logits = math_ops._mul({"X": [x], "Y": [w]}, {})["Out"][0]
    loss = nn_ops._softmax_with_cross_entropy(
        {"Logits": [logits], "Label": [jnp.maximum(label, 0)]},
        {"soft_label": False})["Loss"][0]
    return loss * (label != ignore_index).astype(loss.dtype)


def _run_program(x, label, g, ignore_index, amp):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        lbl = layers.data("label", shape=list(label.shape), dtype="int64",
                          append_batch_size=False)
        loss = layers.linear_cross_entropy(
            xv, VOCAB, lbl, ignore_index=ignore_index,
            param_attr=ParamAttr(
                name="proj.w",
                initializer=fluid.initializer.NormalInitializer(0.0, 0.3)))
        obj = layers.reduce_sum(layers.elementwise_mul(
            loss, layers.assign(g)))
        fluid.append_backward(layers.reshape(obj, [1]))
    main._amp = amp
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    outs = exe.run(main, feed={"x": x, "label": label},
                   fetch_list=[loss, "x@GRAD", "proj.w@GRAD", "proj.w"])
    return outs, main


@pytest.mark.parametrize("ignore_index", [-1, 7], ids=["ignore-1", "ignore7"])
@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
@pytest.mark.parametrize("live", list(LIVE))
def test_op_and_grad_op_agree_with_the_vjp_of_the_old_head(
        live, amp, ignore_index):
    x, label, g, count = _operands(live, ignore_index)
    (loss, dx, dw, w), main = _run_program(x, label, g, ignore_index, amp)
    types = [op.type for op in main.global_block().ops]
    assert types.count("linear_cross_entropy") == 1
    assert types.count("linear_cross_entropy_grad") == 1

    dtype = jnp.bfloat16 if amp else jnp.float32
    xs, ws = jnp.asarray(x).astype(dtype), jnp.asarray(w).astype(dtype)
    want, vjp = jax.vjp(
        lambda a, b: old_head(a, b, jnp.asarray(label), ignore_index),
        xs, ws)
    want_dx, want_dw = vjp(jnp.asarray(g))

    skipped = label[:, 0] == ignore_index
    assert (~skipped).sum() == count
    assert loss.shape == (ROWS, 1) and loss.dtype == np.float32
    assert dx.dtype == want_dx.dtype and dw.dtype == want_dw.dtype == dtype
    assert not loss[skipped].any()
    assert not np.asarray(dx, np.float32)[skipped].any()
    if count:
        assert np.asarray(dx, np.float32)[~skipped].any()
    else:
        assert not np.asarray(dw, np.float32).any()
    # float32: round-off of sums taken in another order. bf16: a logit
    # (up to 8 here) rounds to its other neighbour where a chunk's
    # product sums in another order than the whole matrix's, its row's
    # softmax moves by that much, and dX and dW are float32 sums cast to
    # bf16: an ulp of bf16 at the tensor's largest value
    np.testing.assert_allclose(loss, want, rtol=1e-5,
                               atol=2.0 ** -6 if amp else 1e-5)
    for got_d, want_d in ((dx, want_dx), (dw, want_dw)):
        got_d, want_d = (np.asarray(d, np.float32) for d in (got_d, want_d))
        rtol, atol = (1e-5, 1e-5) if not amp else (
            2.0 ** -6, 2.0 ** -7 * np.abs(want_d).max())
        np.testing.assert_allclose(got_d, want_d, rtol=rtol, atol=atol)


def test_rank3_rows_and_flat_labels_as_berts_head_feeds_them():
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(3, 5, 8), jnp.float32)
    w = jnp.asarray(r.randn(8, 11), jnp.float32)
    label = r.randint(0, 11, (3, 5))
    label[r.rand(3, 5) < 0.6] = -1
    got = nn_ops._linear_cross_entropy(
        {"X": [x], "W": [w], "Label": [jnp.asarray(label)]},
        {"ignore_index": -1})["Loss"][0]
    want = old_head(x.reshape(15, 8), w,
                    jnp.asarray(label).reshape(15, 1), -1)
    assert got.shape == (3, 5, 1)
    np.testing.assert_allclose(got.reshape(15, 1), want, rtol=1e-5,
                               atol=1e-6)


GRAD_META = {"fwd_input_slots": ["X", "W", "Label"],
             "fwd_output_slots": ["Loss"]}


def test_the_eager_engines_gradient_is_the_grad_ops():
    """dygraph/tracer.py differentiates the forward itself
    (autodiff.make_grad_compute): the forward's custom vjp hands it the
    grad op's own function, loop and all."""
    x, label, g, _ = _operands("random_15_percent", -1, seed=5)
    w = np.random.RandomState(6).randn(WIDTH, VOCAB).astype(np.float32)
    ins = {"X": [jnp.asarray(x)], "W": [jnp.asarray(w)],
           "Label": [jnp.asarray(label)], "GRAD::Loss": [jnp.asarray(g)]}
    attrs = {"ignore_index": -1}
    eager = autodiff.make_grad_compute(OpDef(
        type="linear_cross_entropy", compute=nn_ops._linear_cross_entropy,
        diff_inputs=("X", "W")))({**ins, "Loss": [None]},
                                 {**attrs, **GRAD_META})
    own = nn_ops._linear_cross_entropy_grad(ins, attrs)
    for slot in ("GRAD::X", "GRAD::W"):
        np.testing.assert_array_equal(eager[slot][0], own[slot][0])
    assert np.asarray(own["GRAD::W"][0]).any()


def test_dygraph_backward_reaches_both_operands():
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.tracer import get_tracer

    x, label, _, _ = _operands("random_15_percent", -1, seed=8)
    w = np.random.RandomState(9).randn(WIDTH, VOCAB).astype(np.float32) * 0.3
    with dygraph.guard():
        xv, wv = dygraph.to_variable(x), dygraph.to_variable(w)
        xv.stop_gradient = wv.stop_gradient = False
        loss = get_tracer().trace_op(
            "linear_cross_entropy",
            {"X": xv, "W": wv, "Label": dygraph.to_variable(label)},
            {"ignore_index": -1})["Loss"][0]
        total = get_tracer().trace_op(
            "reduce_sum", {"X": loss}, {"reduce_all": True})["Out"][0]
        total.backward()
        dx, dw = np.asarray(xv.gradient()), np.asarray(wv.gradient())
    _, vjp = jax.vjp(
        lambda a, b: jnp.sum(old_head(a, b, jnp.asarray(label), -1)),
        jnp.asarray(x), jnp.asarray(w))
    want_dx, want_dw = vjp(jnp.ones((), jnp.float32))
    np.testing.assert_allclose(dx, want_dx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-5)


# --- what the op may not hold ------------------------------------------------


def _shapes(jaxpr, out):
    for e in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in e.outvars)
        for sub in jax.core.jaxprs_in_params(e.params):
            _shapes(sub, out)
    return out


def _array_shapes(fn, *args):
    """The shape of every array ``fn`` makes, its loops' bodies and its
    custom vjp's rule included."""
    return _shapes(jax.make_jaxpr(fn)(*args).jaxpr, set())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what, clean", [
    ("fwd", True), ("bwd", True), ("eager_bwd", True),
    # the guard itself: the composition the op replaces is what it is
    # there to catch
    ("old_fwd", False), ("old_bwd", False),
], ids=lambda v: v if isinstance(v, str) else "")
def test_no_array_of_rows_by_vocab(what, clean, dtype):
    rows, vocab = 4 * CHUNK, 384
    x = jnp.zeros((rows, WIDTH), dtype)
    w = jnp.zeros((WIDTH, vocab), dtype)
    label = jnp.zeros((rows, 1), jnp.int32)
    g = jnp.ones((rows, 1), jnp.float32)
    new = lambda a, b: nn_ops._linear_cross_entropy(
        {"X": [a], "W": [b], "Label": [label]}, {"ignore_index": -1}
    )["Loss"][0]
    old = lambda a, b: old_head(a, b, label, -1)
    fn = {
        "fwd": new,
        "bwd": lambda a, b: nn_ops._linear_cross_entropy_grad(
            {"X": [a], "W": [b], "Label": [label], "GRAD::Loss": [g]},
            {"ignore_index": -1}),
        "eager_bwd": lambda a, b: jax.vjp(new, a, b)[1](g),
        "old_fwd": old,
        "old_bwd": lambda a, b: jax.vjp(old, a, b)[1](g),
    }[what]
    shapes = _array_shapes(fn, x, w)
    # the walk reaches the loop's body: a chunk's logits are there
    assert ((CHUNK, vocab) in shapes) == clean
    assert ((rows, vocab) not in shapes) == clean, sorted(shapes)


# --- the layer ---------------------------------------------------------------


def test_layer_makes_its_parameter_as_fc_does():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[5, 8], dtype="float32")
        lbl = layers.data("label", shape=[5], dtype="int64")
        layers.fc(x, 13, num_flatten_dims=2, bias_attr=False)
        loss = layers.linear_cross_entropy(x, 13, lbl)
        named = layers.linear_cross_entropy(
            x, 13, lbl, ignore_index=-1, param_attr=ParamAttr(name="head.w"))
    params = {p.name: p for p in main.global_block().all_parameters()}
    auto = [n for n in params if n.startswith("linear_cross_entropy_")]
    assert len(params) == 3 and "head.w" in params
    assert len(auto) == 1 and auto[0].endswith(".w_0")      # as fc_N.w_0
    assert {tuple(p.shape) for p in params.values()} == {(8, 13)}
    inits = [op for op in startup.global_block().ops]
    assert len(inits) == 3 and len({op.type for op in inits}) == 1  # Xavier
    assert tuple(loss.shape) == (-1, 5, 1) and loss.dtype == "float32"
    op = main.global_block().ops[-1]
    assert op.type == "linear_cross_entropy"
    assert op.attrs["ignore_index"] == -1 and named is not None


# --- BERT's program against the parent graph -------------------------------------

TINY = dict(vocab_size=61, max_position=16, d_model=16, d_inner=32,
            n_head=2, n_layer=2, dropout=0.1)


def parent_head(x, size, label, ignore_index=-100, param_attr=None):
    """models/bert.py's head before the op, from the layers it used: fc
    over every position, a label made safe, and the multiply by
    ``is_masked`` (here inside, so that the caller's sum is the
    parent's)."""
    logits = layers.fc(x, size, num_flatten_dims=2, param_attr=param_attr,
                       bias_attr=False)
    safe = layers.elementwise_max(
        label, layers.fill_constant_like(label, 0.0))
    ce = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(safe, [2]))
    is_masked = layers.cast(
        layers.greater_than(
            layers.cast(label, "float32"),
            layers.fill_constant_like(layers.cast(label, "float32"), -0.5)),
        "float32")
    return layers.elementwise_mul(ce, layers.unsqueeze(is_masked, [2]))


def _bert_step(seed, labels):
    """(loss, mlm_loss, mlm_logits, {parameter: gradient}) of one step of
    models/bert.build at a tiny size on seeded weights, dropout on."""
    cfg = B.BertConfig(**TINY)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 100 + seed
    with fluid.program_guard(main, startup):
        model = B.build(cfg)
        pairs = fluid.append_backward(model["loss"])
    feed = B.make_batch(cfg, 6, 16, seed=seed)
    if labels == "none_masked":
        feed["mlm_labels"][:] = -1
    elif labels == "all_masked":
        feed["mlm_labels"] = np.random.RandomState(seed).randint(
            0, cfg.vocab_size, feed["mlm_labels"].shape).astype(np.int64)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    names = sorted(p.name for p, _ in pairs)
    grads = {p.name: g for p, g in pairs}
    outs = exe.run(
        main, feed=feed, scope=scope,
        fetch_list=[model["loss"], model["mlm_loss"], model["mlm_logits"]]
        + [grads[n] for n in names])
    types = [op.type for op in main.global_block().ops]
    return outs[0], outs[1], outs[2], dict(zip(names, outs[3:])), types


@pytest.mark.parametrize("labels", ["as_fed", "none_masked", "all_masked"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_berts_step_is_the_parent_graphs(seed, labels, monkeypatch):
    loss, mlm_loss, logits, grads, types = _bert_step(seed, labels)
    assert types.count("linear_cross_entropy") == 1
    assert types.count("linear_cross_entropy_grad") == 1
    monkeypatch.setattr(layers, "linear_cross_entropy", parent_head)
    p_loss, p_mlm, p_logits, p_grads, p_types = _bert_step(seed, labels)
    assert "linear_cross_entropy" not in p_types

    np.testing.assert_allclose(loss, p_loss, rtol=1e-6)
    np.testing.assert_allclose(mlm_loss, p_mlm, rtol=1e-6, atol=1e-7)
    # every position's logits can still be fetched
    assert logits.shape == (6, 16, TINY["vocab_size"])
    np.testing.assert_allclose(logits, p_logits, rtol=1e-6, atol=1e-7)
    assert sorted(grads) == sorted(p_grads) and "mlm_proj_colp.w" in grads
    for name in grads:
        np.testing.assert_allclose(grads[name], p_grads[name], rtol=2e-5,
                                   atol=2e-7, err_msg=name)
    if labels == "none_masked":
        assert not mlm_loss.any() and not grads["mlm_proj_colp.w"].any()
    else:
        assert mlm_loss > 1.0 and grads["mlm_proj_colp.w"].any()


# --- the dispatch counter ------------------------------------------------------


@pytest.fixture
def telemetry():
    flags.set_flags({"telemetry": True})
    yield
    flags.set_flags({"telemetry": False})


def test_dispatch_counter_rows(telemetry):
    x, label, g, _ = _operands("random_15_percent", -1)
    _run_program(x, label, g, -1, amp=True)
    assert nn_ops.loss_head_dispatch_counts() == {
        "hard_rows fwd 0": 1, "hard_rows bwd 0": 1}


def test_berts_lowered_step_counts_no_hard_row_for_its_vocabulary(telemetry):
    _bert_step(0, "as_fed")
    # the next-sentence head's two columns are the only plain call left
    assert nn_ops.loss_head_dispatch_counts() == {
        "hard_rows fwd 0": 1, "hard_rows bwd 0": 1,
        "hard fwd 0": 1, "hard bwd 0": 1}


def test_the_last_trip_is_a_short_one_with_a_body_of_its_own():
    """One loop of whole chunks each way, and in front of it a branch
    that is the short trip, of a quarter of a chunk, or nothing
    (nn_ops._over_chunks); rows that are no whole sublane tiles a
    quarter have no branch, nor have more rows than four chunks."""
    x, label, _, _ = _operands("random_15_percent", -1)
    w = jnp.zeros((WIDTH, VOCAB), jnp.float32)

    def projected(rows):
        lbl, xs = (jnp.asarray(np.resize(a, (rows, a.shape[1])))
                   for a in (label, x))
        text = str(jax.make_jaxpr(lambda a, b: nn_ops._linear_xent(
            a, b, lbl, -1))(xs, w))
        return {n for n in (rows, CHUNK, SHORT, rows // nn_ops._SHORT_TRIP)
                if f"f32[{n},{VOCAB}]" in text}, (
                    text.count("while["), text.count("cond["))

    assert projected(ROWS) == ({CHUNK, SHORT}, (1, 1))
    assert projected(64) == ({64, 16}, (1, 1))
    assert projected(60) == ({60}, (1, 0))
    assert projected(4 * CHUNK) == ({CHUNK, SHORT}, (1, 1))
    assert projected(4 * CHUNK + 8) == ({CHUNK}, (1, 0))
