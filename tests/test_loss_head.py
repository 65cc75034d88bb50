"""softmax_with_cross_entropy and its own grad op
(paddle_tpu/ops/nn_ops.py): the loss, Softmax and dlogits of the pair
through Program -> Executor against ``jax.vjp`` of the composition the
op was before (benchmarks/xent_candidates.old_composition, kept there
and not in the op: log_softmax in float32, a
gather of the label's log-probability, a zero cotangent where the
program gave none), on every shape of call the models make; what the
lowered pair may not hold (a gather, a scatter-add, a tensor of zeros
the size of the logits); the dispatch counter's rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

import paddle_tpu as fluid
from paddle_tpu import flags, layers
from paddle_tpu.core import autodiff
from paddle_tpu.core.registry import OpDef
from paddle_tpu.ops import nn_ops

from benchmarks.xent_candidates import old_composition


OLD_GRAD = autodiff.make_grad_compute(OpDef(
    type="softmax_with_cross_entropy", compute=old_composition,
    diff_inputs=("Logits",)))
GRAD_META = {"fwd_input_slots": ["Logits", "Label"],
             "fwd_output_slots": ["Softmax", "Loss"]}

VOCAB = 37
# name: (logits' shape, label: hard with / without the trailing 1 or
# soft scaled by a sum, logits' dtype, ignore_index)
CASES = {
    "hard_rank2_trailing1": ((6, VOCAB), "trailing1", "float32", -100),
    "hard_rank2_flat": ((6, VOCAB), "flat", "float32", -100),
    "hard_rank3_trailing1": ((2, 5, VOCAB), "trailing1", "float32", -100),
    "hard_rank3_flat": ((2, 5, VOCAB), "flat", "float32", -100),
    "hard_rank3_bf16": ((2, 5, VOCAB), "trailing1", "bfloat16", -100),
    "hard_two_columns": ((8, 2), "trailing1", "float32", -100),
    "hard_ignore_index": ((2, 5, VOCAB), "trailing1", "float32", 3),
    "hard_ignore_index_bf16": ((9, VOCAB), "flat", "bfloat16", 3),
    "soft_sums_to_1": ((2, 5, VOCAB), 1.0, "float32", -100),
    "soft_sums_to_other": ((6, VOCAB), 0.6, "float32", -100),
    "soft_bf16": ((2, 5, VOCAB), 1.0, "bfloat16", -100),
}


def _operands(case):
    shape, kind, dtype, ignore_index = CASES[case]
    r = np.random.RandomState(sum(map(ord, case)))
    logits = (3.0 * r.randn(*shape)).astype(np.float32)
    soft = not isinstance(kind, str)
    if soft:
        label = r.rand(*shape).astype(np.float32)
        label *= kind / label.sum(-1, keepdims=True)
        label[..., 0, :] *= 0.5     # and one row of another sum still
    else:
        label = r.randint(0, shape[-1], shape[:-1]).astype(np.int64)
        if ignore_index >= 0:
            label.reshape(-1)[::2] = ignore_index
        if kind == "trailing1":
            label = label[..., None]
    attrs = {"soft_label": soft, "ignore_index": ignore_index}
    return logits, label, dtype, attrs


def _run_program(logits, label, dtype, attrs, reads_softmax, w_loss, w_soft):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("logits", shape=list(logits.shape), dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        lbl = layers.data("label", shape=list(label.shape),
                          dtype=label.dtype.name, append_batch_size=False)
        lg = x
        if dtype == "bfloat16":
            # as the cells' logits come: a mul's product under AMP (by
            # the identity, so they are the fed logits rounded to bf16)
            lg = layers.mul(x, layers.assign(
                np.eye(logits.shape[-1], dtype=np.float32)),
                x_num_col_dims=logits.ndim - 1)
        loss, softmax = layers.softmax_with_cross_entropy(
            lg, lbl, return_softmax=True, **attrs)
        obj = layers.reduce_sum(layers.elementwise_mul(
            loss, layers.assign(w_loss)))
        if reads_softmax:
            obj = layers.elementwise_add(obj, layers.reduce_sum(
                layers.elementwise_mul(softmax, layers.assign(w_soft))))
        fluid.append_backward(layers.reshape(obj, [1]), parameter_list=[])
    main._amp = dtype == "bfloat16"
    grad_ops = [op for op in main.global_block().ops
                if op.type == "softmax_with_cross_entropy_grad"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    outs = exe.run(main, feed={"logits": logits, "label": label},
                   fetch_list=[loss, softmax, lg.name + "@GRAD"])
    return outs, grad_ops


@pytest.mark.parametrize("reads_softmax", [False, True],
                         ids=["loss_only", "reads_softmax"])
@pytest.mark.parametrize("case", list(CASES))
def test_pair_agrees_with_the_vjp_of_the_old_composition(case, reads_softmax):
    logits, label, dtype, attrs = _operands(case)
    r = np.random.RandomState(11)
    w_loss = r.uniform(0.1, 1.0, (*logits.shape[:-1], 1)).astype(np.float32)
    w_soft = r.uniform(-1.0, 1.0, logits.shape).astype(np.float32)
    (loss, softmax, dlogits), grad_ops = _run_program(
        logits, label, dtype, attrs, reads_softmax, w_loss, w_soft)

    # the grad op the program holds: the pair's own, and a GRAD::Softmax
    # only where the program made one
    assert len(grad_ops) == 1
    assert sorted(grad_ops[0].inputs) == sorted(
        ["Logits", "Label", "GRAD::Loss"]
        + (["GRAD::Softmax"] if reads_softmax else []))

    lg = jnp.asarray(logits).astype(dtype)
    ins = {"Logits": [lg], "Label": [jnp.asarray(label)]}
    want = old_composition(ins, attrs)
    want_d = OLD_GRAD({**ins, "GRAD::Loss": [jnp.asarray(w_loss)],
                       "GRAD::Softmax": [jnp.asarray(w_soft)]
                       if reads_softmax else [None]},
                      {**attrs, **GRAD_META})["GRAD::Logits"][0]
    assert dlogits.dtype == want_d.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(loss, want["Loss"][0], rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(softmax, want["Softmax"][0], rtol=1e-5,
                               atol=1e-7)
    # float32 round-off; in bf16 one rounding of the float32 value
    rtol, atol = (1e-5, 2e-6) if dtype == "float32" else (2.0 ** -7, 1e-5)
    np.testing.assert_allclose(np.asarray(dlogits, np.float32),
                               np.asarray(want_d, np.float32),
                               rtol=rtol, atol=atol)
    if attrs["ignore_index"] >= 0:
        ignored = (label.reshape(logits.shape[:-1]) == attrs["ignore_index"])
        assert ignored.any() and not ignored.all()
        assert not loss[ignored].any()
        if not reads_softmax:   # (a read Softmax has a gradient there too)
            assert not np.asarray(dlogits, np.float32)[ignored].any()


def _walk(jaxpr, zero, shape, zeros_may_feed, found):
    """Walk ``jaxpr`` with the set ``zero`` of its variables known to be
    all zeros (a literal 0, carried through converts, broadcasts and
    into sub-jaxprs): note every gather / scatter-add, and every reader,
    outside ``zeros_may_feed``, of a float zero tensor of ``shape``."""
    def is_zero(v):
        if isinstance(v, Literal):
            return np.ndim(v.val) == 0 and v.val == 0
        return v in zero

    for e in jaxpr.eqns:
        name = e.primitive.name
        if name in ("gather", "scatter-add", "scatter_add"):
            found.append(name)
        subs = list(jax.core.jaxprs_in_params(e.params))
        if subs:
            for sub in subs:
                inner = {iv for iv, ov in zip(sub.invars, e.invars)
                         if is_zero(ov)}
                _walk(sub, inner, shape, zeros_may_feed, found)
        elif name in ("convert_element_type", "broadcast_in_dim"):
            if is_zero(e.invars[0]):
                zero.add(e.outvars[0])
        elif name not in zeros_may_feed:
            for v in e.invars:
                if (is_zero(v) and v.aval.shape == shape
                        and jnp.issubdtype(v.aval.dtype, jnp.floating)):
                    found.append(f"zeros{list(shape)} -> {name}")


def _faults(fn, logits, *rest, zeros_may_feed=()):
    """What a lowered hard-label call may not hold: a gather or a
    scatter-add, or a float tensor of zeros of the logits' shape (the
    zero cotangent of an unread Softmax) that feeds anything but the
    primitives of ``zeros_may_feed``."""
    found = []
    _walk(jax.make_jaxpr(fn)(logits, *rest).jaxpr, set(),
          tuple(logits.shape), zeros_may_feed, found)
    return sorted(set(found))


HARD = {"soft_label": False, "ignore_index": -100}


def _fwd(compute, logits, label):
    return compute({"Logits": [logits], "Label": [label]}, HARD)["Loss"][0]


def _bwd(compute, logits, label, g):
    return compute({"Logits": [logits], "Label": [label], "Softmax": [None],
                    "Loss": [None], "GRAD::Loss": [g]},
                   {**HARD, **GRAD_META})["GRAD::Logits"][0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what, compute, clean", [
    # the select of the label's logit inside the row's reduce reads
    # zeros; nothing else may
    ("fwd", nn_ops._softmax_with_cross_entropy, True),
    ("bwd", nn_ops._softmax_with_cross_entropy_grad, True),
    # the guard itself: the old composition and its generic grad op are
    # what it is there to catch
    ("fwd", old_composition, False),
    ("bwd", OLD_GRAD, False),
], ids=["fwd", "bwd", "old_fwd_is_caught", "old_bwd_is_caught"])
def test_hard_label_pair_holds_no_gather_and_no_zero_cotangent(
        what, compute, clean, dtype):
    logits = jnp.zeros((2, 8, 128), dtype)
    label = jnp.zeros((2, 8, 1), jnp.int32)
    if what == "fwd":
        faults = _faults(lambda x, lb: _fwd(compute, x, lb), logits, label,
                         zeros_may_feed=("select_n",))
    else:
        faults = _faults(lambda x, lb, g: _bwd(compute, x, lb, g), logits,
                         label, jnp.ones((2, 8, 1), jnp.float32))
    assert (faults == []) if clean else faults, faults


@pytest.fixture
def telemetry():
    flags.set_flags({"telemetry": True})
    yield
    flags.set_flags({"telemetry": False})


@pytest.mark.parametrize("case, reads_softmax, rows", [
    ("hard_rank3_trailing1", False, {"hard fwd 0": 1, "hard bwd 0": 1}),
    ("soft_sums_to_1", False, {"soft fwd 0": 1, "soft bwd 0": 1}),
    ("hard_rank2_flat", True, {"hard fwd 0": 1, "hard bwd 1": 1}),
], ids=["hard", "soft", "reads_softmax"])
def test_dispatch_counter_rows(telemetry, case, reads_softmax, rows):
    logits, label, dtype, attrs = _operands(case)
    _run_program(logits, label, dtype, attrs, reads_softmax,
                 np.ones((*logits.shape[:-1], 1), np.float32),
                 np.ones(logits.shape, np.float32))
    assert nn_ops.loss_head_dispatch_counts() == rows


def test_counter_is_silent_with_telemetry_off():
    before = nn_ops.loss_head_dispatch_counts()
    logits, label, dtype, attrs = _operands("hard_rank2_flat")
    _run_program(logits, label, dtype, attrs, False,
                 np.ones((6, 1), np.float32), np.ones((6, VOCAB), np.float32))
    assert nn_ops.loss_head_dispatch_counts() == before


def test_dygraph_backward_through_the_forward_alone():
    """The eager engine differentiates the forward itself (no grad
    maker there): the new forward's own vjp is the same gradient."""
    logits, label, _, attrs = _operands("hard_ignore_index")
    ins = {"Logits": [jnp.asarray(logits)], "Label": [jnp.asarray(label)]}
    g = jnp.full((*logits.shape[:-1], 1), 0.25, jnp.float32)
    new_grad = autodiff.make_grad_compute(OpDef(
        type="softmax_with_cross_entropy",
        compute=nn_ops._softmax_with_cross_entropy, diff_inputs=("Logits",)))
    got, want = (f({**ins, "GRAD::Loss": [g], "GRAD::Softmax": [None]},
                   {**attrs, **GRAD_META})["GRAD::Logits"][0]
                 for f in (new_grad, OLD_GRAD))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)
