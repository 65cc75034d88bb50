"""An expert matrix's Adam inside the op that makes its gradient
(``AdamOptimizer._fold_into_experts_grad``, ``moe_experts_grad``): which
programs it engages in, that a program it does not engage in is the one
it always was, op for op, and that the state after three steps is the
unfolded program's: to the bit where no kernel runs (the ``adam`` op's
own arithmetic behind the same gradient), within the gradient's bf16
rounding where the step is taken inside the weight-gradient kernel (the
interpreter; the two passes round the gradient to bf16 in between).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import clip as clip_mod
from paddle_tpu import flags, layers, monitor, regularizer
from paddle_tpu.optimizer import AdamOptimizer
from paddle_tpu.parallel import grouped_matmul as gm

N, D, F, E, K = 256, 128, 128, 4, 2
LR = 1e-2
EXPERTS = [f"{layer}_{m}.w" for layer in ("m", "m2")
           for m in ("gate", "up", "down")]


@pytest.fixture(autouse=True)
def _clean():
    def reset():
        monitor.reset()
        clip_mod.set_gradient_clip.__globals__["_clip_attr"] = None
        clip_mod.set_gradient_clip.__globals__["_clip_param_names"] = None
        flags.set_flags({"telemetry": False})
    reset()
    yield
    reset()


@pytest.fixture
def unfolded(monkeypatch):
    """The optimizer as it was: an update op a parameter."""
    def off():
        monkeypatch.setattr(AdamOptimizer, "_fold_into_experts_grad",
                            lambda self, block, pg: False)
    return off


def build(optimizer, amp=False, before_minimize=None, gated=True):
    """Two top-k MoE layers (the second's experts plain units where not
    ``gated``) and a dense projection under ``optimizer().minimize``."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        probe = layers.data("p", shape=[N, D], dtype="float32",
                            append_batch_size=False)
        h, *_ = layers.topk_moe(x, E, K, F, name="m")
        h, *_ = layers.topk_moe(h, E, K, F, name="m2", gated=gated,
                                act="silu" if gated else "relu2")
        h = layers.fc(h, D, num_flatten_dims=1, bias_attr=False)
        loss = layers.reduce_sum(layers.elementwise_mul(h, probe))
        if before_minimize:
            before_minimize(main, loss)
        optimizer().minimize(loss)
    main._amp = amp
    return main, startup, loss


def feed():
    r = np.random.RandomState(11)
    return {"x": r.randn(N, D).astype(np.float32),
            "p": r.randn(N, D).astype(np.float32) * 0.1}


def state_after(prog, steps=3, fetch=()):
    main, startup, loss = prog
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    for _ in range(steps):
        out = exe.run(main, feed=feed(), scope=scope,
                      fetch_list=[loss, *fetch])
    state = {v.name: np.asarray(scope.find_var(v.name))
             for v in main.global_block().vars.values() if v.persistable}
    return state, out[1:]


def ops_of(main):
    return [(op.type, op.inputs, op.outputs, op.attrs)
            for op in main.global_block().ops]


def updates(main):
    """{parameter: type of the op that writes it}."""
    return {name: op.type for op in main.global_block().ops
            for name in op.output("ParamOut")}


def adam(**kw):
    return lambda: fluid.optimizer.Adam(LR, **kw)


def adamw():
    return fluid.optimizer.AdamW(LR, weight_decay=0.05)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("optimizer", [adam(), adamw], ids=["adam", "adamw"])
def test_the_experts_matrices_have_no_update_op_and_the_rest_one_each(
        optimizer, gated):
    main, _, _ = build(optimizer, gated=gated)
    experts = [n for n in EXPERTS if gated or n != "m2_gate.w"]
    params = {p.name for p in main.all_parameters()}
    assert set(experts) < params
    kind = "adamw" if optimizer is adamw else "adam"
    assert updates(main) == {
        n: "moe_experts_grad" if n in experts else kind for n in params}
    for op in main.global_block().ops:
        if op.type != "moe_experts_grad":
            continue
        slots = op.attrs["adam_slots"]
        assert sorted(slots) == sorted(
            s for s in ("WGate", "WUp", "WDown") if op.input(s))
        assert op.attrs["adam_op"] == kind
        assert not any(op.output("GRAD::" + s) for s in slots)
        # one entry a matrix, in the order of the slots, under the names
        # the update op has them
        assert op.input("Param") == [op.input(s)[0] for s in slots]
        assert op.output("ParamOut") == op.input("Param")
        for name, out in (("Moment1", "Moment1Out"),
                          ("Moment2", "Moment2Out"),
                          ("Beta1Pow", "Beta1PowOut"),
                          ("Beta2Pow", "Beta2PowOut")):
            assert op.output(out) == op.input(name)
            assert len(set(op.input(name))) == len(slots)
        assert len(set(op.input("LearningRate"))) == 1


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("optimizer", [adam(), adamw], ids=["adam", "adamw"])
def test_without_a_kernel_three_steps_are_the_unfolded_programs_to_the_bit(
        optimizer, gated, unfolded):
    got, _ = state_after(build(optimizer, gated=gated))
    unfolded()
    plain = build(optimizer, gated=gated)
    assert "moe_experts_grad" not in updates(plain[0]).values()
    want, _ = state_after(plain)
    assert sorted(got) == sorted(want)      # the same variable names
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    assert not np.array_equal(want["m_gate.w_beta1_pow_0"], [1.0])


@pytest.mark.parametrize("optimizer", [adam(), adamw], ids=["adam", "adamw"])
def test_inside_the_kernel_three_steps_are_within_the_gradients_rounding(
        optimizer, unfolded, monkeypatch):
    """bf16 AMP through the interpreted kernels, telemetry on: the folded
    program lowers ``bwd_dw_adam`` calls and no tiled ``bwd_dw``; the
    unfolded one writes each gradient as bf16 and reads it back."""
    monkeypatch.setattr(gm, "_INTERPRET", True)
    flags.set_flags({"telemetry": True})
    got, _ = state_after(build(optimizer, amp=True))
    counts = gm.gmm_dispatch_counts()
    shape = f"m{N * K} k{D} n{F} e{E} [tm128 tk128 tn128]"
    assert counts == {f"{p} {shape}": 6
                      for p in ("fwd", "bwd_dx", "bwd_dw_adam")}
    monitor.reset()
    unfolded()
    want, _ = state_after(build(optimizer, amp=True))
    assert gm.gmm_dispatch_counts() == {
        f"{p} {shape}": 6 for p in ("fwd", "bwd_dx", "bwd_dw")}
    assert sorted(got) == sorted(want)
    for name in want:
        a, b = got[name], want[name]
        if "_pow_" in name or name.startswith("learning_rate"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        elif "_moment" in name:
            # linear in the gradient: 2**-8 of it, and what three steps'
            # slightly different weights do to the next gradient
            np.testing.assert_allclose(a, b, rtol=5e-2,
                                       atol=2e-2 * np.abs(b).max(),
                                       err_msg=name)
        else:
            # a step moves a weight by about the learning rate, either
            # way where its gradient is within the rounding of zero
            np.testing.assert_allclose(a, b, atol=2 * LR, err_msg=name)
            assert np.abs(a - b).mean() < 0.02 * LR, name


def clip_by_global_norm(main, loss):
    clip_mod.set_gradient_clip(clip_mod.GradientClipByGlobalNorm(1.0))


def second_reader(main, loss):
    """Something else reads an expert matrix's gradient: a norm of it,
    appended before the optimizer's ops as an instrument would be."""
    from paddle_tpu.backward import append_backward

    grads = dict((p.name, g) for p, g in append_backward(loss))
    layers.reduce_sum(layers.square(grads["m_up.w"]))


REFUSALS = {
    "clip_by_global_norm": (adam(), clip_by_global_norm, set(EXPERTS)),
    "l2_regulariser": (
        adam(regularization=regularizer.L2Decay(1e-3)), None, set(EXPERTS)),
    "sgd": (lambda: fluid.optimizer.SGD(LR), None, set(EXPERTS)),
    "momentum": (lambda: fluid.optimizer.Momentum(LR, 0.9), None,
                 set(EXPERTS)),
    "lamb": (lambda: fluid.optimizer.Lamb(LR), None, set(EXPERTS)),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_a_program_the_pattern_refuses_is_the_parents_op_for_op(
        case, unfolded):
    optimizer, before, _ = REFUSALS[case]
    got = ops_of(build(optimizer, before_minimize=before)[0])
    assert not any("adam_slots" in attrs for _, _, _, attrs in got)
    clip_mod.set_gradient_clip.__globals__["_clip_attr"] = None
    unfolded()
    assert got == ops_of(build(optimizer, before_minimize=before)[0])


def test_a_gradient_with_a_second_reader_keeps_its_update_op(unfolded):
    """Only that matrix: the five others of the two layers fold."""
    def minimize_read(main, loss):
        pass

    main, startup, loss = fluid.Program(), fluid.Program(), None
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        h, *_ = layers.topk_moe(x, E, K, F, name="m")
        loss = layers.reduce_sum(h)
        opt = fluid.optimizer.Adam(LR)
        pgs = opt.backward(loss)
        g_up = dict((p.name, g) for p, g in pgs)["m_up.w"]
        layers.reduce_sum(layers.square(g_up))
        opt.apply_gradients(pgs)
    assert updates(main) == {
        "m_router.w": "adam", "m_up.w": "adam",
        "m_gate.w": "moe_experts_grad", "m_down.w": "moe_experts_grad"}
    op, = [op for op in main.global_block().ops
           if op.type == "moe_experts_grad"]
    assert op.attrs["adam_slots"] == ["WGate", "WDown"]
    assert op.output("GRAD::WUp") == [g_up.name]


def test_a_learning_rate_of_its_own_keeps_the_update_op():
    from paddle_tpu.param_attr import ParamAttr

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        h, *_ = layers.topk_moe(
            x, E, K, F, name="m",
            param_attr=ParamAttr(learning_rate=0.5))
        fluid.optimizer.Adam(LR).minimize(layers.reduce_sum(h))
    assert set(updates(main).values()) == {"adam"}


def test_a_fetched_gradient_is_written_after_all_and_the_state_is_the_same(
        unfolded):
    """Nothing in the graph reads the gradient, so the update folds; a
    run that fetches it gets it (the lowering has the op write it and
    take the step behind it), and the same state."""
    prog = build(adam())
    op = next(op for op in prog[0].global_block().ops
              if op.type == "moe_experts_grad")
    name = dict(zip(op.attrs["adam_slots"], op.attrs["adam_grads"]))["WUp"]
    got, (g,) = state_after(prog, fetch=[name])
    unfolded()
    want, (g_want,) = state_after(build(adam()), fetch=[name])
    np.testing.assert_array_equal(g, g_want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def test_under_a_mesh_the_step_follows_the_summed_gradient(unfolded,
                                                           monkeypatch):
    """Data parallel over the virtual devices, kernels on: ``gmm_tile``
    gives a program under a mesh no tile, so the folded op makes the
    gradient with ``ragged_dot`` (summed over the chips by the
    partitioner) and takes the op's own step behind it: the state is the
    unfolded program's under the same mesh, and no ``bwd_dw_adam`` call
    is lowered."""
    monkeypatch.setattr(gm, "_INTERPRET", True)
    flags.set_flags({"telemetry": True})

    def run(prog):
        main, startup, loss = prog
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        dp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        for _ in range(2):
            exe.run(dp, feed=feed(), scope=scope, fetch_list=[loss])
        return {v.name: np.asarray(scope.find_var(v.name))
                for v in main.global_block().vars.values() if v.persistable}

    got = run(build(adam(), amp=True))
    counts = gm.gmm_dispatch_counts()
    assert counts and not any("[" in name or "bwd_dw_adam" in name
                              for name in counts), counts
    unfolded()
    want = run(build(adam(), amp=True))
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=1e-6,
                                   atol=1e-7, err_msg=key)


def test_the_state_round_trips_through_save_and_load(tmp_path, unfolded):
    """The folded program's persistables are the unfolded program's, name
    for name: what one saves the other loads, and a step later both hold
    the same state."""
    main, startup, loss = build(adam())
    scope, exe = fluid.Scope(), fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed(), fetch_list=[loss])
        fluid.io.save_persistables(exe, str(tmp_path), main)
        exe.run(main, feed=feed(), fetch_list=[loss])
        got = {v.name: np.asarray(scope.find_var(v.name))
               for v in main.global_block().vars.values() if v.persistable}
    unfolded()
    main2, startup2, loss2 = build(adam())
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup2)
        fluid.io.load_persistables(exe, str(tmp_path), main2)
        exe.run(main2, feed=feed(), fetch_list=[loss2])
        for name, value in got.items():
            np.testing.assert_array_equal(
                np.asarray(scope2.find_var(name)), value, err_msg=name)


def test_the_slot_descriptor_names_the_folded_matrices_state():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[N, D], dtype="float32",
                        append_batch_size=False)
        h, *_ = layers.topk_moe(x, E, K, F, name="m")
        opt = fluid.optimizer.Adam(LR)
        opt.minimize(layers.reduce_sum(h))
    slots = opt.slot_descriptor()
    op, = [op for op in main.global_block().ops
           if op.type == "moe_experts_grad"]
    for name, kind in (("Moment1", "moment1"), ("Moment2", "moment2"),
                       ("Beta1Pow", "beta1_pow"), ("Beta2Pow", "beta2_pow")):
        for param, var in zip(op.input("Param"), op.input(name)):
            assert slots[var] == {"param": param, "slot": kind}
