"""Executor.run_steps: whole-window compiled loop parity with step-wise
run (reference analog: Executor::RunFromDataset hot loop,
framework/executor.cc:120-147)."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers


def _build(seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8, 16], append_batch_size=False,
                        stop_gradient=True)
        label = layers.data("label", shape=[8, 1], dtype="int64",
                            append_batch_size=False)
        h = layers.fc(x, 32, act="relu")
        h = layers.dropout(h, 0.3)      # exercises the per-step RNG fold
        logits = layers.fc(h, 4)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feeds(k=3):
    r = np.random.RandomState(0)
    out = []
    for i in range(k):
        x = r.randn(8, 16).astype(np.float32)
        out.append({"x": x,
                    "label": (np.argmax(x[:, :4], 1)[:, None]).astype(
                        np.int64)})
    return out


def test_run_steps_matches_stepwise():
    main, startup, loss = _build()
    feeds = _feeds(3)
    n = 7  # not a multiple of len(feeds): exercises the rotation

    scope_a, scope_b = fluid.executor.Scope(), fluid.executor.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.executor.scope_guard(scope_a):
        exe.run(startup)
        snapshot = {name: np.asarray(scope_a.find_var(name))
                    for name in scope_a.var_names()}
    for name, v in snapshot.items():
        scope_b.set(name, v)

    exe_a = fluid.Executor(fluid.CPUPlace())
    step_losses = []
    for i in range(n):
        out = exe_a.run(main, feed=feeds[i % len(feeds)], fetch_list=[loss],
                        scope=scope_a)
        step_losses.append(float(np.asarray(out[0])))

    exe_b = fluid.Executor(fluid.CPUPlace())
    out_multi = exe_b.run_steps(main, feed_list=feeds, steps=n,
                                fetch_list=[loss], scope=scope_b)
    # last-step fetch matches the step-wise stream bit-for-bit
    assert float(np.asarray(out_multi[0])) == step_losses[-1]
    # parameters after n steps match
    for name in scope_a.var_names():
        a = np.asarray(scope_a.find_var(name))
        b = np.asarray(scope_b.find_var(name))
        np.testing.assert_array_equal(a, b, err_msg=name)
    # training actually progressed
    assert step_losses[-1] < step_losses[0]


def test_run_steps_sees_in_place_feed_mutation():
    """A feed buffer refilled in place between run_steps calls (the
    preallocated-loader pattern) must be re-staged, not served from the
    identity cache. Only OWNING frozen arrays may be cached — a frozen
    view is still mutable through its writeable base."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 4], append_batch_size=False,
                        stop_gradient=True)
        s = layers.reduce_sum(x)
    exe = fluid.Executor(fluid.CPUPlace())
    buf = np.full((4, 4), 1.0, np.float32)
    out = exe.run_steps(main, feed_list=[{"x": buf}], steps=1,
                        fetch_list=[s])
    assert float(np.asarray(out[0])) == 16.0
    buf[...] = 2.0  # in-place refill, same identity
    out = exe.run_steps(main, feed_list=[{"x": buf}], steps=1,
                        fetch_list=[s])
    assert float(np.asarray(out[0])) == 32.0
    # a frozen VIEW must NOT be cached: its base is still writeable
    view = buf.view()
    view.flags.writeable = False
    exe.run_steps(main, feed_list=[{"x": view}], steps=1, fetch_list=[s])
    assert len(exe._staged) == 0
    buf[...] = 3.0  # mutation through the base reaches the frozen view
    out = exe.run_steps(main, feed_list=[{"x": view}], steps=1,
                        fetch_list=[s])
    assert float(np.asarray(out[0])) == 48.0
    # an OWNING frozen copy DOES hit the staging cache
    frozen = buf.copy()
    frozen.flags.writeable = False
    exe.run_steps(main, feed_list=[{"x": frozen}], steps=1, fetch_list=[s])
    cached = next(iter(exe._staged.values()))["stacked"]["x"]
    # an interleaved mutable-feed call must not wipe the frozen entry
    exe.run_steps(main, feed_list=[{"x": buf}], steps=1, fetch_list=[s])
    exe.run_steps(main, feed_list=[{"x": frozen}], steps=1, fetch_list=[s])
    assert next(iter(exe._staged.values()))["stacked"]["x"] is cached


def test_run_steps_continues_the_step_counter():
    main, startup, loss = _build(seed=11)
    feeds = _feeds(2)
    scope = fluid.executor.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    # interleave: 2 single steps, a 3-step window, 1 single step
    l0 = exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
    exe.run_steps(main, feed_list=feeds, steps=3, fetch_list=[loss],
                  scope=scope)
    out = exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
    assert np.isfinite(np.asarray(out[0])).all()
    assert float(np.asarray(out[0])) < float(np.asarray(l0[0]))


def test_run_steps_refuses_a_compiled_program():
    """A mesh program has one hot loop, Executor.run (where its state is
    committed to the mesh); a window over one is refused, not run on one
    device under its name (ROADMAP Queue 1 item 7)."""
    import pytest

    main, startup, loss = _build()
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(TypeError, match="does not support CompiledProgram"):
        exe.run_steps(compiled, feed_list=_feeds(1), steps=2,
                      fetch_list=[loss])
