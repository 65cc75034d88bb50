"""Set-up timed from inside (PR 36): the ``executor.first_call`` span with
the cause of its miss, jax's compile events charged to the program whose
first call they ran in (``pt_compile_stage_seconds``,
``pt_compile_cache_total``, ``pt_jax_traces_total``), and what the op
rules cost to trace (``pt_op_trace_seconds``). The CPU backend fires the
same ``jax.monitoring`` events the TPU's does."""

import os
import time
import tracemalloc

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.core import registry

import jax_cache_events

STAGES = ("trace", "lower", "backend")
# jax stamps a stage with time.time(), the span runs on perf_counter
CLOCKS_APART_S = 5e-3


@pytest.fixture(autouse=True)
def _telemetry():
    keep = {k: flags.get_flag(k) for k in (
        "telemetry", "step_phases", "executor_cache_capacity")}
    flags.set_flags({"telemetry": True, "step_phases": False})
    yield
    flags.set_flags(keep)


def _build(width=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        loss = layers.mean(layers.fc(x, width))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(rows=2):
    return {"x": np.ones((rows, 8), np.float32)}


def _started(scope=None):
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = scope or fluid.Scope()
    exe.run(startup, scope=scope)
    return exe, main, loss, scope


def pid(program):
    return f"program{program._uid}"


def stage_s(program, stage):
    return monitor.histogram("pt_compile_stage_seconds").sum(
        labels={"program": program, "stage": stage})


def stage_n(program, stage):
    return monitor.histogram("pt_compile_stage_seconds").count(
        labels={"program": program, "stage": stage})


def first_calls(kind="step"):
    """{cause: first calls of ``kind`` so far}"""
    rows = monitor.snapshot()["pt_executor_first_calls_total"]["values"]
    return {r["labels"]["cause"]: int(r["value"]) for r in rows
            if r["labels"]["kind"] == kind}


def first_call_s():
    return monitor.histogram("pt_span_seconds").sum(
        labels={"span": "executor.first_call"})


def registry_state():
    snap = monitor.snapshot()
    return {n: snap[n]["values"] for n in (
        "pt_compile_stage_seconds", "pt_compile_cache_total",
        "pt_jax_traces_total", "pt_op_trace_seconds",
        "pt_executor_first_calls_total")}


# --- a first call, and the calls after it -----------------------------------


def test_a_first_call_records_the_three_stages_under_its_program():
    exe, main, loss, scope = _started()
    before = first_call_s()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    spent = first_call_s() - before
    got = {s: stage_s(pid(main), s) for s in STAGES}
    assert all(stage_n(pid(main), s) == 1 for s in STAGES), got
    assert all(v > 0 for v in got.values()), got
    # the stages are parts of the span, none counted twice
    assert sum(got.values()) <= spent + CLOCKS_APART_S, (got, spent)
    assert first_calls() == {"new_program": 2}     # startup's and main's
    # the traces of the step are counted by the function traced
    traced = {r["labels"]["fun_name"]: r["value"] for r in
              monitor.snapshot()["pt_jax_traces_total"]["values"]}
    assert traced.get("step_fn", 0) >= 1, traced


def test_a_second_call_of_the_same_signature_records_nothing():
    exe, main, loss, scope = _started()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    state, spent = registry_state(), first_call_s()
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    assert registry_state() == state
    assert first_call_s() == spent


def _another_feed_shape(exe, main, loss, scope):
    exe.run(main, feed=_feed(rows=3), fetch_list=[loss], scope=scope)


def _a_version_bump(exe, main, loss, scope):
    with fluid.program_guard(main):
        layers.scale(loss, scale=2.0)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)


def _another_fetch_list(exe, main, loss, scope):
    exe.run(main, feed=_feed(), fetch_list=[], scope=scope)


def _an_evicted_entry(exe, main, loss, scope):
    flags.set_flags({"executor_cache_capacity": 1})
    exe.run(main, feed=_feed(rows=3), fetch_list=[loss], scope=scope)
    assert first_calls()["feed_signature"] == 1   # ... which evicted:
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)


def _amp_flipped(exe, main, loss, scope):
    main._amp = True
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)


def _another_scope(exe, main, loss, scope):
    other = fluid.Scope()
    for n in scope.var_names():
        other.set(n, jax.numpy.array(scope.find_var(n)))
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=other)


def _no_program_cache(exe, main, loss, scope):
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope,
            use_program_cache=False)


@pytest.mark.parametrize("change,cause", [
    (_another_feed_shape, "feed_signature"),
    (_a_version_bump, "program_version"),
    (_another_fetch_list, "fetch_list"),
    (_an_evicted_entry, "evicted"),
    (_amp_flipped, "amp"),
    (_another_scope, "scope"),
    (_no_program_cache, "evicted"),
], ids=lambda v: v if isinstance(v, str) else v.__name__.lstrip("_"))
def test_a_miss_says_why_the_executor_had_no_entry(change, cause):
    exe, main, loss, scope = _started()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    before = first_calls()
    change(exe, main, loss, scope)
    after = first_calls()
    assert after.get(cause, 0) == before.get(cause, 0) + 1, (before, after)
    assert after["new_program"] == before["new_program"]


def test_a_data_parallel_program_misses_by_its_strategy():
    exe, main, loss, scope = _started()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    dp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, devices=jax.devices()[:2])
    exe.run(dp, feed=_feed(), fetch_list=[loss], scope=scope)
    assert first_calls()["strategy"] == 1


def test_a_window_is_a_kind_of_its_own_and_its_length_a_signature():
    exe, main, loss, scope = _started()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    feeds = [_feed()]
    exe.run_steps(main, feeds, steps=2, fetch_list=[loss], scope=scope)
    assert first_calls("window") == {"new_program": 1}
    traces = stage_n(pid(main), "trace")
    exe.run_steps(main, feeds, steps=2, fetch_list=[loss], scope=scope)
    assert first_calls("window") == {"new_program": 1}
    assert stage_n(pid(main), "trace") == traces
    exe.run_steps(main, feeds, steps=3, fetch_list=[loss], scope=scope)
    assert first_calls("window") == {"new_program": 1, "feed_signature": 1}
    assert stage_n(pid(main), "trace") == traces + 1


def test_a_jit_outside_any_executor_call_lands_under_outside():
    exe, main, loss, scope = _started()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    mine = {s: stage_n(pid(main), s) for s in STAGES}
    outside = {s: stage_n(monitor.OUTSIDE, s) for s in STAGES}
    names = len(monitor.snapshot()["pt_jax_traces_total"]["values"])

    def a_function_of_the_test(a):
        return jax.numpy.tanh(a) * 3.0

    jax.block_until_ready(jax.jit(a_function_of_the_test)(np.ones(5)))
    assert {s: stage_n(pid(main), s) for s in STAGES} == mine
    for s in STAGES:
        assert stage_n(monitor.OUTSIDE, s) == outside[s] + 1
    # traces outside a first call share ONE row: the label cap is the
    # program's own
    rows = monitor.snapshot()["pt_jax_traces_total"]["values"]
    assert len(rows) == names
    assert "a_function_of_the_test" not in {
        r["labels"]["fun_name"] for r in rows}


# --- the op rules ------------------------------------------------------------


def test_the_op_histograms_count_is_the_ops_of_the_block():
    exe, main, loss, scope = _started()
    before = {r["labels"]["op"]: r["count"] for r in
              monitor.snapshot()["pt_op_trace_seconds"]["values"]}
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    rows = monitor.snapshot()["pt_op_trace_seconds"]["values"]
    got = {r["labels"]["op"]: r["count"] - before.get(r["labels"]["op"], 0)
           for r in rows}
    want = {}
    for op in main.global_block().ops:
        want[op.type] = want.get(op.type, 0) + 1
    assert {k: v for k, v in got.items() if v} == want
    # and the rules' seconds are seconds of the step's trace
    total = sum(r["sum"] for r in rows) - sum(
        r["sum"] for r in rows if r["labels"]["op"] not in want)
    assert 0 < total <= stage_s(pid(main), "trace") + CLOCKS_APART_S


def test_shape_inference_at_build_time_is_not_a_lowering():
    # a While's sub-block runs through exec_ops under jax.eval_shape as
    # the program is built: no op second is counted there
    _while_program()
    assert monitor.snapshot()["pt_op_trace_seconds"]["values"] == []


def _while_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = layers.fill_constant(shape=[1], dtype="int32", value=0)
        limit = layers.fill_constant(shape=[1], dtype="int32", value=10)
        total = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        cond = layers.less_than(i, limit)
        w = layers.While(cond)
        with w.block():
            layers.assign(total + 2.0, output=total)
            layers.increment(i, value=1.0, in_place=True)
            layers.less_than(i, limit, cond=cond)
    return main, total


def test_a_sub_block_counts_no_second_twice(monkeypatch):
    nap = 0.05
    inc = registry.get_op_def("increment")
    real = inc.compute

    def slow(ins, attrs, **kw):
        time.sleep(nap)
        return real(ins, attrs, **kw)

    monkeypatch.setattr(inc, "compute", slow)
    main, total = _while_program()
    exe = fluid.Executor(fluid.CPUPlace())
    before = first_call_s()
    (out,) = exe.run(main, feed={}, fetch_list=[total])
    assert float(out[0]) == 20.0
    spent = first_call_s() - before
    ops = {r["labels"]["op"]: r for r in
           monitor.snapshot()["pt_op_trace_seconds"]["values"]}
    # the sub-block's ops are observed (jax may trace a body twice) ...
    body_traces = ops["increment"]["count"]
    assert body_traces >= 1
    assert ops["increment"]["sum"] >= nap * body_traces
    # ... and the while op is charged what they are not
    assert ops["while"]["count"] == 1
    assert ops["while"]["sum"] < nap
    # so the rules' sum is part of the trace, which is part of the span
    rules = sum(r["sum"] for r in ops.values())
    trace = stage_s(pid(main), "trace")
    assert nap * body_traces <= rules <= trace + CLOCKS_APART_S
    assert stage_n(pid(main), "trace") == 1    # nested traces not again
    assert sum(stage_s(pid(main), s) for s in STAGES) \
        <= spent + CLOCKS_APART_S


# --- jax's persistent cache ---------------------------------------------------


def cache_rows(program):
    rows = monitor.snapshot()["pt_compile_cache_total"]["values"]
    return {r["labels"]["outcome"]: int(r["value"]) for r in rows
            if r["labels"]["program"] == program}


def test_a_cold_cache_is_written_and_a_warm_one_hit(tmp_path):
    with jax_cache_events.placed_in_process(tmp_path / "jax") as events:
        exe, main, loss, scope = _started()
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert cache_rows(pid(main)) == {"written": 1}
        cold = stage_s(pid(main), "backend")
        # the process forgets what it compiled, the directory does not
        exe.close()
        jax.clear_caches()
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert cache_rows(pid(main)) == {"written": 1, "hit": 1}
        assert first_calls()["evicted"] == 1
        # a read is a backend stage too, as jax times it
        assert stage_n(pid(main), "backend") == 2
        assert stage_s(pid(main), "backend") > cold
        assert events.snapshot()["hits"] >= 1


# --- off ------------------------------------------------------------------------


def test_with_telemetry_off_no_instrument_changes():
    flags.set_flags({"telemetry": False})
    state, spent = registry_state(), first_call_s()
    exe, main, loss, scope = _started()
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    exe.run(main, feed=_feed(rows=3), fetch_list=[loss], scope=scope)
    jax.block_until_ready(jax.jit(lambda a: a + 1)(np.ones(3)))
    assert registry_state() == state and first_call_s() == spent
    # the cause is still worked out, for when telemetry comes on
    assert list(exe._built[("step", main._uid)])[-1][3] == (
        ("x", (3, 8), "float32"),)
    # the miss path opens nothing
    from paddle_tpu import executor

    assert exe._first_call("step", main, 0, (0,) * 6) is executor._NOT_FIRST


def test_a_steady_step_allocates_nothing_for_first_calls():
    flags.set_flags({"telemetry": False})
    exe, main, loss, scope = _started()
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    built = {k: dict(v) for k, v in exe._built.items()}
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for _ in range(30):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grew = sum(s.size_diff for s in snap.compare_to(base, "filename")
               if s.traceback[0].filename.endswith("monitor.py")
               and s.size_diff > 0)
    assert grew < 30 * 16, grew
    assert exe._built == built     # a hit reads and writes none of it


def test_the_identities_remembered_for_a_program_are_bounded():
    from paddle_tpu import executor

    exe = fluid.Executor(fluid.CPUPlace())
    causes = [exe._miss_cause("step", 7, (1, False, 0, (("x", (n,)),), (), 1))
              for n in range(executor._BUILT_CAPACITY + 8)]
    assert causes[0] == "new_program"
    assert set(causes[1:]) == {"feed_signature"}
    assert len(exe._built[("step", 7)]) == executor._BUILT_CAPACITY
    # the oldest were dropped: one of them reads as a change again
    assert exe._miss_cause(
        "step", 7, (1, False, 0, (("x", (0,)),), (), 1)) == "feed_signature"
    assert exe._miss_cause(
        "step", 7, (1, False, 0, (("x", (20,)),), (), 1)) == "evicted"


# --- on the profiler's clock ------------------------------------------------


def test_a_recompile_in_a_profiled_stretch_is_a_span_with_its_cause(
        tmp_path):
    from jax.profiler import ProfileData

    exe, main, loss, scope = _started()
    for _ in range(2):
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    step = exe._step + 1
    jax.profiler.start_trace(str(tmp_path))
    try:
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        exe.run(main, feed=_feed(rows=5), fetch_list=[loss], scope=scope)
        exe.run(main, feed=_feed(rows=5), fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("executor.")]
    (first,) = [e for e in events if e[0] == "executor.first_call"]
    assert first[3] == {"step": step, "program": pid(main), "kind": "step",
                        "cause": "feed_signature"}
    # inside the run_step span of the call that missed
    (holder,) = [e for e in events if e[0] == "executor.run_step"
                 and e[1] <= first[1]
                 and first[1] + first[2] <= e[1] + e[2]]
    assert len([e for e in events if e[0] == "executor.run_step"]) == 3
    assert holder[2] >= first[2] > 0


# --- chip_smoke.py's printout and its recompile phase, off the chip ---------


def test_the_smoke_reads_stages_and_cache_outcomes_by_program(tmp_path):
    import chip_smoke

    with jax_cache_events.placed_in_process(tmp_path / "jax"):
        exe, main, loss, scope = _started()
        exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        rows = chip_smoke.compile_stages()
    mine = rows[pid(main)]
    assert set(mine) == {"trace", "lower", "backend", "written"}
    assert mine["written"] == 1 and mine["backend"] > 0


def test_the_smokes_recompile_phase_finds_the_span_and_needs_a_device(
        capsys):
    import chip_smoke

    # the CPU's trace has the host line and no device plane: the phase
    # gets past its check of the span and stops at the idle gap
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="holds no device op") as failure:
        chip_smoke.recompile_phase(width=16, depth=2, rows=(8, 4))
    assert "'cause': 'feed_signature'" in str(failure.value)
