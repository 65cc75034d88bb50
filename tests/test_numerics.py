"""Device-side numerics plane (paddle_tpu/numerics.py + the
instrument_numerics pass): in-graph tensor stats fetched as one auxiliary
bundle, NaN/Inf provenance naming the first bad op, every-N sampling,
AMP/clip aux decode, the /numerics route, the run_steps first-bad-step
tracker, and the zero-allocation disabled hot path."""

import json
import tracemalloc
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import (
    debugger,
    flags,
    layers,
    monitor,
    numerics,
    passes,
)


@pytest.fixture(autouse=True)
def _clean_numerics():
    flags.set_flags({"telemetry": False, "numerics": False,
                     "numerics_every_n_steps": 1, "numerics_vars": "",
                     "check_nan_inf": False, "step_log_path": ""})
    yield
    monitor.stop_server()
    flags.set_flags({"telemetry": False, "numerics": False,
                     "numerics_every_n_steps": 1, "numerics_vars": "",
                     "check_nan_inf": False, "step_log_path": ""})


def _enable():
    flags.set_flags({"telemetry": True, "numerics": True})


def _small_program():
    """3-op program: scale -> elementwise_sub -> mean."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[4], dtype="float32")
        a = layers.scale(x, scale=2.0)
        t = layers.elementwise_sub(a, y)
        out = layers.mean(t)
    return main, startup, out, t


# --------------------------------------------------------------------------
# the pass + plan
# --------------------------------------------------------------------------

def test_instrument_pass_appends_one_stats_op_with_decode_plan():
    main, _startup, _out, _t = _small_program()
    n_ops = len(main.global_block().ops)
    version = main.version
    plan = passes.apply_pass("instrument_numerics", main)._numerics_plan
    block = main.global_block()
    assert len(block.ops) == n_ops + 1
    assert block.ops[-1].type == "numerics_stats"
    assert main.version > version  # compiled-step cache invalidates
    # every float op output is a stats entry, mapped to its producer
    assert len(plan.entries) == 3
    by_var = {v: (idx, op_type) for v, idx, op_type, _k in plan.entries}
    for var, (idx, op_type) in by_var.items():
        assert block.ops[idx].type == op_type
        assert var in block.ops[idx].output_arg_names
    assert plan.bundle_size == 3 * len(numerics.STAT_FIELDS)
    # idempotent: re-applying returns the same plan, appends nothing
    assert passes.apply_pass(
        "instrument_numerics", main)._numerics_plan is plan
    assert len(block.ops) == n_ops + 1


def test_numerics_vars_flag_filters_instrumented_vars():
    flags.set_flags({"numerics_vars": "mean_*"})
    main, _startup, _out, _t = _small_program()
    plan = numerics.instrument(main)
    assert [v for v, _i, _t2, _k in plan.entries] == [
        main.global_block().ops[2].output_arg_names[0]]


def test_stats_values_match_ground_truth():
    _enable()
    main, startup, out, t = _small_program()
    numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    x = np.array([[1.0, 2.0, -4.0, 0.5]], np.float32)
    y = np.zeros((1, 4), np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": x, "y": y}, fetch_list=[out])
    stats = numerics.latest_stats()[main._uid]["stats"]
    cell = stats[t.name]  # t = 2*x - 0 = [2, 4, -8, 1]
    assert cell["nonfinite"] == 0
    assert cell["maxabs"] == pytest.approx(8.0)
    assert cell["rms"] == pytest.approx(
        float(np.sqrt(np.mean(np.square([2.0, 4.0, -8.0, 1.0])))), rel=1e-5)
    assert monitor.gauge("pt_tensor_maxabs").value(
        labels={"var": t.name}) == pytest.approx(8.0)
    assert monitor.gauge("pt_tensor_rms").value(
        labels={"var": t.name}) == pytest.approx(cell["rms"])
    # summary landed in the step record too
    rec = monitor.recent_steps()[-1]
    assert rec["numerics"]["vars"] == 3
    assert rec["numerics"]["first_bad"] is None
    monitor.validate_step_record(rec)


def test_rms_and_maxabs_computed_over_finite_values_only():
    """Stats must describe the FINITE values exactly when the tensor is
    partly non-finite — the moment the gauges actually get read."""
    _enable()
    main, startup, out, t = _small_program()
    numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    x = np.array([[1.0, 2.0, -4.0, 0.5]], np.float32)
    y = np.array([[np.inf, 0.0, 0.0, 0.0]], np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": x, "y": y}, fetch_list=[out])
    cell = numerics.latest_stats()[main._uid]["stats"][t.name]
    # t = 2x - y = [-inf, 4, -8, 1]: one bad element, finite rest
    assert cell["nonfinite"] == 1
    assert cell["maxabs"] == pytest.approx(8.0)
    assert cell["rms"] == pytest.approx(
        float(np.sqrt((16.0 + 64.0 + 1.0) / 3.0)), rel=1e-5)


def test_optional_histogram_buckets_count_finite_nonzero_elements():
    _enable()
    main, startup, out, t = _small_program()
    numerics.instrument(main, histogram_bins=8)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    x = np.array([[1.0, 2.0, -4.0, 0.0]], np.float32)  # one zero
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": x, "y": np.zeros((1, 4), np.float32)},
                fetch_list=[out])
    cell = numerics.latest_stats()[main._uid]["stats"][t.name]
    assert len(cell["hist"]) == 8
    assert sum(cell["hist"]) == 3  # zero excluded from magnitude buckets


# --------------------------------------------------------------------------
# NaN provenance (acceptance: injected inf - inf mid-graph)
# --------------------------------------------------------------------------

def test_nan_provenance_names_the_inf_minus_inf_op_via_run():
    _enable()
    main, startup, out, t = _small_program()
    numerics.instrument(main)
    sub_idx = next(i for i, op in enumerate(main.global_block().ops)
                   if op.type == "elementwise_sub")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    inf = np.full((1, 4), np.inf, np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        # scale(inf) = inf feeds the sub, but the FEEDS are not op
        # outputs: the first instrumented op producing non-finite values
        # is scale; use finite x and inf y so the sub alone goes bad
        exe.run(main, feed={"x": np.ones((1, 4), np.float32), "y": inf},
                fetch_list=[out])
    recs = numerics.provenance_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["op_idx"] == sub_idx
    assert rec["op_type"] == "elementwise_sub"
    assert rec["var"] == t.name
    assert rec["nonfinite"] == 4
    assert rec["program_uid"] == main._uid
    assert numerics.provenance_for(main._uid)["op_idx"] == sub_idx
    # the step record names the same op
    srec = monitor.recent_steps()[-1]
    assert srec["numerics"]["first_bad"] == {
        "op": sub_idx, "op_type": "elementwise_sub", "var": t.name}
    assert srec["numerics"]["nonfinite_vars"] >= 1
    # provenance fires once per episode: a second bad step adds nothing
    with fluid.scope_guard(scope):
        exe.run(main, feed={"x": np.ones((1, 4), np.float32), "y": inf},
                fetch_list=[out])
    assert len(numerics.provenance_records()) == 1
    # ...and a clean step re-arms it
    with fluid.scope_guard(scope):
        exe.run(main, feed={"x": np.ones((1, 4), np.float32),
                            "y": np.zeros((1, 4), np.float32)},
                fetch_list=[out])
        exe.run(main, feed={"x": np.ones((1, 4), np.float32), "y": inf},
                fetch_list=[out])
    assert len(numerics.provenance_records()) == 2


def test_nan_provenance_via_run_steps_window_with_nan_step():
    _enable()
    flags.set_flags({"check_nan_inf": True})
    main, startup, out, t = _small_program()
    numerics.instrument(main)
    sub_idx = next(i for i, op in enumerate(main.global_block().ops)
                   if op.type == "elementwise_sub")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ones = np.ones((1, 4), np.float32)
    zeros = np.zeros((1, 4), np.float32)
    inf = np.full((1, 4), np.inf, np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)  # step 0
        with pytest.raises(FloatingPointError, match="step 3"):
            exe.run_steps(
                main,
                feed_list=[{"x": ones, "y": zeros},
                           {"x": ones, "y": zeros},
                           {"x": ones, "y": inf},
                           {"x": ones, "y": inf}],
                steps=4, fetch_list=[out])
    # the in-graph tracker named the first bad step of the window
    rec = monitor.recent_steps()[-1]
    assert rec["kind"] == "window"
    assert rec["nan_check"] == "fail"
    assert rec["nan_step"] == 3  # window starts at step 1 (startup = 0)
    monitor.validate_step_record(rec)
    assert monitor.counter(
        "pt_executor_nan_check_failures_total").value() == 1
    # provenance decoded from the window's bundle names the op and step
    prec = numerics.provenance_for(main._uid)
    assert prec is not None
    assert prec["op_idx"] == sub_idx
    assert prec["op_type"] == "elementwise_sub"
    assert prec["var"] == t.name
    assert prec["kind"] == "window"
    assert prec["nan_step"] == 3


def test_run_steps_clean_window_reports_ok_without_nan_step():
    _enable()
    flags.set_flags({"check_nan_inf": True})
    main, startup, out, _t = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ones = np.ones((1, 4), np.float32)
    zeros = np.zeros((1, 4), np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_steps(main, feed_list=[{"x": ones, "y": zeros}],
                      steps=3, fetch_list=[out])
    rec = monitor.recent_steps()[-1]
    assert rec["nan_check"] == "ok"
    assert "nan_step" not in rec
    assert monitor.counter(
        "pt_executor_nan_check_failures_total").value() == 0


def test_pprint_program_annotates_first_nonfinite_op():
    _enable()
    main, startup, out, t = _small_program()
    numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main,
                feed={"x": np.ones((1, 4), np.float32),
                      "y": np.full((1, 4), np.inf, np.float32)},
                fetch_list=[out])
    text = debugger.pprint_program(main)
    assert "numerics provenance" in text
    assert "!! first non-finite" in text
    assert t.name in text
    # opting out removes the annotation
    clean = debugger.pprint_program(main, with_numerics=False)
    assert "first non-finite" not in clean


# --------------------------------------------------------------------------
# sampling + the single-transfer contract
# --------------------------------------------------------------------------

def test_every_n_sampling_bounds_decodes():
    _enable()
    flags.set_flags({"numerics_every_n_steps": 2})
    main, startup, out, _t = _small_program()
    numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((1, 4), np.float32),
            "y": np.zeros((1, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)  # step 0: startup has no plan -> no decode
        for _ in range(4):  # steps 1..4: steps 2 and 4 sample
            exe.run(main, feed=feed, fetch_list=[out])
    assert monitor.counter("pt_numerics_decodes_total").value() == 2
    recs = monitor.recent_steps()
    assert ["numerics" in r for r in recs] == [
        False, False, True, False, True]


def test_sampled_step_performs_exactly_one_auxiliary_transfer(monkeypatch):
    """Acceptance: the instrumented step's stats ride ONE fetched array —
    numerics._to_host (the only device->host sync in the decode path)
    runs exactly once per sampled step and never on unsampled ones."""
    _enable()
    flags.set_flags({"numerics_every_n_steps": 2})
    calls = []
    real = numerics._to_host
    monkeypatch.setattr(numerics, "_to_host",
                        lambda x: (calls.append(np.shape(x)), real(x))[1])
    main, startup, out, _t = _small_program()
    plan = numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((1, 4), np.float32),
            "y": np.zeros((1, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)          # step 0, no plan
        exe.run(main, feed=feed, fetch_list=[out])   # step 1: unsampled
        assert calls == []
        exe.run(main, feed=feed, fetch_list=[out])   # step 2: sampled
    # one transfer, of the one concatenated bundle
    assert calls == [(plan.bundle_size,)]


def test_user_fetches_unchanged_by_instrumentation():
    _enable()
    main, startup, out, t = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((1, 4), np.float32),
            "y": np.zeros((1, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        plain = exe.run(main, feed=feed, fetch_list=[out, t])
    numerics.instrument(main)
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        inst = exe.run(main, feed=feed, fetch_list=[out, t])
    assert len(inst) == 2  # the bundle never leaks into user fetches
    for a, b in zip(plain, inst):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# aux plumbing (AMP / clip values ride the same bundle)
# --------------------------------------------------------------------------

def test_aux_only_plan_builds_lazily_for_amp_programs():
    """A program whose graph code registered aux vars (amp.decorate,
    clip) gets a lazy aux-only bundle on first run — no explicit pass
    needed for the AMP gauges."""
    from paddle_tpu import amp

    _enable()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, 2))
        opt = amp.decorate(fluid.optimizer.SGD(0.1), init_loss_scaling=8.0,
                           use_dynamic_loss_scaling=True)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
    plan = main._numerics_plan
    assert plan.entries == ()  # aux-only: no stats vars were selected
    kinds = [k for k, _v in plan.aux]
    assert "amp_loss_scale" in kinds and "amp_found_inf" in kinds
    assert monitor.gauge("pt_amp_loss_scale").value() == 8.0


def test_numerics_route_serves_provenance_and_stats():
    _enable()
    main, startup, out, t = _small_program()
    numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main,
                feed={"x": np.ones((1, 4), np.float32),
                      "y": np.full((1, 4), np.inf, np.float32)},
                fetch_list=[out])
    port = monitor.serve(0)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/numerics", timeout=10) as r:
        assert r.status == 200
        payload = json.loads(r.read())
    assert payload["active"] is True
    assert payload["provenance"][0]["op_type"] == "elementwise_sub"
    assert t.name in payload["programs"][str(main._uid)]["stats"]


# --------------------------------------------------------------------------
# disabled hot path (acceptance: tracemalloc proof)
# --------------------------------------------------------------------------

def test_disabled_executor_run_allocates_nothing_in_numerics():
    """With the numerics flag off (the default), Executor.run must not
    allocate a single attributable byte in numerics.py — the same
    one-boolean-check contract monitor.py honors."""
    assert not numerics.active()
    main, startup, out, _t = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((1, 4), np.float32),
            "y": np.zeros((1, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # warm the compile cache + lazy interp state
            exe.run(main, feed=feed, fetch_list=[out])
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=feed, fetch_list=[out])
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith("numerics.py")
               and s.size_diff > 0)
    assert grew < n_runs * 16, (
        f"disabled Executor.run allocated {grew}B in numerics.py over "
        f"{n_runs} runs")


def test_flag_flip_activates_and_deactivates_decoding():
    main, startup, out, _t = _small_program()
    numerics.instrument(main)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((1, 4), np.float32),
            "y": np.zeros((1, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[out])  # off: no decode
        assert monitor.counter("pt_numerics_decodes_total").value() == 0
        _enable()
        exe.run(main, feed=feed, fetch_list=[out])
        assert monitor.counter("pt_numerics_decodes_total").value() == 1
        flags.set_flags({"numerics": False})
        exe.run(main, feed=feed, fetch_list=[out])
        assert monitor.counter("pt_numerics_decodes_total").value() == 1


# --------------------------------------------------------------------------
# MNIST e2e (slow tier): trainer-level auto-instrumentation
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_mnist_numerics_e2e_step_log_and_stats(tmp_path):
    from paddle_tpu.models import mnist as mnist_model

    path = tmp_path / "steps.jsonl"
    _enable()
    flags.set_flags({"step_log_path": str(path),
                     "numerics_vars": "*@GRAD"})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = mnist_model.get_model(use_conv=False)
        fluid.optimizer.SGD(0.1).minimize(model["loss"])
    plan = passes.apply_pass("instrument_numerics", main)._numerics_plan
    assert plan.entries and all(
        v.endswith("@GRAD") for v, _i, _t, _k in plan.entries)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            feed = {
                "pixel": rng.rand(16, 784).astype(np.float32),
                "label": rng.randint(0, 10, (16, 1)).astype(np.int64),
            }
            exe.run(main, feed=feed, fetch_list=[model["loss"]])
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    train = [r for r in recs if "numerics" in r]
    assert len(train) == 3
    for r in train:
        monitor.validate_step_record(r)
        assert r["numerics"]["nonfinite_vars"] == 0
        assert r["numerics"]["vars"] == len(plan.entries)
    # gradient stats are live in the registry
    g = monitor.gauge("pt_tensor_rms")
    assert any(g.value(labels={"var": v}) > 0
               for v, _i, _t, _k in plan.entries)
    assert numerics.provenance_records() == []
