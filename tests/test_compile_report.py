"""Compile & memory observability (PR 2 tentpole, piece 1): per-program
compile reports (schema, file emission, gauges, estimate fallback),
the estimate_memory pre-flight + budget warning, and the
debugger.pprint_program annotation. CPU-only jax; non-slow — the graded
smoke for the compile-report plane (also referenced from
.claude/skills/verify/SKILL.md)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import debugger, flags, layers, monitor
from paddle_tpu.core import lowering


@pytest.fixture(autouse=True)
def _clean_telemetry():
    defaults = {"telemetry": False, "step_log_path": "",
                "metrics_dump_path": "", "compile_report_dir": "",
                "device_memory_budget_bytes": 0}
    flags.set_flags(defaults)
    yield
    flags.set_flags(defaults)


def _small_train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        logits = layers.fc(x, 10)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(rng, batch=8):
    return {"x": rng.rand(batch, 16).astype(np.float32),
            "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}


# --------------------------------------------------------------------------
# the acceptance smoke: one compile -> one schema-valid report on disk
# --------------------------------------------------------------------------

def test_compile_emits_schema_valid_report(tmp_path):
    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
        exe.run(main, feed=_feed(rng), fetch_list=[loss])  # cache hit

    # one report per fresh compile: startup + main = 2 files, no third
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 2, files
    main_rep = None
    for f in files:
        rep = json.loads((tmp_path / f).read_text())
        monitor.validate_compile_report(rep)  # schema version + types
        assert rep["v"] == monitor.COMPILE_REPORT_SCHEMA_VERSION
        assert rep["backend"] == "cpu"
        # flops/peak present, or explicitly null with the estimate marker
        if rep["source"] == "xla":
            assert rep["flops"] is not None or rep["peak_bytes"] is not None
        else:
            assert rep["source"] == "estimate"
            assert rep["flops"] is None and rep["peak_bytes"] is None
        assert rep["n_ops"] == sum(rep["op_histogram"].values())
        if rep["program_uid"] == main._uid:
            main_rep = rep
    assert main_rep is not None
    # the training program lowers fc + softmax_xent + mean + sgd (+grads)
    assert main_rep["n_ops"] > 4
    assert main_rep["kind"] == "step"
    assert main_rep["strategy"] is None

    # in-memory mirror (the /compile endpoint's source) + gauges
    reports = monitor.compile_reports()
    assert f"program{main._uid}" in reports
    if main_rep["source"] == "xla":
        assert monitor.gauge("pt_compile_flops").value(
            labels={"program": f"program{main._uid}"}) == main_rep["flops"]
        assert monitor.gauge("pt_compile_peak_bytes").value(
            labels={"program": f"program{main._uid}"}
        ) == main_rep["peak_bytes"]
    assert monitor.counter("pt_compile_reports_total").value() == 2


def test_cpu_backend_reports_real_xla_numbers(tmp_path):
    """On CPU-only jax 0.4.37 cost_analysis/memory_analysis both work —
    this pins the happy path so a silent regression to 'estimate' (an
    API drift swallowed by the guards) fails loudly on the platform the
    suite actually runs."""
    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(np.random.RandomState(0)),
                fetch_list=[loss])
    rep = monitor.compile_reports()[f"program{main._uid}"]
    assert rep["source"] == "xla"
    assert rep["flops"] > 0
    assert rep["bytes_accessed"] > 0
    assert rep["peak_bytes"] > 0
    assert rep["argument_bytes"] > 0
    assert rep["analysis_ms"] > 0


def test_run_steps_window_emits_window_report(tmp_path):
    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_steps(main, feed_list=[_feed(rng), _feed(rng)], steps=4,
                      fetch_list=[loss])
    kinds = {r["kind"] for r in monitor.compile_reports().values()}
    assert "window" in kinds
    win = [r for r in monitor.compile_reports().values()
           if r["kind"] == "window"][0]
    monitor.validate_compile_report(win)


def test_estimate_fallback_marks_source(monkeypatch, tmp_path):
    """When the AOT analysis path is unavailable (older jax, exotic
    backend), the report must still emit — cost fields null, source
    'estimate', op histogram intact."""
    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_train_program()

    class _NoLower:
        def __getattr__(self, name):
            raise AttributeError(name)

    feed = _feed(np.random.RandomState(0))
    lowered = lowering.lower_block(
        main, 0, sorted(feed), [loss.name])
    rep = lowering.build_compile_report(
        _NoLower(), lowered, (), program=main, compile_ms=1.0,
        cache_key=("k",))
    monitor.validate_compile_report(rep)
    assert rep["source"] == "estimate"
    assert rep["flops"] is None and rep["peak_bytes"] is None
    assert rep["analysis_ms"] is None
    assert rep["op_histogram"] and rep["n_ops"] > 0


def test_no_reports_without_dir_or_server():
    """compile_reports_active gates the extra AOT compile: telemetry on
    alone (no dir, no live endpoint) must not generate reports."""
    flags.set_flags({"telemetry": True})
    assert not monitor.compile_reports_active()
    main, startup, loss = _small_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(np.random.RandomState(0)),
                fetch_list=[loss])
    assert monitor.compile_reports() == {}


def test_validate_compile_report_rejects_bad():
    good = {f: None for f in monitor.COMPILE_REPORT_FIELDS}
    good.update({"v": monitor.COMPILE_REPORT_SCHEMA_VERSION, "ts": 0.0,
                 "program": "program1", "program_uid": 1, "cache_key": "k",
                 "kind": "step", "backend": "cpu", "source": "estimate",
                 "n_ops": 0, "op_histogram": {}})
    monitor.validate_compile_report(good)
    with pytest.raises(ValueError, match="missing field"):
        monitor.validate_compile_report(
            {k: v for k, v in good.items() if k != "flops"})
    with pytest.raises(ValueError, match="unknown fields"):
        monitor.validate_compile_report(dict(good, bogus=1))
    with pytest.raises(ValueError, match="schema"):
        monitor.validate_compile_report(dict(good, v=999))
    with pytest.raises(ValueError, match="source"):
        monitor.validate_compile_report(dict(good, source="psychic"))


# --------------------------------------------------------------------------
# pre-flight memory estimate + budget warning
# --------------------------------------------------------------------------

def test_estimate_memory_accounts_params_feeds_activations():
    main, startup, loss = _small_train_program()
    est = monitor.estimate_memory(
        main, {"x": (8, 16), "label": (8, 1)})
    # fc weight [16, 10] f32 + bias [10] f32 (+ SGD has no slots)
    assert est["param_bytes"] >= (16 * 10 + 10) * 4
    assert est["feed_bytes"] == 8 * 16 * 4 + 8 * 1 * 8
    assert est["activation_bytes"] > 0
    assert est["total_bytes"] == (est["param_bytes"] + est["feed_bytes"]
                                  + est["activation_bytes"])
    assert est["fits"] is None  # no budget configured
    # explicit budget: verdict flips around the total
    over = monitor.estimate_memory(
        main, {"x": (8, 16)}, budget_bytes=est["total_bytes"] * 2)
    assert over["fits"] is True
    under = monitor.estimate_memory(main, {"x": (8, 16)}, budget_bytes=1)
    assert under["fits"] is False


def test_budget_preflight_warns_before_compile():
    flags.set_flags({"telemetry": True,
                     "device_memory_budget_bytes": 1})  # everything OOMs
    main, startup, loss = _small_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        with pytest.warns(RuntimeWarning, match="memory estimate"):
            exe.run(startup)
        with pytest.warns(RuntimeWarning, match="likely to OOM"):
            exe.run(main, feed=_feed(np.random.RandomState(0)),
                    fetch_list=[loss])


# --------------------------------------------------------------------------
# debugger annotation
# --------------------------------------------------------------------------

def test_pprint_program_carries_compile_annotation(tmp_path):
    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_train_program()
    # before any compile: listing renders without the annotation
    assert "compile report" not in debugger.pprint_program(main)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(np.random.RandomState(0)),
                fetch_list=[loss])
    text = debugger.pprint_program(main)
    assert "compile report" in text
    assert "flops=" in text and "peak=" in text
    # opt-out restores the plain listing
    assert "compile report" not in debugger.pprint_program(
        main, with_compile_report=False)
