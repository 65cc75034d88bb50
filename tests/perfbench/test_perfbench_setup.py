"""The eight set-up metrics of PR 36 (perf/setup_stages.py and its
readers) on a tiny train run on the CPU: a traced run reports all of
them and they nest as their definitions say, an untraced run and a
program from before PR 36 report none."""

import math

import pytest

from paddle_tpu import flags, monitor
from perf import harness, setup_stages
from perf.kinds import train

import perfbench_tiny as tiny

SETUP_METRICS = (
    "exec.first_call_s", "setup.trace_s", "setup.lower_s",
    "cache.backend_s", "cache.persistent_writes", "setup.jax_traces",
    "lower.op_trace_s", "setup.unnamed_s")
ENTRIES = {m["name"]: m for m in tiny.BENCH["per_layer"]}
V5E = harness.load_json("perf", "peaks.json")["TPU v5 lite"]
# jax stamps its stages with time.time(), spans run on perf_counter
CLOCKS_APART_S = 0.02


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    # a traced tiny run traces under the test's own directory, not into
    # the checkout's one directory a cell, which harness.DeviceTrace
    # empties on entry: two workers would delete each other's trace
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    monitor.reset()
    yield
    flags.set_flags({"telemetry": False})
    monitor.reset()


def tiny_run(traced, monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    cell = tiny.train_cell("tbase-train")
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=traced)
    train.run(run)
    return run


@pytest.fixture
def traced_line(monkeypatch, capsys):
    run = tiny_run(True, monkeypatch)
    line = harness.result_line(run)
    return run, line["metrics"], capsys.readouterr().out


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_the_entry_lists_every_cell_and_moves_setup(metric):
    entry = ENTRIES[metric]
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    # every cell that trains, in the cells' own order: a later cell is
    # appended behind them
    cells = [w["name"] for w in tiny.BENCH["workloads"]]
    assert entry["workloads"] == [c for c in cells
                                  if c in entry["workloads"]]
    assert set(tiny.cells_named(tiny.BENCH, "train_tokens_per_s")) \
        <= set(entry["workloads"])
    assert entry["unit"] == ("count" if metric in (
        "cache.persistent_writes", "setup.jax_traces") else "s")


def test_a_traced_run_reports_all_eight_and_they_nest(traced_line):
    run, metrics, out = traced_line
    got = {m: metrics[m]["value"] for m in SETUP_METRICS}
    assert all(math.isfinite(v) and v >= 0 for v in got.values()), got
    stages = (got["setup.trace_s"] + got["setup.lower_s"]
              + got["cache.backend_s"])
    assert 0 < stages <= got["exec.first_call_s"] + CLOCKS_APART_S
    # from inside the executor the reference and the harness are left out
    assert got["exec.first_call_s"] <= metrics["cache.first_call_s"]["value"]
    assert 0 < got["lower.op_trace_s"] <= got["setup.trace_s"] \
        + CLOCKS_APART_S
    assert got["setup.jax_traces"] >= 100
    assert got["setup.unnamed_s"] < run.setup_s - sum(
        run.first_calls.values())
    # three programs had a first call: startup, the eval clone, the step
    first = monitor.histogram("pt_span_seconds").count(
        labels={"span": "executor.first_call"})
    assert first == len(run.first_calls) == 3
    # the two tables and the cache's account reach the run's log
    assert "most traced functions [name, traces]: [['" in out
    assert "dearest op rules [op, seconds, ops lowered]: [['" in out
    assert "jax's persistent cache: " in out


def test_the_reference_is_outside_and_is_not_counted(traced_line):
    run, metrics, _ = traced_line
    outside = setup_stages.total(
        run, "pt_compile_stage_seconds", "sum",
        lambda lb: lb["program"] == setup_stages.OUTSIDE)
    everything = setup_stages.total(run, "pt_compile_stage_seconds", "sum")
    mine = sum(metrics[m]["value"] for m in (
        "setup.trace_s", "setup.lower_s", "cache.backend_s"))
    assert outside > 0     # check_loss jits the float32 reference
    assert mine == pytest.approx(everything - outside)


def test_an_untraced_run_reports_none(monkeypatch):
    run = tiny_run(False, monkeypatch)
    for metric in SETUP_METRICS:
        assert harness.reader_for(metric).read(run) is None, metric
    assert not set(SETUP_METRICS) & set(harness.result_line(run)["metrics"])


def test_a_program_without_the_instruments_reports_none(monkeypatch):
    # a checkout from before PR 36, traced with this PR's benchmark files
    # laid over it: its registry has none of the names and no such span
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"), traced=True)
    run.first_calls = {"startup": 1.0, "train_step": 2.0}
    run.setup_done()
    old = {"pt_span_seconds": {"kind": "histogram", "doc": "", "values": [
        {"labels": {"span": "executor.run"}, "count": 3, "sum": 0.5}]}}
    monkeypatch.setattr(monitor, "snapshot", lambda: old)
    for metric in SETUP_METRICS:
        assert harness.reader_for(metric).read(run) is None, metric


def test_a_warm_machine_reads_zero_writes_not_none(monkeypatch):
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"), traced=True)
    snap = {"pt_compile_cache_total": {"kind": "counter", "doc": "",
                                       "values": []}}
    monkeypatch.setattr(monitor, "snapshot", lambda: snap)
    read = harness.reader_for("cache.persistent_writes").read
    assert read(run) == 0.0
    snap["pt_compile_cache_total"]["values"] = [
        {"labels": {"program": "program3", "outcome": "hit"}, "value": 3.0},
        {"labels": {"program": "(outside)", "outcome": "written"},
         "value": 1.0},
        {"labels": {"program": "program5", "outcome": "written"},
         "value": 2.0}]
    run._setup_snapshot = None
    assert read(run) == 3.0
