"""The train and serve loops end to end at a tiny config on the CPU:
the last line has exactly the contract's keys, and the command itself
refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from perf import harness
from perf.kinds import serve, train

import perfbench_tiny as tiny

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
V5E = harness.load_json("perf", "peaks.json")["TPU v5 lite"]


@pytest.fixture(autouse=True)
def _trace_into_tmp(monkeypatch, tmp_path):
    """A traced tiny run traces under the test's own directory, not
    into the checkout's one directory a cell, which
    ``harness.DeviceTrace`` empties on entry: two workers of one test
    run would delete each other's trace."""
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))


def line_of(run, capsys):
    line = harness.result_line(run)
    text = json.dumps(line)          # what run.py prints last
    assert LINE_KEYS <= set(json.loads(text))
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    return line


# every train cell of BENCHMARK.json, a later PR's too, on the chips
# (CPU devices here) its entry asks for
@pytest.mark.parametrize("cell_name,chips", tiny.cells_of("train"))
def test_train_loop_prints_the_contracts_line(cell_name, chips, capsys):
    cell = tiny.train_cell(cell_name, chips)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3)
    train.run(run)
    line = line_of(run, capsys)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 2
    assert set(line["metrics"]) == {m["name"] for m in harness.cell_metrics(
        run.bench, cell_name, "end_to_end")} >= {"train_tokens_per_s",
                                                  "setup_s"}
    w = run.window
    assert w["seconds"] >= 0.3 and w["tokens"] > 0
    assert line["metrics"]["train_tokens_per_s"]["value"] == pytest.approx(
        w["tokens"] / w["seconds"])
    assert run.compiles_in_window == 0
    assert "relative difference" in capsys.readouterr().out


def test_traced_train_run_reports_per_layer_metrics(monkeypatch, capsys):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    cell = tiny.train_cell("tbase-train")
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = line_of(run, capsys)
    # no TPU plane in a CPU trace: the device-trace readers find nothing
    # and their metrics are left out, the others are there
    assert run.trace is None
    assert {"exec.host_ms_per_step.train", "cache.first_call_s",
            "lower.dense_attn_calls.train", "step.mfu.train"} \
        <= set(line["metrics"])
    assert "device.idle_share.train" not in line["metrics"]
    assert "train_tokens_per_s" not in line["metrics"]


def test_traced_run_times_host_phases_outside_window_and_trace(
        monkeypatch):
    from paddle_tpu import flags

    seen = []
    real = harness.program_counters

    def spy():
        seen.append(flags.get_flag("step_phases"))
        return real()

    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(harness, "program_counters", spy)
    cell = tiny.train_cell("tbase-train")
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    # the window's snapshot with the phases off, the probe's two with
    # them on; every call of the probe was timed, none of the window
    assert seen == [False, True, True]
    c = run.counters
    assert c["phases_before"]["phase_count"] == c["after"]["phase_count"]
    assert c["phases_after"]["phase_count"] > c["phases_before"][
        "phase_count"]
    # the run ends with the flags' defaults
    assert not flags.get_flag("telemetry")
    assert flags.get_flag("step_phases")
    assert flags.get_flag("step_phases_every_n") == 16


# the serve kind has no cell in BENCHMARK.json yet (PERF.md section 7):
# its loop and readers are driven as a later PR's entries would
SERVE_E2E = ("ttft_p95_ms", "token_gap_p95_ms")
SERVE_LAYER = (("gen.late_p95_ms", "ms"), ("serve.queue_wait_p95_ms", "ms"),
               ("serve.slot_occupancy", "ratio"),
               ("exec.host_ms_per_step.serve", "ms"))


@pytest.mark.parametrize("traced", [False, True])
def test_serve_loop_prints_the_contracts_line(traced, capsys):
    cell = tiny.serve_cell(rate=30.0)
    run = tiny.make_run(
        cell, tiny.config(cell["config"]), seconds=1.0, traced=traced,
        bench=tiny.bench_with(cell["name"], SERVE_E2E, SERVE_LAYER))
    serve.run(run)
    line = line_of(run, capsys)
    assert line["correct"], line
    assert line["attempted"] == 30 and line["failed"] == 0
    if traced:
        assert set(line["metrics"]) == {n for n, _ in SERVE_LAYER} | {
            "cache.first_call_s"}
    else:
        assert set(line["metrics"]) == set(SERVE_E2E) | {"setup_s"}
    assert run.check["logit_rel"] < 1e-4     # f32 on the CPU
    out = capsys.readouterr().out
    assert "ttft_ms: n 30 p50" in out and "token_gap_ms: n" in out


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "tbase-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
