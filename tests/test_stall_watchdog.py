"""Collective stall watchdog (PR 2 tentpole, piece 3): a guarded section
that outlives its deadline must increment pt_stall_total, buffer a
structured stall record carrying the arming thread's span stack, and
(flag-gated) dump the flight recorder — while a fast section leaves no
trace and a disabled guard is the shared nullcontext."""

import json
import time
import warnings

import pytest

from paddle_tpu import flags, monitor


@pytest.fixture(autouse=True)
def _clean_telemetry():
    defaults = {"telemetry": False, "step_log_path": "",
                "stall_timeout_ms": 0, "stall_dump_dir": ""}
    flags.set_flags(defaults)
    yield
    flags.set_flags(defaults)


def test_forced_stall_records_and_counts():
    monitor.enable()
    flags.set_flags({"stall_timeout_ms": 100})
    with pytest.warns(RuntimeWarning, match="stall watchdog"):
        with monitor.span("outer"), monitor.span("fleet.barrier"):
            with monitor.stall_guard("fleet.barrier"):
                time.sleep(0.35)  # deliberately blows the 100ms deadline
    assert monitor.counter("pt_stall_total").value(
        labels={"site": "fleet.barrier"}) == 1
    recs = monitor.stalls()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["site"] == "fleet.barrier"
    assert rec["deadline_ms"] == 100
    # the span stack pinpoints WHERE the thread sat when the timer fired
    assert rec["span_stack"] == ["outer", "fleet.barrier"]
    assert rec["v"] == monitor.STALL_RECORD_SCHEMA_VERSION
    assert rec["last_step"] is None  # no executor steps ran


def test_stall_record_carries_last_step():
    monitor.enable()
    monitor.log_step({"kind": "step", "step": 7, "wall_ms": 1.0,
                      "compile_ms": None, "cache": "hit", "evictions": 0,
                      "feed_bytes": 0, "fetch_bytes": 0,
                      "nan_check": None, "strategy": None})
    with pytest.warns(RuntimeWarning, match="stall watchdog"):
        with monitor.stall_guard("trainer.step", deadline_ms=50):
            time.sleep(0.25)
    rec = monitor.stalls()[-1]
    assert rec["last_step"]["step"] == 7


def test_fast_section_leaves_no_trace():
    monitor.enable()
    flags.set_flags({"stall_timeout_ms": 10_000})
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with monitor.stall_guard("fleet.barrier"):
            pass
    # give a mis-armed timer a moment to (wrongly) fire
    time.sleep(0.05)
    assert monitor.counter("pt_stall_total").value(
        labels={"site": "fleet.barrier"}) == 0
    assert monitor.stalls() == []


def test_disabled_guard_is_shared_nullcontext():
    # telemetry off: no allocation, one shared object
    assert monitor.stall_guard("x") is monitor.stall_guard("y")
    # telemetry on but no deadline anywhere: still the nullcontext
    monitor.enable()
    assert monitor.stall_guard("x") is monitor.stall_guard("y")
    with monitor.stall_guard("x"):
        pass
    assert monitor.stalls() == []


def test_flight_recorder_dump(tmp_path):
    monitor.enable()
    flags.set_flags({"stall_dump_dir": str(tmp_path)})
    monitor.log_step({"kind": "step", "step": 3, "wall_ms": 1.0,
                      "compile_ms": None, "cache": "hit", "evictions": 0,
                      "feed_bytes": 0, "fetch_bytes": 0,
                      "nan_check": None, "strategy": None})
    monitor.counter("t_wd_c", "doc").inc(5)
    with pytest.warns(RuntimeWarning, match="stall watchdog"):
        with monitor.stall_guard("pipeline.dispatch", deadline_ms=50):
            time.sleep(0.25)
    dumps = list(tmp_path.glob("stall-*.json"))
    assert len(dumps) == 1
    payload = json.loads(dumps[0].read_text())
    assert payload["stall"]["site"] == "pipeline.dispatch"
    assert [s["step"] for s in payload["steps"]] == [3]
    assert payload["metrics"]["t_wd_c"]["values"][0]["value"] == 5.0
    assert "compile_reports" in payload


def test_watchdog_fires_once_per_guard():
    """One guarded section -> at most one stall record, however long it
    overruns (threading.Timer is one-shot) — and cancel on exit means a
    section that finishes JUST after arming never double-reports."""
    monitor.enable()
    with pytest.warns(RuntimeWarning, match="stall watchdog"):
        with monitor.stall_guard("fleet.kv_get", deadline_ms=40):
            time.sleep(0.3)  # ~7x the deadline: still one firing
    assert monitor.counter("pt_stall_total").value(
        labels={"site": "fleet.kv_get"}) == 1
    assert len(monitor.stalls()) == 1
