"""The step record as a time-line (monitor.py, executor.py): ``t0``
puts a call on the run's clock (``time.perf_counter`` at its entry),
``gc_ms`` charges the collector's pauses (``pt_gc_pause_seconds``) to
the first record logged after them, the ring holds a run, and with
telemetry off none of it exists: no hook in ``gc.callbacks``, no clock
read, no record."""

import gc
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor, flags, layers, monitor


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags({"telemetry": False, "step_log_path": ""})
    yield
    flags.set_flags({"telemetry": False, "step_log_path": ""})


@pytest.fixture
def quiet_collector():
    """Only a forced collection runs: a generation-0 pass of the
    collector's own would put its microseconds into some record."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 8), np.float32)}
    return exe, main, loss, scope, feed


def gc_row(generation):
    """``pt_gc_pause_seconds``'s row of a generation, read and not
    registered (the program registers it, with its doc)."""
    rows = monitor.snapshot().get("pt_gc_pause_seconds", {"values": []})
    return next((r for r in rows["values"]
                 if r["labels"] == {"generation": str(generation)}),
                {"count": 0, "sum": 0.0})


def gc_pauses(generation):
    return gc_row(generation)["count"]


@pytest.mark.parametrize("calls", [1, 5])
def test_every_run_record_carries_its_start_on_the_callers_clock(calls):
    exe, main, loss, scope, feed = program()
    monitor.enable()
    before = time.perf_counter()
    for _ in range(calls):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    after = time.perf_counter()
    recs = monitor.recent_steps()
    assert len(recs) == calls and {r["kind"] for r in recs} == {"step"}
    for r in recs:
        monitor.validate_step_record(r)
        assert isinstance(r["t0"], float) and isinstance(r["gc_ms"], float)
    t0 = [r["t0"] for r in recs]
    assert before <= t0[0] and t0 == sorted(t0)
    # a call has ended before the next one begins, and the last before
    # the caller reads its clock again
    ends = [r["t0"] + r["wall_ms"] / 1e3 for r in recs]
    assert all(e <= nxt for e, nxt in zip(ends, t0[1:] + [after]))


def test_a_window_record_carries_its_start_too():
    exe, main, loss, scope, feed = program()
    monitor.enable()
    before = time.perf_counter()
    exe.run_steps(main, feed_list=[feed], steps=3, fetch_list=[loss],
                  scope=scope)
    (rec,) = monitor.recent_steps()
    monitor.validate_step_record(rec)
    assert rec["kind"] == "window" and rec["steps"] == 3
    assert before <= rec["t0"]
    assert rec["t0"] + rec["wall_ms"] / 1e3 <= time.perf_counter()


def test_a_collection_is_charged_to_the_record_behind_it(quiet_collector):
    exe, main, loss, scope, feed = program()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)   # compiled
    monitor.enable()
    for i in range(4):
        if i == 2:
            gc.collect()
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    recs = monitor.recent_steps()
    assert [r["gc_ms"] for r in recs[:2] + recs[3:]] == [0.0, 0.0, 0.0]
    assert recs[2]["gc_ms"] > 0.0
    assert gc_pauses(2) == 1 and gc_pauses(0) == gc_pauses(1) == 0
    assert gc_row(2)["sum"] * 1e3 == pytest.approx(
        recs[2]["gc_ms"])


def test_pauses_add_up_until_a_record_takes_them(quiet_collector):
    monitor.enable()
    gc.collect(0)
    gc.collect(1)
    assert gc_pauses(0) == 0        # fed when a record is logged
    monitor.log_step({"kind": "step", "step": 0})
    monitor.log_step({"kind": "step", "step": 1})
    first, second = monitor.recent_steps()
    assert gc_pauses(0) == gc_pauses(1) == 1
    assert first["gc_ms"] > 0.0 and second["gc_ms"] == 0.0
    # a caller's own value stands (tests hand log_step whole records)
    monitor.log_step({"kind": "step", "step": 2, "gc_ms": 7.0})
    assert monitor.recent_steps(1)[0]["gc_ms"] == 7.0


def test_with_telemetry_off_the_collector_calls_nothing_of_ours():
    exe, main, loss, scope, feed = program()
    assert monitor._on_gc not in gc.callbacks
    gc.collect()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert monitor.recent_steps() == []
    monitor.enable()
    assert gc_pauses(2) == 0 and not monitor._GC_PAUSES
    assert monitor._take_gc_ms() == 0.0


def test_with_telemetry_off_a_warm_call_reads_no_clock(monkeypatch):
    exe, main, loss, scope, feed = program()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)   # compiled
    reads = []

    class Clock:
        @staticmethod
        def perf_counter():
            reads.append(1)
            return time.perf_counter()

    monkeypatch.setattr(executor, "time", Clock)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert reads == []
    monitor.enable()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert reads and monitor.recent_steps()[0]["t0"] > 0.0


@pytest.mark.parametrize("times", [1, 2, 3])
def test_the_hook_is_registered_once_and_taken_out(times):
    for _ in range(times):
        monitor.enable()
        flags.set_flags({"telemetry": True})
    assert gc.callbacks.count(monitor._on_gc) == 1
    monitor.disable()
    assert monitor._on_gc not in gc.callbacks
    monitor.disable()       # off twice: nothing to take out
    assert monitor._on_gc not in gc.callbacks


def test_the_ring_holds_a_run_and_drops_the_oldest():
    assert monitor.STEP_RING_CAPACITY == 2048
    monitor.enable()
    for i in range(2048 + 3):
        monitor.log_step({"kind": "step", "step": i, "t0": float(i)})
    recs = monitor.recent_steps()
    assert len(recs) == 2048
    assert recs[0]["step"] == 3 and recs[-1]["t0"] == 2050.0


RECORD = {"v": 1, "ts": 1.0, "seq": 0, "kind": "step", "step": 0,
          "wall_ms": 1.5, "compile_ms": None, "cache": "hit",
          "evictions": 0, "feed_bytes": 64, "fetch_bytes": 4,
          "nan_check": None, "strategy": None}


@pytest.mark.parametrize("extra, problem", [
    ({"t0": 12.5, "gc_ms": 0.0}, None),
    ({"t0": 12, "gc_ms": 3}, None),
    ({"gc_ms": 0.25}, None),            # no t0: an older writer's record
    ({}, None),
    ({"t0": "12.5"}, "t0"),
    ({"gc_ms": None}, "gc_ms"),
    ({"t1": 12.5}, "unknown"),
])
def test_the_schema_takes_the_two_fields_and_no_other(extra, problem):
    rec = dict(RECORD, **extra)
    if problem is None:
        monitor.validate_step_record(rec)
    else:
        with pytest.raises(ValueError, match=problem):
            monitor.validate_step_record(rec)
    assert monitor.STEP_LOG_SCHEMA_VERSION == 1
    assert not monitor.STEP_LOG_FIELDS["t0"][1]
    assert not monitor.STEP_LOG_FIELDS["gc_ms"][1]


def test_a_collection_inside_a_profiler_session_is_a_span(tmp_path):
    """The hook's annotation lands on the trace's host plane, by name,
    with its generation, as the executor's spans do."""
    import jax

    from perf import spans, trace

    exe, main, loss, scope, feed = program()
    monitor.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    host = next(p for p in spans.load(trace.find_xplane(str(tmp_path)))
                ["planes"] if p["name"] == "/host:CPU")
    events = [e for ln in host["lines"] for e in ln["events"]]
    full = [e for e in events if e[0] == "gc.collect"
            and e[3].get("generation") == 2]
    assert full and any(e[0] == "executor.run" for e in events)


def test_no_pause_is_lost_or_counted_twice_under_threads():
    """Collections on four threads while two more log records: every
    pause the histogram took is in exactly one record's ``gc_ms``."""
    import sys
    import threading

    monitor.enable()

    def churn():
        for _ in range(150):
            junk = [[i] for i in range(50)]
            junk.append(junk)       # a cycle for the collector
            gc.collect(0)

    def log(deadline=time.monotonic() + 60):
        while any(t.is_alive() for t in threads) \
                and time.monotonic() < deadline:
            monitor.log_step({"kind": "step", "step": 0})
            time.sleep(0.002)   # (the ring is to hold every record)

    threads = [threading.Thread(target=churn) for _ in range(4)]
    loggers = [threading.Thread(target=log) for _ in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads + loggers:
            t.start()
        for t in threads + loggers:
            t.join(timeout=90)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads + loggers)
    monitor.log_step({"kind": "step", "step": 1})     # the last pauses
    taken = sum(gc_row(g)["sum"] for g in (0, 1, 2))
    # (a collect() that finds another thread's under way does nothing)
    assert 100 < gc_pauses(0) <= 4 * 150 and not monitor._GC_PAUSES
    # (the ring holds the last 2048 records: none of these has left it)
    recs = monitor.recent_steps()
    assert len(recs) < monitor.STEP_RING_CAPACITY
    assert sum(r["gc_ms"] for r in recs) == pytest.approx(taken * 1e3)
