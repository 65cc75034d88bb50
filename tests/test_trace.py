"""Time-attribution plane (PR 4 tentpole): executor step-phase
breakdown + boundedness verdict, the Chrome-trace timeline ring,
trace_dir export, the /trace route, merge_traces, legacy-profiler
routing, and the disabled-path zero-allocation contract."""

import json
import os
import tracemalloc
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor, profiler

# step_phases_every_n forced to 1 here: per-step phases are the thing
# under test (the sampled-phases contract has its own suite in
# tests/test_async_pipeline.py)
_RESET_FLAGS = {"telemetry": False, "step_log_path": "",
                "metrics_dump_path": "", "trace_dir": "",
                "trace_every_n_steps": 1, "metrics_port": 0,
                "step_phases": True, "step_phases_every_n": 1,
                "check_nan_inf": False}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags(dict(_RESET_FLAGS))
    yield
    monitor.stop_server()
    flags.set_flags(dict(_RESET_FLAGS))


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _run_steps(n=3, trace_dir=None):
    """n training steps of the tiny program under telemetry."""
    new = {"telemetry": True}
    if trace_dir is not None:
        new["trace_dir"] = trace_dir
    flags.set_flags(new)
    main, startup, loss = _tiny_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(n):
            exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                    fetch_list=[loss])
    return exe


# --------------------------------------------------------------------------
# activation gate
# --------------------------------------------------------------------------

def test_trace_inactive_without_visibility():
    """Tracing needs telemetry AND a sink (trace_dir or the live
    endpoint) — same never-on-by-accident rule as compile reports."""
    assert not monitor.trace_active()
    flags.set_flags({"telemetry": True})
    assert not monitor.trace_active()
    monitor.trace_event("ghost", "span", 0.0, 1.0)
    assert monitor.trace_events() == []
    flags.set_flags({"trace_dir": "/tmp"})
    assert monitor.trace_active()
    flags.set_flags({"telemetry": False})
    assert not monitor.trace_active()


def test_server_alone_activates_tracing():
    flags.set_flags({"telemetry": True})
    assert not monitor.trace_active()
    monitor.serve(0)
    assert monitor.trace_active()
    monitor.stop_server()
    assert not monitor.trace_active()


# --------------------------------------------------------------------------
# event schema + ring semantics
# --------------------------------------------------------------------------

def _assert_chrome_schema(events):
    """Required keys per event; ts non-negative and monotone per
    (pid, tid) track; X events carry a non-negative dur."""
    last_ts = {}
    assert events, "no trace events"
    for ev in events:
        for k in ("name", "ph", "ts", "pid", "tid"):
            assert k in ev, f"event missing '{k}': {ev}"
        if ev["ph"] == "M":
            continue
        assert ev["ts"] >= 0
        track = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last_ts.get(track, 0.0), (
            f"ts not monotone on track {track}")
        last_ts[track] = ev["ts"]
        if ev["ph"] == "X":
            assert ev["dur"] >= 0


def test_trace_events_conform_to_chrome_schema(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    with monitor.span("trace.outer"):
        with monitor.span("trace.inner"):
            pass
    monitor.trace_event("mark", "stall", 1.0)  # instant event
    doc = monitor.trace_snapshot()
    _assert_chrome_schema(doc["traceEvents"])
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"trace.outer", "trace.inner", "mark"} <= names
    # category -> synthetic track: spans and stalls on distinct tids
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    assert by_name["trace.outer"]["tid"] != by_name["mark"]["tid"]
    # json round-trip (what a trace viewer loads)
    assert json.loads(json.dumps(doc, default=str))["traceEvents"]


def test_trace_ring_is_bounded_with_drop_counter(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    n = monitor.TRACE_RING_CAPACITY
    for i in range(n + 7):
        monitor.trace_event(f"e{i}", "span", float(i), float(i) + 0.5)
    evs = monitor.trace_events()
    assert len(evs) == n
    assert evs[0]["name"] == "e7"  # oldest evicted first
    assert monitor.counter("pt_trace_events_total").value() == n + 7
    assert monitor.counter("pt_trace_events_dropped_total").value() == 7


# --------------------------------------------------------------------------
# legacy profiler routing (satellite): one clock, one stream
# --------------------------------------------------------------------------

def test_record_event_span_appears_in_exported_trace(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    with profiler.record_event("legacy.record_event"):
        pass
    path = monitor.export_trace()
    doc = json.load(open(path))
    spans = [e for e in doc["traceEvents"]
             if e.get("cat") == "span" and e["ph"] == "X"]
    assert any(e["name"] == "legacy.record_event" for e in spans)
    # same clock: the legacy span's ts is comparable to a monitor.span's
    with monitor.span("new.span"):
        pass
    evs = monitor.trace_events()
    legacy = next(e for e in evs if e["name"] == "legacy.record_event")
    new = next(e for e in evs if e["name"] == "new.span")
    assert legacy["tid"] == new["tid"]
    assert legacy["ts"] <= new["ts"]


def test_start_stop_profiler_marks_the_timeline(tmp_path, monkeypatch):
    from paddle_tpu import native

    monkeypatch.setattr(native, "available", lambda: False)
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    profiler.start_profiler()
    profiler.stop_profiler(profile_path=str(tmp_path / "p"))
    names = [e["name"] for e in monitor.trace_events()]
    assert names.count("profiler.start") == 1
    assert names.count("profiler.stop") == 1


def test_record_event_untraced_is_a_bare_yield():
    """Both collectors off: record_event must not buffer anything."""
    with profiler.record_event("invisible"):
        pass
    assert monitor.trace_events() == []


# --------------------------------------------------------------------------
# executor step phases + boundedness verdict
# --------------------------------------------------------------------------

def test_run_records_phases_and_bound(tmp_path):
    _run_steps(3)
    recs = monitor.recent_steps()
    assert len(recs) == 4  # startup + 3 train steps
    for rec in recs:
        monitor.validate_step_record(rec)
        assert rec["sampled"] is True  # every_n=1: all sampled
        phases = rec["phases"]
        assert set(phases) == set(monitor.STEP_PHASES)
        for name, ms in phases.items():
            assert ms > 0, f"phase '{name}' not measured"
        # phases are measured sub-intervals of the wall interval
        assert sum(phases.values()) <= rec["wall_ms"]
    # only COMMITTED CACHE-HIT steps are verdict-scored: a fresh
    # compile's host time would pollute the dispatch share (the two
    # misses here are the startup program and the first train step)
    for rec in recs[:2]:
        assert rec["cache"] == "miss" and "bound" not in rec
    for rec in recs[2:]:
        assert rec["cache"] == "hit"
        assert rec["bound"] in monitor.BOUND_VERDICTS
    # histograms observed once per phase per SAMPLED step (miss or hit)
    h = monitor.histogram("pt_step_phase_seconds")
    for phase in monitor.STEP_PHASES:
        assert h.count(labels={"phase": phase}) == 4
    # every scored step counted into exactly one verdict
    c = monitor.counter("pt_step_bound_total")
    total = sum(c.value(labels={"verdict": v})
                for v in monitor.BOUND_VERDICTS)
    assert total == 2
    assert monitor.boundedness()["steps"] == 2


def test_run_steps_window_records_phases(tmp_path):
    flags.set_flags({"telemetry": True})
    main, startup, loss = _tiny_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, 8), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_steps(main, feed_list=[feed], steps=4, fetch_list=[loss])
        rec = monitor.recent_steps()[-1]
        assert rec["kind"] == "window"
        monitor.validate_step_record(rec)
        assert all(v > 0 for v in rec["phases"].values())
        # the first window is a fresh compile: phases measured, verdict
        # withheld (compile time would pollute the dispatch share)
        assert rec["cache"] == "miss" and "bound" not in rec
        exe.run_steps(main, feed_list=[feed], steps=4, fetch_list=[loss])
    rec = monitor.recent_steps()[-1]
    assert rec["cache"] == "hit"
    assert all(v > 0 for v in rec["phases"].values())
    assert rec["bound"] in monitor.BOUND_VERDICTS


def test_input_wait_tips_verdict_to_input_bound():
    """Reader consumer waits drained into the verdict scores dominate a
    cheap device step: the window must call it input_bound."""
    flags.set_flags({"telemetry": True})
    monitor.note_input_wait(5.0)
    verdict = monitor.record_step_phases(0.001, 0.002, 0.003, 0.001)
    assert verdict == "input_bound"
    b = monitor.boundedness()
    assert b["verdict"] == "input_bound"
    assert b["shares"]["input"] > 0.99
    # the accumulator drained: an undisturbed next step is device_bound
    assert monitor.record_step_phases(0.0, 0.0, 60.0, 0.0) == "device_bound"


def test_step_phases_flag_opts_out_of_sync_and_phases():
    """step_phases=False keeps telemetry records but skips the phase
    marks (and their per-step block_until_ready): no phases/bound
    fields, no histogram cells, no verdict."""
    flags.set_flags({"step_phases": False})
    _run_steps(2)
    recs = monitor.recent_steps()
    assert len(recs) == 3
    for rec in recs:
        monitor.validate_step_record(rec)
        assert "phases" not in rec and "bound" not in rec
        # phase plane fully off: no sampled marker either (the marker
        # distinguishes sampled/unsampled WITHIN an active plane)
        assert "sampled" not in rec
    assert monitor.histogram("pt_step_phase_seconds")._cells == {}
    assert monitor.boundedness() is None
    # flipping it back mid-process takes effect immediately
    flags.set_flags({"step_phases": True})
    assert monitor.phases_active()


def test_failed_step_logs_record_without_phases():
    """A step that raises before commit (check_nan_inf) must log its
    postmortem record WITHOUT phases — truncated durations would skew
    the rolling verdict window."""
    flags.set_flags({"telemetry": True, "check_nan_inf": True})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.log(x)  # log(0) -> -inf
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(FloatingPointError):
            exe.run(main, feed={"x": np.zeros((1, 4), np.float32)},
                    fetch_list=[y])
    rec = monitor.recent_steps()[-1]
    assert rec["nan_check"] == "fail"
    assert "phases" not in rec and "bound" not in rec


def test_phase_trace_events_respect_sampling(tmp_path):
    flags.set_flags({"trace_every_n_steps": 2})
    _run_steps(4, trace_dir=str(tmp_path))
    phase_steps = {e["args"]["step"] for e in monitor.trace_events()
                   if e.get("cat") == "phase"}
    # steps 0 (startup), 1..4 (train); only even executor steps sampled
    assert phase_steps == {0, 2, 4}


def test_window_sampling_does_not_alias_against_stride(tmp_path):
    """A run_steps window is sampled whenever ANY of its steps hits the
    period — windows of 4 against trace_every_n_steps=7 must not only
    trace every lcm(4,7)=28th step."""
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path),
                     "trace_every_n_steps": 7})
    # window [4, 8) contains step 7: sampled despite 4 % 7 != 0
    assert monitor.trace_step_sampled(4, 4)
    assert monitor.trace_step_sampled(7, 1)
    assert not monitor.trace_step_sampled(4, 3)  # [4, 7) misses it
    assert not monitor.trace_step_sampled(8, 1)


def test_stale_input_wait_cleared_when_phases_flip_on():
    """Waits accumulated while nobody drains them (phases off) must not
    dump into the first attributed step and fake an input_bound
    verdict."""
    flags.set_flags({"telemetry": True, "step_phases": False})
    # with phases off the accumulator doesn't even grow...
    monitor.note_input_wait(3600.0)
    flags.set_flags({"step_phases": True})
    # ...and flipping phases on clears anything that did (transition
    # guard) — a device-heavy step stays device_bound
    assert monitor.record_step_phases(0.0, 0.0, 1.0, 0.0) == "device_bound"


def test_compile_events_on_their_own_track(tmp_path):
    _run_steps(2, trace_dir=str(tmp_path))
    evs = monitor.trace_events()
    tids = {cat: {e["tid"] for e in evs if e.get("cat") == cat}
            for cat in ("span", "phase", "compile")}
    assert all(len(v) == 1 for v in tids.values()), tids
    assert len({next(iter(v)) for v in tids.values()}) == 3, tids
    compiles = [e for e in evs if e.get("cat") == "compile"]
    assert len(compiles) == 2  # startup + train program
    assert all(e["dur"] > 0 for e in compiles)


# --------------------------------------------------------------------------
# export / serve / merge
# --------------------------------------------------------------------------

def test_export_trace_writes_per_process_file(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    with monitor.span("export.me"):
        pass
    path = monitor.export_trace()
    assert os.path.basename(path).startswith("trace-")
    assert str(os.getpid()) in os.path.basename(path)
    doc = json.load(open(path))
    assert doc["metadata"]["os_pid"] == os.getpid()
    assert doc["metadata"]["v"] == monitor.TRACE_SCHEMA_VERSION
    _assert_chrome_schema(doc["traceEvents"])
    # no trace_dir, no implicit write target
    flags.set_flags({"trace_dir": ""})
    assert monitor.export_trace() is None


def test_trace_route_round_trips():
    flags.set_flags({"telemetry": True})
    port = monitor.serve(0)
    with monitor.span("served.span"):
        pass
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace", timeout=10) as r:
        assert r.status == 200
        doc = json.loads(r.read())
    assert any(e["name"] == "served.span" for e in doc["traceEvents"])
    _assert_chrome_schema(doc["traceEvents"])


def test_merge_traces_aligns_ranks_and_clocks(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    with monitor.span("worker.span"):
        pass
    base = monitor.trace_snapshot()
    # fake a second worker: same events, clock 1s ahead, rank 1
    other = json.loads(json.dumps(base, default=str))
    other["metadata"]["rank"] = 1
    for ev in other["traceEvents"]:
        if ev["ph"] != "M":
            ev["ts"] += 1e6
    p0, p1 = tmp_path / "t0.json", tmp_path / "t1.json"
    p0.write_text(json.dumps(base, default=str))
    p1.write_text(json.dumps(other, default=str))

    out = tmp_path / "merged.json"
    merged = monitor.merge_traces([str(p0), str(p1)], out_path=str(out))
    assert json.load(open(out)) == json.loads(
        json.dumps(merged, default=str))
    data = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    assert {e["pid"] for e in data} == {0, 1}  # rank-tagged tracks
    assert merged["metadata"]["merged_ranks"] == [0, 1]
    assert min(e["ts"] for e in data) == 0  # rebased
    assert data == sorted(data, key=lambda e: e["ts"])
    # offsets_us corrects a measured skew: rank 1 pulled back into sync
    fixed = monitor.merge_traces([str(p0), str(p1)],
                                 offsets_us={1: -1e6})
    fdata = [e for e in fixed["traceEvents"] if e["ph"] != "M"]
    r0 = sorted(e["ts"] for e in fdata if e["pid"] == 0)
    r1 = sorted(e["ts"] for e in fdata if e["pid"] == 1)
    assert r0 == pytest.approx(r1)


def test_merge_traces_rank_collision_falls_back_to_unused_rank(tmp_path):
    """Two traces claiming the same rank (re-runs, misconfigured fleet)
    must still land on distinct pid tracks."""
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    with monitor.span("dup.span"):
        pass
    base = monitor.trace_snapshot()
    a = json.loads(json.dumps(base, default=str))
    b = json.loads(json.dumps(base, default=str))
    a["metadata"]["rank"] = b["metadata"]["rank"] = 1
    merged = monitor.merge_traces([a, b])
    data = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    assert {e["pid"] for e in data} == {0, 1}
    assert merged["metadata"]["merged_ranks"] == [0, 1]


def test_reset_clears_timeline_and_verdict(tmp_path):
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    with monitor.span("gone"):
        pass
    monitor.record_step_phases(0.1, 0.1, 0.1, 0.1)
    monitor.reset()
    assert monitor.trace_events() == []
    assert monitor.boundedness() is None


# --------------------------------------------------------------------------
# disabled path: the one-boolean-check zero-allocation contract
# --------------------------------------------------------------------------

def test_disabled_executor_run_allocates_nothing_in_new_code():
    """With telemetry off, the PR-4 instrumentation (phase marks, trace
    gates, record_event hook) must add zero allocations attributable to
    monitor.py or profiler.py to Executor.run — the contract that lets
    the hot path stay permanently instrumented."""
    assert not monitor.enabled() and not monitor.trace_active()
    main, startup, _ = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 8), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # warm compile cache + lazy interp state
            exe.run(main, feed=feed)
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=feed)
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith(
                   ("monitor.py", "profiler.py"))
               and s.size_diff > 0)
    assert grew < n_runs * 16, (
        f"disabled Executor.run allocated {grew}B in telemetry code "
        f"over {n_runs} runs")


# --------------------------------------------------------------------------
# end-to-end: 3-step MNIST train with the full plane on
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_mnist_three_step_phase_breakdown_and_trace(tmp_path):
    from paddle_tpu.models import mnist as mnist_model

    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = mnist_model.get_model(use_conv=False)
        fluid.optimizer.SGD(0.1).minimize(model["loss"])
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

    # acceptance: each phase > 0 and the sum within 20% of wall_ms
    for rec in monitor.recent_steps():
        monitor.validate_step_record(rec)
        phases = rec["phases"]
        assert all(phases[p] > 0 for p in monitor.STEP_PHASES)
        assert sum(phases.values()) <= rec["wall_ms"]
        assert sum(phases.values()) >= 0.8 * rec["wall_ms"], (
            phases, rec["wall_ms"])
        # verdicts only on committed cache-hit steps (sampled contract)
        if rec["cache"] == "hit":
            assert rec["bound"] in monitor.BOUND_VERDICTS
        else:
            assert "bound" not in rec

    # acceptance: the exported trace loads, with span + phase + compile
    # events on three distinct tracks
    doc = json.load(open(monitor.export_trace()))
    _assert_chrome_schema(doc["traceEvents"])
    tids = {}
    for cat in ("span", "phase", "compile"):
        evs = [e for e in doc["traceEvents"] if e.get("cat") == cat]
        assert evs, f"no '{cat}' events in the exported trace"
        tids[cat] = {e["tid"] for e in evs}
    assert len({next(iter(v)) for v in tids.values()}) == 3
    phase_names = {e["name"] for e in doc["traceEvents"]
                   if e.get("cat") == "phase"}
    assert phase_names == set(monitor.STEP_PHASES)


# --------------------------------------------------------------------------
# dynamic request tracks (serving request plane)
# --------------------------------------------------------------------------

def test_dynamic_request_tracks_schema_and_metadata(tmp_path):
    """Per-request timeline tracks: trace_event's tid override lands
    events on a dynamic track (>= REQUEST_TRACK_BASE), the registered
    label is exported as thread_name metadata, and the snapshot still
    conforms to the Chrome schema."""
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    base = monitor.REQUEST_TRACK_BASE
    monitor.trace_register_track(base, "req r1")
    monitor.trace_register_track(base + 1, "req r2")
    monitor.trace_event("a", "request", 1.0, 2.0, tid=base)
    monitor.trace_event("b", "request", 1.5, tid=base + 1)
    monitor.trace_event("c", "request", 2.5, 3.0, tid=base)
    doc = monitor.trace_snapshot()
    _assert_chrome_schema(doc["traceEvents"])
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    assert by_name["a"]["tid"] == base
    assert by_name["b"]["tid"] == base + 1
    metas = {e["tid"]: e["args"]["name"]
             for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert metas[base] == "req r1" and metas[base + 1] == "req r2"
    # re-registering a recycled tid replaces its label
    monitor.trace_register_track(base, "req r9")
    metas = {e["tid"]: e["args"]["name"]
             for e in monitor.trace_snapshot()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert metas[base] == "req r9"


def test_dynamic_track_label_set_is_bounded(tmp_path):
    """Track labels are a bounded set: past _DYN_TRACK_CAP the oldest
    registration ages out (its events keep their tid — only the
    thread_name row is dropped). Inactive tracing registers nothing."""
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    base = monitor.REQUEST_TRACK_BASE
    n = monitor._DYN_TRACK_CAP + 7
    for i in range(n):
        monitor.trace_register_track(base + i, f"req r{i}")
    metas = [e for e in monitor.trace_snapshot()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"
             and e["tid"] >= base]
    assert len(metas) == monitor._DYN_TRACK_CAP
    names = {e["args"]["name"] for e in metas}
    assert "req r0" not in names and f"req r{n - 1}" in names
    # inactive: registration is a no-op, reset clears the labels
    monitor.reset()
    flags.set_flags({"telemetry": False, "trace_dir": ""})
    monitor.trace_register_track(base, "ghost")
    with monitor._TRACE_LOCK:
        assert monitor._DYN_TRACKS == {}
