"""Fleet observability plane (ISSUE 9 tentpole): cross-rank digest
publish/aggregate, the /fleet cluster view, straggler detection, dead
-worker marking, device-memory watermarks, OOM forensics, and the
zero-alloc disabled-path contract.

In-process tests drive the plane through a stub KV client (the
test_elastic_resize pattern); the multi-process tests spawn 4 real
workers against the native coord service (tests/fleet_obs_worker.py)
WITHOUT jax.distributed — the digest plane needs only the KV/heartbeat
half of the fleet."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import tracemalloc
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, fleet_monitor, flags, layers, monitor
from paddle_tpu.incubate.fleet.fleet_base import Fleet

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    faults.disarm()
    flags.set_flags({"telemetry": False, "step_log_path": "",
                     "stall_dump_dir": "", "fault_plan": "",
                     "device_memory_budget_bytes": 0,
                     "fleet_metrics_interval_ms": 1000,
                     "fleet_straggler_factor": 2.0,
                     "fleet_straggler_min_ms": 20,
                     "device_memory_every_n_steps": 16,
                     "step_phases_every_n": 1})
    yield
    monitor.stop_server()
    faults.disarm()
    flags.set_flags({"telemetry": False, "step_log_path": "",
                     "stall_dump_dir": "", "fault_plan": "",
                     "device_memory_budget_bytes": 0,
                     "fleet_metrics_interval_ms": 1000,
                     "fleet_straggler_factor": 2.0,
                     "fleet_straggler_min_ms": 20,
                     "device_memory_every_n_steps": 16,
                     "step_phases_every_n": 1})


# --------------------------------------------------------------------------
# stub KV plumbing (the test_elastic_resize pattern, + non-blocking get)
# --------------------------------------------------------------------------

class _StubRole:
    def __init__(self, rank, world):
        self._r, self._n = rank, world

    def worker_index(self):
        return self._r

    def worker_num(self):
        return self._n


class _StubClient:
    def __init__(self, store, lock, dead=()):
        self._store, self._lock, self._dead = store, lock, list(dead)

    def put(self, key, value):
        with self._lock:
            self._store[key] = bytes(value)

    def get(self, key, timeout_ms=-1, max_len=0):
        with self._lock:
            if key in self._store:
                return self._store[key]
        raise TimeoutError(key)

    def heartbeat(self, worker_id):
        pass

    def dead_peers(self, max_age_ms):
        return list(self._dead)


def _stub_fleet(rank, world, store, lock, dead=()):
    f = Fleet()
    f._role = _StubRole(rank, world)
    f._client = _StubClient(store, lock, dead)
    f._initialized = True
    return f


def _run_some_steps(n=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(n):
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    return exe


def _digest_for(rank, wall_ms, phases=None, steps=20, ts=None, world=4):
    """Hand-crafted schema-valid digest for detector/aggregation tests."""
    d = fleet_monitor.registry_digest(rank=rank, world=world, gen=0)
    d["step_wall_ms"] = wall_ms
    d["phases_ms"] = phases
    d["steps"] = steps
    if ts is not None:
        d["ts"] = ts
    monitor.validate_fleet_digest(d)
    return d


# --------------------------------------------------------------------------
# digest assembly + schema
# --------------------------------------------------------------------------

def test_registry_digest_schema_and_trailing_medians():
    monitor.enable()
    _run_some_steps(3)
    d = fleet_monitor.registry_digest(rank=2, world=4, gen=1)
    monitor.validate_fleet_digest(d)
    assert d["rank"] == 2 and d["world"] == 4 and d["gen"] == 1
    # counters carry values, histograms only sum/count
    steps_cells = d["counters"]["pt_executor_steps_total"]
    assert steps_cells[0]["value"] == 4.0  # startup + 3
    phase_cells = d["hists"]["pt_step_phase_seconds"]
    assert all(set(c) == {"labels", "sum", "count"} for c in phase_cells)
    # trailing medians + the last step record with phases and verdict
    assert d["step_wall_ms"] > 0
    assert set(d["phases_ms"]) == set(monitor.STEP_PHASES)
    monitor.validate_step_record(d["last_step"])
    assert d["bound"]["verdict"] in monitor.BOUND_VERDICTS
    assert d["steps"] == 4


def test_registry_digest_serving_section_optional_and_validated():
    """The digest's `serving` section (optional field — schema stays
    v1): absent on ranks that never served, a per-replica request-plane
    rollup once the recently-terminated ring has a record, and digests
    WITHOUT the field still validate (backward compatibility with
    pre-serving publishers)."""
    import types

    from paddle_tpu import serving, serving_trace

    monitor.enable()
    assert not list(serving._ENGINES)  # a leaked engine is a test bug
    d = fleet_monitor.registry_digest(rank=0, world=2)
    assert "serving" not in d  # this rank never served
    monitor.validate_fleet_digest(d)

    # one terminal request through the real recording path
    now = time.perf_counter()
    req = types.SimpleNamespace(
        outcome="completed", ttft_s=0.01, tokens=[5, 7], decode_s=0.02,
        fetch_s=0.001, queue_wait_s=0.005, prefill_s=0.004,
        submit_ts=now - 0.05, deadline_ts=None, replays=0, capped=False,
        censored=False, deadline_attr=None, trace_id="r777", id=777,
        engine_id=9, trace_tid=None)
    serving_trace.note_terminal(req)

    d = fleet_monitor.registry_digest(rank=1, world=2)
    monitor.validate_fleet_digest(d)
    sec = d["serving"]
    assert sec["recent"] == 1 and sec["engines"] == {}
    assert set(sec["slo"]) == {"targets_ms", "ttft", "token",
                               "ttft_censored", "burn"}
    assert set(sec["ttft_ms"]) == {"p50", "p95", "p99"}
    # the rollup rides aggregation into the per-rank /fleet rows
    store, lock = {}, threading.Lock()
    store["fleet/metrics/g0/1"] = json.dumps(d).encode()
    f = _stub_fleet(1, 2, store, lock)
    view = fleet_monitor.aggregate(f)
    assert view["ranks"]["1"]["serving"]["recent"] == 1
    # backward compatibility: a digest without the section validates
    del d["serving"]
    monitor.validate_fleet_digest(d)


def test_publish_rides_heartbeat_and_rate_limits():
    monitor.enable()
    store, lock = {}, threading.Lock()
    f = _stub_fleet(1, 2, store, lock)
    flags.set_flags({"fleet_metrics_interval_ms": 0})
    f.heartbeat()
    key = "fleet/metrics/g0/1"
    assert key in store
    first = json.loads(store[key].decode())
    monitor.validate_fleet_digest(first)
    f.heartbeat()
    assert json.loads(store[key].decode())["seq"] == first["seq"] + 1
    # a large interval rate-limits: the next heartbeat publishes nothing
    flags.set_flags({"fleet_metrics_interval_ms": 3_600_000})
    before = store[key]
    f.heartbeat()
    assert store[key] is before
    assert monitor.counter(
        "pt_fleet_digests_published_total").value() == 2


def test_publish_failure_drops_one_digest_never_raises():
    monitor.enable()
    flags.set_flags({"fleet_metrics_interval_ms": 0})

    class _DeadPut(_StubClient):
        def put(self, key, value):
            raise OSError("kv down")

    f = Fleet()
    f._role = _StubRole(0, 2)
    f._client = _DeadPut({}, threading.Lock())
    f._initialized = True
    with pytest.warns(RuntimeWarning, match="digest publish failed"):
        f.heartbeat()  # must not raise
    assert monitor.counter(
        "pt_fleet_digest_publish_drops_total").value() == 1


# --------------------------------------------------------------------------
# aggregation: cluster view, staleness, stragglers
# --------------------------------------------------------------------------

def test_aggregate_shows_all_ranks_and_merged_prometheus():
    monitor.enable()
    _run_some_steps(2)
    store, lock = {}, threading.Lock()
    flags.set_flags({"fleet_metrics_interval_ms": 0})
    for r in range(3):
        _stub_fleet(r, 3, store, lock).heartbeat()
    f0 = _stub_fleet(0, 3, store, lock)
    view = fleet_monitor.aggregate(f0)
    assert set(view["ranks"]) == {"0", "1", "2"}
    assert view["missing"] == [] and view["dead"] == []
    for row in view["ranks"].values():
        assert row["age_ms"] >= 0 and row["dead"] is False
        assert row["last_step"] is not None
    # merged exposition: every rank's samples, rank-labelled
    text = fleet_monitor.to_prometheus_fleet(view)
    for r in range(3):
        assert f'pt_executor_steps_total{{rank="{r}"}}' in text
    assert 'pt_step_phase_seconds_sum{phase="device",rank="0"}' in text
    # a metric's OWN rank label must survive as exported_rank, not be
    # clobbered into naming the publisher: rank 0's registry carries a
    # straggler detection naming rank 2
    monitor.counter("pt_fleet_straggler_total").inc(labels={"rank": 2})
    _stub_fleet(0, 3, store, lock).heartbeat()  # republish rank 0
    text = fleet_monitor.to_prometheus_fleet(fleet_monitor.aggregate(f0))
    assert ('pt_fleet_straggler_total{exported_rank="2",rank="0"} 1'
            in text)
    assert 'pt_fleet_straggler_total{rank="2"}' not in text


def test_aggregate_marks_stale_rank_dead_not_stale_rows():
    monitor.enable()
    store, lock = {}, threading.Lock()
    now = time.time()
    phases = {"feed": 1.0, "dispatch": 2.0, "device": 1.0, "fetch": 0.5}
    store["fleet/metrics/g0/0"] = json.dumps(
        _digest_for(0, 5.0, phases, ts=now)).encode()
    store["fleet/metrics/g0/1"] = json.dumps(
        _digest_for(1, 5.0, phases, ts=now - 60.0)).encode()  # stale
    f0 = _stub_fleet(0, 3, store, lock)  # rank 2 never published
    view = fleet_monitor.aggregate(f0, max_age_ms=2_000)
    assert view["dead"] == [1]
    assert view["ranks"]["1"]["dead"] is True
    assert view["missing"] == [2]
    # a dead rank must not feed the skew detector either
    assert view["stragglers"] == []


def test_staleness_uses_observed_publish_age_not_publisher_clock():
    """A publisher with a skewed-behind wall clock (broken NTP) must
    not flap dead: once the aggregator OBSERVES a fresh publish (seq
    advanced), age is measured on the aggregator's own clock. A frozen
    seq keeps aging and still goes dead."""
    monitor.enable()
    store, lock = {}, threading.Lock()
    skewed = _digest_for(0, 5.0, None, ts=time.time() - 60.0, world=2)
    store["fleet/metrics/g0/0"] = json.dumps(skewed).encode()
    f0 = _stub_fleet(0, 2, store, lock)
    # first sight: only the self-reported ts exists -> dead
    view = fleet_monitor.aggregate(f0, max_age_ms=2_000)
    assert view["dead"] == [0]
    # re-aggregation with seq unchanged: the observation anchor was
    # BACKDATED by the first-sight age, so the stale digest keeps
    # aging instead of resurrecting as "just seen"
    view = fleet_monitor.aggregate(f0, max_age_ms=2_000)
    assert view["dead"] == [0]
    assert view["ranks"]["0"]["age_ms"] >= 59_000
    # a NEW publish lands (seq advances), ts still 60s behind: the
    # observed publish is what counts -> alive
    skewed2 = dict(skewed, seq=skewed["seq"] + 1, ts=time.time() - 60.0)
    store["fleet/metrics/g0/0"] = json.dumps(skewed2).encode()
    view = fleet_monitor.aggregate(f0, max_age_ms=2_000)
    assert view["dead"] == [] and view["ranks"]["0"]["age_ms"] == 0.0
    # seq frozen: age grows on the aggregator's clock -> dead again
    time.sleep(0.05)
    view = fleet_monitor.aggregate(f0, max_age_ms=40)
    assert view["dead"] == [0]
    assert view["ranks"]["0"]["age_ms"] >= 50


def test_straggler_detector_names_rank_and_inflated_phase():
    monitor.enable()
    store, lock = {}, threading.Lock()
    base = {"feed": 1.0, "dispatch": 2.0, "device": 1.5, "fetch": 0.5}
    slow = {"feed": 1.0, "dispatch": 82.0, "device": 1.5, "fetch": 0.5}
    for r in range(4):
        store[f"fleet/metrics/g0/{r}"] = json.dumps(_digest_for(
            r, 85.0 if r == 2 else 5.0, slow if r == 2 else base,
            steps=12)).encode()
    f0 = _stub_fleet(0, 4, store, lock)
    with pytest.warns(RuntimeWarning, match="straggler: rank 2"):
        view = fleet_monitor.aggregate(f0)
    (rec,) = view["stragglers"]
    assert rec["v"] == monitor.STRAGGLER_RECORD_SCHEMA_VERSION
    assert rec["rank"] == 2
    assert rec["phase"] == "dispatch"
    assert rec["steps"] == 12  # detection latency is step-bounded
    assert rec["factor"] > 2.0
    assert monitor.counter("pt_fleet_straggler_total").value(
        labels={"rank": 2}) == 1
    # re-detection of the SAME (rank, phase) streak (every /fleet
    # scrape re-aggregates): the live view still names it, but the
    # counter/buffer/warning tick once per streak — their rate must not
    # be a function of whoever is polling
    view2 = fleet_monitor.aggregate(f0)
    assert view2["stragglers"][0]["rank"] == 2
    assert monitor.counter("pt_fleet_straggler_total").value(
        labels={"rank": 2}) == 1
    assert len(fleet_monitor.straggler_records()) == 1
    # the stall watchdog's flight-recorder section carries them
    s = fleet_monitor.summary()
    assert s["stragglers"][-1]["rank"] == 2
    assert set(s["view"]["ranks"]) == {"0", "1", "2", "3"}


def test_straggler_floor_suppresses_subms_jitter():
    """3x skew on a sub-ms step is noise, not a straggler: the
    fleet_straggler_min_ms floor gates it."""
    monitor.enable()
    store, lock = {}, threading.Lock()
    for r, wall in enumerate((0.4, 0.4, 1.4)):
        store[f"fleet/metrics/g0/{r}"] = json.dumps(
            _digest_for(r, wall, None, world=3)).encode()
    view = fleet_monitor.aggregate(_stub_fleet(0, 3, store, lock))
    assert view["stragglers"] == []  # 3.5x median but only +1 ms


def test_local_view_without_fleet():
    """/fleet answers the same shape for single-process jobs."""
    monitor.enable()
    _run_some_steps(1)
    view = fleet_monitor.cluster_view()
    assert view["world"] == 1 and list(view["ranks"]) == ["0"]
    assert view["ranks"]["0"]["dead"] is False


# --------------------------------------------------------------------------
# device-memory watermarks + OOM forensics
# --------------------------------------------------------------------------

def test_device_memory_degrades_silently_on_cpu():
    """CPU devices expose no memory_stats(): sampling must neither
    raise nor invent gauge cells."""
    monitor.enable()
    monitor.sample_device_memory(0)
    assert monitor.gauge("pt_device_bytes_in_use")._cells == {}
    assert monitor.gauge("pt_device_bytes_peak")._cells == {}


def test_device_memory_gauges_with_stats_api(monkeypatch):
    monitor.enable()

    class _Dev:
        def __str__(self):
            return "TPU_0"

        def memory_stats(self):
            return {"bytes_in_use": 1234, "peak_bytes_in_use": 9999}

    import jax

    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev()])
    monitor.sample_device_memory(0)
    assert monitor.gauge("pt_device_bytes_in_use").value(
        labels={"device": "TPU_0"}) == 1234
    assert monitor.gauge("pt_device_bytes_peak").value(
        labels={"device": "TPU_0"}) == 9999
    # sampling period honored (the trace_step_sampled convention)
    flags.set_flags({"device_memory_every_n_steps": 8})
    calls = []

    def _counting_devices():
        calls.append(1)
        return [_Dev()]

    monkeypatch.setattr(jax, "local_devices", _counting_devices)
    monitor.sample_device_memory(3)  # 3 % 8 != 0: no device read
    monitor.sample_device_memory(5, steps=2)  # window [5,7): no sample
    assert calls == []
    monitor.sample_device_memory(6, steps=3)  # window [6,9) spans 8
    monitor.sample_device_memory(8)  # a sample point itself
    assert len(calls) == 2


def test_oom_forensics_report_on_injected_resource_exhausted(tmp_path):
    flags.set_flags({"telemetry": True, "stall_dump_dir": str(tmp_path),
                     "device_memory_budget_bytes": 7777})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        faults.arm("executor.step:raise(RESOURCE_EXHAUSTED: fake OOM)@1")
        with pytest.raises(faults.InjectedFault), \
                pytest.warns(RuntimeWarning, match="device OOM during run"):
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    (rec,) = monitor.oom_records()
    monitor.validate_oom_report(rec)
    assert rec["phase"] == "run"
    assert rec["budget_bytes"] == 7777
    assert "RESOURCE_EXHAUSTED" in rec["error"]
    assert rec["last_steps"]  # the startup step at least
    dumps = [p for p in os.listdir(tmp_path) if p.startswith("oom-")]
    assert len(dumps) == 1
    on_disk = json.load(open(tmp_path / dumps[0]))
    monitor.validate_oom_report(on_disk)
    # /fleet surfaces the forensics reports
    view = fleet_monitor.cluster_view()
    assert view["oom_reports"][0]["phase"] == "run"


def test_oom_forensics_with_step_phases_off(monkeypatch):
    """With step_phases off there is no pre-commit block_until_ready:
    an async-dispatched device OOM surfaces inside _commit's transfer
    and must still produce a forensics record (the bench metrics-only
    config is exactly telemetry on + phases off)."""
    flags.set_flags({"telemetry": True, "step_phases": False})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)

        def _boom(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: deferred device OOM")

        monkeypatch.setattr(exe, "_commit", _boom)
        with pytest.raises(RuntimeError), \
                pytest.warns(RuntimeWarning, match="device OOM during run"):
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    (rec,) = monitor.oom_records()
    assert rec["phase"] == "run"
    flags.set_flags({"step_phases": True})


def test_oom_forensics_compile_phase_and_non_oom_ignored():
    monitor.enable()
    monitor.maybe_record_oom(RuntimeError("some other crash"))
    assert monitor.oom_records() == []
    monitor.maybe_record_oom(
        RuntimeError("RESOURCE_EXHAUSTED: 2GB on device"), phase="compile")
    (rec,) = monitor.oom_records()
    assert rec["phase"] == "compile" and rec["program"] is None
    assert monitor.counter("pt_oom_events_total").value(
        labels={"phase": "compile"}) == 1


# --------------------------------------------------------------------------
# disabled-path contract: tracemalloc-proven zero-alloc
# --------------------------------------------------------------------------

def _grew_in(snap, base, filename):
    stats = snap.compare_to(base, "filename")
    return sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith(filename)
               and s.size_diff > 0)


def test_disabled_path_zero_alloc_telemetry_off():
    """Telemetry off: the executor hot loop (now incl. the faults site,
    device-memory gate and OOM hook) plus the heartbeat publish gate
    must allocate nothing in monitor.py or fleet_monitor.py."""
    assert not monitor.enabled()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 4), np.float32)}
    store, lock = {}, threading.Lock()
    f = _stub_fleet(0, 2, store, lock)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[y])
            f.heartbeat()
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=feed, fetch_list=[y])
            f.heartbeat()
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    for fname in ("monitor.py", "fleet_monitor.py", "faults.py"):
        grew = _grew_in(snap, base, fname)
        assert grew < n_runs * 16, (
            f"disabled hot loop allocated {grew}B in {fname} over "
            f"{n_runs} runs")
    assert store == {}  # nothing published with telemetry off


def test_disabled_path_zero_alloc_single_worker_telemetry_on():
    """Telemetry ON but single-worker (no client): the fleet plane must
    stay out of the hot loop entirely."""
    monitor.enable()
    f = Fleet()  # no client
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[y])
            f.heartbeat()
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=feed, fetch_list=[y])
            f.heartbeat()
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    grew = _grew_in(snap, base, "fleet_monitor.py")
    assert grew < n_runs * 16, (
        f"single-worker hot loop allocated {grew}B in fleet_monitor.py")


# --------------------------------------------------------------------------
# the multi-process drills (ISSUE 9 acceptance)
# --------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_fleet(n, extra_env_per_rank, steps=30):
    port = _free_port()
    env_base = {
        **os.environ,
        "PT_TRAINERS": str(n),
        "PT_COORD_ENDPOINT": f"127.0.0.1:{port}",
        "PT_OBS_STEPS": str(steps),
        "JAX_PLATFORMS": "",
        "PT_FLAGS_telemetry": "1",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]),
    }
    procs = []
    for rank in range(n):
        env = {**env_base, "PT_TRAINER_ID": str(rank),
               **extra_env_per_rank.get(rank, {})}
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "fleet_obs_worker.py")],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    return procs


def _read_port(proc, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("OBS_PORT "):
            return int(line.split()[1])
    raise AssertionError("rank 0 never printed OBS_PORT")


def _scrape(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.read()


def _finish(procs, timeout=60):
    # signal every worker FIRST: reaping rank 0 (the coord server)
    # before a slow peer finished its steps would otherwise yank the
    # server out from under it
    for p in procs:
        try:
            p.stdin.write("exit\n")
            p.stdin.flush()
        except OSError:
            pass  # already dead (the dead-worker drill's victim)
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
        outs.append((p.returncode, out, err))
    return outs


def _poll_fleet(port, predicate, timeout=60, interval=0.2):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            last = json.loads(_scrape(port, "/fleet"))
            if predicate(last):
                return last
        except Exception:
            pass
        time.sleep(interval)
    raise AssertionError(f"/fleet never satisfied predicate; last: "
                         f"{json.dumps(last)[:2000] if last else None}")


def test_four_worker_fleet_view_and_straggler_drill():
    """4 workers publish digests; rank 0's /fleet shows every rank with
    a phase breakdown; a seeded faults.py delay on rank 2 is detected
    and attributed (rank 2, dispatch phase) within 16 steps."""
    from paddle_tpu import native

    if not native.available():
        pytest.skip("native library not built")
    procs = _spawn_fleet(4, {
        2: {"PT_FLAGS_fault_plan": "executor.step:delay(0.08)@p1.0",
            "PT_FLAGS_fault_seed": "7"},
    }, steps=30)
    try:
        port = _read_port(procs[0])

        def _all_ranks_with_phases(view):
            if set(view["ranks"]) != {"0", "1", "2", "3"}:
                return False
            return all(isinstance(row.get("phases_ms"), dict)
                       for row in view["ranks"].values())

        view = _poll_fleet(port, _all_ranks_with_phases)
        for row in view["ranks"].values():
            assert set(row["phases_ms"]) == set(monitor.STEP_PHASES)
            assert row["dead"] is False

        view = _poll_fleet(
            port, lambda v: any(r["rank"] == 2 for r in v["stragglers"]))
        rec = next(r for r in view["stragglers"] if r["rank"] == 2)
        assert rec["phase"] == "dispatch"  # the delay lands there
        assert rec["factor"] > 2.0

        # merged Prometheus exposition carries every rank
        text = _scrape(port, "/metrics?fleet=1").decode()
        for r in range(4):
            assert f'pt_executor_steps_total{{rank="{r}"}}' in text
        # the JSON index (satellite): the new routes are discoverable
        index = json.loads(_scrape(port, "/"))
        assert "/fleet" in index["routes"]
    finally:
        outs = _finish(procs)
    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"rank {rank} failed:\n{out}\n{err}"
    # rank 0's final aggregate round-trips the digest schema
    line = [l for l in outs[0][1].splitlines()
            if l.startswith("OBS_RESULT ")][-1]
    result = json.loads(line[len("OBS_RESULT "):])
    for r, row in result["view"]["ranks"].items():
        digest = {k: v for k, v in row.items()
                  if k not in ("age_ms", "dead")}
        monitor.validate_fleet_digest(digest)
    # detection latency bound (acceptance): rank 0 aggregates every
    # step, and the FIRST record naming rank 2 must land within 16 of
    # rank 2's steps — the drill delays it from its very first step
    first = next(r for r in result["stragglers"] if r["rank"] == 2)
    assert 0 < first["steps"] <= 16
    assert first["phase"] == "dispatch"


def test_dead_worker_marked_by_heartbeat_age():
    """Rank 3 dies abruptly mid-run: /fleet marks it dead via digest/
    heartbeat age instead of serving its stale row as live, while the
    survivors stay alive."""
    from paddle_tpu import native

    if not native.available():
        pytest.skip("native library not built")
    procs = _spawn_fleet(4, {
        3: {"PT_OBS_DIE_RANK": "3", "PT_OBS_DIE_STEP": "5"},
    }, steps=40)
    try:
        port = _read_port(procs[0])
        view = _poll_fleet(
            port,
            lambda v: 3 in v.get("dead", []) and all(
                r in v.get("ranks", {}) and not v["ranks"][r]["dead"]
                for r in ("0", "1", "2")),
            timeout=90)
        assert view["ranks"]["3"]["dead"] is True
        assert view["ranks"]["3"]["age_ms"] > 0
        # survivors serve fresh rows
        for r in ("0", "1", "2"):
            assert view["ranks"][r]["dead"] is False
        # the dead rank is never named a straggler for being silent
        assert all(rec["rank"] != 3 for rec in view["stragglers"])
    finally:
        outs = _finish(procs)
    for rank in (0, 1, 2):
        rc, out, err = outs[rank]
        assert rc == 0, f"rank {rank} failed:\n{out}\n{err}"
