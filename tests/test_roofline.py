"""Device-time roofline attribution plane (roofline.py): xplane wire
parsing, HLO -> framework op mapping, roofline verdicts, measured MFU,
the executor sampling hooks and their documented degrades."""

import json
import os
import tempfile
import tracemalloc
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor, profiler, roofline
from paddle_tpu import debugger


@pytest.fixture(autouse=True)
def _clean_telemetry():
    _defaults = {
        "telemetry": False, "step_log_path": "", "compile_report_dir": "",
        "metrics_port": 0, "step_phases": True, "step_phases_every_n": 16,
        "device_profile_every_n_steps": 0, "device_profile_top_k": 10,
        "device_profile_xplane": False, "device_peak_flops": 0.0,
        "device_peak_bytes_per_sec": 0.0,
    }
    flags.set_flags(_defaults)
    yield
    monitor.stop_server()
    flags.set_flags(_defaults)


# --------------------------------------------------------------------------
# xplane wire-format synthesis (test-side encoder for the parser)
# --------------------------------------------------------------------------

def _varint(v: int) -> bytes:
    out = b""
    while True:
        b = v & 0x7F
        v >>= 7
        out += bytes([b | (0x80 if v else 0)])
        if not v:
            return out


def _vfield(fnum: int, v: int) -> bytes:
    return _varint(fnum << 3) + _varint(v)


def _lfield(fnum: int, payload: bytes) -> bytes:
    return _varint((fnum << 3) | 2) + _varint(len(payload)) + payload


def make_xspace(planes) -> bytes:
    """Encode an XSpace: ``planes`` = [(plane_name, lines)] where
    ``lines`` is either [(op, dur_ps, count), ...] (one 'XLA Ops'
    line) or {line_name: [(op, dur_ps, count), ...]} (the multi-line
    TPU plane shape); one metadata entry per distinct op per plane."""
    out = b""
    for plane_name, lines in planes:
        if not isinstance(lines, dict):
            lines = {"XLA Ops": lines}
        meta = b""
        line_bufs = b""
        mid = 0
        for line_name, events in lines.items():
            evs = b""
            for name, dur_ps, count in events:
                mid += 1
                em = _vfield(1, mid) + _lfield(2, name.encode())
                meta += _lfield(4, _vfield(1, mid) + _lfield(2, em))
                for _ in range(count):
                    evs += _lfield(4, _vfield(1, mid)
                                   + _vfield(3, dur_ps))
            line_bufs += _lfield(
                3, _lfield(2, line_name.encode()) + evs)
        out += _lfield(
            1, _lfield(2, plane_name.encode()) + meta + line_bufs)
    return out


def _write_capture(tmp_path, planes, name="host.xplane.pb"):
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    (d / name).write_bytes(make_xspace(planes))
    return str(tmp_path)


PS = int(1e12)  # picoseconds per second


# --------------------------------------------------------------------------
# parsing
# --------------------------------------------------------------------------

def test_parse_xplane_roundtrip_aggregates_device_planes(tmp_path):
    path = _write_capture(tmp_path, [
        ("/host:CPU", [("$python host.py", 5 * PS, 3)]),  # ignored
        ("/device:TPU:0", [("fusion.1", PS // 2, 2),
                           ("dot.7", PS // 4, 1)]),
        ("/device:TPU:1", [("dot.7", PS // 4, 1)]),
    ])
    ops = roofline.parse_xplane(path)
    assert ops is not None
    assert ops["fusion.1"] == {"seconds": pytest.approx(1.0),
                               "count": 2}
    # summed across device planes; the host plane contributed nothing
    assert ops["dot.7"] == {"seconds": pytest.approx(0.5), "count": 2}
    assert set(ops) == {"fusion.1", "dot.7"}


def test_multi_device_capture_device_seconds_is_max_plane(tmp_path):
    """Concurrent device planes overlap in wall time: the profile's
    device_seconds is the MAX per-plane total (not the 8x-inflated
    sum that would deflate measured MFU), while per-op seconds and
    shares aggregate work across every plane."""
    flags.set_flags({"device_peak_flops": 1e12,
                     "device_peak_bytes_per_sec": 1e10})
    path = _write_capture(tmp_path, [
        ("/device:TPU:0", [("dot.1", PS, 1)]),          # 1.0 s
        ("/device:TPU:1", [("dot.1", PS // 2, 1),       # 1.0 s total
                           ("all-reduce-start.2", PS // 2, 1)]),
    ])
    prof = roofline.profile_from_xplane(
        path, fluid.Program(),
        compile_report=_report(8e11, 8e8), record=False)
    assert prof["device_seconds"] == pytest.approx(1.0)  # NOT 2.0
    # measured MFU against the wall interval: 8e11 / 1.0 / 1e12
    assert prof["measured_mfu"] == pytest.approx(0.8)
    # per-op work still aggregates across planes, shares sum to 1
    by_name = {o["name"]: o for o in prof["top_ops"]}
    assert by_name["dot.1"]["seconds"] == pytest.approx(1.5)
    assert by_name["dot.1"]["share"] == pytest.approx(0.75)
    assert sum(o["share"] for o in prof["top_ops"]) == pytest.approx(1.0)
    # async collective pairs land in the collective group
    assert prof["groups"]["collective"]["seconds"] == pytest.approx(0.5)


def test_parse_xplane_multi_line_tpu_plane_counts_ops_line_only(
        tmp_path):
    """A real TPU device plane carries 'XLA Modules' / 'XLA Ops' /
    'Steps' lines covering the SAME wall interval — aggregation must
    use only the op-level line, not sum every granularity."""
    path = _write_capture(tmp_path, [
        ("/device:TPU:0", {
            "XLA Modules": [("jit_step_fn", 2 * PS, 1)],
            "XLA Ops": [("dot.7", PS, 1), ("copy.2", PS, 1)],
            "Steps": [("step 0", 2 * PS, 1)],
        }),
    ])
    ops = roofline.parse_xplane(path)
    assert set(ops) == {"dot.7", "copy.2"}
    total = sum(c["seconds"] for c in ops.values())
    assert total == pytest.approx(2.0)  # NOT 6.0 (triple-counted)
    # a plane with no op-level line (GPU stream rows) still aggregates
    # its non-excluded lines
    path2 = _write_capture(tmp_path / "gpu", [
        ("/device:GPU:0", {
            "Stream #14(Compute)": [("kernel_a", PS, 2)],
            "XLA Modules": [("jit_step_fn", 2 * PS, 1)],
        }),
    ])
    ops2 = roofline.parse_xplane(path2)
    assert set(ops2) == {"kernel_a"}
    assert ops2["kernel_a"]["count"] == 2


def test_parse_xplane_empty_dir_degrades_with_one_warning(tmp_path):
    with pytest.warns(RuntimeWarning, match="no .xplane.pb") as rec:
        assert roofline.parse_xplane(str(tmp_path)) is None
    assert len(rec) == 1


def test_parse_xplane_corrupt_file_degrades_with_one_warning(tmp_path):
    _write_capture(tmp_path, [("/device:TPU:0", [("dot.1", PS, 1)])])
    # truncate mid-message: the wire reader must degrade, not crash
    f = next(p for p in (tmp_path / "plugins" / "profile"
                         / "run1").iterdir())
    f.write_bytes(f.read_bytes()[:-5])
    with pytest.warns(RuntimeWarning, match="parse") as rec:
        assert roofline.parse_xplane(str(tmp_path)) is None
    assert len(rec) == 1


def test_parse_xplane_host_only_capture_degrades_with_one_warning(
        tmp_path):
    """The no-TPU container case: a real capture exists but has only
    host planes — unavailable, one warning."""
    path = _write_capture(tmp_path, [
        ("/host:CPU", [("$python host.py", PS, 1)])])
    with pytest.warns(RuntimeWarning, match="no /device") as rec:
        assert roofline.parse_xplane(path) is None
    assert len(rec) == 1


def test_parse_xplane_warn_false_is_silent(tmp_path):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert roofline.parse_xplane(str(tmp_path), warn=False) is None


def test_profiler_xplane_capture_on_cpu_degrades_to_estimate():
    """profiler.profiler(with_xplane=True) on the CPU container: the
    capture itself succeeds (jax's profiler runs everywhere) but holds
    no /device:* plane, so the profile degrades to source="estimate"
    with one warning — the documented no-TPU degrade, end to end."""
    import jax
    import jax.numpy as jnp

    prog = fluid.Program()
    with tempfile.TemporaryDirectory() as d:
        with profiler.profiler(profile_path=os.path.join(d, "prof"),
                               with_xplane=True):
            jnp.ones((64, 64)).sum().block_until_ready()
        cap_dir = profiler.last_xplane_dir()
        assert cap_dir == os.path.join(d, "prof") + "_xplane"
        with pytest.warns(RuntimeWarning) as rec:
            prof = roofline.profile_from_xplane(
                cap_dir, prog, device_seconds=0.5, record=False)
        assert len(rec) == 1
    assert prof["source"] == "estimate"
    assert prof["device_seconds"] == 0.5
    roofline.validate_device_profile(prof)
    del jax


# --------------------------------------------------------------------------
# classification + framework mapping
# --------------------------------------------------------------------------

def test_classify_hlo():
    assert roofline.classify_hlo("%dot.5") == "matmul"
    assert roofline.classify_hlo("convolution.12") == "matmul"
    assert roofline.classify_hlo("fusion.130") == "fusion"
    assert roofline.classify_hlo("add.3") == "elementwise"
    assert roofline.classify_hlo("reduce.9") == "reduction"
    assert roofline.classify_hlo("copy.2") == "data_movement"
    assert roofline.classify_hlo("all-reduce.1") == "collective"
    assert roofline.classify_hlo("infeed") == "overhead"
    assert roofline.classify_hlo("frobnicate.77") == "other"
    # async pairs (modern XLA's default collective lowering) fall back
    # to the root opcode's group...
    assert roofline.classify_hlo("all-reduce-start.3") == "collective"
    assert roofline.classify_hlo("all-reduce-done.3") == "collective"
    assert roofline.classify_hlo("collective-permute-start.1") == (
        "collective")
    assert roofline.classify_hlo("all-gather-done.8") == "collective"
    # ...unless registered explicitly (copy-start/done are the async
    # HBM<->host transfers, overhead by design)
    assert roofline.classify_hlo("copy-start.2") == "overhead"
    assert roofline.classify_hlo("copy-done.2") == "overhead"


def test_map_to_framework_ops_uses_program_histogram():
    hist = {"mul": 2, "elementwise_add": 2, "relu": 1, "mean": 1}
    assert roofline.map_to_framework_ops("dot.4", hist) == ["mul"]
    assert roofline.map_to_framework_ops("add.1", hist) == [
        "elementwise_add", "relu"]
    # no candidate of the group in the program -> empty shortlist
    assert roofline.map_to_framework_ops("all-reduce.2", hist) == []
    assert roofline.map_to_framework_ops("dot.4", None) == []


# --------------------------------------------------------------------------
# profile schema + verdicts
# --------------------------------------------------------------------------

def _report(flops, bytes_accessed, hist=None, window_steps=None):
    rep = {"flops": flops, "bytes_accessed": bytes_accessed,
           "op_histogram": hist or {"mul": 1}}
    if window_steps is not None:
        rep["window_steps"] = window_steps
    return rep


def test_profile_schema_roundtrip_and_validation():
    prog = fluid.Program()
    prof = roofline.build_device_profile(
        prog, source="estimate", device_seconds=0.1, steps=2,
        compile_report=_report(1e9, 1e7), backend="cpu")
    roofline.validate_device_profile(prof)
    # JSON round-trip survives validation (the /profile + digest path)
    roofline.validate_device_profile(json.loads(json.dumps(prof)))
    bad = dict(prof)
    bad["source"] = "guess"
    with pytest.raises(ValueError, match="source"):
        roofline.validate_device_profile(bad)
    bad = dict(prof)
    bad["verdict"] = "gpu_bound"
    with pytest.raises(ValueError, match="verdict"):
        roofline.validate_device_profile(bad)
    bad = dict(prof)
    bad["surprise"] = 1
    with pytest.raises(ValueError, match="unknown"):
        roofline.validate_device_profile(bad)
    bad = dict(prof)
    del bad["measured_mfu"]
    with pytest.raises(ValueError, match="measured_mfu"):
        roofline.validate_device_profile(bad)


def test_roofline_verdicts_from_synthetic_timings():
    """Fixed peaks (ridge = 100 FLOP/B): intensity and achieved rate
    pick the verdict."""
    flags.set_flags({"device_peak_flops": 1e12,
                     "device_peak_bytes_per_sec": 1e10})
    prog = fluid.Program()

    def verdict(flops, ba, secs):
        p = roofline.build_device_profile(
            prog, source="estimate", device_seconds=secs, steps=1,
            compile_report=_report(flops, ba), backend="cpu")
        roofline.validate_device_profile(p)
        return p

    # intensity 1000 >= ridge 100, achieved 0.8e12 of permitted 1e12
    p = verdict(8e11, 8e8, 1.0)
    assert p["verdict"] == "compute_bound"
    assert p["measured_mfu"] == pytest.approx(0.8)
    assert p["intensity"] == pytest.approx(1000.0)
    assert p["ridge_intensity"] == pytest.approx(100.0)
    # intensity 10 < ridge: memory roof (permitted 1e11; achieved 0.8e11)
    p = verdict(8e10, 8e9, 1.0)
    assert p["verdict"] == "memory_bound"
    # same intensity but 10x slower: under OVERHEAD_FRACTION of the roof
    p = verdict(8e10, 8e9, 10.0)
    assert p["verdict"] == "overhead"
    # no cost numbers at all -> unknown, null mfu
    p = roofline.build_device_profile(
        prog, source="estimate", device_seconds=1.0, steps=1,
        backend="cpu")
    assert p["verdict"] == "unknown" and p["measured_mfu"] is None


def test_profile_from_xplane_top_ops_and_measured_mfu(tmp_path):
    flags.set_flags({"device_peak_flops": 1e12,
                     "device_peak_bytes_per_sec": 1e10,
                     "device_profile_top_k": 2})
    path = _write_capture(tmp_path, [
        ("/device:TPU:0", [("dot.1", PS // 2, 1),      # 0.5 s
                           ("fusion.2", PS // 4, 2),   # 0.5 s
                           ("copy.3", PS // 10, 1)]),  # 0.1 s
    ])
    prog = fluid.Program()
    prof = roofline.profile_from_xplane(
        path, prog, steps=1,
        compile_report=_report(5.5e11, 1e9, hist={"mul": 1}))
    assert prof["source"] == "xplane"
    assert prof["device_seconds"] == pytest.approx(1.1)
    # measured MFU from the PARSED device seconds: 5.5e11/1.1/1e12 = 0.5
    assert prof["measured_mfu"] == pytest.approx(0.5)
    # top-K = 2 trims the copy; ordered by device seconds
    assert [o["name"] for o in prof["top_ops"]] == ["dot.1", "fusion.2"]
    assert prof["top_ops"][0]["share"] == pytest.approx(0.5 / 1.1)
    assert prof["top_ops"][0]["framework_ops"] == ["mul"]
    groups = prof["groups"]
    assert groups["matmul"]["seconds"] == pytest.approx(0.5)
    assert groups["data_movement"]["count"] == 1
    roofline.validate_device_profile(prof)
    # recorded: /profile summary + the top-op gauge
    assert roofline.profiles()[prof["program"]]["source"] == "xplane"
    monitor.enable()
    roofline.record_profile(prof)
    g = monitor.gauge("pt_device_op_seconds")
    assert g.value(labels={"op": "dot.1"}) == pytest.approx(0.5)
    # the gauge mirrors ONE profile: a later profile's cells REPLACE
    # the previous ops (per-compile HLO uids would accrete forever)
    path2 = _write_capture(tmp_path / "second", [
        ("/device:TPU:0", [("dot.9", PS // 5, 1)])])
    roofline.profile_from_xplane(path2, fluid.Program())
    assert g.value(labels={"op": "dot.9"}) == pytest.approx(0.2)
    assert g.value(labels={"op": "dot.1"}) == 0.0  # stale cell gone
    # an untimed (estimate) profile EMPTIES the gauge — a dead
    # capture's op mix must not keep serving next to fresh MFU values
    roofline.estimate_profile(fluid.Program(), device_seconds=0.1)
    assert not g._cells


# --------------------------------------------------------------------------
# executor integration
# --------------------------------------------------------------------------

def _small_program(width=32):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[width], dtype="float32")
        loss = layers.mean(layers.fc(x, width))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def test_executor_samples_estimate_profile_and_instruments(tmp_path):
    flags.set_flags({"telemetry": True, "step_phases_every_n": 1,
                     "device_profile_every_n_steps": 1,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed={"x": np.ones((4, 32), np.float32)},
                    fetch_list=[loss])
    prof = roofline.latest(main)
    assert prof is not None and prof["source"] == "estimate"
    roofline.validate_device_profile(prof)
    # the estimate path joins the compile report's real XLA costs with
    # the executor's measured device phase
    assert prof["flops"] and prof["flops"] > 0
    assert prof["device_seconds"] and prof["device_seconds"] > 0
    assert prof["measured_mfu"] and prof["measured_mfu"] > 0
    assert prof["verdict"] in roofline.ROOFLINE_VERDICTS
    # estimate top_ops mirror the op histogram (no per-op seconds)
    assert prof["top_ops"] and all(o["seconds"] is None
                                   for o in prof["top_ops"])
    assert monitor.gauge("pt_program_mfu").value(
        labels={"program": prof["program"]}) == prof["measured_mfu"]
    assert monitor.counter("pt_device_profiles_total").value(
        labels={"source": "estimate"}) >= 1


def test_executor_window_profile_covers_window_steps(tmp_path):
    flags.set_flags({"telemetry": True, "step_phases_every_n": 1,
                     "device_profile_every_n_steps": 1,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feeds = [{"x": np.ones((4, 32), np.float32)}]
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_steps(main, feed_list=feeds, steps=5, fetch_list=[loss])
        exe.run_steps(main, feed_list=feeds, steps=5, fetch_list=[loss])
    prof = roofline.latest(main)
    assert prof is not None and prof["steps"] == 5
    rep = monitor.compile_reports()[prof["program"]]
    assert rep["window_steps"] == 5
    monitor.validate_compile_report(rep)
    # window report flops cover the whole window; the profile keeps the
    # whole-interval total (flops == report flops for a same-size call)
    if rep["flops"] is not None:
        assert prof["flops"] == pytest.approx(rep["flops"])


def test_executor_xplane_flag_degrades_on_cpu_once(tmp_path):
    """device_profile_xplane on the CPU container: the capture runs but
    has no device plane — every sampled step still profiles via the
    estimate path, and the degrade warns ONCE per process, not once
    per step."""
    flags.set_flags({"telemetry": True, "step_phases_every_n": 1,
                     "device_profile_every_n_steps": 1,
                     "device_profile_xplane": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup, loss = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed={"x": np.ones((4, 32), np.float32)},
                        fetch_list=[loss])
    prof = roofline.latest(main)
    assert prof is not None and prof["source"] == "estimate"
    degrade = [w for w in caught
               if "source=\"estimate\"" in str(w.message)]
    assert len(degrade) == 1, [str(w.message) for w in degrade]


def test_roofline_sampling_counts_phase_sampled_steps_per_program():
    """take_sample fires on every Nth CALL for a given program (the
    executor calls it once per phase-sampled step), so the cadence
    never stretches to lcm(step_phases_every_n,
    device_profile_every_n_steps) the way an absolute-step modulo
    would — and interleaved programs never parity-starve each other
    out of profiles."""
    flags.set_flags({"telemetry": True,
                     "device_profile_every_n_steps": 4})
    assert roofline.active()
    a = fluid.Program()
    fires = [roofline.take_sample(a) for _ in range(9)]
    assert fires == [True, False, False, False,
                     True, False, False, False, True]
    # the starvation trap: two programs strictly alternating with
    # _every=2 — a process-global counter would give one of them every
    # even slot and the other NONE, forever
    flags.set_flags({"device_profile_every_n_steps": 2})
    b, c = fluid.Program(), fluid.Program()
    seen = {b._uid: [], c._uid: []}
    for _ in range(4):
        seen[b._uid].append(roofline.take_sample(b))
        seen[c._uid].append(roofline.take_sample(c))
    assert seen[b._uid] == [True, False, True, False]
    assert seen[c._uid] == [True, False, True, False]
    # disabled: False, and no counter advances
    flags.set_flags({"device_profile_every_n_steps": 0})
    assert not roofline.active() and not roofline.take_sample(a)


# --------------------------------------------------------------------------
# measured vs analytic MFU agreement
# --------------------------------------------------------------------------

def test_measured_mfu_agrees_with_analytic_on_matmul_program(tmp_path):
    """Matmul-dominated forward program: the XLA cost-analysis flops
    behind measured MFU must agree with the hand-derived analytic count
    within the 25% acceptance tolerance (same seconds, same peak, so
    the ratio IS the flops ratio)."""
    import jax

    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path),
                     "device_peak_flops": 1e12,
                     "device_peak_bytes_per_sec": 1e10})
    B, D = 64, 256
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[D], dtype="float32")
        h = layers.fc(layers.fc(layers.fc(x, D), D), D)
        out = layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((B, D), np.float32)}
    import time as _time

    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[out])  # compile + report
        t0 = _time.perf_counter()
        steps = 5
        for _ in range(steps):
            r = exe.run(main, feed=feed, fetch_list=[out],
                        return_numpy=False)
        jax.block_until_ready(r[0])
        secs = _time.perf_counter() - t0
        prof = roofline.estimate_profile(main, device_seconds=secs,
                                         steps=steps)
    analytic_per_step = 3 * 2.0 * B * D * D  # three D x D matmuls
    assert prof["measured_mfu"] is not None
    analytic_mfu = (analytic_per_step * steps / secs) / prof["peak_flops"]
    assert prof["measured_mfu"] == pytest.approx(analytic_mfu, rel=0.25)


# --------------------------------------------------------------------------
# debugger annotation
# --------------------------------------------------------------------------

def test_pprint_program_roofline_header_and_device_column(tmp_path):
    flags.set_flags({"device_peak_flops": 1e12,
                     "device_peak_bytes_per_sec": 1e10})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        layers.mean(layers.fc(x, 4))
    path = _write_capture(tmp_path, [
        ("/device:TPU:0", [("dot.1", PS // 2, 1)])])
    hist = {"mul": 1, "elementwise_add": 1, "mean": 1}
    roofline.profile_from_xplane(
        path, main, compile_report=_report(4e11, 1e9, hist=hist))
    listing = debugger.pprint_program(main)
    assert "device profile (v1, source=xplane" in listing
    assert "top device ops: dot.1=500.00ms" in listing
    # the mul op line carries the per-op device-time column
    mul_line = next(ln for ln in listing.splitlines() if "mul(" in ln)
    assert "[dev ~500.000ms]" in mul_line
    assert "device profile" not in debugger.pprint_program(
        main, with_roofline=False)


# --------------------------------------------------------------------------
# disabled-path allocation proofs
# --------------------------------------------------------------------------

def _alloc_growth(filenames, scope, n_runs, run):
    with fluid.scope_guard(scope):
        for _ in range(3):
            run()
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            run()
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    stats = snap.compare_to(base, "filename")
    return {
        fn: sum(s.size_diff for s in stats
                if s.traceback[0].filename.endswith(fn)
                and s.size_diff > 0)
        for fn in filenames
    }


def test_disabled_plane_zero_alloc_in_monitor_and_roofline():
    """Telemetry fully off: the roofline hooks add nothing to the
    executor hot path — no allocations in roofline.py OR monitor.py."""
    assert not monitor.enabled()
    main, startup, loss = _small_program(width=8)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 8), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
    n = 30
    grew = _alloc_growth(
        ("roofline.py", "monitor.py"), scope, n,
        lambda: exe.run(main, feed=feed, fetch_list=[loss]))
    assert grew["roofline.py"] < n * 16, grew
    assert grew["monitor.py"] < n * 16, grew


def test_roofline_off_zero_alloc_with_telemetry_on():
    """Telemetry + phases on but the roofline plane off (the default
    device_profile_every_n_steps=0): roofline.py allocates nothing."""
    flags.set_flags({"telemetry": True, "step_phases_every_n": 1,
                     "device_profile_every_n_steps": 0})
    main, startup, loss = _small_program(width=8)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 8), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
    n = 30
    grew = _alloc_growth(
        ("roofline.py",), scope, n,
        lambda: exe.run(main, feed=feed, fetch_list=[loss]))
    assert grew["roofline.py"] < n * 16, grew
