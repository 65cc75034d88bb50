"""Live observability endpoint (PR 2 tentpole, piece 2): the stdlib
http.server thread behind monitor.serve — /metrics, /healthz, /steps,
/compile scraped over localhost and matched against the in-process
registry / ring buffer."""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags({"telemetry": False, "step_log_path": "",
                     "metrics_dump_path": "", "compile_report_dir": "",
                     "metrics_port": 0})
    yield
    monitor.stop_server()
    flags.set_flags({"telemetry": False, "step_log_path": "",
                     "metrics_dump_path": "", "compile_report_dir": "",
                     "metrics_port": 0})


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def test_metrics_endpoint_matches_registry():
    monitor.enable()
    monitor.counter("t_srv_c", "scraped counter").inc(3,
                                                      labels={"k": "v"})
    h = monitor.histogram("t_srv_h", "scraped hist", buckets=(0.1, 1.0))
    h.observe(0.05)
    port = monitor.serve(0)  # ephemeral port: parallel-safe
    assert monitor.server_address() == ("127.0.0.1", port)

    status, ctype, body = _get(port, "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    text = body.decode()
    # the scrape IS the exporter output for the live registry
    assert text == monitor.to_prometheus()
    assert 't_srv_c{k="v"} 3.0' in text
    assert 't_srv_h_bucket{le="0.1"} 1' in text
    # builtin instruments are pre-registered, so their TYPE lines appear
    # on a scrape even before first use
    assert "# TYPE pt_stall_total counter" in text
    assert "# TYPE pt_span_seconds histogram" in text


def test_healthz_and_404():
    monitor.enable()
    port = monitor.serve(0)
    status, ctype, body = _get(port, "/healthz")
    assert status == 200 and ctype == "application/json"
    health = json.loads(body)
    assert health["status"] == "ok"
    assert health["telemetry"] is True
    assert health["uptime_s"] >= 0
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(port, "/no/such/route")
    assert ei.value.code == 404


def test_root_serves_json_route_index():
    """`/` (previously a 404) serves a JSON index of every route, and
    the index cannot silently miss one: it IS the handler's table."""
    monitor.enable()
    port = monitor.serve(0)
    status, ctype, body = _get(port, "/")
    assert status == 200 and ctype == "application/json"
    index = json.loads(body)
    assert index == {"routes": monitor.ROUTES}
    # every indexed route actually answers (the index is not aspirational)
    for route in index["routes"]:
        status, _, _ = _get(port, route)
        assert status == 200, route


def test_fleet_route_serves_local_view_single_process():
    """/fleet without a multi-worker fleet: the single-rank local view,
    same shape as the aggregated one."""
    monitor.enable()
    port = monitor.serve(0)
    status, ctype, body = _get(port, "/fleet")
    assert status == 200 and ctype == "application/json"
    view = json.loads(body)
    assert view["world"] == 1 and list(view["ranks"]) == ["0"]
    assert view["ranks"]["0"]["dead"] is False
    assert view["stragglers"] == [] and view["oom_reports"] == []
    # the merged exposition answers too (this rank's samples, rank="0")
    monitor.counter("t_fleet_local_c", "merged-view counter").inc(2)
    status, ctype, body = _get(port, "/metrics?fleet=1")
    assert status == 200 and ctype.startswith("text/plain")
    assert 't_fleet_local_c{rank="0"} 2.0' in body.decode()


def test_lint_endpoint_serves_latest_findings():
    from paddle_tpu import analysis

    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        prog.global_block().append_op(
            "relu", inputs={"X": ["ghost"]}, outputs={"Out": ["o"]})
    analysis.lint(prog)
    monitor.enable()
    port = monitor.serve(0)
    status, ctype, body = _get(port, "/lint")
    assert status == 200 and ctype == "application/json"
    doc = json.loads(body)
    assert doc["mode"] in ("off", "warn", "error")
    rec = doc["reports"][str(prog._uid)]
    assert rec["counts"].get("error", 0) >= 1
    assert any(f["check"] == "dataflow.uninitialized_read"
               for f in rec["findings"])


def test_trace_endpoint_serves_live_timeline():
    """A running server alone makes tracing visible (no trace_dir
    needed): /trace returns loadable Chrome-trace JSON of the ring."""
    monitor.enable()
    port = monitor.serve(0)
    assert monitor.trace_active()  # server IS the visibility sink
    with monitor.span("served.from.ring"):
        pass
    status, ctype, body = _get(port, "/trace")
    assert status == 200 and ctype == "application/json"
    doc = json.loads(body)
    events = doc["traceEvents"]
    assert any(e.get("name") == "served.from.ring" for e in events)
    for e in events:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)


def test_steps_endpoint_serves_ring_buffer():
    """Executor steps land in the bounded ring even with NO step_log_path
    — the /steps route is the zero-config live view."""
    monitor.enable()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            exe.run(main,
                    feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])

    port = monitor.serve(0)
    status, ctype, body = _get(port, "/steps")
    assert status == 200 and ctype == "application/json"
    served = json.loads(body)
    assert served == json.loads(json.dumps(monitor.recent_steps(),
                                           default=str))
    # startup + 3 steps; every record schema-valid with cache accounting
    assert len(served) == 4
    for rec in served:
        monitor.validate_step_record(rec)
    assert [r["cache"] for r in served] == ["miss", "miss", "hit", "hit"]
    # ?n= trims to the newest n
    _, _, body = _get(port, "/steps?n=2")
    assert json.loads(body) == served[-2:]


def test_compile_endpoint_serves_latest_reports(tmp_path):
    flags.set_flags({"telemetry": True,
                     "compile_report_dir": str(tmp_path)})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[y])
    port = monitor.serve(0)
    _, _, body = _get(port, "/compile")
    served = json.loads(body)
    assert set(served) == set(monitor.compile_reports())
    for rep in served.values():
        monitor.validate_compile_report(rep)


def test_server_makes_compile_reports_active_and_stops_cleanly():
    flags.set_flags({"telemetry": True})
    assert not monitor.compile_reports_active()
    port = monitor.serve(0)
    # a live endpoint is a consumer: reports turn on without a dir
    assert monitor.compile_reports_active()
    monitor.stop_server()
    assert monitor.server_address() is None
    assert not monitor.compile_reports_active()
    with pytest.raises(Exception):
        _get(port, "/healthz")


def test_metrics_port_flag_autostarts_server():
    # flag set while telemetry off: nothing listens yet
    flags.set_flags({"metrics_port": 0})
    flags.set_flags({"telemetry": True})
    assert monitor.server_address() is None
    # choosing a real port via flag would race parallel suites, so bind
    # ephemeral first, then verify the watcher path is a no-op re-entry
    port = monitor.serve(0)
    flags.set_flags({"metrics_port": port})  # watcher: server already up
    assert monitor.server_address() == ("127.0.0.1", port)


def test_requests_and_serve_routes_round_trip():
    """/requests serves the live request plane (in-flight table +
    recently-terminated ring + SLO rollup) and /serve the engine
    summary, both matching the in-process views after real traffic."""
    from paddle_tpu import serving, serving_trace
    from paddle_tpu.models import transformer as T

    flags.set_flags({"telemetry": True})
    cfg = T.TransformerConfig(
        src_vocab_size=37, trg_vocab_size=41, max_length=64,
        d_model=16, d_inner=32, n_head=2, n_layer=1,
        dropout=0.0, label_smooth_eps=0.0)
    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        T.build(cfg, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                max_len=10, bos_id=0, end_id=1)
    rng = np.random.RandomState(5)
    reqs = [eng.submit(rng.randint(2, 37, (6,)).astype(np.int64),
                       max_new_tokens=3) for _ in range(3)]
    eng.run_until_idle()
    port = monitor.serve(0)
    status, ctype, body = _get(port, "/requests")
    assert status == 200 and ctype == "application/json"
    served = json.loads(body)
    assert served["v"] == serving_trace.REQUEST_RECORD_SCHEMA_VERSION
    assert served["inflight"] == []
    assert {r["trace_id"] for r in served["recent"]} == {
        q.trace_id for q in reqs}
    for rec in served["recent"]:
        assert rec["outcome"] in ("completed", "length")
        assert set(rec["phases_ms"]) == set(serving_trace.PHASES)
    assert served["slo"] == json.loads(
        json.dumps(serving_trace.slo_summary()))
    # /serve still answers with the aggregate engine summary
    status, ctype, body = _get(port, "/serve")
    assert status == 200 and ctype == "application/json"
    summary = json.loads(body)
    assert any(row["engine_id"] == eng.engine_id
               for row in summary["engines"])
    eng.close()
