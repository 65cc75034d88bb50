"""Telemetry plane (paddle_tpu/monitor.py): registry semantics, exporter
round-trips, disabled-path overhead, span unification, step-log schema,
label-cardinality cap, quantile summaries, the step ring buffer, the
profiler's no-native degrade path, metric doc coverage, and the flags
plane's self-documentation contract."""

import functools
import json
import os
import re
import tracemalloc

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor, profiler


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags({"telemetry": False, "step_log_path": "",
                     "metrics_dump_path": ""})
    yield
    flags.set_flags({"telemetry": False, "step_log_path": "",
                     "metrics_dump_path": ""})


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    monitor.enable()
    c = monitor.counter("t_c", "a counter")
    c.inc()
    c.inc(2, labels={"k": "a"})
    c.inc(3, labels={"k": "a"})
    assert c.value() == 1
    assert c.value(labels={"k": "a"}) == 5

    g = monitor.gauge("t_g", "a gauge")
    g.set(7.5)
    g.add(0.5)
    assert g.value() == 8.0

    h = monitor.histogram("t_h", "a histogram", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(2.55)


def test_same_name_returns_same_instrument_and_kind_conflict_raises():
    c1 = monitor.counter("t_dup", "doc")
    assert monitor.counter("t_dup") is c1
    with pytest.raises(TypeError):
        monitor.gauge("t_dup")


def test_histogram_bucket_conflict_raises():
    h = monitor.histogram("t_hb", "h", buckets=(1.0, 2.0))
    assert monitor.histogram("t_hb", buckets=(2.0, 1.0)) is h  # same set
    with pytest.raises(ValueError, match="buckets"):
        monitor.histogram("t_hb", buckets=(5.0,))


def test_disabled_calls_are_inert_and_allocation_free():
    """With telemetry off (the default), instrument calls must return
    after the flag check: no label cells materialize and no allocations
    are attributable to monitor.py — the hot-path contract that lets the
    executor stay permanently instrumented."""
    assert not monitor.enabled()
    c = monitor.counter("t_off_c", "off")
    g = monitor.gauge("t_off_g", "off")
    h = monitor.histogram("t_off_h", "off")
    # warm up (first calls may touch lazy interpreter state)
    c.inc()
    g.set(1)
    h.observe(1)

    n_calls = 5 * 1000
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for _ in range(1000):
        c.inc()
        c.inc(2)
        g.set(3)
        g.add(1)
        h.observe(0.5)
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()

    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith("monitor.py")
               and s.size_diff > 0)
    # any real per-call allocation would show as >= n_calls * 16 bytes;
    # allow constant interpreter noise (~hundreds of bytes), not growth
    assert grew < n_calls, f"disabled path allocated {grew}B/{n_calls} calls"
    assert c.value() == 0 and g.value() == 0 and h.count() == 0
    assert not c._cells and not g._cells and not h._cells


def test_gauge_replace_swaps_cells_and_honors_label_cap():
    """Gauge.replace (serving.py's per-engine states, fleet_serving.py's
    replicas by state: a bounded map mirrored wholesale): the swap is
    total — no stale cells survive — and the MAX_LABEL_SETS cap applies
    exactly like every other mutator: first-listed values win, drops
    warn once and count into pt_metric_label_overflow_total."""
    monitor.enable()
    g = monitor.gauge("t_repl_g", "replaced gauge")
    g.set(1.0, labels={"op": "stale"})
    g.replace([({"op": "a"}, 2.0), ({"op": "b"}, 3.0)])
    assert g.value(labels={"op": "a"}) == 2.0
    assert g.value(labels={"op": "stale"}) == 0.0  # swap is total
    assert len(g._cells) == 2
    with pytest.warns(RuntimeWarning, match="label-sets"):
        g.replace([({"i": i}, float(i))
                   for i in range(monitor.MAX_LABEL_SETS + 7)])
    assert len(g._cells) == monitor.MAX_LABEL_SETS
    # rank order: the first N values win, the tail is dropped
    assert g.value(labels={"i": 1}) == 1.0
    assert g.value(labels={"i": monitor.MAX_LABEL_SETS + 1}) == 0.0
    assert monitor.counter("pt_metric_label_overflow_total").value(
        labels={"metric": "t_repl_g"}) == 7
    # disabled: replace is a no-op like every mutator
    monitor.disable()
    g.replace([({"op": "z"}, 9.0)])
    assert g.value(labels={"op": "z"}) == 0.0
    monitor.enable()


def test_label_cardinality_cap_collapses_into_overflow_bucket():
    """A mis-labelled hot-path metric (step index in a label) must not
    grow registry memory without bound: past MAX_LABEL_SETS distinct
    label-sets, mutations collapse into one overflow='true' cell, the
    first drop warns, and every drop counts into
    pt_metric_label_overflow_total."""
    monitor.enable()
    c = monitor.counter("t_card_c", "capped counter")
    with pytest.warns(RuntimeWarning, match="label-sets"):
        for i in range(monitor.MAX_LABEL_SETS + 10):
            c.inc(labels={"i": i})
    # the capped cells + exactly one overflow cell
    assert len(c._cells) == monitor.MAX_LABEL_SETS + 1
    assert c.value(labels={"overflow": "true"}) == 10
    assert monitor.counter("pt_metric_label_overflow_total").value(
        labels={"metric": "t_card_c"}) == 10
    # existing label-sets keep mutating normally past the cap
    c.inc(labels={"i": 0})
    assert c.value(labels={"i": 0}) == 2

    # same contract for gauges and histograms
    g = monitor.gauge("t_card_g", "capped gauge")
    h = monitor.histogram("t_card_h", "capped hist", buckets=(1.0,))
    with pytest.warns(RuntimeWarning, match="label-sets"):
        for i in range(monitor.MAX_LABEL_SETS + 3):
            g.set(i, labels={"i": i})
            h.observe(0.5, labels={"i": i})
    assert len(g._cells) == monitor.MAX_LABEL_SETS + 1
    assert len(h._cells) == monitor.MAX_LABEL_SETS + 1
    assert h.count(labels={"overflow": "true"}) == 3


def test_runtime_flag_flip_takes_effect_immediately():
    c = monitor.counter("t_flip", "flip")
    c.inc()
    assert c.value() == 0
    flags.set_flags({"telemetry": True})
    c.inc()
    assert c.value() == 1
    flags.set_flags({"telemetry": False})
    c.inc()
    assert c.value() == 1


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

def _parse_prometheus(text):
    """sample name+labels -> float value (enough to verify round-trip)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val)
    return out


def test_dump_metrics_round_trips_prometheus_and_json(tmp_path):
    monitor.enable()
    monitor.counter("t_exp_c", "requests").inc(4, labels={"route": "a/b"})
    monitor.gauge("t_exp_g", "depth").set(2.5)
    h = monitor.histogram("t_exp_h", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    # JSON: parseable, values intact
    j = json.loads(monitor.dump_metrics(fmt="json"))
    assert j["t_exp_c"]["kind"] == "counter"
    assert j["t_exp_c"]["values"][0] == {
        "labels": {"route": "a/b"}, "value": 4.0}
    assert j["t_exp_g"]["values"][0]["value"] == 2.5
    hist = j["t_exp_h"]["values"][0]
    assert hist["count"] == 3
    assert hist["buckets"] == [[0.1, 1], [1.0, 2], ["+Inf", 3]]

    # Prometheus text: parseable, same numbers, cumulative buckets
    prom = _parse_prometheus(monitor.dump_metrics(fmt="prometheus"))
    assert prom['t_exp_c{route="a/b"}'] == 4.0
    assert prom["t_exp_g"] == 2.5
    assert prom['t_exp_h_bucket{le="0.1"}'] == 1
    assert prom['t_exp_h_bucket{le="1.0"}'] == 2
    assert prom['t_exp_h_bucket{le="+Inf"}'] == 3
    assert prom["t_exp_h_count"] == 3
    assert prom["t_exp_h_sum"] == pytest.approx(5.55)

    # file write path (explicit arg and flag-driven)
    p = tmp_path / "m.prom"
    monitor.dump_metrics(path=str(p))
    assert _parse_prometheus(p.read_text())["t_exp_g"] == 2.5
    flags.set_flags({"metrics_dump_path": str(tmp_path / "m.json")})
    monitor.dump_metrics(fmt="json")
    assert json.loads((tmp_path / "m.json").read_text())["t_exp_g"]


def test_bad_format_raises():
    with pytest.raises(ValueError):
        monitor.dump_metrics(fmt="xml")


def test_histogram_quantile_summaries_in_json_and_prometheus():
    """p50/p95/p99 ride to_json and the Prometheus text as _p50/_p95/_p99
    samples, so latency tails are readable without a Prometheus server
    running histogram_quantile for you."""
    monitor.enable()
    h = monitor.histogram("t_q_h", "latencies", buckets=(1.0, 2.0, 4.0))
    for v in [0.5] * 50 + [1.5] * 40 + [3.0] * 10:
        h.observe(v)
    # linear interpolation inside the target bucket
    assert h.quantile(0.50) == pytest.approx(1.0)
    assert h.quantile(0.95) == pytest.approx(3.0)
    assert h.quantile(0.99) == pytest.approx(3.8)
    assert h.quantile(0.5, labels={"no": "cell"}) is None

    cell = json.loads(monitor.to_json())["t_q_h"]["values"][0]
    assert cell["p50"] == pytest.approx(1.0)
    assert cell["p95"] == pytest.approx(3.0)
    assert cell["p99"] == pytest.approx(3.8)

    prom = _parse_prometheus(monitor.dump_metrics(fmt="prometheus"))
    assert prom["t_q_h_p50"] == pytest.approx(1.0)
    assert prom["t_q_h_p95"] == pytest.approx(3.0)
    assert prom["t_q_h_p99"] == pytest.approx(3.8)

    # +Inf-bucket observations clamp to the top finite bound
    h2 = monitor.histogram("t_q_inf", "h", buckets=(1.0,))
    h2.observe(50.0)
    assert h2.quantile(0.99) == 1.0


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

def test_span_feeds_histogram_when_enabled():
    monitor.enable()
    with monitor.span("test.scope"):
        pass
    h = monitor.histogram("pt_span_seconds")
    assert h.count(labels={"span": "test.scope"}) == 1

    flags.set_flags({"telemetry": False})
    with monitor.span("test.scope"):
        pass
    assert h.count(labels={"span": "test.scope"}) == 1  # unchanged


# --------------------------------------------------------------------------
# step log
# --------------------------------------------------------------------------

def test_log_step_writes_versioned_jsonl(tmp_path):
    path = tmp_path / "steps.jsonl"
    monitor.enable(step_log_path=str(path))
    base = {"kind": "step", "step": 0, "wall_ms": 1.0, "compile_ms": None,
            "cache": "hit", "evictions": 0, "feed_bytes": 0,
            "fetch_bytes": 0, "nan_check": None, "strategy": None}
    monitor.log_step(dict(base))
    monitor.log_step(dict(base, step=1))
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["seq"] for r in recs] == [0, 1]
    for r in recs:
        assert r["v"] == monitor.STEP_LOG_SCHEMA_VERSION
        monitor.validate_step_record(r)


def test_validate_step_record_rejects_bad_records():
    good = {"v": monitor.STEP_LOG_SCHEMA_VERSION, "ts": 0.0, "seq": 0,
            "kind": "step", "step": 0, "wall_ms": 1.0, "compile_ms": None,
            "cache": "miss", "evictions": 0, "feed_bytes": 0,
            "fetch_bytes": 0, "nan_check": "ok", "strategy": None}
    monitor.validate_step_record(good)
    with pytest.raises(ValueError, match="missing field"):
        monitor.validate_step_record({k: v for k, v in good.items()
                                      if k != "cache"})
    with pytest.raises(ValueError, match="type"):
        monitor.validate_step_record(dict(good, step="zero"))
    with pytest.raises(ValueError, match="unknown fields"):
        monitor.validate_step_record(dict(good, bogus=1))
    with pytest.raises(ValueError, match="schema"):
        monitor.validate_step_record(dict(good, v=999))
    # PR-3 optional fields: the numerics summary and a window's
    # first-bad-step index validate when present, stay optional when not
    monitor.validate_step_record(dict(
        good, nan_check="fail", nan_step=7,
        numerics={"vars": 3, "nonfinite_vars": 1,
                  "first_bad": {"op": 2, "op_type": "elementwise_sub",
                                "var": "t"}}))
    with pytest.raises(ValueError, match="type"):
        monitor.validate_step_record(dict(good, nan_step="seven"))
    with pytest.raises(ValueError, match="type"):
        monitor.validate_step_record(dict(good, numerics="not-a-dict"))
    # PR-4 optional fields: the phase breakdown and boundedness verdict
    # validate when present, stay optional when not
    monitor.validate_step_record(dict(
        good, phases={"feed": 0.1, "dispatch": 0.2, "device": 0.3,
                      "fetch": 0.05},
        bound="device_bound"))
    with pytest.raises(ValueError, match="type"):
        monitor.validate_step_record(dict(good, phases=[0.1, 0.2]))
    with pytest.raises(ValueError, match="type"):
        monitor.validate_step_record(dict(good, bound=3))
    # PR-10 optional field: the sampled marker (async-dispatch plane)
    monitor.validate_step_record(dict(good, sampled=False))
    monitor.validate_step_record(dict(good, sampled=True))
    with pytest.raises(ValueError, match="type"):
        monitor.validate_step_record(dict(good, sampled="no"))


def test_log_step_unwritable_path_warns_once_never_raises(tmp_path):
    """Executors call log_step from finally blocks: a bad path must not
    mask the step's real result (or a propagating exception)."""
    monitor.enable(step_log_path=str(tmp_path / "no" / "such" / "s.jsonl"))
    rec = {"kind": "step", "step": 0, "wall_ms": 1.0, "compile_ms": None,
           "cache": "hit", "evictions": 0, "feed_bytes": 0,
           "fetch_bytes": 0, "nan_check": None, "strategy": None}
    with pytest.warns(RuntimeWarning, match="step log"):
        monitor.log_step(dict(rec))
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        monitor.log_step(dict(rec))  # warn-once: silent, and no raise


def test_log_step_noop_without_path_or_telemetry(tmp_path):
    monitor.log_step({"kind": "step"})  # no telemetry: no error, no file
    flags.set_flags({"telemetry": True})
    monitor.log_step({"kind": "step"})  # no path: rings, writes nothing
    assert not monitor.step_log_active()
    assert len(monitor.recent_steps()) == 1  # ring still fed


def test_step_ring_buffer_is_bounded_and_ordered():
    monitor.enable()
    n = monitor.STEP_RING_CAPACITY
    for i in range(n + 10):
        monitor.log_step({"kind": "step", "step": i})
    recs = monitor.recent_steps()
    assert len(recs) == n  # the bound IS the memory contract
    assert recs[0]["step"] == 10 and recs[-1]["step"] == n + 9
    assert [r["seq"] for r in recs] == list(range(10, n + 10))
    assert monitor.recent_steps(5) == recs[-5:]
    assert monitor.recent_steps(0) == []  # not the recs[-0:] full dump
    assert monitor.recent_steps(-3) == []
    monitor.reset()
    assert monitor.recent_steps() == []


# --------------------------------------------------------------------------
# flags plane self-documentation (satellite)
# --------------------------------------------------------------------------

def test_describe_flags_covers_every_flag_with_docs():
    table = flags.describe_flags()
    names = [row["name"] for row in table]
    assert names == sorted(names)
    assert set(names) == set(flags.get_flags())
    for row in table:
        assert row["type"] in ("bool", "int", "float", "str"), row
        assert isinstance(row["doc"], str) and row["doc"].strip(), (
            f"flag '{row['name']}' has no doc string")
        assert row["value"] == flags.get_flag(row["name"])
    by_name = {r["name"]: r for r in table}
    assert by_name["telemetry"]["default"] is False
    # the numerics plane's flags ride the same self-documentation
    # contract: present, typed, defaulted off/every-step/unfiltered
    assert by_name["numerics"]["type"] == "bool"
    assert by_name["numerics"]["default"] is False
    assert by_name["numerics_every_n_steps"]["type"] == "int"
    assert by_name["numerics_every_n_steps"]["default"] == 1
    assert by_name["numerics_vars"]["type"] == "str"
    assert by_name["numerics_vars"]["default"] == ""
    # the time-attribution plane's flags: phases on with telemetry,
    # tracing off / every-step by default
    assert by_name["step_phases"]["type"] == "bool"
    assert by_name["step_phases"]["default"] is True
    assert by_name["trace_dir"]["type"] == "str"
    assert by_name["trace_dir"]["default"] == ""
    assert by_name["trace_every_n_steps"]["type"] == "int"
    assert by_name["trace_every_n_steps"]["default"] == 1
    # the async-dispatch plane's flags: phases sampled every 16 steps,
    # trainer prefetch two batches deep
    assert by_name["step_phases_every_n"]["type"] == "int"
    assert by_name["step_phases_every_n"]["default"] == 16
    assert by_name["prefetch_depth"]["type"] == "int"
    assert by_name["prefetch_depth"]["default"] == 2


def test_watch_flag_fires_immediately_and_on_change():
    seen = []
    flags.watch_flag("benchmark", seen.append)
    assert seen == [False]
    flags.set_flags({"benchmark": True})
    assert seen == [False, True]
    flags.set_flags({"benchmark": False})
    assert seen == [False, True, False]
    with pytest.raises(KeyError):
        flags.watch_flag("no_such_flag", seen.append)


# --------------------------------------------------------------------------
# end-to-end: 3 training steps of the MNIST model produce a valid step
# log whose cache accounting matches ground truth
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_mnist_three_step_train_emits_valid_step_log(tmp_path):
    from paddle_tpu.models import mnist as mnist_model

    path = tmp_path / "mnist_steps.jsonl"
    monitor.enable(step_log_path=str(path))

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

    recs = [json.loads(l) for l in path.read_text().splitlines()]
    for r in recs:
        monitor.validate_step_record(r)
    # startup + 3 train steps, one record each
    assert len(recs) == 4
    assert [r["kind"] for r in recs] == ["step"] * 4
    train = recs[1:]
    # ground truth: first train step compiles, the rest hit the cache
    assert [r["cache"] for r in train] == ["miss", "hit", "hit"]
    assert train[0]["compile_ms"] is not None and train[0]["compile_ms"] > 0
    assert all(r["compile_ms"] is None for r in train[1:])
    assert all(r["feed_bytes"] == 16 * 784 * 4 + 16 * 8 for r in train)
    assert all(r["fetch_bytes"] > 0 for r in train)
    assert all(r["wall_ms"] > 0 for r in train)
    assert [r["step"] for r in recs] == [0, 1, 2, 3]

    # registry agrees with the log
    assert monitor.counter(
        "pt_executor_cache_hits_total").value() == 2
    assert monitor.counter(
        "pt_executor_cache_misses_total").value() == 2  # startup + train
    # exporters round-trip on the live registry
    assert json.loads(monitor.dump_metrics(fmt="json"))
    assert "pt_executor_cache_hits_total 2.0" in monitor.dump_metrics(
        fmt="prometheus")


# --------------------------------------------------------------------------
# profiler degrade path (satellite): no native collector, no crash
# --------------------------------------------------------------------------

def test_profiler_degrades_cleanly_without_native(tmp_path, monkeypatch):
    """With the C++ profiler unavailable, `with profiler.profiler(...)`
    must be a structural no-op: no chrome-trace file, no crash, and
    monitor.span events still round-trip into pt_span_seconds."""
    from paddle_tpu import native

    monkeypatch.setattr(native, "available", lambda: False)
    monitor.enable()
    path = tmp_path / "prof"
    with profiler.profiler(profile_path=str(path)):
        with monitor.span("degrade.scope"):
            pass
        with profiler.record_event("raw.event"):  # host span: plain yield
            pass
    assert not path.with_suffix(".json").exists()
    assert not (tmp_path / "prof.json").exists()
    # telemetry half of the unified span still recorded
    assert monitor.histogram("pt_span_seconds").count(
        labels={"span": "degrade.scope"}) == 1
    # start/stop entry points take the same degrade path
    profiler.start_profiler()
    profiler.stop_profiler(profile_path=str(tmp_path / "prof2"))
    assert not (tmp_path / "prof2.json").exists()


def test_profiler_with_xplane_leaves_a_trace_the_one_reader_reads(tmp_path):
    """``profiler.profiler(with_xplane=True)`` captures jax's trace under
    ``<profile_path>_xplane``, where ``perf/trace.py`` (the one reader of
    a device trace) finds and loads it; a CPU's capture holds no
    ``/device:TPU:*`` plane, so it loads to none."""
    import jax.numpy as jnp

    from perf import trace

    with profiler.profiler(profile_path=str(tmp_path / "prof"),
                           with_xplane=True):
        jnp.ones((64, 64)).sum().block_until_ready()
    path = trace.find_xplane(str(tmp_path / "prof_xplane"))
    assert path.endswith(".xplane.pb") and os.path.getsize(path) > 0
    assert trace.load(path) == {"planes": []}
    # without it no capture is started
    with profiler.profiler(profile_path=str(tmp_path / "bare")):
        pass
    assert not (tmp_path / "bare_xplane").exists()


@functools.cache
def _package_source_but_the_flags():
    root = os.path.dirname(fluid.__file__)
    return "".join(
        open(os.path.join(d, f)).read()
        for d, _, fs in os.walk(root) for f in sorted(fs)
        if f.endswith(".py") and (d, f) != (root, "flags.py"))


@pytest.mark.parametrize("name", sorted(flags.get_flags()))
def test_a_flag_has_a_reader_in_the_package(name):
    """A flag's name, quoted, stands somewhere in ``paddle_tpu/`` outside
    ``flags.py`` (``get_flag``, ``watch_flag``, ``get_flags``): a flag
    whose reader was deleted goes with it (ROADMAP Queue 3 item 12)."""
    assert re.search(rf"""["']{name}["']""",
                     _package_source_but_the_flags()), \
        f"flag '{name}' is defined in flags.py and read nowhere"


# --------------------------------------------------------------------------
# metric doc coverage (satellite): every builtin instrument documented,
# README's Observability table complete
# --------------------------------------------------------------------------

def test_every_builtin_metric_has_doc_and_readme_entry():
    # importing the instrumented modules registers their instruments
    import paddle_tpu.contrib.trainer  # noqa: F401
    import paddle_tpu.core.interp  # noqa: F401
    import paddle_tpu.executor  # noqa: F401
    import paddle_tpu.incubate.fleet.fleet_base  # noqa: F401
    import paddle_tpu.parallel.pipeline  # noqa: F401
    import paddle_tpu.parallel.ring_attention  # noqa: F401

    snap = monitor.snapshot()
    builtin = {n: m for n, m in snap.items() if n.startswith("pt_")}
    assert len(builtin) >= 25, sorted(builtin)
    readme = open(os.path.join(os.path.dirname(fluid.__file__), "..",
                               "README.md")).read()
    for name, m in sorted(builtin.items()):
        assert m["doc"].strip(), f"metric '{name}' has no doc string"
        assert name in readme, (
            f"metric '{name}' missing from README's Observability "
            f"metrics table")


# --------------------------------------------------------------------------
# executor hot path with telemetry off: the one-boolean-check contract
# --------------------------------------------------------------------------

def test_executor_run_disabled_path_allocates_nothing_in_monitor():
    """The PR-2 instrumentation (ring buffer, compile reports, budget
    pre-flight) must not add allocations to Executor.run while telemetry
    is off — same contract the raw instruments honor."""
    assert not monitor.enabled()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # warm the compile cache + lazy interp state
            exe.run(main, feed=feed, fetch_list=[y])
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=feed, fetch_list=[y])
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith("monitor.py")
               and s.size_diff > 0)
    # per-run allocations would show as >= n_runs * 16B growth; allow
    # constant interpreter noise only
    assert grew < n_runs * 16, (
        f"disabled Executor.run allocated {grew}B in monitor.py over "
        f"{n_runs} runs")
