"""Persistent (level-2) compile cache: canonical fingerprints shared
across the executor / lint / compile-report subsystems, disk
round-trips, cross-process warm start with zero fresh compiles,
corruption degrading to a metered miss (never a crash), and the
disabled-path zero-allocation contract."""

import glob
import json
import os
import pickle
import subprocess
import sys
import tracemalloc
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, compile_cache, faults, flags, layers, monitor

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    monitor.reset()
    flags.set_flags({"telemetry": True,
                     "compile_cache_dir": str(tmp_path / "ccache")})
    yield
    monitor.reset()
    faults.disarm()
    flags.set_flags({"telemetry": False, "compile_cache_dir": "",
                     "executor_cache_capacity": 0})


def _build(stateless=False):
    from paddle_tpu import unique_name

    # name counters restart per build (the fresh-process condition the
    # disk tier keys on): identical build code -> identical content
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        if stateless:
            x = layers.data("x", shape=[4, 8], append_batch_size=False,
                            stop_gradient=True)
            out = layers.reduce_sum(x)
        else:
            x = layers.data("x", shape=[8], dtype="float32")
            out = layers.mean(layers.fc(x, 4))
            fluid.optimizer.SGD(0.1).minimize(out)
    return main, startup, out


def _feed(batch=4):
    return {"x": np.arange(batch * 8, dtype=np.float32).reshape(batch, 8)}


def _hits():
    return monitor.counter("pt_compile_cache_hits_total").value()


def _errors(stage):
    return monitor.counter("pt_compile_cache_errors_total").value(
        labels={"stage": stage})


# --------------------------------------------------------------------------
# canonical fingerprint (the satellite: ONE helper for executor key,
# lint-once cache, compile-report cache_key, disk tier)
# --------------------------------------------------------------------------

def test_program_fingerprint_is_content_keyed_across_builds():
    """Two identically-built programs (different uids — the
    cross-process stand-in) fingerprint identically; any content change
    diverges."""
    m1, _, _ = _build(stateless=True)
    m2, _, _ = _build(stateless=True)
    assert m1._uid != m2._uid
    assert m1.content_digest() == m2.content_digest()
    fp = compile_cache.program_fingerprint
    assert fp(m1, feed_sig=("x",), fetch_names=("o",)) == \
        fp(m2, feed_sig=("x",), fetch_names=("o",))
    # feed/fetch signature rides the fingerprint
    assert fp(m1, feed_sig=("x",), fetch_names=("o",)) != \
        fp(m1, feed_sig=("x",), fetch_names=("other",))
    # content mutation diverges (and the per-version digest cache sees it)
    with fluid.program_guard(m2, fluid.Program()):
        layers.scale(m2.global_block().var("x"), scale=2.0)
    assert m1.content_digest() != m2.content_digest()


def test_noncanonical_content_degrades_to_local_fingerprint(monkeypatch):
    """A program whose content cannot be canonicalized still keys
    in-process caches (local- prefix) but never resolves from disk."""
    main, startup, out = _build(stateless=True)
    monkeypatch.setattr(fluid.framework.Program, "content_digest",
                        lambda self: (_ for _ in ()).throw(TypeError("x")))
    fp = compile_cache.program_fingerprint(main)
    assert fp.startswith("local-")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[out])
    # nothing was written: local fingerprints are not portable
    assert glob.glob(flags.get_flag("compile_cache_dir") + "/pcc-*") == []


def test_lint_once_cache_is_content_keyed_via_canonical_fingerprint():
    """The static verifier's lint-once cache now keys on the same
    canonical fingerprint: two identically-built programs share ONE
    lint run (previously uid-keyed — every rebuild re-linted)."""
    m1, _, _ = _build(stateless=True)
    m2, _, _ = _build(stateless=True)

    def runs():
        return monitor.counter("pt_lint_runs_total").value()

    r0 = runs()
    analysis.lint_before_compile(m1, ["x"], ["o"], site="t-ccfp")
    assert runs() == r0 + 1
    analysis.lint_before_compile(m2, ["x"], ["o"], site="t-ccfp")
    assert runs() == r0 + 1  # same content: cached
    analysis.lint_before_compile(m2, ["x"], [], site="t-ccfp")
    assert runs() == r0 + 2  # different fetch signature: re-lints


def test_compile_report_cache_key_is_canonical(tmp_path):
    """Identical programs run through different executors produce
    compile reports with the SAME cache_key digest — the canonical
    fingerprint, not a process-local identity tuple."""
    d = tmp_path / "reports"
    flags.set_flags({"compile_report_dir": str(d),
                     "compile_cache_dir": ""})
    try:
        keys = []
        for _ in range(2):
            main, startup, out = _build(stateless=True)
            scope = fluid.Scope()
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(scope):
                exe.run(startup)
                exe.run(main, feed=_feed(), fetch_list=[out])
                exe.run_steps(main, feed_list=[_feed()], steps=2,
                              fetch_list=[out])
        reports = [json.load(open(f)) for f in glob.glob(str(d) + "/*.json")]
        # 2 iterations x (startup step + main step + window) = 6 reports;
        # each pair of identically-built programs must share ONE key, so
        # the step reports collapse to 2 distinct keys (startup, main)
        # and the window reports to 1
        step_keys = [r["cache_key"] for r in reports if r["kind"] == "step"]
        window_keys = [r["cache_key"] for r in reports
                       if r["kind"] == "window"]
        assert len(step_keys) == 4 and len(set(step_keys)) == 2, step_keys
        assert len(window_keys) == 2 and len(set(window_keys)) == 1
    finally:
        flags.set_flags({"compile_report_dir": ""})


# --------------------------------------------------------------------------
# disk round-trips (same machine, fresh level-1 caches)
# --------------------------------------------------------------------------

def test_fresh_executor_resolves_from_disk_bit_exact():
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        cold = exe.run(main, feed=_feed(), fetch_list=[out])
    assert _hits() == 0
    assert glob.glob(flags.get_flag("compile_cache_dir") + "/pcc-*.bin")
    exe2 = fluid.Executor(fluid.CPUPlace())  # fresh level-1 cache
    with fluid.scope_guard(scope):
        warm = exe2.run(main, feed=_feed(), fetch_list=[out])
    assert _hits() == 1
    assert monitor.recent_steps()[-1]["cache"] == "disk"
    assert float(np.asarray(cold[0])) == float(np.asarray(warm[0]))
    load_ms = monitor.recent_steps()[-1]["compile_ms"]
    assert load_ms is not None and load_ms > 0
    assert monitor.histogram("pt_compile_cache_load_seconds").count() == 1


def test_run_steps_window_resolves_from_disk_and_is_steps_keyed():
    """A run_steps window round-trips through disk; a different
    ``steps`` count is a DIFFERENT entry end to end (the executable
    bakes the static step count)."""
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        cold = exe.run_steps(main, feed_list=[_feed()], steps=3,
                             fetch_list=[out])
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        warm = exe2.run_steps(main, feed_list=[_feed()], steps=3,
                              fetch_list=[out])
        assert monitor.recent_steps()[-1]["cache"] == "disk"
        assert float(np.asarray(cold[0])) == float(np.asarray(warm[0]))
        h = _hits()
        # same signature, different steps: fresh compile, not a stale
        # disk wrapper silently running 3 baked steps
        exe2.run_steps(main, feed_list=[_feed()], steps=2,
                       fetch_list=[out])
        assert _hits() == h
        assert monitor.recent_steps()[-1]["cache"] == "miss"


def test_trained_state_continues_identically_after_disk_resolve():
    """A disk-resolved train step continues a parameter trajectory
    exactly where a fresh-compiled one would: same scope, fresh
    executor, losses keep decreasing from the committed state."""
    main, startup, out = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        l1 = float(np.asarray(exe.run(main, feed=_feed(),
                                      fetch_list=[out])[0]))
        l2 = float(np.asarray(exe.run(main, feed=_feed(),
                                      fetch_list=[out])[0]))
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        l3 = float(np.asarray(exe2.run(main, feed=_feed(),
                                       fetch_list=[out])[0]))
    assert monitor.recent_steps()[-1]["cache"] == "disk"
    assert l2 < l1 and l3 < l2  # SGD keeps descending through the swap


def test_disk_hit_emits_no_fresh_compile_report(tmp_path):
    d = tmp_path / "reports"
    flags.set_flags({"compile_report_dir": str(d)})
    try:
        main, startup, out = _build(stateless=True)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_feed(), fetch_list=[out])
        n_cold = len(glob.glob(str(d) + "/*.json"))
        assert n_cold >= 1
        exe2 = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe2.run(main, feed=_feed(), fetch_list=[out])
        assert monitor.recent_steps()[-1]["cache"] == "disk"
        assert len(glob.glob(str(d) + "/*.json")) == n_cold
    finally:
        flags.set_flags({"compile_report_dir": ""})


# --------------------------------------------------------------------------
# degrade paths: corruption, tampering, torn stores — metered, never fatal
# --------------------------------------------------------------------------

def test_truncated_entry_degrades_to_metered_miss_via_fault_site():
    """The corruption regression, driven through the faults.py site
    machinery: a ccache.load truncate plan tears the published file
    right before the read — the run must recompile (and republish),
    metering one load error, raising nothing."""
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        cold = exe.run(main, feed=_feed(), fetch_list=[out])
    assert _errors("load") == 0
    faults.arm("ccache.load:truncate(8)@1")
    try:
        exe2 = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                warm = exe2.run(main, feed=_feed(), fetch_list=[out])
        assert any("recompiling" in str(x.message) for x in w)
    finally:
        faults.disarm()
    assert _errors("load") == 1
    assert monitor.recent_steps()[-1]["cache"] == "miss"
    assert float(np.asarray(cold[0])) == float(np.asarray(warm[0]))
    assert monitor.counter(
        "pt_fault_injected_total").value(labels={"site": "ccache.load"}) == 1
    # the recompile republished an intact entry: next fresh executor hits
    exe3 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe3.run(main, feed=_feed(), fetch_list=[out])
    assert monitor.recent_steps()[-1]["cache"] == "disk"


def test_env_tampered_entry_is_silent_miss_not_error():
    """A header mismatch (another jax/topology/format wrote this name)
    is an expected miss — counted as such, no error, no warning."""
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[out])
    paths = glob.glob(flags.get_flag("compile_cache_dir") + "/pcc-*.bin")
    assert paths
    for path in paths:  # tamper every entry: the warm run must miss
        payload = pickle.load(open(path, "rb"))
        payload["env"] = ("other-jax",)
        pickle.dump(payload, open(path, "wb"))
    misses0 = monitor.counter("pt_compile_cache_misses_total").value()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe2.run(main, feed=_feed(), fetch_list=[out])
    assert monitor.recent_steps()[-1]["cache"] == "miss"
    assert monitor.counter(
        "pt_compile_cache_misses_total").value() > misses0
    assert _errors("load") == 0


def test_torn_store_leaves_no_published_entry():
    """A crash (raise) at the staged write never publishes a torn file:
    the .tmp straggler is cleaned, the run proceeds on the in-memory
    entry, and the error is metered."""
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    faults.arm("ccache.store:raise@1")
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            with warnings.catch_warnings(record=True):
                warnings.simplefilter("always")
                exe.run(startup)  # first store attempt crashes
                exe.run(main, feed=_feed(), fetch_list=[out])
    finally:
        faults.disarm()
    d = flags.get_flag("compile_cache_dir")
    assert _errors("store") == 1
    assert glob.glob(d + "/*.tmp.*") == []  # no straggler
    # the second entry (not faulted) still published and resolves
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe2.run(main, feed=_feed(), fetch_list=[out])
    assert monitor.recent_steps()[-1]["cache"] == "disk"


def test_aot_build_traces_under_the_strategy_spmd_context():
    """The AOT compile for the disk tier must trace inside
    spmd_ctx_scope(strategy), exactly like the eager jit's first call:
    collective ops (DGC exchange, MoE all_to_all) read the context at
    TRACE time, and without it they silently lower their non-collective
    fallback — which would then be executed AND persisted."""
    import types

    from paddle_tpu.core import interp

    strategy = types.SimpleNamespace(
        mesh=None, context_axis=None, table_axis="tp", data_axis="dp",
        slice_axis=None, expert_axis=None, pipe_axis=None, pipe_micro=None)
    seen = {}

    class FakeJit:
        def lower(self, *args):
            seen["ctx"] = interp.spmd_ctx()
            raise RuntimeError("stop after recording the trace context")

    spec = compile_cache.Spec(
        path="/nonexistent", digest="d", lower_args=({}, {}, None),
        static_steps=None, program=None, feed_names=(), fetch_names=(),
        strategy=strategy)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        assert compile_cache.aot_build(spec, FakeJit()) is None
    assert seen["ctx"] is not None and seen["ctx"].table_axis == "tp"
    # and the executor's spec carries the CompiledProgram's strategy
    assert interp.spmd_ctx() is None  # scope exited


def test_local_fingerprints_build_no_spec(monkeypatch):
    """Non-canonical (local-) fingerprints never resolve from disk.
    NOTE the before/after flip (ISSUE 14): this test used to also pin
    the blanket multi-host decline (``process_count() > 1`` -> no
    spec, a silent fresh compile); multi-host entries are now keyed by
    the OWNING shard's topology instead — see
    tests/test_elastic_grow.py for the after-contract."""
    main, startup, out = _build(stateless=True)
    monkeypatch.setattr(fluid.framework.Program, "content_digest",
                        lambda self: (_ for _ in ()).throw(TypeError("x")))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[out])
    assert glob.glob(flags.get_flag("compile_cache_dir") + "/pcc-*") == []


# --------------------------------------------------------------------------
# cross-process warm start (THE acceptance flow)
# --------------------------------------------------------------------------

def test_cross_process_warm_start_zero_fresh_compiles(tmp_path):
    """A subprocess compiles and populates the disk cache; a second
    fresh subprocess resolves EVERY entry from disk — zero fresh XLA
    compiles (all outcomes 'disk', miss counter 0) and no new compile
    report."""
    cache_d, report_d = str(tmp_path / "cc"), str(tmp_path / "cr")
    env = {**os.environ, "PYTHONPATH": os.path.dirname(HERE)}

    def launch():
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "ccache_worker.py"),
             cache_d, report_d],
            capture_output=True, text=True, timeout=600, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = launch()
    assert cold["stats"]["hits"] == 0
    assert cold["stats"]["misses"] >= 3  # startup + step + window
    assert cold["stats"]["errors"] == {"spec": 0, "load": 0, "store": 0}
    n_reports = len(glob.glob(report_d + "/*.json"))
    assert n_reports >= 1

    warm = launch()
    assert warm["stats"]["misses"] == 0, warm
    assert warm["stats"]["hits"] == cold["stats"]["misses"]
    assert set(warm["outcomes"]) == {"disk"}, warm["outcomes"]
    assert warm["exec_misses"] == cold["exec_misses"]  # L1 always misses
    # no fresh compile -> no new compile report
    assert len(glob.glob(report_d + "/*.json")) == n_reports
    assert np.isfinite(warm["loss"]) and np.isfinite(warm["window_loss"])


# --------------------------------------------------------------------------
# disabled path: the one-boolean-check / zero-allocation contract
# --------------------------------------------------------------------------

def test_disabled_path_allocates_nothing_in_compile_cache():
    flags.set_flags({"compile_cache_dir": "", "telemetry": False})
    assert not compile_cache.active()
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # warm L1 + the fingerprint memo
            exe.run(main, feed=_feed(), fetch_list=[out])
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=_feed(), fetch_list=[out])
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith("compile_cache.py")
               and s.size_diff > 0)
    assert grew < n_runs * 16, (
        f"disabled Executor.run allocated {grew}B in compile_cache.py "
        f"over {n_runs} runs")


# --------------------------------------------------------------------------
# disk GC (ISSUE 9 satellite): size-capped LRU-by-mtime sweep
# --------------------------------------------------------------------------

def _fake_entry(d, name, nbytes, mtime):
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, name)
    with open(path, "wb") as f:
        f.write(b"\0" * nbytes)
    os.utime(path, (mtime, mtime))
    return path


def _evictions():
    return monitor.counter("pt_compile_cache_evictions_total").value()


def test_gc_sweeps_oldest_entries_to_fit_the_cap():
    import time as _time

    d = flags.get_flag("compile_cache_dir")
    now = _time.time()
    old = _fake_entry(d, "pcc-old.bin", 600, now - 300)
    mid = _fake_entry(d, "pcc-mid.bin", 600, now - 200)
    new = _fake_entry(d, "pcc-new.bin", 600, now - 100)
    # a foreign file and a FRESH .tmp straggler are never GC victims
    other = _fake_entry(d, "notes.txt", 600, now - 900)
    staged = _fake_entry(d, "pcc-x.bin.tmp.123", 600, now - 10)
    assert compile_cache.gc(max_bytes=1300) == 1
    assert not os.path.exists(old)
    assert os.path.exists(mid) and os.path.exists(new)
    assert os.path.exists(other) and os.path.exists(staged)
    assert _evictions() == 1
    # an HOUR-old .tmp straggler is a crash leftover: reaped
    crashed = _fake_entry(d, "pcc-y.bin.tmp.9", 10, now - 7200)
    compile_cache.gc(max_bytes=1300)
    assert not os.path.exists(crashed)
    # the newest entry survives even a cap smaller than itself
    compile_cache.gc(max_bytes=100)
    assert os.path.exists(new)
    assert not os.path.exists(mid)
    assert _evictions() == 2


def test_gc_concurrent_removal_counts_freed_space(monkeypatch):
    """Two processes sharing the dir both sweep: an entry a concurrent
    GC already reclaimed (os.remove -> FileNotFoundError) is not OUR
    eviction, but its space IS freed — without the subtraction this
    process would keep looping and over-evict still-hot entries that
    actually fit the budget."""
    import time as _time

    d = flags.get_flag("compile_cache_dir")
    now = _time.time()
    old = _fake_entry(d, "pcc-old.bin", 600, now - 300)
    mid = _fake_entry(d, "pcc-mid.bin", 600, now - 200)
    new = _fake_entry(d, "pcc-new.bin", 600, now - 100)
    real_remove = os.remove

    def _raced(path):
        # the concurrent sweeper wins the race for the oldest entry
        if path == old:
            real_remove(path)
            raise FileNotFoundError(path)
        real_remove(path)

    monkeypatch.setattr(os, "remove", _raced)
    # cap fits two entries: only `old` must go, and it went to the
    # OTHER process — zero evictions of ours, survivors untouched
    assert compile_cache.gc(max_bytes=1300) == 0
    assert os.path.exists(mid) and os.path.exists(new)
    assert _evictions() == 0


def test_gc_disabled_without_cap_and_loads_refresh_mtime():
    """cap 0 = unbounded (no sweep); a disk HIT refreshes the entry's
    mtime so eviction order is least-recently-USED, not least-recently-
    written."""
    import time as _time

    d = flags.get_flag("compile_cache_dir")
    _fake_entry(d, "pcc-a.bin", 4096, _time.time() - 500)
    assert compile_cache.gc() == 0  # flag default: unbounded
    assert os.path.exists(os.path.join(d, "pcc-a.bin"))

    # real entry, stored then re-resolved by a fresh executor: the hit
    # must bump its mtime past the fake older entry's
    main, startup, out = _build(stateless=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[out])
    entries = [p for p in glob.glob(d + "/pcc-*.bin")
               if "pcc-a.bin" not in p]  # startup + main entries
    assert entries
    past = _time.time() - 400
    for p in entries:
        os.utime(p, (past, past))
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe2.run(main, feed=_feed(), fetch_list=[out])
    assert monitor.recent_steps()[-1]["cache"] == "disk"
    # exactly the re-resolved entry (main's) got its mtime refreshed
    refreshed = [p for p in entries if os.stat(p).st_mtime > past + 1]
    assert len(refreshed) == 1


def test_store_sweeps_via_the_flag_cap():
    """A store with compile_cache_max_bytes set runs the sweep
    inline: pre-seeded cold entries beyond the cap are evicted by the
    publish itself, and the metric accounts for them."""
    import time as _time

    d = flags.get_flag("compile_cache_dir")
    for i in range(3):
        _fake_entry(d, f"pcc-cold{i}.bin", 50_000,
                    _time.time() - 1000 - i)
    flags.set_flags({"compile_cache_max_bytes": 120_000})
    try:
        main, startup, out = _build(stateless=True)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_feed(), fetch_list=[out])
    finally:
        flags.set_flags({"compile_cache_max_bytes": 0})
    # the published entries fit only after evicting cold ones
    total = sum(os.path.getsize(p) for p in glob.glob(d + "/pcc-*.bin"))
    assert total <= 120_000
    assert _evictions() >= 1
    # the just-published (newest) entries survived
    assert glob.glob(d + "/pcc-*.bin")
