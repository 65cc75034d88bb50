"""The executor's compiled-entry cache: exact hit/miss/eviction counts
(pt_executor_cache_* counters), the ``executor_cache_capacity`` eviction
policy, the content fingerprint that keys it (core/fingerprint.py,
shared with the lint-once cache and the compile report), and the one
executable behind every entry: it donates its state."""

import glob
import json

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, flags, layers, monitor, unique_name
from paddle_tpu.core import fingerprint


@pytest.fixture(autouse=True)
def _clean():
    flags.set_flags({"telemetry": True, "executor_cache_capacity": 0})
    yield
    flags.set_flags({"telemetry": False, "executor_cache_capacity": 0})


def _build(stateless=False):
    # name counters restart per build (the fresh-process condition):
    # identical build code -> identical content
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], append_batch_size=False,
                        stop_gradient=True)
        if stateless:
            loss = layers.reduce_sum(x)
        else:
            loss = layers.mean(layers.fc(x, 4))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _counts():
    return (
        monitor.counter("pt_executor_cache_hits_total").value(),
        monitor.counter("pt_executor_cache_misses_total").value(),
        monitor.counter("pt_executor_cache_evictions_total").value(),
    )


def _feed(batch=4):
    return {"x": np.ones((batch, 8), np.float32)}


def test_only_a_first_call_goes_through_the_frame_of_its_own(monkeypatch):
    """A call that traces and lowers reaches its jitted function through
    ``executor._first_call_fn``, whose 8,000 declared slots put it on a
    data-stack chunk of its own (PERF.md section 6, PR 43: 40 slots of
    executor frame had cost ``tbase-train-dp4``'s lowering 22 s); a
    cache hit calls the function as it is. The frame looks like a
    function that does nothing: inlined, this fails."""
    from paddle_tpu import executor

    frame = executor._first_call_fn
    assert frame.__code__.co_stacksize == 8000
    through = []

    def counted(fn, *args):
        through.append(fn)
        return frame(fn, *args)

    monkeypatch.setattr(executor, "_first_call_fn", counted)
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)                                # miss
        exe.run(main, feed=_feed(), fetch_list=[loss])  # miss
        assert len(through) == 2 == _counts()[1]
        exe.run(main, feed=_feed(), fetch_list=[loss])  # hit
        exe.run_steps(main, feed_list=[_feed()] * 2, fetch_list=[loss],
                      steps=2)                          # a window: miss
        exe.run_steps(main, feed_list=[_feed()] * 2, fetch_list=[loss],
                      steps=2)                          # hit
    assert len(through) == 3 and _counts() == (2, 3, 0)
    assert len(set(map(id, through))) == 3      # each entry's own fn


def test_hit_miss_counts_exact_across_repeated_runs():
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)                       # miss 1
        assert _counts() == (0, 1, 0)
        for i in range(4):                     # miss 2, then 3 hits
            exe.run(main, feed=_feed(), fetch_list=[loss])
        assert _counts() == (3, 2, 0)
        # a different fetch list is a different compiled program
        exe.run(main, feed=_feed(), fetch_list=[])      # miss 3
        assert _counts() == (3, 3, 0)
        exe.run(main, feed=_feed(), fetch_list=[])      # hit 4
        exe.run(main, feed=_feed(), fetch_list=[loss])  # hit 5
        assert _counts() == (5, 3, 0)
        # use_program_cache=False bypasses the cache: no counter movement
        exe.run(main, feed=_feed(), fetch_list=[loss],
                use_program_cache=False)
        assert _counts() == (5, 3, 0)


def test_capacity_eviction_fires_and_is_counted():
    main, startup, loss = _build()
    flags.set_flags({"executor_cache_capacity": 1})
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)                       # miss; cache = {startup}
        assert len(exe._cache) == 1
        # miss; evicts startup (capacity 1)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        assert len(exe._cache) == 1
        assert _counts() == (0, 2, 1)
        # still cached: hit, no eviction
        exe.run(main, feed=_feed(), fetch_list=[loss])
        assert _counts() == (1, 2, 1)
        # alternate between two signatures at capacity 1: every run
        # recompiles and evicts the other — the thrash eviction exists
        # to make visible
        for _ in range(2):
            exe.run(main, feed=_feed(), fetch_list=[])
            exe.run(main, feed=_feed(), fetch_list=[loss])
        assert _counts() == (1, 6, 5)
        assert len(exe._cache) == 1


def test_capacity_eviction_clears_owned_feed_staging_entries():
    """Evicting a run_steps entry at capacity also drops the staged
    feed windows it owns in the keyed LRU — stale staging would pin
    whole device-resident feed windows after the compiled entry is gone
    (and could never hit again without its entry). A victim that is NOT
    an owner leaves other stagings alone."""
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    frozen = np.arange(32, dtype=np.float32).reshape(4, 8).copy()
    frozen.flags.writeable = False
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_steps(main, feed_list=[{"x": frozen}], steps=2,
                      fetch_list=[loss])
        assert len(exe._staged) == 1
        assert next(iter(exe._staged.values()))["owner"] is not None
        # shrink to capacity 1; the next insert (a fresh run signature)
        # evicts both older entries, including the staging owner — the
        # staged window must go with it
        flags.set_flags({"executor_cache_capacity": 1})
        exe.run(main, feed=_feed(), fetch_list=[loss])
        assert len(exe._staged) == 0
        # at capacity 2 with the window entry RECENT, evicting the
        # older run() entry does not touch the window's staging
        flags.set_flags({"executor_cache_capacity": 2})
        exe.run_steps(main, feed_list=[{"x": frozen}], steps=2,
                      fetch_list=[loss])  # cache: {run, window}
        assert len(exe._staged) == 1
        exe.run(main, feed=_feed(), fetch_list=[])  # evicts the run entry
        assert len(exe._staged) == 1
        assert len(exe._cache) == 2
        exe.close()  # close drops staging with the entries
        assert len(exe._staged) == 0


def test_staged_window_lru_keeps_alternating_rotations():
    """The keyed staging LRU holds several feed rotations at once:
    alternating windows A/B/A/B must both stay staged (the old
    single-slot cache thrashed on exactly this pattern), and the LRU
    cap bounds how many device-resident windows can accumulate."""
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())

    def frozen(seed):
        a = np.random.RandomState(seed).randn(4, 8).astype(np.float32)
        a.flags.writeable = False
        return a

    wa, wb = {"x": frozen(0)}, {"x": frozen(1)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_steps(main, feed_list=[wa], steps=1, fetch_list=[loss])
        exe.run_steps(main, feed_list=[wb], steps=1, fetch_list=[loss])
        assert len(exe._staged) == 2
        staged_a = [e["stacked"]["x"] for e in exe._staged.values()]
        # both rotations hit their staged windows on the second pass
        exe.run_steps(main, feed_list=[wa], steps=1, fetch_list=[loss])
        exe.run_steps(main, feed_list=[wb], steps=1, fetch_list=[loss])
        assert [e["stacked"]["x"] for e in exe._staged.values()] \
            == staged_a
        # the cap bounds device pinning: distinct rotations beyond
        # capacity evict the coldest
        for seed in range(2, 2 + exe.STAGED_WINDOW_CAPACITY):
            exe.run_steps(main, feed_list=[{"x": frozen(seed)}], steps=1,
                          fetch_list=[loss])
        assert len(exe._staged) == exe.STAGED_WINDOW_CAPACITY


@pytest.mark.parametrize("kind", ["step", "window"])
def test_failing_step_still_logs_a_record(tmp_path, kind):
    """A raising call (here: NaN scan), a step's or a window's, must
    still append its step-log record — the crashed step is the record a
    postmortem needs."""
    import json

    path = tmp_path / "s.jsonl"
    flags.set_flags({"step_log_path": str(path), "check_nan_inf": True})
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())

    def call(feed):
        if kind == "step":
            return exe.run(main, feed=feed, fetch_list=[loss])
        return exe.run_steps(main, feed_list=[feed], steps=2,
                             fetch_list=[loss])

    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            call(_feed())
            with pytest.raises(FloatingPointError):
                call({"x": np.full((4, 8), np.nan, np.float32)})
    finally:
        flags.set_flags({"check_nan_inf": False, "step_log_path": ""})
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    for r in recs:
        monitor.validate_step_record(r)
    assert len(recs) == 3
    assert [r["kind"] for r in recs] == ["step", kind, kind]
    assert recs[1]["nan_check"] == "ok"
    assert recs[2]["nan_check"] == "fail" and recs[2]["wall_ms"] > 0
    if kind == "window":  # the in-graph tracker names the first bad step
        assert recs[2]["nan_step"] == recs[2]["step"]


def test_a_step_and_a_window_log_the_same_record(tmp_path):
    """One body writes both records: a window's holds what a step's
    does and ``steps``, with phases on (sampled calls) and off."""
    import json

    path = tmp_path / "s.jsonl"
    flags.set_flags({"step_log_path": str(path), "step_phases_every_n": 1})
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            for phases in (True, False):
                flags.set_flags({"step_phases": phases})
                for _ in range(2):  # a first call, then a cached one
                    exe.run(main, feed=_feed(), fetch_list=[loss])
                    exe.run_steps(main, feed_list=[_feed()], steps=3,
                                  fetch_list=[loss])
    finally:
        flags.set_flags({"step_log_path": "", "step_phases": True,
                         "step_phases_every_n": 16})
    recs = [json.loads(l) for l in path.read_text().splitlines()][1:]
    assert [r["kind"] for r in recs] == ["step", "window"] * 4
    for step, window in zip(recs[::2], recs[1::2]):
        monitor.validate_step_record(window)
        assert window["steps"] == 3
        assert list(step) == [k for k in window if k != "steps"]
        assert step["cache"] == window["cache"]
    assert "phases" in recs[2] and "phases" not in recs[-1]
    # the window's steps moved the PRNG index as three steps do
    assert [r["step"] for r in recs[:4]] == [1, 2, 5, 6]


def test_lru_refresh_keeps_hot_entry():
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        exe.run(main, feed=_feed(), fetch_list=[])
        # touch the loss entry so it is the most recent...
        exe.run(main, feed=_feed(), fetch_list=[loss])
        assert len(exe._cache) == 3
        # ...then shrink capacity to 2; eviction fires on the next INSERT
        # (a fresh signature), dropping the two coldest (startup and the
        # fetch-less entry) and never the refreshed hot entry
        flags.set_flags({"executor_cache_capacity": 2})
        monitor.reset()
        exe.run(main, feed={"x": np.ones((8, 8), np.float32)},
                fetch_list=[loss])  # new batch size: miss + insert
        assert len(exe._cache) == 2
        assert monitor.counter(
            "pt_executor_cache_evictions_total").value() == 2
        # the hot (loss-fetching) entry survived: running it again is
        # a hit, not a recompile
        before = monitor.counter("pt_executor_cache_misses_total").value()
        exe.run(main, feed=_feed(), fetch_list=[loss])
        assert monitor.counter(
            "pt_executor_cache_misses_total").value() == before
        assert monitor.counter(
            "pt_executor_cache_hits_total").value() == 1


# --------------------------------------------------------------------------
# the content fingerprint: ONE helper for the executor key, the lint-once
# cache and the compile report's cache_key
# --------------------------------------------------------------------------

def test_program_fingerprint_is_content_keyed_across_builds():
    """Two identically-built programs (different uids — the
    cross-process stand-in) fingerprint identically; any content change
    diverges."""
    m1, _, _ = _build(stateless=True)
    m2, _, _ = _build(stateless=True)
    assert m1._uid != m2._uid
    assert m1.content_digest() == m2.content_digest()
    fp = fingerprint.program_fingerprint
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
    in-process caches (local- prefix)."""
    main, startup, out = _build(stateless=True)
    monkeypatch.setattr(fluid.framework.Program, "content_digest",
                        lambda self: (_ for _ in ()).throw(TypeError("x")))
    fp = fingerprint.program_fingerprint(main)
    assert fp.startswith("local-")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[out])
        hits = _counts()[0]
        exe.run(main, feed=_feed(), fetch_list=[out])
    assert _counts()[0] == hits + 1


def test_lint_once_cache_is_content_keyed_via_canonical_fingerprint():
    """The static verifier's lint-once cache keys on the same canonical
    fingerprint: two identically-built programs share ONE lint run."""
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
    flags.set_flags({"compile_report_dir": str(d)})
    try:
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


def test_fingerprint_memo_serves_the_hot_path_and_is_bounded(monkeypatch):
    """``fingerprint_for`` is what Executor.run calls on every step: a
    seen identity tuple is one dict read (the digest is not computed
    again), and the memo holds at most ``_FP_CAP`` signatures."""
    main, _, _ = _build(stateless=True)
    calls = []
    real = fingerprint.program_fingerprint

    def counted(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(fingerprint, "program_fingerprint", counted)
    monkeypatch.setattr(fingerprint, "_FP_CAP", 4)
    ident = ("t-memo", main._uid, main.version)
    fp = fingerprint.fingerprint_for(ident, main, feed_sig=("x",))
    assert fingerprint.fingerprint_for(ident, main, feed_sig=("x",)) == fp
    assert len(calls) == 1
    assert fp == real(main, feed_sig=("x",))
    for i in range(8):
        fingerprint.fingerprint_for(ident + (i,), main, feed_sig=("x", i))
    assert len(fingerprint._FP_MEMO) == 4
    assert ident not in fingerprint._FP_MEMO  # oldest went first


def test_run_steps_entries_are_steps_keyed():
    """``steps`` is a static argument of the window's jit: a different
    count is a different entry, reported as its own miss."""
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for steps, outcome in ((3, "miss"), (3, "hit"), (2, "miss")):
            exe.run_steps(main, feed_list=[_feed()], steps=steps,
                          fetch_list=[loss])
            rec = monitor.recent_steps()[-1]
            assert rec["cache"] == outcome, (steps, rec)
            assert (rec["compile_ms"] is None) == (outcome == "hit")


# --------------------------------------------------------------------------
# one executable per program, and it donates its state
# --------------------------------------------------------------------------

def _call(exe, mode, main, loss):
    if mode == "run_steps":
        return exe.run_steps(main, feed_list=[_feed()], steps=2,
                             fetch_list=[loss])
    if mode == "data_parallel":
        main = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
    return exe.run(main, feed=_feed(8), fetch_list=[loss])


@pytest.mark.parametrize("mode", ["run", "run_steps", "data_parallel"])
def test_state_is_donated(mode):
    """A step consumes the state it was given: after the call the
    previous parameter arrays are deleted and the scope holds the new
    ones (no second copy of the state in flight)."""
    main, startup, loss = _build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        l0 = float(np.asarray(_call(exe, mode, main, loss)[0]))
        _, lowered = next(reversed(exe._cache.values()))
        names = list(lowered.state_in_names)
        assert names
        before = {n: scope.find_var(n) for n in names}
        l1 = float(np.asarray(_call(exe, mode, main, loss)[0]))
        assert all(v.is_deleted() for v in before.values()), [
            n for n, v in before.items() if not v.is_deleted()]
        assert not any(scope.find_var(n).is_deleted() for n in names)
        # a fresh executor (its own entry, its own executable) carries
        # the trained state on from the scope
        l2 = float(np.asarray(
            _call(fluid.Executor(fluid.CPUPlace()), mode, main, loss)[0]))
    assert l2 < l1 < l0
