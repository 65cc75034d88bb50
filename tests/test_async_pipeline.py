"""Async executor pipeline (PR 10 tentpole): sampled phase attribution
(`step_phases_every_n`), the all-device feed staging skip, overlapped
fetch (`LazyFetches` + deferred-error hygiene), DeviceLoader lifecycle
(abandoned-consumer stop event, PyReader reset), trainer prefetch
equivalence, and the disabled-path zero-allocation contract."""

import threading
import time
import tracemalloc

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, flags, layers, monitor
from paddle_tpu.executor import LazyFetches
from paddle_tpu.reader.pipeline import DeviceLoader, PyReader

_RESET_FLAGS = {"telemetry": False, "step_phases": True,
                "step_phases_every_n": 16, "prefetch_depth": 2,
                "check_nan_inf": False}


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    flags.set_flags(dict(_RESET_FLAGS))
    yield
    faults.disarm()
    flags.set_flags(dict(_RESET_FLAGS))


def _tiny_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _no_loader_threads(timeout=5.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if not any(t.name == "pt-device-loader" and t.is_alive()
                   for t in threading.enumerate()):
            return True
        time.sleep(0.01)
    return False


# --------------------------------------------------------------------------
# DeviceLoader lifecycle (satellites: abandoned consumer + PyReader)
# --------------------------------------------------------------------------

def test_device_loader_abandoned_consumer_unblocks_worker():
    """A consumer that stops iterating early must release the worker:
    before the stop event, the daemon blocked forever on q.put with up
    to `depth` device-resident batches pinned."""
    produced = []

    def reader():
        for i in range(50):
            produced.append(i)
            yield {"x": np.full((2, 2), i, np.float32)}

    loader = DeviceLoader(reader, feed_names=["x"], depth=2)
    it = iter(loader)
    _stop, _q, thread = loader._active
    first = next(it)
    assert set(first) == {"x"} and isinstance(first["x"], jax.Array)
    it.close()  # the consumer breaks after one batch
    thread.join(5.0)
    assert not thread.is_alive(), "worker still blocked after close"
    assert loader._active is None
    # bounded read-ahead: the worker never drained the 50-batch reader
    assert len(produced) <= 8, produced


def test_device_loader_break_in_for_loop_releases_worker():
    def reader():
        while True:
            yield {"x": np.zeros((2, 2), np.float32)}

    loader = DeviceLoader(reader, feed_names=["x"], depth=3)
    for i, batch in enumerate(loader):
        if i >= 1:
            break
    del batch
    loader.close()  # explicit close is idempotent with GeneratorExit
    assert _no_loader_threads()


def test_device_loader_reiteration_does_not_leak_previous_worker():
    def reader():
        while True:
            yield {"x": np.zeros((2, 2), np.float32)}

    loader = DeviceLoader(reader, feed_names=["x"], depth=2)
    it1 = iter(loader)
    _stop1, _q1, t1 = loader._active
    next(it1)
    it2 = iter(loader)  # restarts: the previous worker must exit
    t1.join(5.0)
    assert not t1.is_alive()
    next(it2)
    loader.close()
    assert _no_loader_threads()


def test_pyreader_reset_stops_active_loader():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")

    def batches():
        while True:
            yield [(np.ones(4, np.float32),)] * 2

    pr = PyReader(feed_list=[x], capacity=2)
    pr.decorate_sample_list_generator(batches)
    it1 = iter(pr)
    assert set(next(it1)) == {"x"}
    t1 = pr._loader._active[2]
    # re-iteration stops the previous iteration's worker (the old
    # silent-no-op start()/reset() leaked it)
    it2 = iter(pr)
    t1.join(5.0)
    assert not t1.is_alive()
    assert set(next(it2)) == {"x"}
    pr.reset()
    assert _no_loader_threads()
    pr.start()  # decorated: validates, does not raise
    with pytest.raises(RuntimeError, match="no reader"):
        PyReader(feed_list=[x]).start()


def test_device_loader_exhaustion_still_propagates_reader_error():
    def bad_reader():
        yield {"x": np.zeros((2, 2), np.float32)}
        raise ValueError("producer died")

    loader = DeviceLoader(bad_reader, feed_names=["x"], depth=2)
    out = []
    with pytest.raises(RuntimeError, match="producer died"):
        for b in loader:
            out.append(b)
    assert len(out) == 1
    assert _no_loader_threads()


# --------------------------------------------------------------------------
# feed-staging skip (satellite): all-jax.Array feeds, zero device_put
# --------------------------------------------------------------------------

def test_all_device_feed_skips_staging_plain_and_compiled(monkeypatch):
    flags.set_flags({"telemetry": True, "step_phases_every_n": 1})
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed_np = {"x": np.ones((2, 8), np.float32)}
    dev_feed = {k: jax.device_put(v) for k, v in feed_np.items()}
    cp = fluid.CompiledProgram(main)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=dev_feed, fetch_list=[loss])   # warm compile
        exe.run(cp, feed=dev_feed, fetch_list=[loss])
        calls = []
        real = jax.device_put

        def spy(*a, **k):
            calls.append(a)
            return real(*a, **k)

        monkeypatch.setattr(jax, "device_put", spy)
        # device-resident feeds: zero additional device_put on BOTH the
        # plain and the compiled path, even on sampled (staging) steps
        exe.run(main, feed=dev_feed, fetch_list=[loss])
        assert calls == []
        exe.run(cp, feed=dev_feed, fetch_list=[loss])
        assert calls == []
        # host numpy feeds DO stage through device_put (sampled path)
        exe.run(main, feed=feed_np, fetch_list=[loss])
        assert len(calls) == 1


# --------------------------------------------------------------------------
# sampled phase attribution
# --------------------------------------------------------------------------

def test_sampled_phase_records_follow_the_period():
    flags.set_flags({"telemetry": True, "step_phases_every_n": 3})
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(8):
            exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                    fetch_list=[loss])
    recs = monitor.recent_steps()
    assert len(recs) == 9
    for rec in recs:
        monitor.validate_step_record(rec)
        want = rec["step"] % 3 == 0
        assert rec["sampled"] is want, rec
        assert ("phases" in rec) == want
        if not want:
            assert "bound" not in rec
    # scored = sampled AND committed AND cache-hit (steps 3 and 6 here;
    # step 0 is the startup compile miss)
    scored = [r for r in recs if "bound" in r]
    assert [r["step"] for r in scored] == [3, 6]
    assert all(r["cache"] == "hit" for r in scored)
    assert monitor.boundedness()["steps"] == 2


def test_window_sampling_matches_any_step_in_window():
    flags.set_flags({"telemetry": True, "step_phases_every_n": 5})
    assert monitor.phases_sampled(0)
    assert not monitor.phases_sampled(4)
    assert monitor.phases_sampled(4, steps=2)   # window [4, 6) holds 5
    assert not monitor.phases_sampled(1, steps=4)  # [1, 5) misses 5
    flags.set_flags({"step_phases": False})
    assert not monitor.phases_sampled(0)


def test_unsampled_steps_discard_input_wait_backlog():
    """Input waits accumulated by unsampled steps must not pile into the
    next sampled step's verdict — the sampled step scores only its own
    input time (else the input share inflates by the period length)."""
    flags.set_flags({"telemetry": True, "step_phases_every_n": 3})
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)  # step 0: sampled compile (unscored)
        exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                fetch_list=[loss])  # step 1: unsampled compile
        monitor.note_input_wait(30.0)  # backlog before unsampled step 2
        exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                fetch_list=[loss])  # step 2: unsampled -> discards
        exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                fetch_list=[loss])  # step 3: sampled hit, scored
    b = monitor.boundedness()
    assert b is not None and b["steps"] == 1
    assert b["verdict"] != "input_bound", b


# --------------------------------------------------------------------------
# overlapped fetch: LazyFetches + deferred-error hygiene
# --------------------------------------------------------------------------

def test_async_fetch_returns_lazy_fetches_with_correct_values():
    flags.set_flags({"telemetry": True, "step_phases_every_n": 1})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 4], append_batch_size=False,
                        stop_gradient=True)
        s = layers.reduce_sum(x)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sync = exe.run(main, feed={"x": np.full((4, 4), 2.0, np.float32)},
                   fetch_list=[s])
    out = exe.run(main, feed={"x": np.full((4, 4), 2.0, np.float32)},
                  fetch_list=[s], async_fetch=True)
    assert isinstance(out, LazyFetches) and not out.ready
    assert len(out) == 1
    assert float(np.asarray(out[0])) == float(np.asarray(sync[0])) == 32.0
    assert out.ready
    # materialization observed the overlap histogram exactly once, and
    # repeated access does not re-observe
    _ = out[0]
    assert monitor.histogram("pt_fetch_overlap_seconds").count() == 1


def test_run_steps_async_fetch():
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 8), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        ref = exe.run_steps(main, feed_list=[feed], steps=3,
                            fetch_list=[loss], scope=scope)
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(startup, scope=scope2)
        out = exe2.run_steps(main, feed_list=[feed], steps=3,
                             fetch_list=[loss], scope=scope2,
                             async_fetch=True)
    assert isinstance(out, LazyFetches)
    assert float(np.asarray(out[0])) == float(np.asarray(ref[0]))


@pytest.mark.parametrize("kind", ["step", "window"])
def test_deferred_fetch_error_runs_hygiene_and_oom_forensics(kind):
    """A device failure surfacing only at the async fetch boundary
    (drilled via the executor.fetch fault site) must run the same
    donated-buffer drop + OOM forensics as the synchronous commit
    sites, then re-raise — and leave the committed state usable. A
    step's fetches and a window's."""
    flags.set_flags({"telemetry": True})
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.ones((2, 8), np.float32)}

    def call(**kw):
        if kind == "step":
            return exe.run(main, feed=feed, fetch_list=[loss], **kw)
        return exe.run_steps(main, feed_list=[feed], steps=2,
                             fetch_list=[loss], **kw)

    with fluid.scope_guard(scope):
        exe.run(startup)
        call()  # warm compile
        faults.arm("executor.fetch:raise(RESOURCE_EXHAUSTED synthetic "
                   "deferred device OOM)@1")
        out = call(async_fetch=True)
        with pytest.raises(faults.InjectedFault):
            out.wait()
        faults.disarm()
        recs = monitor.oom_records()
        assert recs and recs[-1]["phase"] == "fetch"
        assert "RESOURCE_EXHAUSTED" in recs[-1]["error"]
        # state committed before the fetch: training continues cleanly
        nxt = call()
        assert np.isfinite(np.asarray(nxt[0])).all()


def test_prefetch_worker_oom_surfaces_with_forensics():
    """An infeed OOM (device_put in the prefetch worker, drilled via the
    pipeline.prefetch site) must surface in the consumer within one
    queue drain, carrying prefetch-phase OOM forensics."""
    flags.set_flags({"telemetry": True})

    def reader():
        for _ in range(4):
            yield {"x": np.zeros((2, 2), np.float32)}

    faults.arm("pipeline.prefetch:raise(RESOURCE_EXHAUSTED synthetic "
               "infeed OOM)@2")
    loader = DeviceLoader(reader, feed_names=["x"], depth=2)
    got = []
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED") as ei:
        for b in loader:
            got.append(b)
    assert isinstance(ei.value.__cause__, faults.InjectedFault)
    assert len(got) == 1
    recs = monitor.oom_records()
    assert recs and recs[-1]["phase"] == "prefetch"
    assert _no_loader_threads()


# --------------------------------------------------------------------------
# trainer prefetch: loss parity with the synchronous path
# --------------------------------------------------------------------------

def _trainer_pieces():
    def train_func():
        img = layers.data("img", shape=[16], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(img, 32, act="relu",
                      param_attr=fluid.ParamAttr(name="ap1.w"),
                      bias_attr=fluid.ParamAttr(name="ap1.b"))
        logits = layers.fc(h, 4,
                           param_attr=fluid.ParamAttr(name="ap2.w"),
                           bias_attr=fluid.ParamAttr(name="ap2.b"))
        return [layers.mean(
            layers.softmax_with_cross_entropy(logits, label))]

    def reader():
        def gen():
            rng = np.random.RandomState(0)
            probe = np.random.RandomState(5).randn(16, 4)
            for _ in range(8):
                x = rng.randn(32, 16).astype(np.float32)
                y = np.argmax(x @ probe, 1).astype(np.int64)
                yield list(zip(x, y))

        return gen

    return train_func, reader


def test_trainer_prefetch_matches_sync_losses():
    from paddle_tpu.contrib import EndStepEvent, Trainer

    train_func, reader = _trainer_pieces()

    def run(depth):
        flags.set_flags({"prefetch_depth": depth})
        losses = []
        t = Trainer(train_func, lambda: fluid.optimizer.SGD(0.1),
                    fluid.CPUPlace())
        t.train(2, lambda e: losses.append(float(e.metrics[0]))
                if isinstance(e, EndStepEvent) else None,
                reader(), ["img", "label"])
        return losses, t.test(reader(), ["img", "label"])

    pre_losses, pre_test = run(2)
    sync_losses, sync_test = run(0)
    assert len(pre_losses) == 16
    np.testing.assert_allclose(pre_losses, sync_losses, rtol=1e-6)
    np.testing.assert_allclose(pre_test, sync_test, rtol=1e-6)
    assert _no_loader_threads()


def test_trainer_exception_releases_prefetch_worker():
    from paddle_tpu.contrib import Trainer

    train_func, reader = _trainer_pieces()
    faults.arm("reader.next:raise@3")
    t = Trainer(train_func, lambda: fluid.optimizer.SGD(0.1),
                fluid.CPUPlace())
    with pytest.raises(faults.InjectedFault):
        t.train(1, None, reader(), ["img", "label"])
    assert _no_loader_threads()


# --------------------------------------------------------------------------
# disabled path: the zero-allocation contract for the new machinery
# --------------------------------------------------------------------------

def test_async_machinery_allocates_nothing_in_monitor_when_disabled():
    """With telemetry off, the sampled-phase gate, the staging skip and
    the async-fetch path must add zero monitor.py allocations to
    Executor.run — the same contract every prior plane honors."""
    assert not monitor.enabled()
    main, startup, loss = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": jax.device_put(np.ones((2, 8), np.float32))}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # warm compile cache + lazy interp state
            exe.run(main, feed=feed, fetch_list=[loss],
                    async_fetch=True).wait()
        n_runs = 30
        tracemalloc.start()
        base = tracemalloc.take_snapshot()
        for _ in range(n_runs):
            exe.run(main, feed=feed, fetch_list=[loss],
                    async_fetch=True).wait()
        snap = tracemalloc.take_snapshot()
        tracemalloc.stop()
    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith(
                   ("monitor.py", "faults.py"))
               and s.size_diff > 0)
    assert grew < n_runs * 16, (
        f"disabled async Executor.run allocated {grew}B in telemetry/"
        f"fault code over {n_runs} runs")


# --------------------------------------------------------------------------
# end-to-end (slow): 20-step MNIST with prefetch on — no input_bound
# verdict after warmup
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_mnist_prefetch_e2e_no_input_bound_after_warmup():
    from paddle_tpu.contrib import Trainer
    from paddle_tpu.models import mnist as mnist_model

    flags.set_flags({"telemetry": True, "step_phases_every_n": 4,
                     "prefetch_depth": 2})

    def train_func():
        model = mnist_model.get_model(use_conv=False)
        return [model["loss"]]

    def reader():
        def gen():
            rng = np.random.RandomState(0)
            for _ in range(20):
                x = rng.rand(64, 784).astype(np.float32)
                y = rng.randint(0, 10, (64, 1)).astype(np.int64)
                yield list(zip(x, y))

        return gen

    t = Trainer(train_func, lambda: fluid.optimizer.SGD(0.1),
                fluid.CPUPlace())
    t.train(1, None, reader(), ["pixel", "label"])
    c = monitor.counter("pt_step_bound_total")
    mix = {v: c.value(labels={"verdict": v})
           for v in monitor.BOUND_VERDICTS}
    # the prefetched pipeline must never starve the step loop: zero
    # input-bound verdicts across the scored (post-warmup) steps
    assert mix["input_bound"] == 0, mix
    assert sum(mix.values()) >= 3, mix  # the window actually scored
    assert _no_loader_threads()
