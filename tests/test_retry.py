"""Unified retry/backoff policy tests (paddle_tpu/retry.py) and its
fleet threading: decorrelated-jitter backoff under a deadline budget,
``pt_retry_total`` accounting, and the coord KV-get timeout contract
(retried with backoff, raising at the deadline)."""

import random
import socket
import time
import tracemalloc

import pytest

import paddle_tpu as fluid  # noqa: F401
from paddle_tpu import flags, monitor, retry


@pytest.fixture(autouse=True)
def _clean():
    yield
    flags.set_flags({"telemetry": False,
                     "retry_base_delay_ms": 100,
                     "retry_max_delay_ms": 5000,
                     "retry_max_attempts": 0})


@pytest.fixture
def sleeps(monkeypatch):
    out = []
    monkeypatch.setattr(retry, "_sleep", out.append)
    return out


def test_first_try_success_no_sleep_no_metric(sleeps):
    monitor.enable()
    assert retry.call(lambda: 7, site="t") == 7
    assert sleeps == []
    snap = monitor.snapshot()["pt_retry_total"]
    assert snap["values"] == [] or not any(
        v for v in snap["values"])  # no cells at all


def test_retries_then_success_with_backoff(sleeps):
    monitor.enable()
    state = {"n": 0}

    def fn():
        state["n"] += 1
        if state["n"] < 4:
            raise OSError("flaky")
        return "ok"

    p = retry.RetryPolicy(base_delay=0.1, max_delay=2.0)
    out = retry.call(fn, site="flaky", policy=p, rng=random.Random(0))
    assert out == "ok" and state["n"] == 4
    assert len(sleeps) == 3
    # decorrelated jitter: first sleep is the base, then uniform in
    # [base, 3*prev] capped — always within [base, max_delay]
    assert sleeps[0] == pytest.approx(0.1)
    for s in sleeps:
        assert 0.1 <= s <= 2.0
    c = monitor.counter("pt_retry_total")
    assert c.value(labels={"site": "flaky", "outcome": "retry"}) == 3
    assert c.value(labels={"site": "flaky", "outcome": "success"}) == 1


def test_seeded_rng_makes_backoff_deterministic(sleeps):
    def run():
        del sleeps[:]
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            if calls["n"] < 6:
                raise OSError()
            return 1

        retry.call(fn, site="d", rng=random.Random(42),
                   policy=retry.RetryPolicy(base_delay=0.01, max_delay=1.0))
        return list(sleeps)

    assert run() == run()


def test_deadline_budget_raises_the_original_error():
    monitor.enable()

    def fn():
        raise TimeoutError("not yet")

    p = retry.RetryPolicy(base_delay=0.02, max_delay=0.05)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="not yet"):
        retry.call(fn, site="dl", policy=p, deadline_s=0.2)
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0  # gave up at the budget, not much later
    c = monitor.counter("pt_retry_total")
    assert c.value(labels={"site": "dl", "outcome": "exhausted"}) == 1
    assert c.value(labels={"site": "dl", "outcome": "retry"}) >= 1


def test_max_attempts_cap(sleeps):
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise OSError()

    p = retry.RetryPolicy(base_delay=0.001, max_attempts=3)
    with pytest.raises(OSError):
        retry.call(fn, site="cap", policy=p)
    assert calls["n"] == 3


def test_non_retryable_exception_propagates_immediately(sleeps):
    def fn():
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry.call(fn, site="nr")
    assert sleeps == []


def test_default_policy_tracks_flags():
    flags.set_flags({"retry_base_delay_ms": 7, "retry_max_delay_ms": 70,
                     "retry_max_attempts": 2})
    p = retry.default_policy()
    assert p.base_delay == pytest.approx(0.007)
    assert p.max_delay == pytest.approx(0.070)
    assert p.max_attempts == 2


def test_sleeps_never_overshoot_the_deadline(monkeypatch):
    slept = []

    def fake_sleep(s):
        slept.append(s)

    monkeypatch.setattr(retry, "_sleep", fake_sleep)

    def fn():
        raise OSError()

    p = retry.RetryPolicy(base_delay=10.0, max_delay=100.0)
    with pytest.raises(OSError):
        retry.call(fn, site="clamp", policy=p, deadline_s=0.05)
    assert all(s <= 0.05 + 1e-6 for s in slept)


# --------------------------------------------------------------------------
# fleet threading: kv-get timeout retried with backoff, raising at the
# deadline (ISSUE 5 acceptance)
# --------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_fleet_kv_get_retries_then_raises_at_deadline():
    from paddle_tpu import native
    from paddle_tpu.incubate.fleet import UserDefinedRoleMaker
    from paddle_tpu.incubate.fleet.fleet_base import Fleet

    if not native.available():
        pytest.skip("native library not built")
    monitor.enable()
    flags.set_flags({"retry_base_delay_ms": 20, "retry_max_delay_ms": 100})
    port = _free_port()
    f = Fleet()
    f._role = UserDefinedRoleMaker(current_id=0, worker_num=1)
    f._server = native.CoordServer(port)
    f._client = native.CoordClient("127.0.0.1", port)
    try:
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            f.get("never/published", timeout_ms=300)
        elapsed = time.monotonic() - t0
        assert 0.2 <= elapsed < 3.0  # spent ~the budget, then gave up
        c = monitor.counter("pt_retry_total")
        assert c.value(labels={"site": "fleet.kv_get",
                               "outcome": "retry"}) >= 1
        assert c.value(labels={"site": "fleet.kv_get",
                               "outcome": "exhausted"}) == 1
        # a published key still comes straight back
        f.put("k", b"v")
        assert f.get("k", timeout_ms=1000) == b"v"
        # timeout_ms=0 is a real non-blocking present-check, not a
        # synthesized timeout (code-review finding, round 5)
        assert f.get("k", timeout_ms=0) == b"v"
        with pytest.raises(TimeoutError):
            f.get("still/missing", timeout_ms=0)
    finally:
        f.stop_worker()


def test_fleet_connect_uses_retry_policy(monkeypatch):
    """_connect_retry keeps polling until the server exists, under the
    policy (no fixed 0.1 s spin)."""
    from paddle_tpu import native
    from paddle_tpu.incubate.fleet import fleet_base

    if not native.available():
        pytest.skip("native library not built")
    monitor.enable()
    flags.set_flags({"retry_base_delay_ms": 10, "retry_max_delay_ms": 50})
    port = _free_port()
    server = {}

    real_sleep = time.sleep

    def sleep_then_start(s):
        real_sleep(s)
        if "s" not in server:  # bring the server up after the 1st backoff
            server["s"] = native.CoordServer(port)

    monkeypatch.setattr(retry, "_sleep", sleep_then_start)
    try:
        client = fleet_base._connect_retry("127.0.0.1", port,
                                           timeout_ms=5000)
        client.close()
        c = monitor.counter("pt_retry_total")
        assert c.value(labels={"site": "fleet.connect",
                               "outcome": "success"}) == 1
    finally:
        if "s" in server:
            server["s"].stop()


# --------------------------------------------------------------------------
# zero-overhead contract: a first-try success allocates nothing in
# retry.py (the coordination hot loop — heartbeats — rides this path)
# --------------------------------------------------------------------------

def test_success_path_allocates_nothing_in_retry():
    assert not monitor.enabled()

    def fn():
        return None

    for _ in range(3):
        retry.call(fn, site="hot")  # warm the cached default policy
    n = 2000
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for _ in range(n):
        retry.call(fn, site="hot")
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grew = sum(
        st.size_diff for st in snap.compare_to(base, "filename")
        if st.traceback[0].filename.endswith("retry.py")
        and st.size_diff > 0)
    assert grew < n, f"retry.call success path allocated {grew}B"
