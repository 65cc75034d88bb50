"""Elastic scale-OUT (ISSUE 14): the grow half of fleet elasticity.

Before ISSUE 14 the contract was shrink-only: ``plan_resize`` had no
``joins`` parameter at all — a grow spec was inexpressible
(``TypeError: unexpected keyword argument 'joins'``) and a world could
only ever get smaller. The first tests below pin the after-contract: a
grow spec admits joining workers with deterministic rank assignment.

The full 4->8 grow drill (seeded, multi-process, loss parity, with
newcomers reading their XLA compiles from jax's persistent cache) is
the ``chaos``-marked test at the bottom of tests/test_elastic_resize.py.
"""

import json
import threading
import time

import pytest

from paddle_tpu import faults, flags, monitor
from paddle_tpu.incubate.fleet.fleet_base import Fleet


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    yield
    faults.disarm()


# --------------------------------------------------------------------------
# before/after contract: plan_resize admits a grow spec
# --------------------------------------------------------------------------

def test_plan_resize_admits_grow_spec():
    """BEFORE: ``plan_resize(..., joins=...)`` raised TypeError (the
    parameter did not exist; the planner could only shrink). AFTER: a
    grow spec assigns joiners the ranks past the survivors, survivors
    keep relative order, and every participant derives the identical
    world from the same (dead, joins) agreement."""
    f = Fleet()
    spec = f.plan_resize((), joins=[0, 1, 2, 3], rank=2, world=4)
    assert spec["survivors"] == [0, 1, 2, 3]
    assert spec["world"] == 8 and spec["rank"] == 2
    assert spec["joiners"] == [[0, 4], [1, 5], [2, 6], [3, 7]]
    # a joiner derives ITS rank from the same agreement
    jspec = f.plan_resize((), joins=[0, 1, 2, 3], join_id=2, world=4)
    assert jspec["rank"] == 6 and jspec["world"] == 8
    assert jspec["survivors"] == spec["survivors"]
    assert jspec["joiners"] == spec["joiners"]


def test_plan_resize_grow_and_shrink_compose():
    """Replacement flow: dead workers leave AND fresh capacity joins in
    one resize — survivors first (relative order kept), joiners after."""
    f = Fleet()
    spec = f.plan_resize(["worker-1"], joins=[7], rank=2, world=4)
    assert spec["survivors"] == [0, 2, 3]
    assert spec["dead"] == [1]
    assert spec["world"] == 4 and spec["rank"] == 1
    assert spec["joiners"] == [[7, 3]]
    jspec = f.plan_resize(["worker-1"], joins=[7], join_id=7, world=4)
    assert jspec["rank"] == 3


def test_plan_resize_rejects_joiner_id_not_in_joins():
    f = Fleet()
    with pytest.raises(ValueError, match="join"):
        f.plan_resize((), joins=[0, 1], join_id=5, world=4)


# --------------------------------------------------------------------------
# settle_joins / join_world over a stub KV (the in-process protocol half)
# --------------------------------------------------------------------------

class _StubRole:
    def __init__(self, rank, world):
        self._r, self._n = rank, world

    def worker_index(self):
        return self._r

    def worker_num(self):
        return self._n


class _StubClient:
    """In-memory coord KV stand-in (tests/test_elastic_resize.py's, plus
    delete): shared dict + lock, blocking get with timeout."""

    def __init__(self, store, lock, dead=()):
        self._store, self._lock, self._dead = store, lock, list(dead)

    def put(self, key, value):
        with self._lock:
            self._store[key] = bytes(value)

    def get(self, key, timeout_ms=-1, max_len=0):
        deadline = time.monotonic() + max(0, timeout_ms) / 1000.0
        while True:
            with self._lock:
                if key in self._store:
                    return self._store[key]
            if time.monotonic() >= deadline:
                raise TimeoutError(key)
            time.sleep(0.002)

    def heartbeat(self, worker_id):
        pass

    def dead_peers(self, max_age_ms):
        return list(self._dead)

    def delete(self, key):
        with self._lock:
            self._store.pop(key, None)

    def close(self):
        pass


def _stub_fleet(rank, world, store, lock):
    f = Fleet()
    f._role = _StubRole(rank, world)
    f._client = _StubClient(store, lock)
    f._initialized = True
    return f


def test_settle_joins_converges_on_announced_set():
    """Two survivors observe announcements landing at different times;
    settle_joins holds the stability window open until the set stops
    growing, the leader publishes, the peer adopts + acks — the same
    agreement discipline settle_dead uses for deaths."""
    store, lock = {}, threading.Lock()
    f0 = _stub_fleet(0, 2, store, lock)
    f1 = _stub_fleet(1, 2, store, lock)
    # joiner 0 announced already; joiner 1 lands mid-window
    store["fleet/join/g0/0"] = b"1"

    def _late_announce():
        time.sleep(0.03)
        with lock:
            store["fleet/join/g0/1"] = b"1"

    out = {}

    def _run(rank, fobj):
        out[rank] = fobj.settle_joins(max_age_ms=120, poll_ms=10,
                                      timeout_ms=5000, min_count=1)

    ts = [threading.Thread(target=_late_announce),
          threading.Thread(target=_run, args=(0, f0)),
          threading.Thread(target=_run, args=(1, f1))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert out == {0: [0, 1], 1: [0, 1]}
    assert store["fleet/resize/joins/g0"] == b"0,1"
    assert store["fleet/resize/jsack/g0/1"] == b"1"


def test_pending_joins_probes_contiguous_slots():
    store, lock = {}, threading.Lock()
    f = _stub_fleet(0, 2, store, lock)
    assert f.pending_joins() == []
    store["fleet/join/g0/0"] = b"1"
    store["fleet/join/g0/1"] = b"1"
    assert f.pending_joins() == [0, 1]
    # known ids are reported without re-probing (settle_joins'
    # accumulated set keeps each poll tick under the 64-slot scan)
    assert f.pending_joins(known=[0]) == [0, 1]


def test_settle_joins_composed_with_dead_uses_surviving_leader():
    """The composed shrink+grow resize: settle_joins(dead=) derives
    the leader and the ack set from the SURVIVORS. With rank 0 dead,
    rank 1 leads (publishes, collects rank 2's ack) — a dead rank is
    never waited on, so replacement-in-one-resize completes instead of
    timing out against acks nobody will write."""
    store, lock = {}, threading.Lock()
    store["fleet/join/g0/3"] = b"1"
    dead = ["worker-0"]
    f1 = _stub_fleet(1, 3, store, lock)
    f2 = _stub_fleet(2, 3, store, lock)
    out = {}

    def _run(rank, fobj):
        out[rank] = fobj.settle_joins(max_age_ms=60, poll_ms=10,
                                      timeout_ms=5000, min_count=1,
                                      dead=dead)

    ts = [threading.Thread(target=_run, args=(1, f1)),
          threading.Thread(target=_run, args=(2, f2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert out == {1: [3], 2: [3]}
    assert store["fleet/resize/joins/g0"] == b"3"
    assert store["fleet/resize/jsack/g0/2"] == b"1"
    assert "fleet/resize/jsack/g0/0" not in store  # dead: never awaited
    # and the composed plan seats the joiner after the survivors
    spec = f1.plan_resize(dead, joins=out[1], rank=1, world=3)
    assert spec == {"survivors": [1, 2], "rank": 0, "world": 3,
                    "dead": [0], "joiners": [[3, 2]]}


def test_join_world_announce_plan_ack_roundtrip():
    """The newcomer half: announce under the generation key, wait for
    the leader's published plan, ack, return the spec (with the
    recovery endpoints and the newcomer's assigned rank)."""
    store, lock = {}, threading.Lock()
    monitor.enable()
    # the running world published its generation at init (join_world
    # blocks on this key, bounded, before announcing)
    store["fleet/generation"] = b"0"
    leader = _stub_fleet(0, 4, store, lock)
    plan = leader.plan_resize((), joins=[0], rank=0, world=4)
    joins_before = monitor.histogram("pt_fleet_join_seconds").count()

    newcomer = Fleet()
    newcomer._role = _StubRole(0, 1)

    def _leader_side():
        # wait for the announce, then publish the plan like the drill's
        # leader does (publish_join_plan waits for the joiner acks)
        c = _StubClient(store, lock)
        c.get("fleet/join/g0/0", timeout_ms=5000)
        leader.publish_join_plan(
            plan, coord_endpoint="127.0.0.1:9999",
            jax_endpoint="127.0.0.1:9998", timeout_ms=5000)

    t = threading.Thread(target=_leader_side)
    t.start()
    spec = newcomer.join_world(
        "stub", join_id=0, timeout_ms=5000,
        _client=_StubClient(store, lock))
    t.join(10)
    assert spec["rank"] == 4 and spec["world"] == 5
    assert spec["coord_endpoint"] == "127.0.0.1:9999"
    assert spec["jax_endpoint"] == "127.0.0.1:9998"
    assert spec["gen"] == 1
    assert store["fleet/resize/jack/g0/0"] == b"1"
    assert monitor.histogram(
        "pt_fleet_join_seconds").count() == joins_before + 1


def test_fleet_join_fault_site_tears_the_admission():
    """Chaos plans tear admissions at the fleet.join site: the announce
    raises, nothing is published, the injection is metered."""
    monitor.enable()
    store, lock = {}, threading.Lock()
    store["fleet/generation"] = b"0"
    newcomer = Fleet()
    newcomer._role = _StubRole(0, 1)
    inj0 = monitor.counter("pt_fault_injected_total").value(
        labels={"site": "fleet.join"})
    faults.arm("fleet.join:raise@1")
    with pytest.raises(faults.InjectedFault):
        newcomer.join_world("stub", join_id=0, timeout_ms=100,
                            _client=_StubClient(store, lock))
    faults.disarm()
    assert monitor.counter("pt_fault_injected_total").value(
        labels={"site": "fleet.join"}) == inj0 + 1
    assert "fleet/join/g0/0" not in store


def test_join_world_rejects_out_of_range_slot():
    """An announce outside the probed slot range would be a silent
    deterministic hang (pending_joins never sees it) — reject it
    loudly instead."""
    f = Fleet()
    for bad in (-1, 64, 1000):
        with pytest.raises(ValueError, match="join_id"):
            f.join_world("stub", join_id=bad, timeout_ms=50,
                         _client=_StubClient({}, threading.Lock()))


def test_pending_joins_surfaces_connection_failure():
    """A broken coord connection must not read as 'no joiners
    announced' — settle_joins would agree on an EMPTY set and bump the
    generation while the announced joiners hang. TimeoutError (slot
    absent) is the expected answer; other OSErrors propagate."""

    class _Broken:
        def get(self, key, timeout_ms=0, max_len=0):
            raise ConnectionResetError("coord connection died")

    f = Fleet()
    f._role = _StubRole(0, 2)
    f._client = _Broken()
    f._initialized = True
    with pytest.raises(ConnectionResetError):
        f.pending_joins()


# --------------------------------------------------------------------------
# reexec env completeness for a grown world (the satellite bugfix)
# --------------------------------------------------------------------------

def test_reexec_resized_grow_env_is_complete_for_newcomers(monkeypatch):
    """The shrink-only env assembly leaked generation-N endpoints into
    generation N+1: a newcomer that announced against the OLD world
    inherited a stale PT_JAX_COORD_ENDPOINT (the dead generation's PJRT
    coordinator) whenever the caller passed none, and its PT_GEN
    derived from its own (zero) generation instead of the plan's. The
    grow spec's env must be complete and self-consistent: rank/world
    from the spec, endpoints from the plan, stale inherited vars
    scrubbed."""
    import paddle_tpu.incubate.fleet.fleet_base as fb

    calls = {}
    monkeypatch.setattr(
        fb._os, "execve",
        lambda exe, args, env: calls.update(exe=exe, args=args, env=env))
    monkeypatch.setattr(fb._sys, "argv", ["/work/train.py"])
    # the newcomer's inherited env points at the OLD world
    monkeypatch.setenv("PT_JAX_COORD_ENDPOINT", "10.0.0.1:555")
    monkeypatch.setenv("PT_TRAINER_ID", "0")
    monkeypatch.setenv("PT_TRAINERS", "1")

    f = Fleet()
    spec = f.plan_resize((), joins=[0, 1, 2, 3], join_id=1, world=4)
    spec["gen"] = 1
    f.reexec_resized(spec, coord_endpoint="127.0.0.1:7777")
    env = calls["env"]
    assert env["PT_TRAINER_ID"] == "5" and env["PT_TRAINERS"] == "8"
    assert env["PT_COORD_ENDPOINT"] == "127.0.0.1:7777"
    assert env["PT_GEN"] == "1"  # the plan's generation, not ours+1
    # the stale jax coordinator must NOT survive into the new world
    assert "PT_JAX_COORD_ENDPOINT" not in env
    # explicit endpoint still lands
    f2 = Fleet()
    f2.reexec_resized(dict(spec), coord_endpoint="127.0.0.1:7777",
                      jax_endpoint="127.0.0.1:7778")
    assert calls["env"]["PT_JAX_COORD_ENDPOINT"] == "127.0.0.1:7778"


def test_reexec_resized_meters_direction():
    """pt_fleet_resizes_total now carries the direction label; the
    verdict derives from the SPEC through the one resize_direction
    helper (grow = the resize admits joiners, per the metric's doc —
    a composed replacement that loses as many ranks as it admits is
    still an admission event), so survivors and joiners meter
    identically."""
    from paddle_tpu.incubate.fleet.fleet_base import resize_direction

    f0 = Fleet()
    assert resize_direction(
        f0.plan_resize(["worker-1"], joins=[7], rank=0, world=4)) == \
        "grow"  # replacement-in-one-resize admits a joiner
    assert resize_direction(
        f0.plan_resize(["worker-1"], rank=0, world=4)) == "shrink"
    import paddle_tpu.incubate.fleet.fleet_base as fb

    monitor.enable()

    class _NoExec:
        @staticmethod
        def execve(exe, args, env):
            pass

    orig = fb._os.execve
    fb._os.execve = _NoExec.execve
    try:
        f = Fleet()
        g0 = monitor.counter("pt_fleet_resizes_total").value(
            labels={"direction": "grow"})
        s0 = monitor.counter("pt_fleet_resizes_total").value(
            labels={"direction": "shrink"})
        f.reexec_resized(f.plan_resize((), joins=[0], rank=0, world=2),
                         coord_endpoint="127.0.0.1:1")
        f.reexec_resized(f.plan_resize([1], rank=0, world=2),
                         coord_endpoint="127.0.0.1:1")
        assert monitor.counter("pt_fleet_resizes_total").value(
            labels={"direction": "grow"}) == g0 + 1
        assert monitor.counter("pt_fleet_resizes_total").value(
            labels={"direction": "shrink"}) == s0 + 1
    finally:
        fb._os.execve = orig


# --------------------------------------------------------------------------
# /fleet: joining ranks transition missing -> alive (in-process)
# --------------------------------------------------------------------------

def test_fleet_view_joining_ranks_transition_missing_to_alive():
    """The grown world's cluster view before the newcomers' first
    digest publish names them ``missing``; after they publish they are
    alive rows — the /fleet transition the drill watches."""
    from paddle_tpu import fleet_monitor

    flags.set_flags({"telemetry": True, "fleet_metrics_interval_ms": 0})
    try:
        store, lock = {}, threading.Lock()

        class _F:
            _client = _StubClient(store, lock)
            _role = None

            def generation(self):
                return 1

            def worker_num(self):
                return 8

        for r in range(4):  # survivors published; joiners not yet
            d = fleet_monitor.registry_digest(rank=r, world=8, gen=1)
            store[f"fleet/metrics/g1/{r}"] = json.dumps(d).encode()
        view = fleet_monitor.aggregate(_F())
        assert view["missing"] == [4, 5, 6, 7]
        for r in range(4, 8):  # the newcomers' first publish lands
            d = fleet_monitor.registry_digest(rank=r, world=8, gen=1)
            store[f"fleet/metrics/g1/{r}"] = json.dumps(d).encode()
        view = fleet_monitor.aggregate(_F())
        assert view["missing"] == []
        assert set(view["ranks"]) == {str(r) for r in range(8)}
        assert view["dead"] == []
    finally:
        flags.set_flags({"telemetry": False,
                         "fleet_metrics_interval_ms": 1000})
        fleet_monitor.reset()
