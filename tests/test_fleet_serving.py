"""Fleet front door (fleet_serving.py): routed multi-replica serving
with failover replay, autoscaling, and zero-downtime rolling rollout.

The load-bearing drills:

- **routing**: requests spread across replicas by estimated
  time-to-first-token, every stream byte-identical to an undisturbed
  single-engine run; a replica's refusal (QueueFull / deadline) moves
  the request to the next candidate, and the fleet sheds only when
  EVERY replica refuses.
- **kill-one-replica** (the acceptance drill): 3 replicas under load,
  one hard-killed mid-decode via ``router.replica_crash`` — every
  in-flight request still completes with byte-identical greedy tokens,
  the client-visible stream is MONOTONE across the failover (no
  duplicate, no gap), and each request's whole life stays on ONE trace
  tid.
- **journal edge cases**: replica dies mid-prefill (replay from
  scratch), mid-decode (continuation), and during a drain handoff
  (torn ``router.handoff`` degrades to hard harvest — nothing lost).
- **rollout**: a rolling weight rollout rotates every replica to the
  new generation with zero rejected-for-rollout requests; responses
  carry the generation that served them.
- **autoscale**: sustained queue saturation spins a replica up,
  sustained idleness drains-then-retires one; a spin-up reads its XLA
  compiles from jax's persistent cache (in-process under the suite's
  mesh, and across processes via tests/fleet_serve_worker.py).
"""

import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, fleet_serving, flags, monitor, serving
from paddle_tpu.models import transformer as T

from jax_cache_events import placed_in_process, run_worker

BOS, EOS = 0, 1


def tiny_cfg():
    return T.TransformerConfig(
        src_vocab_size=37, trg_vocab_size=41, max_length=64,
        d_model=16, d_inner=32, n_head=2, n_layer=1,
        dropout=0.0, label_smooth_eps=0.0,
    )


@pytest.fixture(scope="module")
def weights():
    cfg = tiny_cfg()
    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        T.build(cfg, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, scope


def _srcs(k, seed=0, lens=(5, 3, 7, 4, 6, 2, 8, 5)):
    r = np.random.RandomState(seed)
    return [r.randint(2, 37, (lens[i % len(lens)],)).astype(np.int64)
            for i in range(k)]


def _undisturbed(cfg, scope, srcs, slots=2, max_new_tokens=None):
    """Token streams of an undisturbed single-engine run at the SAME
    slot geometry as the fleet's replicas (the byte-identity oracle is
    compared executable-for-executable)."""
    eng = serving.ServingEngine(cfg, scope, slots=slots, src_len=8,
                                max_len=12, bos_id=BOS, end_id=EOS)
    out = []
    for s in srcs:
        q = eng.submit(s, max_new_tokens=max_new_tokens)
        eng.run_until_idle()
        out.append(list(q.tokens))
    eng.close()
    return out


def _fleet(cfg, scope, replicas=3, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("src_len", 8)
    kw.setdefault("max_len", 12)
    kw.setdefault("bos_id", BOS)
    kw.setdefault("end_id", EOS)
    kw.setdefault("poll_s", 0.005)
    return fleet_serving.ServingFleet(cfg, scope, replicas=replicas,
                                      **kw)


def _wait_tokens(frs, n=1, timeout=60.0):
    """Block until every request has streamed >= n tokens (the drill's
    'mid-decode' gate) — or is already terminal."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        if all(len(fr.tokens) >= n or fr.done for fr in frs):
            return
        time.sleep(0.002)
    raise TimeoutError("requests never reached mid-decode")


@pytest.fixture()
def telemetry():
    flags.set_flags({"telemetry": True})
    try:
        yield
    finally:
        flags.set_flags({"telemetry": False})


# --------------------------------------------------------------------------
# routing
# --------------------------------------------------------------------------

def test_fleet_streams_byte_identical_and_spread(weights):
    """Requests routed across the fleet produce streams byte-identical
    to an undisturbed single-engine run, and a cold fleet spreads load
    instead of piling everything on one replica."""
    cfg, scope = weights
    srcs = _srcs(6, seed=7)
    clean = _undisturbed(cfg, scope, srcs)
    fleet = _fleet(cfg, scope, replicas=3)
    try:
        frs = [fleet.submit(s) for s in srcs]
        streams = [fr.result(timeout=60) for fr in frs]
        assert streams == clean
        assert len({fr.replica_id for fr in frs}) > 1
        assert all(fr.generation == 0 for fr in frs)
        assert all(fr.outcome in ("completed", "length") for fr in frs)
    finally:
        fleet.close()


def test_router_prefers_less_loaded_replica(weights):
    """With one replica's queue stuffed, a new submit lands on the
    other (the estimated-TTFT score reads queue + in-flight backlog)."""
    cfg, scope = weights
    fleet = _fleet(cfg, scope, replicas=2, slots=1)
    try:
        faults.arm("serve.decode:delay(0.05)@p1.0", seed=3)
        try:
            first = [fleet.submit(s, max_new_tokens=6)
                     for s in _srcs(2, seed=9)]
            loaded = {fr.replica_id for fr in first}
            # both replicas now hold work; the next submit must land on
            # the one with the SMALLER backlog, never error
            nxt = fleet.submit(_srcs(1, seed=10)[0], max_new_tokens=2)
            assert nxt.replica_id in {r["replica"]
                                      for r in fleet.stats()["replicas"]}
            assert len(loaded) == 2  # the cold spread
        finally:
            faults.disarm()
        for fr in first + [nxt]:
            fr.result(timeout=60)
    finally:
        fleet.close()


def test_fleet_sheds_only_when_every_replica_refuses(weights,
                                                     telemetry):
    """Backpressure failover: submits beyond one replica's capacity
    spill to the next; once EVERY replica's queue is at capacity the
    fleet raises QueueFull (metered pt_fleet_serve_shed_total)."""
    cfg, scope = weights
    shed0 = monitor.counter("pt_fleet_serve_shed_total").value(
        labels={"kind": "queue_full"})
    fleet = _fleet(cfg, scope, replicas=2, slots=1, queue_depth=1)
    try:
        # one request through EACH replica to completion first: while a
        # replica's first request is still compiling it sits in the
        # queue, not in the slot, and the burst would be refused early
        warmup = [fleet.submit(s, max_new_tokens=2)
                  for s in _srcs(2, seed=20)]
        assert len({fr.replica_id for fr in warmup}) == 2
        for fr in warmup:
            fr.result(timeout=120)
        faults.arm("serve.decode:delay(0.1)@p1.0", seed=5)
        try:
            # capacity: 2 replicas x (1 slot + 1 queue entry) = 4
            srcs = _srcs(8, seed=21)
            # fill both slots, and let the loop threads take them out
            # of the queues, before the burst fills the queues
            admitted = [fleet.submit(s, max_new_tokens=4)
                        for s in srcs[:2]]
            t0 = time.time()
            while (fleet.stats()["queue_depth"] > 0
                   and time.time() - t0 < 30):
                time.sleep(0.002)
            with pytest.raises(serving.QueueFull):
                for s in srcs[2:]:
                    admitted.append(
                        fleet.submit(s, max_new_tokens=4))
        finally:
            faults.disarm()
        assert len(admitted) >= 3  # spilled across BOTH replicas
        assert len({fr.replica_id for fr in admitted}) == 2
        assert monitor.counter("pt_fleet_serve_shed_total").value(
            labels={"kind": "queue_full"}) > shed0
        for fr in admitted:
            fr.result(timeout=120)
    finally:
        fleet.close()


def test_router_route_site_failure_surfaces(weights):
    """router.route:raise drills a routing-plane failure: the caller
    sees the fault, no replica is charged, and the NEXT submit routes
    normally."""
    cfg, scope = weights
    fleet = _fleet(cfg, scope, replicas=2)
    try:
        clean = _undisturbed(cfg, scope, _srcs(1, seed=33))
        faults.arm("router.route:raise(routing torn)@1")
        try:
            with pytest.raises(faults.InjectedFault):
                fleet.submit(_srcs(1, seed=33)[0])
            assert fleet.stats()["in_flight"] == 0
            fr = fleet.submit(_srcs(1, seed=33)[0])  # hit 2: clean
        finally:
            faults.disarm()
        assert fr.result(timeout=60) == clean[0]
    finally:
        fleet.close()


# --------------------------------------------------------------------------
# the kill-one-replica acceptance drill + journal edge cases
# --------------------------------------------------------------------------

def test_kill_one_replica_mid_decode_chaos_drill(weights, telemetry,
                                                 tmp_path):
    """THE acceptance drill: 3 replicas under load, one hard-killed
    mid-decode (router.replica_crash). Every in-flight request
    completes with byte-identical greedy tokens, the client-visible
    stream never shrinks or duplicates across the failover, and each
    request's whole life — including the replay on the survivor —
    stays on ONE trace tid."""
    cfg, scope = weights
    flags.set_flags({"trace_dir": str(tmp_path)})
    srcs = _srcs(6, seed=41)
    clean = _undisturbed(cfg, scope, srcs, max_new_tokens=8)
    fleet = _fleet(cfg, scope, replicas=3)
    try:
        # slow decode keeps the fleet mid-flight while the kill lands
        faults.arm("serve.decode:delay(0.03)@p1.0", seed=11)
        frs = [fleet.submit(s, max_new_tokens=8) for s in srcs]
        _wait_tokens(frs, n=1)
        snapshots = {id(fr): list(fr.tokens) for fr in frs}
        # re-arm with the kill riding along (hit 1 = next pump tick).
        # The victim is the replica of the request that has streamed
        # least: each replica compiles its own programs, so the one
        # that compiled first may be done by the time the last one
        # emits a token, and a kill there would find no work
        least = min(frs, key=lambda fr: len(fr.tokens))
        victim = sorted(r["replica"] for r in fleet.stats()["replicas"]
                        ).index(least.replica_id)  # the hint is an index
        faults.arm("serve.decode:delay(0.03)@p1.0;"
                   f"router.replica_crash:raise(replica={victim})@1",
                   seed=11)
        try:
            streams = []
            for fr in frs:
                streams.append(fr.result(timeout=120))
                # monotone across the failover: the final stream
                # extends what the client had already seen
                pre = snapshots[id(fr)]
                assert streams[-1][:len(pre)] == pre
        finally:
            faults.disarm()
        assert streams == clean
        assert fleet.failovers >= 1
        assert fleet.stats()["replica_count"] == 2
        rehomed = [fr for fr in frs if fr.failovers >= 1]
        assert rehomed, "the kill landed on a replica with no work"
        for fr in rehomed:
            evs = [e for e in monitor.trace_events()
                   if e.get("args", {}).get("req") == fr.trace_id]
            tids = {e["tid"] for e in evs}
            assert tids == {fr.trace_tid}, (
                f"{fr.trace_id} smeared over tracks {tids}")
            assert [e["name"] for e in evs].count("submit") == 1
    finally:
        fleet.close()
        flags.set_flags({"trace_dir": ""})


def test_replica_dies_mid_prefill_replays_from_scratch(weights):
    """A request still queued (zero tokens — 'mid-prefill') on the
    killed replica replays from scratch on a survivor and emits the
    full byte-identical stream."""
    cfg, scope = weights
    srcs = _srcs(6, seed=55)
    clean = _undisturbed(cfg, scope, srcs, slots=1, max_new_tokens=6)
    # slots=1 per replica: with 6 requests over 2 replicas, several
    # are still queued (no tokens) when the kill lands
    fleet = _fleet(cfg, scope, replicas=2, slots=1)
    try:
        faults.arm("serve.decode:delay(0.04)@p1.0;"
                   "router.replica_crash:raise(replica=0)@3", seed=13)
        try:
            frs = [fleet.submit(s, max_new_tokens=6) for s in srcs]
            streams = [fr.result(timeout=120) for fr in frs]
        finally:
            faults.disarm()
        assert streams == clean
        assert fleet.failovers >= 1
        rehomed = [fr for fr in frs if fr.failovers >= 1]
        assert rehomed
        # the replay wiped nothing the client had: every re-homed
        # request's final stream is complete
        for fr in rehomed:
            assert fr.outcome in ("completed", "length")
    finally:
        fleet.close()


def test_replica_dies_during_drain_handoff(weights):
    """router.handoff tears a rolling-rollout drain mid-handoff: the
    draining replica is hard-harvested instead, and its requests still
    re-home and complete byte-identically — nothing finishes 'drained'
    or 'error'."""
    cfg, scope = weights
    srcs = _srcs(4, seed=61)
    clean = _undisturbed(cfg, scope, srcs, max_new_tokens=8)
    fleet = _fleet(cfg, scope, replicas=2)
    try:
        faults.arm("serve.decode:delay(0.03)@p1.0;"
                   "router.handoff:raise(handoff torn)@1", seed=17)
        try:
            frs = [fleet.submit(s, max_new_tokens=8) for s in srcs]
            _wait_tokens(frs, n=1)
            out = fleet.rollout(scope)
        finally:
            faults.disarm()
        assert out["replicas_rotated"] == 2
        streams = [fr.result(timeout=120) for fr in frs]
        assert streams == clean
        assert all(fr.outcome in ("completed", "length") for fr in frs)
    finally:
        fleet.close()


def test_budget_exhausted_supervisor_hands_off_to_fleet(weights):
    """A supervisor whose restart budget is exhausted no longer fails
    its pending requests: the on_handoff seam gives them to the fleet,
    which replays them on the survivor (outcome completed, stream
    byte-identical); the pump reaps the dead replica."""
    cfg, scope = weights
    srcs = _srcs(4, seed=71)
    clean = _undisturbed(cfg, scope, srcs, max_new_tokens=6)
    fleet = _fleet(cfg, scope, replicas=2, max_restarts=0)
    try:
        # unhinted decode raise = engine-fatal on whichever replica
        # takes hit 4; with max_restarts=0 its supervisor goes
        # terminal immediately
        faults.arm("serve.decode:delay(0.02)@p1.0;"
                   "serve.decode:raise(engine fatal)@4", seed=19)
        try:
            frs = [fleet.submit(s, max_new_tokens=6) for s in srcs]
            streams = [fr.result(timeout=120) for fr in frs]
        finally:
            faults.disarm()
        assert streams == clean
        assert all(fr.outcome in ("completed", "length") for fr in frs)
        t0 = time.time()
        while fleet.stats()["replica_count"] != 1 and \
                time.time() - t0 < 10:
            time.sleep(0.01)
        assert fleet.stats()["replica_count"] == 1
        assert fleet.failovers >= 1
    finally:
        fleet.close()


# --------------------------------------------------------------------------
# rolling rollout + autoscale
# --------------------------------------------------------------------------

def test_rolling_rollout_zero_downtime(weights):
    """rollout() rotates every replica to the new generation while
    requests keep flowing: zero rejected-for-rollout outcomes, streams
    byte-identical, and responses tag the generation that served them
    (mixed tags mid-rollout are the detectability contract)."""
    cfg, scope = weights
    srcs = _srcs(8, seed=81)
    clean = _undisturbed(cfg, scope, srcs, max_new_tokens=6)
    fleet = _fleet(cfg, scope, replicas=2)
    try:
        faults.arm("serve.decode:delay(0.02)@p1.0", seed=23)
        try:
            pre = [fleet.submit(s, max_new_tokens=6)
                   for s in srcs[:4]]
            _wait_tokens(pre, n=1)
            out = fleet.rollout(scope)  # same weights, new generation
            post = [fleet.submit(s, max_new_tokens=6)
                    for s in srcs[4:]]
            streams = [fr.result(timeout=120) for fr in pre + post]
        finally:
            faults.disarm()
        assert streams == clean
        assert out == {"generation": 1, "replicas_rotated": 2,
                       "replicas": 2}
        # nothing was rejected for the rollout's sake
        assert all(fr.outcome in ("completed", "length")
                   for fr in pre + post)
        # post-rollout admissions carry the new generation tag
        assert all(fr.generation == 1 for fr in post)
        assert all(r["generation"] == 1
                   for r in fleet.stats()["replicas"])
        assert fleet.stats()["generation"] == 1
    finally:
        fleet.close()


def test_autoscale_up_under_saturation_and_down_when_idle(weights):
    """The autoscaler's both directions, driven deterministically via
    autoscale_tick(): sustained queue saturation spins a replica up;
    sustained idleness drains-then-retires back to the floor."""
    cfg, scope = weights
    flags.set_flags({"serve_fleet_autoscale_window": 2,
                     "serve_fleet_scale_down_idle_ticks": 3,
                     "serve_fleet_scale_up_queue_factor": 0.5})
    fleet = _fleet(cfg, scope, replicas=1, slots=1, queue_depth=2,
                   min_replicas=1, max_replicas=2)
    try:
        faults.arm("serve.decode:delay(0.05)@p1.0", seed=29)
        try:
            srcs = _srcs(3, seed=91)
            # first request must reach the slot BEFORE the queue is
            # stuffed: 3 rapid submits against queue_depth=2 would shed
            # the third whenever the loop thread hasn't admitted yet
            frs = [fleet.submit(srcs[0], max_new_tokens=4)]
            t0 = time.time()
            while (fleet.stats()["queue_depth"] > 0
                   and time.time() - t0 < 30):
                time.sleep(0.002)
            frs += [fleet.submit(s, max_new_tokens=4)
                    for s in srcs[1:]]
            acts = [fleet.autoscale_tick() for _ in range(2)]
            assert acts[-1] == "up"
            assert fleet.stats()["replica_count"] == 2
            assert fleet.scale_ups == 1
        finally:
            faults.disarm()
        for fr in frs:
            fr.result(timeout=120)
        fleet.drain(timeout_s=60)
        acts = [fleet.autoscale_tick() for _ in range(3)]
        assert acts[-1] == "down"
        assert fleet.stats()["replica_count"] == 1
        assert fleet.scale_downs == 1
        # the retired replica drained: nothing errored, and a fresh
        # submit still serves
        fr = fleet.submit(_srcs(1, seed=92)[0], max_new_tokens=2)
        assert fr.result(timeout=60) is not None
    finally:
        faults.disarm()
        fleet.close()
        flags.set_flags({
            name: flags._DEFS[name][1]
            for name in ("serve_fleet_autoscale_window",
                         "serve_fleet_scale_down_idle_ticks",
                         "serve_fleet_scale_up_queue_factor")})


def test_fleet_replica_spinup_reads_jax_cache(weights, tmp_path):
    """Under the suite's 8-device mesh, in one process: the second
    replica's build traces again but compiles nothing — every XLA
    compile it asks for is a hit in jax's persistent cache — and it
    streams what the first replica streamed."""
    cfg, scope = weights
    srcs = _srcs(2, seed=61)
    with placed_in_process(tmp_path / "jax_cache") as events:
        fleet = _fleet(cfg, scope, replicas=1)
        try:
            first = [fleet.submit(s).result(timeout=120) for s in srcs]
            before = events.snapshot()
            assert before["misses"] >= 2  # prefill + decode written
            new = fleet._spawn_replica()
            frs = [fleet.submit(s) for s in srcs]
            second = [fr.result(timeout=120) for fr in frs]
            # the router prefers the newcomer (no backlog estimate yet),
            # so its prefill and decode programs were built and run
            assert new.id in {fr.replica_id for fr in frs}
            got = events.since(before)
        finally:
            fleet.close()
    assert second == first
    assert got["misses"] == 0, got
    assert got["hits"] == got["requests"] >= 2, got


def test_warm_spinup_zero_fresh_compiles(tmp_path):
    """Two fresh 'fleet host' processes (tests/fleet_serve_worker.py)
    against one jax cache directory: scaling out a replica in-process
    adds no cache miss (the spin-up reads what the first replica
    wrote), and the second process compiles nothing at all — every
    request a hit — with byte-identical tokens."""
    def launch():
        return run_worker("fleet_serve_worker.py",
                          cache_dir=tmp_path / "jax_cache")

    cold = launch()
    assert cold["jax_cache"]["misses"] > 0
    assert cold["spinup"]["misses"] == 0, cold
    assert cold["spinup"]["hits"] >= 2, cold
    assert cold["replica_count"] == 2
    assert cold["scaled_tokens"] == cold["tokens"]

    warm = launch()
    assert warm["jax_cache"]["misses"] == 0, warm
    assert warm["jax_cache"]["hits"] == warm["jax_cache"]["requests"]
    assert warm["spinup"]["misses"] == 0
    assert warm["tokens"] == cold["tokens"]
    assert warm["scaled_tokens"] == cold["tokens"]


# --------------------------------------------------------------------------
# observability + lifecycle
# --------------------------------------------------------------------------

def test_fleet_view_and_request_records(weights, telemetry):
    """fleet_view() (the /fleet route's serving_fleet section) exposes
    per-replica state, queue depth, generation and heartbeat age; the
    fleet metrics tick; request records carry the serving replica."""
    cfg, scope = weights
    assert fleet_serving.fleet_view() is None  # no fleet up
    routed0 = monitor.counter("pt_fleet_serve_routed_total").value()
    fleet = _fleet(cfg, scope, replicas=2)
    try:
        frs = [fleet.submit(s) for s in _srcs(3, seed=95)]
        for fr in frs:
            fr.result(timeout=60)
        view = fleet_serving.fleet_view()
        assert view is not None and view["fleet_count"] == 1
        row = view["fleets"][0]
        assert row["replica_count"] == 2
        assert row["generation"] == 0
        for rep in row["replicas"]:
            assert rep["state"] == "serving"
            assert {"queue_depth", "generation",
                    "heartbeat_age_ms"} <= set(rep)
        assert sum(r["routed"] for r in row["replicas"]) == 3
        assert monitor.counter(
            "pt_fleet_serve_routed_total").value() == routed0 + 3
        # every handle knows which replica served it
        assert all(fr.replica_id in
                   {r["replica"] for r in row["replicas"]}
                   for fr in frs)
    finally:
        fleet.close()
    assert fleet_serving.fleet_view() is None  # closed fleets drop out


def test_close_finishes_every_handle(weights):
    """close() on a fleet with work in flight: every handle reaches a
    terminal outcome — result() never hangs on a closed fleet."""
    cfg, scope = weights
    fleet = _fleet(cfg, scope, replicas=2)
    faults.arm("serve.decode:delay(0.05)@p1.0", seed=31)
    try:
        frs = [fleet.submit(s, max_new_tokens=8)
               for s in _srcs(4, seed=97)]
    finally:
        faults.disarm()
    fleet.close(drain_timeout_s=0.2)
    for fr in frs:
        assert fr.result(timeout=10) is not None
        assert fr.outcome is not None


def test_router_fault_sites_registered():
    """The router.* chaos sites are declaratively discoverable."""
    names = set(faults.sites())
    assert {"router.route", "router.replica_crash",
            "router.handoff"} <= names
    for s in ("router.route", "router.replica_crash",
              "router.handoff"):
        assert faults.BUILTIN_SITES[s]
