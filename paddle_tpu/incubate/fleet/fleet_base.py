"""Fleet: the multi-host training façade
(reference: incubate/fleet/base/fleet_base.py — fleet.init / init_worker /
distributed_optimizer / stop_worker; collective mode
incubate/fleet/collective/__init__.py).

TPU-native bootstrap (replaces the reference's gen_nccl_id RPC exchange,
operators/distributed_ops/gen_nccl_id_op.cc:62):

1. rank 0 starts the native CoordServer (csrc/coord.cc: KV + barrier +
   heartbeat over one TCP port);
2. every worker connects a CoordClient, rendezvouses (KV put/get of the
   PJRT coordinator address), and barriers;
3. ``jax.distributed.initialize`` brings up the PJRT distributed runtime —
   after which ``jax.devices()`` is the GLOBAL device list and GSPMD
   programs span all hosts (collectives ride ICI/DCN, not RPC).

After init, ``fleet.mesh(...)`` builds global meshes and
``fleet.compiled_program(main)`` wraps a Program for global
data parallelism; per-step liveness goes through heartbeat/dead_workers
(SURVEY.md section 5 failure detection).
"""

from __future__ import annotations

import atexit
import json as _json
import os as _os
import re as _re
import sys as _sys
import time as _time
from typing import List, Optional, Sequence

import numpy as np

from paddle_tpu import faults as _faults
from paddle_tpu import fleet_monitor as _fleet_monitor
from paddle_tpu import monitor as _monitor
from paddle_tpu import retry as _retry
from paddle_tpu.incubate.fleet.role_maker import (
    EnvRoleMaker,
    RoleMakerBase,
)

# Barrier waits are THE multi-host stall signal (a slow rank shows up as
# everyone else's barrier time); rendezvous counts > 1 mean the job
# re-formed its world (failure recovery re-rendezvous).
_M_BARRIER_WAIT = _monitor.histogram(
    "pt_fleet_barrier_wait_seconds",
    "time spent waiting in fleet barriers, by barrier name")
_M_RENDEZVOUS = _monitor.counter(
    "pt_fleet_rendezvous_total",
    "successful multi-worker rendezvous (>1 per process = recovery)")
_M_DEAD_EVENTS = _monitor.counter(
    "pt_fleet_dead_worker_events_total",
    "barrier_or_dead returns that reported dead peers")
_M_RESIZES = _monitor.counter(
    "pt_fleet_resizes_total",
    "elastic world resizes launched (re-exec to generation N+1), by "
    "direction: shrink = survivors of dead-worker detection, grow = a "
    "world admitting announced joiners")
_M_JOIN_SECONDS = _monitor.histogram(
    "pt_fleet_join_seconds",
    "scale-out admission latency on the JOINER: announce over the "
    "running world's KV -> leader plan adopted + acked (join_world)")

# chaos hooks: armed plans fail/delay the Nth coordination RPC, so the
# retry policy's behavior is reproducibly testable (faults.py docstring)
_F_CONNECT = _faults.site("fleet.connect")
_F_KV_GET = _faults.site("fleet.kv_get")
_F_KV_PUT = _faults.site("fleet.kv_put")
_F_HEARTBEAT = _faults.site("fleet.heartbeat")
_F_RESIZE = _faults.site("fleet.resize")
_F_JOIN = _faults.site("fleet.join")

# join announcements live in numbered KV slots (fleet/join/g<gen>/<id>);
# the probe scans this many — a resize event admitting more than 64
# hosts at once should land as two resizes
_JOIN_SLOT_CAP = 64

# heartbeats are fired from poll loops — a few quick retries beat a long
# backoff that would itself age the heartbeat past max_age_ms
_HEARTBEAT_POLICY = _retry.RetryPolicy(
    base_delay=0.05, max_delay=0.5, max_attempts=3, retry_on=(OSError,))


def resize_direction(spec: dict) -> str:
    """The ``pt_fleet_resizes_total`` direction label for a
    ``plan_resize`` spec: ``grow`` whenever the resize ADMITS joiners
    (matching the metric's documented meaning — a composed replacement
    resize that loses as many dead ranks as it admits is still an
    admission event, and its join latency already metered), ``shrink``
    otherwise."""
    return "grow" if spec.get("joiners") else "shrink"


def _barrier_label(name: str) -> str:
    """Bounded label cardinality: callers bake step/generation numbers
    into barrier names (e.g. 'step3-g1' in the recovery protocol), and a
    fresh histogram cell per training step would grow the registry and
    the Prometheus export without bound. Digit runs collapse to '*'."""
    return _re.sub(r"\d+", "*", name)


class Fleet:
    def __init__(self):
        self._role: Optional[RoleMakerBase] = None
        self._server = None
        self._client = None
        self._initialized = False
        self._done_barriers: list = []
        self._barrier_seq = 0

    # --- lifecycle (reference: fleet_base.py init/init_worker) ---

    def init(self, role_maker: Optional[RoleMakerBase] = None,
             connect_timeout_ms: Optional[int] = None):
        """Rendezvous + distributed runtime init. Single-worker jobs
        (worker_num == 1) need no endpoints and become a no-op.
        ``connect_timeout_ms`` defaults to the ``rpc_deadline_ms`` flag.

        ``PT_COORD_ONLY=1`` skips ``jax.distributed.initialize`` —
        coordination-only fleets: the coord service, KV, barriers,
        heartbeats, elastic resize and the commit barrier all come up,
        but each process keeps its own single-process jax world. For
        jobs whose compute is per-process (replicated smoke drills on
        backends that cannot form a cross-process XLA world, host-side
        parameter servers), and what gives every rank the SAME device
        identity — the condition under which the persistent compile
        cache's local entries are shareable fleet-wide."""
        if self._initialized:
            return self
        if connect_timeout_ms is None:
            from paddle_tpu import flags as _flags

            connect_timeout_ms = _flags.get_flag("rpc_deadline_ms")
        self._role = role_maker or EnvRoleMaker()
        n = self._role.worker_num()
        if n > 1:
            from paddle_tpu import native

            endpoint = self._role.coord_endpoint()
            if not endpoint:
                raise ValueError(
                    "multi-worker fleet.init needs a coordination endpoint "
                    "(PT_COORD_ENDPOINT=host:port)"
                )
            host, port = endpoint.rsplit(":", 1)
            port = int(port)
            with _monitor.span("fleet.rendezvous"), \
                    _monitor.stall_guard("fleet.rendezvous"):
                if self._role.is_first_worker():
                    self._server = native.CoordServer(port)
                # workers retry-connect until rank 0's server is up
                self._client = _connect_retry(host, port,
                                              connect_timeout_ms)

                jax_ep = (self._role.jax_coord_endpoint()
                          or f"{host}:{port + 1}")
                if self._role.is_first_worker():
                    self.put("fleet/jax_coordinator", jax_ep.encode())
                else:
                    jax_ep = _kv_get_retry(
                        self._client, "fleet/jax_coordinator",
                        connect_timeout_ms,
                    ).decode()
                self._client.barrier("fleet/rendezvous", n)
                if self._role.is_first_worker():
                    # late joiners read the running world's generation
                    # here before announcing (join_world); a world that
                    # never published it is generation 0
                    self.put("fleet/generation",
                             str(self.generation()).encode())

                if _os.environ.get("PT_COORD_ONLY") != "1":
                    import jax

                    jax.distributed.initialize(
                        jax_ep,
                        num_processes=n,
                        process_id=self._role.worker_index(),
                    )
            _M_RENDEZVOUS.inc()
            # register with the fleet observability plane: the /fleet
            # route aggregates through this client (each worker also
            # re-attaches on its first digest publish)
            _fleet_monitor.attach(self)
            atexit.register(self.stop_worker)
        # tag this process's trace exports with its rank so
        # monitor.merge_traces lands each worker's events on its own
        # track (single-worker jobs stay rank 0)
        _monitor.set_trace_rank(self._role.worker_index())
        self._initialized = True
        return self

    def stop_worker(self):
        self._done_barriers = []
        if self._client is not None:
            try:
                self._client.close()
            except OSError:
                pass
            self._client = None
        if self._server is not None:
            self._server.stop()
            self._server = None
        self._initialized = False

    # --- identity ---

    def worker_index(self) -> int:
        return self._role.worker_index() if self._role else 0

    def worker_num(self) -> int:
        return self._role.worker_num() if self._role else 1

    def is_first_worker(self) -> bool:
        return self.worker_index() == 0

    # --- collective helpers ---

    def barrier(self, name: str = "fleet/barrier"):
        if self._client is not None:
            # span and observe are both self-gating: with only the
            # profiler on this still lands in the chrome trace, with
            # only telemetry on it still feeds the histogram
            t0 = _time.perf_counter()
            with _monitor.span("fleet.barrier"), \
                    _monitor.stall_guard("fleet.barrier"):
                self._client.barrier(name, self.worker_num())
            _M_BARRIER_WAIT.observe(_time.perf_counter() - t0,
                                    labels={"barrier": _barrier_label(name)})

    def put(self, key: str, value: bytes):
        if self._client is None:
            raise RuntimeError("fleet.init with multiple workers first")
        from paddle_tpu import flags as _flags

        client = self._client

        def _once():
            _F_KV_PUT.hit()
            client.put(key, value)

        _retry.call(_once, site="fleet.kv_put", retry_on=(OSError,),
                    deadline_s=_flags.get_flag("rpc_deadline_ms") / 1000.0)

    def get(self, key: str, timeout_ms: Optional[int] = None) -> bytes:
        if self._client is None:
            raise RuntimeError("fleet.init with multiple workers first")
        if timeout_ms is None:
            from paddle_tpu import flags as _flags

            timeout_ms = _flags.get_flag("rpc_deadline_ms")
        # a blocked KV get is the classic "peer never published its key"
        # hang (e.g. waiting out a partner's multi-minute first compile)
        with _monitor.stall_guard("fleet.kv_get"):
            return _kv_get_retry(self._client, key, timeout_ms)

    # --- failure detection (SURVEY.md section 5) ---

    def heartbeat(self):
        if self._client is not None:
            client = self._client
            me = self.worker_index()

            def _once():
                _F_HEARTBEAT.hit()
                client.heartbeat(f"worker-{me}")

            _retry.call(_once, site="fleet.heartbeat",
                        policy=_HEARTBEAT_POLICY)
            if _monitor.enabled():
                # fleet observability: the registry digest rides the
                # heartbeat cadence (rate-limited inside by the
                # fleet_metrics_interval_ms flag); with telemetry off
                # this whole plane costs the one boolean check above
                _fleet_monitor.maybe_publish(self)

    def dead_workers(self, max_age_ms: int = 30_000) -> Sequence[str]:
        if self._client is None:
            return []
        return self._client.dead_peers(max_age_ms)

    def barrier_or_dead(self, name: str, max_age_ms: int = 5_000,
                        poll_ms: int = 100,
                        timeout_ms: int = 120_000) -> Sequence[str]:
        """Liveness-guarded barrier — the collective-timeout analog of
        the reference's grpc deadline on sync barriers. Arrive at
        ``name``, then wait until EITHER every worker has arrived
        (returns []) OR some worker's heartbeat ages past
        ``max_age_ms`` (returns the dead ids without blocking on them).
        Workers place this before each step's collectives so a peer
        crash surfaces as a recoverable signal instead of a hang in
        psum. The caller keeps heartbeating while it polls.

        CONTRACT: calls form a collective sequence — every worker must
        make its N-th call together (the same discipline any collective
        requires; epochs are keyed by call count). A TimeoutError is
        NOT retryable in place, and a replacement worker cannot join an
        existing world mid-sequence: both must go through a fresh
        rendezvous (new coord world), as the recovery protocol does.
        Survivors cross the staleness threshold at different polls: one
        that leaves on its own reading while it hosts the coordination
        server fails every peer still polling here (``OSError: heartbeat
        failed``). Pass the result through ``settle_dead`` first: the
        host then leaves after every survivor's ack."""
        if self._client is None:
            return []
        t_wait0 = _time.perf_counter()
        me = self.worker_index()
        # Epoch-keyed arrivals: every call gets this client's barrier
        # SEQUENCE NUMBER in the key. All workers reach their N-th
        # barrier_or_dead call together (the same SPMD contract any
        # collective requires), so the epoch matches across ranks — and
        # a reused name lands in a fresh epoch namespace, so a stale
        # arrive key from an earlier barrier can never satisfy a later
        # one. No reuse guard needed; names need not be unique.
        self._barrier_seq += 1
        tag = f"{self._barrier_seq}:{name}"
        # KV hygiene: reclaim MY arrive key from the OLDER of the last
        # two FULLY-completed barriers. Full completion of the newer one
        # required every peer to arrive there, hence to have LEFT the
        # older one — no live peer can still be polling the key being
        # deleted, however the peers' own returns happened. Dead-path
        # returns clear this history (no reclamation until two fresh
        # full completions), because a falsely-dead-but-alive straggler
        # may still be polling an older barrier whose keys it needs.
        if len(self._done_barriers) >= 2:
            old_tag = self._done_barriers.pop(0)
            try:
                self._client.delete(f"fleet/arrive/{old_tag}/{me}")
            except OSError:
                pass  # hygiene only; never fail the barrier for it
        self._client.put(f"fleet/arrive/{tag}/{me}", b"1")
        deadline = _time.monotonic() + timeout_ms / 1000.0
        # The watchdog fires well before timeout_ms (its deadline is the
        # stall_timeout_ms flag): a stall record with the span stack
        # beats staring at a silent poll loop for two minutes.
        with _monitor.stall_guard("fleet.barrier_or_dead"):
            while True:
                self._client.heartbeat(f"worker-{me}")
                missing = []
                for r in range(self.worker_num()):
                    if r == me:
                        continue
                    try:
                        self._client.get(f"fleet/arrive/{tag}/{r}",
                                         timeout_ms=0)
                    except TimeoutError:
                        missing.append(r)
                if not missing:
                    self._done_barriers.append(tag)
                    _M_BARRIER_WAIT.observe(
                        _time.perf_counter() - t_wait0,
                        labels={"barrier": _barrier_label(name)})
                    return []
                dead = list(self._client.dead_peers(max_age_ms))
                dead_missing = [d for d in dead
                                if any(d == f"worker-{r}" for r in missing)]
                if dead_missing:
                    self._done_barriers = []
                    _M_DEAD_EVENTS.inc()
                    _M_BARRIER_WAIT.observe(
                        _time.perf_counter() - t_wait0,
                        labels={"barrier": _barrier_label(name)})
                    return dead_missing
                if _time.monotonic() > deadline:
                    # the timeout IS the pathological wait this histogram
                    # exists to surface — record it before raising
                    _M_BARRIER_WAIT.observe(
                        _time.perf_counter() - t_wait0,
                        labels={"barrier": _barrier_label(name)})
                    raise TimeoutError(
                        f"barrier_or_dead {name!r}: workers {missing} "
                        f"neither arrived nor declared dead within "
                        f"{timeout_ms} ms")
                _time.sleep(poll_ms / 1000.0)

    # --- elastic resize (SURVEY.md section 5 recovery loop) ---

    def generation(self) -> int:
        """How many times this process's lineage re-rendezvoused (0 =
        the original world; ``reexec_resized`` bumps it via PT_GEN)."""
        return int(_os.environ.get("PT_GEN", "0"))

    def settle_dead(self, observed: Sequence = (),
                    max_age_ms: int = 5_000, poll_ms: int = 100,
                    timeout_ms: Optional[int] = None) -> Sequence[str]:
        """One AGREED dead set for every survivor. The liveness signal
        is not atomic: peers of the same crash cross the staleness
        threshold at different poll instants, so two survivors can
        return from ``barrier_or_dead`` with DIFFERENT partial dead sets
        — and would then derive different shrunk worlds and hang each
        other's recovery rendezvous. Each survivor keeps polling (and
        heartbeating, so survivors never mutually expire) until its
        accumulated dead set has been stable for one full staleness
        window; then the lowest-ranked survivor publishes its settled
        set over the KV (generation-keyed, so a later resize gets fresh
        keys) and every other survivor adopts the published set, acking
        the read so the leader never tears its coord server down under
        a peer still fetching. Assumes declared-dead workers stay dead
        (there is no mid-sequence rejoin; a falsely-stale-but-alive
        worker is excluded like a dead one and must re-enter through a
        fresh rendezvous)."""
        if self._client is None:
            return sorted(str(d) for d in observed)
        if timeout_ms is None:
            from paddle_tpu import flags as _flags

            timeout_ms = _flags.get_flag("rpc_deadline_ms")
        gen = self.generation()
        cur = {str(d) for d in observed}
        stable = 0.0
        with _monitor.stall_guard("fleet.settle_dead"):
            while stable < max_age_ms:
                self.heartbeat()
                _time.sleep(poll_ms / 1000.0)
                nxt = cur | set(self._client.dead_peers(max_age_ms))
                if nxt == cur:
                    stable += poll_ms
                else:
                    stable, cur = 0.0, nxt
            dead_ranks = {int(str(d).rsplit("-", 1)[-1]) for d in cur}
            survivors = [r for r in range(self.worker_num())
                         if r not in dead_ranks]
            if not survivors:
                raise ValueError(
                    f"settle_dead: every rank is stale ({sorted(cur)})")
            agreed = self._leader_adopt(
                f"fleet/resize/dead/g{gen}",
                f"fleet/resize/ack/g{gen}",
                ",".join(sorted(cur)).encode(),
                survivors[0], survivors[1:], timeout_ms)
            return sorted(x for x in agreed.decode().split(",") if x)

    def pending_joins(self, known: Sequence[int] = ()) -> List[int]:
        """Join ids announced against THIS generation: a non-blocking
        probe of the numbered join slots (``fleet/join/g<gen>/<id>``,
        ids 0..63). Incumbents poll this to notice newcomers; the
        settle/plan flow (``settle_joins`` -> ``plan_resize(joins=)``)
        turns the announcements into a grown world. Announcements never
        retract, so ``known`` ids are reported without re-probing —
        settle_joins passes its accumulated set, keeping each poll tick
        at (64 - seen) non-blocking gets instead of a fixed 64."""
        if self._client is None:
            return []
        gen = self.generation()
        out = list(known)
        for j in range(_JOIN_SLOT_CAP):
            if j in out:
                continue
            try:
                self._client.get(f"fleet/join/g{gen}/{j}", timeout_ms=0)
                out.append(j)
            except TimeoutError:
                continue  # slot not announced — the expected answer
            # any OTHER OSError propagates: a broken coord connection
            # must not read as "no joiners announced" (settle_joins
            # would agree on an EMPTY set and bump the generation while
            # the announced joiners hang)
        return sorted(out)

    def _leader_adopt(self, key: str, ack_prefix: str, payload: bytes,
                      leader: int, peers: Sequence[int],
                      timeout_ms: int) -> bytes:
        """The agreement tail ``settle_dead``/``settle_joins`` share:
        the LEADER (lowest surviving rank) publishes its settled
        payload under the generation-keyed ``key`` and collects one ack
        per surviving peer — so it never tears its coord server down
        under a peer still fetching — while every peer adopts the
        published payload and acks the read."""
        me = self.worker_index()
        if me == leader:
            self.put(key, payload)
            dl = _retry.Deadline(timeout_ms / 1000.0)
            for r in peers:
                self.get(f"{ack_prefix}/{r}",
                         timeout_ms=max(1, dl.remaining_ms()))
            return payload
        agreed = self.get(key, timeout_ms=timeout_ms)
        self.put(f"{ack_prefix}/{me}", b"1")
        return agreed

    def settle_joins(self, max_age_ms: int = 5_000, poll_ms: int = 100,
                     timeout_ms: Optional[int] = None,
                     min_count: int = 0,
                     dead: Sequence = ()) -> List[int]:
        """One AGREED joiner set for every surviving incumbent — the
        grow twin of ``settle_dead``. Join announcements are not atomic
        either: a scale-out event's hosts come up at different
        instants, so each incumbent keeps polling (and heartbeating)
        until the announced set has been stable for one full window AND
        holds at least ``min_count`` ids; then the lowest SURVIVING
        rank publishes its settled set over the KV (generation-keyed)
        and every other survivor adopts the published set, acking the
        read. ``dead`` (a ``settle_dead`` result) makes the composed
        shrink+grow resize work: the leader and the ack set are derived
        from the survivors, never from ranks that can no longer ack.
        Raises TimeoutError when ``min_count`` announcements never
        materialize inside ``timeout_ms``."""
        if self._client is None:
            return []
        if timeout_ms is None:
            from paddle_tpu import flags as _flags

            timeout_ms = _flags.get_flag("rpc_deadline_ms")
        gen = self.generation()
        dead_ranks = {int(str(d).rsplit("-", 1)[-1]) for d in dead}
        survivors = [r for r in range(self.worker_num())
                     if r not in dead_ranks]
        deadline = _time.monotonic() + timeout_ms / 1000.0
        cur: List[int] = []
        stable = 0.0
        with _monitor.stall_guard("fleet.settle_joins"):
            while stable < max_age_ms or len(cur) < min_count:
                self.heartbeat()
                _time.sleep(poll_ms / 1000.0)
                nxt = self.pending_joins(known=cur)
                if nxt == cur and len(cur) >= min_count:
                    stable += poll_ms
                else:
                    stable, cur = 0.0, nxt
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"settle_joins: {len(cur)} of {min_count} "
                        f"expected joiners announced within {timeout_ms} "
                        f"ms ({cur})")
            agreed = self._leader_adopt(
                f"fleet/resize/joins/g{gen}",
                f"fleet/resize/jsack/g{gen}",
                ",".join(str(j) for j in cur).encode(),
                survivors[0], survivors[1:], timeout_ms)
            return sorted(int(x) for x in agreed.decode().split(",")
                          if x)

    def plan_resize(self, dead_ids: Sequence, joins: Sequence = (),
                    rank: Optional[int] = None,
                    world: Optional[int] = None,
                    join_id: Optional[int] = None) -> dict:
        """Deterministic resized-world spec. Shrink: ``dead_ids``
        (``worker-<r>`` ids or plain ranks; pass them through
        ``settle_dead`` first so every survivor plans from the SAME
        set). Grow: ``joins`` (settled join ids from ``settle_joins``)
        — survivors keep their relative rank order and joiners take the
        ranks after them, in join-id order, so every participant
        derives the identical world from the same (dead, joins)
        agreement. A joiner passes ``join_id`` instead of ``rank`` to
        derive ITS new rank. Both compose: dead workers leave and fresh
        capacity arrives in one resize. Chaos plans can tear this step
        via the ``fleet.resize`` site (a raise here models a
        participant that fails during the resize decision).

        Returns ``{"survivors": [old ranks], "rank": my new rank,
        "world": new size, "dead": [dead old ranks]}`` plus
        ``"joiners": [[join id, new rank], ...]`` when growing.
        """
        _F_RESIZE.hit()
        world = self.worker_num() if world is None else int(world)
        dead = set()
        for d in dead_ids:
            if isinstance(d, int):
                dead.add(d)
            else:
                # "worker-3" and plain "3" both parse (settle_dead's
                # client-less fallback stringifies whatever it was fed)
                dead.add(int(str(d).rsplit("-", 1)[-1]))
        survivors = [r for r in range(world) if r not in dead]
        if not survivors:
            raise ValueError(f"resize with no survivors (dead: {sorted(dead)})")
        join_list = sorted(int(j) for j in joins)
        joiner_ranks = {j: len(survivors) + i
                        for i, j in enumerate(join_list)}
        if join_id is not None:
            if int(join_id) not in joiner_ranks:
                raise ValueError(
                    f"join_id {join_id} is not in the settled join set "
                    f"{join_list}; a joiner must announce and be settled "
                    f"before planning")
            new_rank = joiner_ranks[int(join_id)]
        else:
            rank = self.worker_index() if rank is None else int(rank)
            if rank not in survivors:
                raise ValueError(
                    f"rank {rank} is itself in the dead set "
                    f"{sorted(dead)}; a declared-dead worker must not "
                    f"plan the resize")
            new_rank = survivors.index(rank)
        spec = {"survivors": survivors, "rank": new_rank,
                "world": len(survivors) + len(join_list),
                "dead": sorted(dead)}
        if join_list:
            spec["joiners"] = [[j, joiner_ranks[j]] for j in join_list]
        return spec

    def publish_join_plan(self, spec: dict, coord_endpoint: str,
                          jax_endpoint: Optional[str] = None,
                          timeout_ms: Optional[int] = None):
        """Leader-only (rank 0): publish the grown-world plan — the
        joiners' half of the agreement, carrying their assigned ranks
        and the generation-N+1 recovery endpoints — then WAIT for every
        joiner's ack before returning. The leader owns the
        generation-N coord server and ``reexec_resized`` tears it down;
        returning before the acks would strand a joiner mid-read."""
        if timeout_ms is None:
            from paddle_tpu import flags as _flags

            timeout_ms = _flags.get_flag("rpc_deadline_ms")
        gen = self.generation()
        plan = {"survivors": spec["survivors"],
                "dead": spec.get("dead", []),
                "joiners": spec.get("joiners", []),
                "world": spec["world"], "gen": gen + 1,
                "coord": coord_endpoint, "jax": jax_endpoint}
        self.put(f"fleet/resize/plan/g{gen}",
                 _json.dumps(plan).encode())
        dl = _retry.Deadline(timeout_ms / 1000.0)
        for j, _r in spec.get("joiners", []):
            self.get(f"fleet/resize/jack/g{gen}/{j}",
                     timeout_ms=max(1, dl.remaining_ms()))

    def join_world(self, coord_endpoint: str, join_id: int,
                   connect_timeout_ms: Optional[int] = None,
                   timeout_ms: Optional[int] = None,
                   _client=None) -> dict:
        """NEWCOMER side of scale-OUT: connect to the RUNNING world's
        coord service, announce under the generation-keyed join slot,
        wait for the leader's published plan, ack it, and return the
        resize spec (rank/world/endpoints/generation) ready for
        ``reexec_resized``. The two ``fleet.join`` fault-site hits —
        before the announce and at plan adoption — let chaos plans tear
        an admission at either seam. Metered into
        ``pt_fleet_join_seconds`` (announce -> plan adopted)."""
        from paddle_tpu import flags as _flags

        if connect_timeout_ms is None:
            connect_timeout_ms = _flags.get_flag("rpc_deadline_ms")
        if timeout_ms is None:
            timeout_ms = _flags.get_flag("rpc_deadline_ms")
        if not 0 <= int(join_id) < _JOIN_SLOT_CAP:
            # an out-of-range slot would announce where pending_joins
            # never probes: a silent deterministic hang, not a join
            raise ValueError(
                f"join_id must be in [0, {_JOIN_SLOT_CAP}), got "
                f"{join_id}")
        t0 = _time.perf_counter()
        client = _client
        if client is None:
            host, port = coord_endpoint.rsplit(":", 1)
            client = _connect_retry(host, int(port), connect_timeout_ms)
        try:
            try:
                # bounded BLOCKING read: a newcomer can connect in the
                # window before rank 0's post-rendezvous publish, and a
                # wrong-generation announce lands in a slot nobody
                # probes. Worlds predating the key (which cannot settle
                # joins anyway) fall back to generation 0 at timeout.
                gen = int(_kv_get_retry(
                    client, "fleet/generation",
                    min(int(timeout_ms), 5_000)).decode())
            except (TimeoutError, OSError, ValueError):
                gen = 0
            _F_JOIN.hit()  # hit 1: the announce
            client.put(f"fleet/join/g{gen}/{int(join_id)}", b"1")
            with _monitor.stall_guard("fleet.join"):
                raw = _kv_get_retry(client, f"fleet/resize/plan/g{gen}",
                                    timeout_ms)
            plan = _json.loads(raw.decode())
            _F_JOIN.hit()  # hit 2: plan adoption
            joiner_ranks = {int(j): int(r)
                            for j, r in plan.get("joiners", [])}
            if int(join_id) not in joiner_ranks:
                raise ValueError(
                    f"join {join_id}: the leader's plan admitted only "
                    f"{sorted(joiner_ranks)}; this announcement landed "
                    f"after the join set settled — re-announce against "
                    f"the next generation")
            client.put(f"fleet/resize/jack/g{gen}/{int(join_id)}", b"1")
        finally:
            if _client is None:
                try:
                    client.close()
                except OSError:
                    pass
        dt = _time.perf_counter() - t0
        _M_JOIN_SECONDS.observe(dt)
        return {"survivors": plan["survivors"],
                "dead": plan.get("dead", []),
                "joiners": plan.get("joiners", []),
                "rank": joiner_ranks[int(join_id)],
                "world": int(plan["world"]),
                "gen": int(plan.get("gen", 1)),
                "coord_endpoint": plan.get("coord"),
                "jax_endpoint": plan.get("jax"),
                "join_latency_s": dt}

    def reexec_resized(self, spec: dict, coord_endpoint: str,
                       jax_endpoint: Optional[str] = None,
                       script: Optional[str] = None,
                       argv: Optional[Sequence[str]] = None,
                       extra_env: Optional[dict] = None):
        """Re-exec THIS process as generation N+1 of the shrunk world
        described by ``plan_resize``'s spec: rank/world/coordination
        endpoints land in the EnvRoleMaker env vars, PT_GEN increments,
        the coord connection closes, and the process image is replaced
        (``os.execve`` — no return). The restarted process's recovery
        path (e.g. Trainer auto-resume or ``checkpoint.load_latest``)
        then restores the newest valid checkpoint onto the NEW topology:
        manifest-v2 checkpoints reassemble and re-shard on any world
        shape, which is what makes this resize safe.

        The command line survives the re-exec: ``argv`` defaults to
        ``sys.argv[1:]``, so a job launched with flags restarts with the
        same flags (hyperparameters must not silently reset to defaults
        across generations). A ``python -m pkg.mod`` entrypoint re-runs
        as a plain script path — pass ``script``/``argv`` explicitly if
        your ``__main__`` relies on package-relative imports.

        Grown worlds: a JOINER re-execs through the same call with the
        spec ``join_world`` returned. Its env must be complete and
        self-consistent for ``EnvRoleMaker`` — rank/world from the
        spec, the generation from the PLAN (``spec["gen"]``, not this
        process's own generation + 1: a joiner's own is 0), and a stale
        inherited ``PT_JAX_COORD_ENDPOINT`` scrubbed when the caller
        passes none (it names the DEAD generation's PJRT coordinator;
        EnvRoleMaker's coord-host default is the correct one)."""
        env = dict(_os.environ)
        env.update({
            "PT_TRAINER_ID": str(spec["rank"]),
            "PT_TRAINERS": str(spec["world"]),
            "PT_COORD_ENDPOINT": coord_endpoint,
            "PT_GEN": str(int(spec.get("gen", self.generation() + 1))),
        })
        if jax_endpoint:
            env["PT_JAX_COORD_ENDPOINT"] = jax_endpoint
        else:
            env.pop("PT_JAX_COORD_ENDPOINT", None)
        if extra_env:
            env.update({k: str(v) for k, v in extra_env.items()})
        # direction derives from the SPEC, so survivors and joiners
        # meter identically (resize_direction is the one definition)
        _M_RESIZES.inc(labels={"direction": resize_direction(spec)})
        self.stop_worker()
        script = script or _os.path.abspath(_sys.argv[0])
        args = list(_sys.argv[1:] if argv is None else argv)
        _os.execve(_sys.executable, [_sys.executable, script] + args, env)

    # --- program compilation over the global mesh ---

    def mesh(self, shape: Optional[Sequence[int]] = None,
             axis_names: Sequence[str] = ("data",)):
        """A Mesh over ALL global devices (defaults to 1-D data mesh)."""
        import jax
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices())
        if shape is not None:
            devs = devs.reshape(tuple(shape))
        return Mesh(devs, tuple(axis_names))

    def compiled_program(self, main_program, strategy=None):
        """Program -> CompiledProgram over the global device mesh; pass a
        DistributedStrategy for tp/sp/table sharding on top of dp."""
        from paddle_tpu.compiler import CompiledProgram

        if strategy is not None:
            return CompiledProgram(main_program).with_strategy(strategy)
        return CompiledProgram(main_program).with_data_parallel()

    def distributed_optimizer(self, optimizer, strategy=None):
        return DistributedOptimizer(self, optimizer, strategy)


class DistributedOptimizer:
    """Wraps an Optimizer for fleet jobs (reference: fleet_base.py
    DistributedOptimizer): minimize() is unchanged graph-side — data
    parallelism is a sharding of the SAME program, not a graph rewrite —
    and the fleet remembers the strategy for compiled_program()."""

    def __init__(self, fleet: Fleet, inner, strategy=None):
        self._fleet = fleet
        self._inner = inner
        self.strategy = strategy

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        return self._inner.minimize(
            loss, startup_program, parameter_list, no_grad_set
        )

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _connect_retry(host: str, port: int, timeout_ms: int):
    """Retry-connect under the unified policy (exponential backoff +
    decorrelated jitter, deadline budget) — replaces the fixed 0.1 s
    spin. Workers poll here until rank 0's server is up."""
    from paddle_tpu import native

    def _once():
        _F_CONNECT.hit()
        return native.CoordClient(host, port)

    return _retry.call(_once, site="fleet.connect", retry_on=(OSError,),
                       deadline_s=timeout_ms / 1000.0)


# between kv-get attempts the real waiting happens SERVER-side (the
# growing slice below) — the client-side sleep is kept tiny so a key
# published during a slice is served the instant it lands, not after a
# multi-second backoff nap
_KV_GAP_POLICY = _retry.RetryPolicy(
    base_delay=0.002, max_delay=0.02, retry_on=(OSError,))


def _kv_get_retry(client, key: str, timeout_ms: int) -> bytes:
    """KV get under the retry policy: server-side wait slices that grow
    exponentially from ``retry_base_delay_ms`` up to
    ``retry_max_delay_ms`` (instant wakeup when the key is published —
    the server holds the request), with only millisecond client-side
    gaps between attempts, raising TimeoutError once the overall
    ``timeout_ms`` budget is spent. ``timeout_ms`` < 0 = block forever
    (one server-side wait, no retry loop)."""
    from paddle_tpu import flags as _flags

    if timeout_ms is not None and timeout_ms <= 0:
        # -1 = block forever; 0 = one non-blocking present-check — both
        # are single passthrough calls, no retry loop (a 0 budget must
        # still ASK the server, not synthesize a timeout)
        _F_KV_GET.hit()
        return client.get(key, timeout_ms=int(timeout_ms))
    base_ms = max(1, _flags.get_flag("retry_base_delay_ms"))
    cap_ms = max(base_ms, _flags.get_flag("retry_max_delay_ms"))
    deadline = _time.monotonic() + timeout_ms / 1000.0
    state = {"slice": base_ms}

    def _once():
        _F_KV_GET.hit()
        remaining = deadline - _time.monotonic()
        if remaining <= 0:  # same float compare retry.call makes below
            raise TimeoutError(
                f"coord get {key!r}: {timeout_ms} ms budget spent")
        s = min(state["slice"], max(1, int(remaining * 1000)))
        state["slice"] = min(state["slice"] * 2, cap_ms)
        return client.get(key, timeout_ms=s)

    # the SAME absolute deadline governs _once's budget check and the
    # retry loop: when _once raises the budget-spent TimeoutError,
    # retry.call sees remaining <= 0 on the same clock and converts it
    # to a terminal 'exhausted' raise instead of one more retry cycle
    return _retry.call(
        _once, site="fleet.kv_get", retry_on=(OSError,),
        deadline_at=deadline, policy=_KV_GAP_POLICY,
    )


fleet = Fleet()
