"""Elastic fleet resize (ISSUE 7): plan_resize spec derivation, the
fleet.resize chaos site, and the end-to-end 8->4 shrink drill — kill
half the world mid-training via a SEEDED fault plan, survivors
re-rendezvous as a 4-worker generation, restore the newest valid
checkpoint (committed by the 8-writer world through the coordinated
commit barrier) and finish, with loss parity against an uninterrupted
single-process run. (Worker compute is replicated — see
fleet_resize_worker.py's docstring for why, and test_checkpoint.py's
mesh matrix for the sharded cross-topology restore proof.)

The multi-process drill is `chaos`-marked: deterministic but expensive
(8 subprocesses + re-exec), deselected from the tier-1 smoke gate; run
with `-m chaos`."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, monitor
from paddle_tpu.incubate.fleet.fleet_base import Fleet

from jax_cache_events import child_env

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _chaos_clean():
    faults.disarm()
    yield
    faults.disarm()


# --------------------------------------------------------------------------
# plan_resize: the survivors' agreement function (pure, rank-overridable)
# --------------------------------------------------------------------------

def test_plan_resize_survivors_keep_relative_order():
    f = Fleet()
    spec = f.plan_resize(["worker-3"], rank=1, world=4)
    assert spec == {"survivors": [0, 1, 2], "rank": 1, "world": 3,
                    "dead": [3]}
    # every survivor derives the identical world from the same dead set
    specs = [f.plan_resize(["worker-3"], rank=r, world=4) for r in (0, 1, 2)]
    assert [s["rank"] for s in specs] == [0, 1, 2]
    assert all(s["survivors"] == [0, 1, 2] and s["world"] == 3
               for s in specs)


def test_plan_resize_8_to_4_shrink_spec():
    f = Fleet()
    dead = [f"worker-{r}" for r in (4, 5, 6, 7)]
    spec = f.plan_resize(dead, rank=2, world=8)
    assert spec == {"survivors": [0, 1, 2, 3], "rank": 2, "world": 4,
                    "dead": [4, 5, 6, 7]}


def test_plan_resize_accepts_plain_ranks_and_rejects_dead_self():
    f = Fleet()
    spec = f.plan_resize([0, 2], rank=1, world=4)
    assert spec["survivors"] == [1, 3] and spec["rank"] == 0
    # string plain ranks too: settle_dead's client-less fallback
    # stringifies whatever it was fed, and that output feeds here
    assert f.plan_resize(["0", "2"], rank=1, world=4) == spec
    with pytest.raises(ValueError, match="dead set"):
        f.plan_resize([1], rank=1, world=4)


def test_fleet_resize_fault_site_tears_the_decision():
    """Chaos plans can fail the resize step itself (a survivor dying
    DURING recovery), metered like every injection."""
    monitor.enable()
    f = Fleet()
    inj0 = monitor.counter("pt_fault_injected_total").value(
        labels={"site": "fleet.resize"})
    faults.arm("fleet.resize:raise@1")
    with pytest.raises(faults.InjectedFault):
        f.plan_resize(["worker-3"], rank=0, world=4)
    faults.disarm()
    assert monitor.counter("pt_fault_injected_total").value(
        labels={"site": "fleet.resize"}) == inj0 + 1
    # disarmed: the same call is the plain decision again
    assert f.plan_resize(["worker-3"], rank=0, world=4)["world"] == 3


def test_reexec_resized_preserves_command_line(monkeypatch):
    """Generation N+1 re-runs with the SAME flags as generation N — a
    job launched `python train.py --lr 0.01` must not restart with
    default hyperparameters. (execve is stubbed: the subject is the
    argv/env the re-exec would carry, not the process replacement.)"""
    import paddle_tpu.incubate.fleet.fleet_base as fb

    calls = {}
    monkeypatch.setattr(
        fb._os, "execve",
        lambda exe, args, env: calls.update(exe=exe, args=args, env=env))
    monkeypatch.setattr(
        fb._sys, "argv", ["/work/train.py", "--lr", "0.01", "--cfg", "p.yml"])
    f = Fleet()
    spec = f.plan_resize(["worker-3"], rank=1, world=4)
    f.reexec_resized(spec, coord_endpoint="127.0.0.1:1234")
    assert calls["args"][1:] == ["/work/train.py", "--lr", "0.01",
                                 "--cfg", "p.yml"]
    assert calls["env"]["PT_TRAINER_ID"] == "1"
    assert calls["env"]["PT_TRAINERS"] == "3"
    assert calls["env"]["PT_GEN"] == "1"
    # explicit argv overrides the inherited command line
    f2 = Fleet()
    f2.reexec_resized(spec, coord_endpoint="127.0.0.1:1234",
                      script="/work/other.py", argv=["--resumed"])
    assert calls["args"][1:] == ["/work/other.py", "--resumed"]


# --------------------------------------------------------------------------
# settle_dead: survivors with DIVERGENT partial views agree on one set
# --------------------------------------------------------------------------

class _StubRole:
    def __init__(self, rank, world):
        self._r, self._n = rank, world

    def worker_index(self):
        return self._r

    def worker_num(self):
        return self._n


class _StubClient:
    """In-memory stand-in for the coord KV client: shared store + a
    fixed dead-peer answer, enough to drive settle_dead's poll/publish/
    ack protocol deterministically in one process."""

    def __init__(self, store, lock, dead):
        self._store, self._lock, self._dead = store, lock, dead

    def put(self, key, value):
        with self._lock:
            self._store[key] = bytes(value)

    def get(self, key, timeout_ms=-1, max_len=0):
        import time as _t
        deadline = _t.monotonic() + max(0, timeout_ms) / 1000.0
        while True:
            with self._lock:
                if key in self._store:
                    return self._store[key]
            if _t.monotonic() >= deadline:
                raise TimeoutError(key)
            _t.sleep(0.002)

    def heartbeat(self, worker_id):
        pass

    def dead_peers(self, max_age_ms):
        return list(self._dead)


def _stub_fleet(rank, world, store, lock, dead):
    f = Fleet()
    f._role = _StubRole(rank, world)
    f._client = _StubClient(store, lock, dead)
    f._initialized = True
    return f


def test_settle_dead_repairs_divergent_partial_views():
    """Two survivors of the same 4-worker crash observed DIFFERENT
    partial dead sets (liveness is not atomic); settle_dead converges
    both on the full set — leader publishes, peer adopts and acks — so
    plan_resize derives the SAME world on every survivor."""
    import threading
    store, lock = {}, threading.Lock()
    dead = ["worker-2", "worker-3"]
    f0 = _stub_fleet(0, 4, store, lock, dead)
    f1 = _stub_fleet(1, 4, store, lock, dead)
    out = {}

    def _run(rank, fleet_obj, observed):
        out[rank] = list(fleet_obj.settle_dead(
            observed, max_age_ms=80, poll_ms=10, timeout_ms=5000))

    ts = [threading.Thread(target=_run, args=(0, f0, ["worker-2"])),
          threading.Thread(target=_run, args=(1, f1, dead))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert out == {0: dead, 1: dead}
    assert store["fleet/resize/dead/g0"] == b"worker-2,worker-3"
    assert store["fleet/resize/ack/g0/1"] == b"1"
    specs = [f.plan_resize(out[r], rank=r, world=4)
             for r, f in ((0, f0), (1, f1))]
    assert [s["world"] for s in specs] == [2, 2]
    assert [s["rank"] for s in specs] == [0, 1]


def test_settle_dead_without_client_passes_observed_through():
    f = Fleet()
    assert f.settle_dead(["worker-1", "worker-0"]) == \
        ["worker-0", "worker-1"]


def test_settle_dead_all_stale_raises():
    import threading
    store, lock = {}, threading.Lock()
    dead = [f"worker-{r}" for r in range(2)]
    f = _stub_fleet(0, 2, store, lock, dead)
    with pytest.raises(ValueError, match="every rank is stale"):
        f.settle_dead(dead, max_age_ms=30, poll_ms=10, timeout_ms=500)


# --------------------------------------------------------------------------
# the multi-process shrink drill (ISSUE 7 acceptance)
# --------------------------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _single_process_losses():
    sys.path.insert(0, HERE)
    try:
        import fleet_resize_worker as fw
    finally:
        sys.path.pop(0)
    main, startup, loss, _opt = fw.build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = []
        for x, y in fw.global_batches():
            out.append(float(
                exe.run(main, feed={"img": x, "label": y},
                        fetch_list=[loss])[0]))
    return out


@pytest.mark.chaos
def test_fleet_8_to_4_shrink_restores_and_finishes(tmp_path):
    from paddle_tpu import native

    if not native.available():
        pytest.skip("native library not built")
    n, kill_ranks, kill_step = 8, (4, 5, 6, 7), 2
    env_base = {
        **os.environ,
        "PT_TRAINERS": str(n),
        "PT_COORD_ENDPOINT": f"127.0.0.1:{_free_port()}",
        "PT_JAX_COORD_ENDPOINT": f"127.0.0.1:{_free_port()}",
        "PT_RECOVER_PORT": str(_free_port()),
        "PT_RECOVER_JAX_PORT": str(_free_port()),
        "PT_CKPT_DIR": str(tmp_path / "ckpt"),
        "JAX_PLATFORMS": "",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]
        ),
    }
    os.makedirs(tmp_path / "ckpt", exist_ok=True)
    procs = []
    for rank in range(n):
        env = {**env_base, "PT_TRAINER_ID": str(rank)}
        if rank in kill_ranks:
            # the SEEDED kill: a fault plan, not test scaffolding — the
            # same plan string replays the same crash (hit kill_step+1
            # of the per-step site = the start of step kill_step)
            env["PT_FLAGS_fault_plan"] = \
                f"elastic.step:raise@{kill_step + 1}"
            env["PT_FLAGS_fault_seed"] = "7"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "fleet_resize_worker.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))
    results = {}
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=300)
        if rank in kill_ranks:
            assert p.returncode == 1, \
                f"victim {rank} should have died abruptly:\n{out}\n{err}"
            continue
        assert p.returncode == 0, f"worker {rank} failed:\n{out}\n{err}"
        line = [l for l in out.splitlines()
                if l.startswith("FLEET_RESULT ")]
        assert line, f"no result line from worker {rank}:\n{out}\n{err}"
        results[rank] = json.loads(line[-1][len("FLEET_RESULT "):])

    assert set(results) == {0, 1, 2, 3}
    single = _single_process_losses()
    for r in results.values():
        # every survivor re-rendezvoused at the shrunk world and resumed
        # from the newest valid 8-world checkpoint
        assert r["gen"] == 1 and r["world"] == 4
        assert r["start_step"] == kill_step
        assert sorted(r["dead_seen"]) == [
            f"worker-{k}" for k in kill_ranks]
        np.testing.assert_allclose(r["losses"], single[kill_step:],
                                   rtol=1e-4, atol=1e-5)
    assert results[0]["losses"][-1] < single[0]  # learning resumed


# --------------------------------------------------------------------------
# the multi-process GROW drill (ISSUE 14 acceptance): 4 -> 8 mid-run,
# newcomers read their XLA compiles from jax's persistent cache (no
# fresh compile on rejoin), optimizer slot state reshards, loss parity
# --------------------------------------------------------------------------

@pytest.mark.chaos
def test_fleet_4_to_8_grow_warm_starts_and_matches_loss(tmp_path):
    from paddle_tpu import native

    if not native.available():
        pytest.skip("native library not built")
    n0, n_join, grow_step = 4, 4, 2
    coord_ep = f"127.0.0.1:{_free_port()}"
    env_base = {
        **os.environ,
        "PT_TRAINERS": str(n0),
        "PT_COORD_ENDPOINT": coord_ep,
        "PT_JAX_COORD_ENDPOINT": f"127.0.0.1:{_free_port()}",
        "PT_RECOVER_PORT": str(_free_port()),
        "PT_RECOVER_JAX_PORT": str(_free_port()),
        "PT_CKPT_DIR": str(tmp_path / "ckpt"),
        # the jax cache every generation shares: incumbents write it
        # cold in generation 0, EVERYONE (newcomers included) must read
        # from it in generation 1
        **child_env(tmp_path / "jax_cache"),
        # coordination-only fleet: this container's CPU jax cannot form
        # a cross-process XLA world anyway (compute is replicated), and
        # single-process jax gives every rank the SAME device identity
        # — the condition (one shared local executable, the TPU-SPMD
        # same-global-program analog) under which newcomers lower the
        # HLO the incumbents compiled
        "PT_COORD_ONLY": "1",
        "JAX_PLATFORMS": "",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]
        ),
    }
    os.makedirs(tmp_path / "ckpt", exist_ok=True)
    procs = []
    for rank in range(n0):  # the generation-0 incumbents
        env = {**env_base, "PT_TRAINER_ID": str(rank),
               "PT_GROW_AT_STEP": str(grow_step),
               "PT_EXPECT_JOINERS": str(n_join)}
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "fleet_resize_worker.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))
    join_procs = []
    for j in range(n_join):  # the newcomers: announce + wait for plan
        env = {**env_base, "PT_JOIN_ID": str(j),
               "PT_JOIN_TARGET": coord_ep}
        env.pop("PT_TRAINER_ID", None)
        join_procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "fleet_resize_worker.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))

    def _collect(p, who):
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"{who} failed:\n{out}\n{err}"
        return out, err

    results, resize_plans, join_results = {}, [], []
    for rank, p in enumerate(procs):
        out, _err = _collect(p, f"incumbent {rank}")
        plan = [l for l in out.splitlines()
                if l.startswith("RESIZE_PLAN ")]
        assert plan, f"incumbent {rank} never planned the grow:\n{out}"
        resize_plans.append(json.loads(plan[-1][len("RESIZE_PLAN "):]))
        line = [l for l in out.splitlines()
                if l.startswith("FLEET_RESULT ")]
        assert line, f"no result line from incumbent {rank}:\n{out}"
        r = json.loads(line[-1][len("FLEET_RESULT "):])
        results[r["rank"]] = r
    for j, p in enumerate(join_procs):
        out, _err = _collect(p, f"joiner {j}")
        jline = [l for l in out.splitlines()
                 if l.startswith("JOIN_RESULT ")]
        assert jline, f"joiner {j} never admitted:\n{out}"
        join_results.append(json.loads(jline[-1][len("JOIN_RESULT "):]))
        line = [l for l in out.splitlines()
                if l.startswith("FLEET_RESULT ")]
        assert line, f"no result line from joiner {j}:\n{out}"
        r = json.loads(line[-1][len("FLEET_RESULT "):])
        results[r["rank"]] = r

    # every participant reached generation 1 of the 8-world
    assert set(results) == set(range(n0 + n_join))
    # every incumbent derived the SAME grow plan (direction metered)
    assert all(pl["direction"] == "grow" and pl["world"] == 8
               and pl["joins"] == [0, 1, 2, 3] for pl in resize_plans)
    # joiners were assigned the ranks after the survivors, and the
    # join-latency histogram observed each admission
    assert sorted(jr["rank"] for jr in join_results) == [4, 5, 6, 7]
    assert all(jr["join_latency_s"] >= 0 for jr in join_results)

    single = _single_process_losses()
    for r in results.values():
        assert r["gen"] == 1 and r["world"] == 8
        assert r["start_step"] == grow_step
        # THE warm-start acceptance: generation 1 compiled nothing —
        # everything it asked of the compiler was in jax's cache
        assert r["jax_cache"]["misses"] == 0, r
        assert r["jax_cache"]["hits"] >= 2, r  # startup + train step
        assert r["jax_cache"]["hits"] == r["jax_cache"]["requests"], r
        # loss parity vs the uninterrupted run: parameters AND Momentum
        # velocity state survived the grow (a dropped velocity diverges
        # the very first resumed step)
        np.testing.assert_allclose(r["losses"], single[grow_step:],
                                   rtol=1e-4, atol=1e-5)
    assert results[0]["losses"][-1] < single[0]  # learning resumed
