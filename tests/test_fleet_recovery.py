"""End-to-end failure recovery (VERDICT r4 item 6): kill one of 4 fleet
workers mid-train; survivors detect the death through coord liveness
(csrc/coord.cc op 'L' via fleet.barrier_or_dead), re-rendezvous as a
3-worker world, restore the per-step checkpoint, and finish training —
with per-step loss parity against an uninterrupted single-process run
of the same global batches.

Reference bar: SURVEY.md §5 failure-detection bullet (the reference's
heartbeat plane plus the recovery loop it never demonstrates)."""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid

HERE = os.path.dirname(os.path.abspath(__file__))
# ONE deadline for the four children together: a passing drill takes
# 25 s alone on this machine and 47-58 s beside sixteen busy processes
# on its eight cores, so 150 s is a hang, not a slow run
DRILL_DEADLINE_S = 150


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _single_process_losses():
    sys.path.insert(0, HERE)
    try:
        import fleet_recover_worker as fw
    finally:
        sys.path.pop(0)
    main, startup, loss = fw.build()
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


def test_fleet_kill_one_worker_recover(tmp_path):
    from paddle_tpu import native

    if not native.available():
        pytest.skip("native library not built")
    n, kill_rank, kill_step = 4, 3, 2
    env_base = {
        **os.environ,
        "PT_TRAINERS": str(n),
        "PT_COORD_ENDPOINT": f"127.0.0.1:{_free_port()}",
        "PT_JAX_COORD_ENDPOINT": f"127.0.0.1:{_free_port()}",
        "PT_RECOVER_PORT": str(_free_port()),
        "PT_RECOVER_JAX_PORT": str(_free_port()),
        "PT_CKPT_DIR": str(tmp_path / "ckpt"),
        "PT_KILL_RANK": str(kill_rank),
        "PT_KILL_STEP": str(kill_step),
        "JAX_PLATFORMS": "",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]
        ),
    }
    os.makedirs(tmp_path / "ckpt", exist_ok=True)
    # the children write to files, so a killed one's stderr is still
    # there to fail with
    deadline = time.monotonic() + DRILL_DEADLINE_S
    logs = [(tmp_path / f"worker{rank}.out", tmp_path / f"worker{rank}.err")
            for rank in range(n)]
    procs = []
    try:
        for rank, (out_path, err_path) in enumerate(logs):
            env = {**env_base, "PT_TRAINER_ID": str(rank)}
            with open(out_path, "w") as out, open(err_path, "w") as err:
                procs.append(subprocess.Popen(
                    [sys.executable,
                     os.path.join(HERE, "fleet_recover_worker.py")],
                    env=env, stdout=out, stderr=err,
                ))
        for p in procs:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                break
    finally:
        # on every way out: a survivor re-execs in place (same pid), so
        # this reaches generation 1 too
        hung = [rank for rank, p in enumerate(procs) if p.poll() is None]
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()
    outs = [(o.read_text(), e.read_text()) for o, e in logs]
    assert not hung, (
        f"workers {hung} still ran after {DRILL_DEADLINE_S} s; all "
        f"killed\n" + "\n".join(
            f"--- worker {rank} stderr ---\n{err[-4000:]}"
            for rank, (_, err) in enumerate(outs)))
    results = {}
    for rank, (p, (out, err)) in enumerate(zip(procs, outs)):
        if rank == kill_rank:
            assert p.returncode == 1, \
                f"victim should have died abruptly:\n{out}\n{err}"
            continue
        assert p.returncode == 0, f"worker {rank} failed:\n{out}\n{err}"
        line = [l for l in out.splitlines()
                if l.startswith("FLEET_RESULT ")]
        assert line, f"no result line from worker {rank}:\n{out}\n{err}"
        r = json.loads(line[-1][len("FLEET_RESULT "):])
        results[rank] = r

    assert set(results) == {0, 1, 2}
    single = _single_process_losses()
    for r in results.values():
        # every survivor went through recovery: generation 1, shrunk
        # world, resumed exactly at the kill step, having SEEN the dead
        # worker through the liveness query
        assert r["gen"] == 1 and r["world"] == n - 1
        assert r["start_step"] == kill_step
        assert r["dead_seen"] == [f"worker-{kill_rank}"]
        # the resumed trajectory matches the uninterrupted run
        np.testing.assert_allclose(r["losses"], single[kill_step:],
                                   rtol=1e-4, atol=1e-5)
    assert results[0]["losses"][-1] < single[0]  # learning resumed
