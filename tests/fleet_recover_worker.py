"""Self-healing fleet worker for the failure-recovery test (VERDICT r4
item 6; SURVEY.md §5 failure detection/recovery).

Generation 0: 4 workers train data-parallel; rank 0 checkpoints after
every step; the designated victim (PT_KILL_RANK) dies abruptly at the
start of step PT_KILL_STEP (no farewell — just process exit, so only
its heartbeat going stale reveals the death). The survivors' per-step
``fleet.barrier_or_dead`` (liveness-guarded barrier over csrc/coord.cc
op 'L') returns the dead id instead of hanging in the next psum; they
agree on the dead set (``fleet.settle_dead``: rank 0, which hosts the
generation's coordination server, leaves only when every survivor has
the set), plan the shrunk world (surviving old ranks in order), and each
re-execs itself as generation 1 with the pre-provisioned recovery
endpoints (``fleet.reexec_resized``).

Generation 1: 3 workers rendezvous fresh, restore the checkpoint, and
finish the remaining steps on 3-way shards of the SAME global batches —
so the harness can assert loss parity against an uninterrupted
single-process run of the whole schedule.

Run (harness: tests/test_fleet_recovery.py):
  PT_TRAINER_ID=r PT_TRAINERS=4 PT_COORD_ENDPOINT=127.0.0.1:p
  PT_RECOVER_PORT=p2 PT_RECOVER_JAX_PORT=p3 PT_CKPT_DIR=dir
  PT_KILL_RANK=3 PT_KILL_STEP=2 python fleet_recover_worker.py
"""

import json
import os

import jax

if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import io, layers  # noqa: E402
from paddle_tpu.incubate.fleet import fleet  # noqa: E402

GLOBAL_BATCH = 24
STEPS = 6
DIM, HID, CLS = 16, 32, 4


def deterministic_params():
    r = np.random.RandomState(11)
    return (
        r.normal(0, 0.1, (DIM, HID)).astype(np.float32),
        np.zeros(HID, np.float32),
        r.normal(0, 0.1, (HID, CLS)).astype(np.float32),
        np.zeros(CLS, np.float32),
    )


def global_batches():
    rng = np.random.RandomState(3)
    probe = np.random.RandomState(5).randn(DIM, CLS)
    out = []
    for _ in range(STEPS):
        x = rng.randn(GLOBAL_BATCH, DIM).astype(np.float32)
        y = np.argmax(x @ probe, 1).astype(np.int64)[:, None]
        out.append((x, y))
    return out


def build():
    w1, b1, w2, b2 = deterministic_params()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", shape=[DIM], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(
            img, HID, act="relu",
            param_attr=fluid.ParamAttr(
                name="w1",
                initializer=fluid.initializer.NumpyArrayInitializer(w1)),
            bias_attr=fluid.ParamAttr(
                name="b1",
                initializer=fluid.initializer.NumpyArrayInitializer(b1)),
        )
        logits = layers.fc(
            h, CLS,
            param_attr=fluid.ParamAttr(
                name="w2",
                initializer=fluid.initializer.NumpyArrayInitializer(w2)),
            bias_attr=fluid.ParamAttr(
                name="b2",
                initializer=fluid.initializer.NumpyArrayInitializer(b2)),
        )
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        opt = fleet.distributed_optimizer(fluid.optimizer.SGD(0.1))
        opt.minimize(loss)
    return main, startup, loss


def main():
    gen = int(os.environ.get("PT_GEN", "0"))
    kill_rank = int(os.environ.get("PT_KILL_RANK", "-1"))
    kill_step = int(os.environ.get("PT_KILL_STEP", "2"))
    ckpt = os.environ["PT_CKPT_DIR"]

    fleet.init()
    rank, n = fleet.worker_index(), fleet.worker_num()

    main_prog, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    start_step = 0
    if gen == 1:
        start_step = int(os.environ["PT_RESUME_STEP"])
        io.load_persistables(exe, ckpt, main_prog)
    compiled = fleet.compiled_program(main_prog)

    shard = GLOBAL_BATCH // n
    losses = []
    batches = global_batches()
    for i in range(start_step, STEPS):
        if gen == 0 and rank == kill_rank and i == kill_step:
            os._exit(1)  # abrupt death: no farewell, heartbeat goes stale
        dead = fleet.barrier_or_dead(f"step{i}-g{gen}", max_age_ms=1500)
        if dead:
            # The survivors cross the staleness threshold at different
            # polls. Rank 0 hosts this generation's coordination server
            # (and jax's service): were it to re-exec on its own reading,
            # a survivor still polling that server would die of the
            # closed connection and generation 1 would wait for it for
            # ever. settle_dead gives every survivor ONE dead set and
            # has rank 0 collect their acks before it leaves; a survivor
            # asks the server nothing after its ack.
            dead = fleet.settle_dead(dead, max_age_ms=1500)
            host = os.environ["PT_COORD_ENDPOINT"].rsplit(":", 1)[0]
            fleet.reexec_resized(
                fleet.plan_resize(dead),
                coord_endpoint=f"{host}:{os.environ['PT_RECOVER_PORT']}",
                jax_endpoint=f"{host}:{os.environ['PT_RECOVER_JAX_PORT']}",
                extra_env={"PT_RESUME_STEP": i,
                           "PT_DEAD_SEEN": ",".join(dead)})
        x, y = batches[i]
        xs = x[rank * shard:(rank + 1) * shard]
        ys = y[rank * shard:(rank + 1) * shard]
        out = exe.run(compiled, feed={"img": xs, "label": ys},
                      fetch_list=[loss])
        losses.append(float(out[0]))
        fleet.heartbeat()
        if rank == 0:
            io.save_persistables(exe, ckpt, main_prog)
            with open(os.path.join(ckpt, "meta.json"), "w") as f:
                json.dump({"next_step": i + 1}, f)

    print("FLEET_RESULT " + json.dumps({
        "rank": rank, "gen": gen, "world": n, "start_step": start_step,
        "dead_seen": os.environ.get("PT_DEAD_SEEN", "").split(",")
        if os.environ.get("PT_DEAD_SEEN") else [],
        "losses": losses}), flush=True)
    fleet.barrier(f"done-g{gen}")
    fleet.stop_worker()


if __name__ == "__main__":
    main()
