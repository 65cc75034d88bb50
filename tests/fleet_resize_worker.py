"""Elastic-resize fleet worker for the shrink AND grow chaos drills
(ISSUE 7 8->4 shrink; ISSUE 14 4->8 scale-OUT; SURVEY.md §5 failure
detection/recovery + ROADMAP item 3 elastic resize).

SHRINK (ISSUE 7): generation 0: 8 workers train; EVERY worker
participates in the per-step coordinated checkpoint save (the
multi-host commit barrier: non-zero ranks write their manifest fragment
+ shard file, ack over the fleet KV, rank 0 publishes only after all
acks). The victims die at the start of a chosen step, driven by a
SEEDED fault plan (`elastic.step:raise@N` via PT_FLAGS_fault_plan, so
the chaos run replays exactly); only their heartbeats going stale
reveals the deaths. Survivors' ``fleet.barrier_or_dead`` returns the
dead ids; each derives the SAME shrunk world via ``fleet.plan_resize``
and re-execs itself through ``fleet.reexec_resized`` (generation 1,
pre-provisioned recovery endpoints).

GROW (ISSUE 14): generation 0: 4 workers train. Newcomer processes
(PT_JOIN_ID set) announce themselves against the RUNNING world through
``fleet.join_world`` — the generation-keyed join protocol over fleet
KV — and wait for the leader's published plan. At PT_GROW_AT_STEP the
incumbents settle the announced joiner set (``fleet.settle_joins``,
same stability-window agreement settle_dead uses), derive the grown
world (``plan_resize(joins=...)``, survivors keep relative order,
joiners take the ranks after them), rank 0 publishes the plan +
recovery endpoints for the joiners, and EVERYONE re-execs to
generation 1. The 8-worker generation restores the newest valid
4-writer checkpoint — optimizer slot state re-keyed through
``checkpoint.reshard_optimizer_state`` — and, with
``JAX_COMPILATION_CACHE_DIR`` placed by the harness, reads its XLA
compiles from jax's persistent cache (the generation-0 incumbents wrote
them; every result line carries jax's own cache events).

Generation 1 (both drills): workers rendezvous fresh, restore the
newest VALID checkpoint via ``checkpoint.load_latest`` and finish the
remaining steps, so the harness can assert loss parity against an
uninterrupted single-process run.

Compute is REPLICATED (every worker runs the full global batch on its
local device): this environment's jax/CPU build cannot execute
multiprocess XLA computations (the same pre-existing wall behind the
test_fleet/test_fleet_recovery parity failures), and the drills'
subject is the host-side recovery plane — seeded kill, stale-heartbeat
detection, join announcement/settling, resize agreement, re-exec,
commit barrier, cross-world restore, compile-cache warm start.
Bit-exact SHARDED save-on-A/restore-on-B — parameters AND optimizer
slot state — is proven in-process by the mesh matrices in
tests/test_checkpoint.py.

Run (harness: tests/test_elastic_resize.py):
  PT_TRAINER_ID=r PT_TRAINERS=8 PT_COORD_ENDPOINT=127.0.0.1:p
  PT_RECOVER_PORT=p2 PT_RECOVER_JAX_PORT=p3 PT_CKPT_DIR=dir
  PT_FLAGS_fault_plan='elastic.step:raise@3'  # shrink victims only
  PT_GROW_AT_STEP=2 PT_EXPECT_JOINERS=4       # grow incumbents only
  PT_JOIN_ID=j PT_JOIN_TARGET=127.0.0.1:p     # grow joiners only
  python fleet_resize_worker.py
"""

import json
import os

import jax

if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import faults, layers  # noqa: E402
from paddle_tpu.executor import global_scope  # noqa: E402
from paddle_tpu.incubate.fleet import fleet  # noqa: E402
from paddle_tpu.parallel import checkpoint as ckpt  # noqa: E402

from jax_cache_events import CacheEvents  # noqa: E402

GLOBAL_BATCH = 24
STEPS = 6
DIM, HID, CLS = 16, 32, 4

# the victims' seeded fault plan raises here (PT_FLAGS_fault_plan armed
# the site at import); survivors' plans are empty
_F_STEP = faults.site("elastic.step")


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
        # Momentum, not SGD: velocity slot state makes the resumed-loss
        # parity assert prove optimizer-state survival across the resize
        opt = fluid.optimizer.Momentum(0.1, momentum=0.9)
        opt.minimize(loss)
    return main, startup, loss, opt


def main():
    events = CacheEvents()
    gen = fleet.generation()
    ckpt_dir = os.environ["PT_CKPT_DIR"]

    join_id = os.environ.get("PT_JOIN_ID")
    if join_id is not None and gen == 0:
        # NEWCOMER: announce against the running generation-0 world and
        # wait for the leader's plan; then re-exec as a full member of
        # generation 1 (complete EnvRoleMaker env from the plan)
        spec = fleet.join_world(os.environ["PT_JOIN_TARGET"],
                                join_id=int(join_id), timeout_ms=120_000)
        print("JOIN_RESULT " + json.dumps({
            "join_id": int(join_id), "rank": spec["rank"],
            "world": spec["world"],
            "join_latency_s": spec["join_latency_s"]}), flush=True)
        fleet.reexec_resized(spec,
                             coord_endpoint=spec["coord_endpoint"],
                             jax_endpoint=spec.get("jax_endpoint"))

    fleet.init()
    rank, n = fleet.worker_index(), fleet.worker_num()

    main_prog, startup, loss, opt = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    slots = opt.slot_descriptor()

    start_step = 0
    if gen == 1:
        # cross-world restore: serials were committed by the OTHER-SIZED
        # world (one manifest fragment + shard file per old rank);
        # load_latest reassembles them regardless of who saved, and
        # optimizer slot state is re-keyed onto THIS build's slot names
        # (identity here — the drift matrix is tests/test_checkpoint.py)
        loaded = ckpt.load_latest(ckpt_dir)
        assert loaded is not None, "no valid checkpoint after resize"
        start_step = loaded[0]
        values = ckpt.reshard_optimizer_state(
            loaded[1], ckpt.manifest_slots(ckpt_dir, start_step), slots)
        scope = global_scope()
        for k, v in values.items():
            scope.set(k, v)

    host = os.environ["PT_COORD_ENDPOINT"].rsplit(":", 1)[0]
    grow_at = os.environ.get("PT_GROW_AT_STEP")
    losses = []
    batches = global_batches()
    for i in range(start_step, STEPS):
        try:
            _F_STEP.hit()  # victims' seeded plan kills them HERE
        except faults.InjectedFault:
            os._exit(1)  # abrupt death: heartbeat goes stale, no farewell
        if gen == 0 and grow_at is not None and i == int(grow_at):
            # INCUMBENT at the grow step: settle the announced joiner
            # set, derive the grown world, leader publishes the plan
            # (and holds the coord server up until every joiner acked),
            # everyone re-execs to generation 1
            joins = fleet.settle_joins(
                max_age_ms=1500,
                min_count=int(os.environ.get("PT_EXPECT_JOINERS", "1")))
            spec = fleet.plan_resize((), joins=joins)
            coord_ep = f"{host}:{os.environ['PT_RECOVER_PORT']}"
            jax_ep = f"{host}:{os.environ['PT_RECOVER_JAX_PORT']}"
            if fleet.is_first_worker():
                fleet.publish_join_plan(spec, coord_endpoint=coord_ep,
                                        jax_endpoint=jax_ep)
            from paddle_tpu.incubate.fleet.fleet_base import (
                resize_direction,
            )
            print("RESIZE_PLAN " + json.dumps({
                "rank": rank, "direction": resize_direction(spec),
                "world": spec["world"], "joins": joins}), flush=True)
            fleet.reexec_resized(spec, coord_endpoint=coord_ep,
                                 jax_endpoint=jax_ep)
        dead = fleet.barrier_or_dead(f"step{i}-g{gen}", max_age_ms=1500)
        if dead:
            # simultaneous deaths go stale at different poll instants:
            # settle + agree on ONE dead set before planning the world
            dead = fleet.settle_dead(dead, max_age_ms=1500)
            spec = fleet.plan_resize(dead)
            fleet.reexec_resized(
                spec,
                coord_endpoint=f"{host}:{os.environ['PT_RECOVER_PORT']}",
                jax_endpoint=f"{host}:{os.environ['PT_RECOVER_JAX_PORT']}",
                extra_env={"PT_DEAD_SEEN": ",".join(
                    sorted(str(d) for d in dead))},
            )
        x, y = batches[i]
        out = exe.run(main_prog, feed={"img": x, "label": y},
                      fetch_list=[loss])
        losses.append(float(out[0]))
        fleet.heartbeat()
        # EVERY rank joins the coordinated save (commit barrier): rank 0
        # publishes only after all acks, so a committed serial always
        # holds every writer's fragments. The manifest records the slot
        # descriptors so a differently-built restore can re-key them.
        ckpt.save_scope(ckpt_dir, step=i + 1, slots=slots)

    result = {
        "rank": rank, "gen": gen, "world": n, "start_step": start_step,
        "dead_seen": os.environ.get("PT_DEAD_SEEN", "").split(",")
        if os.environ.get("PT_DEAD_SEEN") else [],
        "losses": losses,
        # the grow drill's warm-start accounting: what generation 1
        # asked of the compiler, and what jax's cache answered
        "jax_cache": events.snapshot()}
    print("FLEET_RESULT " + json.dumps(result), flush=True)
    fleet.barrier(f"done-g{gen}")
    fleet.stop_worker()


if __name__ == "__main__":
    main()
