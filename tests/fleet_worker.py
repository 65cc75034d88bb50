"""Worker process for the fleet 2-process localhost test
(the analog of the reference's dist_mnist.py trainer script spawned by
tests/unittests/test_dist_base.py:311). Prints per-step losses as one JSON
line on stdout; the harness asserts parity against a single-process run.

Run: PT_TRAINER_ID=<r> PT_TRAINERS=2 PT_COORD_ENDPOINT=127.0.0.1:<p> \
     python fleet_worker.py
"""

import json

import jax

if __name__ == "__main__":
    # Only when run as a worker process — the test harness also imports
    # this module (for build()/global_batches()) inside a pytest process
    # whose jax backend is already configured.
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.incubate.fleet import fleet  # noqa: E402

GLOBAL_BATCH = 32
STEPS = 3
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
    fleet.init()
    rank, n = fleet.worker_index(), fleet.worker_num()
    assert jax.device_count() == 2 * n, jax.devices()

    main_prog, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    compiled = fleet.compiled_program(main_prog)

    shard = GLOBAL_BATCH // n
    losses = []
    for x, y in global_batches():
        xs = x[rank * shard : (rank + 1) * shard]
        ys = y[rank * shard : (rank + 1) * shard]
        out = exe.run(compiled, feed={"img": xs, "label": ys},
                      fetch_list=[loss])
        losses.append(float(out[0]))
        fleet.heartbeat()

    assert fleet.dead_workers(max_age_ms=60_000) == []
    fleet.barrier("done")
    print("FLEET_RESULT " + json.dumps({"rank": rank, "losses": losses}),
          flush=True)
    fleet.stop_worker()


if __name__ == "__main__":
    main()
