"""Subprocess worker for the fresh-process warm-start test
(tests/test_warm_start.py): builds the same program pair (startup +
train step) every invocation, runs a startup pass and then three train
steps through ``Executor.run`` (``argv[1] == "run"``), a three-step
``run_steps`` window (``"run_steps_window"``), or three steps of the
program data-parallel over eight virtual devices
(``"data_parallel"``), and prints ONE JSON line with jax's persistent-cache events, the executor's own
accounting and the losses. The parent places the cache in this
process's environment.

Determinism contract: the program built here must lower to the same
HLO in every process — that is what jax's cache keys on.
"""

import json
import sys

import jax

jax.config.update("jax_platforms", "cpu")
if sys.argv[1] == "data_parallel":
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import flags, layers, monitor  # noqa: E402

from jax_cache_events import CacheEvents  # noqa: E402

STEPS = 3


def main():
    mode = sys.argv[1]
    events = CacheEvents()
    flags.set_flags({"telemetry": True})
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": np.linspace(-1.0, 1.0, 64, dtype=np.float32).reshape(8, 8)}
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if mode == "run_steps_window":
            losses = exe.run_steps(main_prog, feed_list=[feed], steps=STEPS,
                                   fetch_list=[loss])
        else:
            prog = main_prog if mode == "run" else fluid.CompiledProgram(
                main_prog).with_data_parallel(loss_name=loss.name)
            losses = [exe.run(prog, feed=feed, fetch_list=[loss])[0]
                      for _ in range(STEPS)]
    print(json.dumps({
        "jax_cache": events.snapshot(),
        "exec_misses":
            monitor.counter("pt_executor_cache_misses_total").value(),
        "outcomes": [r["cache"] for r in monitor.recent_steps()],
        # float.hex: the comparison is bit for bit
        "result": [float(v).hex() for v in np.ravel(np.asarray(losses))],
    }))


if __name__ == "__main__":
    main()
