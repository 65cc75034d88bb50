"""Benchmark rider: synchronous vs pipelined steady-state step time.

Drives the SAME trainer workload twice through `contrib.Trainer`:

- **sync** — the pre-PR-10 configuration: per-step phase attribution
  (`step_phases_every_n=1`, a `block_until_ready` every step) and
  synchronous `DataFeeder` staging (`prefetch_depth=0`).
- **pipelined** — the async steady-state default: sampled phases
  (`step_phases_every_n=8`), `DeviceLoader` device-feed prefetch
  (batch N+1's `device_put` overlaps batch N's device phase) and
  overlapped fetch (`LazyFetches`).

Steady state is the LAST epoch (epoch 0 pays the compile + warmup).
Prints ONE JSON line in the driver format: ``value`` is the pipelined
steady-state ms/step, ``vs_baseline`` is ``sync / pipelined`` (>1.0 =
the pipeline beats the synchronous path). The pipelined run's final
boundedness verdict mix rides along — acceptance is `input_bound` +
`dispatch_bound` ~zero at steady state — and the full metrics snapshot
lands in the row's ``metrics`` field.

Env knobs: ``PT_BENCH_BATCH`` (default 256), ``PT_BENCH_WIDTH``
(hidden width, default 1024), ``PT_BENCH_PIPE_STEPS`` (steps/epoch,
default 30); ``JAX_PLATFORMS=cpu`` runs it on the CPU.
"""

from __future__ import annotations

import json
import os
import time

BATCH = int(os.environ.get("PT_BENCH_BATCH", "256"))
WIDTH = int(os.environ.get("PT_BENCH_WIDTH", "1024"))
STEPS = int(os.environ.get("PT_BENCH_PIPE_STEPS", "30"))
EPOCHS = 3


def run_mode(pipelined: bool):
    """One trainer run; returns (ms/step over the last epoch, verdict
    mix at the end of the run)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import flags, layers, monitor
    from paddle_tpu.contrib import BeginEpochEvent, EndEpochEvent, Trainer

    monitor.reset()
    flags.set_flags({
        "telemetry": True,
        "step_phases": True,
        "step_phases_every_n": 8 if pipelined else 1,
        "prefetch_depth": 2 if pipelined else 0,
    })

    def train_func():
        x = layers.data("x", shape=[WIDTH], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        h = x
        for _ in range(4):
            h = layers.fc(h, WIDTH, act="relu")
        logits = layers.fc(h, 16)
        return [layers.mean(
            layers.softmax_with_cross_entropy(logits, label))]

    def reader():
        # a realistic host pipeline: generate + normalize (the synthetic
        # stand-in for decode/augment) per batch. Sync mode pays this
        # serially on the step loop; the pipelined mode overlaps it in
        # the prefetch worker.
        def gen():
            rng = np.random.RandomState(0)
            for _ in range(STEPS):
                x = rng.randn(BATCH, WIDTH)
                x = (x - x.mean(axis=1, keepdims=True)) / (
                    x.std(axis=1, keepdims=True) + 1e-6)
                yield list(zip(
                    x.astype(np.float32),
                    rng.randint(0, 16, BATCH).astype(np.int64)))

        return gen

    marks = []

    def handler(event):
        if isinstance(event, (BeginEpochEvent, EndEpochEvent)):
            marks.append((type(event).__name__, event.epoch,
                          time.perf_counter()))

    trainer = Trainer(train_func, lambda: fluid.optimizer.SGD(0.05))
    trainer.train(EPOCHS, handler, reader(), ["x", "label"],
                  log_time_attribution=False)
    last = EPOCHS - 1
    t0 = next(t for k, e, t in marks if k == "BeginEpochEvent"
              and e == last)
    t1 = next(t for k, e, t in marks if k == "EndEpochEvent" and e == last)
    ms_per_step = (t1 - t0) * 1e3 / STEPS
    c = monitor.counter("pt_step_bound_total")
    mix = {v: int(c.value(labels={"verdict": v}))
           for v in monitor.BOUND_VERDICTS}
    return ms_per_step, mix


def main():
    from bench_common import attach_metrics, configure_process, log

    configure_process()
    sync_ms, sync_mix = run_mode(pipelined=False)
    log(f"sync: {sync_ms:.3f} ms/step, verdicts {sync_mix}")
    pipe_ms, pipe_mix = run_mode(pipelined=True)
    log(f"pipelined: {pipe_ms:.3f} ms/step, verdicts {pipe_mix}")
    overhead_verdicts = pipe_mix["input_bound"] + pipe_mix["dispatch_bound"]
    print(json.dumps(attach_metrics({
        "metric": "pipeline_steady_step_ms",
        "value": round(pipe_ms, 3),
        "unit": "ms/step",
        "vs_baseline": round(sync_ms / pipe_ms, 3) if pipe_ms else 0.0,
        "sync_ms_per_step": round(sync_ms, 3),
        "pipelined_ms_per_step": round(pipe_ms, 3),
        "sync_verdicts": sync_mix,
        "pipelined_verdicts": pipe_mix,
        "pipelined_overhead_verdicts": overhead_verdicts,
    })))


if __name__ == "__main__":
    main()
