"""Shared measurement protocol for the bench_* scripts.

ONE copy of the window methodology: feeds pre-staged on device, 3x30-step
windows with a single host sync per window, best window = headline
device-throughput estimate, mean reported alongside. All bench
entrypoints import these so a protocol change cannot skew one family's
numbers against another's.
"""

from __future__ import annotations

import sys
import time

import numpy as np


def mfu(flops_per_step: float, steps: int, seconds: float,
        device_kind=None) -> float:
    """Analytic model-FLOPs utilization: ``flops_per_step * steps /
    seconds`` achieved FLOP/s over the bf16 peak of the device that ran
    (``roofline.backend_peaks``: the table row for ``device_kind``,
    default the attached device; a device without a row raises)."""
    from paddle_tpu import roofline

    return (float(flops_per_step) * steps / seconds) / \
        roofline.backend_peaks(device_kind)[0]


def configure_process():
    """Every bench child's first step, before any compile: place jax's
    persistent compilation cache (paddle_tpu.jax_cache) and log the
    device the numbers will come from."""
    import jax

    from paddle_tpu import jax_cache

    cache = jax_cache.configure()
    dev = jax.devices()[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}; jax cache: {cache}")


def measured_mfu(program, window_seconds: float, steps: int):
    """MEASURED MFU for a bench row, from the roofline plane: builds an
    estimate-source device profile (XLA cost-analysis flops from the
    program's compile report over the measured window seconds) and
    returns its ``measured_mfu`` — None when telemetry is off or no
    compile report carries flops (the row's field is then null, same
    backward-compatible rider contract as ``metrics``)."""
    try:
        from paddle_tpu import monitor, roofline

        if not monitor.enabled():
            return None
        prof = roofline.estimate_profile(
            program, device_seconds=float(window_seconds),
            steps=int(steps))
        v = prof.get("measured_mfu")
        return None if v is None else round(v, 4)
    except Exception as e:
        log(f"measured-MFU profile skipped: {type(e).__name__}: {e}")
        return None


def _is_oom(exc) -> bool:
    from paddle_tpu import monitor

    return monitor.is_oom_error(exc)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def enable_bench_metrics() -> bool:
    """Metrics-only telemetry for bench processes (PT_BENCH_METRICS=0
    opts out): counters/gauges/step records WITHOUT the step_phases
    plane, whose honest device timing would put a block_until_ready
    inside every timed window. Counter mutations are lock-guarded dict
    writes — noise-floor next to a training step.

    Also points ``compile_report_dir`` at a scratch dir so every fresh
    compile records its XLA cost analysis — the flops source for the
    rows' ``measured_mfu`` field. The report's extra AOT compile lands
    at warmup (cache misses), never inside a timed window;
    PT_BENCH_PROFILE=0 opts out of just this half."""
    import os

    if os.environ.get("PT_BENCH_METRICS", "1") != "1":
        return False
    from paddle_tpu import flags

    new = {"telemetry": True, "step_phases": False}
    if (os.environ.get("PT_BENCH_PROFILE", "1") == "1"
            and not flags.get_flag("compile_report_dir")):
        # a user-configured report dir (PT_FLAGS_compile_report_dir)
        # wins — only an UNSET flag gets the self-reaping scratch dir
        import atexit
        import shutil
        import tempfile

        d = tempfile.mkdtemp(prefix="pt_bench_cr_")
        # scratch dir, one per bench process: reap it at exit or a
        # bench.py invocation (~9 fresh subprocesses) leaks 9 of them
        atexit.register(shutil.rmtree, d, ignore_errors=True)
        new["compile_report_dir"] = d
    flags.set_flags(new)
    return True


def attach_metrics(row: dict) -> dict:
    """Snapshot the metrics registry into the BENCH row's ``metrics``
    field so a perf regression is attributable after the fact (cache
    hit/miss mix, feed bytes, retry counts, ...). Backward-compatible
    rider: the field is simply absent when telemetry is off, and a
    snapshot failure never loses the row. Empty instruments are dropped
    to keep rows readable."""
    try:
        from paddle_tpu import monitor

        if monitor.enabled():
            snap = monitor.snapshot()
            row["metrics"] = {name: m for name, m in snap.items()
                              if m["values"]}
    except Exception as e:
        log(f"metrics snapshot skipped: {type(e).__name__}: {e}")
    return row


def run_windows(exe, program, loss, feeds, steps=30, n_windows=3,
                multi=None):
    """Returns (best, mean) window seconds.

    ``multi`` (default on; PT_BENCH_MULTI=0 disables) runs each window
    as ONE compiled multi-step program (Executor.run_steps — the
    RunFromDataset-style hot loop): one host dispatch per window instead
    of one per step, without disturbing donation aliasing."""
    if multi is None:
        import os

        multi = os.environ.get("PT_BENCH_MULTI", "1") == "1"
    if multi:
        # Freeze the feed buffers ONCE (owning non-writeable copies) so
        # run_steps' staging cache may legally key on identity —
        # mutable numpy feeds are re-staged every call, which would put
        # the device_put stack back inside the timed window. Owning
        # copies, not views: a frozen view is still mutable through its
        # base, so the executor refuses to cache it.
        frozen = []
        for fd in feeds:
            ffd = {}
            for k, v in fd.items():
                if isinstance(v, np.ndarray):
                    v = v.copy()
                    v.flags.writeable = False
                ffd[k] = v
            frozen.append(ffd)
        feeds = frozen
        # warmup = one full-size window so only ONE multi-step executable
        # is compiled (steps is a static arg). The windowed program +
        # stacked feeds cost more HBM than the single-step program the
        # OOM backoff validated; an OOM here is the row's failure, not a
        # reason to change protocol under the same row name.
        exe.run_steps(program, feed_list=feeds, steps=steps,
                      fetch_list=[loss])
        windows = []
        for w in range(n_windows):
            t0 = time.perf_counter()
            out = exe.run_steps(program, feed_list=feeds, steps=steps,
                                fetch_list=[loss])
            loss_v = float(np.asarray(out[0]))
            elapsed = time.perf_counter() - t0
            log(f"window {w}: {steps} steps in {elapsed:.2f}s, "
                f"loss={loss_v:.3f}")
            windows.append(elapsed)
        return min(windows), sum(windows) / len(windows)
    for fd in feeds[:2]:
        exe.run(program, feed=fd, fetch_list=[loss])
    windows = []
    for w in range(n_windows):
        t0 = time.perf_counter()
        out = None
        for i in range(steps):
            out = exe.run(program, feed=feeds[i % len(feeds)],
                          fetch_list=[loss], return_numpy=False)
        loss_v = float(np.asarray(out[0]))  # sync once per window
        elapsed = time.perf_counter() - t0
        log(f"window {w}: {steps} steps in {elapsed:.2f}s, loss={loss_v:.3f}")
        windows.append(elapsed)
    return min(windows), sum(windows) / len(windows)


class AllBatchesOOM(RuntimeError):
    """Every batch size down to the floor hit device OOM."""


def compile_with_oom_backoff(make_exe, run_first, batch, floor=8):
    """Compile + run the first step, halving ``batch`` on device OOM.
    Returns (executor, batch). Any non-OOM error surfaces; total
    exhaustion raises AllBatchesOOM, which the bench scripts let
    propagate (a row that could not run exits non-zero, it does not
    print a perf 0)."""
    while batch >= floor:
        try:
            exe = make_exe()
            t0 = time.perf_counter()
            run_first(exe, batch)
            log(f"compile+first step: {time.perf_counter() - t0:.1f}s "
                f"(batch={batch})")
            return exe, batch
        except Exception as e:
            if not _is_oom(e):
                raise
            log(f"batch {batch} OOM; halving")
            batch //= 2
    raise AllBatchesOOM("all batch sizes OOM")
