"""Profiler front end (reference: python/paddle/fluid/profiler.py).

Host spans go to the native C++ profiler (csrc/profiler.cc -> chrome trace,
the analog of RecordEvent + tools/timeline.py). Device-side profiling is
delegated to jax.profiler (XLA xplane -> TensorBoard/perfetto), replacing
the reference's CUPTI DeviceTracer (reference: platform/device_tracer.cc).
"""

from __future__ import annotations

import contextlib
import time
import warnings
from typing import Optional

# Fast-path flag so per-step record_event calls cost one attribute check
# when profiling is off.
_host_enabled = False

# Trace-timeline hook, installed by monitor.py at import: a zero-arg
# callable returning either an ``emit(name, t0_perf, t1_perf)`` function
# (trace collection active) or None. Keeping the gate on monitor's side
# means record_event needs no monitor import and the old profiler API
# and the new timeline share ONE clock (perf_counter) and one stream.
_trace_hook = None

def _trace_mark(name: str):
    """Instant event on the timeline (no-op unless monitor's trace
    collection is active) marking a legacy profiler lifecycle call."""
    import sys

    monitor = sys.modules.get("paddle_tpu.monitor")
    if monitor is not None:
        monitor.trace_event(name, "profiler", time.perf_counter())


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: str = "/tmp/profile", with_xplane: bool = False):
    """Context manager enabling host-span + device profiling.

    Writes <profile_path>.json (chrome trace of host spans). With
    ``with_xplane=True`` also captures the XLA device trace to
    <profile_path>_xplane/ via jax.profiler (opt-in: traces are large
    and tracing slows the host).
    """
    global _host_enabled
    from paddle_tpu import native

    use_native = native.available()
    if use_native:
        native.profiler_enable()
        _host_enabled = True
    _trace_mark("profiler.start")
    jax_trace_dir = profile_path + "_xplane"
    jax_started = False
    if with_xplane:
        try:
            import jax

            jax.profiler.start_trace(jax_trace_dir)
            jax_started = True
        except Exception as e:
            # a silently-dead xplane capture looks identical to "forgot
            # to open TensorBoard" — make the failure visible
            warnings.warn(
                f"jax.profiler.start_trace({jax_trace_dir!r}) failed; "
                f"no xplane device trace will be captured: {e!r}",
                RuntimeWarning, stacklevel=3)
    try:
        yield
    finally:
        if jax_started:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as e:
                warnings.warn(
                    f"jax.profiler.stop_trace() failed; the xplane trace "
                    f"under {jax_trace_dir!r} may be missing or "
                    f"truncated: {e!r}", RuntimeWarning, stacklevel=3)
        _trace_mark("profiler.stop")
        if use_native:
            native.profiler_disable()
            _host_enabled = False
            native.profiler_dump(profile_path + ".json")


@contextlib.contextmanager
def record_event(name: str):
    """RAII host span (reference: platform/profiler.h:81 RecordEvent).

    With monitor's trace collection active every span — including
    legacy direct callers of this API — additionally lands in the
    trace-event ring on the same perf_counter clock as the new
    timeline. Both collectors off: a bare yield."""
    emit = _trace_hook() if _trace_hook is not None else None
    host = _host_enabled
    if not host and emit is None:
        yield
        return
    if host:
        from paddle_tpu import native

        native.profiler_begin(name)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if emit is not None:
            emit(name, t0, time.perf_counter())
        if host:
            native.profiler_end()


def start_profiler(state: str = "All"):
    global _host_enabled
    from paddle_tpu import native

    if native.available():
        native.profiler_enable()
        _host_enabled = True
    _trace_mark("profiler.start")


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile"):
    global _host_enabled
    from paddle_tpu import native

    _trace_mark("profiler.stop")
    if native.available():
        native.profiler_disable()
        _host_enabled = False
        native.profiler_dump(profile_path + ".json")
