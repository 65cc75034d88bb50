"""python perf/tools/span_cost.py [n]

What one ``monitor.span`` costs on the host, in microseconds (best of
five loops of n, default 200000): with telemetry off (the path every
untraced run takes), with telemetry on and no profiler session open,
and with a jax.profiler session open. Host clock only; touches no
device."""

import os
import sys
import tempfile
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def loop(n):
    from paddle_tpu import monitor

    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            with monitor.span("executor.run", step=i):
                pass
        best = min(best, time.perf_counter() - t0)
    t0 = time.perf_counter()
    for i in range(n):
        pass
    return (best - (time.perf_counter() - t0)) / n * 1e6


def main():
    import jax

    from paddle_tpu import flags

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200000
    flags.set_flags({"telemetry": False})
    off = loop(n)
    flags.set_flags({"telemetry": True})
    on = loop(n // 10)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            traced = loop(n // 100)
        finally:
            jax.profiler.stop_trace()
    flags.set_flags({"telemetry": False})
    print(f"span_cost: us a span: telemetry off {off:.3f}, telemetry on "
          f"without a profiler session {on:.3f}, inside a jax.profiler "
          f"session {traced:.3f}")


if __name__ == "__main__":
    main()
