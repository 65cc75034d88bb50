"""Mean duration of the ``executor.run`` spans (``executor.run_window``
for run_steps) of the traced stretch: host time of an executor call
while the device runs ahead, nothing serialised. Timed under
jax.profiler, whose Python tracer is on (harness.DeviceTrace): it
reads above an untraced call by what the tracer costs."""

from perf import spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["host"]:
        return None
    return s["host"]["run_ns"] / 1e6
