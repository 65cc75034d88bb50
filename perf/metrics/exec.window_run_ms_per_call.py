"""Mean ``wall_ms`` of the window's step records
(perf/step_records.py): host time of an executor call over every call
of the measured window, no profiler session open, where
``exec.run_ms_per_call`` reads the few spans of the traced stretch
under jax.profiler's Python tracer."""

from perf import step_records


def read(run):
    s = step_records.for_run(run)
    return s["run_ms"] if s else None
