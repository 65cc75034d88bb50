"""Mean distance between the ``t0`` of the traced stretch's step
records over the window's (perf/step_records.py): how far the 2 s
every share by scope is read in stand from the 20 s
``train_tokens_per_s`` is read in; in a stationary cell, what the
profiler session costs a step."""

from perf import step_records


def read(run):
    s = step_records.for_run(run)
    return s["trace_ratio"] if s else None
