"""Sum of the ``gc_ms`` of the window's step records
(perf/step_records.py): milliseconds CPython's collector paused the
dispatching process for inside the window (``pt_gc_pause_seconds``,
``monitor._on_gc``)."""

from perf import step_records


def read(run):
    s = step_records.for_run(run)
    return s["gc_ms"] if s else None
