"""Of the window's seconds, the percent lost in single stretches: over
the distances between the ``t0`` of its step records that exceed 1.25
times their median, the sum of what each is over the median, less what
the two distances behind it are under it (a host that is late by less
than the steps in flight last delays a call and not the device: the
next calls follow sooner and nothing is lost; perf/step_records.py).
The window's end counts as a distance: from the last call to the
clock's stop, over the two steps then in flight. A lost stretch reads
its length, a window whose every step is slow reads 0."""

from perf import step_records


def read(run):
    s = step_records.for_run(run)
    return s["late_share"] if s else None
