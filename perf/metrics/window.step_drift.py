"""Mean distance between the ``t0`` of the window's step records in
the window's last tenth over its first tenth (perf/step_records.py): 1
where the window is stationary, and only then is a table by scope from
the traced stretch behind it the window's own. None under eight
distances."""

from perf import step_records


def read(run):
    s = step_records.for_run(run)
    return s["drift"] if s else None
