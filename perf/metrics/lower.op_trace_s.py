"""Seconds of the first calls' jaxpr traces spent in the program's own
op rules (``pt_op_trace_seconds`` summed over the op types: a
control-flow op is charged only what its sub-block's ops are not, so no
second counts twice); the ten dearest op types go to the run's log."""

from perf import setup_stages


def read(run):
    name = "pt_op_trace_seconds"
    s = setup_stages.total(run, name, "sum")
    if s is not None:
        setup_stages.say_top(run, name, "op",
                             "dearest op rules [op, seconds, ops lowered]")
    return s
