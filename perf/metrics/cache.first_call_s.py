"""Seconds of the first calls of the cell's own programs during set-up
(compile, or load from jax's persistent cache), the correctness
sample's programs included."""


def read(run):
    return sum(run.first_calls.values()) if run.first_calls else None
