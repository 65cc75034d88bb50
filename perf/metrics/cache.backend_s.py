"""Seconds of the backend stage of the program's first calls
(``pt_compile_stage_seconds{stage="backend"}`` over every program but
``(outside)``): XLA's compile, or the read of the executable from jax's
persistent cache, as jax times it."""

from perf import setup_stages


def read(run):
    return setup_stages.stage_seconds(run, "backend")
