"""Seconds jax spent lowering the program's jaxprs to StableHLO
(``pt_compile_stage_seconds{stage="lower"}`` over every program but
``(outside)``)."""

from perf import setup_stages


def read(run):
    return setup_stages.stage_seconds(run, "lower")
