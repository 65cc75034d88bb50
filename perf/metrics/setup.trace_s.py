"""Seconds jax spent tracing the program's executables into jaxprs
(``pt_compile_stage_seconds{stage="trace"}`` over every program but
``(outside)``; a trace nested in another is counted once)."""

from perf import setup_stages


def read(run):
    return setup_stages.stage_seconds(run, "trace")
