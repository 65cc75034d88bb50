"""Seconds under the program's ``executor.first_call`` spans: the first
calls of the PROGRAM's executables by the executor's own clock (trace,
lowering, XLA or the read from jax's cache, the first dispatch), the
reference's and the harness's own work left out. ``cache.first_call_s``
times the same calls from outside, with that work in."""

from perf import setup_stages


def read(run):
    return setup_stages.span_seconds(run, "executor.first_call")
