"""Peak bytes held on the fullest of the cell's chips, in GB: the
high-water marks of memory_stats()'s bytes_in_use (arrays) and
bytes_reserved (a running program's temporaries) added up
(harness.peak_memory_bytes says why)."""

from perf import harness


def read(run):
    peak = harness.peak_memory_bytes(run.devices[:run.cell["chips"]])
    return peak / 1e9 if peak else None
