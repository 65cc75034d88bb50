"""Mean of ``executor.prepare`` + ``executor.state`` per executor call
of the traced stretch: what a call costs before the jitted function
is entered (feed normalisation, fingerprint, cache entry; gathering,
committing and sharding the state)."""

from perf import spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["host"]:
        return None
    c = s["host"]["child_ns"]
    return (c["executor.prepare"] + c["executor.state"]) / 1e6
