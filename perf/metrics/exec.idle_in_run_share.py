"""Of the first chip's idle time in gaps of 20 us and more, the share
whose gap begins while the dispatching thread is inside an
``executor.run`` span: idle the executor's host work is answerable
for, against idle while the caller waits or prepares the next call.
0 when the chip has no such gap at all."""

from perf import spans


def read(run):
    s = spans.for_run(run)
    if not s or not s["host"]:
        return None
    h = s["host"]
    return 100.0 * h["idle_in_run_ns"] / h["idle_ns"] if h["idle_ns"] \
        else 0.0
