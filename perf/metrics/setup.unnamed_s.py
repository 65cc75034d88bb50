"""``setup_s`` less the harness's first-call timers less the three
Program-building spans (``backward.append_backward``,
``optimizer.apply_gradients``, ``program.clone``): what neither the
harness's timers nor the program's spans name (imports, the backend's
start, the layers' own graph building, feeds, ``device_put``)."""

from perf import setup_stages
from perf.harness import say


def read(run):
    built = {s: setup_stages.span_seconds(run, s)
             for s in setup_stages.BUILD_SPANS}
    if all(b is None for b in built.values()) or not run.first_calls:
        return None
    first = sum(run.first_calls.values())
    unnamed = run.setup_s - first - sum(b or 0.0 for b in built.values())
    # a traced run's line carries no setup_s: the log does
    say(f"perf: set-up: setup_s {run.setup_s:.3f} = first calls "
        f"{first:.3f} + Program building "
        f"{ {k: round(v or 0.0, 3) for k, v in built.items()} } + "
        f"unnamed {unnamed:.3f}")
    return unnamed
