"""python perf/tools/scope_table.py <cell>.xplane.pb[.gz] <steps> [--blocks]

The WHOLE table by scope of one traced run (perf/spans.reduce's
``by_scope_ns``; a run's log prints its fifteen largest), ms a step and
chip: the raw trace a run leaves under ``PERF_KEEP_TRACE=<dir>``, the
steps it traced (the log's "traced N steps"). Scopes are summed over
the blocks (``blk3`` -> ``blk#``) and over an op and its grad op unless
``--blocks``; forward, backward and optimizer apart beside each sum."""

import gzip
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main():
    from perf import spans

    path, steps = sys.argv[1], int(sys.argv[2])
    blocks = "--blocks" in sys.argv[3:]
    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb",
                                         delete=False) as tmp, \
                gzip.open(path, "rb") as src:
            shutil.copyfileobj(src, tmp)
        path = tmp.name
    s = spans.reduce(spans.load(path))
    n = steps * s["chips"] * 1e6
    table = {}
    for key, ns in s["by_scope_ns"].items():
        phase, *scope = key.split("/")
        scope = "/".join(scope[:-1] if len(scope) > 1 else scope)
        if not blocks:
            scope = re.sub(r"blk\d+", "blk#", scope)
        row = table.setdefault(scope, {"fwd": 0.0, "bwd": 0.0, "opt": 0.0})
        row[phase] = row.get(phase, 0.0) + ns / n
    print(f"steps {steps} busy ms/step {s['busy_ns'] / n:.3f} scoped "
          f"{s['scoped_ns'] / n:.3f} unscoped "
          f"{(s['busy_ns'] - s['scoped_ns']) / n:.3f}")
    for scope, row in sorted(table.items(), key=lambda kv: -sum(
            kv[1].values())):
        print(f"{sum(row.values()):9.3f}  {scope}   (" + " ".join(
            f"{p} {v:.3f}" for p, v in row.items()) + ")")
    print("without a phase:", [[k, round(v / n, 3)]
                               for k, v in s["unscoped"]])


if __name__ == "__main__":
    main()
