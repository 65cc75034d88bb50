"""python perf/tools/spread.py chiprun_out/sets-<cell>.jsonl

Medians and spreads of the two sets a cell was measured in, as the
contract reads them: per metric the spread of each set (quartile
distance over the median, statistics.quantiles), the wider of the two,
five times it (the bound it asks for), and how far the second set's
median lies from the first's. The first run of the file compiled (or
loaded every program for the first time): its setup_s is shown apart."""

import json
import os
import statistics
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from perf import stats  # noqa: E402


def main():
    rows = [json.loads(ln) for ln in open(sys.argv[1])]
    sets = {s: [r for r in rows if r["set"] == s and r["line"]]
            for s in (1, 2)}
    bad = [r for r in rows if not r["line"] or not r["line"]["correct"]
           or r["rc"] != 0]
    print(f"{len(rows)} runs, {len(bad)} failed or incorrect")
    names = list(sets[1][0]["line"]["metrics"])
    for name in names:
        per_set = {}
        for s, rs in sets.items():
            vals = [r["line"]["metrics"][name]["value"] for r in rs]
            if name == "setup_s" and s == 1:
                print(f"  setup_s of the first run (compiles): {vals[0]}")
                vals = vals[1:]
            per_set[s] = vals
        med = {s: statistics.median(v) for s, v in per_set.items()}
        spr = {s: stats.spread(v) if len(v) >= 2 else float("nan")
               for s, v in per_set.items()}
        wide = max(spr.values())
        print(f"{name}: medians {med[1]:.6g} / {med[2]:.6g} (second "
              f"{(med[2] / med[1] - 1) * 100:+.3f}%), spreads "
              f"{spr[1] * 100:.3f}% / {spr[2] * 100:.3f}%, five times "
              f"the wider {5 * wide * 100:.2f}%")
        print(f"    set 1 {['%.6g' % v for v in per_set[1]]}")
        print(f"    set 2 {['%.6g' % v for v in per_set[2]]}")
    traced = [r for r in rows if r["trace"] == 1 and r["line"]]
    for r in traced:
        print("traced:", json.dumps(
            {k: v["value"] for k, v in r["line"]["metrics"].items()}))
        print("  device:", json.dumps(r["line"]["device"]))
        print("  breakdown:", json.dumps(r["line"].get("breakdown")))


if __name__ == "__main__":
    main()
