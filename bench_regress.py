"""Bench-trajectory regression gate.

Compares a fresh bench row (bench.py's driver-format JSON, headline +
nested family rows) against a history of earlier rounds (``--history``,
a glob of round files; the repo commits none) and exits nonzero when any family's throughput regressed: a metric fails
when its ``value_mean`` (falling back to ``value``) drops more than the
family tolerance below the TRAILING BEST across the history rounds.

Two metric classes are gated. Higher-is-better throughput metrics —
rows whose ``unit`` contains ``/sec`` (tokens/sec, images/sec,
examples/sec) — fail when they drop below the trailing best. A small
explicit allowlist of lower-is-better latency metrics
(``LATENCY_TOLERANCE``: serving TTFT / queue-wait p95) fail when they
rise above the trailing best (the MINIMUM across history). All other
lower-is-better riders (pipeline step times) are
reported informationally but never gate: their CPU-vs-TPU variance is
not a regression signal.

Usage:
    python bench_regress.py --history 'rounds/r*.json'
                                   # newest round vs the earlier ones
    python bench_regress.py --history 'rounds/r*.json' --row fresh.json
                                   # a fresh row vs ALL rounds
    python bench_regress.py ... --tolerance 0.2  # loosen every family

``--row`` accepts either a bare bench row or the driver wrapper
(``{"parsed": {...}}``). Exit code: 0 = no gated metric regressed,
1 = regression(s) found, 2 = usage/history errors.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# Per-family tolerance: fraction below the trailing best that still
# passes. 0.10 was the round-to-round noise envelope of rounds 1-5
# (worst healthy ratio: deepfm r05/r04 = 0.979; the round files are no
# longer in the repo); widen a family here — not globally — when its methodology says so.
DEFAULT_TOLERANCE = 0.10
FAMILY_TOLERANCE: Dict[str, float] = {
    # the serving decode loop is host-scheduler-paced (one Python tick
    # per emitted token), so its throughput carries more host jitter
    # than the compiled train-step families; gates under the
    # union-baseline rules from its first history round onward
    "serving_decode_tokens_per_sec": 0.15,
    # the degraded-mode serving row (bench_serving.py: the same sweep
    # under a seeded serve.decode delay fault at 1% of steps) measures
    # resilience overhead; the injected delays add sampling noise on
    # top of the host jitter, so it gets the widest envelope
    "serving_degraded_tokens_per_sec": 0.20,
    # the fleet row (bench_serving.py: N routed replicas vs one at the
    # same offered load) layers router scheduling + supervisor loop
    # threads on top of the host-paced decode, so it inherits the
    # degraded row's envelope
    "serving_fleet_tokens_per_sec": 0.20,
}

# Lower-is-better latency families (explicit allowlist — a unit of
# "ms" alone does NOT gate): fraction ABOVE the trailing best (the
# minimum across history) that still passes. The serving latency
# riders are host-timed tail percentiles over a small request sample,
# so they carry far more noise than the throughput means — hence the
# wide 50% envelope; tighten per-family once a history shows a
# stable floor.
LATENCY_TOLERANCE: Dict[str, float] = {
    "serving_ttft_ms_p95": 0.50,
    "serving_queue_wait_ms_p95": 0.50,
    "serving_fleet_token_ms_p99": 0.50,
}

# Deliberately dropped families: a gated metric carried by ANY history
# round must reappear in every fresh row (a crashed bench subprocess
# must not pass the gate by producing no number — even if one bad
# round already committed without it); retiring a family is an
# explicit entry here, not a silent disappearance.
RETIRED_METRICS: frozenset = frozenset()


def flatten_row(parsed: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{metric: {"value", "unit"}} over a driver row: the headline plus
    every nested family/rider row carrying a numeric ``value`` (the
    ``metrics`` registry snapshot is skipped)."""
    out: Dict[str, Dict[str, Any]] = {}

    def visit(row):
        if not isinstance(row, dict):
            return
        name = row.get("metric")
        val = row.get("value_mean", row.get("value"))
        if isinstance(name, str) and isinstance(val, (int, float)):
            out[name] = {"value": float(val),
                         "unit": str(row.get("unit", ""))}
        for k, v in row.items():
            if k != "metrics" and isinstance(v, dict):
                visit(v)

    visit(parsed)
    return out


def _load_round(path: str) -> Optional[Dict[str, Any]]:
    with open(path) as f:
        doc = json.load(f)
    parsed = doc.get("parsed", doc) if isinstance(doc, dict) else None
    if not isinstance(parsed, dict):
        return None
    return flatten_row(parsed)


def load_history(paths: List[str]) -> List[Tuple[str, Dict[str, Any]]]:
    """[(round_name, flat_row)] in path-sorted (round) order, skipping
    rounds whose JSON carries no parseable row (a crashed bench run
    records rc/tail but parsed: null)."""
    hist = []
    for p in sorted(paths):
        try:
            flat = _load_round(p)
        except (OSError, ValueError) as e:
            print(f"bench_regress: skipping unreadable {p}: {e}",
                  file=sys.stderr)
            continue
        if flat:
            hist.append((os.path.basename(p), flat))
    return hist


def gated(unit: str) -> bool:
    """Whether a metric's unit marks it higher-is-better throughput."""
    return "/sec" in unit


def gated_latency(metric: str) -> bool:
    """Whether a metric is on the lower-is-better latency allowlist."""
    return metric in LATENCY_TOLERANCE


def check(fresh: Dict[str, Dict[str, Any]],
          history: List[Tuple[str, Dict[str, Any]]],
          tolerance: float = DEFAULT_TOLERANCE) -> List[Dict[str, Any]]:
    """Regression findings for ``fresh`` against the trailing best of
    ``history``: one record per gated metric whose value fell more than
    the (per-family) tolerance below the best historical value. Metrics
    with no history (a brand-new family) never gate — but a gated
    metric carried by ANY history round and absent from ``fresh`` is
    itself a finding (`missing: true`): a family whose bench
    subprocess crashed outright must not pass the gate by producing no
    number, and one bad committed round must not erode the guarantee
    for every later run. Deliberate removals go in
    ``RETIRED_METRICS``."""
    findings = []
    # latest carrier per gated metric across the whole history
    carriers: Dict[str, Tuple[str, Dict[str, Any]]] = {}
    for rname, flat in history:
        for metric, cell in flat.items():
            if gated(cell.get("unit", "")) or gated_latency(metric):
                carriers[metric] = (rname, cell)
    for metric, (rname, cell) in sorted(carriers.items()):
        if metric not in fresh and metric not in RETIRED_METRICS:
            findings.append({
                "metric": metric,
                "value": None,
                "unit": cell["unit"],
                "best": cell["value"],
                "best_round": rname,
                "ratio": 0.0,
                "tolerance": LATENCY_TOLERANCE.get(
                    metric, FAMILY_TOLERANCE.get(metric, tolerance)),
                "missing": True,
            })
    for metric, cell in sorted(fresh.items()):
        if not gated(cell.get("unit", "")):
            continue
        best = best_round = None
        for rname, flat in history:
            prev = flat.get(metric)
            if prev is None or not gated(prev.get("unit", "")):
                continue
            if best is None or prev["value"] > best:
                best, best_round = prev["value"], rname
        if best is None or best <= 0:
            continue
        tol = FAMILY_TOLERANCE.get(metric, tolerance)
        ratio = cell["value"] / best
        if ratio < 1.0 - tol:
            findings.append({
                "metric": metric,
                "value": cell["value"],
                "unit": cell["unit"],
                "best": best,
                "best_round": best_round,
                "ratio": round(ratio, 4),
                "tolerance": tol,
            })
    # lower-is-better latency allowlist: "best" is the MINIMUM across
    # history; a fresh value more than 1+tol times the best fails
    for metric, cell in sorted(fresh.items()):
        if not gated_latency(metric):
            continue
        best = best_round = None
        for rname, flat in history:
            prev = flat.get(metric)
            if prev is None:
                continue
            if best is None or prev["value"] < best:
                best, best_round = prev["value"], rname
        if best is None or best <= 0:
            continue
        tol = LATENCY_TOLERANCE[metric]
        ratio = cell["value"] / best
        if ratio > 1.0 + tol:
            findings.append({
                "metric": metric,
                "value": cell["value"],
                "unit": cell["unit"],
                "best": best,
                "best_round": best_round,
                "ratio": round(ratio, 4),
                "tolerance": tol,
                "direction": "above",
            })
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--row", default=None,
                    help="fresh bench row JSON (bare row or driver "
                         "{'parsed': ...} wrapper); default: the newest "
                         "history round, gated against the earlier ones")
    ap.add_argument("--history", required=True,
                    help="glob of history round files")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="allowed fraction below the trailing best "
                         f"(default {DEFAULT_TOLERANCE})")
    args = ap.parse_args(argv)

    history = load_history(glob.glob(args.history))
    if args.row is not None:
        try:
            fresh = _load_round(args.row)
        except (OSError, ValueError) as e:
            print(f"bench_regress: cannot read --row {args.row}: {e}",
                  file=sys.stderr)
            return 2
        if not fresh:
            print(f"bench_regress: --row {args.row} has no parseable "
                  f"bench row", file=sys.stderr)
            return 2
        fresh_name = os.path.basename(args.row)
    else:
        if len(history) < 2:
            print("bench_regress: need >= 2 history rounds (or --row) "
                  "to gate anything", file=sys.stderr)
            return 2
        fresh_name, fresh = history[-1]
        history = history[:-1]
    if not history:
        print("bench_regress: no history rounds to compare against",
              file=sys.stderr)
        return 2

    findings = check(fresh, history, tolerance=args.tolerance)
    verdict = {
        "row": fresh_name,
        "rounds": [name for name, _ in history],
        "gated_metrics": sorted(m for m, c in fresh.items()
                                if gated(c.get("unit", ""))
                                or gated_latency(m)),
        "regressions": findings,
        "ok": not findings,
    }
    print(json.dumps(verdict, indent=1, sort_keys=True))
    if findings:
        for f in findings:
            if f.get("missing"):
                print(f"REGRESSION {f['metric']}: MISSING from the "
                      f"fresh row (was {f['best']:.1f} {f['unit']} in "
                      f"{f['best_round']}) — did the family's bench "
                      f"subprocess crash?", file=sys.stderr)
            elif f.get("direction") == "above":
                print(f"REGRESSION {f['metric']}: {f['value']:.1f} "
                      f"{f['unit']} is {f['ratio']:.1%} of the "
                      f"trailing best (lowest) {f['best']:.1f} "
                      f"({f['best_round']}; tolerance "
                      f"+{f['tolerance']:.0%})", file=sys.stderr)
            else:
                print(f"REGRESSION {f['metric']}: {f['value']:.1f} "
                      f"{f['unit']} is {f['ratio']:.1%} of the "
                      f"trailing best {f['best']:.1f} "
                      f"({f['best_round']}; tolerance "
                      f"{f['tolerance']:.0%})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
