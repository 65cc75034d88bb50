"""The train step's memory ledger (PR 69): what the readers of the
``mem.*`` metrics share. With telemetry on (traced runs) every lowering
of a program leaves a record in ``monitor.memory_ledgers()``: the bytes
of the state arrays (parameters / optimizer), of one step's feeds, of
every value the forward pass keeps for the backward pass (by name
scope, op and slot, at the shape and dtype the lowering gave it, plain
and padded to the chip's tiles) and the peak of a liveness walk over
the program's ops. A run lowers its eval clone beside its train step:
the step's ledger is the one WITH a backward pass, and of several the
one that keeps the most.

The ledger counts the Program's variables. What an op's compute makes
inside itself (AMP's bf16 casts of the weights) and whatever XLA
decides afterwards (fusion, rematerialisation, its temporaries) it
cannot see: ``device.peak_hbm_gb.train`` stays the chip's reading.

A reader returns None in an untraced run, against a program that keeps
no ledger (a checkout from before PR 69) and where no program with a
backward pass was lowered.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional

if __name__ == "__main__":   # (as perf/run.py: perf/trace.py off the path)
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from perf.harness import say  # noqa: E402

GB = 1e9
# the saved rows a run's log shows (the record keeps 32)
TOP_ROWS = 10


def train_ledger(run) -> Optional[Dict]:
    """The ledger of the run's train step, its table said to the log
    the first time it is asked for."""
    if not run.traced:
        return None
    if not hasattr(run, "_mem_ledger"):
        from paddle_tpu import monitor

        steps = [led for led in getattr(
            monitor, "memory_ledgers", dict)().values()
            if led["has_backward"]]
        run._mem_ledger = max(
            steps, key=lambda led: led["saved"]["padded_bytes"],
            default=None)
        if run._mem_ledger is not None:
            say_table(run._mem_ledger,
                      run.cell.get("traffic", {}).get("feeds"))
    return run._mem_ledger


def gb(run, of) -> Optional[float]:
    """``of(ledger)`` bytes of the train step's ledger in GB."""
    led = train_ledger(run)
    return None if led is None else of(led) / GB


def _row(r):
    """[scope, op, slot, dtype, shape, count, GB padded, padded/plain]"""
    return [r["scope"], r["op"], r["slot"], r["dtype"], r["shape"],
            r["count"], round(r["padded_bytes"] / GB, 4),
            round(r["padded_bytes"] / max(r["bytes"], 1), 2)]


def say_table(led, feeds_held=None):
    """The ledger to the run's log, the way setup_stages.say_top says a
    table: its totals (``feeds_held``: the feeds the cell's loop keeps
    resident), the ``TOP_ROWS`` largest rows of what the forward pass keeps,
    every kept row that is tile padding by half or more, the walk's peak
    and where it falls."""
    state, saved, walk = led["state"], led["saved"], led["walk_peak"]
    say(f"perf: memory ledger of {led['program']} ({led['n_ops']} ops): "
        f"state {(state['param'] + state['optimizer']) / GB:.3f} GB "
        f"(parameters {state['param'] / GB:.3f}, optimizer "
        f"{state['optimizer'] / GB:.3f}, {state['arrays']} arrays), one "
        f"step's feeds {led['feed']['bytes'] / GB:.4f} GB (the loop "
        f"holds {feeds_held}), saved for "
        f"the backward pass {saved['padded_bytes'] / GB:.3f} GB in "
        f"{saved['values']} values ({saved['bytes'] / GB:.3f} before "
        f"tile padding)")
    say(f"perf: memory ledger: largest saved rows [scope, op, slot, "
        f"dtype, shape, count, GB padded, padded/plain]: "
        f"{[_row(r) for r in saved['rows'][:TOP_ROWS]]}")
    padded = [_row(r) for r in saved["rows"]
              if r["padded_bytes"] >= 2 * r["bytes"]]
    if padded:
        say(f"perf: memory ledger: saved rows padded twofold or more: "
            f"{padded}")
    alive = [[r["name"], *_row(r)[:5], round(r["padded_bytes"] / GB, 4)]
             for r in walk["alive"]]
    say(f"perf: memory ledger: walk peak {walk['bytes'] / GB:.3f} GB at "
        f"op {walk['index']} ({walk['role']}/{walk['scope']}/"
        f"{walk['op']}); largest alive there [name, scope, op, slot, "
        f"dtype, shape, GB padded]: {alive}")


def main():
    """python perf/mem_ledger.py <train cell> [lower_cell.py's options]

    The ledger of a cell's train step with no chip: lower_cell.py lowers
    the step for a described v5e with telemetry on (``--no-compile``
    where only the ledger is wanted), and its table follows."""
    import runpy

    from paddle_tpu import monitor

    runpy.run_path(os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "tools", "lower_cell.py"), run_name="__main__")
    for led in monitor.memory_ledgers().values():
        say_table(led)


if __name__ == "__main__":
    main()
