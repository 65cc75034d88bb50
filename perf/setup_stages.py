"""Set-up as the program times it from inside (PR 36): what the
readers of the set-up metrics share. With telemetry on (traced runs:
``kinds/train.py`` turns it on before the Program is built) the
program's registry holds, at the end of a run,

- ``pt_span_seconds{span}`` for ``executor.first_call`` (the first call
  of every executable the executor built: trace, lowering, XLA or the
  read from jax's cache, the first execution's dispatch) and the three
  Program-building calls ``BUILD_SPANS``;
- ``pt_compile_stage_seconds{program, stage}``: jax's own duration
  events (``trace``, ``lower``, ``backend``), outermost only, by the
  program whose first call they ran in, ``(outside)`` for the rest
  (the float32 reference under ``jax.jit``, ``device_put`` helpers);
- ``pt_compile_cache_total{program, outcome}``: ``hit`` or ``written``;
- ``pt_jax_traces_total{fun_name}`` and ``pt_op_trace_seconds{op}``.

A run whose window holds a compile is incorrect, so what they hold is
set-up's. A reader returns None in an untraced run and against a
program that has no such instrument (a checkout from before PR 36).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from perf.harness import say

OUTSIDE = "(outside)"
BUILD_SPANS = ("backward.append_backward", "optimizer.apply_gradients",
               "program.clone")


def rows(run, name: str) -> Optional[List[Dict]]:
    """The cells of instrument ``name`` ([{"labels", "value" | "count",
    "sum", ...}]) as the registry stands; None where the run was not
    traced or the program registers no such instrument."""
    if not run.traced:
        return None
    if getattr(run, "_setup_snapshot", None) is None:
        from paddle_tpu import monitor

        run._setup_snapshot = monitor.snapshot()
    metric = run._setup_snapshot.get(name)
    return None if metric is None else metric["values"]


def total(run, name: str, field: str,
          keep: Callable[[Dict], bool] = lambda labels: True
          ) -> Optional[float]:
    """Sum of ``field`` over the cells of ``name`` whose labels ``keep``
    takes; None where ``rows`` is."""
    cells = rows(run, name)
    if cells is None:
        return None
    return float(sum(c[field] for c in cells if keep(c["labels"])))


def span_seconds(run, span: str) -> Optional[float]:
    """Seconds under ``span`` so far, None where no such span was ever
    opened (a program from before PR 36 opens none of this PR's)."""
    cells = rows(run, "pt_span_seconds")
    mine = [c for c in cells or () if c["labels"].get("span") == span]
    return float(sum(c["sum"] for c in mine)) if mine else None


def stage_seconds(run, stage: str) -> Optional[float]:
    """Seconds of one of jax's compile stages inside the first calls of
    the program's own executables."""
    return total(run, "pt_compile_stage_seconds", "sum",
                 lambda lb: lb.get("stage") == stage
                 and lb.get("program") != OUTSIDE)


def say_top(run, name: str, label: str, what: str, n: int = 10):
    """The ``n`` largest rows of ``name`` by ``label`` to the run's log:
    a counter's by value, a histogram's by seconds with its count."""
    cells = [c for c in rows(run, name) or ()
             if c["labels"].get(label) != OUTSIDE]
    if not cells:
        return
    if "value" in cells[0]:
        top = sorted(cells, key=lambda c: -c["value"])[:n]
        table = [[c["labels"].get(label, "(other)"), int(c["value"])]
                 for c in top]
    else:
        top = sorted(cells, key=lambda c: -c["sum"])[:n]
        table = [[c["labels"].get(label, "(other)"), round(c["sum"], 4),
                  c["count"]] for c in top]
    say(f"perf: set-up: {what}: {table}")
