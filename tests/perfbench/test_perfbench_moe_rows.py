"""``lower.whole_buffer_moe_calls.train``: the passes of an expert
layer over its row buffer that walk it whole, from the program's
``pt_moe_rows_dispatch_total`` (ops/moe_ops.py). The cells whose
expert layers hold a share of their experts (their configuration file
says which with ``held_first``) report it in a traced run,
at their families' tiny sizes here, and read 0: every pass of a held
layer is a loop over the windows of live rows."""

import json

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from perf import harness
from perf.kinds import train

METRIC = "lower.whole_buffer_moe_calls.train"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def read(run):
    return harness.reader_for(METRIC).read(run)


def test_the_metric_lists_the_cells_with_held_experts_and_moves_the_step():
    entry = next(m for m in tiny.BENCH["per_layer"] if m["name"] == METRIC)
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Program lowering"
    # the cells whose configuration holds a share of its experts
    held = {c["name"] for c in tiny.BENCH["workloads"]
            if "held_first" in harness.load_json(
                "perf", "configs", f"{c['config']}.json")}
    assert held and set(entry["workloads"]) == held


@pytest.mark.parametrize("cell_name", tiny.cells_named(tiny.BENCH, METRIC))
def test_a_traced_tiny_run_of_a_held_cell_reads_zero(cell_name, monkeypatch,
                                                      capsys):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monitor.reset()
    cell = tiny.train_cell(cell_name)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert line["metrics"][METRIC]["value"] == 0
    rows = monitor.snapshot()["pt_moe_rows_dispatch_total"]["values"]
    assert rows and all(
        r["labels"]["form"] in ("windowed", "windowed|by_token") and
        int(r["labels"]["window"]) >= 8 for r in rows)
    # the live share of the sample goes into the log beside the load
    assert 0 < run.check["second"]["held_row_share"] < 1


def test_the_reader_counts_whole_passes_and_reports_nothing_without_rows():
    """A tree before the counter, or a program without an expert layer:
    None and no exception. A layer that holds every expert walks its
    buffer whole: each of its passes counts."""
    from paddle_tpu.core import interp
    from paddle_tpu.ops import moe_ops

    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(run) is None
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    try:
        for w in (None, 16):    # a pass that walks 64 rows; by window
            moe_ops._note_passes("moe_combine_grad", 64, w, "d_ys", "d_w")
        moe_ops._note_passes("moe_combine", 64, 16, "sum_pairs",
                             by_token=("sum_pairs",))
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
    assert read(run) == 2
    monitor.reset()
    assert read(run) is None
