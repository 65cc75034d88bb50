"""``lower.filled_moe_buffers.train``: the whole-buffer zero fills a held
expert layer still lowers, from the program's
``pt_moe_buffer_fills_total`` (ops/moe_ops.py: a row-major pass whose
first carry is zeros and not memory nothing filled). The cells whose
expert layers hold a share of their experts report it in a traced run:
0 on the chip, where every such pass starts from
``grouped_matmul.unfilled``; at their families' tiny sizes on the CPU
here every carry is zeros, as it was, and the count says so. None on a
tree without the counter."""

import json

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from paddle_tpu.core import interp
from paddle_tpu.ops import moe_ops
from perf import harness
from perf.kinds import train

METRIC = "lower.filled_moe_buffers.train"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def read(run):
    return harness.reader_for(METRIC).read(run)


def test_the_metric_lists_the_cells_with_held_experts_and_moves_the_step():
    assert tiny.listed_as(METRIC, "count", "lower", "program_counter",
                          "Program lowering", "sdar-train-s4096",
                          "qwen3next-train-s8192")
    whole = tiny.entry(tiny.BENCH, "lower.whole_buffer_moe_calls.train")
    assert tiny.cells_named(tiny.BENCH, METRIC) == whole["workloads"]


@pytest.mark.parametrize("cell_name", [
    "qwen3next-train-s8192", "nemotron3nano-train-s4096",
    "sdar-train-s4096"])
def test_a_traced_tiny_run_of_a_held_cell_reports_its_fills(cell_name,
                                                            monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monitor.reset()
    cell = tiny.train_cell(cell_name)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    fills = moe_ops.buffer_fill_counts()
    # no kernel runs here: every carry of every held layer is zeros
    assert line["metrics"][METRIC]["value"] == sum(fills.values()) > 0
    assert {name.split()[1] for name in fills} >= {"Xs", "h", "GRAD::Ys"}
    monitor.reset()


def test_the_reader_says_nothing_without_the_counter_and_counts_with_it(
        monkeypatch):
    """A tree before the counter (the parent's), or a program without a
    held expert layer: None and no exception. A held layer whose passes
    are windowed and fill nothing: 0. Every fill lowered: one."""
    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(run) is None
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    attrs = {"num_experts": 16, "held_count": 4}
    try:
        # a layer that holds every expert walks its buffer whole
        moe_ops._note_passes("moe_experts", 64, None, "swiglu")
        assert read(run) is None
        moe_ops._note_passes("moe_experts", 64, 16, "swiglu")
        assert read(run) == 0
        snapshot = monitor.snapshot
        monkeypatch.setattr(monitor, "snapshot", lambda: {
            k: v for k, v in snapshot().items()
            if k != "pt_moe_buffer_fills_total"})
        assert read(run) is None            # the parent's tree
        monkeypatch.undo()
        for buffer in ("Xs", "h", "h"):
            moe_ops._carry(attrs, "moe_experts", 16, 64, "float32", buffer,
                           8)
        assert read(run) == 3
        assert moe_ops.buffer_fill_counts() == {
            "moe_experts Xs 64x8": 1, "moe_experts h 64x8": 2}
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
