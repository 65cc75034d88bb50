"""``lower.unfused_expert_adam_calls.train``: the experts' weight-gradient
calls that wrote their gradient to HBM for an update op to read back,
from the program's ``pt_moe_gmm_dispatch_total``
(parallel/grouped_matmul.py): rows of pass ``bwd_dw`` with a tile. The
eight cells with expert layers report it in a traced run, at their
families' tiny sizes here, where no call has a tile (the CPU) and it
reads 0."""

import json

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from paddle_tpu.core import interp
from paddle_tpu.parallel import grouped_matmul as gm
from perf import harness
from perf.kinds import train

METRIC = "lower.unfused_expert_adam_calls.train"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def read(run):
    return harness.reader_for(METRIC).read(run)


def test_the_metric_lists_the_cells_with_experts_and_moves_the_step():
    assert tiny.listed_as(METRIC, "calls", "lower", "program_counter",
                          "Program lowering", "olmoe-train-s4096")
    # the cells whose programs lower a grouped matmul
    ragged = tiny.entry(tiny.BENCH, "lower.ragged_moe_calls.train")
    assert tiny.cells_named(tiny.BENCH, METRIC) == ragged["workloads"]


@pytest.mark.parametrize("cell_name", tiny.cells_named(tiny.BENCH, METRIC))
def test_a_traced_tiny_run_of_an_expert_cell_reports_it(cell_name,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monitor.reset()
    cell = tiny.train_cell(cell_name)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert line["metrics"][METRIC]["value"] == 0
    rows = monitor.snapshot()["pt_moe_gmm_dispatch_total"]["values"]
    assert {r["labels"]["pass"] for r in rows} == {"fwd", "bwd_dx", "bwd_dw"}
    monitor.reset()


def test_the_reader_counts_tiled_bwd_dw_rows_alone():
    """A tree before the counter, or a program without an expert layer:
    None and no exception. A gradient ``ragged_dot`` made (no tile) and
    a call that took the step inside the kernel do not count; a tiled
    ``bwd_dw`` does, once a lowering."""
    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(run) is None
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    try:
        dims = (65536, 2048, 1024, 64)
        gm._note_dispatch("fwd", *dims, (256, 2048, 1024))
        gm._note_dispatch("bwd_dx", *dims, (256, 1024, 2048))
        gm._note_dispatch("bwd_dw", *dims, None)
        gm._note_dispatch("bwd_dw_adam", *dims, (256, 1024, 1024))
        assert read(run) == 0
        for _ in range(3):
            gm._note_dispatch("bwd_dw", *dims, (256, 2048, 1024))
        gm._note_dispatch("bwd_dw", 24576, 2688, 1856, 8, (128, 2688, 640))
        assert read(run) == 4
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
        monitor.reset()
