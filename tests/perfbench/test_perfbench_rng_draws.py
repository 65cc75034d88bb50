"""mesh.replicated_rng_draws.train: its entry, and its reader against
the program's counter (pt_rng_draw_total) at a tiny size on the CPU."""

import pytest

from perf import harness
from perf.kinds import train

import perfbench_tiny as tiny

NAME = "mesh.replicated_rng_draws.train"
BENCH = harness.load_json("BENCHMARK.json")
V5E = harness.load_json("perf", "peaks.json")["TPU v5 lite"]


@pytest.fixture(autouse=True)
def _trace_into_tmp(monkeypatch, tmp_path):
    """A traced tiny run traces under the test's own directory, not
    into the checkout's one directory a cell, which
    ``harness.DeviceTrace`` empties on entry: two workers of one test
    run would delete each other's trace."""
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))


@pytest.mark.parametrize("bench", tiny.BENCHES.values(),
                         ids=tiny.BENCHES.keys())
def test_the_entry_is_found_by_name_and_lists_the_three_train_cells(bench):
    # wherever later PRs' entries put it: they are appended after it
    (entry,) = [dict(m) for m in bench["per_layer"] if m["name"] == NAME]
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Parallelism",
        "moves": "train_tokens_per_s"}
    assert {"tbase-train", "bert-train", "tbase-train-dp4"} <= set(cells)
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] != NAME}


@pytest.mark.parametrize("rows,value", [
    ({}, None),                                       # nothing lowered
    ({"dropout": 44}, 0),                             # one chip
    ({"dropout sharded_over=data": 44}, 0),           # four, each its rows
    ({"dropout sharded_over=data": 40,
      "dropout replicated_over=data": 4}, 4),
    ({"dropout sharded_over=data replicated_over=model": 3,
      "dropout replicated_over=data,model": 2}, 5),
])
def test_the_reader_sums_the_rows_some_axis_repeats(monkeypatch, rows,
                                                    value):
    from paddle_tpu.ops import nn_ops

    monkeypatch.setattr(nn_ops, "rng_draw_counts", lambda: rows)
    assert harness.reader_for(NAME).read(None) == value


def test_a_program_without_the_counter_reports_nothing(monkeypatch):
    from paddle_tpu.ops import nn_ops

    monkeypatch.delattr(nn_ops, "rng_draw_counts")   # the parent commit
    assert harness.reader_for(NAME).read(None) is None


@pytest.mark.parametrize("cell_name,chips", [("tbase-train", 1),
                                             ("tbase-train-dp4", 4)])
def test_a_traced_train_run_reports_no_repeated_draw(
        monkeypatch, cell_name, chips):
    from paddle_tpu import monitor
    from paddle_tpu.ops import nn_ops

    monitor.reset()  # the counter is the process's: a run is a process
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    cell = tiny.train_cell(cell_name, chips)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = harness.result_line(run)
    assert line["metrics"][NAME] == {"value": 0.0, "unit": "count"}
    # 2 layer pairs: 2 embedding, 10 residual, 4 FFN-inner dropouts
    assert nn_ops.rng_draw_counts() == {
        "dropout sharded_over=data" if chips > 1 else "dropout": 16}
