"""``mem.state_gb.train``, ``mem.saved_gb.train``,
``mem.saved_pad_share.train`` and ``mem.walk_peak_gb.train`` (PR 69):
the train step's memory ledger (``monitor.memory_ledgers()``, which the
lowering records with telemetry on) read by perf/mem_ledger.py and four
readers, on the thirteen one-chip train cells."""

import json

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from perf import harness, mem_ledger
from perf.kinds import train

METRICS = {"mem.state_gb.train": "GB", "mem.saved_gb.train": "GB",
           "mem.saved_pad_share.train": "%", "mem.walk_peak_gb.train": "GB"}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


@pytest.fixture(autouse=True)
def nothing_counted_is_left_behind():
    yield
    flags.set_flags({"telemetry": False})
    monitor.reset()


def read_all(run):
    return {m: harness.reader_for(m).read(run) for m in METRICS}


def a_run(traced=True, cell="tbase-train"):
    cell = tiny.train_cell(cell)
    return tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                         traced=traced)


def a_ledger(program, has_backward, saved=(1000, 1500), state=(400, 800),
             walk=5000):
    return {"v": 1, "ts": 0.0, "program": program, "program_uid": 1,
            "n_ops": 3, "amp": True, "has_backward": has_backward,
            "state": {"param": state[0], "optimizer": state[1],
                      "padded_bytes": sum(state), "arrays": 2},
            "feed": {"bytes": 64, "padded_bytes": 4096, "arrays": 1},
            "saved": {"bytes": saved[0], "padded_bytes": saved[1],
                      "values": 1, "rows": [
                          {"scope": "blk#/attn", "op": "mul", "slot": "Out",
                           "shape": [2, 3], "dtype": "bfloat16", "count": 1,
                           "bytes": saved[0], "padded_bytes": saved[1]}]},
            "walk_peak": {"bytes": walk, "index": 1, "role": "bwd",
                          "scope": "blk0/attn", "op": "mul_grad",
                          "alive": []}}


def test_the_four_entries_list_the_thirteen_one_chip_train_cells():
    one_chip = [c for c, chips in tiny.cells_of("train") if chips == 1]
    assert len(one_chip) >= 13 and "tbase-train-dp4" not in one_chip
    for metric, unit in METRICS.items():
        assert tiny.listed_as(metric, unit, "lower", "program_counter",
                              "Program lowering", *one_chip[:13])
        # the traced shapes under a mesh are the global batch's: the
        # per-chip split waits for a sharded decoder cell
        assert "tbase-train-dp4" not in tiny.cells_named(tiny.BENCH, metric)


def test_an_untraced_run_reports_none():
    monitor.record_memory_ledger(a_ledger("program7", True))
    assert read_all(a_run(traced=False)) == dict.fromkeys(METRICS)


def test_a_run_that_lowered_no_backward_pass_reports_none(monkeypatch):
    monkeypatch.setattr(monitor, "memory_ledgers", lambda: {
        "program3": a_ledger("program3", False),
        "program4": a_ledger("program4", False)})
    assert read_all(a_run()) == dict.fromkeys(METRICS)
    monkeypatch.setattr(monitor, "memory_ledgers", lambda: {})
    assert read_all(a_run()) == dict.fromkeys(METRICS)


def test_a_program_without_the_instrument_reports_none(monkeypatch):
    # a checkout from before PR 69, traced with this PR's benchmark files
    # laid over it: its monitor has no memory_ledgers at all
    monkeypatch.delattr(monitor, "memory_ledgers")
    assert read_all(a_run()) == dict.fromkeys(METRICS)


def test_the_train_step_is_the_ledger_with_a_backward_pass_that_keeps_most(
        monkeypatch, capsys):
    """A run lowers its eval clone twice beside its step: the clone's
    ledger has no backward pass, however much state it reads."""
    monkeypatch.setattr(monitor, "memory_ledgers", lambda: {
        "program5": a_ledger("program5", False, state=(9000, 0)),
        "program3": a_ledger("program3", True, saved=(10, 20)),
        "program4": a_ledger("program4", True, saved=(3e9, 4e9),
                             state=(1e9, 2e9), walk=8e9)})
    run = a_run()
    assert read_all(run) == {
        "mem.state_gb.train": 3.0, "mem.saved_gb.train": 4.0,
        "mem.saved_pad_share.train": 25.0, "mem.walk_peak_gb.train": 8.0}
    out = capsys.readouterr().out
    # the table is said once, by whichever reader asks first
    assert out.count("perf: memory ledger of program4") == 1
    assert "largest saved rows" in out and "blk#/attn" in out
    assert "walk peak 8.000 GB at op 1 (bwd/blk0/attn/mul_grad)" in out
    # nothing kept: the share has nothing to be a share of
    monkeypatch.setattr(monitor, "memory_ledgers", lambda: {
        "program3": a_ledger("program3", True, saved=(0, 0))})
    got = read_all(a_run())
    assert got["mem.saved_pad_share.train"] is None
    assert got["mem.saved_gb.train"] == 0.0


@pytest.mark.parametrize("cell_name", ["olmoe-train-s4096", "tbase-train"])
def test_a_traced_tiny_train_run_reads_what_the_program_recorded(
        cell_name, monkeypatch, capsys):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    run = a_run(cell=cell_name)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line["problems"]
    ledgers = monitor.memory_ledgers()
    for led in ledgers.values():
        monitor.validate_memory_ledger(led)
    steps = [led for led in ledgers.values() if led["has_backward"]]
    assert len(steps) == 1 and len(ledgers) >= 3   # startup, step, clone
    step = steps[0]
    got = {m: line["metrics"][m] for m in METRICS}
    assert {m: v["unit"] for m, v in got.items()} == METRICS
    saved = step["saved"]
    assert got["mem.state_gb.train"]["value"] == pytest.approx(
        (step["state"]["param"] + step["state"]["optimizer"]) / 1e9)
    assert got["mem.saved_gb.train"]["value"] == pytest.approx(
        saved["padded_bytes"] / 1e9)
    assert got["mem.saved_pad_share.train"]["value"] == pytest.approx(
        100 * (1 - saved["bytes"] / saved["padded_bytes"]))
    assert got["mem.walk_peak_gb.train"]["value"] == pytest.approx(
        step["walk_peak"]["bytes"] / 1e9)
    # the registry says the same, under the step's program
    rows = {c["labels"]["kind"]: c["value"]
            for c in monitor.snapshot()["pt_program_memory_bytes"]["values"]
            if c["labels"]["program"] == step["program"]}
    assert rows["saved"] == saved["padded_bytes"]
    assert rows["param"] + rows["optimizer"] == pytest.approx(
        got["mem.state_gb.train"]["value"] * 1e9)
    # Adam: two moments a trained parameter (a table of sinusoids has
    # none)
    assert step["state"]["optimizer"] > 1.5 * step["state"]["param"] > 0
    assert step["amp"] and 0 < saved["bytes"] < saved["padded_bytes"]
    assert step["walk_peak"]["bytes"] > step["state"]["padded_bytes"]
    assert f"perf: memory ledger of {step['program']}" in \
        capsys.readouterr().out


def test_a_traced_run_under_a_mesh_keeps_a_ledger_and_prints_no_metric(
        monkeypatch, capsys):
    """``tbase-train-dp4`` is on none of the lists: under
    with_data_parallel the traced shapes are the GLOBAL batch's (8
    sequences here, over four devices), so the ledger is kept but says
    nothing of one chip, and the cell reports none of the four."""
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    cell = tiny.train_cell("tbase-train-dp4", 4)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = harness.result_line(run)
    assert line["correct"], line["problems"]
    assert not set(METRICS) & set(line["metrics"])
    assert "perf: memory ledger" not in capsys.readouterr().out
    step, = [led for led in monitor.memory_ledgers().values()
             if led["has_backward"]]
    monitor.validate_memory_ledger(step)
    # src and trg ids and the labels of the whole batch, not a quarter
    assert step["feed"]["bytes"] >= 3 * 8 * 16 * 4


def test_mem_ledgers_table_reads_as_a_log_line(capsys):
    led = a_ledger("program9", True, saved=(1000, 4000))
    mem_ledger.say_table(led, feeds_held=4)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 4 and all(s.startswith("perf: memory ledger")
                                 for s in out)
    assert "the loop holds 4" in out[0]
    assert "padded twofold or more" in out[2] and "4.0]" in out[2]
