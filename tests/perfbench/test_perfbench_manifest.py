"""BENCHMARK.json against the files it names and the contract's rules a
reader can check without a chip."""

import os
import re

import pytest

from perf import harness

BENCH = harness.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def exists(*parts):
    return os.path.exists(os.path.join(harness.ROOT, *parts))


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_names_units_and_lines():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names) and len(set(CELLS)) == len(CELLS)
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_agree(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert exists("perf", "workloads", f"{cell}.json")
    spec = harness.load_json("perf", "workloads", f"{cell}.json")
    assert spec["name"] == cell and spec["config"] == entry["config"]
    assert spec["chips"] == entry["chips"]
    assert spec["traffic"]["name"] == entry["traffic"]
    assert exists("perf", "kinds", f"{spec['kind']}.py")
    cfg_entry = next(c for c in BENCH["configs"]
                     if c["name"] == spec["config"])
    assert exists(cfg_entry["file"])
    cfg = harness.load_json(cfg_entry["file"])
    assert cfg["reduced"] == cfg_entry["reduced"]
    assert exists("perf", "families", f"{cfg['family']}.py")
    assert exists("perf", "reference", f"{cfg['family']}.py")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.reader_for(metric).read)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_its_metrics_move(cell):
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                   "end_to_end")}
    layer = harness.cell_metrics(BENCH, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (cell, m["name"], m["moves"])


def test_every_config_is_used_and_paths_hold_the_files():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert BENCH["command"] == ["python3", "perf/run.py"]
    assert all(os.path.isdir(os.path.join(harness.ROOT, p))
               for p in BENCH["paths"])
