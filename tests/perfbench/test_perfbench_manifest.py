"""BENCHMARK.json against the files it names and the contract's rules a
reader can check without a chip. The rules that need no file on disk
run twice: on the file as it is, and on an in-memory copy with a later
PR's entries APPENDED (perfbench_tiny.appended: a configuration, a
one-chip cell, three per-layer metrics), so that a check which only
holds for today's lists fails here and not in that PR's review. And the
directory's own sources are searched for what made appending impossible
from PR 38 to PR 54: a test that reads one of the four lists by a
position, or holds a list's length to a number."""

import ast
import glob
import json
import os
import re

import pytest

from perf import harness

import perfbench_tiny as tiny

BENCH = tiny.BENCH
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
both = pytest.mark.parametrize("bench", tiny.BENCHES.values(),
                               ids=tiny.BENCHES.keys())


def exists(*parts):
    return os.path.exists(os.path.join(harness.ROOT, *parts))


def test_the_rehearsal_appends_and_edits_no_entry_that_is_there():
    more = tiny.BENCHES["appended"]
    for group, added in (("configs", 1), ("workloads", 1),
                         ("end_to_end", 0), ("per_layer", 3)):
        old, new = BENCH[group], more[group]
        assert len(new) == len(old) + added
        for a, b in zip(old, new):   # the old entries, in their places
            assert {k: v for k, v in b.items() if k != "workloads"} \
                == {k: v for k, v in a.items() if k != "workloads"}
            assert b.get("workloads", [])[:len(a.get("workloads", []))] \
                == a.get("workloads", [])
    cell = more["workloads"][-1]["name"]
    assert cell in tiny.cells_named(more, "train_tokens_per_s")
    assert cell in tiny.cells_named(more, "attn.time_share.train")
    assert cell not in tiny.cells_named(more, "mesh.collective_share")
    for metric in tiny.REHEARSED_LISTS:     # at the END of a shorter list
        assert tiny.cells_named(more, metric) \
            == tiny.cells_named(BENCH, metric) + [cell]


# --- no test pins a list --------------------------------------------------

LISTS = ("per_layer", "workloads", "configs", "end_to_end")
LOOKUPS = ("cells_named", "cells_of")    # perfbench_tiny's, lists too


def _is_list(node):
    """``x["per_layer"]`` (or another of the four), or a call of
    perfbench_tiny's that returns such a list's cells."""
    if isinstance(node, ast.Subscript):
        key = node.slice
        return isinstance(key, ast.Constant) and key.value in LISTS
    if isinstance(node, ast.Call):
        f = node.func
        return getattr(f, "attr", getattr(f, "id", None)) in LOOKUPS
    return False


def pins(source):
    """[(line, what)] of the places a test file reads one of
    BENCHMARK.json's lists by a position or holds its length to a
    number. A name bound to such a list (``CELLS = cells_named(...)``,
    ``held = {... for c in BENCH["workloads"] ...}``) counts as the
    list. What does NOT count: a lookup by name (``next(m for m in
    ... if m["name"] == X)``, an unpacking of a list filtered by name),
    the contract's own limits (``1 <= len(...) <= 24``), a length held
    to another length, and the FIRST cell of a metric's list through a
    bound name (``CELLS[0]``: a list only grows at its end)."""
    tree = ast.parse(source)
    bound = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                _is_list(n) for n in ast.walk(node.value)):
            bound |= {t.id for t in node.targets if isinstance(t, ast.Name)}

    def is_list(node):
        return _is_list(node) or (isinstance(node, ast.Name)
                                  and node.id in bound)

    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript):
            base, at = node.value, node.slice
            number = isinstance(at, ast.UnaryOp) or (
                isinstance(at, ast.Constant) and isinstance(at.value, int))
            first = number and getattr(at, "value", None) == 0
            if isinstance(base, ast.Subscript) and _is_list(base):
                # the list itself: no entry is reached by a place
                pinned = number or isinstance(at, ast.Slice)
            else:   # a metric's cells, or a name bound to a list
                pinned = is_list(base) and number and not first
            if pinned:
                found.append((node.lineno, "a list read by a position"))
        if isinstance(node, ast.Compare) and any(
                isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops):
            sides = [node.left] + node.comparators
            lens = [s for s in sides if isinstance(s, ast.Call)
                    and getattr(s.func, "id", None) == "len"
                    and any(is_list(n) for n in ast.walk(s.args[0]))]
            if lens and any(isinstance(s, ast.Constant)
                            and isinstance(s.value, int) for s in sides):
                found.append((node.lineno, "a list's length held to a "
                                           "number"))
        if isinstance(node, ast.Assign) and _is_list(node.value) and any(
                isinstance(t, (ast.Tuple, ast.List)) for t in node.targets):
            found.append((node.lineno, "a list unpacked into a fixed "
                                       "number of cells"))
    return found


def test_no_test_reads_a_list_by_position_or_holds_its_length():
    """The rule of perf/README.md ("Adding things"), held on the
    sources: an entry is found by its name. The one exception is the
    rehearsal's own look at what ``perfbench_tiny.appended`` has just
    appended, ``more["workloads"][-1]`` in this file: it reads the
    copy, not BENCHMARK.json."""
    here = os.path.dirname(os.path.abspath(__file__))
    found = {}
    for path in sorted(glob.glob(os.path.join(here, "*.py"))):
        with open(path) as f:
            source = f.read()
        lines = source.splitlines()
        hits = [(n, what, lines[n - 1].strip()) for n, what in pins(source)
                if 'more["workloads"][-1]' not in lines[n - 1]]
        if hits:
            found[os.path.basename(path)] = hits
    assert not found, found


# (spelt with other quotes and names than the lines they stand for, so
# that a grep of this directory for a pin finds a pin and not this list)
@pytest.mark.parametrize("put_back", [
    "entry = tiny.BENCH['per_layer'][-1]",
    "cells = tiny.cells_named(tiny.BENCH, M)\nassert len(cells) == 4",
    "some = {c['name'] for c in tiny.BENCH['workloads']}\n"
    "assert set(e['workloads']) == some and len(some) == 2",
    "(one_cell,) ="
    " tiny.cells_named(tiny.BENCH, METRIC)",
    "assert entry == tiny.BENCH['workloads'][-1]",
    "assert tiny.BENCH['configs'][-1]['name'] == CONFIG",
    "assert e['workloads'][:6] == [w for w in B['workloads'][:6]]",
    "assert len(tiny.BENCH['per_layer']) == 44"])
def test_the_guard_sees_a_pin_put_back(put_back):
    """The pins PR 54 lifted (PERF.md section 6), each alone."""
    assert pins(put_back), put_back


@pytest.mark.parametrize("fine", [
    'entry = next(m for m in B["per_layer"] if m["name"] == METRIC)',
    '(entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]',
    'assert 1 <= len(bench["configs"]) <= 24',
    'old, new = B[g], more[g]\nassert len(new) == len(old) + added',
    'CELLS = tiny.cells_named(tiny.BENCH, M)\nrun(CELLS[0])',
    'names = [m["name"] for m in B["per_layer"]]\n'
    'assert names[:len(OLDER)] == OLDER'])
def test_the_guard_lets_a_lookup_by_name_be(fine):
    assert not pins(fine), fine


@both
def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench, indent=1)) < 64 * 1024
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


@both
def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= set(cells), m
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    # one layer, one spelling, letter for letter
    assert all(len(v) == 1 for v in layers.values()), layers
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


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


@pytest.mark.parametrize("bench,cell", [
    (b, w["name"]) for b in tiny.BENCHES.values() for w in b["workloads"]],
    ids=[f"{k}-{w['name']}" for k, b in tiny.BENCHES.items()
         for w in b["workloads"]])
def test_every_cell_reports_what_its_metrics_move(bench, cell):
    e2e = {m["name"] for m in harness.cell_metrics(bench, cell,
                                                   "end_to_end")}
    layer = harness.cell_metrics(bench, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e, (cell, m["name"], m["moves"])


@both
def test_every_config_is_used_and_lies_under_paths(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith(tuple(p + "/" for p in bench["paths"]))
    assert bench["command"] == ["python3", "perf/run.py"]


def test_the_file_is_small_and_paths_hold_the_files():
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    assert all(os.path.isdir(os.path.join(harness.ROOT, p))
               for p in BENCH["paths"])
