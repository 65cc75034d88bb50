"""Tiny configurations and cells for the benchmark's CPU tests: the
same files the chip runs, cut to sizes a CPU compiles in seconds."""

import copy
import time

import jax

from perf import harness, models

BENCH = harness.load_json("BENCHMARK.json")
# what the tests that run every cell and configuration are parametrised
# over: a cell or configuration a later PR appends is picked up here
CONFIGS = [c["name"] for c in BENCH["configs"]]


def cells_of(kind):
    """[(cell, chips)] of BENCHMARK.json's cells of one kind."""
    return [(w["name"], w["chips"]) for w in BENCH["workloads"]
            if harness.load_json("perf", "workloads",
                                 f"{w['name']}.json")["kind"] == kind]


def train_cell_of(config_name):
    """The tiny form of the first train cell of a configuration."""
    return next(cell for cell in (train_cell(c) for c, _ in cells_of("train"))
                if cell["config"] == config_name)


def config(name):
    """A configuration file at its family's tiny sizes (``TINY`` in
    perf/families/<family>.py)."""
    cfg = harness.load_json("perf", "configs", f"{name}.json")
    cfg.update(models.family(cfg).TINY)
    return cfg


def train_cell(name, chips=None):
    """A train cell's file cut to 8 x 16 positions: its other traffic
    keys (what a family's generator reads besides) stay."""
    cell = harness.load_json("perf", "workloads", f"{name}.json")
    cell["chips"] = chips or cell["chips"]
    cell["trace_seconds"] = 0.3
    full = cell["traffic"]["real_len"][0] == cell["traffic"]["real_len"][1]
    cell["traffic"] = dict(cell["traffic"], batch=8, seq_len=16, feeds=4,
                           real_len=[16, 16] if full else [8, 16])
    return cell


def serve_cell(rate=30.0):
    return {
        "name": "tiny-serve", "config": "transformer-base",
        "kind": "serve", "chips": 1, "trace_seconds": 0.3,
        "engine": {"slots": 4, "src_len": 16, "max_len": 24,
                   "queue_depth": 1000},
        "traffic": {
            "name": "steady", "rate_per_s": rate, "drain_seconds": 5.0,
            "src_len": {"median": 6, "sigma": 0.6, "min": 2, "max": 16},
            "max_new": {"ratio": 1.1, "min": 2, "max": 23}}}


# lists of some of the train cells that a new decoder cell joins: until
# PR 54 tests held both to four cells and to each other's equality
REHEARSED_LISTS = ("step.block_share.train",
                   "lower.split_bwd_attn_calls.train")


def appended():
    """An in-memory copy of BENCHMARK.json as a ``model_config`` PR
    leaves it: one more configuration, one more one-chip train cell and
    three more per-layer metrics, each at the END of its list, the cell
    also listed under ``train_tokens_per_s`` and the ``.train`` metrics
    every train cell reports, and at the end of two lists of SOME cells
    (``REHEARSED_LISTS``: a decoder with BHTD attention belongs on
    both). No file of theirs exists on disk."""
    b = copy.deepcopy(BENCH)
    b["configs"].append({
        "name": "rehearsal-lm", "source": "https://example.org/rehearsal-lm",
        "file": f"{b['paths'][0]}/configs/rehearsal-lm.json",
        "reduced": ["num_hidden_layers"],
        "why": "a decoder-only family with experts, as a later PR adds"})
    b["workloads"].append({
        "name": "rehearsal-train-s4096", "config": "rehearsal-lm",
        "traffic": "b2-s4096", "chips": 1,
        "why": "batch 2 x 4096 packed positions; BHTD attention and the "
               "experts' grouped matmuls do the work"})
    train = set(cells_named(BENCH, "train_tokens_per_s"))
    for m in b["end_to_end"] + b["per_layer"]:
        if train <= set(m.get("workloads", ())) \
                or m["name"] in REHEARSED_LISTS:
            m["workloads"].append("rehearsal-train-s4096")
    b["per_layer"] += [
        {"name": n, "unit": "%", "better": better, "source": source,
         "layer": layer, "moves": "train_tokens_per_s",
         "workloads": ["rehearsal-train-s4096"]}
        for n, better, source, layer in (
            ("moe.time_share.train", "lower", "program_span",
             "Program lowering"),
            ("moe.dispatch_share.train", "lower", "program_span",
             "Program lowering"),
            ("train_moe_roofline", "higher", "device_trace", "Kernels"))]
    return b


def entry(bench, metric):
    """A metric's entry in BENCHMARK.json, found by its name: the one
    way a test reaches an entry (never by its place in a list)."""
    return next(m for m in bench["end_to_end"] + bench["per_layer"]
                if m["name"] == metric)


def cells_named(bench, metric):
    """The cells a metric of BENCHMARK.json lists."""
    return entry(bench, metric).get("workloads", [])


def listed_as(metric, unit, better, source, layer, *cells,
              moves="train_tokens_per_s"):
    """BENCHMARK.json holds ``metric`` with these keys and lists
    ``cells`` (other cells may be listed too: a list grows); says which
    of the two does not hold."""
    m = entry(BENCH, metric)
    keys = {k: v for k, v in m.items() if k not in ("name", "workloads")}
    assert keys == {"unit": unit, "better": better, "source": source,
                    "layer": layer, "moves": moves}, (metric, keys)
    assert set(cells) <= set(m["workloads"]), (metric, m["workloads"])
    return True


BENCHES = {"as-it-is": BENCH, "appended": appended()}


def bench_with(cell_name, end_to_end=(), per_layer=()):
    """BENCHMARK.json as a later PR would extend it: the cell named in
    further metrics, which their readers (perf/metrics/) then report."""
    bench = harness.load_json("BENCHMARK.json")
    bench["end_to_end"] = bench["end_to_end"] + [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": [cell_name]}
        for n in end_to_end]
    bench["per_layer"] = bench["per_layer"] + [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": "Serving engine", "moves": end_to_end[0],
         "workloads": [cell_name]} for n, u in per_layer]
    return bench


def make_run(cell, cfg, seconds=0.5, traced=False, seed=2 ** 31 + 7,
             bench=None):
    run = harness.Run(bench or BENCH, cell, cfg, seed, seconds, traced,
                      time.perf_counter())
    run.devices = jax.devices()
    return run
