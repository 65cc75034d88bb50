"""Tiny configurations and cells for the benchmark's CPU tests: the
same files the chip runs, cut to sizes a CPU compiles in seconds."""

import time

import jax

from perf import harness

TINY_TRANSFORMER = dict(d_model=32, d_inner=64, n_head=4, n_layer=2,
                        src_vocab_size=50, trg_vocab_size=60, max_length=32)
TINY_BERT = dict(d_model=32, d_inner=64, n_head=4, n_layer=2,
                 vocab_size=50, max_position=16)


def config(name):
    cfg = harness.load_json("perf", "configs", f"{name}.json")
    cfg.update(TINY_TRANSFORMER if cfg["family"] == "transformer"
               else TINY_BERT)
    return cfg


def train_cell(name, chips=1):
    cell = harness.load_json("perf", "workloads", f"{name}.json")
    cell["chips"] = chips
    cell["trace_seconds"] = 0.3
    full = cell["traffic"]["real_len"][0] == cell["traffic"]["real_len"][1]
    cell["traffic"] = {"batch": 8, "seq_len": 16, "feeds": 4,
                       "real_len": [16, 16] if full else [8, 16]}
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
    run = harness.Run(bench or harness.load_json("BENCHMARK.json"), cell,
                      cfg, seed, seconds, traced, time.perf_counter())
    run.devices = jax.devices()
    return run
