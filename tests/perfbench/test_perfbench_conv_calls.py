"""``lower.xla_conv_calls.train``: the causal convolutions in front of
the gated delta rule that lowered as float32 XLA ops, from the
program's ``pt_causal_conv_dispatch_total``
(ops/linear_attention_ops.py), and any other causal convolution the
program lowers (a Mamba layer's, a gated short convolution's: the
reader counts every ``impl="xla"`` row of the counter). The cell with
DeltaNet layers reports it in a traced run, and so do the cells of the
other families with such a layer (their own test files run them traced
at tiny sizes); at the family's tiny sizes here (64 channels, on the
CPU) ``conv_tile`` gives no call a tile and the metric counts every
call, on the chip at the cell's sizes it reads 0."""

import json

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from perf import harness
from perf.kinds import train

METRIC = "lower.xla_conv_calls.train"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def read(run):
    return harness.reader_for(METRIC).read(run)


def test_the_metric_lists_the_cell_with_deltanet_layers_and_moves_the_step():
    entry = next(m for m in tiny.BENCH["per_layer"] if m["name"] == METRIC)
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Program lowering"
    assert entry["unit"] == "count" and entry["better"] == "lower"
    # the cells that report the delta rule's own counter's metric, and
    # any other whose program lowers a causal convolution
    gdn = tiny.cells_named(tiny.BENCH, "lower.recurrent_gdn_calls.train")
    assert gdn and set(gdn) <= set(entry["workloads"])


def test_a_traced_tiny_run_counts_the_calls_that_got_no_tile(monkeypatch,
                                                             capsys):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monitor.reset()
    # the list's first cell, the one with DeltaNet layers (a list only
    # grows at its end)
    cell_name = tiny.cells_named(tiny.BENCH, METRIC)[0]
    assert cell_name in tiny.cells_named(
        tiny.BENCH, "lower.recurrent_gdn_calls.train")
    cell = tiny.train_cell(cell_name)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    rows = monitor.snapshot()["pt_causal_conv_dispatch_total"]["values"]
    assert rows and all(r["labels"]["impl"] == "xla" for r in rows)
    assert {r["labels"]["pass"] for r in rows} == {"fwd", "bwd"}
    assert line["metrics"][METRIC]["value"] == sum(
        int(r["value"]) for r in rows) > 0


def test_the_reader_counts_xla_rows_and_reports_nothing_without_rows():
    """A tree before the counter, or a program without the op: None and
    no exception. A call that took the kernel does not count."""
    from paddle_tpu.core import interp
    from paddle_tpu.ops import linear_attention_ops as L
    import jax.numpy as jnp

    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(run) is None
    x = jnp.zeros((1, 8192, 8192), jnp.bfloat16)
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    try:
        for impl in ("kernel", "kernel", "xla"):
            L._note_conv("fwd", x, 4, impl)
        L._note_conv("bwd", x, 4, "xla")
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
    assert read(run) == 2
    assert L.conv_dispatch_counts() == {
        "kernel fwd b1 t8192 c8192 taps4": 2,
        "xla fwd b1 t8192 c8192 taps4": 1,
        "xla bwd b1 t8192 c8192 taps4": 1}
    monitor.reset()
    assert read(run) is None
