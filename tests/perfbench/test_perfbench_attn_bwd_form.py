"""``lower.split_bwd_attn_calls.train``: the BHTD attention backward
calls that lowered as the pair ``attn.bhtd.bwd_dq`` +
``attn.bhtd.bwd_dkv`` and not as the one call ``attn.bhtd.bwd``, from
the ``form`` label of the program's ``pt_attention_dispatch_total``
(ops/attention_ops.py; ``flash_attention.bhtd_bwd_form``'s answer). The
decoder cells report it in a traced run. On the CPU a cell's
attention is the dense composition and no row carries the label (None:
the line leaves the metric out); through the kernels' interpreter, at
the families' tiny sizes, a configuration with grouped key/value heads
keeps one head a step and reads 0 as on the chip, one whose heads share
a step (a tile of a short sequence) counts its calls."""

import json

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from paddle_tpu.parallel import flash_attention as fa
from perf import harness
from perf.kinds import train

METRIC = "lower.split_bwd_attn_calls.train"
CELLS = tiny.cells_named(tiny.BENCH, METRIC)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def read(run):
    return harness.reader_for(METRIC).read(run)


def bwd_rows():
    rows = monitor.snapshot().get("pt_attention_dispatch_total", {})
    return [r for r in rows.get("values", [])
            if r["value"] and r["labels"]["pass"] == "bwd"]


def test_the_metric_lists_the_decoder_cells_and_moves_the_step():
    entry = next(m for m in tiny.BENCH["per_layer"] if m["name"] == METRIC)
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Program lowering"
    assert entry["unit"] == "count" and entry["better"] == "lower"
    # the cells whose attention is the BHTD kernels' are among those with
    # a decoder block
    blocks = tiny.cells_named(tiny.BENCH, "step.block_share.train")
    assert CELLS and set(CELLS) <= set(blocks)
    assert set(CELLS) <= set(tiny.cells_named(tiny.BENCH,
                                              "train_tokens_per_s"))


def traced_tiny_run(cell_name, monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    monitor.reset()
    cell = tiny.train_cell(cell_name)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    return line


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_traced_tiny_run_reads_the_form_label(cell_name, monkeypatch,
                                                tmp_path, capsys):
    """Through the interpreter the cell's attention takes the BHTD
    kernels and every backward row says which form it is; the metric is
    the count of the rows that are not fused."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    line = traced_tiny_run(cell_name, monkeypatch, tmp_path)
    rows = bwd_rows()
    assert rows and all(r["labels"]["family"] == "bhtd" for r in rows)
    assert {r["labels"]["form"] for r in rows} <= {"fused", "split"}
    split = sum(int(r["value"]) for r in rows
                if r["labels"]["form"] == "split")
    assert line["metrics"][METRIC]["value"] == split
    grouped = " kv" in rows[0]["labels"]["shape"]
    # grouped key/value heads: one head a step, the fused call, 0 as on
    # the chip; else the tiny tile batches its heads and every call is
    # the pair
    assert (split == 0) == grouped, rows


def test_a_cell_whose_attention_is_dense_leaves_the_metric_out(
        monkeypatch, tmp_path, capsys):
    """The CPU without the interpreter: the dense composition, no row
    with the label, nothing reported and nothing raised."""
    line = traced_tiny_run(CELLS[0], monkeypatch, tmp_path)
    rows = bwd_rows()
    assert rows and all(r["labels"]["family"] == "dense"
                        and "form" not in r["labels"] for r in rows)
    assert METRIC not in line["metrics"]


def test_the_reader_counts_split_rows_and_reports_nothing_without_the_label(
        monkeypatch):
    """A tree before the label, or a program without a BHTD backward
    call: None and no exception. A fused call does not count."""
    from paddle_tpu.core import interp
    from paddle_tpu.ops import attention_ops

    monkeypatch.setattr(fa, "_INTERPRET", True)     # the kernels take calls
    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(run) is None
    dims = (1, 16384, 16384, 28, 128, 4, 128)
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    try:
        # rows as the parent wrote them: no label, not counted
        attention_ops._note_dispatch("bhtd", "fwd", dims)
        attention_ops._note_dispatch("bhtd", "bwd", dims)
        attention_ops._note_dispatch("bthd_small", "bwd",
                                     (64, 256, 256, 8, 64))
        assert read(run) is None
        for form in ("fused", "fused", "split"):
            attention_ops._note_dispatch("bhtd", "bwd", dims, form=form)
        attention_ops._note_dispatch("bhtd", "bwd", dims, window=4096,
                                     form="split")
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
    assert read(run) == 2
    shape = "b1 tq16384 tk16384 h28 kv4 dh128"
    assert attention_ops.dispatch_counts() == {
        f"bhtd fwd {shape}": 1, f"bhtd bwd {shape}": 4,
        f"bhtd bwd {shape} w4096": 1,
        "bthd_small bwd b64 tq256 tk256 h8 dh64": 1}
    assert attention_ops.dispatch_counts(tiles=True, forms=True) == {
        f"bhtd fwd {shape} [hb1 bq512 bk512]": 1,
        f"bhtd bwd {shape} [hb1 bq512 bk512]": 1,
        f"bhtd bwd {shape} [hb1 bq512 bk512] form=fused": 2,
        f"bhtd bwd {shape} [hb1 bq512 bk512] form=split": 1,
        f"bhtd bwd {shape} w4096 [hb1 bq512 bk512] form=split": 1,
        "bthd_small bwd b64 tq256 tk256 h8 dh64": 1}
    monitor.reset()
    assert read(run) is None


def test_the_grad_op_labels_its_row_with_the_kernel_layers_answer(
        monkeypatch):
    """The sdpa grad op asks ``bhtd_bwd_form`` for the call it hands the
    kernels: one head a step is fused, heads that share a step the
    pair, a call with attention dropout the pair."""
    import jax.numpy as jnp

    from paddle_tpu.core import interp
    from paddle_tpu.ops import attention_ops

    monkeypatch.setattr(fa, "_INTERPRET", True)
    monitor.reset()
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)
    try:
        for h, hk in ((4, 2), (2, 2)):
            q = jnp.zeros((1, h, 256, 16), jnp.float32)
            k = jnp.zeros((1, hk, 256, 16), jnp.float32)
            ins = {"Q": [q], "K": [k], "V": [k]}
            out = attention_ops._sdpa(ins, {"causal": True})
            attention_ops._sdpa_grad(
                {**ins, "Out": out["Out"], "Lse": out["Lse"],
                 "GRAD::Out": [q]}, {"causal": True})
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
    forms = {r["labels"]["shape"]: r["labels"]["form"] for r in bwd_rows()}
    assert forms == {"b1 tq256 tk256 h4 kv2 dh16": "fused",
                     "b1 tq256 tk256 h2 dh16": "split"}
    monitor.reset()
