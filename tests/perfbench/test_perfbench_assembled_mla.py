"""``lower.assembled_mla_calls.train`` (PR 70): the attention calls that
were GIVEN their queries and keys in two parts (``QPe``, ``KPe``: latent
attention's rotary features and the keys' ONE shared head,
``models/decoder.latent_attention``) and whose parts the sdpa op
assembled itself, from the ``parts`` label of the program's
``pt_attention_dispatch_total`` (ops/attention_ops.py ``_two_parts``;
``flash_attention.bhtd_parts``'s answer). The three latent cells report
it in a traced run: 0 where the fused BHTD kernels read the parts as
operands of their own (on the chip; here through the kernels'
interpreter with one head a step), every call both ways where the
composition runs (this CPU), None where no row carries the label (the
parent's tree, another family's program)."""

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from paddle_tpu.core import interp
from paddle_tpu.ops import attention_ops
from paddle_tpu.parallel import flash_attention as fa
from perf import harness
from test_perfbench_attn_bwd_form import traced_tiny_run

METRIC = "lower.assembled_mla_calls.train"
CELLS = ["joyai-train-s4096", "kimilinear-train-s4096", "xing4-train-s4096"]
# what the latent cells report of their attention blocks: the lists a
# latent cell stands on, this one among them since PR 70
LATENT = ("mla.step_share.train", "mla.assemble_share.train", METRIC)


@pytest.fixture(autouse=True)
def nothing_counted_is_left_behind():
    """A traced run turns telemetry on and its dispatch rows stay in the
    process: the next file of this worker must not read them."""
    yield
    flags.set_flags({"telemetry": False})
    monitor.reset()


def read(run):
    return harness.reader_for(METRIC).read(run)


def rows_with_parts():
    rows = monitor.snapshot().get("pt_attention_dispatch_total", {})
    return [r for r in rows.get("values", [])
            if r["value"] and "parts" in r["labels"]]


def test_the_entry_lists_the_three_latent_cells():
    assert tiny.listed_as(METRIC, "count", "lower", "program_counter",
                          "Program lowering", *CELLS)
    assert tiny.cells_named(tiny.BENCH, METRIC) == CELLS
    # the cells with a latent block, and no other, stand on all three
    # lists; each of them trains and counts its split backward calls too
    for name in LATENT:
        assert tiny.cells_named(tiny.BENCH, name) == CELLS, name
    for name in ("train_tokens_per_s", "lower.split_bwd_attn_calls.train",
                 "lower.dense_attn_calls.train"):
        assert set(CELLS) <= set(tiny.cells_named(tiny.BENCH, name)), name
    assert callable(harness.reader_for(METRIC).read)
    for cell in CELLS:
        assert tiny.entry(tiny.BENCH, METRIC) in harness.cell_metrics(
            tiny.BENCH, cell, "per_layer")


@pytest.mark.parametrize("cell_name", CELLS)
def test_through_the_kernels_every_latent_call_is_read_in_place(
        cell_name, monkeypatch, tmp_path, capsys):
    """The interpreter with the cap on a step's K and V so low that one
    tiny head fits and two do not (as one head of 192 over 128 a step on
    the chip): every row says ``parts=own``, forward and backward, and
    the metric reads 0, a number."""
    cfg = tiny.config(tiny.train_cell(cell_name)["config"])
    head = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            + cfg["v_head_dim"])
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_KV_VMEM_BYTES", 12 * 16 * head)
    line = traced_tiny_run(cell_name, monkeypatch, tmp_path)
    rows = rows_with_parts()
    assert rows and {r["labels"]["parts"] for r in rows} == {"own"}
    assert {r["labels"]["pass"] for r in rows} == {"fwd", "bwd"}
    assert {r["labels"]["family"] for r in rows} == {"bhtd"}
    wide = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    assert all(f"dk{wide} dv{cfg['v_head_dim']}" in r["labels"]["shape"]
               for r in rows)
    assert line["metrics"][METRIC]["value"] == 0
    assert line["metrics"]["lower.split_bwd_attn_calls.train"]["value"] == 0


@pytest.mark.parametrize("cell_name", CELLS[:1])
def test_on_this_cpu_the_op_assembles_and_every_call_counts(
        cell_name, monkeypatch, tmp_path, capsys):
    """No kernel: the op concatenates q and k itself and runs the
    composition; the metric is the latent calls, forward and backward
    (it is what says how often the mechanism does NOT engage)."""
    line = traced_tiny_run(cell_name, monkeypatch, tmp_path)
    rows = rows_with_parts()
    assert rows and {r["labels"]["parts"] for r in rows} == {"assembled"}
    assert {r["labels"]["family"] for r in rows} == {"dense"}
    calls = sum(int(r["value"]) for r in rows)
    assert calls >= 2 and calls % 2 == 0
    assert line["metrics"][METRIC]["value"] == calls


def test_the_reader_counts_assembled_rows_and_nothing_without_the_label():
    """A tree before the label, or a program that made no call in two
    parts: None and no exception. A call the kernels read in place does
    not count."""
    monitor.reset()
    run = tiny.make_run(tiny.train_cell("olmoe-train-s4096"),
                        tiny.config("olmoe-1b-7b"))
    assert read(run) is None
    dims = (1, 4096, 4096, 32, 192, 32, 128)
    flags.set_flags({"telemetry": True})
    tok = interp.set_amp_active(False)      # as inside a lowering
    try:
        # rows as the parent wrote them: no label, not counted
        attention_ops._note_dispatch("bhtd", "fwd", dims)
        attention_ops._note_dispatch("bhtd", "bwd", dims, form="fused")
        assert read(run) is None
        attention_ops._note_dispatch("bhtd", "fwd", dims, parts="own")
        attention_ops._note_dispatch("bhtd", "bwd", dims, form="fused",
                                     parts="own")
        assert read(run) == 0
        for direction in ("fwd", "bwd", "bwd"):
            attention_ops._note_dispatch("dense", direction, dims,
                                         parts="assembled")
        attention_ops._note_dispatch("bhtd", "fwd", dims, window=512,
                                     parts="assembled")
    finally:
        interp._AMP_ACTIVE.reset(tok)
        flags.set_flags({"telemetry": False})
    assert read(run) == 4
    # the rows' names are the wide call's: the label is beside them
    shape = "b1 tq4096 tk4096 h32 dk192 dv128"
    assert attention_ops.dispatch_counts() == {
        f"bhtd fwd {shape}": 2, f"bhtd bwd {shape}": 2,
        f"dense fwd {shape}": 1, f"dense bwd {shape}": 2,
        f"bhtd fwd {shape} w512": 1}
    monitor.reset()
    assert read(run) is None
