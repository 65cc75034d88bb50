"""``attn.qk_norm_share.train`` (PR 66): the per-head QK-norm's share of
the device's busy time, read from the table by scope, in the two cells
whose builders norm each head of q and k (``sdar-train-s4096``, whose
rotary op now takes the heads' gains and does the norm in the
``rope.*`` kernels' pass: no ``qk_norm`` scope is left, the share is
0.0, and its ``pt_rope_dispatch_total`` rows carry ``norm=head``; and
``lfm2moe-train-s8192``, whose heads of 64 ``rope_tile`` refuses and
whose two ``rms_norm`` ops a layer stay under ``qk_norm``)."""

import json
import types

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from perf import harness, models
from perf.kinds import train

SHARE = "attn.qk_norm_share.train"
CELL, CONFIG = "sdar-train-s4096", "sdar-30b-a3b"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


@pytest.fixture(autouse=True)
def nothing_counted_is_left_behind():
    """A traced run turns telemetry on and its dispatch rows stay in the
    process: the next file of this worker must not read them."""
    yield
    flags.set_flags({"telemetry": False})
    monitor.reset()


def read(run):
    return harness.reader_for(SHARE).read(run)


def scopes_run(by_scope, busy=100.0):
    """A run whose table by scope (ns of self time) is written by hand."""
    run = tiny.make_run(tiny.train_cell(CELL), tiny.config(CONFIG),
                        traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": 1}
    run.trace = {"devices": 1, "busy_s": busy / 1e9, "by_family_s": {}}
    run._spans = {"chips": 1, "busy_ns": busy, "by_scope_ns": by_scope,
                  "scoped_ns": sum(by_scope.values()), "head_ns": 0.0}
    return run


def test_the_entry_lists_the_two_cells_that_norm_each_head():
    assert tiny.listed_as(SHARE, "%", "lower", "device_trace", "Kernels",
                          CELL, "lfm2moe-train-s8192")
    assert tiny.cells_named(tiny.BENCH, SHARE) == [
        CELL, "lfm2moe-train-s8192"]


@pytest.mark.parametrize("busy,want", [(100.0, 8.0), (200.0, 4.0)])
def test_the_share_sums_the_ops_under_qk_norm_inside_a_blocks_attention(
        busy, want):
    """Forward and backward, every block: a share of BUSY time, not of
    the table. A ``qk_norm`` scope outside a block's attention and the
    attention's other norms are not the per-head norm."""
    run = scopes_run({
        "fwd/blk0/attn/qk_norm/rms_norm": 1.5,
        "bwd/blk0/attn/qk_norm/rms_norm_grad": 2.5,
        "fwd/blk2/attn/qk_norm/rms_norm": 1.0,
        "bwd/blk2/attn/qk_norm/rms_norm_grad": 3.0,
        "fwd/blk0/attn/rms_norm": 7.0,              # the pre-norm
        "fwd/blk0/attn/rope/rotary_embedding": 2.0,
        "fwd/blk0/moe/qk_norm/rms_norm": 5.0,       # not an attention's
        "fwd/qk_norm/rms_norm": 5.0,                # not a block's
        "fwd/blk0/attn/core/scaled_dot_product_attention": 20.0,
        "opt/adam": 10.0}, busy=busy)
    assert read(run) == pytest.approx(want)


def test_a_program_without_the_scope_reads_zero_and_one_without_blocks_none():
    """The norm inside the rotary op: ops under ``blk<i>/attn``, none
    under ``qk_norm``: 0.0, a number (the metric did not fall silent,
    the work went). No ``blk<i>/attn`` at all (the encoder-era cells), a
    run that traced nothing, an untraced run: None and no exception."""
    folded = scopes_run({
        "fwd/blk0/attn/rms_norm": 7.0,
        "fwd/blk0/attn/rope/rotary_embedding": 3.0,
        "bwd/blk0/attn/rope/rotary_embedding_grad": 4.0,
        "fwd/blk0/attn/bd/scaled_dot_product_attention": 20.0,
        "opt/adam": 10.0})
    assert read(folded) == 0.0
    assert read(scopes_run({"fwd/enc0/attn/mul": 5.0,
                            "fwd/loss_head/matmul": 6.0})) is None
    assert read(scopes_run({"fwd/blk0/attn/mul": 5.0}, busy=0.0)) is None
    nothing = scopes_run({})
    nothing._spans = None
    nothing.trace = None
    assert read(nothing) is None
    untraced = tiny.make_run(tiny.train_cell(CELL), tiny.config(CONFIG))
    assert read(untraced) is None


def test_a_traced_tiny_sdar_run_reads_zero_and_its_rows_carry_norm_head(
        monkeypatch):
    """The tiny cell at heads of 128 through the interpreter, a row of
    64 data tokens (two runs of 64 positions: a block of rows each):
    every rotary call, the step's and the eval clone's, is a kernel
    call with the heads' gains, none XLA's. The CPU leaves no device
    trace, so the result line has no share; the table the program's own
    ops would write (one ns an op under its scope) reads 0.0: no op of a
    block's attention is under ``qk_norm``."""
    from paddle_tpu.parallel import rope

    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(rope, "_INTERPRET", True)
    monitor.reset()
    cell = tiny.train_cell(CELL)
    cell["traffic"].update(batch=1, seq_len=64, real_len=[64, 64])
    cfg = dict(tiny.config(CONFIG), head_dim=128)
    run = tiny.make_run(cell, cfg, seconds=0.3, traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert SHARE not in line["metrics"]
    assert line["metrics"]["lower.xla_rope_calls.train"]["value"] == 0
    rows = harness.counter_rows("pt_rope_dispatch_total")
    layers = cfg["num_hidden_layers"]
    assert rows and all(lb["impl"] == "kernel" and lb["norm"] == "head"
                        and lb["dh"] == "128" for lb, _ in rows)
    assert {lb["pass"]: n for lb, n in rows}["bwd"] == layers
    # the program's own scopes as a table
    main = models.build_train(cfg, 7)[0]
    table = {}
    for op in main.global_block().ops:
        if op.namescope:
            key = f"{op.role}/{op.namescope}/{op.type}"
            table[key] = table.get(key, 0.0) + 1.0
    attn = [k for k in table if "/attn/" in k]
    assert any(k.endswith("/rope/rotary_embedding_grad") for k in attn)
    assert not any("qk_norm" in k for k in table)
    assert sum(k.endswith("/rms_norm") for k in attn) == layers  # pre-norms
    assert read(scopes_run(table, busy=sum(table.values()))) == 0.0
