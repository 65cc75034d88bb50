"""The rotary embedding's and the embedding gradient's pairs of
metrics (PR 54): ``lower.xla_rope_calls.train`` and
``lower.xla_embed_grad_calls.train`` from the program's dispatch
counters (``pt_rope_dispatch_total``, ops/attention_ops.py;
``pt_embedding_grad_dispatch_total``, ops/tensor_ops.py),
``rope.step_share.train`` from the table by scope and
``embed.grad_share.train`` from the Mosaic calls' time by family. On the
CPU no call gets a tile, so a traced tiny run counts every call; on the
chip the two counts read 0 where the kernels take the calls."""

import json
import types

import pytest

import perfbench_tiny as tiny
from paddle_tpu import flags, monitor
from perf import harness
from perf.kinds import train

ROPE_CALLS, ROPE_SHARE = "lower.xla_rope_calls.train", "rope.step_share.train"
EMBED_CALLS, EMBED_SHARE = ("lower.xla_embed_grad_calls.train",
                            "embed.grad_share.train")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def read(metric, run):
    return harness.reader_for(metric).read(run)


def scopes_run(by_scope, busy=100.0, by_family_s=None):
    """A run whose trace is written by hand: the table by scope in ns,
    the Mosaic calls' seconds by family."""
    run = tiny.make_run(tiny.train_cell("olmoe-train-s4096"),
                        tiny.config("olmoe-1b-7b"), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": 1}
    run.trace = {"devices": 1, "busy_s": busy / 1e9,
                 "by_family_s": by_family_s or {}}
    run._spans = {"chips": 1, "busy_ns": busy, "by_scope_ns": by_scope,
                  "scoped_ns": sum(by_scope.values())}
    return run


def test_the_four_are_entries_on_the_cells_that_rotate_and_that_embed():
    PL, K = "Program lowering", "Kernels"
    rotate = ["olmoe-train-s4096", "qwen3next-train-s8192",
              "joyai-train-s4096", "smallthinker-train-s16384",
              "lfm2moe-train-s8192"]
    assert tiny.listed_as(ROPE_CALLS, "count", "lower", "program_counter",
                          PL, *rotate)
    assert tiny.listed_as(ROPE_SHARE, "%", "lower", "device_trace", K,
                          *rotate)
    # every decoder cell: those with a block (their tables are 1024
    # wide or more; the encoder-era cells' are 512 and 768)
    decoders = tiny.cells_named(tiny.BENCH, "step.block_share.train")
    assert tiny.listed_as(EMBED_CALLS, "count", "lower", "program_counter",
                          PL, *decoders)
    assert tiny.listed_as(EMBED_SHARE, "%", "lower", "device_trace", K,
                          *decoders)
    # a pair lists the same cells, and none that does not train
    assert tiny.cells_named(tiny.BENCH, ROPE_CALLS) \
        == tiny.cells_named(tiny.BENCH, ROPE_SHARE)
    assert tiny.cells_named(tiny.BENCH, EMBED_CALLS) \
        == tiny.cells_named(tiny.BENCH, EMBED_SHARE)
    assert "tbase-train" not in tiny.cells_named(tiny.BENCH, EMBED_CALLS)


def test_the_rope_reader_counts_xla_rows_and_reports_nothing_without_rows():
    """A tree before the counter, or a program that rotates nothing:
    None and no exception. A call that took the kernel does not count."""
    from paddle_tpu.ops import attention_ops

    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(ROPE_CALLS, run) is None
    flags.set_flags({"telemetry": True})
    try:
        def note(impl, direction, dh):
            attention_ops._M_ROPE.inc(labels={
                "impl": impl, "pass": direction, "layout": "bthd",
                "dh": str(dh)})

        note("kernel", "fwd", 128)
        note("kernel", "bwd", 128)
        assert read(ROPE_CALLS, run) == 0
        for direction in ("fwd", "fwd", "bwd"):     # eval clone + step
            note("xla", direction, 64)
    finally:
        flags.set_flags({"telemetry": False})
    assert read(ROPE_CALLS, run) == 3
    rows = {(lb["impl"], lb["pass"], lb["dh"]): n
            for lb, n in harness.counter_rows("pt_rope_dispatch_total")}
    assert rows == {("kernel", "fwd", "128"): 1, ("kernel", "bwd", "128"): 1,
                    ("xla", "fwd", "64"): 2, ("xla", "bwd", "64"): 1}
    monitor.reset()
    assert read(ROPE_CALLS, run) is None
    assert harness.counter_rows("pt_rope_dispatch_total") == []
    assert harness.counter_rows("pt_no_such_counter_total") == []


def test_the_embed_grad_reader_counts_xla_rows_and_reports_nothing_without():
    """A tree before the counter, or a program without a dense table's
    gradient: None and no exception. The kernel's calls do not count."""
    from paddle_tpu.ops import tensor_ops

    monitor.reset()
    run = tiny.make_run(tiny.train_cell("tbase-train"),
                        tiny.config("transformer-base"))
    assert read(EMBED_CALLS, run) is None
    flags.set_flags({"telemetry": True})
    try:
        tensor_ops._M_EMBED_GRAD.inc(labels={"impl": "kernel"})
        assert read(EMBED_CALLS, run) == 0
        tensor_ops._M_EMBED_GRAD.inc(labels={"impl": "xla"})
        tensor_ops._M_EMBED_GRAD.inc(labels={"impl": "xla"})
    finally:
        flags.set_flags({"telemetry": False})
    assert read(EMBED_CALLS, run) == 2
    monitor.reset()
    assert read(EMBED_CALLS, run) is None


def test_the_rope_share_sums_the_op_and_the_scope_whichever_way_it_lowered():
    """A kernel's call carries its op's scope, XLA's form is the op's
    own ops, and a builder's ``rope`` scope holds what it keeps around
    the rotation: each row of the table counts once."""
    by_scope = {
        # OLMoE's builder opens no rope scope: the op type says it
        "fwd/blk0/attn/rotary_embedding": 2.0,
        "bwd/blk0/attn/rotary_embedding_grad": 3.0,
        # under a rope scope: the op, and the assembly beside it
        "fwd/blk1/attn/rope/rotary_embedding": 1.0,
        "fwd/blk1/attn/rope/concat": 0.5,
        "bwd/blk1/attn/rope/split_grad": 0.25,
        # not the rotary embedding
        "fwd/blk0/attn/core/scaled_dot_product_attention": 20.0,
        "fwd/blk0/attn/qkv/mul": 10.0,
        "fwd/rope/mul": 4.0,        # counted: a scope named rope
        "fwd/loss_head/matmul": 6.0, "opt/adam": 10.0}
    run = scopes_run(by_scope)
    assert read(ROPE_SHARE, run) == pytest.approx(2 + 3 + 1 + 0.5 + 0.25 + 4)
    # half the chips' time elsewhere: a share of busy, not of the table
    assert read(ROPE_SHARE, scopes_run(by_scope, busy=200.0)) \
        == pytest.approx((2 + 3 + 1 + 0.5 + 0.25 + 4) / 2)
    # a program that rotates nothing, a run that traced nothing
    plain = scopes_run({"fwd/blk0/attn/core/scaled_dot_product_attention":
                        20.0, "opt/adam": 10.0})
    assert read(ROPE_SHARE, plain) is None
    plain._spans = None
    plain.trace = None
    assert read(ROPE_SHARE, plain) is None


def test_the_embed_share_is_the_familys_time_over_busy():
    run = scopes_run({"bwd/embed/lookup_table_grad": 9.0}, busy=100.0,
                     by_family_s={"embed": 4e-9, "attn": 30e-9})
    assert read(EMBED_SHARE, run) == pytest.approx(4.0)
    # XLA's scatter-add is no Mosaic call: nothing to read, not 0
    run = scopes_run({"bwd/embed/lookup_table_grad": 9.0},
                     by_family_s={"attn": 30e-9})
    assert read(EMBED_SHARE, run) is None
    run.trace = None
    assert read(EMBED_SHARE, run) is None


@pytest.mark.parametrize("cell_name", tiny.cells_named(tiny.BENCH,
                                                       ROPE_CALLS))
def test_a_traced_tiny_run_counts_its_rotations_and_its_tables_gradient(
        cell_name, monkeypatch, tmp_path, capsys):
    """On the CPU ``rope_tile`` and ``embed_grad_tile`` give no call a
    tile: the line carries both counts, the rotary embedding's calls
    (forward in the eval clone and the step, backward in the step) and
    the one table's gradient; on the chip the kernels take what they
    can and the counts read what PERF.md section 5 says."""
    from perf import models

    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    cell = tiny.train_cell(cell_name)
    cfg = tiny.config(cell["config"])
    # (the files' limits are the chip's at the published widths: the
    # tiny program under bf16 reads higher against 16 positions)
    ref = models.reference(cfg)
    for limit in ("LOGIT_ERR_LIMIT", "FLIP_LIMIT"):
        if hasattr(ref, limit):
            monkeypatch.setattr(ref, limit, 0.3)
    monitor.reset()
    run = tiny.make_run(cell, cfg, seconds=0.3, traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    rows = harness.counter_rows("pt_rope_dispatch_total")
    assert rows and {lb["impl"] for lb, _ in rows} == {"xla"}
    assert {lb["pass"] for lb, _ in rows} == {"fwd", "bwd"}
    assert line["metrics"][ROPE_CALLS]["value"] \
        == sum(n for _, n in rows) >= 3
    assert line["metrics"][EMBED_CALLS]["value"] >= 1
    # no device trace on a CPU: the two shares have nothing to read
    assert ROPE_SHARE not in line["metrics"]
    assert EMBED_SHARE not in line["metrics"]
    monitor.reset()
