"""What the OLMoE family brings of its own: its second check against a
lower-precision control, its FLOP and byte functions, its packed
generator, and the five readers of the MoE metrics on the trace cut
from the cell's own traced run on the v5e."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_olmoe, harness, models, moe_spans, spans, trace
from perf.kinds import train
from perf.reference import olmoe as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "olmoe-train-s4096", "olmoe-1b-7b"
FIXTURE = os.path.join(harness.HERE, "fixtures",
                       "olmoe-train-v5e-one-step.xplane.pb.gz")


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


def v5e_run(cell):
    """A traced run's record as a v5e's: the readers ask the peaks of
    its device kind."""
    run = tiny.make_run(cell, full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    return run


# --- the second check ---------------------------------------------------


@pytest.fixture(scope="module")
def sample_readings():
    """(cfg, float32 weights, the sample's feed, what the eval clone
    under bf16 AMP gave for CHECK_FETCH) at the family's tiny sizes."""
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)
    _, startup, evalp, _, model = models.build_train(cfg, seed=2 ** 31 + 11)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    sample = train.sample_of(
        fam.feeds(cfg, tiny.train_cell(CELL)["traffic"], 5)[0])
    fetch, shape = jax.tree.flatten({k: model[k] for k in fam.CHECK_FETCH})
    w = {k: np.asarray(v) for k, v in weights_from_scope(scope).items()}
    fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
        evalp, feed=sample, fetch_list=fetch, scope=scope)])
    return cfg, w, sample, fetched


def test_second_check_passes_the_program(sample_readings):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        problems, record = ref.second_check(w, cfg, sample, fetched)
    assert problems == []
    assert set(record) == {"logit_err_over_rms", "positions_compared",
                           "positions", "flipped_share", "max_expert_load",
                           "limits"}
    assert record["positions"] == 8 * ref.LAST_POSITIONS
    assert record["positions_compared"] > record["positions"] // 2
    # bf16 matmuls at a width of 32: coarser than the chip's reading at
    # 2048 (PERF.md), still well inside the limit
    assert 0 < record["logit_err_over_rms"] < ref.LOGIT_ERR_LIMIT
    assert record["flipped_share"] <= ref.FLIP_LIMIT
    assert 1.0 <= record["max_expert_load"] < cfg["num_experts"]


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every matmul operand rounded to float8,
    # judged as if it were the program. The loss check does not see it.
    cfg, w, sample, _ = sample_readings
    with jax.default_matmul_precision("highest"):
        low = ref.forward(w, cfg, sample["input_ids"],
                          round_to=getattr(jnp, control),
                          last=ref.LAST_POSITIONS)
        rows = [np.bincount(np.asarray(t).ravel(),
                            minlength=cfg["num_experts"])
                for t in low["top_i"]]
        problems, record = ref.second_check(
            w, cfg, sample, {"last_logits": low["logits"],
                             "top_i": low["top_i"], "expert_rows": rows})
        want = float(ref.loss(w, cfg, sample))
        got = float(ref.loss(w, cfg, sample,
                             round_to=getattr(jnp, control)))
    assert problems, record
    assert (record["logit_err_over_rms"] > ref.LOGIT_ERR_LIMIT
            or record["flipped_share"] > ref.FLIP_LIMIT)
    assert abs(got - want) / want < train.LOSS_REL_TOL


def test_choices_compare_as_sets_and_count_per_slot():
    a = np.array([[0, 1], [2, 3], [4, 5]])
    b = np.array([[1, 0], [2, 7], [6, 7]])
    assert ref.choices_differ(a, b, 8).tolist() == [0, 1, 2]
    cfg = dict(num_experts=8, num_experts_per_tok=2)
    want = {"logits": np.ones((1, 2, 4), np.float32), "top_i": [b]}
    got = np.ones((1, 2, 4), np.float32)
    got[0, 0, 0] = 1.5      # at a position whose choices agree: not
    got[0, 1, 0] = 3.0      # the last two of three positions
    rec = ref.compare(cfg, want, got, [a])
    assert rec["flipped_share"] == pytest.approx(3 / 6)
    assert rec["positions"] == 2 and rec["positions_compared"] == 0
    rec = ref.compare(cfg, want, got, [b])
    assert rec["positions_compared"] == 2
    assert rec["logit_err_over_rms"] == pytest.approx(2.0)


# --- the generator and the FLOPs ------------------------------------------


def test_packed_feeds_shift_labels_by_one_and_have_no_padding():
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)
    traffic = tiny.train_cell(CELL)["traffic"]
    feeds = fam.feeds(cfg, traffic, 2 ** 31 + 5)
    assert len(feeds) == 4
    for f in feeds:
        assert f["input_ids"].shape == f["labels"].shape == (8, 16)
        assert (f["input_ids"][:, 1:] == f["labels"][:, :-1]).all()
        assert 0 <= f["input_ids"].min() and f["labels"].max() < 50
        assert fam.real_tokens(f) == 8 * 16
    with pytest.raises(AssertionError):
        fam.feeds(cfg, dict(traffic, real_len=[8, 16]), 1)


def test_train_flops_count_active_parameters_and_half_the_attention():
    cfg = full_config()
    d, f, v = 2048, 1024, 50304
    active = 4 * d * d + 8 * 3 * d * f + d * 64     # a block, per token
    tok, t = 2 * 4096, 4096
    want = 6 * tok * (active + d * v) + 3 * (2 * 2 * tok * t * d / 2)
    assert flops_olmoe.olmoe_train_flops(cfg, 2, 4096) == pytest.approx(want)
    # all 64 experts would be 2.7 times the step
    dense = want + 6 * tok * 56 * 3 * d * f
    assert dense / want > 2.5
    assert flops_olmoe.olmoe_train_flops(
        dict(cfg, num_hidden_layers=2), 2, 4096) == pytest.approx(
            want + 6 * tok * active + 3 * 2 * tok * t * d)
    # causal attention through the shared function: half of full
    cost = models.family(cfg).attention_cost(cfg, 2, 4096)
    assert cost["calls"] == 2
    assert cost["flops"] == pytest.approx(12 * 2 * 16 * 4096 * 4096 * 128 / 2)
    assert cost["bytes"] == 12 * 2 * 4096 * 2048 * 2


def test_gmm_cost_is_nine_grouped_matmuls_a_block():
    cfg = full_config()
    cost = flops_olmoe.moe_gmm_cost(cfg, 2, 4096)
    m = 2 * 4096 * 8
    assert cost["calls"] == 9
    assert cost["flops"] == 9 * 2 * m * 2048 * 1024     # 2.47 TFLOP
    assert cost["bytes"] == 9 * 2 * (m * 2048 + m * 1024 + 64 * 2048 * 1024)
    peaks = harness.peaks_for("TPU v5 lite")
    # FLOP-bound at these widths: 12.6 ms against 7.4 ms of bytes
    assert cost["flops"] / peaks["bf16_flops_per_s"] == pytest.approx(
        12.558e-3, rel=1e-3)
    assert cost["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(
        7.375e-3, rel=1e-3)
    assert flops_olmoe.moe_gmm_cost(dict(cfg, num_hidden_layers=3), 2,
                                    4096)["calls"] == 27


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, gmm_s=0.0, family=moe_spans.XLA_GMM, busy=100.0,
               devices=1):
    """A run whose trace reduced to these numbers (ns by scope)."""
    run = v5e_run(tiny.train_cell(CELL))
    run.trace = {"devices": devices, "busy_s": busy / 1e9,
                 "by_family_s": {family: gmm_s} if gmm_s else {}}
    run._spans = {"busy_ns": busy * devices, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


def test_moe_readers_sum_scopes_and_the_unscoped_grouped_matmuls():
    by_scope = {
        "fwd/embed/lookup_table": 2.0,
        "fwd/blk0/attn/mul": 6.0,
        "bwd/blk0/attn/scaled_dot_product_attention_grad": 8.0,
        "fwd/blk0/moe/rms_norm": 1.0,
        "fwd/blk0/moe/router/moe_router": 1.0,
        "bwd/blk0/moe/dispatch/moe_dispatch_grad": 2.0,
        "bwd/blk0/moe/combine/moe_combine_grad": 3.0,
        "fwd/blk0/moe/experts/moe_experts": 4.0,
        "bwd/blk1/moe/elementwise_add_grad": 1.0,
        "bwd/loss_head/mul_grad": 30.0,
        "opt/adam": 12.0,
        "fwd/moe/mul": 5.0,        # a scope named moe outside a block
    }
    # XLA's own calls carry no scope: added to block and MoE
    run = scopes_run(by_scope, gmm_s=20e-9)
    assert read("step.block_share.train", run) == pytest.approx(46.0)
    assert read("moe.step_share.train", run) == pytest.approx(32.0)
    assert read("moe.route_share.train", run) == pytest.approx(
        100 * 6.0 / 32.0)
    # the program's own kernel sits under its op's scope: nothing added
    own = scopes_run(dict(by_scope, **{
        "fwd/blk0/moe/experts/moe_experts": 24.0}), gmm_s=20e-9,
        family="moe")
    assert read("moe.step_share.train", own) == pytest.approx(32.0)
    assert moe_spans.gmm_family(own) == "moe"
    # four chips: by_family_s is a chip's mean, the scopes a sum
    four = scopes_run({k: 4 * v for k, v in by_scope.items()},
                      gmm_s=20e-9, devices=4)
    assert read("moe.step_share.train", four) == pytest.approx(32.0)


@pytest.mark.parametrize("metric", [
    "step.block_share.train", "moe.step_share.train",
    "moe.route_share.train", "moe.gmm_roofline.train",
    "moe.max_expert_load.train"])
def test_moe_readers_find_nothing_in_a_program_without_the_block(metric):
    # the parent of PR 28, and the three cells that were there
    run = scopes_run({"fwd/enc0/attn/mul": 5.0, "opt/adam": 1.0})
    run.config = harness.load_json("perf", "configs", "bert-base.json")
    assert read(metric, run) is None
    run.trace = None
    run._spans = None
    assert read(metric, run) is None


def test_gmm_roofline_reads_the_steps_least_time_over_the_calls_time():
    run = scopes_run({"fwd/blk0/moe/rms_norm": 1.0}, gmm_s=0.0277 * 17)
    run.window = {"traced_steps": 17}
    run.cell["traffic"].update(batch=2, seq_len=4096)
    # 12.56 ms least over 27.7 ms measured
    assert read("moe.gmm_roofline.train", run) == pytest.approx(
        100 * 12.558 / 27.7, rel=1e-3)
    run.window = {}
    assert read("moe.gmm_roofline.train", run) is None


def test_max_expert_load_is_the_second_checks_reading():
    run = scopes_run({})
    assert read("moe.max_expert_load.train", run) is None
    run.check = {"rel": 1e-5, "second": {"max_expert_load": 1.21}}
    assert read("moe.max_expert_load.train", run) == 1.21


# --- the fixture: one step of the cell's traced run on the v5e -----------


@pytest.fixture(scope="module")
def fixture_run():
    run = v5e_run(harness.load_json("perf", "workloads", f"{CELL}.json"))
    run.trace = trace.reduce(trace.load(FIXTURE))
    run._spans = spans.reduce(spans.load(FIXTURE))
    run.window = {"traced_steps": 1}
    return run


def test_fixture_holds_the_blocks_names(fixture_run):
    s, t = fixture_run._spans, fixture_run.trace
    assert t["devices"] == 1 and set(t["by_family_s"]) == {
        "attn", "ragged-dot-none", "ragged-dot-metadata"}
    assert set(t["by_kernel_s"]) >= {
        "attn.bhtd.fwd", "attn.bhtd.bwd_dq", "attn.bhtd.bwd_dkv",
        "ragged-dot-none"}
    parts = {tuple(k.split("/")[1:-1]) for k in s["by_scope_ns"]}
    for scope in (("embed",), ("blk0", "attn"), ("blk0", "moe"),
                  ("blk0", "moe", "router"), ("blk0", "moe", "dispatch"),
                  ("blk0", "moe", "experts"), ("blk0", "moe", "combine"),
                  ("final_norm",), ("loss_head",)):
        assert scope in parts, scope
    phases = {k.split("/")[0] for k in s["by_scope_ns"]
              if "/blk0/moe/" in k}
    assert phases == {"fwd", "bwd"}
    assert set(s["kernel_ns"]) == {"attn.bhtd.fwd", "attn.bhtd.bwd_dq",
                                   "attn.bhtd.bwd_dkv"}


# the readers' values on that one step (137.7 ms): what the whole traced
# run read over its 17 steps, to three digits (PERF.md section 5)
PINNED = {
    "step.block_share.train": 49.955800105471546,
    "moe.step_share.train": 32.010117652374866,
    "moe.route_share.train": 24.73504648765584,
    "moe.gmm_roofline.train": 45.23883310917644,
    "attn.time_share.train": 10.066630348006576,
    "train_attn_roofline": 15.098588256161547,
    "attn.bwd_time_share.train": 6.66786257790216,
    "step.head_share.train": 29.333619437749302,
    "step.opt_share.train": 14.619213891567558,
    "step.bwd_share.train": 42.45964301651302,
    "lower.scoped_share.train": 76.43249883745011,
}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_readers_on_the_fixture(fixture_run, metric):
    cost = models.family(fixture_run.config).attention_cost(
        fixture_run.config, 2, 4096)
    fixture_run.window["attention"] = cost
    assert read(metric, fixture_run) == pytest.approx(PINNED[metric],
                                                      rel=1e-6)
    assert 0 < PINNED[metric] <= 100
