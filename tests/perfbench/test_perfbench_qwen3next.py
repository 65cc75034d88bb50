"""What the Qwen3-Next family brings of its own: its second check
against a lower-precision control, the held experts' rows, its FLOP and
byte functions, and the readers of the four ``gdn`` metrics."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, monitor
from perf import flops_qwen3next as fq
from perf import gdn_spans, harness, models
from perf.kinds import train
from perf.reference import qwen3next as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "qwen3next-train-s8192", "qwen3-next-80b-a3b"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import qwen3_next as M

    cfg, pub = full_config(), M.Qwen3NextConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (48, 4), "vocab_size": (151936, 18992)}
    for key, value in vars(pub).items():
        if key in ("held_experts",):
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 512; the chip holds experts 0..31
    assert pcfg.num_experts == 512 == cfg["reduced_from"]["num_experts"]
    assert pcfg.held_experts == (0, 32) and cfg["num_experts"] == 32
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    # one whole period: linear, linear, linear, full
    assert [pcfg.is_full_attention(i) for i in range(4)] == [
        False, False, False, True]
    assert fq.layer_kinds(cfg) == (3, 1)
    assert pcfg.rotary_dim == 64


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
    assert set(record) == {"logit_err_over_rms", "logit_max_err_over_rms",
                           "positions_compared",
                           "positions", "flipped_share", "max_expert_load",
                           "held_row_share", "limits"}
    # the tiny rows have 16 positions: all of them are "last"
    assert record["positions"] == 8 * min(16, ref.LAST_POSITIONS)
    assert record["positions_compared"] > record["positions"] // 2
    assert 0 < record["logit_err_over_rms"] < ref.LOGIT_ERR_LIMIT
    assert record["logit_err_over_rms"] <= record["logit_max_err_over_rms"]
    assert record["flipped_share"] <= ref.FLIP_LIMIT


def test_expert_rows_are_the_held_experts(sample_readings):
    """4 of the 16 experts the tiny router scores are held: the eval
    clone's ``expert_rows`` has 4 entries a layer, they count the pairs
    on experts 0..3 and no other, and the record's ``held_row_share`` is
    those pairs over all 8 x 16 x 3."""
    cfg, w, sample, fetched = sample_readings
    assert (cfg["num_experts"], cfg["router_experts"]) == (4, 16)
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (cfg["num_hidden_layers"], 4)
    for layer, top_i in enumerate(fetched["top_i"]):
        assert top_i.shape == (8 * 16, 3) and top_i.max() < 16
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=16)[:4]).all()
    with jax.default_matmul_precision("highest"):
        _, record = ref.second_check(w, cfg, sample, fetched)
    assert record["held_row_share"] == pytest.approx(
        rows.sum() / (cfg["num_hidden_layers"] * 8 * 16 * 3))
    assert 0.05 < record["held_row_share"] < 0.6     # about 4 / 16
    assert 1.0 <= record["max_expert_load"] <= 4.0


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control, monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands rounded to
    # float8, judged as if it were the program. The loss check does not
    # see it. The limits in the file are the chip's, between readings at
    # the published widths (sums over 2048 terms, ten of 512 experts);
    # at the tiny sizes (32 terms, three of 16) both sides read several
    # times lower, so the limits are set here as there: at the
    # geometric middle of the two readings.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = ref.forward(w, cfg, sample["input_ids"],
                          round_to=getattr(jnp, control),
                          last=ref.LAST_POSITIONS)
        rows = [np.bincount(np.asarray(t).ravel(), minlength=16)[:4]
                for t in low["top_i"]]
        as_program = {"last_logits": low["logits"], "top_i": low["top_i"],
                      "expert_rows": rows}
        _, record = ref.second_check(w, cfg, sample, as_program)
        want = float(ref.loss(w, cfg, sample))
        got = float(ref.loss(w, cfg, sample,
                             round_to=getattr(jnp, control)))
        assert abs(got - want) / want < train.LOSS_REL_TOL
        # the control is further from the reference than the program by
        # both readings, the logits' by several times
        assert record["logit_err_over_rms"] > 3 * program[
            "logit_err_over_rms"]
        assert record["flipped_share"] > program["flipped_share"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        monkeypatch.setattr(ref, "FLIP_LIMIT", float(np.sqrt(
            record["flipped_share"] * max(program["flipped_share"], 1e-3))))
        problems, _ = ref.second_check(w, cfg, sample, as_program)
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 2 and passes == []
    assert "logits differ" in problems[0] and "choices" in problems[1]


# --- the FLOPs ----------------------------------------------------------------


def test_train_flops_count_this_chips_share_of_a_token():
    cfg = full_config()
    d, tok, t = 2048, 8192, 8192
    scan = 32 * (4 * 64 * 128 + 64 * 256 + 2 * 64 * 128 + 6 * 128 * 128)
    assert fq.gdn_scan_flops_per_token(cfg, 64) == scan        # 5.24M
    gdn = 2 * d * (12288 + 64) + 2 * 4096 * d + scan           # 72.6M
    attn = 2 * d * 9216 + 2 * 4096 * d + 2 * t * 16 * 256      # 121.6M
    # router over all 512, shared expert and its gate, and 10 x 32 / 512
    # of a row on the held experts
    moe = 2 * d * 512 + 6 * d * 512 + 2 * d + 0.625 * 6 * d * 512
    head = 2 * d * 18992
    want = 3.0 * tok * (3 * gdn + attn + 4 * moe + head)
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, 8192) == pytest.approx(want)
    assert want / (3 * tok) == pytest.approx(466.4e6, rel=2e-3)
    assert want == pytest.approx(11.46e12, rel=2e-3)
    # every expert held and chosen-by-all would be the dense count
    all_held = fq.qwen3next_train_flops(
        dict(cfg, num_experts=512), 1, 8192)
    assert all_held - want == pytest.approx(
        3.0 * tok * 4 * (10 - 0.625) * 6 * d * 512)
    cost = fam.attention_cost(cfg, 1, 8192)
    assert cost["calls"] == 2
    assert cost["flops"] == pytest.approx(12 * 16 * 8192 * 8192 * 256 / 2)
    peaks = harness.peaks_for("TPU v5 lite")
    # FLOP-bound by far: the byte count (K and V counted at 16 heads)
    # cannot lift the roofline share over 100
    assert cost["flops"] / peaks["bf16_flops_per_s"] > 5 * (
        cost["bytes"] / peaks["hbm_bytes_per_s"])


def test_gdn_scan_cost_counts_three_layers_forward_and_twice_backward():
    cfg = full_config()
    cost = fq.gdn_scan_cost(cfg, 1, 8192, 64)
    per_tok = fq.gdn_scan_flops_per_token(cfg, 64)
    assert cost["calls"] == 6
    assert cost["flops"] == 3 * 3 * 8192 * per_tok            # 0.386 TFLOP
    moved = 8192 * ((2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4)
    assert cost["bytes"] == 2 * 3 * moved
    peaks = harness.peaks_for("TPU v5 lite")
    assert cost["flops"] / peaks["bf16_flops_per_s"] == pytest.approx(
        1.96e-3, rel=2e-2)
    # a longer chunk does more inside a chunk and as much against the state
    assert fq.gdn_scan_flops_per_token(cfg, 128) > per_tok
    assert fq.gdn_scan_cost(dict(cfg, num_hidden_layers=8), 1, 8192,
                            64)["calls"] == 12


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.cell = harness.load_json("perf", "workloads", f"{CELL}.json")
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9, "by_family_s": {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk0/gdn/rms_norm": 1.0,
    "fwd/blk0/gdn/proj/mul": 9.0,
    "fwd/blk0/gdn/conv/causal_conv1d": 2.0,
    "fwd/blk0/gdn/rule/gated_delta_rule": 6.0,
    "bwd/blk1/gdn/rule/gated_delta_rule_grad": 12.0,
    "bwd/blk1/gdn/gate_norm/gated_rms_norm_grad": 2.0,
    "bwd/blk1/gdn/out/mul_grad": 8.0,
    "fwd/blk3/attn/scaled_dot_product_attention": 10.0,
    "fwd/blk3/moe/shared/mul": 3.0,
    "fwd/gdn/mul": 5.0,            # a scope named gdn outside a block
    "opt/adam": 10.0,
}


def test_gdn_readers_sum_the_mixers_scopes():
    run = scopes_run(BY_SCOPE)
    assert read("gdn.step_share.train", run) == pytest.approx(40.0)
    assert read("gdn.scan_share.train", run) == pytest.approx(
        100 * (2 + 6 + 12 + 2) / 40.0)
    # the shared expert is inside the expert layer, the mixers are not
    assert read("moe.step_share.train", run) == pytest.approx(3.0)
    assert read("step.block_share.train", run) == pytest.approx(53.0)


def test_gdn_readers_report_nothing_for_a_program_without_the_layer():
    """A parent's tree, or another family's cell: no ``gdn`` scope and
    no counter row. None, and no exception."""
    run = scopes_run({k: v for k, v in BY_SCOPE.items()
                      if "/gdn/" not in k or not k.split("/")[1].startswith(
                          "blk")})
    for metric in ("gdn.step_share.train", "gdn.scan_share.train",
                   "gdn.scan_roofline.train",
                   "lower.recurrent_gdn_calls.train"):
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    assert read("gdn.step_share.train", run) is None


def test_roofline_and_recurrent_count_read_the_dispatch_counter():
    from paddle_tpu.ops import linear_attention_ops as L

    q = jnp.zeros((1, 16, 2, 8))
    v = jnp.zeros((1, 16, 4, 8))
    flags.set_flags({"telemetry": True})
    try:
        from paddle_tpu.core import interp

        tok = interp.set_amp_active(False)
        try:
            for direction in ("fwd", "bwd"):
                for _ in range(3):
                    L._note_dispatch(direction, q, v, 64, "chunked")
        finally:
            interp._AMP_ACTIVE.reset(tok)
        rows = gdn_spans.dispatch_rows()
        assert sum(n for _, n in rows) == 6
        # 18 ns under rule for one step: the count's 1.96 ms over it
        run = scopes_run(BY_SCOPE)
        cost = fq.gdn_scan_cost(full_config(), 1, 8192, 64)
        peaks = harness.peaks_for("TPU v5 lite")
        least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                    cost["bytes"] / peaks["hbm_bytes_per_s"])
        assert read("gdn.scan_roofline.train", run) == pytest.approx(
            100 * least / 18e-9)
        assert read("lower.recurrent_gdn_calls.train", run) == 0
        tok = interp.set_amp_active(False)
        try:
            L._note_dispatch("fwd", q, v, 1, "recurrent")
        finally:
            interp._AMP_ACTIVE.reset(tok)
        assert read("lower.recurrent_gdn_calls.train", run) == 1
        # two chunk sizes in one process: which one the time is of is
        # not known, so nothing is reported
        tok = interp.set_amp_active(False)
        try:
            L._note_dispatch("fwd", q, v, 32, "chunked")
        finally:
            interp._AMP_ACTIVE.reset(tok)
        assert read("gdn.scan_roofline.train", run) is None
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_positions_compare_where_the_held_choices_agree():
    """4 experts scored, experts 2..3 held, top 2: a choice that differs
    among experts held elsewhere counts as a flip and still leaves the
    position compared; one that touches a held expert takes it out."""
    cfg = dict(num_experts=2, held_first=2, router_experts=4,
               num_experts_per_tok=2)
    ref_i = np.array([[0, 1], [0, 2], [2, 3], [1, 3]])
    got_i = np.array([[1, 0], [1, 2], [2, 0], [1, 3]])
    #                  same   0 -> 1  3 -> 0  same
    want = {"logits": np.ones((1, 4, 5), np.float32), "top_i": [ref_i]}
    got = np.ones((1, 4, 5), np.float32)
    got[0, 1] += 0.5           # compared: both chose expert 2 of the held
    got[0, 2] += 7.0           # not compared: expert 3 was dropped
    rec = ref.compare(cfg, want, got, [got_i])
    assert rec["flipped_share"] == pytest.approx(2 / 8)
    assert (rec["positions"], rec["positions_compared"]) == (4, 3)
    assert rec["logit_max_err_over_rms"] == pytest.approx(0.5)
    assert rec["logit_err_over_rms"] == pytest.approx(np.sqrt(0.25 / 3))
    assert ref.choices_differ(got_i, ref_i, 4).tolist() == [0, 1, 1, 0]
