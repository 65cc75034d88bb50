"""What the SmallThinker family brings of its own: its configuration's
cut, its FLOP and band counts against a brute-force count, its second
check against a lower-precision control and a no-window control, and the
readers of the ``swa`` metrics."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_smallthinker as fs
from perf import harness, models
from perf.kinds import train
from perf.reference import smallthinker as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "smallthinker-train-s16384", "smallthinker-21b-a3b"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import smallthinker as M

    cfg, pub = full_config(), M.SmallThinkerConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (52, 4), "vocab_size": (151936, 18992)}
    for key, value in vars(pub).items():
        if key in ("held_experts", "sliding_window_layout", "rope_layout"):
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 64; the chip holds experts 0..7
    assert pcfg.moe_num_primary_experts == 64 \
        == cfg["reduced_from"]["moe_num_primary_experts"]
    assert pcfg.held_experts == (0, 8) and cfg["moe_num_primary_experts"] == 8
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    # the layouts as published (52 entries); the four layers that are
    # built read the first period: global NoPE, then three that rotate
    # and forget
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] \
        == [0, 1, 1, 1] * 13
    assert [pcfg.window(i) for i in range(4)] == [None, 4096, 4096, 4096]
    assert [pcfg.rotates(i) for i in range(4)] == [False, True, True, True]
    assert fs.layer_windows(cfg) == [None, 4096, 4096, 4096]
    assert ref.AUX_COEF == pub.router_aux_loss_coef
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS
    traffic = harness.load_json("perf", "workloads", f"{CELL}.json")["traffic"]
    assert traffic["seq_len"] == cfg["max_position_embeddings"] == 16384


# --- the FLOPs ----------------------------------------------------------------


@pytest.mark.parametrize("t,window", [(16, 5), (16, 16), (16, 40), (64, 1),
                                      (300, 128), (16, None)])
def test_visible_pairs_against_a_brute_force_count(t, window):
    p, s = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (s <= p) & (p - s < (window or t))
    assert fs.visible_pairs(t, window) == seen.sum()


def test_band_of_the_cell_by_elements_and_by_blocks():
    t, w = 16384, 4096
    assert fs.visible_pairs(t, w) == 58_722_304          # 58.7M
    assert fs.visible_pairs(t, None) == 134_225_920
    assert fs.visible_pairs(t, w) / fs.visible_pairs(t, None) \
        == pytest.approx(0.4375, abs=2e-4)
    # whole blocks of 512: 252 live of the triangle's 528 (the kernels'
    # own count: tests/test_window_attention.py)
    j, kk = np.arange(32)[:, None], np.arange(32)[None, :]
    live = (kk <= j) & (j * 512 - (kk * 512 + 511) < w)
    assert live.sum() == 252
    assert 252 * 512 * 512 == pytest.approx(66.1e6, rel=1e-3)


def test_train_flops_count_a_triangle_and_three_bands():
    cfg = full_config()
    d, tok, t = 2560, 16384, 16384
    # q and o 3584 wide, k and v 512 each
    proj = 2 * d * (3584 + 2 * 512) + 2 * 3584 * d
    # router over all 64, and 6 x 8 / 64 of a row on the held experts
    moe = 2 * d * 64 + 0.75 * 6 * d * 768
    head = 2 * d * 18992
    attn = 12.0 * 28 * 128 * (fs.visible_pairs(t, None)
                              + 3 * fs.visible_pairs(t, 4096))
    want = 3.0 * tok * (4 * (proj + moe) + head) + attn
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, t) == pytest.approx(want)
    assert want == pytest.approx(28.1e12, rel=1e-2)
    assert attn == pytest.approx(13.3e12, rel=1e-2)      # 47% of the step
    assert attn / want == pytest.approx(0.47, abs=0.01)
    # four triangles would count attention 1.7 times too high
    triangles = 12.0 * 28 * 128 * 4 * fs.visible_pairs(t, None)
    assert triangles == pytest.approx(23.1e12, rel=1e-2)
    assert triangles / attn == pytest.approx(1.73, abs=0.02)
    # every expert held would be the whole k a token
    all_held = fs.smallthinker_train_flops(
        dict(cfg, moe_num_primary_experts=64), 1, t)
    assert all_held - want == pytest.approx(
        3.0 * tok * 4 * (6 - 0.75) * 6 * d * 768)


def test_attention_and_swa_cost_by_kind():
    cfg = full_config()
    t = 16384
    cost = models.family(cfg).attention_cost(cfg, 1, t)
    swa = fs.swa_cost(cfg, 1, t)
    assert (cost["calls"], swa["calls"]) == (8, 6)
    band, tri = fs.visible_pairs(t, 4096), fs.visible_pairs(t, None)
    assert swa["flops"] == 3 * 12.0 * 28 * 128 * band     # 7.58 TFLOP
    assert cost["flops"] - swa["flops"] == 12.0 * 28 * 128 * tri
    assert swa["flops"] == pytest.approx(7.58e12, rel=1e-3)
    assert cost["flops"] - swa["flops"] == pytest.approx(5.77e12, rel=1e-3)
    # q, o, dq, do twice... six tensors of 28 heads and six of 4
    assert swa["bytes"] == 3 * 6 * (28 + 4) * t * 128 * 2
    assert cost["bytes"] == 4 * 6 * (28 + 4) * t * 128 * 2
    peaks = harness.peaks_for("TPU v5 lite")
    # FLOP-bound: 12.8 ms a window layer against 0.98 ms of bytes
    assert swa["flops"] / peaks["bf16_flops_per_s"] / 3 \
        == pytest.approx(12.8e-3, rel=1e-2)
    assert swa["flops"] / peaks["bf16_flops_per_s"] \
        > 10 * swa["bytes"] / peaks["hbm_bytes_per_s"]
    # a kernel that computes whole blocks of 512 reads at most 88.8%
    assert band / (252 * 512 * 512) == pytest.approx(0.888, abs=1e-3)


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


def as_program(low, held=2, scored=8):
    rows = [np.bincount(np.asarray(t).ravel(), minlength=scored)[:held]
            for t in low["top_i"]]
    return {"last_logits": low["logits"], "top_i": low["top_i"],
            "expert_rows": rows}


def test_second_check_passes_the_program(sample_readings):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        problems, record = ref.second_check(w, cfg, sample, fetched)
    assert problems == []
    assert set(record) == {"logit_err_over_rms", "logit_max_err_over_rms",
                           "positions_compared", "positions",
                           "flipped_share", "max_expert_load",
                           "held_row_share", "limits"}
    # the tiny row is 16 positions: all of them are "last"
    assert record["positions"] == 8 * 16
    assert record["positions_compared"] > record["positions"] // 2
    assert 0 < record["logit_err_over_rms"] < ref.LOGIT_ERR_LIMIT
    assert record["flipped_share"] <= ref.FLIP_LIMIT
    # 2 of the 8 experts the tiny router scores are held, in four layers
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (4, 2) and len(fetched["top_i"]) == 4
    for layer, top_i in enumerate(fetched["top_i"]):
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=8)[:2]).all()
    assert record["held_row_share"] == pytest.approx(
        rows.sum() / (4 * 8 * 16 * 3))


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control, monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands rounded to
    # float8, judged as if it were the program. The loss check does not
    # see it. The limits in the file are the chip's, between readings at
    # the published widths; at the tiny sizes both sides read lower, so
    # the limits are set here as there: at the geometric middle of the
    # two readings.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = ref.forward(w, cfg, sample["input_ids"],
                          round_to=getattr(jnp, control),
                          last=ref.LAST_POSITIONS)
        _, record = ref.second_check(w, cfg, sample, as_program(low))
        want = float(ref.loss(w, cfg, sample))
        got = float(ref.loss(w, cfg, sample, round_to=getattr(jnp, control)))
        assert abs(got - want) / want < train.LOSS_REL_TOL
        assert record["logit_err_over_rms"] \
            > 3 * program["logit_err_over_rms"]
        assert record["flipped_share"] > program["flipped_share"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        monkeypatch.setattr(ref, "FLIP_LIMIT", float(np.sqrt(
            record["flipped_share"] * max(program["flipped_share"], 1e-3))))
        problems, _ = ref.second_check(w, cfg, sample, as_program(low))
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 2 and passes == []
    assert "logits differ" in problems[0] and "choices" in problems[1]


def test_a_reference_without_the_window_is_another_model(sample_readings):
    """The no-window control at the tiny sizes (a window of 5 over 16
    positions): four global layers judged as if they were the program
    move the logits by far more than the program's rounding."""
    cfg, w, sample, fetched = sample_readings
    # (larger attention projections, so that what a query sees matters
    # as it does at the published sizes; the program is not rerun: the
    # two references are compared with each other)
    r = np.random.RandomState(0)
    w = dict(w, **{k: (0.3 * r.randn(*v.shape)).astype(np.float32)
                   for k, v in w.items() if k.endswith("_attn_qkv_colp.w")})
    with jax.default_matmul_precision("highest"):
        want = ref.forward(w, cfg, sample["input_ids"],
                           last=ref.LAST_POSITIONS)
        dropped = ref.forward(w, cfg, sample["input_ids"],
                              last=ref.LAST_POSITIONS, no_window=True)
        loss = float(ref.loss(w, cfg, sample))
        loss_dropped = float(ref.loss(w, cfg, sample, no_window=True))
    record = ref.compare(cfg, want, dropped["logits"], dropped["top_i"])
    # the first 5 positions of a row see the same keys either way
    same_head = np.asarray(want["logits"])[:, :5]
    np.testing.assert_allclose(np.asarray(dropped["logits"])[:, :5],
                               same_head, rtol=1e-5, atol=1e-6)
    assert record["flipped_share"] > 0.02 \
        or record["logit_err_over_rms"] > 0.05
    assert loss != loss_dropped


def test_positions_compare_where_the_held_choices_agree():
    """4 experts scored, experts 2..3 held, top 2: a choice that differs
    among experts held elsewhere counts as a flip and still leaves the
    position compared; one that touches a held expert takes it out."""
    cfg = dict(moe_num_primary_experts=2, held_first=2, router_experts=4,
               moe_num_active_primary_experts=2)
    ref_i = np.array([[0, 1], [0, 2], [2, 3], [1, 3]])
    got_i = np.array([[1, 0], [1, 2], [2, 0], [1, 3]])
    #                  same   0 -> 1  3 -> 0  same
    ones = np.ones((1, 4, 5), np.float32)
    want = {"logits": ones, "top_i": [ref_i]}
    got = ones.copy()
    got[0, 1] += 0.5           # compared: both chose expert 2 of the held
    got[0, 2] += 7.0           # not compared: expert 3 was dropped
    rec = ref.compare(cfg, want, got, [got_i])
    assert rec["flipped_share"] == pytest.approx(2 / 8)
    assert (rec["positions"], rec["positions_compared"]) == (4, 3)
    assert rec["logit_err_over_rms"] == pytest.approx(np.sqrt(0.25 / 3))
    assert rec["logit_max_err_over_rms"] == pytest.approx(0.5)


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9, "by_family_s": {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": sum(
            v for k, v in by_scope.items() if k.split("/")[1] == "loss_head")}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk0/attn/rms_norm": 1.0,
    "fwd/blk0/attn/qkv/mul": 5.0,
    "fwd/blk0/attn/core/scaled_dot_product_attention": 9.0,
    "bwd/blk0/attn/core/scaled_dot_product_attention_grad": 20.0,
    "fwd/blk1/attn/rope/rotary_embedding": 1.0,
    "fwd/blk1/attn/swa/scaled_dot_product_attention": 4.0,
    "bwd/blk1/attn/swa/scaled_dot_product_attention_grad": 8.0,
    "bwd/blk3/attn/swa/scaled_dot_product_attention_grad": 7.0,
    "bwd/blk1/attn/out/mul_grad": 4.0,
    "fwd/blk1/moe/router/moe_router": 1.0,
    "fwd/blk1/moe/experts/moe_experts": 6.0,
    "fwd/loss_head/mul": 6.0,
    "fwd/swa/mul": 5.0,             # a scope named swa outside a block
    "opt/adam": 10.0,
}


def test_swa_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    assert read("swa.step_share.train", run) == pytest.approx(4 + 8 + 7)
    # the least time of a step's three windowed calls over 19 ns
    cfg, peaks = full_config(), harness.peaks_for("TPU v5 lite")
    traffic = run.cell["traffic"]           # the tiny cell: 8 x 16
    cost = fs.swa_cost(cfg, traffic["batch"], traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    assert read("swa.roofline.train", run) == pytest.approx(
        100 * least / 19e-9)
    two = scopes_run(BY_SCOPE, traced_steps=2)
    assert read("swa.roofline.train", two) == pytest.approx(
        200 * least / 19e-9)
    # the readers that exist count the window layers as blocks and
    # their experts as such, and none takes this for latent attention
    # or a delta rule
    assert read("step.block_share.train", run) == pytest.approx(
        1 + 5 + 9 + 20 + 1 + 4 + 8 + 7 + 4 + 1 + 6)
    assert read("moe.step_share.train", run) == pytest.approx(7.0)
    for metric in ("mla.step_share.train", "mla.assemble_share.train",
                   "mtp.step_share.train", "gdn.step_share.train",
                   "gdn.scan_share.train", "gdn.scan_roofline.train"):
        assert read(metric, run) is None, metric


def test_readers_report_nothing_for_a_program_without_a_window():
    """A parent's tree, or another family's cell (attention under
    ``attn`` or ``attn/core``, no ``swa`` scope): None, no exception."""
    run = scopes_run({
        "fwd/blk0/attn/scaled_dot_product_attention": 10.0,
        "fwd/blk0/attn/core/scaled_dot_product_attention": 10.0,
        "fwd/blk0/attn/mul": 5.0, "fwd/loss_head/mul": 6.0,
        "opt/adam": 10.0})
    metrics = ("swa.step_share.train", "swa.roofline.train")
    for metric in metrics:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    for metric in metrics:
        assert read(metric, run) is None, metric


def test_full_band_counter_reads_the_band_label():
    from paddle_tpu import flags, monitor
    from paddle_tpu.ops import attention_ops

    run = scopes_run(BY_SCOPE)
    monitor.reset()
    assert read("lower.full_band_swa_calls.train", run) is None
    flags.set_flags({"telemetry": True})
    try:
        def note(family, window):
            labels = {"family": family, "pass": "fwd",
                      "shape": "b1 tq16 tk16 h7 kv1 dh8", "tile": "",
                      "replicated_over": ""}
            if window:
                labels["shape"] += f" w{window}"
                labels["band"] = "skip" if family == "bhtd" else "dense"
            attention_ops._M_DISPATCH.inc(labels=labels)

        note("bhtd", None)          # a plain call: no band, not counted
        assert read("lower.full_band_swa_calls.train", run) is None
        note("bhtd", 5)
        note("bhtd", 5)
        assert read("lower.full_band_swa_calls.train", run) == 0
        note("dense", 5)
        assert read("lower.full_band_swa_calls.train", run) == 1
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_a_traced_tiny_run_walks_no_expert_buffer_whole(monkeypatch,
                                                        tmp_path):
    """The cell holds a share of its experts (experts 0-7; the file
    says ``held_first`` 0 since PR 54, which lifted the pin that held
    the files with that key to two), so it is on the list of
    ``lower.whole_buffer_moe_calls.train`` and its line carries the
    metric, which reads 0: every pass of the held layers is a loop over
    live rows."""
    import json

    from paddle_tpu import monitor

    metric = "lower.whole_buffer_moe_calls.train"
    assert tiny.listed_as(metric, "count", "lower", "program_counter",
                          "Program lowering", CELL)
    assert full_config()["held_first"] == 0
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9})
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    monitor.reset()
    cell = tiny.train_cell(CELL)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert line["metrics"][metric]["value"] == 0
    assert read(metric, run) == 0
    # (without a TPU the windowed calls are the dense composition's)
    assert line["metrics"]["lower.full_band_swa_calls.train"]["value"] > 0
    rows = monitor.snapshot()["pt_moe_rows_dispatch_total"]["values"]
    assert rows and all(
        r["labels"]["form"] in ("windowed", "windowed|by_token")
        for r in rows)
    assert 0 < run.check["second"]["held_row_share"] < 1
    monitor.reset()
