"""What the Laguna family brings of its own: its configuration's cut
against the catalog's numbers, its FLOP and band counts by kind (each
kind at its own head count) by hand at the cell's sizes, its second
check against a lower-precision control and the mechanism controls, and
the readers of ``swa.family_roofline.train``, ``attn.gate_share.train``
and ``attn.core_share.train``."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_laguna as fl
from perf import harness, models
from perf.kinds import train
from perf.reference import laguna as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "laguna-train-s8192", "laguna-xs-2"
NEW = ("swa.family_roofline.train", "attn.gate_share.train",
       "attn.core_share.train")


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import laguna as M

    cfg, pub = full_config(), M.LagunaConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (40, 5), "vocab_size": (100352, 12544)}
    for key, value in vars(pub).items():
        if key == "held_experts":
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 256; the chip holds experts 0..15
    assert pcfg.num_experts == 256 == cfg["reduced_from"]["num_experts"] \
        == cfg["router_experts"]
    assert pcfg.held_experts == (0, 16) and cfg["num_experts"] == 16
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    # the widths, as published
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["moe_routed_scaling_factor"]) \
        == (2048, 8192, 128, 8, 512, 512, 8, 512, 2.5)
    # the per-layer lists as published (40 entries); the five layers
    # that are built read the first five: full, three windows, full
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) \
        == len(cfg["num_attention_heads_per_layer"]) == 40
    assert [pcfg.heads(i) for i in range(5)] == [48, 64, 64, 64, 48]
    assert [pcfg.window(i) for i in range(5)] == [None, 512, 512, 512, None]
    assert [pcfg.dense(i) for i in range(5)] == [True] + [False] * 4
    assert pcfg.rope(0)[:2] == (500000.0, 64) and pcfg.rope(0)[2][
        "attention_factor"] == pytest.approx(1.41589, abs=1e-5)
    assert pcfg.rope(1) == (10000.0, 128, None)
    assert fl.layer_calls(cfg) == [(48, None), (64, 512), (64, 512),
                                   (64, 512), (48, None)]
    assert ref.ALPHA == pub.router_aux_loss_coef
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS
    for key in ("the_cut", "deployment"):
        assert len(cfg[key]) > 200, key
    assert {"gate", "router", "QK-norm", "auxiliary loss", "packing",
            "training precision", "initialisation", "storage"} \
        <= set(cfg["assumed"])
    traffic = harness.load_json("perf", "workloads", f"{CELL}.json")["traffic"]
    assert (traffic["batch"], traffic["seq_len"], traffic["feeds"]) \
        == (1, 8192, 4) and traffic["real_len"] == [8192, 8192]


def test_every_number_of_the_catalogs_config_stands_under_its_key():
    """The keys of the published ``config.json`` as the catalog beside
    the model-configs guide holds them (written out here: the tests read
    no file outside the repo), but the three that ``reduced`` names."""
    cfg = full_config()
    published = dict(
        model_type="laguna", vocab_size=100352, hidden_size=2048,
        intermediate_size=8192, num_hidden_layers=40,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=262144, attention_bias=False,
        rms_norm_eps=1e-6, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        tie_word_embeddings=False, gating=True, sliding_window=512,
        moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
        moe_routed_scaling_factor=2.5)
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["reduced_from"][key] == value, key
        else:
            assert cfg[key] == value, key
    rp = cfg["rope_parameters"]
    assert rp["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert rp["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert cfg["layer_types"] == (["full_attention"]
                                  + ["sliding_attention"] * 3) * 10
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 39


def test_the_cut_counts_its_parameters_as_built():
    """490.3M parameters, 7.84 GB at 16 bytes, by kind of layer."""
    d, dh = 2048, 128
    full = d * ((48 + 16) * dh + 48) + 48 * dh * d
    window = d * ((64 + 16) * dh + 64) + 64 * dh * d
    assert (full, window) == (29_458_432, 37_879_808)
    dense, expert, router = 3 * d * 8192, 3 * d * 512, d * 256
    layer0 = full + dense + 2 * d
    sparse = router + expert + 16 * expert + 2 * d
    total = (layer0 + 3 * (window + sparse) + full + sparse
             + 2 * 12544 * d + d)
    assert total == pytest.approx(490.3e6, rel=1e-4)
    assert 16 * total == pytest.approx(7.84e9, rel=1e-3)
    assert "490.3" in full_config()["the_cut"]


# --- the FLOPs ----------------------------------------------------------------


@pytest.mark.parametrize("t,window", [(16, 5), (16, 16), (16, 40), (64, 1),
                                      (300, 128), (16, None)])
def test_visible_pairs_against_a_brute_force_count(t, window):
    p, s = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (s <= p) & (p - s < (window or t))
    assert fl.visible_pairs(t, window) == seen.sum()


def test_band_of_the_cell_by_elements_and_by_blocks():
    """A window equal to one block: every row of query blocks but the
    first walks TWO key blocks, both cut by an edge of the band."""
    t, w = 8192, 512
    assert fl.visible_pairs(t, w) == 4_063_488            # 4.06M
    assert fl.visible_pairs(t, None) == 33_558_528        # 33.56M
    j, kk = np.arange(16)[:, None], np.arange(16)[None, :]
    live = (kk <= j) & (j * 512 - (kk * 512 + 511) < w)
    assert live.sum() == 31
    assert fl.visible_pairs(t, w) / (31 * 512 * 512) \
        == pytest.approx(0.50, abs=0.005)
    # the issue's count: 8192 x 1024 pairs a head, 48%
    assert fl.visible_pairs(t, w) / (8192 * 1024) \
        == pytest.approx(0.484, abs=0.001)


def test_train_flops_by_hand_at_the_cells_sizes():
    """19.4 TFLOP a step: 13.2 of projections, experts and head, 6.1 of
    attention, two triangles at 48 heads and three bands at 64."""
    cfg = full_config()
    d, tok, t = 2048, 8192, 8192
    full = 2 * d * ((48 + 16) * 128 + 48) + 2 * 48 * 128 * d
    window = 2 * d * ((64 + 16) * 128 + 64) + 2 * 64 * 128 * d
    dense = 6 * d * 8192
    # router over all 256, the shared expert, 8 x 16 / 256 of a row on
    # the held experts
    moe = 2 * d * 256 + 6 * d * 512 + 0.5 * 6 * d * 512
    head = 2 * d * 12544
    tri, band = fl.visible_pairs(t, None), fl.visible_pairs(t, 512)
    attn = 12.0 * 128 * (2 * 48 * tri + 3 * 64 * band)
    matmul = 3.0 * tok * (full + dense + 3 * (window + moe) + full + moe
                          + head)
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, t) == pytest.approx(matmul + attn)
    assert matmul == pytest.approx(13.25e12, rel=2e-3)
    assert attn == pytest.approx(6.15e12, rel=2e-3)
    assert matmul + attn == pytest.approx(19.4e12, rel=2e-3)
    assert attn / (matmul + attn) == pytest.approx(0.32, abs=0.005)
    # five triangles at one head count would count attention twice over
    assert 12.0 * 128 * 48 * 5 * tri / attn == pytest.approx(2.0, abs=0.02)
    # every expert held would be the whole k a token
    all_held = fl.laguna_train_flops(dict(cfg, num_experts=256), 1, t)
    assert all_held - (matmul + attn) == pytest.approx(
        3.0 * tok * 4 * (8 - 0.5) * 6 * d * 512)


def test_attention_and_swa_cost_by_kind():
    cfg = full_config()
    t = 8192
    cost = models.family(cfg).attention_cost(cfg, 1, t)
    swa = fl.swa_cost(cfg, 1, t)
    # the full layers' calls: what is not a window layer's
    full = {k: cost[k] - swa[k] for k in cost}
    assert (cost["calls"], swa["calls"], full["calls"]) == (10, 6, 4)
    band, tri = fl.visible_pairs(t, 512), fl.visible_pairs(t, None)
    assert swa["flops"] == 3 * 12.0 * 64 * 128 * band       # 1.20 TFLOP
    assert full["flops"] == 2 * 12.0 * 48 * 128 * tri       # 4.95 TFLOP
    assert swa["flops"] == pytest.approx(1.198e12, rel=1e-3)
    assert full["flops"] == pytest.approx(4.948e12, rel=1e-3)
    # six tensors of the call's query heads and six of the 8 key/value
    assert swa["bytes"] == 3 * 6 * (64 + 8) * t * 128 * 2
    assert full["bytes"] == 2 * 6 * (48 + 8) * t * 128 * 2
    peaks = harness.peaks_for("TPU v5 lite")
    # a window layer's calls: 2.03 ms of FLOPs against 1.11 ms of bytes
    assert swa["flops"] / peaks["bf16_flops_per_s"] / 3 \
        == pytest.approx(2.03e-3, rel=1e-2)
    assert swa["bytes"] / peaks["hbm_bytes_per_s"] / 3 \
        == pytest.approx(1.11e-3, rel=1e-2)


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


def as_program(low, held=4, scored=16):
    rows = [np.bincount(np.asarray(t).ravel(), minlength=scored)[:held]
            for t in low["top_i"]]
    return {"last_logits": low["logits"], "top_i": low["top_i"],
            "expert_rows": rows}


def test_tiny_sizes_have_unequal_heads_a_short_window_and_half_a_head():
    cfg = tiny.config(CONFIG)
    pcfg = models.family(cfg).program_config(cfg)
    heads = [pcfg.heads(i) for i in range(5)]
    assert heads == [6, 8, 8, 8, 6] and len(set(heads)) == 2
    assert pcfg.sliding_window < tiny.train_cell(CELL)["traffic"]["seq_len"]
    theta, rotary_dim, scaling = pcfg.rope(0)
    assert rotary_dim * 2 == pcfg.head_dim and scaling["rope_type"] == "yarn"
    # yarn's ramp lies inside the tiny head's four frequencies
    f = ref.inv_freq(rotary_dim, scaling)
    plain = ref.inv_freq(rotary_dim, scaling, no_yarn=True)
    np.testing.assert_allclose(f / plain, 1 - 0.75 * np.array(
        [0, 0.2, 0.4, 0.6]), rtol=1e-12)


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
    # 4 of the 16 experts the tiny router scores are held, in the four
    # expert layers (layer 0 is dense and has no router)
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (4, 4) and len(fetched["top_i"]) == 4
    for layer, top_i in enumerate(fetched["top_i"]):
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=16)[:4]).all()
    assert record["held_row_share"] == pytest.approx(
        rows.sum() / (4 * 8 * 16 * 3))


# the control's seeds at the tiny sizes: the weights are drawn anew from
# each (perf/tools/laguna_logits_control.py does the same at the
# published widths on the chip)
CONTROL_SEEDS = (3, 2 ** 31 + 11, 77)


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
@pytest.mark.parametrize("control", ["bfloat16", "float8_e4m3fn"])
def test_second_check_fails_a_lower_precision_reference_and_passes_the_program(
        control, seed, monkeypatch):
    """The reference itself with every weight matmul's operands rounded
    to a lower precision, judged as if it were the program, over the
    control's seeds: float8_e4m3fn, the nearest precision below the
    configuration's bf16, as on the chip; and bfloat16 against the
    program run in FLOAT32 (no AMP), the nearest below that. The loss
    check does not see either. The limits in the file are the chip's,
    between readings at the published widths; at the tiny sizes both
    sides read lower, so the limits are set here as there: at the
    geometric middle of the two readings."""
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)
    _, startup, evalp, _, model = models.build_train(cfg, seed=seed)
    if control == "bfloat16":
        evalp._amp = False
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    sample = train.sample_of(
        fam.feeds(cfg, tiny.train_cell(CELL)["traffic"], seed)[0])
    fetch, shape = jax.tree.flatten({k: model[k] for k in fam.CHECK_FETCH})
    w = {k: np.asarray(v) for k, v in weights_from_scope(scope).items()}
    fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
        evalp, feed=sample, fetch_list=fetch, scope=scope)])
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
        assert record["flipped_share"] >= program["flipped_share"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        problems, _ = ref.second_check(w, cfg, sample, as_program(low))
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert any("logits differ" in p for p in problems) and passes == []


@pytest.mark.parametrize("control", ["no_window", "no_gate", "no_yarn"])
def test_a_reference_without_one_mechanism_is_another_model(sample_readings,
                                                            control):
    """The mechanism controls at the tiny sizes: the reference with the
    window dropped, every gate at 1 or yarn dropped, judged as if it
    were the program, moves the logits by far more than the program's
    rounding."""
    cfg, w, sample, fetched = sample_readings
    # (larger attention projections, so that what a query sees matters
    # as it does at the published sizes; the program is not rerun: the
    # two references are compared with each other)
    r = np.random.RandomState(0)
    w = dict(w, **{k: (0.3 * r.randn(*v.shape)).astype(np.float32)
                   for k, v in w.items() if k.endswith("_attn_qkvg_colp.w")})
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(
            {k: v for k, v in sample_readings[1].items()}, cfg, sample,
            fetched)
        want = ref.forward(w, cfg, sample["input_ids"],
                           last=ref.LAST_POSITIONS)
        dropped = ref.forward(w, cfg, sample["input_ids"],
                              last=ref.LAST_POSITIONS, **{control: True})
        loss = float(ref.loss(w, cfg, sample))
        loss_dropped = float(ref.loss(w, cfg, sample, **{control: True}))
    record = ref.compare(cfg, want, dropped["logits"], dropped["top_i"])
    assert record["flipped_share"] > 0.02 \
        or record["logit_err_over_rms"] > 0.05
    assert record["logit_err_over_rms"] \
        > 5 * program["logit_err_over_rms"] \
        or record["flipped_share"] > 5 * max(program["flipped_share"], 1e-3)
    assert loss != loss_dropped


def test_positions_compare_where_the_held_choices_agree():
    """4 experts scored, experts 2..3 held, top 2: a choice that differs
    among experts held elsewhere counts as a flip and still leaves the
    position compared; one that touches a held expert takes it out."""
    cfg = dict(num_experts=2, held_first=2, router_experts=4,
               num_experts_per_tok=2)
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


def test_reference_imports_nothing_of_the_program():
    import perf.reference.laguna as module

    src = open(module.__file__).read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert "import paddle" not in src


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1, config=None):
    run = tiny.make_run(tiny.train_cell(CELL), config or full_config(),
                        traced=True)
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
    "fwd/blk0/attn/rope/rotary_embedding": 2.0,
    "fwd/blk0/attn/core/scaled_dot_product_attention": 9.0,
    "bwd/blk0/attn/core/scaled_dot_product_attention_grad": 20.0,
    "fwd/blk0/attn/gate/elementwise_mul": 1.5,
    "bwd/blk0/attn/gate/elementwise_mul_grad": 2.5,
    "fwd/blk0/mlp/mul": 6.0,
    "fwd/blk1/attn/rope/rotary_embedding": 1.0,
    "fwd/blk1/attn/swa/scaled_dot_product_attention": 4.0,
    "bwd/blk1/attn/swa/scaled_dot_product_attention_grad": 8.0,
    "bwd/blk3/attn/swa/scaled_dot_product_attention_grad": 7.0,
    "fwd/blk1/attn/gate/sigmoid": 0.5,
    "bwd/blk1/attn/out/mul_grad": 4.0,
    "fwd/blk1/moe/router/moe_router": 1.0,
    "fwd/blk1/moe/experts/moe_experts": 6.0,
    "fwd/loss_head/mul": 6.0,
    "fwd/gate/mul": 5.0,            # a scope named gate outside a block
    "fwd/blk2/moe/gate/mul": 3.0,   # and one that is not the attention's
    "opt/adam": 10.0,
}


def test_the_three_are_entries_on_the_cell():
    assert tiny.listed_as(NEW[0], "%", "higher", "device_trace", "Kernels",
                          CELL)
    for metric in NEW[1:]:
        assert tiny.listed_as(metric, "%", "lower", "program_span",
                              "Program lowering", CELL)
    # the family's own window count keeps the cell off the reader that
    # counts one head count for every layer
    assert CELL not in tiny.cells_named(tiny.BENCH, "swa.roofline.train")
    for metric in ("swa.step_share.train", "lower.full_band_swa_calls.train",
                   "lower.xla_rope_calls.train", "rope.step_share.train",
                   "moe.step_share.train",
                   "lower.whole_buffer_moe_calls.train"):
        assert CELL in tiny.cells_named(tiny.BENCH, metric), metric


def test_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    assert read("swa.step_share.train", run) == pytest.approx(4 + 8 + 7)
    assert read("attn.core_share.train", run) == pytest.approx(9 + 20)
    assert read("attn.gate_share.train", run) == pytest.approx(1.5 + 2.5 + .5)
    # the least time of a step's three windowed calls, at 64 heads, over
    # 19 ns
    cfg, peaks = full_config(), harness.peaks_for("TPU v5 lite")
    traffic = run.cell["traffic"]           # the tiny cell: 8 x 16
    cost = fl.swa_cost(cfg, traffic["batch"], traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    assert read("swa.family_roofline.train", run) == pytest.approx(
        100 * least / 19e-9)
    two = scopes_run(BY_SCOPE, traced_steps=2)
    assert read("swa.family_roofline.train", two) == pytest.approx(
        200 * least / 19e-9)
    # the dense layer's MLP counts as a block, not as experts
    assert read("moe.step_share.train", run) == pytest.approx(1 + 6 + 3)
    assert read("rope.step_share.train", run) == pytest.approx(3.0)


def test_family_roofline_finds_the_cost_by_the_families_name():
    """The reader imports ``perf.flops_<family>`` of the run's
    configuration: another family's ``swa_cost`` for that family's run,
    None for one that has none."""
    reader = harness.reader_for("swa.family_roofline.train")
    small = harness.load_json("perf", "configs", "smallthinker-21b-a3b.json")
    run = scopes_run(BY_SCOPE, config=small)
    from perf import flops_smallthinker

    assert reader.family_swa_cost(run) is flops_smallthinker.swa_cost
    assert reader.read(run) == pytest.approx(
        read("swa.roofline.train", run))
    for family in ("olmoe", "no_such_family", None):
        run = scopes_run(BY_SCOPE, config=dict(full_config(), family=family))
        assert reader.family_swa_cost(run) is None
        assert reader.read(run) is None


def test_readers_report_nothing_without_their_scopes():
    """A parent's tree, or another family's cell: None, no exception."""
    run = scopes_run({
        "fwd/blk0/attn/scaled_dot_product_attention": 10.0,
        "fwd/blk0/attn/mul": 5.0, "fwd/loss_head/mul": 6.0,
        "opt/adam": 10.0})
    for metric in NEW:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    for metric in NEW:
        assert read(metric, run) is None, metric


def test_a_traced_tiny_run_prints_the_cells_line(monkeypatch, tmp_path):
    """The cell at the family's tiny sizes through the train loop with
    the trace on: correct, the counters' metrics in the line (without a
    TPU the windowed calls are the dense composition's and every rotary
    call XLA's), no expert buffer walked whole, the dispatch rows of the
    windowed calls carrying their heads."""
    from paddle_tpu import monitor

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
    assert line["metrics"]["lower.whole_buffer_moe_calls.train"]["value"] == 0
    assert line["metrics"]["lower.full_band_swa_calls.train"]["value"] > 0
    # the step's and the eval clone's rotary calls, all XLA's here: 5
    # layers x (fwd + bwd), and 5 forward in each of the eval clone's two
    # lowerings (the loss; the second check's fetches). On the chip the
    # three window layers' go to the kernels and the full layers' stay:
    # 2 x (2 + 2) = 8
    assert line["metrics"]["lower.xla_rope_calls.train"]["value"] == 20
    rows = monitor.snapshot()["pt_rope_dispatch_total"]["values"]
    assert {r["labels"]["scaling"] for r in rows} == {"yarn", "none"}
    attn = monitor.snapshot()["pt_attention_dispatch_total"]["values"]
    assert {r["labels"]["heads"] for r in attn if r["labels"].get("band")} \
        == {"8"}
    assert 0 < run.check["second"]["held_row_share"] < 1
    monitor.reset()
