"""What the SDAR family brings of its own: its configuration's cut
against the catalog's numbers, its feeds (the noise is drawn on the host
from the seed), its FLOP and pair counts against a brute-force count of
the mask, its second check against a lower-precision control and a
control whose mask leaks, and the readers of ``bd.step_share.train``,
``bd.live_pair_share.train`` and ``lower.dense_bd_calls.train``."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from perf import flops_sdar as fs
from perf import harness, models
from perf.kinds import train
from perf.reference import sdar as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "sdar-train-s4096", "sdar-30b-a3b"
NEW = ("bd.step_share.train", "bd.live_pair_share.train",
       "lower.dense_bd_calls.train")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


@pytest.fixture(autouse=True)
def nothing_counted_is_left_behind():
    """A traced run turns telemetry on and its dispatch rows stay in the
    process: the next file of this worker must not read them."""
    from paddle_tpu import flags

    yield
    flags.set_flags({"telemetry": False})
    monitor.reset()


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import sdar as M

    cfg, pub = full_config(), M.SdarConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (48, 5), "vocab_size": (151936, 18992),
           "mask_token_id": (151669, 18991)}
    for key, value in vars(pub).items():
        if key == "held_experts":
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut and key in cfg["reduced_from"]:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 128; the chip holds experts 0..15
    assert pcfg.num_experts == 128 == cfg["reduced_from"]["num_experts"] \
        == cfg["router_experts"]
    assert pcfg.held_experts == (0, 16) and cfg["num_experts"] == 16
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    # the widths, as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) \
        == (2048, 32, 4, 128, 768, 8, 1000000, 1e-6)
    # the mask id is the last id of the held slice, the data below it
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    assert cfg["block_length"] == 4
    for said in ("block length", "schedule", "no shift", "mask id"):
        assert said in cfg["assumed"], said
    assert "8 chips" in cfg["deployment"]
    assert ref.AUX_COEF == pub.router_aux_loss_coef
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS
    assert ref.IGNORE_INDEX == M.IGNORE_INDEX


def test_configuration_file_holds_the_catalogs_numbers():
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(e for e in map(json.loads, open(catalog))
                 if e["name"] == "SDAR-30B-A3B-Chat")
    cfg = full_config()
    assert cfg["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])


# --- the feeds ---------------------------------------------------------------


def test_feeds_repeat_for_a_seed_differ_across_seeds_and_follow_the_schedule():
    cfg = full_config()
    traffic = dict(harness.load_json(
        "perf", "workloads", f"{CELL}.json")["traffic"], seq_len=2048,
        real_len=[2048, 2048])
    fam = models.family(cfg)
    a, again, other = (fam.feeds(cfg, traffic, s)
                       for s in (2 ** 31 + 5, 2 ** 31 + 5, 9))
    assert len(a) == traffic["feeds"]
    for x, y in zip(a, again):
        assert all((x[k] == y[k]).all() for k in x)
    assert any((x["input_ids"] != y["input_ids"]).any()
               for x, y in zip(a, other))
    half, block, mask = 2048, cfg["block_length"], cfg["mask_token_id"]
    for f in a:
        assert f["input_ids"].shape == (1, 2 * half)
        assert f["labels"].shape == f["loss_weight"].shape == (1, half)
        assert fam.real_tokens(f) == half      # the DATA tokens of the row
        xt, x0 = f["input_ids"][:, :half], f["input_ids"][:, half:]
        masked = xt == mask
        assert (x0 < mask).all() and x0.max() > mask - 200
        # labels are ignore_index exactly where xt is not the mask id
        assert ((f["labels"] == ref.IGNORE_INDEX) == ~masked).all()
        assert (f["labels"][masked] == x0[masked]).all()
        assert (xt[~masked] == x0[~masked]).all()
        # weights are 1 / p, one p a block
        p = 1.0 / f["loss_weight"].reshape(-1, block)
        assert (p == p[:, :1]).all() and p.min() >= 1e-3 and p.max() <= 1
        # a block's masked share follows its p: by thirds of the schedule
        share = masked.reshape(-1, block).mean(1)
        for lo, hi in ((0, 1 / 3), (1 / 3, 2 / 3), (2 / 3, 1)):
            at = (p[:, 0] >= lo) & (p[:, 0] < hi)
            assert at.sum() > 100
            assert abs(share[at].mean() - p[at, 0].mean()) < 0.05
        # p is uniform: half the row masked, in expectation
        assert abs(masked.mean() - 0.5) < 0.05


# --- the FLOPs ----------------------------------------------------------------


@pytest.mark.parametrize("t,block", [(16, 4), (24, 2), (64, 16), (48, 48)])
def test_visible_pairs_against_a_brute_force_count_of_the_mask(t, block):
    p, s = jnp.arange(2 * t)[:, None], jnp.arange(2 * t)[None, :]
    seen = np.asarray(ref.visible(p, s, t, block))
    assert fs.visible_pairs(t, block) == seen.sum() == t * t + block * t
    # no clean query sees a noised key
    assert not seen[t:, :t].any()


def test_attention_and_step_counts_by_hand_at_the_cells_sizes():
    cfg = full_config()
    t = 4096
    assert fs.visible_pairs(t, 4) == 16_793_600           # 16.79M
    cost = fs.attention_cost(cfg, 1, t)
    # 12 x pairs x heads x head_dim a layer, five layers
    assert cost["flops"] == 5 * 12.0 * 16_793_600 * 32 * 128
    assert cost["calls"] == 10
    assert cost["bytes"] == 5 * 6 * (32 + 4) * 8192 * 128 * 2
    assert fs.bd_attention_cost(cfg, 1, t, 1)["flops"] * 5 == cost["flops"]
    d, f = 2048, 768
    kv = 2 * d * 2 * 4 * 128
    proj = 2 * d * 32 * 128 * 2 + kv
    moe = 2 * d * 128 + 8 * 16 / 128 * 3 * 2 * d * f
    positions = (4 * 8192 + 4096) * (proj + moe) + 4096 * kv
    head = 2048 * 2 * d * 18992
    want = 3.0 * (positions + head) + cost["flops"]
    assert fs.sdar_train_flops(cfg, 1, t) == pytest.approx(want, rel=1e-12)
    # about 10 TFLOP a step, attention two fifths of it
    assert want == pytest.approx(9.93e12, rel=0.01)
    assert cost["flops"] / want == pytest.approx(0.415, abs=0.01)
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, t) == want
    assert fam.attention_cost(cfg, 1, t) == cost


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


def test_the_start_state_is_a_converted_checkpoints(sample_readings):
    cfg, w, _, _ = sample_readings
    fam = models.family(cfg)
    table = w["sdar_tok_emb.w"]
    assert table.std() == pytest.approx(fam.TABLE_STD, rel=0.1)
    mask_row = table[cfg["mask_token_id"]]
    assert mask_row.std() == pytest.approx(fam.TABLE_STD, rel=0.4)
    gains = np.concatenate([v.ravel() for k, v in w.items()
                            if k.endswith(("_qnorm.scale", "_knorm.scale"))])
    assert gains.size == 2 * cfg["num_hidden_layers"] * cfg["head_dim"]
    assert gains.mean() == pytest.approx(fam.QK_GAIN[0], abs=0.1)
    assert 0.05 < gains.std() < 0.4
    # every router's columns are orthogonal to the mask token's row, and
    # of the family's length (ROUTER_STD sqrt(d), less one direction of 32)
    routers = [v for k, v in w.items() if k.endswith("_moe_router.w")]
    assert len(routers) == cfg["num_hidden_layers"]
    for wr in routers:
        assert np.abs(mask_row @ wr).max() < 1e-6
        assert wr.std() == pytest.approx(fam.ROUTER_STD, rel=0.25)
        lengths = np.linalg.norm(wr, axis=0)
        assert lengths == pytest.approx(
            fam.ROUTER_STD * cfg["hidden_size"] ** 0.5, rel=1e-4)
    # nothing else of the startup program's stays in the scope
    assert not [k for k in w if "tmp" in k or "assign" in k]


def test_second_check_passes_the_program(sample_readings):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        problems, record = ref.second_check(w, cfg, sample, fetched)
    assert problems == []
    assert set(record) == {"logit_err_over_rms", "logit_max_err_over_rms",
                           "positions_compared", "positions",
                           "flipped_share", "max_expert_load",
                           "held_row_share", "limits"}
    # the tiny row is 16 data tokens: all of its masked ones are compared
    masked = sample["labels"] != ref.IGNORE_INDEX
    assert record["positions"] == masked.sum() > 8
    assert record["positions_compared"] > record["positions"] // 2
    assert 0 < record["logit_err_over_rms"] < ref.LOGIT_ERR_LIMIT
    assert record["flipped_share"] <= ref.FLIP_LIMIT
    # 2 of the 8 experts the tiny router scores are held, in every
    # layer, and ALL 2L positions are routed
    layers = cfg["num_hidden_layers"]
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (layers, 2) and len(fetched["top_i"]) == layers
    for layer, top_i in enumerate(fetched["top_i"]):
        assert top_i.shape == (8 * 32, 3)
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=8)[:2]).all()
    assert record["held_row_share"] == pytest.approx(
        rows.sum() / (layers * 8 * 32 * 3))


def test_the_compared_positions_are_each_rows_last_masked_ones():
    labels = np.full((2, 12), ref.IGNORE_INDEX)
    labels[0, [1, 4, 5, 9, 11]] = 7
    labels[1, [2, 3]] = 7
    at = ref.compared_positions(labels, last=8, n=3)
    # row 0's last 8 positions are 4 .. 11: masked 4, 5, 9, 11, the last
    # three of them taken; row 1 has none among its last 8
    assert at.shape == (2, 8)
    assert (np.flatnonzero(at[0]) + 4 == [5, 9, 11]).all() and not at[1].any()
    assert ref.compared_positions(labels, last=12, n=64).sum() == 7


# the control's seeds at the tiny sizes: the weights are drawn anew from
# each (perf/tools/sdar_logits_control.py does the same at the published
# widths on the chip)
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
    sides read otherwise, so the limits are set here as there: at the
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
        assert abs(got - want) / want < 10 * train.LOSS_REL_TOL
        assert record["flipped_share"] >= program["flipped_share"]
        monkeypatch.setattr(ref, "FLIP_LIMIT", 1.0)
        if record["positions_compared"]:
            assert record["logit_err_over_rms"] \
                > 3 * program["logit_err_over_rms"]
            monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
                record["logit_err_over_rms"]
                * program["logit_err_over_rms"])))
        # (2 of 8 experts held, 3 a position, five layers: a control may
        # leave no position whose held choices all agree, and fails by that)
        problems, _ = ref.second_check(w, cfg, sample, as_program(low))
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert any("logits differ" in p or "nothing to compare" in p
               for p in problems) and passes == []


def test_a_mask_that_lets_the_clean_half_see_the_noised_half_is_not_correct(
        sample_readings):
    """The leak control at the tiny sizes: the reference whose clean
    queries see the noised keys of their own and earlier blocks, judged
    as if it were the program, moves the logits by far more than the
    program's rounding (the chip's run of it, at the published widths,
    is perf/tools/sdar_logits_control.py --mechanism-seeds)."""
    cfg, w, sample, fetched = sample_readings
    # (larger attention projections, so that what a query sees matters
    # as it does at the published sizes; the program is not rerun: the
    # two references are compared with each other)
    r = np.random.RandomState(0)
    big = dict(w, **{k: (0.3 * r.randn(*v.shape)).astype(np.float32)
                     for k, v in w.items()
                     if k.endswith(("_attn_qkv_colp.w", "_attn_out_rowp.w"))})
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        want = ref.forward(big, cfg, sample["input_ids"],
                           last=ref.LAST_POSITIONS)
        leaked = ref.forward(big, cfg, sample["input_ids"],
                             last=ref.LAST_POSITIONS, leak=True)
        loss = float(ref.loss(big, cfg, sample))
        loss_leaked = float(ref.loss(big, cfg, sample, leak=True))
    record = ref.compare(cfg, sample["labels"], want, leaked["logits"],
                         leaked["top_i"])
    assert record["flipped_share"] > 0.02 \
        or record["logit_err_over_rms"] > 0.05
    assert record["logit_err_over_rms"] \
        > 5 * program["logit_err_over_rms"] \
        or record["flipped_share"] > 5 * max(program["flipped_share"], 1e-3)
    assert loss != loss_leaked
    # the noised half's own logits see the leak only through the clean
    # half's keys and values: a leak into the FIRST clean block alone
    # leaves block 0 of the noised half as it was
    first = np.asarray(want["logits"])[:, :cfg["block_length"]]
    assert np.allclose(
        first, np.asarray(leaked["logits"])[:, :cfg["block_length"]],
        atol=1e-4)


def test_positions_compare_where_the_held_choices_agree():
    """4 experts scored, experts 2..3 held, top 2, a row of 2 data tokens
    (4 positions): a choice that differs among experts held elsewhere
    counts as a flip and still leaves the position compared; one that
    touches a held expert takes it out; the clean half's positions are
    routed and counted, and never compared."""
    cfg = dict(num_experts=2, held_first=2, router_experts=4,
               num_experts_per_tok=2)
    ref_i = np.array([[0, 1], [2, 3], [0, 2], [1, 3]])
    got_i = np.array([[1, 0], [2, 0], [1, 2], [1, 3]])
    #                  same   3 -> 0  0 -> 1  same
    labels = np.array([[7, 7]])
    ones = np.ones((1, 2, 5), np.float32)
    want = {"logits": ones, "top_i": [ref_i]}
    got = ones.copy()
    got[0, 0] += 0.5           # compared: the same experts
    got[0, 1] += 7.0           # not compared: expert 3 was dropped
    rec = ref.compare(cfg, labels, want, got, [got_i])
    assert rec["flipped_share"] == pytest.approx(2 / 8)
    assert (rec["positions"], rec["positions_compared"]) == (2, 1)
    assert rec["logit_err_over_rms"] == pytest.approx(np.sqrt(0.25))
    assert rec["logit_max_err_over_rms"] == pytest.approx(0.5)


def test_reference_imports_nothing_of_the_program():
    import perf.reference.sdar as module

    src = open(module.__file__).read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert "import paddle" not in src


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, busy=100.0):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": 1}
    run.trace = {"devices": 1, "busy_s": busy / 1e9, "by_family_s": {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": 0.0}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


def test_the_three_are_entries_on_the_cell():
    assert tiny.listed_as(NEW[0], "%", "lower", "program_span",
                          "Program lowering", CELL)
    assert tiny.listed_as(NEW[1], "%", "higher", "program_counter",
                          "Kernels", CELL)
    assert tiny.listed_as(NEW[2], "count", "lower", "program_counter",
                          "Program lowering", CELL)
    # every attention call of the cell is block-masked: the attn family's
    # roofline IS the mask's, and the cell is on no window's list
    for metric in ("train_attn_roofline", "attn.time_share.train",
                   "step.mfu.train",
                   "lower.xla_rope_calls.train", "rope.step_share.train",
                   "moe.step_share.train", "lower.ragged_moe_calls.train",
                   "lower.whole_buffer_moe_calls.train",
                   "lower.xla_embed_grad_calls.train",
                   "embed.grad_share.train", "step.block_share.train"):
        assert CELL in tiny.cells_named(tiny.BENCH, metric), metric
    # (not on lower.split_bwd_attn_calls.train: that list's accepted test
    # drives every cell on it through the kernels at 32 positions, which
    # no tile cuts in two halves; the reader itself is read below)
    for metric in ("moe.gmm_roofline.train", "swa.step_share.train",
                   "lower.split_bwd_attn_calls.train",
                   "swa.roofline.train", "lower.full_band_swa_calls.train",
                   "swa.family_roofline.train"):
        assert CELL not in tiny.cells_named(tiny.BENCH, metric), metric


def test_the_step_share_sums_the_bd_scopes_and_nothing_else():
    run = scopes_run({
        "fwd/blk0/attn/qkv/mul": 5.0,
        "fwd/blk0/attn/rope/rotary_embedding": 2.0,
        "fwd/blk0/attn/bd/scaled_dot_product_attention": 9.0,
        "bwd/blk0/attn/bd/scaled_dot_product_attention_grad": 20.0,
        "bwd/blk3/attn/bd/copy": 1.0,
        "fwd/blk1/attn/core/scaled_dot_product_attention": 4.0,
        "fwd/bd/mul": 5.0,              # a scope named bd outside a block
        "fwd/blk2/moe/bd/mul": 3.0,     # and one that is not the attention's
        "opt/adam": 10.0})
    assert read(NEW[0], run) == pytest.approx(30.0)
    # a program without the scope (the parent, any other family): nothing
    assert read(NEW[0], scopes_run({"fwd/blk0/attn/core/sdpa": 4.0})) is None
    untraced = tiny.make_run(tiny.train_cell(CELL), full_config())
    assert read(NEW[0], untraced) is None


def counted(rows):
    """A run whose process lowered ``rows`` ([(labels, calls)]) of
    pt_attention_dispatch_total."""
    from paddle_tpu import flags
    from paddle_tpu.ops import attention_ops

    flags.set_flags({"telemetry": True})
    monitor.reset()
    for labels, n in rows:
        attention_ops._M_DISPATCH.inc(n, labels=labels)
    return tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)


def bd_row(direction, band="skip", t=8192, tile="hb1 bq512 bk512", **more):
    labels = {"family": "bhtd" if band == "skip" else "dense",
              "pass": direction, "shape": f"b1 tq{t} tk{t} h32 kv4 dh128",
              "tile": tile if band == "skip" else "", "replicated_over": "",
              "mask": "block_diffusion", "block": "4", "band": band}
    if direction == "bwd" and band == "skip":
        labels.update(form="fused", edge="")
    labels.update(more)
    return labels


def test_the_counter_readers_by_hand():
    from paddle_tpu import flags

    try:
        # the cell's own rows: eval clone and step, 10 forward, 5 backward
        run = counted([(bd_row("fwd", stats="rows"), 10),
                       (bd_row("bwd"), 5)])
        assert read(NEW[2], run) == 0
        # 80 blocks of 512 x 512 a head either way for 16.79M live pairs
        assert read(NEW[1], run) == pytest.approx(
            100 * 16_793_600 / (80 * 512 * 512))
        assert read(NEW[1], run) == pytest.approx(80.08, abs=0.01)
        # a call that fell to the composition is counted, and leaves the
        # pairs to the calls the kernels took
        run = counted([(bd_row("fwd", stats="rows"), 5), (bd_row("bwd"), 5),
                       (bd_row("fwd", band="dense", t=96), 1),
                       (bd_row("bwd", band="dense", t=96), 2)])
        assert read(NEW[2], run) == 3
        assert read(NEW[1], run) == pytest.approx(80.08, abs=0.01)
        # other cells' rows (a causal call, a windowed one) are not read
        other = {"family": "bhtd", "pass": "fwd", "tile": "hb1 bq512 bk512",
                 "shape": "b1 tq8192 tk8192 h48 kv8 dh128 w512",
                 "replicated_over": "", "band": "skip", "heads": "48"}
        run = counted([(other, 3)])
        assert read(NEW[1], run) is None and read(NEW[2], run) is None
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_a_traced_tiny_run_reports_the_three(monkeypatch):
    """The readers on a tiny traced run on the CPU: no device trace
    there, so the span's share has nothing to read; the counter's two
    find the cell's calls, all dense here (a row of 32 positions is no
    multiple of any tile) and counted."""
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monitor.reset()
    cell = tiny.train_cell(CELL)
    run = tiny.make_run(cell, tiny.config(CONFIG), seconds=0.3, traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    layers = run.config["num_hidden_layers"]
    # eval clone (two fetches of it) and the step: forward 3 x, backward 1 x
    assert line["metrics"][NEW[2]]["value"] == 4 * layers
    assert NEW[1] not in line["metrics"] and NEW[0] not in line["metrics"]
    assert line["metrics"]["lower.dense_attn_calls.train"]["value"] \
        == 4 * layers
    rows = [labels for labels, _ in harness.counter_rows(
        "pt_attention_dispatch_total")]
    assert rows and all(r["mask"] == "block_diffusion" and r["block"] == "4"
                        and r["band"] == "dense" for r in rows)


def test_a_traced_tiny_run_on_the_kernels_reads_a_skipped_band(monkeypatch):
    """The same run where the kernels take the call: through the
    interpreter, heads on the grid at blocks of 128, a row of 256 data
    tokens (512 positions: two whole tiles a half). Every row of the
    counter says ``band=skip``, the backward is the ONE call, and the
    readers find the walk's computed pairs: 8 blocks of 16 a head (2 + 3
    for the noised half's rows, 1 + 2 for the clean half's) for 256^2 +
    4 x 256 live pairs."""
    from paddle_tpu.parallel import flash_attention as fa

    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_GRID_HEADS_BLOCK", 128)
    monitor.reset()
    cell = tiny.train_cell(CELL)
    cell["traffic"].update(batch=1, seq_len=256, real_len=[256, 256])
    run = tiny.make_run(cell, tiny.config(CONFIG), seconds=0.3, traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    rows = [labels for labels, _ in harness.counter_rows(
        "pt_attention_dispatch_total")]
    assert rows and all(r["family"] == "bhtd" and r["band"] == "skip"
                        and r["mask"] == "block_diffusion"
                        and r["tile"] == "hb1 bq128 bk128" for r in rows)
    assert {r["form"] for r in rows if r["pass"] == "bwd"} == {"fused"}
    assert {r["stats"] for r in rows if r["pass"] == "fwd"} == {"rows"}
    assert line["metrics"][NEW[2]]["value"] == 0
    assert line["metrics"]["lower.dense_attn_calls.train"]["value"] == 0
    assert read("lower.split_bwd_attn_calls.train", run) == 0
    live, computed = 256 * 256 + 4 * 256, 8 * 128 * 128
    assert fa.bhtd_pairs(512, 512, (1, 128, 128), False, None,
                         block_diffusion=4) == (computed, live)
    assert line["metrics"][NEW[1]]["value"] == pytest.approx(
        100 * live / computed)
    assert line["metrics"][NEW[1]]["value"] < 100


def test_the_start_state_tool_lays_the_familys_state_part_by_part(
        monkeypatch):
    """perf/tools/sdar_start_states.py names the family's state
    `t1-g2-o-l-r0.2` and lays the same weights; `fresh` is the builder's
    model, and a part left out is left as drawn."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "sdar_start_states", os.path.join(
            harness.ROOT, "perf", "tools", "sdar_start_states.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)

    def weights(state):
        if state != "family":
            monkeypatch.setattr(
                fam, "build_graph", lambda pcfg, is_test=False: tool.lay(
                    pcfg, is_test, **tool.parse(state)))
        _, startup, _, _, _ = models.build_train(cfg, seed=2 ** 31 + 11)
        monkeypatch.undo()
        scope = fluid.Scope()
        fluid.Executor().run(startup, scope=scope)
        return {k: np.asarray(v) for k, v in weights_from_scope(scope).items()
                if k.endswith((".w", ".scale"))}

    own, named = weights("family"), weights("t1-g2-o-l-r0.2")
    assert sorted(own) == sorted(named)
    assert all(np.array_equal(own[k], named[k]) for k in own)
    fresh, part = weights("fresh"), weights("t1-l-r0.2")
    mask = cfg["mask_token_id"]
    assert fresh["sdar_tok_emb.w"].std() == pytest.approx(0.02, rel=0.1)
    assert np.all(fresh["blk0_attn_qnorm.scale"] == 1)
    assert part["sdar_tok_emb.w"].std() == pytest.approx(1.0, rel=0.1)
    assert np.all(part["blk0_attn_qnorm.scale"] == 1)
    wr = part["blk0_moe_router.w"]
    assert np.linalg.norm(wr, axis=0) == pytest.approx(
        0.2 * cfg["hidden_size"] ** 0.5, rel=1e-4)
    assert np.abs(part["sdar_tok_emb.w"][mask] @ wr).max() > 0.1
    with pytest.raises(SystemExit):
        tool.parse("t1-x")
