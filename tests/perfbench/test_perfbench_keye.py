"""What the Keye family brings of its own: its configuration's cut, its
FLOP and pair counts against a brute-force count, its second check
against the controls (no selection, the most recent keys, a smaller
top-k, each piece of the indexer dropped) and a lower-precision control,
and the readers of the ``dsa`` metrics."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_keye as fk
from perf import harness, models
from perf.kinds import train
from perf.reference import keye as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "keye-train-s16384", "keye-vl-2.0-30b-a3b"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import keye as M

    cfg, pub = full_config(), M.KeyeConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (48, 4), "vocab_size": (151936, 18992)}
    for key, value in vars(pub).items():
        if key == "held_experts":
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 128; the chip holds experts 0..15
    assert pcfg.num_experts == 128 == cfg["reduced_from"]["num_experts"] \
        == cfg["num_local_experts"]
    assert pcfg.held_experts == (0, 16) and cfg["num_experts"] == 16
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    # the nested groups whole, as published
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cfg["source"].endswith("Keye-VL-2.0-30B-A3B/blob/main/config.json")
    for key in ("the_cut", "assumed", "deployment"):
        assert cfg[key], key
    assert "15.996 GB" in cfg["the_cut"]
    traffic = harness.load_json("perf", "workloads", f"{CELL}.json")["traffic"]
    assert (traffic["batch"], traffic["seq_len"], traffic["feeds"]) == (
        1, 16384, 4)
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS


def test_the_feed_carries_three_equal_position_rows():
    cfg = tiny.config(CONFIG)
    feed = models.family(cfg).feeds(cfg, tiny.train_cell(CELL)["traffic"],
                                    2 ** 31 + 3)[0]
    assert feed["position_ids"].shape == (3, 16)
    assert (feed["position_ids"] == np.arange(16)).all()
    assert train.sample_of(feed)["position_ids"].shape == (3, 16)
    assert models.family(cfg).real_tokens(feed) == 8 * 16


# --- FLOPs and pairs, from shapes -------------------------------------------


@pytest.mark.parametrize("t,topk", [(16, 6), (64, 64), (40, 100), (128, 1)])
def test_selected_pairs_against_a_brute_force_count(t, topk):
    assert fk.selected_pairs(t, topk) == sum(
        min(p + 1, topk) for p in range(t))


def test_costs_of_the_cell_from_its_shapes():
    cfg = full_config()
    assert fk.selected_pairs(16384, 2048) == 31_458_304
    triangle = 16384 * 16385 // 2
    assert 0.234 < fk.selected_pairs(16384, 2048) / triangle < 0.235
    att = fk.attention_cost(cfg, 1, 16384)
    assert att["flops"] == 4 * 12 * 32 * 128 * 31_458_304
    assert att["calls"] == 8
    index = fk.dsa_index_cost(cfg, 1, 16384)
    assert index["flops"] == 4 * (
        2 * 16 * 64 * (triangle + 2 * 31_458_304) + 2 * 32 * 128 * 31_458_304)
    total = fk.keye_train_flops(cfg, 1, 16384)
    d, tok = 2048, 16384
    proj = 2 * d * 40 * 128 + 2 * 4096 * d + 2 * d * (1024 + 64 + 16)
    moe = 2 * d * 128 + 8 * 16 / 128 * 6 * d * 768
    assert total == pytest.approx(
        3.0 * tok * (4 * (proj + moe) + 2 * d * 18992) + att["flops"]
        + index["flops"])
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, 16384) == total
    assert fam.attention_cost(cfg, 1, 16384) == att


# --- the second check -------------------------------------------------------


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


def as_program(cfg, low, held=2, scored=8):
    """A reference forward's outputs as if the program had given them."""
    rows = [np.bincount(np.asarray(t).ravel(), minlength=scored)[:held]
            for t in low["top_i"]]
    return {"last_logits": low["logits"], "top_i": low["top_i"],
            "expert_rows": rows,
            "last_selected": [np.asarray(mine).astype(np.int8)
                              for mine, _ in low["kept"]]}


def control(w, cfg, sample, **kw):
    return ref.forward(w, cfg, sample["input_ids"], sample["position_ids"],
                       last=ref.LAST_POSITIONS, keep=ref.LAST_POSITIONS, **kw)


def test_second_check_passes_the_program(sample_readings):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        problems, record = ref.second_check(w, cfg, sample, fetched)
    assert problems == []
    assert record["rows_with_wrong_count"] == record["keys_after_query"] == 0
    # the tiny row is 16 positions: all of them are "last"
    assert record["positions"] == 8 * 16
    assert record["positions_compared"] > record["positions"] // 2
    assert 0 < record["logit_err_over_rms"] < ref.LOGIT_ERR_LIMIT
    assert record["flipped_share"] <= ref.FLIP_LIMIT
    assert len(record["mean_row_diff_by_layer"]) == 4
    # rows below and above k = 6 both occur in a row of 16
    counts = np.asarray(fetched["last_selected"][0] != 0).sum(-1)
    assert (counts == np.minimum(np.arange(16) + 1, 6)).all()
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (4, 2) and len(fetched["top_i"]) == 4


CONTROLS = {"dense": dict(select="dense"), "recent": dict(select="recent"),
            "top-3": dict(select=3), "no relu": dict(ablate="relu"),
            "w uniform": dict(ablate="weights"),
            "no LayerNorm on kI": dict(ablate="knorm"),
            "indexer rotation off": dict(ablate="rope"),
            "QK-norm off": dict(ablate="qknorm")}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_control_is_another_model(sample_readings, name):
    """Each control, judged as if it were the program, fails the second
    check: by its selection (count, margin or share of a row), or, where
    the selection stands (QK-norm off), by its logits. The limits are
    the chip's; at the tiny sizes the program reads far under them and
    the controls far over."""
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        low = control(w, cfg, sample, **CONTROLS[name])
        problems, record = ref.second_check(w, cfg, sample,
                                            as_program(cfg, low))
        _, program = ref.second_check(w, cfg, sample, fetched)
    if name == "QK-norm off":
        # the indexer does not read q and k: the selection stands and
        # the logits carry it (at the tiny sizes under the chip's limit,
        # and over twice the program's own reading)
        assert record["logit_err_over_rms"] \
            > 2 * program["logit_err_over_rms"], record["logit_err_over_rms"]
        return
    assert problems, (name, record)
    assert any("selection" in p or "threshold" in p or "rows' keys" in p
               for p in problems), problems


def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every matmul's operands rounded to float8,
    # judged as if it were the program. The limits in the file are the
    # chip's; at the tiny sizes both sides read lower, so the limit is
    # set here as there: at the geometric middle of the two readings.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = control(w, cfg, sample, round_to=jnp.float8_e4m3fn)
        _, record = ref.second_check(w, cfg, sample, as_program(cfg, low))
        assert record["logit_err_over_rms"] \
            > 3 * program["logit_err_over_rms"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        problems, _ = ref.second_check(w, cfg, sample, as_program(cfg, low))
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert problems and passes == []


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9, "by_family_s": {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": 0.0}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk0/attn/qkv/mul": 5.0,
    "fwd/blk0/attn/dsa/proj/mul": 3.0,
    "fwd/blk0/attn/dsa/select/dsa_select": 12.0,
    "fwd/blk1/attn/dsa/loss/dsa_index_loss": 9.0,
    "bwd/blk1/attn/dsa/loss/dsa_index_loss_grad": 1.0,
    "bwd/blk1/attn/dsa/proj/mul_grad": 5.0,
    "fwd/blk0/attn/core/scaled_dot_product_attention": 9.0,
    "bwd/blk0/attn/core/scaled_dot_product_attention_grad": 20.0,
    "fwd/blk1/moe/experts/moe_experts": 6.0,
    "fwd/dsa/select/mul": 4.0,      # a scope named dsa outside a block
    "opt/adam": 10.0,
}


def test_dsa_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    assert read("dsa.step_share.train", run) == pytest.approx(30.0)
    # (no kernel named in the trace: the scope's time whole)
    assert read("dsa.select_share.train", run) == pytest.approx(40.0)
    run._spans["kernel_ns"] = {"dsa.score.fwd": 3.0, "attn.bhtd.fwd": 9.0}
    assert read("dsa.select_share.train", run) == pytest.approx(30.0)
    cfg, peaks = full_config(), harness.peaks_for("TPU v5 lite")
    traffic = run.cell["traffic"]           # the tiny cell: 8 x 16
    cost = fk.dsa_index_cost(cfg, traffic["batch"], traffic["seq_len"])
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    assert read("dsa.index_roofline.train", run) == pytest.approx(
        100 * least / 22e-9)
    two = scopes_run(BY_SCOPE, traced_steps=2)
    assert read("dsa.index_roofline.train", two) == pytest.approx(
        200 * least / 22e-9)
    assert read("moe.step_share.train", run) == pytest.approx(6.0)
    for metric in ("bd.step_share.train", "hc.step_share.train",
                   "swa.step_share.train"):
        assert read(metric, run) is None, metric


def test_readers_report_nothing_for_a_program_without_an_indexer():
    """A parent's tree, or another family's cell: None, no exception."""
    from paddle_tpu import monitor

    monitor.reset()
    run = scopes_run({
        "fwd/blk0/attn/core/scaled_dot_product_attention": 10.0,
        "fwd/blk0/attn/mul": 5.0, "fwd/loss_head/mul": 6.0,
        "opt/adam": 10.0})
    metrics = ("dsa.step_share.train", "dsa.select_share.train",
               "dsa.index_roofline.train", "dsa.live_pair_share.train",
               "lower.dense_dsa_calls.train")
    for metric in metrics:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    for metric in metrics:
        assert read(metric, run) is None, metric


def test_counters_read_the_sel_label():
    from paddle_tpu import flags, monitor
    from paddle_tpu.core import interp
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.parallel import flash_attention as fa

    run = scopes_run(BY_SCOPE)
    monitor.reset()
    flags.set_flags({"telemetry": True})
    token = interp.set_amp_active(False)    # a lowering is active
    try:
        dims = (1, 16384, 16384, 32, 128, 4, 128, 2)
        on_tpu = pytest.MonkeyPatch()
        on_tpu.setattr(fa, "kernels_enabled", lambda: True)
        for direction, form in (("fwd", None), ("bwd", "fused")):
            attention_ops._note_dispatch("bhtd", direction, dims, form=form,
                                         causal=True, sel="operand")
        on_tpu.undo()
        assert read("lower.dense_dsa_calls.train", run) == 0
        share = read("dsa.live_pair_share.train", run)
        tile = (1, 512, 512)
        walked = sum(fa.bhtd_pairs(16384, 16384, tile, True, form=f)[0]
                     for f in (None, "fused"))
        assert share == pytest.approx(
            100 * 2 * fk.selected_pairs(16384, 2048) / walked)
        assert 22 < share < 24
        attention_ops._note_dispatch("dense", "fwd", dims, sel="dense")
        assert read("lower.dense_dsa_calls.train", run) == 1
    finally:
        interp._AMP_ACTIVE.reset(token)
        flags.set_flags({"telemetry": False})
        monitor.reset()
