"""What the JoyAI-LLM-Flash family brings of its own: its configuration's
cut, its second check (main and MTP logits) against a lower-precision
control, its FLOP and byte functions against hand counts, and the
readers of the ``mla`` and ``mtp`` metrics."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_joyai as fj
from perf import harness, models
from perf.kinds import train
from perf.reference import joyai as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "joyai-train-s4096", "joyai-llm-flash"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import joyai_flash as M

    cfg, pub = full_config(), M.JoyaiFlashConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (40, 5), "vocab_size": (129280, 16160)}
    for key, value in vars(pub).items():
        if key == "held_experts":
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 256; the chip holds experts 0..15
    assert pcfg.n_routed_experts == 256 \
        == cfg["reduced_from"]["n_routed_experts"]
    assert pcfg.held_experts == (0, 16) and cfg["n_routed_experts"] == 16
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    # the leading dense layer, four expert layers, the MTP module whole
    assert (pcfg.first_k_dense_replace, pcfg.num_nextn_predict_layers) \
        == (1, 1) and fj.mla_blocks(cfg) == 6
    assert pcfg.qk_head_dim == 192 == cfg["qk_head_dim"]
    # the reference's training constants are the builder's defaults
    assert (ref.ALPHA, ref.MTP_LAMBDA) == (pub.balance_alpha, pub.mtp_lambda)
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS


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
    assert set(record) == {"logit_err_over_rms", "mtp_logit_err_over_rms",
                           "positions_compared", "positions",
                           "flipped_share", "max_expert_load",
                           "held_row_share", "limits"}
    assert record["positions"] == 8 * ref.LAST_POSITIONS
    assert record["positions_compared"] > record["positions"] // 2
    for key in ("logit_err_over_rms", "mtp_logit_err_over_rms"):
        assert 0 < record[key] < ref.LOGIT_ERR_LIMIT
    assert record["flipped_share"] <= ref.FLIP_LIMIT
    # 4 of the 16 experts the tiny router scores are held, in the two
    # expert layers of the stack and the MTP module's
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (3, 4) and len(fetched["top_i"]) == 3
    for layer, top_i in enumerate(fetched["top_i"]):
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=16)[:4]).all()
    assert record["held_row_share"] == pytest.approx(
        rows.sum() / (3 * 8 * 16 * 3))


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control, monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands rounded to
    # float8, judged as if it were the program. The loss check does not
    # see it (and the MTP path, a tenth of the loss, least of all). The
    # limits in the file are the chip's, between readings at the
    # published widths; at the tiny sizes both sides read several times
    # lower, so the limits are set here as there: at the geometric
    # middle of the two readings.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = ref.forward(w, cfg, sample["input_ids"], sample["labels"],
                          round_to=getattr(jnp, control),
                          last=ref.LAST_POSITIONS)
        rows = [np.bincount(np.asarray(t).ravel(), minlength=16)[:4]
                for t in low["top_i"]]
        as_program = {"last_logits": low["logits"],
                      "mtp_last_logits": low["mtp_logits"],
                      "top_i": low["top_i"], "expert_rows": rows}
        _, record = ref.second_check(w, cfg, sample, as_program)
        want = float(ref.loss(w, cfg, sample))
        got = float(ref.loss(w, cfg, sample,
                             round_to=getattr(jnp, control)))
        assert abs(got - want) / want < train.LOSS_REL_TOL
        # the control is further from the reference than the program by
        # every reading, the logits' by several times
        worst = max(program["logit_err_over_rms"],
                    program["mtp_logit_err_over_rms"])
        for key in ("logit_err_over_rms", "mtp_logit_err_over_rms"):
            assert record[key] > 3 * worst, key
        assert record["flipped_share"] > program["flipped_share"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            min(record["logit_err_over_rms"],
                record["mtp_logit_err_over_rms"]) * worst)))
        monkeypatch.setattr(ref, "FLIP_LIMIT", float(np.sqrt(
            record["flipped_share"] * max(program["flipped_share"], 1e-3))))
        problems, _ = ref.second_check(w, cfg, sample, as_program)
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 3 and passes == []
    assert "logits differ" in problems[0] and "mtp_logits" in problems[1] \
        and "choices" in problems[2]


def test_a_lower_precision_mtp_path_alone_fails_the_check(sample_readings):
    """Only the MTP module's logits off (its path computed in float8,
    the stack's as the program gave it): the main logits' reading and
    the loss stay inside their limits, the MTP reading does not."""
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        low = ref.forward(w, cfg, sample["input_ids"], sample["labels"],
                          round_to=jnp.float8_e5m2, last=ref.LAST_POSITIONS)
        _, program = ref.second_check(w, cfg, sample, fetched)
        mixed = dict(fetched, mtp_last_logits=low["mtp_logits"])
        _, record = ref.second_check(w, cfg, sample, mixed)
    assert record["logit_err_over_rms"] == program["logit_err_over_rms"]
    assert record["mtp_logit_err_over_rms"] > 3 * program[
        "mtp_logit_err_over_rms"]


def test_positions_compare_where_the_held_choices_agree():
    """4 experts scored, experts 2..3 held, top 2: a choice that differs
    among experts held elsewhere counts as a flip and still leaves the
    position compared; one that touches a held expert takes it out, for
    the main and the MTP logits alike."""
    cfg = dict(n_routed_experts=2, held_first=2, router_experts=4,
               num_experts_per_tok=2)
    ref_i = np.array([[0, 1], [0, 2], [2, 3], [1, 3]])
    got_i = np.array([[1, 0], [1, 2], [2, 0], [1, 3]])
    #                  same   0 -> 1  3 -> 0  same
    ones = np.ones((1, 4, 5), np.float32)
    want = {"logits": ones, "mtp_logits": 2 * ones, "top_i": [ref_i]}
    got, got_mtp = ones.copy(), 2 * ones
    got[0, 1] += 0.5           # compared: both chose expert 2 of the held
    got[0, 2] += 7.0           # not compared: expert 3 was dropped
    got_mtp[0, 3] += 1.0
    rec = ref.compare(cfg, want, (got, got_mtp), [got_i])
    assert rec["flipped_share"] == pytest.approx(2 / 8)
    assert (rec["positions"], rec["positions_compared"]) == (4, 3)
    assert rec["logit_err_over_rms"] == pytest.approx(np.sqrt(0.25 / 3))
    assert rec["mtp_logit_err_over_rms"] == pytest.approx(
        np.sqrt(1.0 / 3) / 2)


# --- the FLOPs ----------------------------------------------------------------


def test_train_flops_count_this_chips_share_and_both_head_passes():
    cfg = full_config()
    d, tok, t = 2048, 4096, 4096
    # one latent-attention block a token: q 2048 -> 1536 -> 32 x 192,
    # kv 2048 -> 512 + 64, 512 -> 32 x 256, out 4096 -> 2048, and the
    # causal half of scores over 192 and the weighted sum over 128
    proj = 2 * (d * 1536 + 1536 * 6144 + d * 576 + 512 * 8192 + 4096 * d)
    mla = proj + t * 32 * (192 + 128)
    assert fj.mla_flops_per_token(cfg, t) == mla              # 94.6M
    dense = 6 * d * 7168
    # router over all 256, the ungated shared expert, and 8 x 16 / 256
    # of a row on the held experts
    moe = 2 * d * 256 + 6 * d * 768 + 0.5 * 6 * d * 768
    head = 2 * d * 16160
    want = 3.0 * tok * (6 * mla + dense + 5 * moe + 2 * (2 * d) * d
                        + 2 * head)
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, 4096) == pytest.approx(want)
    assert want == pytest.approx(10.83e12, rel=2e-3)
    # without the MTP module: one block, one expert layer, the merge
    # projection and one head pass less
    no_mtp = fj.joyai_train_flops(dict(cfg, num_nextn_predict_layers=0),
                                  1, 4096)
    assert want - no_mtp == pytest.approx(
        3.0 * tok * (mla + moe + 4 * d * d + head))
    # every expert held would be the whole k a token
    all_held = fj.joyai_train_flops(dict(cfg, n_routed_experts=256), 1, 4096)
    assert all_held - want == pytest.approx(
        3.0 * tok * 5 * (8 - 0.5) * 6 * d * 768)


def test_mla_attention_cost_counts_both_widths():
    cfg = full_config()
    fam = models.family(cfg)
    cost = fam.attention_cost(cfg, 1, 4096)
    assert cost["calls"] == 12                # six forward, six backward
    # a head forward: 2 x (t^2 / 2) x (192 + 128); backward twice that
    assert cost["flops"] == 6 * 32 * 3 * 4096 * 4096 * 320   # 3.09 TFLOP
    # q, k, dq, dk, and k again... six tensors of 192 and six of 128
    assert cost["bytes"] == 6 * 6 * 4096 * 32 * 320 * 2
    peaks = harness.peaks_for("TPU v5 lite")
    least_f = cost["flops"] / peaks["bf16_flops_per_s"] / 6
    least_b = cost["bytes"] / peaks["hbm_bytes_per_s"] / 6
    # FLOP-bound at 4096 (2.6 ms a block against 0.6 ms of bytes): the
    # roofline share cannot pass 100 by the byte count
    assert least_f == pytest.approx(2.62e-3, rel=1e-2) and least_f > 4 * least_b
    # padded to 256 the scores would cost a third more than is counted
    padded = 6 * 32 * 3 * 4096 * 4096 * (256 + 128)
    assert padded / cost["flops"] == pytest.approx(1.2)


# --- the readers ------------------------------------------------------------


def scopes_run(by_scope, busy=100.0):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": 1}
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
    "fwd/blk0/attn/q_lora/mul": 5.0,
    "fwd/blk0/attn/kv_lora/mul": 3.0,
    "fwd/blk0/attn/rope/concat": 2.0,
    "fwd/blk0/attn/rope/rotary_embedding": 1.0,
    "fwd/blk0/attn/core/scaled_dot_product_attention": 6.0,
    "bwd/blk1/attn/core/scaled_dot_product_attention_grad": 12.0,
    "bwd/blk1/attn/rope/concat_grad": 3.0,
    "bwd/blk1/attn/out/mul_grad": 4.0,
    "fwd/blk0/ffn/mul": 7.0,
    "fwd/blk1/moe/shared/mul": 3.0,
    "opt/blk1/moe/router/moe_bias_update": 1.0,
    "fwd/blk_mtp/merge/mul": 2.0,
    "fwd/blk_mtp/attn/kv_lora/mul": 3.0,
    "bwd/blk_mtp/moe/experts/moe_experts_grad": 4.0,
    "fwd/loss_head/mul": 6.0,
    "bwd/loss_head/mtp/mul_grad": 5.0,
    "fwd/attn/mul": 5.0,            # a scope named attn outside a block
    "opt/adam": 10.0,
}


def test_mla_and_mtp_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    attn = 1 + 5 + 3 + 2 + 1 + 6 + 12 + 3 + 4 + 3
    assert read("mla.step_share.train", run) == pytest.approx(attn)
    assert read("mla.assemble_share.train", run) == pytest.approx(
        100 * (2 + 1 + 3) / attn)
    assert read("mtp.step_share.train", run) == pytest.approx(2 + 3 + 4 + 5)
    # the readers that exist count the MTP module as a block, its expert
    # layer as one, the bias's step with the routing, and its head pass
    # with the head
    assert read("step.block_share.train", run) == pytest.approx(
        attn + 7 + 3 + 1 + 2 + 4)
    assert read("moe.step_share.train", run) == pytest.approx(3 + 1 + 4)
    assert read("moe.route_share.train", run) == pytest.approx(100 / 8)
    assert read("step.head_share.train", run) == pytest.approx(11.0)


def test_readers_report_nothing_for_a_program_without_latent_attention():
    """A parent's tree, or another family's cell (an ``attn`` scope with
    no ``kv_lora`` under it): None, and no exception."""
    run = scopes_run({
        "fwd/blk0/attn/scaled_dot_product_attention": 10.0,
        "fwd/blk0/attn/mul": 5.0, "fwd/blk0/moe/shared/mul": 3.0,
        "fwd/loss_head/mul": 6.0, "opt/adam": 10.0})
    metrics = ("mla.step_share.train", "mla.assemble_share.train",
               "mtp.step_share.train")
    for metric in metrics:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    for metric in metrics:
        assert read(metric, run) is None, metric
