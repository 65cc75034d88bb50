"""What the Xing4.0 family brings of its own: its configuration's cut,
the start state its ``build_graph`` lays, its second check against a
lower-precision control and against every control of the mechanism, its
FLOP and byte functions from shapes, and the readers of the four ``hc``
metrics."""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, monitor
from perf import flops_xing4 as fx
from perf import harness, hc_spans, models
from perf.kinds import train
from perf.reference import xing4 as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "xing4-train-s4096", "xing4.0-29b-a4b"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_vocabulary_and_mtp_and_no_width():
    from paddle_tpu.models import xing4 as M

    cfg, pub = full_config(), M.xing4_0_29b()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (2, 1),
           "vocab_size": (131072, 16384),
           "num_nextn_predict_layers": (1, 0)}
    for key, value in vars(pub).items():
        if key == "held_experts":
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 64; the chip holds experts 0..7
    assert pcfg.n_routed_experts == 64 == cfg["reduced_from"][
        "n_routed_experts"]
    assert pcfg.held_experts == (0, 8) and cfg["n_routed_experts"] == 8
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    # the published widths, letter for letter
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"]) == (3584, 9216, 1024, 768, 512)
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
            cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]) == (
        4, 20, 1e-6, -30, 30)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"]) == (
        128, 64, 128, 4, 2, 1e-6)
    assert cfg["rope_scaling"] == M.YARN
    for key in ("hyper-connections, the equations",
                "hyper-connections, what the paper and the config leave open",
                "hyper-connections, start values", "start state", "yarn",
                "bias update", "balance loss", "multi-token prediction",
                "packing", "training precision", "initialisation",
                "storage"):
        assert cfg["assumed"][key], key
    assert "8 chips" in cfg["deployment"] and "14.862 GB" in cfg["the_cut"]
    # the reference's training constants are the builder's defaults
    assert ref.ALPHA == pub.balance_alpha
    assert ref.MTP_LAMBDA == pub.mtp_lambda
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS


def test_the_start_state_opens_the_gates_and_sharpens_the_queries():
    """``build_graph`` lays gates of 1, a drawn H_res bias and a sharper
    q_b over the builder's state (the configuration's ``assumed`` says
    why); every other matrix keeps the builder's 0.02, and another seed
    draws another bias."""
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)

    def weights(seed):
        _, startup, _, _, _ = models.build_train(cfg, seed=seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        return {k: np.asarray(v) for k, v in weights_from_scope(scope).items()}

    w, other = weights(7), weights(8)
    stds = {n: float(np.std(v)) for n, v in w.items()
            if n.endswith(".w") and "router" not in n}
    sharp = sorted(n for n in stds if n.endswith("_attn_q_b_colp.w"))
    assert sharp == [f"blk{i}_attn_q_b_colp.w" for i in range(3)]
    assert fam.LATENT_QUERY_STD == 0.05 and fam.HC_ALPHA == 1.0
    for n, std in stds.items():
        assert std == pytest.approx(0.05 if n in sharp else 0.02, rel=0.2), n
    mixes = sorted(n[:-len("_hc.alpha")] for n in w if n.endswith(".alpha"))
    assert mixes == sorted(f"blk{i}_{s}" for i in range(3) for s in (
        "attn", "ffn" if i == 0 else "moe"))
    for p in mixes:
        np.testing.assert_array_equal(w[f"{p}_hc.alpha"], 1.0)
        bias = w[f"{p}_hc.bias"]
        np.testing.assert_allclose(bias[:4], math.log(1 / 3), rtol=1e-6)
        np.testing.assert_array_equal(bias[4:8], 0.0)
        assert 0.5 < np.std(bias[8:]) < 1.6
        assert np.abs(bias[8:] - other[f"{p}_hc.bias"][8:]).max() > 0.1
    assert np.abs(w["blk0_attn_hc.bias"][8:]
                  - w["blk1_attn_hc.bias"][8:]).max() > 0.1


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
                           "held_row_share", "limits"}
    assert record["positions"] == 8 * ref.LAST_POSITIONS
    assert record["positions_compared"] > record["positions"] // 2
    assert 0 < record["logit_err_over_rms"] < ref.LOGIT_ERR_LIMIT
    assert record["flipped_share"] <= ref.FLIP_LIMIT
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (2, 4) and len(fetched["top_i"]) == 2
    for layer, top_i in enumerate(fetched["top_i"]):
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=16)[:4]).all()


def _as_program(low):
    rows = [np.bincount(np.asarray(t).ravel(), minlength=16)[:4]
            for t in low["top_i"]]
    return {"last_logits": low["logits"], "top_i": low["top_i"],
            "expert_rows": rows}


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control, monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands (the mixes'
    # projections too) rounded to float8, judged as if it were the
    # program. The loss check does not see it. The limits in the file are
    # the chip's, between readings at the published widths; at the tiny
    # sizes both sides read lower, so the limits are set here as there:
    # at the geometric middle of the two readings.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = ref.forward(w, cfg, sample["input_ids"], sample["labels"],
                          round_to=getattr(jnp, control),
                          last=ref.LAST_POSITIONS)
        _, record = ref.second_check(w, cfg, sample, _as_program(low))
        want = float(ref.loss(w, cfg, sample))
        got = float(ref.loss(w, cfg, sample,
                             round_to=getattr(jnp, control)))
        assert abs(got - want) / want < train.LOSS_REL_TOL
        assert record["logit_err_over_rms"] > 3 * program[
            "logit_err_over_rms"]
        assert record["flipped_share"] > program["flipped_share"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        monkeypatch.setattr(ref, "FLIP_LIMIT", float(np.sqrt(
            record["flipped_share"] * max(program["flipped_share"], 1e-3))))
        problems, _ = ref.second_check(w, cfg, sample, _as_program(low))
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 2 and passes == []
    assert "logits differ" in problems[0] and "choices" in problems[1]


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_second_check_fails_every_control_of_the_mechanism(sample_readings,
                                                           control,
                                                           monkeypatch):
    """A model that left a piece of the mechanism out, in full float32,
    judged as if it were the program, fails by the logits where the
    reference as it is passes. The weights are laid so that the pieces
    matter as they do at the published widths: sharp attention, mixes
    that depend on the token, and for ``no_clamp`` an H_res bias wide
    enough to reach the clamp. The limit in the file is the chip's; here
    it is set as there, between the two readings: a control has to read
    ten times what the bf16 program reads of these sizes."""
    cfg, w, sample, fetched = sample_readings
    r = np.random.RandomState(3)
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
    w = dict(w)
    for name in w:
        # (at 32 features a stream a mix's pre-activations have a tenth
        # of the spread they have at 3584: Phi times ten lays that back)
        if name.endswith("_hc_phi.w"):
            w[name] = (10.0 * w[name]).astype(np.float32)
        if name.endswith("_attn_q_b_colp.w"):
            w[name] = (30.0 * w[name]).astype(np.float32)
        if control == "no_clamp" and name.endswith("_hc.bias"):
            w[name] = np.concatenate(
                [w[name][:8], 40.0 * r.randn(16)]).astype(np.float32)
    ids, lbl = sample["input_ids"], sample["labels"]
    with jax.default_matmul_precision("highest"):
        plain = ref.forward(w, cfg, ids, lbl, last=ref.LAST_POSITIONS)
        other = ref.forward(w, cfg, ids, lbl, last=ref.LAST_POSITIONS,
                            control=control)
        _, record = ref.second_check(w, cfg, sample, _as_program(other))
        assert record["logit_err_over_rms"] > 10 * program[
            "logit_err_over_rms"], (record, program)
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        passes, same = ref.second_check(w, cfg, sample, _as_program(plain))
        problems, _ = ref.second_check(w, cfg, sample, _as_program(other))
    assert passes == [] and same["logit_err_over_rms"] < 1e-5
    assert any("logits differ" in p for p in problems)


# --- the FLOPs and the bytes ------------------------------------------------


def brute_force_flops(cfg, t):
    """2 x the multiply-adds a token of every weight matmul, the latent
    attention's causal pairs and the hyper-connections' products, a loop
    at a time, with no formula shared with perf/flops_xing4.py."""
    d, n = cfg["hidden_size"], cfg["hc_mult"]
    hq, nope, pe, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        for _ in range(2):               # a mix, a read, a write-back
            total += 2 * (n * d) * (n * n + 2 * n)
            total += 2 * n * d + 2 * (n * n) * d + 2 * n * d
        for rows, cols in ((d, rq), (rq, hq * (nope + pe)), (d, r + pe),
                           (r, hq * (nope + dv)), (hq * dv, d)):
            total += 2 * rows * cols
        pairs = sum(p + 1 for p in range(t)) / t         # a token's keys
        total += hq * 2 * pairs * (nope + pe + dv)
        if i < cfg["first_k_dense_replace"]:
            total += 3 * 2 * d * cfg["intermediate_size"]
        else:
            f = cfg["moe_intermediate_size"]
            total += 2 * d * cfg["router_experts"] + 3 * 2 * d * f
            total += (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                      / cfg["router_experts"]) * 3 * 2 * d * f
    return total + 2 * d * cfg["vocab_size"]


@pytest.mark.parametrize("sizes", ["tiny", "published"])
def test_train_flops_are_a_brute_force_count(sizes):
    cfg = tiny.config(CONFIG) if sizes == "tiny" else full_config()
    t = 16 if sizes == "tiny" else 4096
    fam = models.family(cfg)
    want = 3.0 * t * brute_force_flops(cfg, t)
    # the count takes a causal call as t^2 / 2 pairs; a loop finds
    # t (t + 1) / 2
    assert fam.train_flops(cfg, 1, t) == pytest.approx(
        want, rel=2e-3 if sizes != "tiny" else 2e-2)
    if sizes == "published":
        assert want == pytest.approx(11.7e12, rel=0.02)    # ISSUE 67: 11.6
        cost = fam.attention_cost(cfg, 1, t)
        assert cost["calls"] == 10              # five triangles each way
        assert cost["flops"] == pytest.approx(
            5 * 3.0 * 32 * 4096 * 4096 * (192 + 128))


def test_hc_stream_cost_counts_ten_sublayers_in_four_passes():
    cfg = full_config()
    cost = fx.hc_stream_cost(cfg, 1, 4096)
    assert fx.hc_sublayers(cfg) == 10 and cost["calls"] == 60
    td = 4096 * 3584
    # forward: read X, write h; read X and y, write X' (3n + 2); backward:
    # read X, y, dX', write dy; read X, dX', dh, write dX (5n + 3)
    assert cost["forward_bytes"] == 10 * (4 + 1 + 4 + 1 + 4) * td * 2
    assert cost["forward_bytes"] / 10 == pytest.approx(0.411e9, rel=1e-2)
    assert cost["bytes"] == 10 * ((3 * 4 + 2) + (5 * 4 + 3)) * td * 2
    assert cost["flops"] == 3 * 4096 * 10 * (
        2 * 14336 * 24 + 2 * 4 * 3584 + 2 * 20 * 3584)
    peaks = harness.peaks_for("TPU v5 lite")
    # the passes are byte-bound by far
    assert cost["bytes"] / peaks["hbm_bytes_per_s"] > 20 * (
        cost["flops"] / peaks["bf16_flops_per_s"])
    # with the MTP module two sublayers more; n = 1 is a plain residual's
    # 13 t d
    assert fx.hc_stream_cost(dict(cfg, num_nextn_predict_layers=1), 1,
                             4096)["calls"] == 72
    assert fx.hc_stream_cost(dict(cfg, hc_mult=1), 1, 4096)[
        "bytes"] == 10 * 13 * td * 2
    share = cost["flops"] / fx.xing4_train_flops(cfg, 1, 4096)
    assert 0.005 < share < 0.015


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
    "fwd/embed/expand": 1.0,
    "fwd/blk0/attn/hc/mix/hc_mix": 3.0,
    "fwd/blk0/attn/hc/pre/hc_pre": 2.0,
    "fwd/blk0/attn/kv_lora/mul": 4.0,
    "fwd/blk0/attn/core/scaled_dot_product_attention": 15.0,
    "fwd/blk0/attn/hc/post/hc_post": 4.0,
    "fwd/blk0/ffn/hc/mix/hc_mix": 1.0,
    "bwd/blk1/moe/hc/post/hc_post_grad": 6.0,
    "bwd/blk1/moe/hc/mix/hc_mix_grad": 2.0,
    "bwd/blk1/moe/hc/pre/hc_pre_grad": 2.0,
    "bwd/blk1/moe/shared/mul_grad": 8.0,
    "bwd/sum": 5.0,                 # a gradient summed outside the scopes
    "fwd/hc/mix/hc_mix": 7.0,       # a scope named hc outside a block
    "opt/adam": 10.0,
}
METRICS = ("hc.step_share.train", "hc.mix_share.train",
           "hc.stream_roofline.train", "lower.xla_hc_calls.train")


def test_hc_readers_sum_the_scopes_under_every_sublayer():
    run = scopes_run(BY_SCOPE)
    assert read("hc.step_share.train", run) == pytest.approx(20.0)
    assert read("hc.mix_share.train", run) == pytest.approx(6.0)
    cost = fx.hc_stream_cost(full_config(), 1, 4096)
    least = cost["bytes"] / harness.peaks_for("TPU v5 lite")[
        "hbm_bytes_per_s"]
    assert read("hc.stream_roofline.train", run) == pytest.approx(
        100 * least / 20e-9)
    assert read("hc.stream_roofline.train", scopes_run(
        BY_SCOPE, traced_steps=3)) == pytest.approx(100 * 3 * least / 20e-9)
    # the sublayers' own readers hold the hyper-connections under them
    assert read("mla.step_share.train", run) == pytest.approx(28.0)
    assert read("moe.step_share.train", run) == pytest.approx(18.0)
    assert read("mtp.step_share.train", run) in (None, 0.0)


@pytest.mark.parametrize("metric", METRICS)
def test_hc_readers_report_nothing_for_a_program_without_the_ops(metric):
    """A parent's tree, or another family's cell: no ``hc`` scope under a
    block and no counter. None, and no exception."""
    run = scopes_run({k: v for k, v in BY_SCOPE.items()
                      if "/hc/" not in k or not k.split("/")[1].startswith(
                          "blk")})
    monitor.reset()
    assert read(metric, run) is None
    run._spans = None
    run.trace = None
    assert read(metric, run) is None
    # another family's configuration under a program with the scopes
    other = scopes_run(BY_SCOPE)
    other.config = {k: v for k, v in other.config.items() if k != "hc_mult"}
    if metric == "hc.stream_roofline.train":
        assert read(metric, other) is None


@pytest.mark.parametrize("metric", METRICS)
def test_every_new_metric_is_listed_for_the_cell_and_moves_the_rate(metric):
    by_name = {m["name"]: m for m in tiny.BENCH["per_layer"]}
    entry = by_name[metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == {
        "hc.stream_roofline.train": "device_trace",
        "lower.xla_hc_calls.train": "program_counter"}.get(
        metric, "program_span")
    assert CELL not in by_name["mtp.step_share.train"]["workloads"]
    assert CELL in by_name["mla.step_share.train"]["workloads"]


def test_a_tiny_traced_program_counts_its_sixty_xla_calls_by_op():
    """The family's tiny program lowered with telemetry on: three layers,
    six sublayers, each a mix, a read and a write-back each way, all as
    XLA's ops; ``lower.xla_hc_calls.train`` counts them and a row that
    says ``kernel`` is not counted."""
    cfg = tiny.config(CONFIG)
    flags.set_flags({"telemetry": True})
    monitor.reset()
    try:
        main, startup, _, loss, _ = models.build_train(cfg, seed=3)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        feed = models.family(cfg).feeds(
            cfg, tiny.train_cell(CELL)["traffic"], 5)[0]
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        rows = hc_spans.dispatch_rows()
        assert {lb["impl"] for lb, _ in rows} == {"xla"}
        assert {(lb["op"], lb["pass"]): n for lb, n in rows} == {
            (op, way): 6 for op in ("mix", "pre", "post")
            for way in ("fwd", "bwd")}
        run = scopes_run(BY_SCOPE)
        assert read("lower.xla_hc_calls.train", run) == 36
        from paddle_tpu.ops import hc_ops

        hc_ops._M_DISPATCH.inc(labels={"op": "post", "pass": "fwd",
                                       "impl": "kernel"})
        assert read("lower.xla_hc_calls.train", run) == 36
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
