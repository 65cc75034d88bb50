"""What the Kimi Linear family brings of its own: its configuration's
cut, its second check against a lower-precision control and against a
control that rotates the shared key features, its FLOP and byte
functions against brute-force counts, and the readers of the four
``kda`` metrics."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, monitor
from perf import flops_kimilinear as fk
from perf import harness, kda_spans, models
from perf.kinds import train
from perf.reference import kimilinear as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CELL, CONFIG = "kimilinear-train-s4096", "kimi-linear-48b-a3b"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import kimi_linear as M

    cfg, pub = full_config(), M.KimiLinearConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (27, 5), "vocab_size": (163840, 20480)}
    for key, value in vars(pub).items():
        if key == "held_experts":
            continue
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
        if key in cut:
            assert value == cut[key][0] == cfg["reduced_from"][key]
    # the router scores the published 256; the chip holds experts 0..7
    assert pcfg.num_experts == 256 == cfg["reduced_from"]["num_experts"]
    assert pcfg.held_experts == (0, 8) and cfg["num_experts"] == 8
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    # the leading dense layer under a KDA mixer, then KDA, KDA, MLA, KDA
    assert [pcfg.is_kda(i) for i in range(5)] == [True, True, True, False,
                                                  True]
    assert [pcfg.dense(i) for i in range(5)] == [True] + [False] * 4
    assert fk.layer_kinds(cfg) == (4, 1)
    assert pcfg.q_lora_rank is None and pcfg.mla_use_nope
    # the published widths, letter for letter
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["kv_lora_rank"]) == (
        2304, 9216, 1024, 512)
    assert cfg["linear_attn_config"] == pub.linear_attn_config
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["routed_scaling_factor"],
            cfg["rms_norm_eps"]) == (128, 64, 128, 2.446, 1e-5)
    for key in ("bias update", "balance loss", "low-rank pairs",
                "decay initialisation", "no positional embedding",
                "kda_chunk", "packing", "training precision",
                "initialisation", "start state", "storage"):
        assert cfg["assumed"][key], key
    assert "32 chips" in cfg["deployment"]
    # the reference's training constants are the builder's defaults
    assert ref.ALPHA == pub.balance_alpha
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS


def test_the_start_state_sharpens_the_latent_layers_queries_alone():
    """``build_graph`` lays a second initializer over the latent
    layers' query projection (``LATENT_QUERY_STD``; the configuration's
    ``assumed`` says why): every other matrix keeps the builder's 0.02."""
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)
    _, startup, _, _, _ = models.build_train(cfg, seed=7)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    w = weights_from_scope(scope)
    stds = {n: float(np.std(np.asarray(v))) for n, v in w.items()
            if n.endswith(".w") and "router" not in n}
    sharp = [n for n in stds if n.endswith("_attn_q_colp.w")]
    assert sharp == ["blk3_attn_q_colp.w"] and fam.LATENT_QUERY_STD == 0.1
    assert stds[sharp[0]] == pytest.approx(0.1, rel=0.1)
    for n, std in stds.items():
        if n not in sharp:
            assert std == pytest.approx(0.02, rel=0.2), n


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
    # 4 of the 16 experts the tiny router scores are held, in the three
    # expert layers behind the dense one
    rows = np.asarray(fetched["expert_rows"])
    assert rows.shape == (3, 4) and len(fetched["top_i"]) == 3
    for layer, top_i in enumerate(fetched["top_i"]):
        assert (rows[layer] == np.bincount(top_i.ravel(),
                                           minlength=16)[:4]).all()
    assert record["held_row_share"] == pytest.approx(
        rows.sum() / (3 * 8 * 16 * 3))


def _as_program(low):
    rows = [np.bincount(np.asarray(t).ravel(), minlength=16)[:4]
            for t in low["top_i"]]
    return {"last_logits": low["logits"], "top_i": low["top_i"],
            "expert_rows": rows}


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control, monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands rounded to
    # float8, judged as if it were the program. The loss check does not
    # see it. The limits in the file are the chip's, between readings at
    # the published widths; at the tiny sizes both sides read several
    # times lower, so the limits are set here as there: at the geometric
    # middle of the two readings.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = ref.forward(w, cfg, sample["input_ids"],
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


def test_second_check_fails_a_model_that_rotates_the_shared_features(
        sample_readings):
    """A model that quietly applied RoPE to the 64 features the config
    calls ``qk_rope_head_dim`` (DeepSeek-V3's latent attention does; this
    model has ``mla_use_nope``): the reference with ``rotate``, in full
    float32, judged as if it were the program, fails by the logits at
    the file's own limit, where the program passes. The weights are laid
    so that the latent layer matters as it does at the published widths
    (its shared-feature scores of the order of the others)."""
    cfg, w, sample, fetched = sample_readings
    r = np.random.RandomState(3)
    w = dict(w)
    for name in w:
        if "_attn_q_colp" in name or "_attn_kv_a.w" in name \
                or "_attn_out_rowp" in name or "_attn_kv_b" in name:
            w[name] = (r.randn(*w[name].shape) / np.sqrt(
                w[name].shape[0])).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        plain = ref.forward(w, cfg, sample["input_ids"],
                            last=ref.LAST_POSITIONS)
        turned = ref.forward(w, cfg, sample["input_ids"], rotate=1e4,
                             last=ref.LAST_POSITIONS)
        passes, same = ref.second_check(w, cfg, sample, _as_program(plain))
        problems, record = ref.second_check(w, cfg, sample,
                                            _as_program(turned))
    assert passes == [] and same["logit_err_over_rms"] < 1e-5
    assert record["logit_err_over_rms"] > ref.LOGIT_ERR_LIMIT
    assert any("logits differ" in p for p in problems)


# --- the FLOPs ----------------------------------------------------------------


def brute_force_matmul_flops(cfg, t, chunk):
    """2 x the multiply-adds a token of every weight matmul, the latent
    layer's causal pairs and the chunkwise rule's products, a loop at a
    time, with no formula shared with perf/flops_kimilinear.py."""
    d, la = cfg["hidden_size"], cfg["linear_attn_config"]
    h, dh = la["num_heads"], la["head_dim"]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        if i + 1 in la["kda_layers"]:
            for rows, cols in ((d, h * dh),) * 3 + (
                    (d, dh), (dh, h * dh), (d, dh), (dh, h * dh), (d, h),
                    (h * dh, d)):
                total += 2 * rows * cols
            for _ in range(h):
                # a token's share of a chunk: K K^T and Q K^T rows of C
                # entries over dk, half a product for each column of U
                # and W, P V' over C, three products against the state
                total += 2 * (2 * chunk * dh) + chunk * 2 * dh / 2 * 2
                total += 2 * chunk * dh + 3 * 2 * dh * dh
        else:
            hq = cfg["num_attention_heads"]
            nope, pe, dv, r = (cfg["qk_nope_head_dim"],
                               cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                               cfg["kv_lora_rank"])
            for rows, cols in ((d, hq * (nope + pe)), (d, r + pe),
                               (r, hq * (nope + dv)), (hq * dv, d)):
                total += 2 * rows * cols
            pairs = sum(p + 1 for p in range(t)) / t     # a token's keys
            total += hq * 2 * pairs * (nope + pe + dv)
        if i < cfg["first_k_dense_replace"]:
            total += 3 * 2 * d * cfg["intermediate_size"]
        else:
            f = cfg["moe_intermediate_size"]
            total += 2 * d * cfg["router_experts"] + 3 * 2 * d * f
            total += (cfg["num_experts_per_token"] * cfg["num_experts"]
                      / cfg["router_experts"]) * 3 * 2 * d * f
    return total + 2 * d * cfg["vocab_size"]


@pytest.mark.parametrize("sizes", ["tiny", "published"])
def test_train_flops_are_a_brute_force_count(sizes):
    cfg = tiny.config(CONFIG) if sizes == "tiny" else full_config()
    t, chunk = (16, 8) if sizes == "tiny" else (4096, 64)
    fam = models.family(cfg)
    want = 3.0 * t * brute_force_matmul_flops(cfg, t, chunk)
    # the count takes a causal call as t^2 / 2 pairs; a loop finds
    # t (t + 1) / 2
    assert fam.train_flops(cfg, 1, t) == pytest.approx(want, rel=2e-3 if
                                                       sizes != "tiny"
                                                       else 2e-2)
    if sizes == "published":
        assert want == pytest.approx(9.1e12, rel=0.03)    # ISSUE 64
        cost = fam.attention_cost(cfg, 1, t)
        assert cost["calls"] == 2               # ONE triangle each way
        assert cost["flops"] == pytest.approx(
            3.0 * 32 * 4096 * 4096 * (192 + 128))
        assert cost["bytes"] == 6 * 4096 * 32 * 320 * 2


def test_kda_scan_cost_counts_four_layers_forward_and_twice_backward():
    cfg = full_config()
    cost = fk.kda_scan_cost(cfg, 1, 4096, 64)
    per_tok = 32 * (4 * 64 * 128 + 64 * 256 + 2 * 64 * 128 + 6 * 128 * 128)
    assert fk.kda_scan_flops_per_token(cfg, 64) == per_tok
    assert cost["calls"] == 8
    assert cost["flops"] == 3 * 4 * 4096 * per_tok           # 0.258 TFLOP
    # q, k, v, o bf16; g a float32 [t, 32, 128]; beta float32; and their
    # gradients
    moved = 4096 * (4 * 4096 * 2 + 4096 * 4 + 32 * 4)
    assert cost["bytes"] == 2 * 4 * moved
    peaks = harness.peaks_for("TPU v5 lite")
    # the float32 gate a feature makes the calls byte-bound
    assert cost["bytes"] / peaks["hbm_bytes_per_s"] > (
        cost["flops"] / peaks["bf16_flops_per_s"])
    assert fk.kda_scan_cost(dict(cfg, num_hidden_layers=8), 1, 4096,
                            64)["calls"] == 12
    # the rule is about 3% of the counted work (ISSUE 64)
    share = cost["flops"] / fk.kimilinear_train_flops(cfg, 1, 4096, 64)
    assert 0.02 < share < 0.04


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
    "fwd/blk0/kda/rms_norm": 1.0,
    "fwd/blk0/kda/proj/mul": 9.0,
    "fwd/blk0/kda/conv/causal_conv1d": 2.0,
    "fwd/blk0/kda/rule/gated_delta_rule": 6.0,
    "bwd/blk1/kda/rule/gated_delta_rule_grad": 12.0,
    "bwd/blk1/kda/rule/gdn_gates_grad": 2.0,
    "bwd/blk1/kda/out/mul_grad": 8.0,
    "fwd/blk3/attn/kv_lora/mul": 4.0,
    "fwd/blk3/attn/rope/concat": 1.0,
    "fwd/blk3/attn/core/scaled_dot_product_attention": 5.0,
    "fwd/blk3/moe/shared/mul": 3.0,
    "fwd/kda/mul": 5.0,            # a scope named kda outside a block
    "opt/adam": 10.0,
}
METRICS = ("kda.step_share.train", "kda.scan_share.train",
           "kda.scan_roofline.train", "lower.xla_kda_calls.train")


def test_kda_readers_sum_the_mixers_scopes():
    run = scopes_run(BY_SCOPE)
    assert read("kda.step_share.train", run) == pytest.approx(40.0)
    assert read("kda.scan_share.train", run) == pytest.approx(
        100 * (6 + 12 + 2) / 40.0)
    # the latent layer's readers read this family's scopes too
    assert read("mla.step_share.train", run) == pytest.approx(10.0)
    assert read("mla.assemble_share.train", run) == pytest.approx(10.0)
    assert read("moe.step_share.train", run) == pytest.approx(3.0)
    assert read("step.block_share.train", run) == pytest.approx(53.0)
    # the scalar rule's readers find no scope of theirs here
    assert read("gdn.step_share.train", run) is None


def test_kda_readers_report_nothing_for_a_program_without_the_layer():
    """A parent's tree, or another family's cell: no ``kda`` scope and
    no counter row with the label. None, and no exception."""
    run = scopes_run({k: v for k, v in BY_SCOPE.items()
                      if "/kda/" not in k or not k.split("/")[1].startswith(
                          "blk")})
    monitor.reset()
    for metric in METRICS:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    assert read("kda.step_share.train", run) is None


def test_every_new_metric_is_listed_for_the_cell_and_moves_the_rate():
    by_name = {m["name"]: m for m in tiny.BENCH["per_layer"]}
    for metric in METRICS:
        entry = by_name[metric]
        assert entry["workloads"] == [CELL], metric
        assert entry["moves"] == "train_tokens_per_s"
    assert by_name["kda.scan_roofline.train"]["source"] == "device_trace"
    assert by_name["lower.xla_kda_calls.train"]["source"] == \
        "program_counter"
    for metric in ("gdn.scan_roofline.train", "moe.gmm_roofline.train",
                   "rope.step_share.train", "mtp.step_share.train"):
        assert CELL not in by_name[metric]["workloads"], metric


def test_roofline_and_xla_count_read_the_rows_with_a_feature_gate():
    from paddle_tpu.core import interp
    from paddle_tpu.ops import linear_attention_ops as L

    q = v = jnp.zeros((1, 16, 2, 8))
    g_feature, g_head = jnp.zeros((1, 16, 2, 8)), jnp.zeros((1, 16, 2))

    def note(*a):
        tok = interp.set_amp_active(False)
        try:
            L._note_dispatch(*a)
        finally:
            interp._AMP_ACTIVE.reset(tok)

    flags.set_flags({"telemetry": True})
    monitor.reset()
    try:
        for direction in ("fwd", "bwd"):
            for _ in range(4):
                note(direction, q, v, 64, "kernel", g_feature)
        # a scalar-gate call of another chunk in the same process: not
        # this reader's
        note("fwd", q, v, 32, "chunked", g_head)
        rows = kda_spans.dispatch_rows()
        assert sum(n for _, n in rows) == 8
        run = scopes_run(BY_SCOPE)
        cost = fk.kda_scan_cost(full_config(), 1, 4096, 64)
        peaks = harness.peaks_for("TPU v5 lite")
        least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                    cost["bytes"] / peaks["hbm_bytes_per_s"])
        # 20 ns under kda/rule for one step
        assert read("kda.scan_roofline.train", run) == pytest.approx(
            100 * least / 20e-9)
        assert read("lower.xla_kda_calls.train", run) == 0
        note("fwd", q, v, 64, "chunked", g_feature)      # a refused tile
        note("bwd", q, v, 1, "recurrent", g_feature)
        assert read("lower.xla_kda_calls.train", run) == 2
        assert read("kda.scan_roofline.train", run) == pytest.approx(
            100 * least / 20e-9)
        # two chunk sizes among the feature rows: which one the time is
        # of is not known, so nothing is reported
        note("fwd", q, v, 32, "chunked", g_feature)
        assert read("kda.scan_roofline.train", run) is None
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_a_tiny_traced_program_counts_xla_calls_where_the_tile_is_refused():
    """The family's tiny program lowered with telemetry on, on the CPU
    (no tile: float32 heads of 8): every KDA call is the chunked XLA
    form, ``lower.xla_kda_calls.train`` counts them, three layers each
    way, and the chunk they report is the configuration's."""
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
        rows = kda_spans.dispatch_rows()
        assert {lb["impl"] for lb, _ in rows} == {"chunked"}
        assert {lb["chunk"] for lb, _ in rows} == {"8"}
        run = scopes_run(BY_SCOPE)
        assert read("lower.xla_kda_calls.train", run) == 6
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()
