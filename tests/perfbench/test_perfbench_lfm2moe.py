"""What is the LFM2-MoE family's own in the benchmark: the
configuration's cut against the catalog's row, its parameters by layer,
FLOPs and bytes, the second check against a lower-precision control and
the six ablations, and the gated-convolution readers
(perf/sconv_spans.py and four metrics that BENCHMARK.json does not list
yet: PERF.md section 7 (20))."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_lfm2moe as fl
from perf import harness, models, sconv_spans
from perf.kinds import train
from perf.reference import lfm2moe as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CONFIG, CELL = "lfm2-24b-a2b", "lfm2moe-train-s8192"
BLOCKS = [(1, "sconv", True), (2, "attn", False), (3, "sconv", False),
          (4, "sconv", False), (5, "sconv", False)]
NEW = ("sconv.step_share.train", "sconv.gate_share.train",
       "sconv.roofline.train", "lower.xla_sconv_calls.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import lfm2_moe as M

    cfg, pub = full_config(), M.Lfm2MoeConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": 5, "vocab_size": 8192, "first_layer": 1,
           "held_experts": (0, 8)}
    for key, value in vars(pub).items():
        assert getattr(pcfg, key) == cut.get(key, value), key
    assert pcfg.num_experts == 64                # the router's outputs
    assert (pcfg.head_dim, pcfg.conv_L_cache, pcfg.rope_theta) == (
        64, 3, 1e6)
    assert cfg["reduced_from"] == {"num_hidden_layers": 40,
                                   "num_experts": 64, "vocab_size": 65536}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    assert cfg["vocab_size"] * 8 == cfg["reduced_from"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["router_experts"] == 64
    # the share starts at expert 0 and the file says so (since PR 54)
    assert cfg["held_first"] == 0 and "held share" in cfg["assumed"]
    assert next(iter(cfg["assumed"])) == "tied table"
    for key in ("source", "the_cut", "assumed", "deployment"):
        assert cfg[key], key
    assert "8 chips" in cfg["deployment"]
    # layer_types is kept whole and the five blocks read their own
    # entries and the count of leading dense layers for themselves
    assert len(cfg["layer_types"]) == 40 == cfg["model_layers"]
    assert tuple(cfg["layer_types"]) == M.LAYER_TYPES
    assert pcfg.blocks == BLOCKS == ref.blocks(cfg)
    assert fl.blocks(cfg) == [(k, d) for _, k, d in BLOCKS]
    assert [cfg["layer_types"][i] for i, _, _ in BLOCKS] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (fl.count(cfg, "sconv"), fl.count(cfg, "attn"),
            fl.count(cfg, "dense"), fl.count(cfg, "moe")) == (4, 1, 1, 4)
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS == 64
    traffic = harness.load_json("perf", "workloads", f"{CELL}.json")["traffic"]
    assert (traffic["batch"], traffic["seq_len"], traffic["name"]) == (
        1, 8192, "b1-s8192")


def test_every_width_is_the_catalog_rows():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog of architectures on this machine")
    row = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
    cfg = full_config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["reduced_from"][key] == value
        else:
            assert cfg[key] == value, key


def test_parameters_as_built_are_the_issues_count_layer_by_layer():
    cfg = full_config()
    main, _, _, _, _ = models.build_train(cfg, 3)
    by_block, by_name = {}, {}
    for p in main.all_parameters():
        key = p.name.split("_")[0] if p.name.startswith("blk") else p.name
        by_block[key] = by_block.get(key, 0) + int(np.prod(p.shape))
        by_name[p.name] = tuple(p.shape)
    assert sum(by_block.values()) == pytest.approx(469.28e6, rel=1e-4)
    # the tied table: one parameter, no head matrix
    assert by_block["lfm2_tok_emb.w"] == 8192 * 2048
    assert not any("head" in n for n in by_name)
    want = {1: 89.14e6, 2: 86.12e6, 3: 92.42e6, 4: 92.42e6, 5: 92.42e6}
    for i, total in want.items():
        assert by_block[f"blk{i}"] == pytest.approx(total, rel=2e-4), i
    assert by_name["blk1_sconv_in_colp.w"] == (2048, 6144)
    assert by_name["blk1_sconv_conv.w"] == (2048, 3)
    assert by_name["blk1_ffn_w1_colp.w"] == (2048, 11776)
    assert by_name["blk2_attn_qkv_colp.w"] == (2048, (32 + 2 * 8) * 64)
    assert by_name["blk2_attn_qnorm.scale"] == (64,) \
        == by_name["blk2_attn_knorm.scale"]
    assert by_name["blk2_moe_router.w"] == (2048, 64)
    assert by_name["blk2_moe_gate.w"] == (8, 2048, 1536)
    assert by_name["blk2_moe_down.w"] == (8, 1536, 2048)
    assert "blk2_sconv_conv.w" not in by_name and "blk1_moe_up.w" \
        not in by_name


# --- the FLOPs and the bytes -------------------------------------------------


def test_train_flops_count_what_does_the_work():
    cfg = full_config()
    t = 8192
    fwd = fl.forward_flops_per_token(cfg, t)
    # the issue's arithmetic, forward FLOPs a token at 8192 positions
    assert fwd["sconv"] == pytest.approx(134.3e6, rel=0.005)
    assert fwd["dense"] == pytest.approx(144.7e6, rel=0.005)
    assert fwd["attn"] == pytest.approx(54.6e6, rel=0.005)
    assert fwd["moe"] == pytest.approx(37.7e6 + 1.05e6, rel=0.005)
    assert fwd["head"] == pytest.approx(33.6e6, rel=0.005)
    assert sum(fwd.values()) == pytest.approx(405.9e6, rel=0.002)
    attn = fl.attention_cost(cfg, 1, t)
    assert attn["flops"] / 3 / t == pytest.approx(33.6e6, rel=0.005)
    assert attn["calls"] == 2
    total = fl.lfm2moe_train_flops(cfg, 1, t)
    assert total == pytest.approx(3 * t * sum(fwd.values()), rel=1e-9)
    assert total == pytest.approx(9.98e12, rel=0.005)
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, t) == total
    assert fam.attention_cost(cfg, 1, t) == attn
    # each held expert's rows a step: an eighth of the deployment's
    assert t * cfg["num_experts_per_tok"] * fl.held_share(cfg) \
        / cfg["num_experts"] == 512


def test_gated_convolution_bytes():
    cfg = full_config()
    cost = fl.sconv_cost(cfg, 1, 8192)
    a_layer = 11 * 8192 * 2048 * 2               # 369 MB a layer and step
    # by placement: the 5 t c that cross HBM wherever XLA keeps the
    # projection (y; dy, d[B | C | u]), which the roofline divides
    in_hbm = 5 * 8192 * 2048 * 2                 # 168 MB a layer and step
    assert cost == {"flops": 0.0, "bytes": float(4 * a_layer),
                    "hbm_bytes": float(4 * in_hbm), "calls": 8}
    assert a_layer == pytest.approx(369.1e6, rel=1e-3)
    assert cost["bytes"] == pytest.approx(1.476e9, rel=1e-3)
    assert cost["hbm_bytes"] == pytest.approx(0.671e9, rel=1e-3)
    # 0.82 ms of HBM time a step: under the kernels' 1.82 ms on the chip
    assert cost["hbm_bytes"] / 819e9 == pytest.approx(0.82e-3, rel=5e-3)
    # no such block, no cost
    none = dict(cfg, num_hidden_layers=1, first_layer=2)
    assert fl.sconv_cost(none, 1, 8192)["bytes"] == 0
    assert fl.attention_cost(dict(cfg, num_hidden_layers=1), 1, 8192)[
        "calls"] == 0


# --- the second check --------------------------------------------------------


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
    # (projections large enough that what the taps keep and what a query
    # sees reach the logits; the QK-norms' gains and the selection bias
    # are the family's startup program's: build_graph holds them where
    # dropping the norm or ignoring the bias is another model)
    r = np.random.RandomState(0)
    for k, v in w.items():
        if k.endswith(("_colp.w", "_rowp.w", "_gate.w", "_up.w", "_down.w",
                       "_tok_emb.w")):
            w[k] = (0.3 * r.randn(*v.shape)).astype(np.float32)
            scope.set(k, jnp.asarray(w[k]))
    fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
        evalp, feed=sample, fetch_list=fetch, scope=scope)])
    return cfg, w, sample, fetched


def as_program(w, cfg, sample, fetched, **kw):
    out = ref.forward(w, cfg, sample["input_ids"], last=ref.LAST_POSITIONS,
                      **kw)
    return ref.second_check(w, cfg, sample, dict(
        fetched, last_logits=out["logits"],
        top_i=[np.asarray(t) for t in out["top_i"]]))


def test_a_run_starts_where_the_qk_norm_and_the_selection_bias_act(
        sample_readings):
    """``correct`` is read before the first step: the family's startup
    program holds the QK-norms' gains off 1 (drawn from the seed) and
    the selection bias off 0 (+- by the expert's parity, so that the
    held experts carry as many of each sign), every other gain at 1."""
    cfg, w, _, _ = sample_readings
    fam = models.family(cfg)
    mean, std = fam.QK_GAIN
    gains = {k: v for k, v in w.items() if k.endswith("norm.scale")}
    qk = [k for k in gains if k.endswith(("_qnorm.scale", "_knorm.scale"))]
    assert sorted(qk) == ["blk2_attn_knorm.scale", "blk2_attn_qnorm.scale"]
    for k, g in gains.items():
        if k in qk:
            assert np.all(np.abs(g - mean) < 5 * std) and g.std() > 0
        else:
            assert np.all(g == 1)
    assert not np.array_equal(gains[qk[0]], gains[qk[1]])
    biases = [v for k, v in w.items() if k.endswith("_router.bias")]
    assert len(biases) == 4
    for b in biases:
        assert b.shape == (cfg["router_experts"],)
        np.testing.assert_allclose(
            b, np.resize([fam.SELECT_BIAS, -fam.SELECT_BIAS], b.shape))
        assert b[:cfg["num_experts"]].sum() == 0


def test_second_check_passes_the_program(sample_readings, monkeypatch):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, record = ref.second_check(w, cfg, sample, fetched)
        assert set(record) == {
            "logit_err_over_rms", "logit_max_err_over_rms",
            "positions_compared", "positions", "flipped_share",
            "max_expert_load", "held_row_share", "limits"}
        # the tiny row is 16 positions: all of them are "last"
        assert record["positions"] == 8 * 16
        assert record["positions_compared"] > 0
        assert 0 < record["logit_err_over_rms"] < 0.1
        assert 0 < record["held_row_share"] < 1
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT",
                            2 * record["logit_err_over_rms"])
        monkeypatch.setattr(ref, "FLIP_LIMIT",
                            2 * record["flipped_share"] + 0.01)
        problems, _ = ref.second_check(w, cfg, sample, fetched)
    assert problems == []


def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands rounded to
    # float8, judged as if it were the program. The limits in the file
    # are the chip's, between readings at the published widths; at the
    # tiny sizes they are set here as there: at the geometric middle.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        _, record = as_program(w, cfg, sample, fetched,
                               round_to=jnp.float8_e4m3fn)
        assert record["logit_err_over_rms"] \
            > 2 * program["logit_err_over_rms"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        monkeypatch.setattr(ref, "FLIP_LIMIT", 1.0)
        problems, _ = as_program(w, cfg, sample, fetched,
                                 round_to=jnp.float8_e4m3fn)
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 1 and passes == []
    assert "logits differ" in problems[0]


@pytest.mark.parametrize("ablation", ref.ABLATIONS)
def test_a_reference_without_the_mechanism_is_another_model(sample_readings,
                                                            ablation):
    """The B gate dropped, the C gate dropped, the convolution cut to
    its last tap, the taps reversed, the per-head QK-norm dropped, the
    selection bias ignored in the choice: judged as if they were the
    program they move the logits or the choices by more than its
    rounding."""
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        _, record = as_program(w, cfg, sample, fetched, ablate=ablation)
    # (an ablation that moves the choices compares few positions or
    # none: either reading shows it)
    assert (not record["positions_compared"]
            or record["logit_err_over_rms"]
            > 1.5 * program["logit_err_over_rms"]
            or record["flipped_share"] > 2 * program["flipped_share"] + 0.01)


def test_the_reference_keeps_hfs_epsilon_and_the_program_leaves_it_out():
    """``w = s / (sum s + 1e-6)`` against ``s / sum s``: under 1e-6 of a
    weight where the chosen scores sum to 1 and more."""
    x = jnp.asarray(np.random.RandomState(0).randn(1, 6, 8), jnp.float32)
    wr = jnp.asarray(np.random.RandomState(1).randn(8, 8), jnp.float32)
    cfg = dict(num_experts_per_tok=4, norm_topk_prob=True,
               routed_scaling_factor=1)
    top_w, _, _ = ref.route(x, wr, jnp.zeros(8), cfg)
    total = np.asarray(top_w).sum(-1)
    assert (total < 1).all() and (total > 1 - 2e-6).all()


# --- the readers --------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1, kernel_s=0.0):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9,
                 "by_family_s": {"sconv": kernel_s} if kernel_s else {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": sum(
            v for k, v in by_scope.items() if k.split("/")[1] == "loss_head")}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk1/sconv/rms_norm": 1.0,
    "fwd/blk1/sconv/in_proj/mul": 6.0,
    "fwd/blk1/sconv/gconv/gated_short_conv": 1.0,
    "bwd/blk1/sconv/gconv/gated_short_conv_grad": 2.0,
    "fwd/blk3/sconv/out_proj/mul": 2.0,
    "bwd/blk3/sconv/in_proj/mul_grad": 8.0,
    "fwd/blk1/ffn/mul": 9.0,
    "fwd/blk2/attn/qk_norm/rms_norm": 1.0,
    "fwd/blk2/attn/core/scaled_dot_product_attention": 4.0,
    "fwd/blk2/moe/router/moe_router": 1.0,
    "fwd/blk2/moe/experts/moe_experts": 3.0,
    "fwd/loss_head/matmul": 6.0,
    "fwd/sconv/mul": 5.0,           # a scope named sconv outside a block
    "opt/adam": 10.0,
}


def test_sconv_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    mixers = 1 + 6 + 1 + 2 + 2 + 8
    assert read("sconv.step_share.train", run) == pytest.approx(mixers)
    assert read("sconv.gate_share.train", run) == pytest.approx(
        100 * (1 + 2) / mixers)
    # the readers that exist take the expert layers for expert layers
    # and none takes a gated convolution for another family's mixer
    assert read("moe.step_share.train", run) == pytest.approx(4.0)
    for metric in ("gdn.step_share.train", "gdn.scan_share.train",
                   "ssm.step_share.train", "mamba2.step_share.train",
                   "mamba2.scan_share.train", "mla.step_share.train",
                   "swa.step_share.train"):
        assert read(metric, run) is None, metric


def test_roofline_reads_the_kernels_time_and_the_counters_gated_rows():
    from paddle_tpu import flags, monitor
    from paddle_tpu.ops import linear_attention_ops as L

    cfg, peaks = full_config(), harness.peaks_for("TPU v5 lite")
    monitor.reset()
    run = scopes_run(BY_SCOPE, kernel_s=20e-9)
    traffic = run.cell["traffic"]               # the tiny cell: 8 x 16
    cost = fl.sconv_cost(cfg, traffic["batch"], traffic["seq_len"])
    least = cost["hbm_bytes"] / peaks["hbm_bytes_per_s"]
    assert 11 * cost["hbm_bytes"] == 5 * cost["bytes"]
    assert read("sconv.roofline.train", run) == pytest.approx(
        100 * least / 20e-9)
    two = scopes_run(BY_SCOPE, traced_steps=2, kernel_s=20e-9)
    assert read("sconv.roofline.train", two) == pytest.approx(
        200 * least / 20e-9)
    # no dispatch row yet
    assert read("lower.xla_sconv_calls.train", run) is None
    flags.set_flags({"telemetry": True})
    try:
        def note(impl, gated, direction="fwd"):
            labels = {"pass": direction, "shape": "b8 t16 c32", "taps": "3",
                      "impl": impl}
            if gated:
                labels["gated"] = "1"
            L._M_CONV_DISPATCH.inc(labels=labels)

        # a plain call's rows are not the gated reader's, whatever they
        # are, and the plain reader counts both kinds as before
        note("xla", False)
        assert read("lower.xla_sconv_calls.train", run) is None
        assert read("lower.xla_conv_calls.train", run) == 1
        note("kernel", True)
        note("kernel", True, "bwd")
        assert read("lower.xla_sconv_calls.train", run) == 0
        note("xla", True, "bwd")
        assert read("lower.xla_sconv_calls.train", run) == 1
        assert [lb["impl"] for lb, _ in sconv_spans.gated_rows()] == [
            "kernel", "kernel", "xla"]
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_readers_report_nothing_for_a_program_without_the_layers():
    """A parent's tree, or another family's cell: None, no exception."""
    from paddle_tpu import monitor

    monitor.reset()
    run = scopes_run({
        "fwd/blk0/attn/core/scaled_dot_product_attention": 10.0,
        "fwd/blk0/gdn/conv/causal_conv1d": 10.0,
        "fwd/blk0/attn/mul": 5.0, "fwd/loss_head/mul": 6.0,
        "opt/adam": 10.0})
    for metric in NEW:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    for metric in NEW:
        assert read(metric, run) is None, metric
    assert sconv_spans.summary(run) is None
    assert sconv_spans.kernel_s(run) == 0.0
    assert sconv_spans.gated_rows() == []


def test_the_new_readers_are_entries_that_list_the_cell():
    """The four are entries since PR 54 (they waited as files while a
    pin in tests/perfbench/ held ``per_layer``'s last entry), with the
    keys PERF.md section 3 gives them."""
    PL, K = "Program lowering", "Kernels"
    for metric, unit, better, source, layer in (
            ("sconv.step_share.train", "%", "lower", "program_span", PL),
            ("sconv.gate_share.train", "%", "lower", "program_span", PL),
            ("sconv.roofline.train", "%", "higher", "device_trace", K),
            ("lower.xla_sconv_calls.train", "count", "lower",
             "program_counter", PL)):
        assert metric in NEW
        assert tiny.listed_as(metric, unit, better, source, layer, CELL)
        assert callable(harness.reader_for(metric).read)
    on = {m["name"] for m in tiny.BENCH["end_to_end"] + tiny.BENCH["per_layer"]
          if CELL in m.get("workloads", ())}
    assert {"train_tokens_per_s", "moe.step_share.train",
            "moe.route_share.train", "moe.max_expert_load.train",
            "step.mfu.train", "train_attn_roofline",
            "device.peak_hbm_gb.train"} <= on
    # the lists that were closed to the cell until PR 54, the rotary
    # embedding's pair and the embedding gradient's
    assert {"step.block_share.train", "lower.split_bwd_attn_calls.train",
            "lower.whole_buffer_moe_calls.train",
            "lower.xla_conv_calls.train", "lower.xla_rope_calls.train",
            "rope.step_share.train", "lower.xla_embed_grad_calls.train",
            "embed.grad_share.train"} <= on
    assert "moe.gmm_roofline.train" not in on   # held cells: PERF.md 7
    entry = next(w for w in tiny.BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "b1-s8192", 1)
    assert CONFIG in tiny.CONFIGS


def test_a_traced_tiny_run_counts_its_convolutions_and_passes_both_checks(
        monkeypatch, tmp_path):
    from paddle_tpu import monitor

    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9})
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    # (the file's limits are the chip's at the published widths; the tiny
    # program under bf16 reads higher against 16 positions)
    monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", 0.3)
    monkeypatch.setattr(ref, "FLIP_LIMIT", 0.3)
    monitor.reset()
    cell = tiny.train_cell(CELL)
    cfg = tiny.config(cell["config"])
    assert cfg["held_first"] == 0
    run = tiny.make_run(cell, cfg, seconds=0.3, traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert run.check["rel"] < train.LOSS_REL_TOL
    assert run.check["second"]["positions"] == 8 * 16
    # without a TPU the four gated convolutions are the composition,
    # forward in the eval clone and the step, backward in the step: the
    # reader counts every one of them as not a kernel, and the plain
    # reader, which reads every row of the counter, counts them too
    rows = sconv_spans.gated_rows()
    assert rows and {lb["impl"] for lb, _ in rows} == {"xla"}
    assert {lb["shape"] for lb, _ in rows} == {"b8 t16 c32"}
    assert {lb["taps"] for lb, _ in rows} == {"3"}
    assert read("lower.xla_sconv_calls.train", run) \
        == sum(n for _, n in rows) >= 12
    assert read("lower.xla_conv_calls.train", run) \
        == read("lower.xla_sconv_calls.train", run)
    assert read("lower.dense_attn_calls.train", run) is not None
    # the metrics the cell is listed under are on its line
    for metric in ("moe.max_expert_load.train", "step.mfu.train"):
        assert metric in line["metrics"], metric
    # no device trace on a CPU: the span readers have nothing to read
    for metric in ("sconv.step_share.train", "sconv.gate_share.train",
                   "sconv.roofline.train"):
        assert read(metric, run) is None
    monitor.reset()
