"""What is the Phi-4-mini-flash family's own in the benchmark: the
configuration's cut, its FLOPs and bytes by layer kind, the second check
against lower-precision controls and against references that leave the
window or the scan out, and the state-space and differential-attention
readers (perf/ssm_spans.py and five metrics)."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_phi4flash as fp
from perf import harness, models, ssm_spans
from perf.kinds import train
from perf.reference import phi4flash as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CONFIG, CELL = "phi-4-mini-flash", "phi4flash-train-s4096"
KINDS = ["mamba", "swa", "mamba_mem", "full", "gmu", "cross"]
NEW = ("ssm.step_share.train", "ssm.scan_share.train",
       "ssm.scan_roofline.train", "lower.recurrent_ssm_calls.train",
       "attn.diff_share.train")


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_and_vocabulary_and_no_width():
    from paddle_tpu.models import phi4flash as M

    cfg, pub = full_config(), M.Phi4FlashConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": (32, 6), "vocab_size": (200064, 25008),
           "first_layer": (0, 14)}
    for key, value in vars(pub).items():
        assert getattr(pcfg, key) == cut.get(key, (None, value))[1], key
    assert cfg["reduced_from"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    assert cfg["vocab_size"] * 8 == cfg["reduced_from"]["vocab_size"]
    # every published key of the catalog's row, unchanged but the two
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    for key, value in published.items():
        assert cfg[key] == (value if key not in cfg["reduced"]
                            else cut[key][1]), key
    # the layers keep their published indices and every kind is held
    assert M.layer_kinds(pcfg) == list(zip(range(14, 20), KINDS))
    assert fp.layer_kinds(cfg) == KINDS
    assert [k for _, k in ref.layer_kinds(cfg)] == KINDS
    # the sizes the published file leaves to HF's defaults are assumed
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160) == (
        pub.mamba_d_state, pub.mamba_d_conv, pub.mamba_expand,
        pub.mamba_dt_rank)
    assert "mamba sizes" in cfg["assumed"] and "deployment" in cfg
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS and ref.TABLE == M.TABLE
    traffic = harness.load_json("perf", "workloads", f"{CELL}.json")["traffic"]
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)


def test_parameters_as_built_are_the_issues_count():
    cfg = full_config()
    main, _, _, _, _ = models.build_train(cfg, 3)
    by_layer = {}
    for p in main.all_parameters():
        key = p.name.split("_")[0] if p.name.startswith("blk") else p.name
        by_layer[key] = by_layer.get(key, 0) + int(np.prod(p.shape))
    total = sum(by_layer.values())
    assert total == pytest.approx(696.8e6, rel=0.01)
    assert by_layer["phi4flash_tok_emb.w"] == 25008 * 2560
    mlp = 3 * 2560 * 10240
    for i, kind in zip(range(14, 20), KINDS):
        # the mixer's matrices as perf/flops_phi4flash counts them, plus
        # what no row is multiplied by (norms, biases, conv, A_log, D)
        assert by_layer[f"blk{i}"] == pytest.approx(
            fp.mixer_params(cfg, kind) + mlp, rel=2e-3), kind
    assert by_layer["blk14"] == pytest.approx(119.77e6, rel=2e-3)
    assert by_layer["blk15"] == pytest.approx(98.30e6, rel=2e-3)
    assert by_layer["blk18"] == pytest.approx(104.86e6, rel=2e-3)
    assert by_layer["blk19"] == pytest.approx(91.75e6, rel=2e-3)


# --- the FLOPs and the bytes -------------------------------------------------


def test_train_flops_count_every_kind_of_layer():
    cfg = full_config()
    tok, d, f, e = 4096, 2560, 10240, 5120
    attn = fp.attention_cost(cfg, 1, 4096)
    matmul = fp.phi4flash_train_flops(cfg, 1, 4096) - attn["flops"]
    mlps = 6 * 3 * d * f
    mixers = (2 * (d * 2 * e + e * 192 + 160 * e + e * d)    # two Mambas
              + 2 * (d * 5120 + 2560 * d)                    # window, full
              + 2 * d * e                                    # GMU
              + 2 * d * 2560)                                # cross
    head = d * 25008
    assert matmul == pytest.approx(6.0 * tok * (mlps + mixers + head))
    # the issue's arithmetic: 17.1 TFLOP of parameter matmuls, the MLPs
    # 11.6, the head 1.57
    assert matmul == pytest.approx(17.1e12, rel=0.01)
    assert 6.0 * tok * mlps == pytest.approx(11.6e12, rel=0.01)
    assert 6.0 * tok * head == pytest.approx(1.57e12, rel=0.01)
    assert 6.0 * tok * mlps / (matmul + attn["flops"]) == pytest.approx(
        0.64, abs=0.01)


def test_attention_cost_is_a_band_and_two_triangles_twice():
    cfg = full_config()
    cost = fp.attention_cost(cfg, 1, 4096)
    triangle = 4096 * 4097 // 2
    band = 512 * 513 // 2 + (4096 - 512) * 512
    assert band / triangle == pytest.approx(0.234, abs=0.001)
    # two maps a layer, 20 pair-heads a map, 64 wide over values of 128:
    # q.k^T, dq, dk at 64 and p.v, dv, dp at 128, a multiply-add 2
    per_pair = 2 * 20 * 2 * (3 * 64 + 3 * 128)
    assert cost["flops"] == pytest.approx(per_pair * (band + 2 * triangle))
    assert cost["calls"] == 12
    assert cost["flops"] == pytest.approx(0.86e12, rel=0.01)
    # a cut without the window layer counts triangles alone
    two = dict(cfg, num_hidden_layers=4, first_layer=16)
    assert fp.attention_cost(two, 1, 4096)["flops"] == pytest.approx(
        per_pair * 2 * triangle)


def test_ssm_scan_cost_counts_bytes_and_no_flops():
    cfg = full_config()
    cost = fp.ssm_scan_cost(cfg, 1, 4096)
    tok, e, n = 4096, 5120, 16
    gated = tok * (8 * e + 4 * n) * 2 + 32 * e * n * 4
    plain = tok * (6 * e + 4 * n) * 2 + 32 * e * n * 4
    assert cost == {"flops": 0.0, "bytes": float(gated + plain), "calls": 4,
                    "updates": float(2 * tok * e * n)}
    # a longer block saves fewer states
    assert fp.ssm_scan_cost(cfg, 1, 4096, block=256)["bytes"] < cost["bytes"]
    # no scan layer, no cost
    none = dict(cfg, num_hidden_layers=2, first_layer=17, model_layers=32)
    assert fp.ssm_scan_cost(none, 1, 4096)["bytes"] == 0


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
    # (projections large enough that what a query sees reaches the
    # logits; step sizes of 0.3 with slow decays and a small skip D, in
    # place of Mamba's initial 0.001-0.1, 1..n and 1, so that what a
    # state keeps over 16 positions does)
    r = np.random.RandomState(0)
    for k, v in w.items():
        if k.endswith(("_colp.w", "_rowp.w", "_ssm_dt.w")):
            w[k] = (0.3 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith(("_ssm_dt.b", "_ssm_a_log")):
            w[k] = (-1.0 + 0.1 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith("_ssm_d"):
            w[k] = np.full(v.shape, 0.1, np.float32)
        else:
            continue
        scope.set(k, jnp.asarray(w[k]))
    fetched = jax.tree.unflatten(shape, [np.asarray(g) for g in exe.run(
        evalp, feed=sample, fetch_list=fetch, scope=scope)])
    return cfg, w, sample, fetched


def test_second_check_passes_the_program(sample_readings, monkeypatch):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, record = ref.second_check(w, cfg, sample, fetched)
        assert set(record) == {"logit_err_over_rms",
                               "logit_max_err_over_rms", "positions",
                               "limits"}
        # the tiny row is 16 positions: all of them are "last"
        assert record["positions"] == 8 * 16
        assert 0 < record["logit_err_over_rms"] < 0.1
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT",
                            2 * record["logit_err_over_rms"])
        problems, _ = ref.second_check(w, cfg, sample, fetched)
    assert problems == []


@pytest.mark.parametrize("control", ["float8_e4m3fn", "float8_e5m2"])
def test_second_check_fails_a_float8_rounded_control(sample_readings,
                                                     control, monkeypatch):
    # the nearest precision below the configuration's bf16: the
    # reference itself with every weight matmul's operands rounded to
    # float8, judged as if it were the program. The limit in the file is
    # the chip's, between readings at the published widths; at the tiny
    # sizes it is set here as there: at the geometric middle of the two.
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        low = ref.forward(w, cfg, sample["input_ids"],
                          round_to=getattr(jnp, control),
                          last=ref.LAST_POSITIONS)
        _, record = ref.second_check(w, cfg, sample,
                                     {"last_logits": low["logits"]})
        assert record["logit_err_over_rms"] \
            > 3 * program["logit_err_over_rms"]
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        problems, _ = ref.second_check(w, cfg, sample,
                                       {"last_logits": low["logits"]})
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 1 and passes == []
    assert "logits differ" in problems[0]


@pytest.mark.parametrize("ablation", ["no_window", "no_scan"])
def test_a_reference_without_the_mechanism_is_another_model(sample_readings,
                                                            ablation):
    """The window dropped (a window of 5 over 16 positions), or the
    scan's state forgotten at every position: judged as if they were the
    program they move the logits by far more than its rounding."""
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        want = ref.forward(w, cfg, sample["input_ids"],
                           last=ref.LAST_POSITIONS)
        other = ref.forward(w, cfg, sample["input_ids"],
                            last=ref.LAST_POSITIONS, **{ablation: True})
    record = ref.compare(want["logits"], other["logits"])
    # (the tiny program under bf16 rounds coarsely: 0.06 here, 0.019 on
    # the chip at the published widths, where the window dropped reads
    # 0.33)
    assert record["logit_err_over_rms"] > 1.5 * program["logit_err_over_rms"]
    # the first position sees and remembers the same either way
    np.testing.assert_allclose(np.asarray(other["logits"])[:, 0],
                               np.asarray(want["logits"])[:, 0],
                               rtol=1e-4, atol=1e-5)


# --- the readers --------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1, kernel_s=0.0):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9,
                 "by_family_s": {"ssm": kernel_s} if kernel_s else {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": sum(
            v for k, v in by_scope.items() if k.split("/")[1] == "loss_head")}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk14/ssm/layer_norm": 1.0,
    "fwd/blk14/ssm/proj/mul": 5.0,
    "fwd/blk14/ssm/conv/causal_conv1d": 1.0,
    "fwd/blk14/ssm/xproj/mul": 1.0,
    "fwd/blk14/ssm/sscan/selective_scan": 3.0,
    "bwd/blk14/ssm/sscan/selective_scan_grad": 9.0,
    "bwd/blk16/ssm/sscan/selective_scan_grad": 8.0,
    "fwd/blk16/ssm/gate/elementwise_mul": 1.0,
    "bwd/blk16/ssm/out/mul_grad": 3.0,
    "fwd/blk18/gmu/mul": 4.0,
    "fwd/blk15/attn/swa/scaled_dot_product_attention": 2.0,
    "fwd/blk17/attn/core/scaled_dot_product_attention": 4.0,
    "bwd/blk19/attn/cross/scaled_dot_product_attention_grad": 6.0,
    "fwd/blk17/attn/diff/diff_attention_combine": 0.5,
    "bwd/blk19/attn/diff/diff_attention_combine_grad": 1.5,
    "fwd/blk15/mlp/mul": 10.0,
    "fwd/loss_head/matmul": 6.0,
    "fwd/ssm/mul": 5.0,             # a scope named ssm outside a block
    "opt/adam": 10.0,
}


def test_ssm_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    ssm = 1 + 5 + 1 + 1 + 3 + 9 + 8 + 1 + 3
    assert read("ssm.step_share.train", run) == pytest.approx(ssm + 4)
    assert read("ssm.scan_share.train", run) == pytest.approx(
        100 * (3 + 9 + 8) / ssm)
    assert read("attn.diff_share.train", run) == pytest.approx(2.0)
    # the readers that exist count these layers as blocks, the window
    # layer as a window, and none takes them for a delta rule or experts
    assert read("swa.step_share.train", run) == pytest.approx(2.0)
    # (since PR 54 step.block_share.train starts from the whole table
    # by scope and not from the expert layers': a block needs no ``moe``
    # scope to count, and the cell is on its list)
    blocks = sum(v for k, v in BY_SCOPE.items() if "/blk" in k)
    assert read("step.block_share.train", run) == pytest.approx(blocks)
    assert CELL in tiny.cells_named(tiny.BENCH, "step.block_share.train")
    for metric in ("gdn.step_share.train", "gdn.scan_share.train",
                   "gdn.scan_roofline.train", "moe.step_share.train",
                   "mla.step_share.train"):
        assert read(metric, run) is None, metric


def test_scan_roofline_reads_the_kernels_time_and_the_counters_block():
    from paddle_tpu import flags, monitor
    from paddle_tpu.ops import selective_scan_ops as S

    cfg, peaks = full_config(), harness.peaks_for("TPU v5 lite")
    monitor.reset()
    run = scopes_run(BY_SCOPE, kernel_s=20e-9)
    # no dispatch row yet: nothing says which block the states are of
    assert read("ssm.scan_roofline.train", run) is None
    assert read("lower.recurrent_ssm_calls.train", run) is None
    flags.set_flags({"telemetry": True})
    try:
        def note(impl, chunk, direction="fwd"):
            S._M_DISPATCH.inc(labels={
                "pass": direction, "shape": "b8 t16 e64 n4",
                "chunk": str(chunk), "impl": impl})

        note("kernel", 128)
        note("kernel", 128, "bwd")
        assert read("lower.recurrent_ssm_calls.train", run) == 0
        traffic = run.cell["traffic"]           # the tiny cell: 8 x 16
        cost = fp.ssm_scan_cost(cfg, traffic["batch"], traffic["seq_len"],
                                128)
        least = cost["bytes"] / peaks["hbm_bytes_per_s"]
        assert read("ssm.scan_roofline.train", run) == pytest.approx(
            100 * least / 20e-9)
        two = scopes_run(BY_SCOPE, traced_steps=2, kernel_s=20e-9)
        assert read("ssm.scan_roofline.train", two) == pytest.approx(
            200 * least / 20e-9)
        # calls that are not kernels are counted, whichever form
        note("chunked", 64)
        note("recurrent", 1, "bwd")
        assert read("lower.recurrent_ssm_calls.train", run) == 2
    finally:
        flags.set_flags({"telemetry": False})
        monitor.reset()


def test_readers_report_nothing_for_a_program_without_the_layers():
    """A parent's tree, or another family's cell: None, no exception."""
    from paddle_tpu import monitor

    monitor.reset()
    run = scopes_run({
        "fwd/blk0/attn/core/scaled_dot_product_attention": 10.0,
        "fwd/blk0/gdn/rule/gated_delta_rule": 10.0,
        "fwd/blk0/attn/mul": 5.0, "fwd/loss_head/mul": 6.0,
        "opt/adam": 10.0})
    for metric in NEW:
        assert read(metric, run) is None, metric
    run._spans = None
    run.trace = None
    for metric in NEW:
        assert read(metric, run) is None, metric
    assert ssm_spans.summary(run) is None and ssm_spans.kernel_s(run) == 0.0
    assert ssm_spans.dispatch_rows() == []


def test_the_new_readers_are_entries_that_list_the_cell():
    """The five are entries since PR 54 (they waited as files while a
    pin in tests/perfbench/ held ``per_layer``'s last entry), with the
    keys PERF.md section 3 gives them; and the cell is on the lists that
    were closed to it."""
    PL, K = "Program lowering", "Kernels"
    for metric, unit, better, source, layer in (
            ("ssm.step_share.train", "%", "lower", "program_span", PL),
            ("ssm.scan_share.train", "%", "lower", "program_span", PL),
            ("ssm.scan_roofline.train", "%", "higher", "device_trace", K),
            ("lower.recurrent_ssm_calls.train", "count", "lower",
             "program_counter", PL),
            ("attn.diff_share.train", "%", "lower", "program_span", PL)):
        assert metric in NEW
        assert tiny.listed_as(metric, unit, better, source, layer, CELL)
        assert tiny.cells_named(tiny.BENCH, metric) == [CELL]
    for metric in ("step.block_share.train", "lower.xla_conv_calls.train",
                   "lower.split_bwd_attn_calls.train",
                   "lower.xla_embed_grad_calls.train",
                   "embed.grad_share.train"):
        assert CELL in tiny.cells_named(tiny.BENCH, metric), metric
    # no expert layer, no rotary embedding: not on those lists
    for metric in ("lower.ragged_moe_calls.train",
                   "lower.whole_buffer_moe_calls.train",
                   "lower.xla_rope_calls.train", "rope.step_share.train"):
        assert CELL not in tiny.cells_named(tiny.BENCH, metric), metric


def test_a_traced_tiny_run_counts_its_scans_and_passes_both_checks(
        monkeypatch, tmp_path):
    from paddle_tpu import monitor

    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9})
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    # (the file's limit is the chip's at the published widths; the tiny
    # program under bf16 reads higher against 16 positions of unit logits)
    monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", 0.2)
    monitor.reset()
    cell = tiny.train_cell(CELL)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=0.3,
                        traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert run.check["rel"] < train.LOSS_REL_TOL
    assert run.check["second"]["positions"] == 8 * 16
    # without a TPU the two scans are the chunked XLA form, forward in
    # the eval clone and the step, backward in the step: the reader
    # counts every one of them as not a kernel
    rows = ssm_spans.dispatch_rows()
    assert rows and {lb["impl"] for lb, _ in rows} == {"chunked"}
    assert {lb["shape"] for lb, _ in rows} == {"b8 t16 e64 n4"}
    assert read("lower.recurrent_ssm_calls.train", run) \
        == sum(n for _, n in rows) >= 4
    # the conv's counter sees Mamba's convolutions too
    assert read("lower.xla_conv_calls.train", run) >= 4
    # no device trace on a CPU: the span readers have nothing to read
    for metric in ("ssm.step_share.train", "ssm.scan_share.train",
                   "ssm.scan_roofline.train", "attn.diff_share.train"):
        assert read(metric, run) is None
    monitor.reset()
