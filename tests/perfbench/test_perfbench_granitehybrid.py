"""What is the Granite-4.0-H family's own in the benchmark: the
configuration's cut against the catalog's row, its parameters, FLOPs and
bytes by layer kind, the second check against a lower-precision control
and the three mechanism controls, and the readers of recomputation and
of the one-group scan (perf/recompute_spans.py and three metrics)."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_granitehybrid as fg
from perf import harness, models, recompute_spans
from perf.kinds import train
from perf.reference import granitehybrid as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CONFIG, CELL = "granite-4.0-h-micro", "granite-train-s16384"
KINDS = ["mamba2"] * 5 + ["attn"] + ["mamba2"] * 4
NEW = ("recompute.step_share.train", "lower.recomputed_ops.train",
       "mamba2.group_scan_roofline.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_and_vocabulary_and_no_width():
    from paddle_tpu.models import granite_hybrid as M

    cfg, pub = full_config(), M.GraniteHybridConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": 10, "vocab_size": 12544,
           "recompute": "layer"}
    for key, value in vars(pub).items():
        assert getattr(pcfg, key) == cut.get(key, value), key
    assert cfg["reduced_from"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    assert cfg["vocab_size"] * 8 == cfg["reduced_from"]["vocab_size"]
    for key in ("source", "the_cut", "assumed", "deployment"):
        assert cfg[key], key
    # the chunk is where states are saved, not mathematics: the file
    # keeps the published 256, the scan runs at the kernels' 128
    assert cfg["mamba_chunk_size"] == 256 and cfg["kernel_chunk"] == 128
    assert pcfg.mamba_chunk_size == 128 == ref.KERNEL_CHUNK
    assert "kernel chunk" in cfg["assumed"]
    # one whole period with its published indices, 9 : 1 as 36 : 4
    assert [k for _, k in pcfg.blocks] == KINDS == fg.layer_kinds(cfg)
    assert [i for i, _ in pcfg.blocks] == list(range(10))
    assert ref.blocks(cfg) == pcfg.blocks
    assert len(cfg["layer_types"]) == 40 == cfg["model_layers"]
    assert tuple(cfg["layer_types"]) == M.LAYER_TYPES
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS and ref.TABLE == M.TABLE
    cell = harness.load_json("perf", "workloads", f"{CELL}.json")
    assert (cell["traffic"]["batch"], cell["traffic"]["seq_len"]) == (
        1, 16384)
    assert cell["chips"] == 1 and cell["trace_seconds"] == 4.0


def test_every_width_is_the_catalog_rows():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog of architectures on this machine")
    row = next(r for r in rows if r["name"] == CONFIG)
    cfg = full_config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["reduced_from"][key] == value
        else:
            assert cfg[key] == value, key


def test_parameters_as_built_are_the_issues_count():
    cfg = full_config()
    main, _, _, _, _ = models.build_train(cfg, 3)
    by_layer = {}
    for p in main.all_parameters():
        key = p.name.split("_")[0] if p.name.startswith("blk") else p.name
        by_layer[key] = by_layer.get(key, 0) + int(np.prod(p.shape))
    assert sum(by_layer.values()) == pytest.approx(772.16e6, rel=1e-4)
    assert by_layer["granitehybrid_tok_emb.w"] == 12544 * 2048
    for i, kind in enumerate(KINDS):
        want = {"mamba2": 76.18e6, "attn": 60.82e6}[kind]
        assert by_layer[f"blk{i}"] == pytest.approx(want, rel=2e-4), kind
    # the table is the head: no second matrix of the vocabulary's size
    assert [p.name for p in main.all_parameters()
            if 12544 in p.shape] == ["granitehybrid_tok_emb.w"]
    # the marks are on the Program: ten layers' inputs, the last one's output
    assert len(main._checkpoints) == 11
    from paddle_tpu.core.interp import op_scope_name

    replayed = [op for op in main.global_block().ops
                if recompute_spans.replayed(op_scope_name(op).split("/"))]
    assert {op.namescope.split("/")[0] for op in replayed} == {
        f"blk{i}" for i in range(10)}


# --- the FLOPs and the bytes -------------------------------------------------


def test_train_flops_against_a_count_by_hand():
    cfg = full_config()
    t = 16384
    # forward FLOPs a token, by hand (ISSUE 75's arithmetic)
    mamba_proj = 2 * (2048 * (4096 + 4096 + 256 + 64) + 4096 * 2048)
    swiglu = 2 * 3 * 2048 * 8192
    scan = 2 * 64 * (128 * 128 * 128 / 64 + 128 * 128 * 64
                     + 2 * 128 * 128 * 64) / 128
    attn_proj = 2 * (2048 * (32 + 16) * 64 + 2048 * 2048)
    triangle = 4 * 32 * 64 * (t + 1) / 2
    head = 2 * 12544 * 2048
    assert mamba_proj == pytest.approx(51.6e6, rel=2e-3)
    assert swiglu == pytest.approx(100.7e6, rel=1e-3)
    assert scan == pytest.approx(3.18e6, rel=2e-3)
    assert triangle == pytest.approx(67.1e6, rel=1e-3)
    token = 9 * (mamba_proj + scan + swiglu) + (
        attn_proj + triangle + swiglu) + head
    assert token == pytest.approx(1.64e9, rel=2e-3)
    assert fg.train_flops(cfg, 1, t) == pytest.approx(3 * t * token,
                                                      rel=1e-9)
    assert fg.train_flops(cfg, 1, t) == pytest.approx(80.6e12, rel=1e-3)
    assert 2 * fg.layer_params(cfg, "mamba2") == mamba_proj + swiglu
    assert 2 * fg.layer_params(cfg, "attn") == attn_proj + swiglu
    fam = models.family(cfg)
    assert fam.train_flops(cfg, 1, t) == fg.train_flops(cfg, 1, t)
    a = fam.attention_cost(cfg, 1, t)
    assert a["calls"] == 2 and a["flops"] == pytest.approx(
        3 * t * triangle, rel=1e-9)
    assert a["bytes"] == 6 * t * (32 + 8) * 64 * 2


def test_mamba2_scan_cost_is_the_needed_work():
    cfg = full_config()
    t, c = 16384, 128
    cost = fg.mamba2_scan_cost(cfg, 1, t, c)
    assert cost["calls"] == 18
    per_chunk_head = 2 * (c * c * 128 / 64 + c * c * 64 + 2 * c * 128 * 64)
    assert cost["flops"] == pytest.approx(
        9 * 3 * (t // c) * 64 * per_chunk_head, rel=1e-9)
    # C B^T once a GROUP: a kernel that makes it once a head block of 8
    # heads (8 times over) does 7% more than this count
    blocked = 2 * (c * c * 128 / 8 + c * c * 64 + 2 * c * 128 * 64)
    assert blocked / per_chunk_head == pytest.approx(1.072, abs=0.001)
    states = 2 * (t // c) * 64 * 64 * 128 * 4
    assert cost["bytes"] == 9 * (t * (4 * 4096 + 4 * 128) * 2 + states)
    # nemotron's count on nemotron's keys is the same function of shapes
    from perf import flops_nemotronh as fn
    nemo = dict(mamba_num_heads=64, mamba_head_dim=64, n_groups=1,
                ssm_state_size=128, hidden_size=2048, num_attention_heads=32,
                num_key_value_heads=8, head_dim=64, moe_intermediate_size=1,
                moe_shared_expert_intermediate_size=1)
    assert fn.mamba2_scan_flops(nemo, 1, t, c) == fg.mamba2_scan_flops(
        cfg, 1, t, c)


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
    w = {k: np.asarray(v) for k, v in weights_from_scope(scope).items()}
    # (projections large enough that what a state keeps and what a query
    # sees at a scale of 1 / 64 reach the logits; step sizes of 0.3 with
    # slow decays and a small skip D, in place of Mamba-2's initial
    # 0.001-0.1 and 1, so that what a state keeps over 16 positions does)
    r = np.random.RandomState(0)
    for k, v in w.items():
        if k.endswith("_attn_qkv_colp.w"):
            w[k] = (1.5 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith(("_colp.w", "_rowp.w", "_tok_emb.w")):
            w[k] = (0.3 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith(("_mamba_dt.b", "_mamba_a_log")):
            w[k] = (-1.0 + 0.3 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith("_mamba_d"):
            w[k] = np.full(v.shape, 0.1, np.float32)
        else:
            continue
        scope.set(k, jnp.asarray(w[k]))
    fetched = {"last_logits": np.asarray(exe.run(
        evalp, feed=sample, fetch_list=[model["last_logits"]],
        scope=scope)[0])}
    return cfg, w, sample, fetched


def as_program(w, cfg, sample, **kw):
    out = ref.forward(w, cfg, sample["input_ids"], last=ref.LAST_POSITIONS,
                      **kw)
    return ref.second_check(w, cfg, sample, {"last_logits": out})


def test_the_start_state_sharpens_the_queries_and_keys_alone():
    cfg = tiny.config(CONFIG)
    fam = models.family(cfg)
    assert fam.CHECK_FETCH == ("last_logits",)
    _, startup, _, _, _ = models.build_train(cfg, seed=3)
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    qkv = np.asarray(scope.find_var("blk5_attn_qkv_colp.w"))
    qk = (4 + 2) * 8
    assert qkv.shape == (32, qk + 2 * 8)
    assert qkv[:, :qk].std() == pytest.approx(0.02 * fam.QK_STD_FACTOR,
                                              rel=0.15)
    assert qkv[:, qk:].std() == pytest.approx(0.02, rel=0.2)
    other = np.asarray(scope.find_var("blk4_mamba_in_colp.w"))
    assert other.std() == pytest.approx(0.02, rel=0.1)


def test_second_check_passes_the_program(sample_readings, monkeypatch):
    cfg, w, sample, fetched = sample_readings
    with jax.default_matmul_precision("highest"):
        _, record = ref.second_check(w, cfg, sample, fetched)
        assert set(record) == {"logit_err_over_rms", "logit_max_err_over_rms",
                               "logit_rms", "positions", "limits"}
        # the tiny row is 16 positions: all of them are "last"
        assert record["positions"] == 8 * 16
        assert 0 < record["logit_err_over_rms"] < 0.1
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT",
                            2 * record["logit_err_over_rms"])
        problems, _ = ref.second_check(w, cfg, sample, fetched)
    assert problems == []


def control_of(name):
    return ({"round_to": jnp.float8_e4m3fn} if name == "float8"
            else {"ablate": name})


@pytest.mark.parametrize("control", ("float8",) + ref.ABLATIONS)
def test_second_check_fails_each_of_its_four_controls(sample_readings,
                                                      monkeypatch, control):
    """The reference with every weight matmul's operands rounded to
    float8 (the nearest precision below the configuration's bf16), with
    the softmax scale 1 / sqrt(dh) where the config states 1 / 64, with
    the gated norm's statistics in 8 groups, and with the state dropped
    at every chunk boundary (chunks of 8 over 16 positions), each judged
    as if it were the program. The limit in the file is the chip's,
    between readings at the published widths; at the tiny sizes it is
    set here as there: at the geometric middle."""
    cfg, w, sample, fetched = sample_readings
    if control == "no_carry":
        monkeypatch.setattr(ref, "KERNEL_CHUNK", 8)
    with jax.default_matmul_precision("highest"):
        _, program = ref.second_check(w, cfg, sample, fetched)
        _, record = as_program(w, cfg, sample, **control_of(control))
        assert record["logit_err_over_rms"] \
            > 1.5 * program["logit_err_over_rms"], control
        monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", float(np.sqrt(
            record["logit_err_over_rms"] * program["logit_err_over_rms"])))
        problems, _ = as_program(w, cfg, sample, **control_of(control))
        passes, _ = ref.second_check(w, cfg, sample, fetched)
    assert len(problems) == 1 and passes == []
    assert "logits differ" in problems[0]


# --- the readers --------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1, kernel_s=0.0,
               config=None):
    run = tiny.make_run(tiny.train_cell(CELL), config or full_config(),
                        traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9,
                 "by_family_s": {"mamba2": kernel_s} if kernel_s else {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": 0.0}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk3/mamba2/proj/mul": 6.0,
    "fwd/blk3/mamba2/chunks/mamba2_scan": 2.0,
    "bwd/blk3/mamba2/proj/mul": 6.5,               # the replay
    "bwd/blk3/mamba2/chunks/mamba2_scan": 2.5,     # the replay
    "bwd/blk3/mamba2/chunks/mamba2_scan_grad": 5.0,
    "bwd/blk3/mlp/mul": 3.0,                       # the replay
    "bwd/blk3/mlp/mul_grad": 7.0,
    "bwd/blk5/attn/core/scaled_dot_product_attention": 4.0,   # the replay
    "bwd/blk5/attn/core/scaled_dot_product_attention_grad": 9.0,
    "bwd/sum": 1.0,                  # no name scope: not a replay
    "bwd/recompute_barrier": 0.5,
    "bwd/loss_head/softmax_with_cross_entropy_grad": 2.0,
    "fwd/loss_head/matmul": 6.0,
    "opt/adam": 10.0,
}


def test_the_one_predicate_tells_a_replay_from_a_first_run():
    took = {k for k in BY_SCOPE if recompute_spans.replayed(k.split("/"))}
    assert took == {"bwd/blk3/mamba2/proj/mul",
                    "bwd/blk3/mamba2/chunks/mamba2_scan",
                    "bwd/blk3/mlp/mul",
                    "bwd/blk5/attn/core/scaled_dot_product_attention"}
    run = scopes_run(BY_SCOPE)
    assert read("recompute.step_share.train", run) == pytest.approx(
        6.5 + 2.5 + 3.0 + 4.0)
    # a replayed op counts where its cost belongs
    assert read("mamba2.step_share.train", run) == pytest.approx(
        6 + 2 + 6.5 + 2.5 + 5)
    assert read("step.block_share.train", run) == pytest.approx(
        sum(v for k, v in BY_SCOPE.items() if "/blk" in k))


def test_readers_report_nothing_for_a_program_without_marks():
    from paddle_tpu import monitor

    monitor.reset()
    plain = {k: v for k, v in BY_SCOPE.items()
             if not recompute_spans.replayed(k.split("/"))}
    run = scopes_run(plain, kernel_s=0.5)
    assert read("recompute.step_share.train", run) is None
    assert read("lower.recomputed_ops.train", run) is None
    assert recompute_spans.replayed_ops() is None
    # no dispatch row, or another family's configuration: no roofline
    assert read("mamba2.group_scan_roofline.train", run) is None
    run._spans = None
    assert read("recompute.step_share.train", run) is None


def test_group_scan_roofline_reads_the_kernels_time(monkeypatch):
    from perf import mamba2_spans

    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    rows = [({"impl": "kernel", "chunk": "128", "pass": "fwd",
              "tile": "hb8 c8"}, 17),
            ({"impl": "kernel", "chunk": "128", "pass": "bwd",
              "tile": "hb8 c8"}, 9)]
    monkeypatch.setattr(mamba2_spans, "dispatch_rows", lambda: rows)
    run = scopes_run(BY_SCOPE, traced_steps=4, kernel_s=2.0)
    run.cell = harness.load_json("perf", "workloads", f"{CELL}.json")
    cost = fg.mamba2_scan_cost(full_config(), 1, 16384, 128)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert read("mamba2.group_scan_roofline.train", run) == pytest.approx(
        100 * least * 4 / 2.0)
    assert 0 < read("mamba2.group_scan_roofline.train", run) < 100
    # nemotron's configuration has no such key: its own reader's cell
    nemo = harness.load_json("perf", "configs",
                             "nemotron-3-nano-30b-a3b.json")
    other = scopes_run(BY_SCOPE, traced_steps=4, kernel_s=2.0, config=nemo)
    other.cell = run.cell
    assert read("mamba2.group_scan_roofline.train", other) is None


def test_the_new_readers_are_entries_that_list_the_cell():
    PL, K = "Program lowering", "Kernels"
    for metric, unit, better, source, layer in (
            ("recompute.step_share.train", "%", "lower", "program_span", PL),
            ("lower.recomputed_ops.train", "ops", "lower",
             "program_counter", PL),
            ("mamba2.group_scan_roofline.train", "%", "higher",
             "device_trace", K)):
        assert metric in NEW
        assert tiny.listed_as(metric, unit, better, source, layer, CELL)
        assert callable(harness.reader_for(metric).read)
    on = {m["name"] for m in tiny.BENCH["end_to_end"] + tiny.BENCH["per_layer"]
          if CELL in m.get("workloads", ())}
    assert {"train_tokens_per_s", "step.mfu.train", "train_attn_roofline",
            "step.block_share.train", "mamba2.step_share.train",
            "mamba2.scan_share.train", "lower.xla_mamba2_calls.train",
            "lower.xla_conv_calls.train", "lower.split_bwd_attn_calls.train",
            "lower.xla_embed_grad_calls.train", "embed.grad_share.train",
            "mem.state_gb.train", "mem.saved_gb.train",
            "mem.saved_pad_share.train", "mem.walk_peak_gb.train",
            "device.peak_hbm_gb.train"} <= on
    # nemotron's roofline reads nemotron's key names: not this cell's
    assert "mamba2.scan_roofline.train" not in on


def test_a_traced_tiny_run_replays_its_layers_and_passes_both_checks(
        monkeypatch, tmp_path):
    from paddle_tpu import monitor
    from perf import mamba2_spans

    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9})
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    # (the file's limit is the chip's at the published widths; the tiny
    # program under bf16 reads higher against 16 positions)
    monkeypatch.setattr(ref, "LOGIT_ERR_LIMIT", 0.3)
    monitor.reset()
    cell = tiny.train_cell(CELL)
    cfg = tiny.config(cell["config"])
    assert cfg["recompute"] == "layer" and cfg["kernel_chunk"] == 8
    run = tiny.make_run(cell, cfg, seconds=0.3, traced=True)
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    assert run.check["rel"] < train.LOSS_REL_TOL
    assert run.check["second"]["positions"] == 8 * 16
    # the tiny cut's three layers are replayed: three segments
    rows = harness.counter_rows(recompute_spans.COUNTER)
    assert sorted(lb["segment"] for lb, _ in rows) == ["0", "1", "2"]
    assert read("lower.recomputed_ops.train", run) \
        == sum(n for _, n in rows) > 30
    assert harness.counter_rows("pt_backward_checkpoints_total") == [
        ({"program": rows[0][0]["program"], "used": "true"}, 4)]
    # without a TPU the scans are the chunked XLA form: two in the eval
    # clone and the step's forward, two made again, two backward
    scans = mamba2_spans.dispatch_rows()
    assert {lb["impl"] for lb, _ in scans} == {"chunked"}
    assert {lb["tile"] for lb, _ in scans} == {""}
    assert {lb["shape"] for lb, _ in scans} == {"b8 t16 h4 p8 g1 n8"}
    by_pass = {p: sum(n for lb, n in scans if lb["pass"] == p)
               for p in ("fwd", "bwd")}
    assert by_pass["bwd"] == 2 and by_pass["fwd"] >= 2 + 2 + 2
    assert read("lower.xla_mamba2_calls.train", run) == sum(
        by_pass.values())
    for metric in ("lower.recomputed_ops.train", "step.mfu.train",
                   "mem.saved_gb.train", "mem.state_gb.train"):
        assert metric in line["metrics"], metric
