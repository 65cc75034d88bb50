"""What is the Nemotron-H family's own in the benchmark: the
configuration's cut against the catalog's row, its parameters, FLOPs and
bytes by block kind, the second check against a lower-precision control
and the four ablations, and the Mamba-2 and grouped-matmul readers
(perf/mamba2_spans.py and five metrics that BENCHMARK.json does not list
yet: PERF.md section 7 (20))."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from perf import flops_nemotronh as fn
from perf import harness, mamba2_spans, models
from perf.kinds import train
from perf.reference import nemotronh as ref
from perf.reference.common import weights_from_scope

import perfbench_tiny as tiny

CONFIG, CELL = "nemotron-3-nano-30b-a3b", "nemotron3nano-train-s4096"
KINDS = ["moe", "mamba2", "moe", "mamba2", "moe", "mamba2", "moe", "mamba2",
         "attn"]
NEW = ("mamba2.step_share.train", "mamba2.scan_share.train",
       "mamba2.scan_roofline.train", "lower.xla_mamba2_calls.train",
       "lower.ragged_moe_calls.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def full_config():
    return harness.load_json("perf", "configs", f"{CONFIG}.json")


# --- the configuration ------------------------------------------------------


def test_configuration_cuts_depth_experts_and_vocabulary_and_no_width():
    from paddle_tpu.models import nemotron_h as M

    cfg, pub = full_config(), M.NemotronHConfig()
    pcfg = models.family(cfg).program_config(cfg)
    cut = {"num_hidden_layers": 9, "vocab_size": 16384, "first_layer": 34,
           "held_experts": (0, 8)}
    for key, value in vars(pub).items():
        assert getattr(pcfg, key) == cut.get(key, value), key
    assert pcfg.n_routed_experts == 128          # the router's outputs
    assert cfg["reduced_from"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    assert cfg["vocab_size"] * 8 == cfg["reduced_from"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["router_experts"] == 128
    # the share starts at expert 0 and the file says so (since PR 54)
    assert cfg["held_first"] == 0 and "held share" in cfg["assumed"]
    for key in ("source", "the_cut", "assumed", "deployment"):
        assert cfg[key], key
    # the blocks keep their published indices and every kind is held
    assert [k for _, k in pcfg.blocks] == KINDS == fn.block_kinds(cfg)
    assert [i for i, _ in pcfg.blocks] == list(range(34, 43))
    assert ref.blocks(cfg) == pcfg.blocks
    assert len(cfg["hybrid_override_pattern"]) == 52 == cfg["model_layers"]
    assert ref.LAST_POSITIONS == M.LAST_POSITIONS
    traffic = harness.load_json("perf", "workloads", f"{CELL}.json")["traffic"]
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)


def test_every_width_is_the_catalog_rows():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog of architectures on this machine")
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
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
    by_block = {}
    for p in main.all_parameters():
        key = p.name.split("_")[0] if p.name.startswith("blk") else p.name
        by_block[key] = by_block.get(key, 0) + int(np.prod(p.shape))
    assert sum(by_block.values()) == pytest.approx(666.96e6, rel=1e-4)
    assert by_block["nemotronh_tok_emb.w"] == 16384 * 2688 \
        == by_block["lm_head_colp.w"]
    for i, kind in zip(range(34, 43), KINDS):
        want = {"mamba2": 38.74e6, "attn": 23.40e6, "moe": 100.13e6}[kind]
        assert by_block[f"blk{i}"] == pytest.approx(want, rel=2e-4), kind
    # two matrices an expert: no gate among the experts' parameters
    names = {p.name for p in main.all_parameters()}
    assert "blk34_moe_up.w" in names and "blk34_moe_shared_up.w" in names
    assert not any("gate" in n for n in names)


# --- the FLOPs and the bytes -------------------------------------------------


def test_train_flops_count_every_kind_of_block():
    cfg = full_config()
    t = 4096
    fwd = {k: 2.0 * t * fn.block_params(cfg, k)
           for k in ("mamba2", "moe", "attn")}
    # the issue's arithmetic, forward at 4096 positions
    assert fwd["mamba2"] == pytest.approx(317e9, rel=0.01)
    assert fwd["moe"] == pytest.approx(196.6e9, rel=0.01)
    assert fwd["attn"] == pytest.approx(191.7e9, rel=0.01)
    scan = fn.mamba2_scan_flops(cfg, 1, t, 128)
    assert scan == pytest.approx(14e9, rel=0.01)
    attn = fn.attention_cost(cfg, 1, t)
    assert attn["flops"] / 3 == pytest.approx(137.5e9, rel=0.01)
    head = 2.0 * t * 2688 * 16384
    total = fn.nemotronh_train_flops(cfg, 1, t)
    assert total == pytest.approx(
        3 * (4 * (fwd["mamba2"] + scan) + 4 * fwd["moe"] + fwd["attn"]
             + head) + attn["flops"])
    assert total == pytest.approx(8.4e12, rel=0.01)
    assert 3 * 4 * (fwd["mamba2"] + scan) / total == pytest.approx(
        0.47, abs=0.01)


def test_scan_and_grouped_matmul_costs():
    cfg = full_config()
    cost = fn.mamba2_scan_cost(cfg, 1, 4096, 128)
    tok, e, gn = 4096, 4096, 1024
    states = 32 * 64 * 64 * 128 * 4              # 67 MB a layer, float32
    assert cost == {"flops": 4 * 3 * fn.mamba2_scan_flops(cfg, 1, 4096, 128),
                    "bytes": float(4 * (tok * (4 * e + 4 * gn) * 2
                                        + 2 * states)),
                    "calls": 8}
    # a longer chunk saves fewer states and does more work a position
    longer = fn.mamba2_scan_cost(cfg, 1, 4096, 256)
    assert longer["bytes"] < cost["bytes"] and longer["flops"] > cost["flops"]
    # six products a block at the rows an even router holds here
    gmm = fn.moe_gmm_cost(cfg, 1, 4096)
    m = 4096 * 6 / 16
    assert gmm == {"flops": 24 * 2.0 * m * 2688 * 1856,
                   "bytes": float(24 * (m * 2688 + m * 1856
                                        + 8 * 2688 * 1856) * 2),
                   "calls": 24}
    # no such block, no cost
    none = dict(cfg, num_hidden_layers=1, first_layer=42)
    assert fn.mamba2_scan_cost(none, 1, 4096)["bytes"] == 0
    assert fn.moe_gmm_cost(none, 1, 4096)["calls"] == 0


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
    # (projections large enough that what a state keeps and what a query
    # sees reach the logits; step sizes of 0.3 with slow decays and a
    # small skip D, in place of Mamba-2's initial 0.001-0.1, 1..heads and
    # 1, so that what a state keeps over 16 positions does)
    r = np.random.RandomState(0)
    for k, v in w.items():
        if k.endswith(("_colp.w", "_rowp.w", "_up.w", "_down.w",
                       "_tok_emb.w")):
            w[k] = (0.3 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith(("_mamba_dt.b", "_mamba_a_log")):
            w[k] = (-1.0 + 0.3 * r.randn(*v.shape)).astype(np.float32)
        elif k.endswith("_mamba_d"):
            w[k] = np.full(v.shape, 0.1, np.float32)
        else:
            continue
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
    """The state dropped at every chunk boundary (chunks of 8 over 16
    positions), the gate behind the norm, relu for relu^2, one decay for
    all heads: judged as if they were the program they move the logits
    by more than its rounding."""
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


# --- the readers --------------------------------------------------------------


def scopes_run(by_scope, busy=100.0, traced_steps=1, kernel_s=0.0):
    run = tiny.make_run(tiny.train_cell(CELL), full_config(), traced=True)
    run.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    run.window = {"traced_steps": traced_steps}
    run.trace = {"devices": 1, "busy_s": busy / 1e9,
                 "by_family_s": {"mamba2": kernel_s} if kernel_s else {}}
    run._spans = {"chips": 1, "busy_ns": busy, "scoped_ns": sum(
        by_scope.values()), "by_scope_ns": by_scope, "head_ns": sum(
            v for k, v in by_scope.items() if k.split("/")[1] == "loss_head")}
    return run


def read(metric, run):
    return harness.reader_for(metric).read(run)


BY_SCOPE = {
    "fwd/embed/lookup_table": 2.0,
    "fwd/blk35/mamba2/rms_norm": 1.0,
    "fwd/blk35/mamba2/proj/mul": 6.0,
    "fwd/blk35/mamba2/conv/causal_conv1d": 1.0,
    "fwd/blk35/mamba2/chunks/mamba2_scan": 2.0,
    "bwd/blk35/mamba2/chunks/mamba2_scan_grad": 5.0,
    "fwd/blk37/mamba2/gate_norm/gated_rms_norm": 1.0,
    "bwd/blk37/mamba2/out/mul_grad": 4.0,
    "fwd/blk34/moe/router/moe_router": 1.0,
    "fwd/blk34/moe/experts/moe_experts": 3.0,
    "fwd/blk34/moe/shared/mul": 4.0,
    "fwd/blk42/attn/core/scaled_dot_product_attention": 4.0,
    "fwd/loss_head/mul": 6.0,
    "fwd/mamba2/mul": 5.0,          # a scope named mamba2 outside a block
    "opt/adam": 10.0,
}


def test_mamba2_readers_sum_their_scopes():
    run = scopes_run(BY_SCOPE)
    mixers = 1 + 6 + 1 + 2 + 5 + 1 + 4
    assert read("mamba2.step_share.train", run) == pytest.approx(mixers)
    assert read("mamba2.scan_share.train", run) == pytest.approx(
        100 * (1 + 2 + 5 + 1) / mixers)
    # the readers that exist take the expert layers for expert layers
    # and none takes a Mamba-2 mixer for a delta rule or a Mamba-1 scan
    assert read("moe.step_share.train", run) == pytest.approx(8.0)
    for metric in ("gdn.step_share.train", "gdn.scan_share.train",
                   "ssm.step_share.train", "ssm.scan_share.train",
                   "ssm.scan_roofline.train", "mla.step_share.train",
                   "swa.step_share.train"):
        assert read(metric, run) is None, metric


def test_scan_roofline_reads_the_kernels_time_and_the_counters_chunk():
    from paddle_tpu import flags, monitor
    from paddle_tpu.ops import mamba2_scan_ops as S
    from paddle_tpu.parallel import grouped_matmul as gm

    cfg, peaks = full_config(), harness.peaks_for("TPU v5 lite")
    monitor.reset()
    run = scopes_run(BY_SCOPE, kernel_s=20e-9)
    # no dispatch row yet: nothing says which chunk the states are of
    for metric in ("mamba2.scan_roofline.train",
                   "lower.xla_mamba2_calls.train",
                   "lower.ragged_moe_calls.train"):
        assert read(metric, run) is None
    flags.set_flags({"telemetry": True})
    try:
        def note(impl, chunk, direction="fwd"):
            S._M_DISPATCH.inc(labels={
                "pass": direction, "shape": "b8 t16 h4 p8 g2 n8",
                "chunk": str(chunk), "impl": impl})

        note("kernel", 128)
        note("kernel", 128, "bwd")
        assert read("lower.xla_mamba2_calls.train", run) == 0
        traffic = run.cell["traffic"]           # the tiny cell: 8 x 16
        cost = fn.mamba2_scan_cost(cfg, traffic["batch"], traffic["seq_len"],
                                   128)
        least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                    cost["bytes"] / peaks["hbm_bytes_per_s"])
        assert read("mamba2.scan_roofline.train", run) == pytest.approx(
            100 * least / 20e-9)
        two = scopes_run(BY_SCOPE, traced_steps=2, kernel_s=20e-9)
        assert read("mamba2.scan_roofline.train", two) == pytest.approx(
            200 * least / 20e-9)
        # calls that are not kernels are counted, whichever form
        note("chunked", 128)
        note("recurrent", 1, "bwd")
        assert read("lower.xla_mamba2_calls.train", run) == 2
        # a grouped matmul with a tile, and two without
        for tile in ("tm128 tk896 tn1856", "", ""):
            gm._M_DISPATCH.inc(labels={
                "pass": "fwd", "shape": "m24576 k2688 n1856 e8",
                "tile": tile})
        assert read("lower.ragged_moe_calls.train", run) == 2
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
    assert mamba2_spans.summary(run) is None
    assert mamba2_spans.kernel_s(run) == 0.0
    assert mamba2_spans.dispatch_rows() == [] == mamba2_spans.gmm_rows()


def test_the_new_readers_are_entries_that_list_the_cell():
    """The five are entries since PR 54 (they waited as files while a
    pin in tests/perfbench/ held ``per_layer``'s last entry), with the
    keys PERF.md section 3 gives them."""
    PL, K = "Program lowering", "Kernels"
    for metric, unit, better, source, layer in (
            ("mamba2.step_share.train", "%", "lower", "program_span", PL),
            ("mamba2.scan_share.train", "%", "lower", "program_span", PL),
            ("mamba2.scan_roofline.train", "%", "higher", "device_trace", K),
            ("lower.xla_mamba2_calls.train", "count", "lower",
             "program_counter", PL),
            ("lower.ragged_moe_calls.train", "count", "lower",
             "program_counter", PL)):
        assert metric in NEW
        assert tiny.listed_as(metric, unit, better, source, layer, CELL)
        assert callable(harness.reader_for(metric).read)
    # the guard of a width off the lanes is every expert cell's
    assert tiny.cells_named(tiny.BENCH, "lower.ragged_moe_calls.train") \
        == tiny.cells_named(tiny.BENCH, "moe.step_share.train")
    on = {m["name"] for m in tiny.BENCH["end_to_end"] + tiny.BENCH["per_layer"]
          if CELL in m.get("workloads", ())}
    assert {"train_tokens_per_s", "moe.step_share.train",
            "moe.route_share.train", "moe.max_expert_load.train",
            "step.mfu.train", "train_attn_roofline"} <= on
    # the lists that were closed to the cell until PR 54
    assert {"step.block_share.train", "lower.split_bwd_attn_calls.train",
            "lower.whole_buffer_moe_calls.train",
            "lower.xla_conv_calls.train"} <= on


def test_a_traced_tiny_run_counts_its_scans_and_passes_both_checks(
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
    # without a TPU the four scans are the chunked XLA form, forward in
    # the eval clone and the step, backward in the step: the reader
    # counts every one of them as not a kernel
    rows = mamba2_spans.dispatch_rows()
    assert rows and {lb["impl"] for lb, _ in rows} == {"chunked"}
    assert {lb["shape"] for lb, _ in rows} == {"b8 t16 h4 p8 g2 n8"}
    assert {lb["chunk"] for lb, _ in rows} == {"8"}
    assert read("lower.xla_mamba2_calls.train", run) \
        == sum(n for _, n in rows) >= 8
    # ... and every grouped matmul ragged_dot: six a block and pass
    gmm = mamba2_spans.gmm_rows()
    assert gmm and read("lower.ragged_moe_calls.train", run) \
        == sum(n for _, n in gmm) >= 24
    # the held layers' passes go by windows, none walks its buffer whole
    assert read("lower.whole_buffer_moe_calls.train", run) == 0
    assert read("lower.split_bwd_attn_calls.train", run) in (0, None)
    assert read("lower.xla_conv_calls.train", run) >= 8
    # the metrics the cell is listed under are on its line
    for metric in ("moe.max_expert_load.train", "step.mfu.train"):
        assert metric in line["metrics"], metric
    # no device trace on a CPU: the span readers have nothing to read
    for metric in ("mamba2.step_share.train", "mamba2.scan_share.train",
                   "mamba2.scan_roofline.train"):
        assert read(metric, run) is None
    monitor.reset()
