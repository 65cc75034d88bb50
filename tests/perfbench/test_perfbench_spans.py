"""perf/spans.py and the eight per-layer readers that use it: the wire
reader against tensorflow's xplane_pb2 on the v5e fixtures, the scope
grammar, a synthetic trace in plain lists, a trace without scopes, a
run without a device trace, and the values pinned on the fixture cut
from the scoped tree's run on the v5e (PR 24)."""

import gzip
import os

import pytest

from perf import harness, spans, trace

import perfbench_tiny as tiny

OLD = os.path.join(harness.HERE, "fixtures",
                   "tbase-train-v5e-one-step.xplane.pb.gz")
SCOPED = os.path.join(harness.HERE, "fixtures",
                      "tbase-train-v5e-scoped-one-step.xplane.pb.gz")
NEW_METRICS = (
    "lower.scoped_share.train", "step.bwd_share.train",
    "step.opt_share.train", "step.head_share.train",
    "attn.bwd_time_share.train", "exec.run_ms_per_call.train",
    "exec.prepare_ms_per_call.train", "exec.idle_in_run_share.train")


def read_all(run):
    return {m: harness.reader_for(m).read(run) for m in NEW_METRICS}


def run_with(summary, traced=True):
    """A run record whose trace was already reduced to ``summary``."""
    cell = tiny.train_cell("tbase-train")
    run = tiny.make_run(cell, tiny.config(cell["config"]), traced=True)
    run.trace = {"busy_s": 1.0} if traced else None
    run._spans = summary
    return run


# --- the wire reader ---------------------------------------------------


def ops_of(doc):
    return next(ln["events"] for ln in doc["planes"][0]["lines"]
                if ln["name"] == trace.OPS_LINE)


def test_the_old_fixture_names_94_percent_of_its_self_time():
    ops = ops_of(spans.load(OLD))
    assert len(ops) == 9039
    tf_op = {e[0]: e[3] for e in ops}
    total = named = 0.0
    for name, self_ns in trace.self_times([e[:3] for e in ops]):
        total += self_ns
        named += self_ns if tf_op[name] else 0.0
    assert named / total == pytest.approx(0.942, abs=5e-4)
    assert tf_op[max(tf_op, key=lambda n: "dot_general" in tf_op[n])] \
        .startswith("jit(step_fn)/")


@pytest.mark.parametrize("path", [OLD, SCOPED])
def test_the_wire_reader_agrees_with_xplane_pb2_event_for_event(path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    space.ParseFromString(gzip.open(path, "rb").read())
    mine = {p["name"]: p for p in spans.load(path)["planes"]}
    seen = 0
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}

        def value(st):
            kind = st.WhichOneof("value")
            return (names[st.ref_value] if kind == "ref_value"
                    else getattr(st, kind))

        got = {ln["name"]: ln["events"]
               for ln in mine[plane.name]["lines"]}
        for ln in plane.lines:
            if ln.name not in got:
                continue
            want = []
            for ev in ln.events:
                meta = plane.event_metadata[ev.metadata_id]
                if plane.name == "/host:CPU":
                    extra = {names[s.metadata_id]: value(s)
                             for s in ev.stats}
                else:
                    extra = next((value(s) for s in meta.stats
                                  if names[s.metadata_id] == "tf_op"), "")
                want.append([meta.name,
                             ln.timestamp_ns + ev.offset_ps / 1e3,
                             ev.duration_ps / 1e3, extra])
            assert got[ln.name] == want
            seen += len(want)
    assert seen > 9000


def test_the_scoped_fixture_is_small_and_keeps_the_host_spans():
    assert os.path.getsize(SCOPED) < 400 * 1024
    doc = spans.load(SCOPED)
    dev, host = doc["planes"]
    assert dev["name"] == "/device:TPU:0" and host["name"] == "/host:CPU"
    (line,) = host["lines"]
    names = [e[0] for e in line["events"]]
    assert names.count("executor.run") == 3
    assert {n for n in names} == {"executor.run", *spans.CHILD_SPANS}
    steps = [e[3]["step"] for e in line["events"] if e[0] == "executor.run"]
    assert steps == [steps[0], steps[0] + 1, steps[0] + 2]


# --- the scope grammar -------------------------------------------------


@pytest.mark.parametrize("tf_op,want", [
    ("jit(step_fn)/fwd/enc0/attn/mul/dot_general:",
     ("fwd", "enc0/attn", "mul", None)),
    ("jit(step_fn)/bwd/dec3/ffn/mul_grad/transpose(jvp())/dot_general:",
     ("bwd", "dec3/ffn", "mul_grad", None)),
    ("jit(step_fn)/bwd/enc1/ffn/relu_grad/transpose(bwd/enc1/ffn/relu_grad)"
     "/jvp()/select_n:", ("bwd", "enc1/ffn", "relu_grad", None)),
    ("jit(step_fn)/opt/adam/mul:", ("opt", "", "adam", None)),
    ("jit(step_fn)/fwd/loss_head/softmax_with_cross_entropy/reduce_max:",
     ("fwd", "loss_head", "softmax_with_cross_entropy", None)),
    ("jit(step_fn)/fwd/enc0/attn/layer_norm/jit(_var)/square:",
     ("fwd", "enc0/attn", "layer_norm", None)),
    ("jit(step_fn)/fwd/enc0/ffn/dropout/while/body/closed_call/xor:",
     ("fwd", "enc0/ffn", "dropout", None)),
    ("jit(step_fn)/fwd/dec0/self/scaled_dot_product_attention/"
     "attn.bthd_small.fwd/pallas_call:",
     ("fwd", "dec0/self", "scaled_dot_product_attention",
      "attn.bthd_small.fwd")),
    ("jit(step_fn)/bwd/enc0/attn/scaled_dot_product_attention_grad/"
     "shard_map/attn.bhtd.bwd_dkv/pallas_call:",
     ("bwd", "enc0/attn", "scaled_dot_product_attention_grad",
      "attn.bhtd.bwd_dkv")),
    ("jit(step_fn)/fwd/dec0/cross/scaled_dot_product_attention/"
     "bqhd,bkhd->bhqk/dot_general:",
     ("fwd", "dec0/cross", "scaled_dot_product_attention", None)),
    # any family's kernel name, not attention's alone
    ("jit(step_fn)/fwd/blk3/moe/moe_experts/moe.gmm.fwd/pallas_call:",
     ("fwd", "blk3/moe", "moe_experts", "moe.gmm.fwd")),
    # a control-flow op's sub-block nests under the op that owns it
    ("jit(main)/fwd/decode/while/while/body/fwd/decode/step/mul/dot_general:",
     ("fwd", "decode", "while", None)),
])
def test_a_scope_is_parsed_from_the_op_name(tf_op, want):
    sc = spans.parse_scope(tf_op)
    assert (sc["phase"], sc["scope"], sc["op"], sc["kernel"]) == want


@pytest.mark.parametrize("tf_op", [
    "jit(step_fn)/transpose(jvp())/dot_general:", "jit(step_fn)/pallas_call:",
    "", "copy.3"])
def test_an_op_name_without_a_phase_has_no_scope(tf_op):
    assert spans.parse_scope(tf_op) is None


# --- a synthetic trace in plain lists ----------------------------------

PALLAS_BWD = ('%attn.bthd_small.bwd.3 = (bf16[8,16,32]{2,1,0}) custom-call('
              'bf16[8,16,32]{2,1,0} %q), custom_call_target='
              '"tpu_custom_call"')
ALLREDUCE = ('%all-reduce.3 = bf16[8,64]{1,0} all-reduce(bf16[8,64]{1,0} '
             '%fusion.7), channel_id=4')
J = "jit(step_fn)/"


def synthetic():
    chip0 = [
        ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0., 100.,
         J + "fwd/enc0/attn/mul/dot_general:"],
        [PALLAS_BWD, 100., 200.,
         J + "bwd/enc0/attn/scaled_dot_product_attention_grad/"
         "attn.bthd_small.bwd/pallas_call:"],
        # 50 us idle from 300: begins inside the first executor.run
        ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 50300.,
         50., J + "opt/adam/mul:"],
        ["%copy.9 = f32[8]{0} copy(f32[8]{0} %p)", 50350., 50., ""],
        # 50 us idle from 50400: begins between the two calls; then 10 us
        # of slack that is no gap
        ["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput",
         100400., 60., J + "fwd/loss_head/mul/dot_general:"],
        ["%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput",
         100470., 40., J + "fwd/loss_head/mul/dot_general:"],
    ]
    chip1 = [
        ["%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput", 10.,
         300., J + "bwd/loss_head/mul_grad/transpose(jvp())/dot_general:"],
        [ALLREDUCE, 310., 100.,
         J + "bwd/dec0/cross/elementwise_add_grad/transpose(jvp())/"
         "reduce_sum:"],
    ]
    host = [
        ["executor.run", -1000., 3000., {"step": 7}],
        ["executor.prepare", -900., 200., {}],
        ["executor.state", -700., 300., {}],
        ["executor.run_step", -300., 1500., {}],
        ["executor.commit", 1300., 500., {}],
        ["executor.run", 60000., 2000., {"step": 8}],
        ["executor.prepare", 60050., 100., {}],
        ["executor.state", 60150., 200., {}],
        ["executor.run_step", 60400., 1000., {}],
        ["executor.commit", 61500., 400., {}],
    ]
    return {"planes": [
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": chip1}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": chip0},
            {"name": "XLA Modules", "events": [
                ["jit_step_fn(1)", 0., 50400., ""],
                ["jit_step_fn(1)", 100400., 110., ""]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "worker/3", "events": [["Execute", 0., 10., {}]]},
            {"name": "python3", "events": host}]}]}


def test_the_reduction_of_a_synthetic_trace_by_hand():
    s = spans.reduce(synthetic())
    assert s["chips"] == 2 and s["busy_ns"] == 900.0
    assert s["scoped_ns"] == 850.0 and s["head_ns"] == 400.0
    assert s["by_phase_ns"] == {"fwd": 200.0, "bwd": 600.0, "opt": 50.0}
    assert s["kernel_ns"] == {"attn.bthd_small.bwd": 200.0}
    assert s["top_scopes"][0] == ["bwd/loss_head/mul_grad", 300.0]
    assert ["opt/adam", 50.0] in s["top_scopes"]
    assert s["collectives"] == [["all-reduce.3 all-reduce bf16[8,64]",
                                 "bwd/dec0/cross/elementwise_add_grad",
                                 100.0]]
    assert s["unscoped"] == [["copy.9 copy f32[8]", 50.0]]
    h = s["host"]
    assert h["calls"] == 2 and h["run_ns"] == 2500.0
    assert h["child_ns"]["executor.run_step"] == 1250.0
    # the first chip's two gaps of 50 us; the 10 us of slack is none
    assert h["idle_ns"] == 100000.0 and h["idle_in_run_ns"] == 50000.0
    assert h["idle_by_span"] == [("executor.run_step", 50000.0),
                                 ("outside", 50000.0)]
    assert h["skew_ns"] == [300.0, 20150.0, 40000.0]


def test_the_eight_readers_on_the_synthetic_trace(capsys):
    got = read_all(run_with(spans.reduce(synthetic())))
    assert got == {
        "lower.scoped_share.train": pytest.approx(100 * 850 / 900),
        "step.bwd_share.train": pytest.approx(100 * 600 / 900),
        "step.opt_share.train": pytest.approx(100 * 50 / 900),
        "step.head_share.train": pytest.approx(100 * 400 / 900),
        "attn.bwd_time_share.train": pytest.approx(100 * 200 / 900),
        "exec.run_ms_per_call.train": pytest.approx(0.0025),
        "exec.prepare_ms_per_call.train": pytest.approx(0.0004),
        "exec.idle_in_run_share.train": pytest.approx(50.0),
    }


def test_the_report_names_scopes_collectives_spans_and_gaps(capsys):
    spans.report(spans.reduce(synthetic()), steps=1)
    out = capsys.readouterr().out
    for piece in ("94.44% of busy self time carries a phase",
                  "'bwd/loss_head/mul_grad', 0.0001",
                  "all-reduce.3 all-reduce bf16[8,64]",
                  "bwd/dec0/cross/elementwise_add_grad",
                  "executor.run 0.0025 ms a call",
                  "['executor.run_step', 0.05], ['outside', 0.05]",
                  "[0.0003, 0.0202, 0.04]"):
        assert piece in out, piece


def test_a_trace_without_scopes_or_spans_leaves_every_metric_out():
    # the parent's trace: tf_op for 94% of its time, nothing of the
    # program's in it, device planes only
    s = spans.reduce(spans.load(OLD))
    assert s["busy_ns"] > 0 and s["scoped_ns"] == 0 and s["host"] is None
    assert set(read_all(run_with(s)).values()) == {None}
    # host spans but a chip that never idles: the idle share is 0
    doc = synthetic()
    doc["planes"][1]["lines"][0]["events"] = \
        doc["planes"][1]["lines"][0]["events"][:2]
    run = run_with(spans.reduce(doc))
    assert harness.reader_for(
        "exec.idle_in_run_share.train").read(run) == 0.0


def test_a_run_without_a_device_trace_leaves_every_metric_out(
        monkeypatch, tmp_path):
    # a CPU traced run: harness.DeviceTrace found no device plane
    assert set(read_all(run_with(None, traced=False)).values()) == {None}
    # a device trace was reduced but its raw file is gone
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    assert set(read_all(run_with(None)).values()) == {None}


def test_for_run_finds_the_raw_trace_of_the_cell(monkeypatch, tmp_path,
                                                capsys):
    import shutil

    d = tmp_path / "tbase-train" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    with gzip.open(SCOPED, "rb") as src, \
            open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    run = run_with(None)
    run.window["traced_steps"] = 1
    assert spans.for_run(run)["chips"] == 1
    assert spans.for_run(run) is run._spans          # reduced once
    assert capsys.readouterr().out.count("perf: scopes:") == 1


# --- the fixture from the scoped tree's run on the v5e -------------------


def test_the_eight_metrics_on_the_scoped_fixture():
    """One step of tbase-train and the head of the next (PR 24, my chip
    run): the values the readers gave when the fixture was cut."""
    got = read_all(run_with(spans.reduce(spans.load(SCOPED))))
    assert got == {
        "lower.scoped_share.train": pytest.approx(95.0812, abs=1e-3),
        "step.bwd_share.train": pytest.approx(50.7890, abs=1e-3),
        "step.opt_share.train": pytest.approx(0.1664, abs=1e-3),
        "step.head_share.train": pytest.approx(6.1119, abs=1e-3),
        "attn.bwd_time_share.train": pytest.approx(9.2846, abs=1e-3),
        "exec.run_ms_per_call.train": pytest.approx(10.1195, abs=1e-3),
        "exec.prepare_ms_per_call.train": pytest.approx(1.7071, abs=1e-3),
        "exec.idle_in_run_share.train": 0.0,
    }
    s = spans.reduce(spans.load(SCOPED))
    assert set(s["kernel_ns"]) == {"attn.bthd_small.fwd",
                                   "attn.bthd_small.bwd"}
    assert s["top_scopes"][0][0] == "bwd/loss_head/mul_grad"
    # every executor.run_step span begins before its module's first op
    assert s["host"]["skew_ns"][0] > 0


# --- the whole table by scope, and any family's kernels -------------------

MOE_BWD = ('%moe.gmm.bwd.4 = (bf16[8,16,32]{2,1,0}) custom-call('
           'bf16[8,16,32]{2,1,0} %x), custom_call_target="tpu_custom_call"')


def with_experts():
    """The synthetic trace with a block of experts on the first chip:
    a router's fusion and a ``moe.gmm.bwd`` kernel under ``blk0/moe``."""
    doc = synthetic()
    doc["planes"][1]["lines"][0]["events"] += [
        ["%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 100600.,
         30., J + "fwd/blk0/moe/softmax/reduce_max:"],
        [MOE_BWD, 100700., 70.,
         J + "bwd/blk0/moe/moe_experts_grad/moe.gmm.bwd/pallas_call:"]]
    return doc


@pytest.mark.parametrize("doc", [synthetic(), with_experts(),
                                 spans.load(SCOPED)],
                         ids=["synthetic", "experts", "scoped-fixture"])
def test_the_whole_table_by_scope_sums_to_the_scoped_time(doc):
    s = spans.reduce(doc)
    assert sum(s["by_scope_ns"].values()) == pytest.approx(
        s["scoped_ns"], rel=1e-12)
    assert len(s["top_scopes"]) == min(15, len(s["by_scope_ns"]))
    assert all(s["by_scope_ns"][k] == v for k, v in s["top_scopes"])
    assert min(v for _, v in s["top_scopes"]) >= sorted(
        s["by_scope_ns"].values())[-len(s["top_scopes"])]
    # the sums share() knows are predicate sums over the table
    assert spans.scope_ns(s, lambda parts: True) == pytest.approx(
        s["scoped_ns"], rel=1e-12)
    assert spans.scope_ns(
        s, lambda parts: parts[1] in spans.HEAD_SCOPES) == pytest.approx(
            s["head_ns"], rel=1e-12)
    for phase, ns in s["by_phase_ns"].items():
        assert spans.scope_ns(
            s, lambda parts: parts[0] == phase) == pytest.approx(
                ns, rel=1e-12, abs=1e-9)


def test_self_time_under_a_scope_component_is_a_few_lines():
    s = spans.reduce(with_experts())
    assert s["by_scope_ns"]["fwd/blk0/moe/softmax"] == 30.0
    assert spans.scope_ns(s, lambda parts: "moe" in parts[1:-1]) == 100.0
    # what a later family's reader is, whole (cf. step.head_share.py)
    run = run_with(s)
    assert spans.share(run, lambda s_: spans.scope_ns(
        s_, lambda parts: "moe" in parts[1:-1])) == pytest.approx(
            100 * 100 / 1000)
    assert spans.scope_ns(s, lambda parts: "nowhere" in parts) == 0


def test_another_familys_kernel_shows_and_is_not_read_as_attention():
    s = spans.reduce(with_experts())
    assert s["kernel_ns"] == {"attn.bthd_small.bwd": 200.0,
                              "moe.gmm.bwd": 70.0}
    run = run_with(s)
    assert harness.reader_for("attn.bwd_time_share.train").read(
        run) == pytest.approx(100 * 200 / 1000)
    # kernels, but none of attention's: the reader finds nothing
    s["kernel_ns"] = {"moe.gmm.bwd": 70.0}
    assert harness.reader_for("attn.bwd_time_share.train").read(
        run_with(s)) is None


def test_the_raw_trace_is_parsed_once_for_every_reader(monkeypatch,
                                                       tmp_path, capsys):
    import shutil

    d = tmp_path / "tbase-train" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    with gzip.open(SCOPED, "rb") as src, \
            open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    loads, real = [], spans.load.__wrapped__
    monkeypatch.setattr(spans, "load",
                        lambda path: loads.append(path) or real(path))
    run = run_with(None)
    run.window["traced_steps"] = 1
    read_all(run)                       # eight readers over for_run
    doc = spans.doc_for_run(run)        # a later reader of the events
    assert doc is spans.doc_for_run(run) and len(loads) == 1
    assert spans.reduce(doc)["by_scope_ns"] == run._spans["by_scope_ns"]
    # a run that traced nothing has no document either
    assert spans.doc_for_run(run_with(None, traced=False)) is None
