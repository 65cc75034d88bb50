"""perf/trace.py against a trace recorded on the v5e (one step of
tbase-train, cut down by perf/tools/cut_trace.py) and by hand."""

import os

import pytest

from perf import harness, trace

FIXTURE = os.path.join(harness.HERE, "fixtures",
                       "tbase-train-v5e-one-step.xplane.pb.gz")
PALLAS = ('%step_fn.36 = (bf16[128,256,512]{2,1,0:T(8,128)(2,1)}, '
          'f32[128,256,8]{2,1,0:T(8,128)}) custom-call(s32[2]{0:T(128)S(1)} '
          '%pad_add_fusion.6, bf16[128,256,512]{2,1,0} %x), '
          'custom_call_target="tpu_custom_call", operand_layout_constr={}')
FUSION = ('%fusion.3654 = (bf16[128,256]{1,0:T(8,128)(2,1)S(1)}, '
          'bf16[128,256,10000]{1,2,0:T(8,128)(2,1)}) fusion(f32[512,10000]'
          '{0,1:T(8,128)} %state__proj_colp_w__.1), kind=kOutput')
ALLREDUCE = ('%all-reduce-start.3 = f32[512,2048]{1,0:T(8,128)} '
             'all-reduce-start(f32[512,2048]{1,0} %fusion.7), channel_id=4')
OTHER_CC = ('%custom-call.46 = u64[2]{0:T(128)S(1)} custom-call(u32[2]{0} '
            '%a, u32[2]{0} %b), custom_call_target="X64Combine"')


@pytest.fixture(scope="module")
def doc():
    return trace.load(FIXTURE)


def test_the_fixture_is_small_and_loads_the_device_plane(doc):
    assert os.path.getsize(FIXTURE) < 400 * 1024
    (plane,) = doc["planes"]
    assert plane["name"] == "/device:TPU:0"
    lines = {ln["name"]: len(ln["events"]) for ln in plane["lines"]}
    assert lines["XLA Ops"] == 9039 and lines["XLA Modules"] == 2
    assert lines["Async XLA Ops"] == 3587


def test_busy_union_idle_share_and_time_by_kind(doc):
    r = trace.reduce(doc)
    assert r["devices"] == 1 and r["ops_seen"] == 9039
    # one 119.3 ms step of tbase-train and the head of the next
    assert r["window_s"] == pytest.approx(0.122263645, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.121627956, rel=1e-6)
    assert r["idle_share"] == pytest.approx(0.0051993, rel=1e-3)
    # 36 Pallas attention calls a step (and the next step's first)
    assert r["by_kind_s"]["pallas"] == pytest.approx(0.023363303, rel=1e-6)
    assert r["by_kind_s"]["collective"] == 0.0
    assert sum(r["by_kind_s"].values()) == pytest.approx(r["busy_s"],
                                                         rel=1e-6)
    assert r["device_ops"][0][0] == \
        "multiply_subtract_fusion.2 fusion f32[512,10000]"
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert r["idle_gaps"][0][0].startswith("before copy-start")


def test_pallas_calls_are_told_from_other_custom_calls(doc):
    ops = next(ln["events"] for ln in doc["planes"][0]["lines"]
               if ln["name"] == "XLA Ops")
    assert sum(1 for e in ops if trace.op_kind(e[0]) == "pallas") == 37
    assert trace.op_kind(PALLAS) == "pallas"
    assert trace.op_kind(OTHER_CC) == "other"
    assert trace.op_kind(FUSION) == "other"
    assert trace.op_kind(ALLREDUCE) == "collective"
    assert trace.parse(FUSION)[:2] == ("fusion.3654", "fusion")
    assert trace.label(PALLAS) == "step_fn.36 pallas bf16[128,256,512]"
    assert trace.label(ALLREDUCE) == \
        "all-reduce-start.3 all-reduce-start f32[512,2048]"


def test_union_and_self_times_by_hand():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.union_ns([]) == 0
    nested = [["while", 0, 100], ["a", 10, 20], ["b", 40, 30],
              ["c", 120, 5]]
    assert trace.self_times(nested) == [("while", 50), ("a", 20),
                                        ("b", 30), ("c", 5)]


def test_several_chips_are_averaged_and_collectives_counted():
    def plane(n, shift):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": [
                [FUSION, 0 + shift, 600], [ALLREDUCE, 600 + shift, 10],
                ["%all-reduce-done.3 = f32[8]{0} all-reduce-done(f32[8]{0} "
                 "%all-reduce-start.3)", 800 + shift, 100]]},
            {"name": "Async XLA Ops", "events": [
                [ALLREDUCE, 600 + shift, 300]]}]}
    r = trace.reduce({"planes": [plane(0, 0), plane(1, 100),
                                 {"name": "/host:CPU", "lines": []}]})
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(710e-9)
    assert r["by_kind_s"]["collective"] == pytest.approx(110e-9)
    assert r["async_collective_s"] == pytest.approx(300e-9)
    assert r["idle_share"] == pytest.approx(0.29)


def test_a_trace_without_device_ops_reduces_to_nothing():
    assert trace.reduce({"planes": [{"name": "/host:CPU",
                                     "lines": []}]}) is None


# --- a Mosaic call is named by its kernel --------------------------------

SCOPED = os.path.join(harness.HERE, "fixtures",
                      "tbase-train-v5e-scoped-one-step.xplane.pb.gz")
V5E = harness.load_json("perf", "peaks.json")["TPU v5 lite"]


def mosaic(inst):
    return (f'%{inst} = (bf16[8,16,32]{{2,1,0}}) custom-call(bf16[8,16,32]'
            f'{{2,1,0}} %q), custom_call_target="tpu_custom_call"')


def traced_run(summary, steps=1):
    """What the two attention readers ask of a run record."""
    import types

    from perf import flops

    cfg = harness.load_json("perf", "configs", "transformer-base.json")
    n = cfg["n_layer"]
    return types.SimpleNamespace(
        trace=summary, devices=[types.SimpleNamespace(
            device_kind="TPU v5 lite")],
        window={"traced_steps": steps,
                "attention": flops.attention_train_cost(
                    {"enc_self": n, "dec_self_causal": n, "dec_cross": n},
                    cfg, 128, 256)})


def attention_readers(run):
    return [harness.reader_for(m).read(run)
            for m in ("attn.time_share.train", "train_attn_roofline")]


@pytest.mark.parametrize("path,kernels,family", [
    (SCOPED, {"attn.bthd_small.fwd", "attn.bthd_small.bwd"}, "attn"),
    # cut before PR 24 named the kernels: the jitted function's name
    (FIXTURE, {"step_fn"}, "step_fn")])
def test_mosaic_time_by_kernel_name_on_the_fixtures(path, kernels, family):
    r = trace.reduce(trace.load(path))
    assert set(r["by_kernel_s"]) == kernels
    assert sum(r["by_kernel_s"].values()) == pytest.approx(
        r["by_kind_s"]["pallas"], rel=1e-12)
    # one family: summed in the same order as the kind, the same bits
    assert r["by_family_s"] == {family: r["by_kind_s"]["pallas"]}


def test_the_attention_readers_on_the_scoped_fixture_bit_for_bit(
        monkeypatch):
    """The values the parent's readers (``by_kind_s["pallas"]``) gave on
    this fixture, to the last bit: every Mosaic call in it is attn.*."""
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    r = trace.reduce(trace.load(SCOPED))
    assert attention_readers(traced_run(r)) == [12.140539216516435,
                                                48.65800830206971]
    assert attention_readers(traced_run(r))[0] == \
        100.0 * r["by_kind_s"]["pallas"] / r["busy_s"]
    # the older trace names no kernel: nothing is read as attention
    old = trace.reduce(trace.load(FIXTURE))
    assert attention_readers(traced_run(old)) == [None, None]


def test_only_the_attn_family_counts_as_attention(monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    ops = [[mosaic("attn.x.fwd.7"), 0, 100], [FUSION, 100, 400],
           [mosaic("moe.gmm.fwd.2"), 500, 300],
           [mosaic("ragged-dot-none.3"), 800, 150],
           [mosaic("attn.x.fwd.8"), 950, 50], [OTHER_CC, 1000, 10]]
    r = trace.reduce({"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]}]})
    assert r["by_kind_s"]["pallas"] == pytest.approx(600e-9)
    assert r["by_kernel_s"] == {
        "attn.x.fwd": pytest.approx(150e-9),
        "moe.gmm.fwd": pytest.approx(300e-9),
        "ragged-dot-none": pytest.approx(150e-9)}
    assert r["by_family_s"] == {
        "attn": pytest.approx(150e-9), "moe": pytest.approx(300e-9),
        "ragged-dot-none": pytest.approx(150e-9)}
    share, roofline = attention_readers(traced_run(r))
    assert share == pytest.approx(100 * 150 / 1010)
    alone = trace.reduce({"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [ops[0], ops[4]]}]}]})
    assert roofline == attention_readers(traced_run(alone))[1]
    # a program with Mosaic calls but no attention kernel reads nothing
    none = trace.reduce({"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops[1:4]}]}]})
    assert attention_readers(traced_run(none)) == [None, None]
    assert trace.kernel_name(mosaic("attn.bhtd.bwd_dq.12")) == \
        "attn.bhtd.bwd_dq"
    assert trace.kernel_name(mosaic("step_fn.36")) == "step_fn"
