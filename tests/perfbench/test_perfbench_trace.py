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
