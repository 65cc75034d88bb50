"""The window's time-line (perf/step_records.py and the five metrics
that read it): the program's step records, sorted by their ``t0`` into
the measured window, the traced stretch behind it and the rest, and
reduced to how stationary the window was (``window.step_drift.train``),
how far the traced stretch stands from it
(``window.trace_step_ratio.train``), what it lost in single stretches
(``window.late_share.train``), what the collector took
(``window.gc_ms.train``) and the host's call with no profiler on
(``exec.window_run_ms_per_call.train``). The reduction is held on
records written by hand; a tiny traced run reports all five."""

import json
import math

import pytest

import perfbench_tiny as tiny
from paddle_tpu import monitor
from perf import harness, step_records
from perf.kinds import train

NEW = {"window.step_drift.train": "ratio",
       "window.trace_step_ratio.train": "ratio",
       "window.late_share.train": "%",
       "window.gc_ms.train": "ms",
       "exec.window_run_ms_per_call.train": "ms"}
# per_layer as it stood before PR 54 appended to it, in its order
OLDER = """exec.host_ms_per_step.train cache.first_call_s
lower.dense_attn_calls.train step.mfu.train mesh.collective_share
attn.time_share.train train_attn_roofline device.idle_share.train
device.peak_hbm_gb.train lower.scoped_share.train step.bwd_share.train
step.opt_share.train step.head_share.train attn.bwd_time_share.train
exec.run_ms_per_call.train exec.prepare_ms_per_call.train
exec.idle_in_run_share.train mesh.replicated_rng_draws.train
step.block_share.train moe.step_share.train moe.route_share.train
moe.gmm_roofline.train moe.max_expert_load.train gdn.step_share.train
gdn.scan_share.train gdn.scan_roofline.train
lower.recurrent_gdn_calls.train mla.step_share.train
mla.assemble_share.train mtp.step_share.train
lower.whole_buffer_moe_calls.train exec.first_call_s setup.trace_s
setup.lower_s cache.backend_s cache.persistent_writes setup.jax_traces
lower.op_trace_s setup.unnamed_s lower.xla_conv_calls.train
swa.step_share.train swa.roofline.train lower.full_band_swa_calls.train
lower.split_bwd_attn_calls.train""".split()
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


# --- BENCHMARK.json ------------------------------------------------------


def unlisted():
    """An in-memory copy of BENCHMARK.json without the five entries: a
    tree in which the readers are files and no more, as the parent's."""
    return dict(tiny.BENCH, per_layer=[
        m for m in tiny.BENCH["per_layer"] if m["name"] not in NEW])


@pytest.mark.parametrize("name", NEW)
def test_the_reader_is_an_entry_that_lists_every_train_cell(name):
    """The five are entries since PR 54, which appended them at the END
    of ``per_layer`` (the driver takes a new entry there alone), each
    with every cell that trains."""
    train_cells = tiny.cells_named(tiny.BENCH, "train_tokens_per_s")
    assert tiny.listed_as(name, NEW[name], "lower", "program_span",
                          "Executor", *train_cells)
    assert callable(harness.reader_for(name).read)
    assert tiny.entry(tiny.BENCH, name) in harness.cell_metrics(
        tiny.BENCH, "tbase-train-dp4", "per_layer")


def test_every_older_entry_is_where_it_was():
    """What PR 54 appended stands behind the entries that were there,
    and those stand in their order: the driver reads an entry anywhere
    but at the end of its list as a change to what was there."""
    names = [m["name"] for m in tiny.BENCH["per_layer"]]
    assert names[:len(OLDER)] == OLDER
    assert set(NEW) <= set(names[len(OLDER):])


# --- the reduction, on records written by hand ---------------------------

T_WINDOW = 1000.0


def records(distances, t_first=T_WINDOW + 0.001, wall_ms=5.0, **extra):
    """Step records whose ``t0`` stand ``distances`` apart."""
    t, out = t_first, []
    for d in list(distances) + [0.0]:
        out.append(dict({"kind": "step", "t0": t, "wall_ms": wall_ms,
                         "gc_ms": 0.0}, **extra))
        t += d
    return out


def setup_records():
    """What a run logs before its window: a start-up program, the
    eval clone, two warm-up steps."""
    return records([8.0, 2.0, 0.4, 0.1], t_first=T_WINDOW - 10.6,
                   wall_ms=900.0)


def reduced(window, traced=(), probe=(), seconds=20.0):
    recs = setup_records() + list(window)
    t = T_WINDOW + seconds + 0.5
    recs += records(traced, t_first=t) if traced else []
    recs += records(probe, t_first=t + 3.0, sampled=True) if probe else []
    return step_records.reduce(recs, T_WINDOW, seconds,
                               len(traced) + 1 if traced else 0)


def test_an_even_cadence_is_stationary_and_loses_nothing():
    s = reduced(records([0.1] * 199), traced=[0.1] * 19)
    assert s["window"] == 200 and s["traced"] == 20
    assert s["drift"] == pytest.approx(1.0)
    assert s["trace_ratio"] == pytest.approx(1.0)
    assert s["late_share"] == 0.0 and s["gc_ms"] == 0.0 and s["late"] == []
    assert s["run_ms"] == pytest.approx(5.0)
    assert s["step_s"] == pytest.approx(0.1)


@pytest.mark.parametrize("step_s", [0.01, 0.1, 0.4])
@pytest.mark.parametrize("gc_ms", [0.0, 100.0])
def test_one_gap_of_100_ms_in_20_s_reads_half_a_percent(step_s, gc_ms):
    n = int(19.9 / step_s)
    d = [step_s] * n
    d[n // 2] += 0.1
    window = records(d)
    window[n // 2 + 1]["gc_ms"] = gc_ms   # the record behind the gap
    s = reduced(window)
    assert s["late_share"] == pytest.approx(0.5)
    assert s["gc_ms"] == gc_ms
    (late,) = s["late"]
    assert late == {"at": n // 2 + 1, "gc_ms": [0.0, gc_ms],
                    "ms": pytest.approx((step_s + 0.1) * 1e3),
                    "lost_ms": pytest.approx(100.0),
                    "next_ms": [pytest.approx(step_s * 1e3)] * 2,
                    "call_ms": [5.0, 5.0]}
    assert s["drift"] == pytest.approx(1.0)


@pytest.mark.parametrize("lost_s, slow", [
    (2.4, 1.0),       # one stretch of 2.4 s
    (0.0, 1.13),      # every step 13% slow: the device's, not a gap
])
def test_a_lost_stretch_reads_its_length_and_a_slow_window_nothing(
        lost_s, slow):
    step = 0.105 * slow
    d = [step] * 150
    d[70] += lost_s
    seconds = sum(d) + 2 * step + 0.006     # two steps behind the last
    s = reduced(records(d), seconds=seconds)
    assert s["late_share"] == pytest.approx(100.0 * lost_s / seconds)
    assert [late["at"] for late in s["late"]] == ([71] if lost_s else [])


def test_a_collection_inside_a_call_is_in_the_record_in_front():
    """``tbase-train-dp4``'s window as the chip showed it: a full
    collection inside call 331, whose record takes it, and the device out
    of steps before the next call comes."""
    d = [0.0278] * 714
    d[331] = 0.1934
    window = records(d, wall_ms=16.0)
    window[331].update(wall_ms=192.4, gc_ms=172.8)
    seconds = sum(d) + 0.016 + 2 * 0.0278 + 0.001
    s = reduced(window, seconds=seconds)
    assert s["late"] == [{
        "at": 332, "ms": pytest.approx(193.4), "gc_ms": [172.8, 0.0],
        "call_ms": [192.4, 16.0], "next_ms": [pytest.approx(27.8)] * 2,
        "lost_ms": pytest.approx(165.6)}]
    assert s["gc_ms"] == 172.8
    assert s["late_share"] == pytest.approx(16.56 / seconds)


@pytest.mark.parametrize("tail_steps, lost_steps", [
    (2.0, 0.0), (2.4, 0.0),     # the two steps in flight, and a little
    (2.6, 0.6),                 # over 1.25 x the usual: late, by 0.6
    (13.0, 11.0),               # 153 calls on time, then a drain of 1.7 s
])
def test_the_windows_end_is_a_distance_too(tail_steps, lost_steps):
    # (the usual end: the last call's 5 ms, then two steps)
    step = 0.1314
    d = [step] * 152
    seconds = sum(d) + tail_steps * step + 0.005 + 0.001
    traced = records([step] * 17, t_first=T_WINDOW + seconds + 0.5)
    traced[0]["gc_ms"] = 1.5
    s = step_records.reduce(setup_records() + records(d) + traced,
                            T_WINDOW, seconds, 18)
    assert (s["window"], s["traced"]) == (153, 18)
    assert s["late_share"] == pytest.approx(
        100.0 * lost_steps * step / seconds)
    if not lost_steps:
        assert s["late"] == []
        return
    assert s["late"] == [{
        "at": 153, "ms": pytest.approx(tail_steps * step * 1e3 + 5.0),
        "lost_ms": pytest.approx(lost_steps * step * 1e3),
        "next_ms": [], "gc_ms": [0.0, 1.5], "call_ms": [5.0]}]


@pytest.mark.parametrize("behind, lost_ms", [
    ([0.0385, 0.1285], 0.0),     # made up at once: the device never waited
    ([0.0885, 0.0885], 10.0),    # over two calls, all but 10 ms
    ([0.0035, 0.0035], 0.0),     # more than made up: two steps were done
    ([0.1285, 0.0385], 0.0),
    ([0.1285, 0.1285], 90.0),    # not made up: the device stood still
    ([0.1485, 0.1285], 90.0),    # (a slower call behind it is no gain)
])
def test_a_late_call_the_next_ones_make_up_for_loses_nothing(behind,
                                                             lost_ms):
    """A host late by 90 ms of a 128.5 ms step with two in flight."""
    d = [0.1285] * 155
    d[58:61] = [0.2185] + behind
    s = reduced(records(d))
    (late,) = s["late"]
    assert (late["at"], late["ms"]) == (59, pytest.approx(218.5))
    assert late["next_ms"] == [pytest.approx(y * 1e3) for y in behind]
    assert late["lost_ms"] == pytest.approx(lost_ms, abs=1e-6)
    assert s["late_share"] == pytest.approx(lost_ms / 200.0, abs=1e-9)


@pytest.mark.parametrize("flat, drift", [
    (6, 496 / 412),    # its first and last tenth at 412 and 496: 1.20
    (0, 1.177),        # a straight line: a tenth's mean lies inside it
])
def test_a_climb_from_412_to_496_ms_reads_a_drift_of_a_fifth(flat, drift):
    n = 43 - 2 * flat
    d = [0.412] * flat + [0.412 + (0.496 - 0.412) * i / (n - 1)
                          for i in range(n)] + [0.496] * flat
    s = reduced(records(d))
    assert s["window"] == 44
    assert s["drift"] == pytest.approx(drift, abs=1e-3)
    assert s["late_share"] == 0.0


def test_a_traced_stretch_9_percent_slower_reads_1_09():
    s = reduced(records([0.3905] * 50), traced=[0.3905, 0.3905]
                + [0.3905 * 1.09] * 3)
    assert s["traced"] == 6
    assert s["trace_ratio"] == pytest.approx(1.09)


def test_set_up_and_the_probe_are_in_neither_stretch():
    window = records([0.1] * 199)
    s = reduced(window, traced=[0.1] * 19, probe=[0.2] * 9)
    assert (s["window"], s["traced"]) == (200, 20)
    assert s["run_ms"] == pytest.approx(5.0)       # set-up's calls: 900
    # with no traced stretch the probe's records are not taken for one
    recs = setup_records() + window + records(
        [0.2] * 9, t_first=T_WINDOW + 23.0, sampled=True)
    s = step_records.reduce(recs, T_WINDOW, 20.0, 10)
    assert s["traced"] == 0 and s["trace_ratio"] is None
    # other kinds of record and ones with no clock are passed over
    recs.insert(6, {"kind": "eval", "t0": T_WINDOW + 0.2, "wall_ms": 1.0})
    recs.insert(7, {"kind": "step", "wall_ms": 1.0})
    assert step_records.reduce(recs, T_WINDOW, 20.0, 10)["window"] == 200


@pytest.mark.parametrize("what", ["wrapped", "no-clock", "empty"])
def test_no_time_line_from_part_of_a_window(what):
    recs = {"wrapped": records([0.1] * 150, t_first=T_WINDOW + 5.0),
            "no-clock": [{"kind": "step", "wall_ms": 5.0}] * 50,
            "empty": []}[what]
    assert step_records.reduce(recs, T_WINDOW, 20.0, 0) is None


@pytest.mark.parametrize("steps, has", [(10, False), (11, True)])
def test_under_eight_distances_there_is_no_tenth_to_compare(steps, has):
    # (steps - 1 distances, less the first IN_FLIGHT)
    s = reduced(records([0.1] * (steps - 1)), traced=[0.1] * 4,
                seconds=2.0)
    assert s["window"] == steps and s["gc_ms"] == 0.0
    assert s["run_ms"] == pytest.approx(5.0)
    for key in ("drift", "trace_ratio", "late_share"):
        assert (s[key] is not None) == has


def test_the_first_distances_of_a_stretch_are_left_out():
    # nothing is in flight yet: the loop's first calls follow each other
    # at the host's pace, in the window and in the traced stretch
    assert train.IN_FLIGHT == step_records.IN_FLIGHT == 2
    s = reduced(records([0.004, 0.004] + [0.1] * 100),
                traced=[0.004, 0.004] + [0.1] * 10)
    assert s["step_s"] == pytest.approx(0.1)
    assert s["trace_ratio"] == pytest.approx(1.0)


# --- for_run and the result line -----------------------------------------


class Run:
    def __init__(self, t_window=T_WINDOW):
        self.t_window = t_window
        self.window = {"steps": 200, "seconds": 20.0, "traced_steps": 20}


def test_for_run_reads_the_ring_once_and_says_what_it_found(monkeypatch,
                                                            capsys):
    ring = setup_records() + records([0.1] * 199) + records(
        [0.1] * 19, t_first=T_WINDOW + 20.5)
    monkeypatch.setattr(monitor, "recent_steps", lambda: list(ring))
    run = Run()
    s = step_records.for_run(run)
    assert (s["window"], s["traced"]) == (200, 20)
    assert "step records: window 200 (the loop counted 200)" in \
        capsys.readouterr().out
    ring.clear()
    assert step_records.for_run(run) is s       # kept on the run


@pytest.mark.parametrize("ring, says", [
    ("wrapped", "it wrapped"), ("no-clock", ""), ("empty", "")])
def test_for_run_gives_none_and_every_reader_with_it(ring, says,
                                                     monkeypatch, capsys):
    recs = {"wrapped": records([0.1] * 150, t_first=T_WINDOW + 5.0),
            "no-clock": [{"kind": "step", "wall_ms": 5.0}] * 50,
            "empty": []}[ring]
    monkeypatch.setattr(monitor, "recent_steps", lambda: recs)
    run = Run()
    assert [harness.reader_for(n).read(run) for n in NEW] == [None] * 5
    out = capsys.readouterr().out
    assert (says in out) if says else ("step records" not in out)


@pytest.mark.parametrize("traced, entries", [
    (True, True), (True, False), (False, True)])
def test_a_tiny_run_reports_the_five_only_when_traced(
        traced, entries, monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    monitor.reset()
    cell = dict(tiny.train_cell("tbase-train"), trace_seconds=0.5)
    run = tiny.make_run(cell, tiny.config(cell["config"]), seconds=1.0,
                        traced=traced, bench=None if entries else unlisted())
    train.run(run)
    line = json.loads(json.dumps(harness.result_line(run)))
    assert line["correct"], line
    got = {n: line["metrics"][n] for n in NEW if n in line["metrics"]}
    if not traced:
        assert got == {} and step_records.for_run(run) is None
        return
    s = step_records.for_run(run)
    # every call of the window and of the traced stretch has its record
    assert s["window"] == run.window["steps"]
    assert s["traced"] == run.window["traced_steps"]
    values = {n: harness.reader_for(n).read(run) for n in NEW}
    assert all(math.isfinite(v) for v in values.values())
    assert values["exec.window_run_ms_per_call.train"] > 0.0
    if not entries:     # a BENCHMARK.json without them: files, no entries
        assert got == {}
        return
    assert {n: m["unit"] for n, m in got.items()} == NEW
    assert {n: m["value"] for n, m in got.items()} == values
