"""Request-scoped tracing & SLO plane (serving_trace.py): per-phase
latency decomposition on every terminal request, deadline attribution
on expired/rejected_early outcomes, censored-TTFT survivorship-bias
metering, SLO met/missed/burn accounting, per-request Chrome-trace
tracks that survive a supervised engine restart (one request, ONE
trace), the /requests view, and the telemetry-off zero-allocation
contract for the new hooks."""

import tracemalloc
import types

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, flags, monitor, serving, serving_trace
from paddle_tpu.models import transformer as T

BOS, EOS = 0, 1

_RESET_FLAGS = {"telemetry": False, "trace_dir": "",
                "trace_every_n_steps": 1, "serve_slo_ttft_ms": 0.0,
                "serve_slo_token_ms": 0.0, "serve_recent_requests": 256,
                "serve_admission_control": True}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags(dict(_RESET_FLAGS))
    yield
    monitor.stop_server()
    flags.set_flags(dict(_RESET_FLAGS))


def tiny_cfg(n_layer=1):
    return T.TransformerConfig(
        src_vocab_size=37, trg_vocab_size=41, max_length=64,
        d_model=16, d_inner=32, n_head=2, n_layer=n_layer,
        dropout=0.0, label_smooth_eps=0.0,
    )


@pytest.fixture(scope="module")
def weights():
    cfg = tiny_cfg()
    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        T.build(cfg, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, scope


def _srcs(k, seed=0, lens=(5, 3, 7, 4, 6, 2, 8, 5)):
    r = np.random.RandomState(seed)
    return [r.randint(2, 37, (lens[i % len(lens)],)).astype(np.int64)
            for i in range(k)]


def _engine(cfg, scope, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 10)
    return serving.ServingEngine(cfg, scope, src_len=8, bos_id=BOS,
                                 end_id=EOS, **kw)


# --------------------------------------------------------------------------
# per-phase latency decomposition
# --------------------------------------------------------------------------

def test_phase_decomposition_recorded_per_outcome(weights):
    """Every terminal request lands on the recently-terminated ring
    with measured queue-wait/prefill/decode/fetch milliseconds, TTFT,
    and (absent SLO targets) a null SLO scorecard."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    eng = _engine(cfg, scope)
    reqs = [eng.submit(s, max_new_tokens=5) for s in _srcs(3, seed=7)]
    eng.run_until_idle()
    eng.close()

    view = serving_trace.requests_view()
    assert view["inflight"] == []
    by_id = {r["trace_id"]: r for r in view["recent"]}
    assert set(by_id) == {q.trace_id for q in reqs}
    for q in reqs:
        rec = by_id[q.trace_id]
        assert rec["v"] == serving_trace.REQUEST_RECORD_SCHEMA_VERSION
        assert rec["outcome"] in ("completed", "length")
        assert set(rec["phases_ms"]) == set(serving_trace.PHASES)
        assert rec["phases_ms"]["prefill"] > 0.0
        assert rec["phases_ms"]["decode"] > 0.0
        assert rec["ttft_ms"] is not None and rec["ttft_ms"] > 0.0
        assert rec["wall_ms"] > 0.0
        assert rec["tokens"] == len(q.tokens)
        # no targets configured: scored as None, no attribution
        assert rec["slo"] == {"ttft": None, "token": None}
        assert rec["deadline_attribution"] is None
        assert rec["censored"] is False


@pytest.mark.slow
def test_phase_sums_cover_wall_time(weights):
    """The decomposition is honest: per-request phase milliseconds sum
    to the request's wall time within 20% — queue wait absorbs
    everything before admission and decode/fetch are measured per
    dispatch, so nothing material is double-counted or dropped."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    eng = _engine(cfg, scope)
    reqs = [eng.submit(s, max_new_tokens=6) for s in _srcs(4, seed=9)]
    eng.run_until_idle()
    eng.close()
    by_id = {r["trace_id"]: r
             for r in serving_trace.requests_view()["recent"]}
    for q in reqs:
        rec = by_id[q.trace_id]
        total = sum(rec["phases_ms"].values())
        assert total == pytest.approx(rec["wall_ms"],
                                      rel=0.20, abs=2.0), (
            f"{q.trace_id}: phases {rec['phases_ms']} sum {total} vs "
            f"wall {rec['wall_ms']}")


# --------------------------------------------------------------------------
# deadline attribution + SLO burn
# --------------------------------------------------------------------------

def test_deadline_attribution_under_overload(weights):
    """The overload half of the acceptance drill: a rejected_early
    refusal and an expired-in-queue request BOTH carry deadline
    attribution naming queue wait as the phase that ate the budget,
    and the deadline burn counter matches the outcome counts."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    eng = _engine(cfg, scope, slots=1, max_len=32, queue_depth=8)
    eng._token_ewma_s = 0.05  # white-box primed latency estimator
    a = eng.submit(_srcs(1, seed=51)[0], max_new_tokens=10)
    with pytest.raises(serving.DeadlineUnmeetable) as ei:
        eng.submit(_srcs(1, seed=52)[0], deadline_ms=20)
    rej = ei.value.request
    assert rej.outcome == "rejected_early"
    attr = rej.deadline_attr
    assert attr is not None and attr["phase"] == "queue_wait"
    assert attr["budget_ms"] == pytest.approx(20.0)
    assert set(attr["phases_ms"]) == set(serving_trace.PHASES)

    # expired in queue: admission control off lets a dead-on-arrival
    # deadline queue up; the admit-time check expires it before prefill
    flags.set_flags({"serve_admission_control": False})
    exp = eng.submit(_srcs(1, seed=53)[0], deadline_ms=0.001)
    flags.set_flags({"serve_admission_control": True})
    eng.run_until_idle()
    eng.close()
    assert a.outcome in ("completed", "length")
    assert exp.outcome == "expired"
    assert exp.deadline_attr["phase"] == "queue_wait"
    assert exp.deadline_attr["phase_ms"] > 0.0

    burn = monitor.counter("pt_slo_burn_total")
    assert burn.value(labels={"slo": "deadline",
                              "outcome": "rejected_early"}) == 1
    assert burn.value(labels={"slo": "deadline",
                              "outcome": "expired"}) == 1
    # the ring records carry the attribution too
    recs = {r["trace_id"]: r
            for r in serving_trace.requests_view()["recent"]}
    assert recs[rej.trace_id]["deadline_attribution"][
        "phase"] == "queue_wait"
    assert recs[exp.trace_id]["deadline_attribution"][
        "phase"] == "queue_wait"


def test_deadline_attribution_names_dominant_phase():
    """Attribution picks the dominant MEASURED phase, not always queue
    wait: a request whose decode ate the budget says so."""
    req = types.SimpleNamespace(queue_wait_s=0.01, prefill_s=0.02,
                                decode_s=0.5, fetch_s=0.01,
                                submit_ts=0.0, deadline_ts=0.3)
    attr = serving_trace._attribute_deadline(req, now=0.6)
    assert attr["phase"] == "decode"
    assert attr["phase_ms"] == pytest.approx(500.0)
    assert attr["budget_ms"] == pytest.approx(300.0)


# --------------------------------------------------------------------------
# censored TTFT (survivorship bias) + SLO scoring
# --------------------------------------------------------------------------

def test_censored_ttft_counts_against_slo_target(weights):
    """A request that expires before its first token never observes
    pt_serve_ttft_seconds — without the censored meter, p99 TTFT would
    IMPROVE as overload worsens. It must be metered censored and count
    against the TTFT target."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True, "serve_slo_ttft_ms": 10_000.0,
                     "serve_admission_control": False})
    eng = _engine(cfg, scope)
    exp = eng.submit(_srcs(1, seed=61)[0], deadline_ms=0.001)
    ok = eng.submit(_srcs(1, seed=62)[0], max_new_tokens=3)
    eng.run_until_idle()
    eng.close()
    assert exp.outcome == "expired" and exp.ttft_s is None
    assert exp.censored is True
    assert ok.outcome in ("completed", "length")

    assert monitor.counter("pt_serve_ttft_censored_total").value(
        labels={"outcome": "expired"}) == 1
    slo = serving_trace.slo_summary()
    assert slo["targets_ms"]["ttft"] == pytest.approx(10_000.0)
    assert slo["ttft"]["censored"] == 1
    assert slo["ttft"]["met"] == 1  # the survivor scored normally
    assert monitor.counter("pt_slo_burn_total").value(
        labels={"slo": "ttft", "outcome": "expired"}) == 1
    # refusals are NOT censored: never entered service
    assert "rejected_early" not in serving_trace.CENSORED_OUTCOMES


def test_slo_met_and_missed_scoring(weights):
    """Generous targets score met/met with zero burn; impossibly tight
    targets score missed/missed and burn both SLOs."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True, "serve_slo_ttft_ms": 60_000.0,
                     "serve_slo_token_ms": 60_000.0})
    eng = _engine(cfg, scope)
    ok = eng.submit(_srcs(1, seed=71)[0], max_new_tokens=3)
    eng.run_until_idle()
    assert ok.outcome in ("completed", "length")
    slo = serving_trace.slo_summary()
    assert slo["ttft"] == {"met": 1, "missed": 0, "censored": 0}
    assert slo["token"] == {"met": 1, "missed": 0}
    assert slo["burn"] == {}

    flags.set_flags({"serve_slo_ttft_ms": 0.0001,
                     "serve_slo_token_ms": 0.0001})
    bad = eng.submit(_srcs(1, seed=72)[0], max_new_tokens=3)
    eng.run_until_idle()
    eng.close()
    assert bad.outcome in ("completed", "length")
    slo = serving_trace.slo_summary()
    assert slo["ttft"]["missed"] == 1 and slo["token"]["missed"] == 1
    burn = monitor.counter("pt_slo_burn_total")
    assert burn.value(labels={"slo": "ttft", "outcome": bad.outcome}) == 1
    assert burn.value(labels={"slo": "token",
                              "outcome": bad.outcome}) == 1
    # the ring scorecards disagree across the flag flip
    recs = {r["trace_id"]: r
            for r in serving_trace.requests_view()["recent"]}
    assert recs[ok.trace_id]["slo"] == {"ttft": "met", "token": "met"}
    assert recs[bad.trace_id]["slo"] == {"ttft": "missed",
                                         "token": "missed"}


# --------------------------------------------------------------------------
# per-request trace tracks: one request, ONE trace across a restart
# --------------------------------------------------------------------------

def test_request_track_timeline_events(weights, tmp_path):
    """A request's life lands on one dynamic timeline track: queue +
    prefill + sampled decode/fetch spans and the terminal instant all
    share a tid >= REQUEST_TRACK_BASE, labeled by thread_name
    metadata."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    eng = _engine(cfg, scope)
    req = eng.submit(_srcs(1, seed=81)[0], max_new_tokens=4)
    eng.run_until_idle()
    eng.close()
    assert req.outcome in ("completed", "length")

    evs = [e for e in monitor.trace_events()
           if e.get("args", {}).get("req") == req.trace_id]
    names = {e["name"] for e in evs}
    assert {"submit", "queue", "prefill", "decode",
            "fetch", f"outcome:{req.outcome}"} <= names
    tids = {e["tid"] for e in evs}
    assert tids == {req.trace_tid}
    assert req.trace_tid >= monitor.REQUEST_TRACK_BASE
    by_name = {e["name"]: e for e in evs}
    assert by_name["queue"]["ph"] == "X"
    assert by_name["prefill"]["ph"] == "X"
    assert by_name[f"outcome:{req.outcome}"]["ph"] == "i"
    # decode spans are annotated with the emitted token + its logit
    dec = by_name["decode"]["args"]
    assert dec["token"] == req.tokens[-1] or "token" in dec
    assert isinstance(dec["logit"], float)
    # the track is labeled in the exportable snapshot
    metas = [e for e in monitor.trace_snapshot()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any(e["tid"] == req.trace_tid
               and e["args"]["name"] == f"req {req.trace_id}"
               for e in metas)


def test_supervised_restart_replays_as_one_trace(weights, tmp_path):
    """The restart half of the acceptance drill: an engine-killing
    decode fault triggers a supervised warm restart; the replayed
    request's tokens are byte-identical, its events before AND after
    the restart share ONE track, and the restart itself is annotated
    as a span on that track."""
    cfg, scope = weights
    srcs = _srcs(2, seed=41)
    clean_eng = _engine(cfg, scope)
    clean_reqs = [clean_eng.submit(s) for s in srcs]
    clean_eng.run_until_idle()
    clean = [list(q.tokens) for q in clean_reqs]
    clean_eng.close()

    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    sup = serving.EngineSupervisor(
        cfg, scope, slots=2, src_len=8, max_len=10, bos_id=BOS,
        end_id=EOS, poll_s=0.005, wedge_timeout_ms=60_000,
        max_restarts=2)
    try:
        warm = sup.submit(_srcs(1, seed=42)[0], max_new_tokens=2)
        assert warm.result(timeout=60) is not None
        faults.arm("serve.decode:raise@2")
        try:
            reqs = [sup.submit(s) for s in srcs]
            streams = [r.result(timeout=120) for r in reqs]
        finally:
            faults.disarm()
    finally:
        sup.close(drain_timeout_s=5.0)
    assert streams == clean
    replayed = [r for r in reqs if r.replays >= 1]
    assert replayed, "no request was replayed"
    for r in replayed:
        evs = [e for e in monitor.trace_events()
               if e.get("args", {}).get("req") == r.trace_id]
        tids = {e["tid"] for e in evs}
        assert tids == {r.trace_tid}, (
            f"{r.trace_id} smeared over tracks {tids}")
        names = [e["name"] for e in evs]
        assert names.count("submit") == 1  # ONE trace, not re-submit
        restarts = [e for e in evs if e["name"] == "restart"]
        assert restarts and all(e["ph"] == "X" for e in restarts)
        assert restarts[0]["args"]["replay"] == r.replays
        assert f"outcome:{r.outcome}" in names


def test_eviction_lands_on_victims_track(weights, tmp_path):
    """Containment epilogue: a slot-hinted decode fault's eviction and
    scrub instants land on the VICTIM's own track."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True, "trace_dir": str(tmp_path)})
    eng = _engine(cfg, scope, max_len=32)
    reqs = [eng.submit(s, max_new_tokens=8) for s in _srcs(2, seed=91)]
    faults.arm("serve.decode:raise(poisoned slot=1)@3")
    try:
        eng.run_until_idle()
    finally:
        faults.disarm()
    eng.close()
    victims = [r for r in reqs if r.outcome == "evicted"]
    assert victims, "fault did not evict"
    v = victims[0]
    evs = [e for e in monitor.trace_events()
           if e.get("args", {}).get("req") == v.trace_id]
    names = {e["name"] for e in evs}
    assert {"evicted", "scrub", "outcome:evicted"} <= names
    assert {e["tid"] for e in evs} == {v.trace_tid}
    ev = next(e for e in evs if e["name"] == "evicted")
    assert ev["args"]["cause"] == "fault"


# --------------------------------------------------------------------------
# /requests view + ring bounds
# --------------------------------------------------------------------------

def test_requests_view_inflight_states_and_ring(weights):
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    eng = _engine(cfg, scope)
    reqs = [eng.submit(s, max_new_tokens=5) for s in _srcs(4, seed=11)]
    view = serving_trace.requests_view()
    assert len(view["inflight"]) == 4
    assert all(r["state"] == "queued" and r["slot"] is None
               for r in view["inflight"])
    eng.step()  # admissions fill the 2 slots
    view = serving_trace.requests_view()
    rows = {r["trace_id"]: r for r in view["inflight"]}
    states = [r["state"] for r in rows.values()]
    assert states.count("decoding") == 2 and states.count("queued") == 2
    for r in rows.values():
        if r["state"] == "decoding":
            assert isinstance(r["slot"], int)
        assert r["age_ms"] >= 0.0
        assert set(r["phases_ms"]) == set(serving_trace.PHASES)
    eng.run_until_idle()
    eng.close()
    view = serving_trace.requests_view()
    assert view["inflight"] == []
    assert len(view["recent"]) == 4
    assert view["recent_cap"] == 256
    assert {q.trace_id for q in reqs} == {
        r["trace_id"] for r in view["recent"]}


def test_recent_ring_bounded_by_flag(weights):
    cfg, scope = weights
    flags.set_flags({"telemetry": True, "serve_recent_requests": 3})
    eng = _engine(cfg, scope)
    reqs = [eng.submit(s, max_new_tokens=2) for s in _srcs(5, seed=13)]
    eng.run_until_idle()
    eng.close()
    assert all(q.done for q in reqs)
    view = serving_trace.requests_view()
    assert view["recent_cap"] == 3
    assert len(view["recent"]) == 3  # oldest evicted, newest kept


# --------------------------------------------------------------------------
# telemetry-off: the zero-allocation contract for the new hooks
# --------------------------------------------------------------------------

def test_disabled_serving_allocates_nothing_in_request_plane(weights):
    """With telemetry off, the request-plane hooks wired through
    submit/admit/decode/finish must add zero allocations attributable
    to serving_trace.py — the serving hot loop stays permanently
    instrumented for free."""
    cfg, scope = weights
    assert not monitor.enabled() and not monitor.trace_active()
    eng = _engine(cfg, scope)
    warm = eng.submit(_srcs(1, seed=21)[0], max_new_tokens=2)
    eng.run_until_idle()  # warm compiles + lazy state
    assert warm.done
    n_reqs = 10
    srcs = _srcs(n_reqs, seed=22)
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    reqs = [eng.submit(s, max_new_tokens=3) for s in srcs]
    eng.run_until_idle()
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    eng.close()
    assert all(q.done for q in reqs)
    stats = snap.compare_to(base, "filename")
    grew = sum(s.size_diff for s in stats
               if s.traceback[0].filename.endswith("serving_trace.py")
               and s.size_diff > 0)
    assert grew < n_reqs * 16, (
        f"disabled serving run allocated {grew}B in serving_trace.py "
        f"over {n_reqs} requests")
    # and the ring stayed empty: nothing was recorded
    assert serving_trace.requests_view()["recent"] == []
