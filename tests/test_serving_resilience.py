"""Serving-plane resilience (serving.py): decode fault containment,
supervised warm engine restart, and deadline-aware overload shedding.

The load-bearing drills:

- **containment**: a slot-hinted decode/fetch fault (or per-slot
  non-finite logits) evicts ONLY the poisoned slot — every other
  in-flight request's token stream is byte-identical to an undisturbed
  run — and the freed slot serves the next admission.
- **supervised restart**: an engine-killing fault (unhinted raise,
  wedged decode loop) triggers an EngineSupervisor restart with ZERO
  fresh XLA compiles (the rebuilt engine's compiles are all hits in
  jax's persistent cache), after which replayed requests return
  byte-identical tokens.
- **overload**: with submit rate over capacity, unmeetable-deadline
  requests are refused AT SUBMIT (outcome ``rejected_early``, never
  queued), admitted requests' per-token p99 stays within 2x the
  unloaded p99, and no handle ever hangs; sustained saturation engages
  brownout (admissions' max_new_tokens capped).
"""

import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, flags, monitor, numerics, serving
from paddle_tpu.models import transformer as T

from jax_cache_events import placed_in_process

BOS, EOS = 0, 1


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


def _undisturbed(cfg, scope, srcs, slots, max_len=10, **kw):
    """Token streams of an undisturbed engine run over ``srcs``."""
    eng = serving.ServingEngine(cfg, scope, slots=slots, src_len=8,
                                max_len=max_len, bos_id=BOS, end_id=EOS,
                                **kw)
    reqs = [eng.submit(s) for s in srcs]
    eng.run_until_idle()
    out = [list(q.tokens) for q in reqs]
    eng.close()
    return out


@pytest.fixture()
def telemetry():
    flags.set_flags({"telemetry": True})
    try:
        yield
    finally:
        flags.set_flags({"telemetry": False})


# --------------------------------------------------------------------------
# decode fault containment
# --------------------------------------------------------------------------

def test_slot_hinted_decode_fault_evicts_only_poisoned_slot(
        weights, telemetry):
    """The chaos drill: serve.decode:raise(slot=1) mid-stream evicts
    only slot 1 — its request finishes 'evicted' with the partial
    output, every other stream is byte-identical to an undisturbed run,
    and the freed slot serves a queued request."""
    cfg, scope = weights
    srcs = _srcs(4, seed=31)
    clean = _undisturbed(cfg, scope, srcs, slots=3)

    ev0 = monitor.counter("pt_serve_slot_evictions_total").value(
        labels={"cause": "fault"})
    eng = serving.ServingEngine(cfg, scope, slots=3, src_len=8, max_len=10,
                                bos_id=BOS, end_id=EOS)
    reqs = [eng.submit(s) for s in srcs]
    faults.arm("serve.decode:raise(poisoned slot=1)@3")
    try:
        eng.run_until_idle()  # the fault is CONTAINED: nothing raises
    finally:
        faults.disarm()
    assert eng.state == "serving"  # the engine never failed
    # slot 1's occupant (admission order = submit order): evicted with
    # the tokens emitted before the poisoned step — a byte-prefix of
    # its undisturbed stream
    assert reqs[1].outcome == "evicted"
    assert list(reqs[1].tokens) == clean[1][:len(reqs[1].tokens)]
    assert len(reqs[1].tokens) < len(clean[1])
    # every healthy stream byte-identical
    for i in (0, 2, 3):
        assert list(reqs[i].tokens) == clean[i], f"request {i}"
        assert reqs[i].outcome in ("completed", "length")
    # the queued 4th request was admitted into a freed slot
    assert reqs[3].done
    assert monitor.counter("pt_serve_slot_evictions_total").value(
        labels={"cause": "fault"}) == ev0 + 1
    eng.close()


def test_nonfinite_logits_evict_only_poisoned_slot(weights, telemetry):
    """Per-slot poison probe: NaN injected into one slot's device-
    resident cross-attention cache evicts that slot (outcome 'error',
    numerics-plane provenance) while the neighbor decodes
    byte-identically; the scrubbed slot serves the next admission."""
    cfg, scope = weights
    srcs = _srcs(3, seed=33)
    clean = _undisturbed(cfg, scope, srcs, slots=2)

    numerics.reset()
    nf0 = monitor.counter("pt_nonfinite_total").value(
        labels={"op": "decode_step", "var": "slot1:logits"})
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8, max_len=10,
                                bos_id=BOS, end_id=EOS)
    reqs = [eng.submit(s) for s in srcs]
    eng.step()  # admit both + dispatch step 1 (clean)
    eng.step()  # process step 1 + dispatch step 2 (clean)
    # poison slot 1's device state: the next decode step's logits for
    # slot 1 (and ONLY slot 1 — rows are independent) go non-finite
    arr = np.array(np.asarray(eng.scope.find_var("serve_ck0")))
    arr[1] = np.nan
    eng.scope.set("serve_ck0", arr)
    eng.run_until_idle()
    assert reqs[1].outcome == "error"
    assert list(reqs[1].tokens) == clean[1][:len(reqs[1].tokens)]
    assert list(reqs[0].tokens) == clean[0]
    assert reqs[0].outcome in ("completed", "length")
    # the scrubbed slot admitted the queued request, which decodes
    # byte-identically (a stale NaN K/V row would have re-poisoned it
    # through the softmax mask: 0 * NaN = NaN)
    assert list(reqs[2].tokens) == clean[2]
    # surfaced through the numerics plane
    assert monitor.counter("pt_nonfinite_total").value(
        labels={"op": "decode_step", "var": "slot1:logits"}) > nf0
    recs = [r for r in numerics.provenance_records()
            if r["op_type"] == "decode_step"]
    assert recs and recs[-1]["kind"] == "serve"
    eng.close()


def test_fetch_fault_contained_and_healthy_tokens_kept(weights, telemetry):
    """A slot-hinted serve.fetch fault (async materialization seam)
    evicts the hinted slot and RETRIES the step's fetches once — the
    healthy slot's already-computed token is not lost, its stream stays
    byte-identical."""
    cfg, scope = weights
    srcs = _srcs(2, seed=35)
    clean = _undisturbed(cfg, scope, srcs, slots=2)

    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8, max_len=10,
                                bos_id=BOS, end_id=EOS)
    reqs = [eng.submit(s) for s in srcs]
    faults.arm("serve.fetch:raise(slot=0)@2")
    try:
        eng.run_until_idle()
    finally:
        faults.disarm()
    assert eng.state == "serving"
    assert reqs[0].outcome == "evicted"
    assert list(reqs[0].tokens) == clean[0][:len(reqs[0].tokens)]
    assert list(reqs[1].tokens) == clean[1]
    eng.close()


def test_unhinted_fetch_fault_fails_engine(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    eng.submit(_srcs(1, seed=36)[0])
    faults.arm("serve.fetch:raise@1")
    try:
        with pytest.raises(faults.InjectedFault):
            eng.run_until_idle()
    finally:
        faults.disarm()
    assert eng.state == "failed"
    eng.close()


def test_decode_oom_runs_serve_forensics_and_fails_engine(
        weights, telemetry):
    """RESOURCE_EXHAUSTED on the decode path runs the existing OOM
    forensics with phase='serve' (donated-buffer hygiene already ran in
    the executor) and fails the engine — the supervisor-restart seam,
    not a containment case."""
    cfg, scope = weights
    oom0 = monitor.counter("pt_oom_events_total").value(
        labels={"phase": "serve"})
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    req = eng.submit(_srcs(1, seed=37)[0])
    faults.arm("serve.decode:raise(RESOURCE_EXHAUSTED: synthetic)@1")
    try:
        with pytest.raises(faults.InjectedFault):
            eng.run_until_idle()
    finally:
        faults.disarm()
    assert eng.state == "failed"
    assert monitor.counter("pt_oom_events_total").value(
        labels={"phase": "serve"}) == oom0 + 1
    assert any(r["phase"] == "serve" for r in monitor.oom_records())
    eng.close()
    assert req.outcome == "error"


# --------------------------------------------------------------------------
# supervised warm restart
# --------------------------------------------------------------------------

def test_supervised_restart_zero_fresh_compiles_byte_identical_replay(
        weights, telemetry, tmp_path):
    """The restart half of the chaos drill: an engine-killing decode
    fault triggers a supervised restart whose rebuilt engine traces
    again and reads every XLA compile from jax's persistent cache (no
    cache miss, at least the prefill and decode programs as hits),
    after which every replayed request returns tokens byte-identical
    to an undisturbed run."""
    cfg, scope = weights
    srcs = _srcs(3, seed=41)
    clean = _undisturbed(cfg, scope, srcs, slots=2)

    sup = None
    with placed_in_process(tmp_path / "jax_cache") as events:
        try:
            sup = serving.EngineSupervisor(
                cfg, scope, slots=2, src_len=8, max_len=10, bos_id=BOS,
                end_id=EOS, poll_s=0.005, wedge_timeout_ms=60_000,
                max_restarts=2)
            # warm jax's cache (prefill + decode written on first use)
            warm = sup.submit(_srcs(1, seed=42)[0], max_new_tokens=2)
            assert warm.result(timeout=60) is not None
            before = events.snapshot()
            assert before["misses"] >= 2
            restarts0 = monitor.counter(
                "pt_serve_engine_restarts_total").value()

            # hit counters reset at arm(): the 2nd decode step AFTER
            # arming fails with no slot hint -> engine-fatal ->
            # supervised restart
            faults.arm("serve.decode:raise@2")
            try:
                reqs = [sup.submit(s) for s in srcs]
                streams = [r.result(timeout=120) for r in reqs]
            finally:
                faults.disarm()
            assert streams == clean
            assert all(r.outcome in ("completed", "length") for r in reqs)
            assert sup.restarts == 1
            assert sup.replayed >= 1
            assert any(r.replays >= 1 for r in reqs)
            assert monitor.counter(
                "pt_serve_engine_restarts_total").value() == restarts0 + 1
            assert monitor.counter(
                "pt_serve_requests_replayed_total").value() >= 1
            # zero fresh compiles: everything the rebuilt engine asked
            # the compiler for was answered from the cache
            got = events.since(before)
            assert got["misses"] == 0, got
            assert got["hits"] == got["requests"] >= 2, got
        finally:
            if sup is not None:
                sup.close(drain_timeout_s=5.0)


def test_supervisor_restarts_wedged_engine(weights, telemetry):
    """Wedge detection rides engine heartbeats + monitor.stall_guard: a
    decode step stuck past serve_wedge_timeout_ms is declared dead by
    the watchdog, a stall record fires for site 'serve.decode', and the
    replayed requests complete byte-identically."""
    cfg, scope = weights
    srcs = _srcs(2, seed=44)
    clean = _undisturbed(cfg, scope, srcs, slots=2)

    stalls0 = monitor.counter("pt_stall_total").value(
        labels={"site": "serve.decode"})
    sup = serving.EngineSupervisor(
        cfg, scope, slots=2, src_len=8, max_len=10, bos_id=BOS,
        end_id=EOS, poll_s=0.01, wedge_timeout_ms=250, max_restarts=2)
    try:
        faults.arm("serve.decode:delay(1.5)@2")
        try:
            with pytest.warns(RuntimeWarning):
                reqs = [sup.submit(s) for s in srcs]
                streams = [r.result(timeout=60) for r in reqs]
        finally:
            faults.disarm()
        assert streams == clean
        assert sup.restarts == 1
        assert monitor.counter("pt_stall_total").value(
            labels={"site": "serve.decode"}) > stalls0
    finally:
        sup.close(drain_timeout_s=5.0)


def test_supervisor_restart_budget_exhaustion_fails_pending(weights):
    """Past serve_max_restarts the supervisor gives up: pending handles
    finish 'error' (no hang), the supervisor closes, submit raises."""
    cfg, scope = weights
    sup = serving.EngineSupervisor(
        cfg, scope, slots=1, src_len=8, max_len=8, poll_s=0.005,
        wedge_timeout_ms=60_000, max_restarts=0)
    try:
        faults.arm("serve.decode:raise@1")
        try:
            req = sup.submit(_srcs(1, seed=45)[0])
            assert req.result(timeout=30) == []
        finally:
            faults.disarm()
        assert req.outcome == "error"
        deadline = time.perf_counter() + 10
        while sup.state != "closed" and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert sup.state == "closed"
        with pytest.raises(serving.EngineClosed):
            sup.submit(_srcs(1, seed=46)[0])
    finally:
        sup.close(drain_timeout_s=1.0)


def test_supervised_front_end_and_predictor_seam(weights):
    """serve(..., supervised=True) returns a self-driving supervisor
    (no caller step loop needed); Predictor exposes the same seam."""
    from paddle_tpu import inference

    cfg, scope = weights
    sup = serving.serve(cfg, scope, supervised=True, slots=1, src_len=8,
                        max_len=8, poll_s=0.005)
    try:
        req = sup.submit(_srcs(1, seed=47)[0])
        assert req.result(timeout=60) == list(req.tokens)
        assert req.outcome in ("completed", "length")
        assert sup.stats()["supervised"] and sup.stats()["restarts"] == 0
    finally:
        sup.close(drain_timeout_s=5.0)
    assert callable(getattr(inference.Predictor, "serving_engine"))


# --------------------------------------------------------------------------
# deadline-aware admission control + overload drill
# --------------------------------------------------------------------------

def test_rejected_early_refused_at_submit(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                max_len=32, queue_depth=8)
    # measured per-token latency: 50 ms (white-box primed — the EWMA
    # normally comes from decode-step walls)
    eng._token_ewma_s = 0.05
    a = eng.submit(_srcs(1, seed=51)[0], max_new_tokens=10)
    # ~10 tokens ahead x 50 ms >> 20 ms deadline: refused AT submit
    with pytest.raises(serving.DeadlineUnmeetable) as ei:
        eng.submit(_srcs(1, seed=52)[0], deadline_ms=20)
    rej = ei.value.request
    assert rej.done and rej.outcome == "rejected_early"
    assert eng.stats()["queue_depth"] == 1  # never queued
    # a meetable deadline is admitted
    ok = eng.submit(_srcs(1, seed=53)[0], deadline_ms=60_000)
    assert ok.outcome is None
    # flag off: no admission control
    flags.set_flags({"serve_admission_control": False})
    try:
        off = eng.submit(_srcs(1, seed=54)[0], deadline_ms=20)
        assert off.outcome is None
    finally:
        flags.set_flags({"serve_admission_control": True})
    eng.run_until_idle()
    assert a.done and ok.done and off.done
    eng.close()


@pytest.fixture(scope="module")
def weights_mid():
    """A model whose decode step costs a few ms: the overload drill's
    2x p99 bound compares device-paced steps, not sub-ms host churn."""
    cfg = T.TransformerConfig(
        src_vocab_size=37, trg_vocab_size=41, max_length=64,
        d_model=96, d_inner=256, n_head=4, n_layer=3,
        dropout=0.0, label_smooth_eps=0.0,
    )
    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        T.build(cfg, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, scope


@pytest.mark.multidevice_fragile
def test_overload_drill_p99_and_no_hangs(weights_mid, telemetry):
    """The overload acceptance drill: submit rate >= 2x capacity —
    unmeetable deadlines are refused at submit (rejected_early, never
    queued), admitted requests' per-token p99 stays within 2x the
    unloaded p99, and every handle reaches a terminal outcome."""
    cfg, scope = weights_mid

    def drive(eng, reqs_srcs, deadline_ms=None, submit_per_step=1):
        """Submit while stepping (sustained pressure); returns
        (handles, rejected_early_count, dispatch->host decode walls —
        the honest per-token latency, prefill work excluded)."""
        handles, rejected = [], 0
        pending = list(reqs_srcs)
        eng._step_walls.clear()
        while pending or eng.busy():
            for _ in range(submit_per_step):
                if not pending:
                    break
                try:
                    handles.append(eng.submit(
                        pending.pop(0), max_new_tokens=6,
                        deadline_ms=deadline_ms))
                except serving.DeadlineUnmeetable as e:
                    rejected += 1
                    assert e.request.outcome == "rejected_early"
                except serving.QueueFull:
                    pass
            eng.step()
        return handles, rejected, list(eng._step_walls)

    # unloaded baseline: trickled requests through the same engine
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                max_len=10, queue_depth=16)
    w = eng.submit(_srcs(1, seed=60)[0], max_new_tokens=2)
    eng.run_until_idle()  # warmup: compiles excluded from the window
    assert w.done
    _, _, unloaded = drive(eng, _srcs(4, seed=61))
    unloaded_p99 = float(np.percentile(unloaded, 99))

    # loaded: 16 requests pushed 2-per-step through 2 slots with a
    # deadline sized for roughly a third of them
    per_token_ms = eng._token_ewma_s * 1e3
    deadline_ms = per_token_ms * 6 * 3
    handles, rejected, loaded = drive(
        eng, _srcs(16, seed=62), deadline_ms=deadline_ms,
        submit_per_step=2)
    loaded_p99 = float(np.percentile(loaded, 99))

    assert rejected >= 1, "no request was refused at submit"
    assert handles, "every request was refused"
    for h in handles:
        h.result(timeout=30)  # no handle ever hangs
        assert h.outcome in ("completed", "length", "expired")
    assert loaded_p99 <= 2.0 * unloaded_p99, (
        f"loaded p99 {loaded_p99 * 1e3:.2f} ms vs unloaded "
        f"{unloaded_p99 * 1e3:.2f} ms")
    eng.close()


def test_brownout_caps_admissions_under_sustained_saturation(
        weights, telemetry):
    cfg, scope = weights
    flags.set_flags({"serve_brownout_queue_factor": 0.5,
                     "serve_brownout_window": 2,
                     "serve_brownout_max_new_tokens": 2})
    capped0 = monitor.counter("pt_serve_brownout_capped_total").value()
    try:
        eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                    max_len=32, queue_depth=8)
        srcs = _srcs(6, seed=65, lens=(7, 7, 7, 7, 7, 7))
        reqs = [eng.submit(s, max_new_tokens=8) for s in srcs]
        with pytest.warns(RuntimeWarning, match="brownout engaged"):
            eng.run_until_idle()
        assert monitor.counter(
            "pt_serve_brownout_capped_total").value() > capped0
        capped = [r for r in reqs if r.capped]
        assert capped, "brownout never capped an admission"
        for r in capped:
            assert len(r.tokens) <= 2
            assert r.outcome in ("completed", "length")
        # the first admission predates the engage window
        assert not reqs[0].capped
        # queue drained -> disengaged
        assert eng.stats()["brownout"] is False
        assert all(r.done for r in reqs)
        eng.close()
    finally:
        flags.set_flags({"serve_brownout_queue_factor": 0.0,
                         "serve_brownout_window": 16,
                         "serve_brownout_max_new_tokens": 16})


# --------------------------------------------------------------------------
# deadline eviction racing the async double-buffered fetch (satellite)
# --------------------------------------------------------------------------

def test_deadline_expiring_during_inflight_fetch_keeps_partial_output(
        weights):
    """A request whose deadline expires while step N's LazyFetches is
    still in flight keeps the partial output already materialized (plus
    step N's token, which was computed before the boundary) and never
    hangs result()."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                max_len=32, pipeline_depth=1)
    req = eng.submit(_srcs(1, seed=70)[0], deadline_ms=40)
    eng.step()  # admit + dispatch step 1; fetches in flight
    assert eng._pending is not None
    time.sleep(0.08)  # the deadline passes with the fetch in flight
    eng.run_until_idle()
    assert req.outcome == "expired"
    assert len(req.tokens) >= 1  # step N's token was kept
    assert req.result(timeout=1) == list(req.tokens)  # no hang
    assert eng.stats()["slots_active"] == 0  # the slot was freed
    eng.close()
    assert req.result(timeout=1) == list(req.tokens)


# --------------------------------------------------------------------------
# engine-state map hygiene (satellite)
# --------------------------------------------------------------------------

def test_closed_engine_state_rows_age_out(weights, telemetry):
    """A rotated replica's terminal 'closed' row (and its
    pt_serve_engine_state gauge cell) ages out of /healthz after
    ENGINE_STATE_TTL_S instead of being served forever."""
    cfg, scope = weights
    old_ttl = serving.ENGINE_STATE_TTL_S
    serving.ENGINE_STATE_TTL_S = 0.05
    try:
        eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                    max_len=8)
        eid = str(eng.engine_id)
        eng.close()
        assert serving.engine_states().get(eid) == "closed"
        cells = monitor.snapshot()["pt_serve_engine_state"]["values"]
        assert any(c["labels"].get("engine") == eid for c in cells)
        time.sleep(0.08)
        assert eid not in serving.engine_states()
        cells = monitor.snapshot()["pt_serve_engine_state"]["values"]
        assert not any(c["labels"].get("engine") == eid for c in cells)
    finally:
        serving.ENGINE_STATE_TTL_S = old_ttl


# --------------------------------------------------------------------------
# review-round regressions
# --------------------------------------------------------------------------

def test_fetch_materialization_does_not_hold_engine_lock(weights):
    """A slow/hung fetch must not wedge submit()/busy() behind it (the
    supervisor watchdog takes the same lock to declare a wedge): the
    blocking device wait runs outside the engine lock."""
    import threading

    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                max_len=12)
    eng.submit(_srcs(1, seed=80)[0])
    eng.step()  # dispatch; the next _process_ready materializes
    faults.arm("serve.fetch:delay(0.6)@1")
    stepper = threading.Thread(target=eng.step)
    try:
        stepper.start()
        time.sleep(0.1)  # the stepper is inside the delayed wait
        t0 = time.perf_counter()
        eng.submit(_srcs(1, seed=81)[0])
        eng.busy()
        blocked_s = time.perf_counter() - t0
        assert blocked_s < 0.3, (
            f"submit()/busy() blocked {blocked_s:.2f}s behind the fetch")
    finally:
        stepper.join(timeout=5)
        faults.disarm()
    eng.run_until_idle()
    eng.close()


def test_idle_gap_does_not_read_as_wedge(weights):
    """The heartbeat resets at work arrival: an idle gap longer than
    serve_wedge_timeout_ms followed by a submit must not be declared a
    wedge (it previously burned one restart per idle gap)."""
    cfg, scope = weights
    sup = serving.EngineSupervisor(
        cfg, scope, slots=1, src_len=8, max_len=8, poll_s=0.01,
        wedge_timeout_ms=200, max_restarts=1)
    try:
        warm = sup.submit(_srcs(1, seed=82)[0])
        warm.result(timeout=60)  # warmed: decode_steps > 0
        time.sleep(0.5)  # idle well past the wedge timeout
        req = sup.submit(_srcs(1, seed=83)[0])
        req.result(timeout=60)
        assert req.outcome in ("completed", "length")
        assert sup.restarts == 0
    finally:
        sup.close(drain_timeout_s=5.0)


def test_replay_that_never_reprefills_keeps_partial_output(weights):
    """The replay token wipe happens at the rebuilt engine's ADMISSION:
    a replay whose intake lands on a dead engine finishes 'error' with
    the already-streamed partial output intact (and is not counted as
    replayed)."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    req = eng.submit(_srcs(1, seed=84)[0])
    req.tokens.extend([7, 8, 9])  # the partial stream already handed out
    (harvested,) = eng._harvest_for_replay()
    assert harvested is req
    dead = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                 max_len=8)
    dead.close()
    replays0 = req.replays
    dead._enqueue_replay(req)
    assert req.done and req.outcome == "error"
    assert list(req.tokens) == [7, 8, 9]  # partial output survived
    assert req.replays == replays0  # never re-prefilled, never counted
    eng.close()


def test_submit_after_supervisor_drain_fails_fast(weights):
    """drain() is explicit rotation, not a restart race: a subsequent
    submit() raises EngineClosed immediately instead of spinning the
    supervisor's restart-retry window."""
    cfg, scope = weights
    sup = serving.EngineSupervisor(
        cfg, scope, slots=1, src_len=8, max_len=8, poll_s=0.005,
        wedge_timeout_ms=60_000)
    try:
        sup.submit(_srcs(1, seed=85)[0]).result(timeout=60)
        assert sup.drain(timeout_s=30)
        t0 = time.perf_counter()
        with pytest.raises(serving.EngineClosed):
            sup.submit(_srcs(1, seed=86)[0])
        assert time.perf_counter() - t0 < 5.0
    finally:
        sup.close(drain_timeout_s=5.0)


def test_hint_matching_no_active_slot_fails_engine(weights):
    """A slot hint that evicts nothing (out-of-range / already-finished
    slot) contains nothing: the error must fail the engine, not be
    swallowed into a zero-progress livelock."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    eng.submit(_srcs(1, seed=90)[0])
    faults.arm("serve.decode:raise(slot=9)@1")
    try:
        with pytest.raises(faults.InjectedFault):
            eng.run_until_idle()
    finally:
        faults.disarm()
    assert eng.state == "failed"
    eng.close()


def test_brownout_never_caps_a_replay(weights):
    """Capping a replay would break the byte-identical invariant (and
    could return fewer tokens than its pre-restart partial output):
    replays are exempt from the brownout cap at admission."""
    cfg, scope = weights
    flags.set_flags({"serve_brownout_queue_factor": 0.5,
                     "serve_brownout_window": 1,
                     "serve_brownout_max_new_tokens": 1})
    try:
        eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                    max_len=10, queue_depth=4)
        req = eng.submit(_srcs(1, seed=91)[0], max_new_tokens=6)
        (harvested,) = eng._harvest_for_replay()
        assert harvested is req
        eng2 = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                     max_len=10, queue_depth=4)
        eng2.brownout = True  # engaged when the replay is admitted
        eng2._enqueue_replay(req)
        eng2.run_until_idle()
        assert req.done and not req.capped
        assert req.max_new_tokens == 6  # the budget survived brownout
        assert req.replays == 1
        eng.close()
        eng2.close()
    finally:
        flags.set_flags({"serve_brownout_queue_factor": 0.0,
                         "serve_brownout_window": 16,
                         "serve_brownout_max_new_tokens": 16})


def test_steady_submit_traffic_does_not_defer_wedge_detection(weights):
    """The work-arrival heartbeat reset applies only to an IDLE engine:
    submits landing on an engine with work in flight must not refresh
    the beat, or steady traffic would hide a wedged decode loop from
    the watchdog until the queue filled."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                max_len=12, queue_depth=8)
    eng.submit(_srcs(1, seed=92)[0])
    eng.step()  # in flight: slot occupied
    eng._beat -= 100.0  # simulate a long-wedged decode loop
    eng.submit(_srcs(1, seed=93)[0])  # traffic keeps arriving
    assert eng.heartbeat_age_s() > 50.0  # the wedge age survived
    eng.run_until_idle()
    # and the idle case still resets (the false-positive guard)
    eng._beat -= 100.0
    eng.submit(_srcs(1, seed=94)[0])
    assert eng.heartbeat_age_s() < 50.0
    eng.run_until_idle()
    eng.close()


def test_slot_scrub_runs_on_device(weights):
    """The poisoned-slot scrub is a compiled device-state update: no
    host round-trip of the KV caches (the whole point of the serving
    state design), and the scrubbed rows really are zero."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                max_len=10)
    reqs = [eng.submit(s) for s in _srcs(2, seed=95)]
    eng.step()
    eng.step()  # both slots hold real K/V rows now
    before = np.array(np.asarray(eng.scope.find_var("serve_k0")))
    assert np.abs(before[0]).sum() > 0
    eng._scrub_slot_state(0)
    after = np.asarray(eng.scope.find_var("serve_k0"))
    assert np.abs(after[0]).sum() == 0  # slot 0 zeroed...
    np.testing.assert_array_equal(after[1], before[1])  # ...slot 1 kept
    assert not np.asarray(eng.scope.find_var("serve_live"))[0]
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    eng.close()


def test_scrub_runs_outside_engine_lock(weights):
    """The scrub is a blocking device call: it must run with the engine
    lock RELEASED, or a hung scrub would wedge submit()/busy() and the
    watchdog itself (the exact hang the supervisor recovers from)."""
    import threading

    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                max_len=12)
    reqs = [eng.submit(s) for s in _srcs(2, seed=96)]
    eng.step()  # admit + dispatch; the next materialization can fault
    orig = eng._scrub_slot_state
    in_scrub = threading.Event()

    def slow_scrub(i):
        in_scrub.set()
        time.sleep(0.6)
        orig(i)

    eng._scrub_slot_state = slow_scrub
    faults.arm("serve.fetch:raise(slot=1)@1")
    stepper = threading.Thread(target=eng.step)
    stepper.start()
    try:
        assert in_scrub.wait(10)
        t0 = time.perf_counter()
        eng.submit(_srcs(1, seed=97)[0])
        eng.busy()
        blocked_s = time.perf_counter() - t0
        assert blocked_s < 0.3, (
            f"submit()/busy() blocked {blocked_s:.2f}s behind the scrub")
    finally:
        stepper.join(timeout=10)
        faults.disarm()
        eng._scrub_slot_state = orig
    assert reqs[1].outcome == "evicted"  # the eviction still landed
    eng.run_until_idle()
    eng.close()


def test_scrub_failure_fails_engine_without_dropping_tokens(weights):
    """A failing scrub leaves an unscrubbed slot that would re-poison
    its next occupant: the engine must FAIL (supervisor restarts), not
    half-contain — and the healthy slot's token from that step was
    already applied before the scrub ran."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                max_len=12)
    reqs = [eng.submit(s) for s in _srcs(2, seed=98)]
    eng.step()
    eng.step()
    tokens_before = len(reqs[0].tokens)
    arr = np.array(np.asarray(eng.scope.find_var("serve_ck0")))
    arr[1] = np.nan
    eng.scope.set("serve_ck0", arr)

    def broken_scrub(i):
        raise RuntimeError("scrub device error")

    eng._scrub_slot_state = broken_scrub
    with pytest.raises(RuntimeError, match="scrub device error"):
        eng.run_until_idle()
    assert eng.state == "failed"
    # the poisoned step's healthy-slot token landed before the scrub
    assert len(reqs[0].tokens) > tokens_before
    eng.close()
    assert all(r.done for r in reqs)
