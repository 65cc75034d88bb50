"""Serving plane (serving.py + transformer build_prefill/build_decode_step):
continuous batch assembly over an on-device KV cache.

The load-bearing drill: N requests of different lengths admitted at
staggered steps through a shared slot pool must produce token-for-token
identical output to each request decoded solo (greedy) — the continuous
batching correctness contract. Around it: decode-loop executor-cache
accounting (zero fresh compiles in steady state), queue backpressure,
deadlines, graceful drain, chaos sites, the /serve route, and the int8
PTQ artifact as a deployable weight source.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import faults, flags, monitor, serving
from paddle_tpu.models import transformer as T

BOS, EOS = 0, 1


def tiny_cfg(n_layer=1):
    return T.TransformerConfig(
        src_vocab_size=37, trg_vocab_size=41, max_length=64,
        d_model=16, d_inner=32, n_head=2, n_layer=n_layer,
        dropout=0.0, label_smooth_eps=0.0,
    )


@pytest.fixture(scope="module")
def weights():
    """Startup-initialized tiny transformer weights (shared scope)."""
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


def _solo_decode(cfg, scope, src, max_len=10, end_id=EOS):
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                max_len=max_len, bos_id=BOS, end_id=end_id)
    req = eng.submit(src)
    eng.run_until_idle()
    eng.close()
    return list(req.tokens), req.outcome


# --------------------------------------------------------------------------
# the continuous-batching correctness drill
# --------------------------------------------------------------------------

def test_staggered_admissions_match_solo_greedy(weights):
    """5 requests, 2 slots: admissions happen at staggered decode steps
    as slots free up, yet every request's tokens must equal its solo
    greedy decode — the mixed in-flight batch never contaminates a
    neighbor's math (slot rows are independent in every kernel)."""
    cfg, scope = weights
    srcs = _srcs(5, seed=1)
    solo = [_solo_decode(cfg, scope, s)[0] for s in srcs]

    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8, max_len=10,
                                bos_id=BOS, end_id=EOS)
    reqs = [eng.submit(s) for s in srcs]
    eng.run_until_idle()
    batched = [list(q.tokens) for q in reqs]
    assert batched == solo
    assert all(q.done for q in reqs)
    assert eng.stats()["requests_completed"] == 5
    # staggering really happened: 5 requests cannot fit 2 slots at once
    assert eng.stats()["decode_steps"] < sum(len(t) + 1 for t in solo)
    eng.close()


def test_engine_matches_offline_beam1_decode(weights):
    """Anchor the KV-cache decode step to the INDEPENDENTLY-tested
    offline path: the engine's greedy stream must equal
    build_decode(beam_size=1) (which test_decode.py proves equal to the
    training program's step-by-step argmax) — so a systematic
    decode-step math bug cannot hide behind engine-vs-engine parity."""
    cfg, scope = weights
    max_len = 6
    srcs = _srcs(3, seed=20, lens=(8, 8, 8))  # src_len must match
    src = np.stack(srcs)
    src_pad = np.ones((3, 8), np.float32)
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        dec = T.build_decode(cfg, beam_size=1, max_len=max_len,
                             src_len=8, bos_id=BOS, end_id=EOS)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        ids, _ = exe.run(prog, feed={"src_ids": src,
                                     "src_pad_mask": src_pad},
                         fetch_list=[dec["ids"], dec["scores"]])
    ids = np.asarray(ids)

    eng = serving.ServingEngine(cfg, scope, slots=3, src_len=8,
                                max_len=max_len, bos_id=BOS, end_id=EOS)
    reqs = [eng.submit(s) for s in srcs]
    eng.run_until_idle()
    for row, req in enumerate(reqs):
        seq = list(ids[row, 0, 1:])  # strip BOS
        if EOS in seq:
            seq = seq[:seq.index(EOS)]
        assert list(req.tokens) == seq, f"row {row}"
    eng.close()


def test_eos_completion_and_slot_reuse(weights):
    """Pick end_id = the model's actually-favored first token so the EOS
    path fires deterministically: the request completes without the
    token, the slot frees, and a queued request is admitted into it."""
    cfg, scope = weights
    srcs = _srcs(3, seed=2)
    probe, _ = _solo_decode(cfg, scope, srcs[0], max_len=6)
    eos = probe[0]  # this source's greedy first token
    toks, outcome = _solo_decode(cfg, scope, srcs[0], max_len=6,
                                 end_id=eos)
    assert toks == [] and outcome == "completed"

    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=6,
                                bos_id=BOS, end_id=eos)
    reqs = [eng.submit(s) for s in srcs]
    eng.run_until_idle()
    assert [q.outcome for q in reqs] == ["completed"] * 3
    assert list(reqs[0].tokens) == []  # EOS excluded from the output
    solo = [_solo_decode(cfg, scope, s, max_len=6, end_id=eos)[0]
            for s in srcs]
    assert [list(q.tokens) for q in reqs] == solo
    eng.close()


def test_max_new_tokens_and_length_outcome(weights):
    cfg, scope = weights
    # probe for a source whose natural greedy decode runs >= 4 tokens,
    # so a 3-token budget is a real truncation
    for seed in range(3, 16):
        (src,) = _srcs(1, seed=seed)
        full, _ = _solo_decode(cfg, scope, src)
        if len(full) >= 4:
            break
    else:
        pytest.skip("no probe source decoded >= 4 tokens")
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=10)
    req = eng.submit(src, max_new_tokens=3)
    eng.run_until_idle()
    assert len(req.tokens) == 3 and req.outcome == "length"
    assert list(req.tokens) == full[:3]
    eng.close()


# --------------------------------------------------------------------------
# decode loop x executor cache: zero fresh compiles in steady state
# --------------------------------------------------------------------------

def test_decode_loop_hits_executor_cache_after_warmup(weights):
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    try:
        eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                    max_len=12)
        reqs = [eng.submit(s) for s in _srcs(2, seed=4)]
        eng.step()  # warmup: prefill x2 + first decode step compile
        eng.step()
        misses0 = monitor.counter(
            "pt_executor_cache_misses_total").value()
        steps0 = eng.stats()["decode_steps"]
        eng.run_until_idle()
        assert eng.stats()["decode_steps"] > steps0
        assert monitor.counter(
            "pt_executor_cache_misses_total").value() == misses0
        outcomes = [r["cache"] for r in monitor.recent_steps()]
        assert outcomes[-3:] == ["hit", "hit", "hit"]
        assert all(q.done for q in reqs)
        eng.close()
    finally:
        flags.set_flags({"telemetry": False})


def test_close_releases_compiled_entries(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8, max_len=8)
    eng.submit(_srcs(1, seed=5)[0])
    eng.run_until_idle()
    assert len(eng._exe._cache) >= 2  # prefill + decode entries
    eng.close()
    assert len(eng._exe._cache) == 0
    with pytest.raises(serving.EngineClosed):
        eng.submit([2, 3])
    eng.close()  # idempotent


# --------------------------------------------------------------------------
# queue backpressure, deadlines, drain
# --------------------------------------------------------------------------

def test_queue_backpressure_rejects_beyond_capacity(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8,
                                queue_depth=2)
    srcs = _srcs(3, seed=6)
    eng.submit(srcs[0])
    eng.submit(srcs[1])
    with pytest.raises(serving.QueueFull):
        eng.submit(srcs[2])
    eng.close()


def test_deadline_evicts_at_token_boundary(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                max_len=32)
    req = eng.submit(_srcs(1, seed=7)[0], deadline_ms=1.0)
    time.sleep(0.01)  # the deadline passes before/while decoding
    eng.run_until_idle()
    assert req.outcome == "expired"
    # the partial output (possibly empty) stays on the handle and the
    # slot was freed for the next admission
    assert eng.stats()["slots_active"] == 0
    eng.close()


def test_drain_finishes_inflight_and_marks_queued(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8, max_len=8)
    srcs = _srcs(4, seed=8)
    reqs = [eng.submit(s) for s in srcs]
    eng.step()  # admit two into slots
    assert eng.drain(timeout_s=60.0)
    outs = [q.outcome for q in reqs]
    assert outs.count("drained") == 2  # the two never admitted
    assert all(o in ("completed", "length") for o in outs[:2])
    with pytest.raises(serving.EngineClosed):
        eng.submit(srcs[0])
    eng.close()


def test_submit_validation_and_pad_shapes(weights):
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    # src_pad accepted at the request's own length AND the engine's
    # full src_len (the training graph's mask shape); others raise
    r_short = eng.submit([5, 6, 7], src_pad=[1, 1, 1])
    r_full = eng.submit([5, 6, 7], src_pad=[1, 1, 1, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(r_short.src_pad, r_full.src_pad)
    with pytest.raises(ValueError, match="matches neither"):
        eng.submit([5, 6, 7], src_pad=[1, 1, 1, 0])
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([5, 6], max_new_tokens=0)
    eng.run_until_idle()
    # identical pads -> identical greedy streams
    assert list(r_short.tokens) == list(r_full.tokens)
    eng.close()


def test_close_after_failed_drain_never_strands_handles(weights):
    """A close whose drain times out (stalled decode loop) must still
    finish every in-flight handle — result() may never block forever on
    a closed engine."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                max_len=32)
    req = eng.submit(_srcs(1, seed=12)[0])
    eng.step()  # admitted + first decode step in flight
    eng.close(drain_timeout_s=0.0)  # drain gives up immediately
    assert req.done and req.outcome in ("drained", "completed", "length")
    assert req.result(timeout=1) == list(req.tokens)


def test_queue_and_slot_gauges_sum_across_engines(weights):
    """The process-wide gauges aggregate over live engines: an idle
    engine must not zero out a busy neighbor's queue reading."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    try:
        busy = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                     max_len=8)
        idle = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                     max_len=8)
        for s in _srcs(3, seed=13):
            busy.submit(s)
        # the idle engine republishing (via its own submit/finish flow)
        # must still report the busy engine's queue
        r = idle.submit([2, 3])
        idle.run_until_idle()
        assert r.done
        assert monitor.gauge("pt_serve_queue_depth").value() == 3
        busy.run_until_idle()
        assert monitor.gauge("pt_serve_queue_depth").value() == 0
        busy.close()
        idle.close()
    finally:
        flags.set_flags({"telemetry": False})


# --------------------------------------------------------------------------
# chaos sites + SLO metrics + /serve route
# --------------------------------------------------------------------------

def test_serve_fault_sites_registered_and_fire(weights):
    cfg, scope = weights
    assert {"serve.enqueue", "serve.prefill", "serve.decode",
            "serve.fetch"} <= set(faults.BUILTIN_SITES)
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    faults.arm("serve.enqueue:raise@1")
    try:
        with pytest.raises(faults.InjectedFault):
            eng.submit([2, 3, 4])
    finally:
        faults.disarm()
    # a prefill-site fault tears the admission seam: the popped request
    # surfaces 'error' on its handle, the engine keeps serving
    req = eng.submit([2, 3, 4])
    faults.arm("serve.prefill:raise@1")
    try:
        with pytest.raises(faults.InjectedFault):
            eng.run_until_idle()
    finally:
        faults.disarm()
    assert req.done and req.outcome == "error"
    req2 = eng.submit([2, 3, 4])
    eng.run_until_idle()
    assert req2.done and req2.outcome in ("completed", "length")
    eng.close()


def test_unhinted_decode_fault_fails_engine(weights):
    """A decode raise WITHOUT a slot hint is an unattributable device
    error: the engine fails (an EngineSupervisor would restart it),
    step() raises EngineFailed from then on, and close() finishes the
    pending handle with 'error' — result() never hangs."""
    cfg, scope = weights
    eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8, max_len=8)
    req = eng.submit([2, 3, 4])
    faults.arm("serve.decode:raise@1")
    try:
        with pytest.raises(faults.InjectedFault):
            eng.run_until_idle()
    finally:
        faults.disarm()
    assert eng.state == "failed"
    with pytest.raises(serving.EngineFailed):
        eng.step()
    with pytest.raises(serving.EngineFailed):
        eng.submit([5, 6])
    assert not req.done  # pending: a supervisor could still replay it
    eng.close()
    assert req.done and req.outcome == "error"
    assert req.result(timeout=1) == []


def test_serve_metrics_and_route(weights):
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    try:
        tokens0 = monitor.counter("pt_serve_tokens_total").value()
        eng = serving.ServingEngine(cfg, scope, slots=2, src_len=8,
                                    max_len=8)
        reqs = [eng.submit(s) for s in _srcs(2, seed=9)]
        eng.run_until_idle()
        emitted = sum(len(q.tokens) for q in reqs)
        assert emitted > 0
        assert monitor.counter(
            "pt_serve_tokens_total").value() == tokens0 + emitted
        assert monitor.counter("pt_serve_prefill_total").value() >= 2
        assert serving._M_TOKEN_SECONDS.count() >= emitted
        assert serving._M_TTFT_SECONDS.count() >= 2

        port = monitor.serve(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/serve", timeout=10) as r:
                doc = json.loads(r.read())
            assert doc["engine_count"] >= 1
            row = next(e for e in doc["engines"]
                       if e["tokens_emitted"] == emitted)
            assert row["requests_completed"] == 2
            assert doc["token_latency_s"]["p50"] is not None
            # the route is in the served index
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/", timeout=10) as r:
                assert "/serve" in json.loads(r.read())["routes"]
        finally:
            monitor.stop_server()
        eng.close()
    finally:
        flags.set_flags({"telemetry": False})


def test_engine_lifecycle_state_on_monitor_plane(weights):
    """ISSUE 14 serving tie-in: a replica being rotated out is
    observable BEFORE its queue is torn down — the engine lifecycle
    (serving -> draining -> closed) surfaces as the
    pt_serve_engine_state gauge, the /serve stats row, and per-engine
    rows on /healthz (a load balancer's probe must see 'draining' and
    stop routing while in-flight requests finish)."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    try:
        eng = serving.ServingEngine(cfg, scope, slots=1, src_len=8,
                                    max_len=8)
        eid = str(eng.engine_id)

        def _gauge():
            return monitor.gauge("pt_serve_engine_state").value(
                labels={"engine": eid})

        def _healthz(port):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
                return json.loads(r.read())

        assert eng.state == "serving" and _gauge() == 0
        assert eng.stats()["state"] == "serving"
        port = monitor.serve(port=0)
        try:
            assert _healthz(port)["engines"][eid] == "serving"
            req = eng.submit(_srcs(1, seed=4)[0])
            eng.drain()
            # drained with the request finished; the engine stays
            # draining (rotated out, not yet torn down) and says so
            assert req.done
            assert eng.state == "draining" and _gauge() == 1
            assert _healthz(port)["engines"][eid] == "draining"
            with pytest.raises(serving.EngineClosed):
                eng.submit(_srcs(1, seed=5)[0])
            eng.close()
            assert eng.state == "closed" and _gauge() == 2
            assert _healthz(port)["engines"][eid] == "closed"
            # idempotent shutdown: drain() on a closed engine must not
            # regress the published lifecycle closed -> draining
            assert eng.drain() is True
            assert eng.state == "closed" and _gauge() == 2
            assert _healthz(port)["engines"][eid] == "closed"
        finally:
            monitor.stop_server()
    finally:
        flags.set_flags({"telemetry": False})


# --------------------------------------------------------------------------
# int8 PTQ artifact as a deployable weight source
# --------------------------------------------------------------------------

def test_int8_artifact_deploys_into_engine(weights, tmp_path):
    """Calibrate + export the tiny transformer's int8 artifact (slim/),
    then deploy it: the engine loads the dequantized weights and serves
    greedy decode from them."""
    from paddle_tpu.slim.calibration import (Calibrator,
                                             save_int8_inference_model)

    cfg, scope = weights
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        model = T.build(cfg, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "int8_model")
    with fluid.scope_guard(scope):
        calib = Calibrator(main, exe, scope=scope, algo="abs_max")
        for s in range(2):
            calib.sample(T.make_batch(cfg, 2, 5, 5, seed=s))
        calib.compute_scales()
        save_int8_inference_model(
            d, ["src_ids", "trg_ids", "lbl_ids", "src_pad_mask",
                "trg_pad_mask"], [model["logits"]], exe, main, calib,
            scope=scope)

    eng = serving.ServingEngine(cfg, d, slots=2, src_len=8, max_len=8)
    assert eng.int8 and eng.stats()["int8"]
    reqs = [eng.submit(s) for s in _srcs(2, seed=10)]
    eng.run_until_idle()
    assert all(q.done for q in reqs)
    assert all(len(q.tokens) > 0 for q in reqs)
    # int8 deployment is deterministic: a second engine over the same
    # artifact reproduces the tokens exactly
    eng2 = serving.ServingEngine(cfg, d, slots=2, src_len=8, max_len=8)
    reqs2 = [eng2.submit(s) for s in _srcs(2, seed=10)]
    eng2.run_until_idle()
    assert [list(q.tokens) for q in reqs2] == [list(q.tokens)
                                              for q in reqs]
    eng.close()
    eng2.close()


# --------------------------------------------------------------------------
# the full-slot-count e2e (the verify SKILL.md smoke, tier-2)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.serving_e2e
def test_eight_concurrent_requests_match_solo(weights):
    """8 concurrent requests through a 4-slot engine: every stream must
    match its solo greedy decode, with zero fresh compiles after the
    warmup step and SLO histograms populated."""
    cfg, scope = weights
    flags.set_flags({"telemetry": True})
    try:
        srcs = _srcs(8, seed=11)
        solo = [_solo_decode(cfg, scope, s, max_len=12)[0] for s in srcs]
        eng = serving.ServingEngine(cfg, scope, slots=4, src_len=8,
                                    max_len=12)
        reqs = [eng.submit(s) for s in srcs]
        eng.step()
        eng.step()  # warmup: prefills + decode compile
        misses0 = monitor.counter(
            "pt_executor_cache_misses_total").value()
        eng.run_until_idle()
        assert monitor.counter(
            "pt_executor_cache_misses_total").value() == misses0
        assert [list(q.tokens) for q in reqs] == solo
        assert serving._M_TOKEN_SECONDS.count() > 0
        eng.close()
    finally:
        flags.set_flags({"telemetry": False})
