"""Request-scoped tracing & SLO plane for the serving fleet.

serving.py's aggregate telemetry (histograms, engine states) cannot
answer the questions a router or an SLO review asks: *where did THIS
request's latency go, and which phase ate the deadline it missed?*
This module keeps the per-request story:

- **Per-phase latency decomposition**: every ``ServeRequest`` carries
  measured queue-wait / prefill / decode / fetch seconds (accumulated
  by the engine's scheduler tick); at the terminal outcome the
  breakdown is recorded onto a bounded recently-terminated ring served
  at ``/requests`` (next to the live in-flight table).
- **Deadline attribution**: every ``expired`` / ``rejected_early``
  request names the phase that ate its budget (the dominant measured
  phase — under overload that is queue wait, which is exactly the
  routing signal a multi-replica front door needs).
- **SLO accounting** (``pt_slo_*``, targets from the
  ``serve_slo_ttft_ms`` / ``serve_slo_token_ms`` flags): terminal
  requests are scored met/missed and every miss burns
  ``pt_slo_burn_total{slo=,outcome=}``. The TTFT survivorship bias is
  closed here: a request terminating BEFORE its first token (expired /
  evicted / drained / error) never observes ``pt_serve_ttft_seconds``
  — so p99 TTFT would *improve* as overload worsens — and is instead
  metered as censored (``pt_serve_ttft_censored_total{outcome=}``)
  and counted AGAINST the TTFT target.
- **Per-request Chrome-trace tracks**: a request's whole life (submit,
  queue, prefill, sampled decode steps, restart replays, eviction /
  scrub events, terminal outcome) lands on ONE dynamic timeline track
  (``monitor.REQUEST_TRACK_BASE`` + slot, recycled round-robin), so
  Perfetto shows it across batch steps and across a supervised
  engine restart — the replay continues the original trace with the
  restart annotated as a span.

House invariant: with telemetry off every ``note_*`` hook is a single
cached-boolean check and allocates nothing (the tracemalloc proof in
tests/test_request_trace.py filters on this file). The module never
imports serving.py at module level — the view builders reach it
through ``sys.modules``, so a monitor-only process answers
``/requests`` with an empty view instead of pulling the serving stack
in.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from paddle_tpu import flags as _flags
from paddle_tpu import monitor as _monitor

REQUEST_RECORD_SCHEMA_VERSION = 1

# terminal outcomes that can end a request before its first token: the
# TTFT histogram never sees these (survivorship bias) so they are
# metered as censored instead. 'rejected'/'rejected_early' are refusals
# — the request never entered service, so its TTFT is not censored
# (the deadline burn row still ticks for rejected_early).
CENSORED_OUTCOMES = ("expired", "evicted", "drained", "error")

PHASES = ("queue_wait", "prefill", "decode", "fetch")

# dynamic timeline tracks are recycled round-robin across this many
# slots (a bounded label set: a server churning thousands of requests
# reuses tracks; the ring + /requests keep the full per-request story)
REQUEST_TRACK_SLOTS = 64

_M_TTFT_CENSORED = _monitor.counter(
    "pt_serve_ttft_censored_total",
    "requests that reached a terminal outcome before their first token, "
    "by outcome (expired / evicted / drained / error): "
    "pt_serve_ttft_seconds never observes them, so without this meter "
    "p99 TTFT *improves* as overload worsens (survivorship bias); the "
    "SLO plane counts every censored request against the TTFT target")
_M_SLO_TTFT = _monitor.counter(
    "pt_slo_ttft_total",
    "terminal requests measured against the serve_slo_ttft_ms target, "
    "by status (met / missed / censored — a censored request never saw "
    "a first token and counts against the target); empty while the "
    "target flag is 0")
_M_SLO_TOKEN = _monitor.counter(
    "pt_slo_token_total",
    "terminal requests measured against the serve_slo_token_ms "
    "per-token decode-latency target (mean decode+fetch seconds per "
    "emitted token), by status (met / missed); requests that emitted "
    "no token are not measured; empty while the target flag is 0")
_M_SLO_BURN = _monitor.counter(
    "pt_slo_burn_total",
    "SLO error-budget burn events by slo + outcome: slo='ttft' (missed "
    "or censored vs serve_slo_ttft_ms), slo='token' (missed vs "
    "serve_slo_token_ms), slo='deadline' (every expired / "
    "rejected_early request — its own deadline IS an SLO, so these "
    "rows tick even with the target flags unset)")

# cached hot flag values (watch_flag pattern: no dict lookup per call)
_slo_ttft_s = 0.0
_slo_token_s = 0.0

_RECENT_LOCK = threading.Lock()
_RECENT: collections.deque = collections.deque(maxlen=256)

_TRACK_LOCK = threading.Lock()
_track_seq = 0


def _sync_slo_ttft(value):
    global _slo_ttft_s
    _slo_ttft_s = float(value) / 1e3


def _sync_slo_token(value):
    global _slo_token_s
    _slo_token_s = float(value) / 1e3


def _sync_recent_cap(value):
    global _RECENT
    cap = max(1, int(value))
    with _RECENT_LOCK:
        if _RECENT.maxlen != cap:
            _RECENT = collections.deque(_RECENT, maxlen=cap)


def _ensure_track(req) -> int:
    """Lazily pin one dynamic timeline track (tid) to ``req`` — every
    span/instant of the request's life lands there, INCLUDING replays
    on a rebuilt engine (the tid lives on the handle, which survives
    the restart), so Perfetto shows one continuous request row."""
    tid = req.trace_tid
    if tid is None:
        global _track_seq
        with _TRACK_LOCK:
            slot = _track_seq % REQUEST_TRACK_SLOTS
            _track_seq += 1
        tid = _monitor.REQUEST_TRACK_BASE + slot
        req.trace_tid = tid
        _monitor.trace_register_track(tid, f"req {req.trace_id}")
    return tid


# --- lifecycle hooks (called by serving.py; trace hooks gate on
# trace_active, accounting hooks on enabled — all one cached boolean
# when telemetry is off) ---


def note_submit(req):
    """Queued (or replay-intake'd) — opens the request's track."""
    if not _monitor.trace_active():
        return
    _monitor.trace_event(
        "submit", "request", req.submit_ts,
        args={"req": req.trace_id, "engine": req.engine_id,
              "max_new_tokens": req.max_new_tokens},
        tid=_ensure_track(req))


def note_admit(req):
    """Admitted into a batch slot: closes the queue span and records
    the prefill span (``req.admit_ts`` / ``req.prefill_s`` were just
    measured by the engine)."""
    if not _monitor.trace_active():
        return
    tid = _ensure_track(req)
    if not req.replays:
        # a replay's wait is annotated by the restart span instead — a
        # second queue span over the first life would overlap it
        _monitor.trace_event("queue", "request", req.submit_ts,
                             req.admit_ts, args={"req": req.trace_id},
                             tid=tid)
    if req.prefill_s is not None:
        _monitor.trace_event("prefill", "request", req.admit_ts,
                             req.admit_ts + req.prefill_s,
                             args={"req": req.trace_id,
                                   "engine": req.engine_id}, tid=tid)


def note_decode_step(req, step, t0, t_f0, t_f1, token, pos, score):
    """One sampled decode step on the request's track: the dispatch ->
    device span plus the host-materialization (fetch) span, annotated
    with the emitted token and the greedy head's own logit."""
    tid = _ensure_track(req)
    _monitor.trace_event(
        "decode", "request", t0, t_f0,
        args={"req": req.trace_id, "step": step, "token": token,
              "pos": pos, "logit": score}, tid=tid)
    _monitor.trace_event("fetch", "request", t_f0, t_f1,
                         args={"req": req.trace_id, "step": step},
                         tid=tid)


def note_restart(req):
    """Supervised-restart replay re-entering decode (called from the
    request's replay reset at the rebuilt engine's admission): the
    restart is annotated as a span from the supervisor's replay intake
    to re-admission, ON the original request's track — one request,
    one trace."""
    if not _monitor.trace_active():
        return
    t1 = time.perf_counter()
    t0 = (req._replay_intake_ts if req._replay_intake_ts is not None
          else t1)
    _monitor.trace_event(
        "restart", "request", t0, t1,
        args={"req": req.trace_id, "replay": req.replays,
              "engine": req.engine_id}, tid=_ensure_track(req))


def note_evicted(req, cause: str, slot: int):
    """Containment evicted the request's slot (fault = slot-hinted
    decode/fetch error, nonfinite = logit probe): an instant on the
    VICTIM's track, so the eviction reads in the request's own story."""
    if not _monitor.trace_active():
        return
    _monitor.trace_event(
        "evicted", "request", time.perf_counter(),
        args={"req": req.trace_id, "cause": cause, "slot": slot},
        tid=_ensure_track(req))


def note_scrub(req, slot: int):
    """The evicted slot's device rows were scrubbed — the victim's
    containment epilogue, on its track."""
    if not _monitor.trace_active():
        return
    _monitor.trace_event(
        "scrub", "request", time.perf_counter(),
        args={"req": req.trace_id, "slot": slot},
        tid=_ensure_track(req))


def note_terminal(req):
    """Terminal-outcome accounting, called from ``ServeRequest._finish``
    (the one hook every outcome path funnels through): censored-TTFT
    metering, SLO scoring + burn, deadline attribution, the
    recently-terminated ring record, and the closing trace instant."""
    if not _monitor.enabled():
        return
    now = time.perf_counter()
    req.finish_ts = now
    outcome = req.outcome
    censored = req.ttft_s is None and outcome in CENSORED_OUTCOMES
    if censored:
        req.censored = True
        _M_TTFT_CENSORED.inc(labels={"outcome": outcome})
    ttft_status = token_status = None
    if _slo_ttft_s > 0.0:
        if req.ttft_s is not None:
            ttft_status = ("met" if req.ttft_s <= _slo_ttft_s
                           else "missed")
        elif censored:
            ttft_status = "censored"
        if ttft_status is not None:
            _M_SLO_TTFT.inc(labels={"status": ttft_status})
            if ttft_status != "met":
                _M_SLO_BURN.inc(labels={"slo": "ttft",
                                        "outcome": outcome})
    if _slo_token_s > 0.0 and req.tokens and req.decode_s > 0.0:
        per_tok = (req.decode_s + req.fetch_s) / len(req.tokens)
        token_status = "met" if per_tok <= _slo_token_s else "missed"
        _M_SLO_TOKEN.inc(labels={"status": token_status})
        if token_status == "missed":
            _M_SLO_BURN.inc(labels={"slo": "token", "outcome": outcome})
    if outcome in ("expired", "rejected_early"):
        # the request's own deadline is an SLO in itself: burn + name
        # the phase that ate the budget
        _M_SLO_BURN.inc(labels={"slo": "deadline", "outcome": outcome})
        req.deadline_attr = _attribute_deadline(req, now)
    _record(req, now, ttft_status, token_status)
    if _monitor.trace_active():
        _monitor.trace_event(
            f"outcome:{outcome}", "request", now,
            args={"req": req.trace_id, "tokens": len(req.tokens),
                  "replays": req.replays}, tid=_ensure_track(req))


def _phases_s(req, now: float) -> Dict[str, float]:
    """Measured per-phase seconds. A request still queued (or refused
    before queueing) charges everything since submit to queue wait —
    the phase it is actually stuck in."""
    qw = req.queue_wait_s
    if qw is None:
        qw = max(0.0, now - req.submit_ts)
    return {
        "queue_wait": qw,
        "prefill": req.prefill_s or 0.0,
        "decode": req.decode_s,
        "fetch": req.fetch_s,
    }


def _attribute_deadline(req, now: float) -> Dict[str, Any]:
    """Name the phase that ate an expired/rejected_early request's
    budget: the dominant measured phase (under queue overload that is
    queue wait — the signal a router sheds load on)."""
    phases = _phases_s(req, now)
    phase = max(PHASES, key=lambda k: phases[k])
    return {
        "phase": phase,
        "phase_ms": round(phases[phase] * 1e3, 3),
        "budget_ms": (None if req.deadline_ts is None else
                      round((req.deadline_ts - req.submit_ts) * 1e3, 3)),
        "phases_ms": {k: round(v * 1e3, 3) for k, v in phases.items()},
    }


def _record(req, now: float, ttft_status, token_status):
    phases = _phases_s(req, now)
    rec = {
        "v": REQUEST_RECORD_SCHEMA_VERSION,
        "trace_id": req.trace_id,
        "id": req.id,
        "engine": req.engine_id,
        "outcome": req.outcome,
        "tokens": len(req.tokens),
        "replays": req.replays,
        "capped": req.capped,
        "censored": req.censored,
        "wall_ms": round((now - req.submit_ts) * 1e3, 3),
        "ttft_ms": (None if req.ttft_s is None
                    else round(req.ttft_s * 1e3, 3)),
        "deadline_ms": (None if req.deadline_ts is None else
                        round((req.deadline_ts - req.submit_ts) * 1e3,
                              3)),
        "phases_ms": {k: round(v * 1e3, 3) for k, v in phases.items()},
        "deadline_attribution": req.deadline_attr,
        "slo": {"ttft": ttft_status, "token": token_status},
    }
    with _RECENT_LOCK:
        _RECENT.append(rec)


# --- view builders (the /requests route + fleet digest section) ---


def _inflight_row(req, state: str, slot: Optional[int],
                  now: float) -> Dict[str, Any]:
    return {
        "trace_id": req.trace_id,
        "id": req.id,
        "engine": req.engine_id,
        "state": state,
        "slot": slot,
        "tokens": len(req.tokens),
        "replays": req.replays,
        "age_ms": round((now - req.submit_ts) * 1e3, 3),
        "deadline_remaining_ms": (
            None if req.deadline_ts is None
            else round((req.deadline_ts - now) * 1e3, 3)),
        "ttft_ms": (None if req.ttft_s is None
                    else round(req.ttft_s * 1e3, 3)),
        "phases_ms": {k: round(v * 1e3, 3)
                      for k, v in _phases_s(req, now).items()},
    }


def slo_summary() -> Dict[str, Any]:
    """Targets + met/missed/censored counts + burn totals by SLO."""
    burn: Dict[str, int] = {}
    for cell in (_monitor.snapshot().get("pt_slo_burn_total", {})
                 .get("values", ())):
        slo = cell["labels"].get("slo", "?")
        burn[slo] = burn.get(slo, 0) + int(cell["value"])
    return {
        "targets_ms": {
            "ttft": _slo_ttft_s * 1e3 if _slo_ttft_s > 0.0 else None,
            "token": _slo_token_s * 1e3 if _slo_token_s > 0.0 else None,
        },
        "ttft": {s: int(_M_SLO_TTFT.value(labels={"status": s}))
                 for s in ("met", "missed", "censored")},
        "token": {s: int(_M_SLO_TOKEN.value(labels={"status": s}))
                  for s in ("met", "missed")},
        "ttft_censored": {
            o: int(_M_TTFT_CENSORED.value(labels={"outcome": o}))
            for o in CENSORED_OUTCOMES},
        "burn": burn,
    }


def requests_view() -> Dict[str, Any]:
    """The ``/requests`` route payload: the live in-flight table (one
    row per queued/decoding request across every live engine) + the
    bounded recently-terminated ring + the SLO rollup."""
    inflight: List[Dict[str, Any]] = []
    srv = sys.modules.get("paddle_tpu.serving")
    if srv is not None:
        now = time.perf_counter()
        for eng in list(srv._ENGINES):
            with eng._lock:
                queued = list(eng._queue)
                slotted = [(i, s.request)
                           for i, s in enumerate(eng._slots)
                           if s.request is not None]
            for req in queued:
                if req.outcome is None:
                    inflight.append(_inflight_row(req, "queued", None,
                                                  now))
            for i, req in slotted:
                if req.outcome is None:
                    inflight.append(_inflight_row(req, "decoding", i,
                                                  now))
    with _RECENT_LOCK:
        recent = list(_RECENT)
        cap = _RECENT.maxlen
    return {
        "v": REQUEST_RECORD_SCHEMA_VERSION,
        "inflight": inflight,
        "recent": recent,  # oldest -> newest
        "recent_cap": cap,
        "slo": slo_summary(),
    }


def digest_section() -> Optional[Dict[str, Any]]:
    """Compact per-replica serving rollup for the fleet digest
    (optional: absent on ranks that never served, fleet-digest schema
    stays v1). ``/fleet`` renders this as the per-replica SLO/latency
    row a multi-replica router selects on."""
    engines: Dict[str, Any] = {}
    srv = sys.modules.get("paddle_tpu.serving")
    if srv is not None:
        for eng in list(srv._ENGINES):
            with eng._lock:
                qlen = len(eng._queue)
            engines[str(eng.engine_id)] = {
                "state": eng.state,
                "queue_depth": qlen,
                "slots": eng.slots,
                "slots_active": int(eng._active_mask().sum()),
                "brownout": eng.brownout,
                "token_ewma_ms": (
                    None if eng._token_ewma_s is None
                    else round(eng._token_ewma_s * 1e3, 3)),
            }
    with _RECENT_LOCK:
        n_recent = len(_RECENT)
    if srv is None or (not engines and n_recent == 0):
        return None
    ttft_h = srv._M_TTFT_SECONDS
    token_h = srv._M_TOKEN_SECONDS
    return {
        "engines": engines,
        "recent": n_recent,
        "ttft_ms": {
            label: (None if ttft_h.quantile(q) is None
                    else round(ttft_h.quantile(q) * 1e3, 3))
            for label, q in _monitor.QUANTILE_LABELS},
        "token_ms": {
            label: (None if token_h.quantile(q) is None
                    else round(token_h.quantile(q) * 1e3, 3))
            for label, q in _monitor.QUANTILE_LABELS},
        "slo": slo_summary(),
    }


def reset():
    """Test-isolation hook (rides monitor.reset): clears the
    recently-terminated ring and rewinds track recycling."""
    global _track_seq
    with _RECENT_LOCK:
        _RECENT.clear()
    with _TRACK_LOCK:
        _track_seq = 0


_flags.watch_flag("serve_slo_ttft_ms", _sync_slo_ttft)
_flags.watch_flag("serve_slo_token_ms", _sync_slo_token)
_flags.watch_flag("serve_recent_requests", _sync_recent_cap)
