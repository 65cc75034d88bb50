"""Process-wide flag plane (reference: gflags — 41 ``DEFINE_*`` sites in
core C++ — bootstrapped from whitelisted env vars in
fluid/__init__.py:106-164 via ``init_gflags``/pybind.cc:954).

Flags are typed, defaulted, and settable three ways: env vars
``PT_FLAGS_<name>`` at import, ``set_flags({...})`` at runtime, or the
reference-style ``FLAGS_<name>`` env spelling. Unknown names raise —
a typo'd flag silently doing nothing is the failure mode gflags avoids.
"""

from __future__ import annotations

import os
from typing import Any, Dict

# name -> (type, default, doc)
_DEFS: Dict[str, tuple] = {
    # per-step NaN/Inf scan of updated state + fetches
    # (reference: FLAGS_check_nan_inf, operator.cc:950)
    "check_nan_inf": (bool, False, "scan step outputs for NaN/Inf"),
    # block until device work finishes each step, for honest timing
    # (reference: FLAGS_benchmark forced dev_ctx->Wait, operator.cc:946)
    "benchmark": (bool, False, "synchronize after every step"),
    # executor compile-cache capacity (entries); 0 = unbounded
    "executor_cache_capacity": (int, 0, "compiled-step cache entries"),
    # program-level PRNG: auto = rbg on TPU (fast hardware generator),
    # threefry elsewhere; or force 'threefry2x32' / 'rbg' / 'unsafe_rbg'
    "prng_impl": (str, "auto", "PRNG implementation for program RNG"),
    # coordination-service RPC deadline (reference: FLAGS_rpc_deadline,
    # default 180s). Generous default: rendezvous keys are often published
    # only after a peer's multi-minute first compile. Pass timeout_ms=-1
    # to a specific call for block-forever.
    "rpc_deadline_ms": (int, 600_000, "coord/KV operation deadline"),
    # runtime telemetry plane (monitor.py): metrics registry + structured
    # step logs + span histograms. Off by default — with it off every
    # instrument call is one boolean check.
    "telemetry": (bool, False, "enable the monitor.py telemetry plane"),
    # one JSONL record per Executor.run / run_steps call (monitor.py
    # STEP_LOG_FIELDS schema); empty = no step log even with telemetry on
    "step_log_path": (str, "", "JSONL step-log file path"),
    # monitor.dump_metrics() target; also dumped at process exit while
    # telemetry is on
    "metrics_dump_path": (str, "", "metrics export file path"),
    # per-program compile reports (monitor.COMPILE_REPORT_FIELDS schema):
    # one JSON file per fresh executor compile, written here. Costs one
    # extra AOT lower+compile per cache miss (jax shares no compile cache
    # between the analysis path and the eager jit); empty = off
    "compile_report_dir": (str, "", "per-compile JSON report directory"),
    # live observability endpoint (monitor.serve): /metrics /healthz
    # /steps /compile on this port; 0 = no server. Needs `telemetry`.
    "metrics_port": (int, 0, "HTTP port for the live /metrics endpoint"),
    # pre-flight memory budget: before a fresh compile the executor runs
    # monitor.estimate_memory and warns when the static estimate exceeds
    # this many bytes; 0 = no pre-flight
    "device_memory_budget_bytes": (int, 0,
                                   "warn threshold for pre-compile "
                                   "memory estimates"),
    # collective stall watchdog: guarded blocking sections (fleet
    # barriers/rendezvous, ring-attention / pipeline dispatch) that
    # exceed this deadline increment pt_stall_total and log a structured
    # stall record; 0 = watchdog disarmed
    "stall_timeout_ms": (int, 0, "watchdog deadline for collectives"),
    # on a stall, also dump the flight recorder (step ring buffer +
    # metrics snapshot + stall record) as JSON into this directory
    "stall_dump_dir": (str, "", "flight-recorder dump dir on stall"),
    # per-step phase attribution (feed/dispatch/device/fetch): on by
    # default with telemetry, but separately disablable because honest
    # device timing costs a jax.block_until_ready per step — a user who
    # wants only cheap counters/step-logs can keep async dispatch
    "step_phases": (bool, True,
                    "measure per-step phases (adds a device sync)"),
    # sample the phase marks every N executor steps: only sampled steps
    # pay the honest-device-timing block_until_ready; unsampled steps
    # dispatch fully async (their records carry sampled=False and no
    # phases). 1 = every step (the pre-sampling behavior)
    "step_phases_every_n": (int, 16, "step-phase sampling period"),
    # device-feed prefetch depth for Trainer.train/test's DeviceLoader:
    # batch N+1's host->device transfer overlaps batch N's device phase;
    # 0 = stage feeds synchronously through DataFeeder (the old path)
    "prefetch_depth": (int, 2, "trainer device-feed prefetch depth"),
    # trace-event timeline (monitor.py): host spans, executor step
    # phases, compiles and stall records buffered as Chrome-trace events
    # and written as trace-<host>-<pid>.json into this directory at
    # process exit (or monitor.export_trace()); empty = no file, but the
    # /trace route still serves the ring while the live endpoint is up
    "trace_dir": (str, "", "Chrome-trace timeline output directory"),
    # sample step-phase trace events every N executor steps (spans,
    # compiles and stalls are always traced while tracing is active —
    # phase events are the per-step volume this bounds); 1 = every step
    "trace_every_n_steps": (int, 1, "step-phase trace sampling period"),
    # device-side numerics plane (numerics.py): executors fetch + decode
    # the in-graph tensor-stats bundle of instrumented programs into
    # pt_tensor_* / pt_nonfinite_* instruments and NaN-provenance
    # records. Needs `telemetry`; off = the one-boolean-check hot path.
    "numerics": (bool, False, "decode in-graph tensor-stats bundles"),
    # sample the numerics bundle every N executor steps (the stats are
    # computed on device every step either way — sampling bounds the
    # device->host transfer + decode cost); 1 = every step
    "numerics_every_n_steps": (int, 1, "numerics decode sampling period"),
    # comma-separated fnmatch patterns selecting which vars the
    # instrument_numerics pass instruments (e.g. '*@GRAD,fc_*'); empty =
    # every float activation/gradient/parameter
    "numerics_vars": (str, "", "var-name filter for instrument_numerics"),
    # deterministic fault-injection plan (faults.py):
    # 'site:action@trigger[,trigger];site2:...' — e.g.
    # 'ckpt.write_shards:raise@2;fleet.kv_get:delay(0.05)@1,3'. Actions:
    # raise[(msg)] / delay(seconds) / truncate(bytes); triggers: Nth hit
    # (1-based) or pFLOAT (per-hit probability from the fault_seed
    # stream). Empty = injection disarmed (the one-boolean hot path).
    "fault_plan": (str, "", "deterministic fault-injection plan"),
    # seed for pFLOAT plan triggers: the per-site random stream is
    # derived from (seed, site name), so a seeded chaos run reproduces
    # its fault sequence exactly
    "fault_seed": (int, 0, "seed for probabilistic fault-plan triggers"),
    # fleet observability plane (fleet_monitor.py): minimum gap between
    # registry-digest publishes into fleet KV, piggybacked on heartbeat
    # calls (needs `telemetry` and a multi-worker fleet); 0 = publish on
    # every heartbeat
    "fleet_metrics_interval_ms": (int, 1_000,
                                  "min gap between fleet metric-digest "
                                  "publishes"),
    # cross-rank straggler detector (fleet_monitor.py): a rank is named
    # a straggler when its rolling step time exceeds BOTH the alive-rank
    # median times this factor AND the median plus the _min_ms floor
    # (the floor keeps sub-millisecond jitter from naming stragglers on
    # fast steps)
    "fleet_straggler_factor": (float, 2.0,
                               "straggler threshold vs median step time"),
    "fleet_straggler_min_ms": (int, 20,
                               "absolute step-time skew floor for the "
                               "straggler detector"),
    # device-memory watermarks (monitor.py): sample guarded
    # Device.memory_stats() into pt_device_bytes_in_use/peak every N
    # executor steps (CPU/backends without the API degrade silently);
    # 0 = off. Needs `telemetry`.
    "device_memory_every_n_steps": (int, 16,
                                    "device-memory watermark sampling "
                                    "period"),
    # pre-compile static program verifier (analysis.py): 'warn' lints
    # every program before its first compile and logs warning/error
    # findings; 'error' additionally raises LintError on error-severity
    # findings; 'off' disables the verifier entirely (the executor hot
    # path is then one boolean check, zero allocations)
    "static_lint": (str, "warn",
                    "pre-compile static verifier: off|warn|error"),
    # serving plane (serving.py): request-queue backpressure — submit()
    # raises QueueFull (and counts the request rejected) once this many
    # requests are waiting for a batch slot
    "serve_queue_depth": (int, 64, "serving request-queue capacity"),
    # default per-request deadline for serving engines: a request still
    # decoding past its deadline is evicted at the next token boundary
    # (outcome 'expired', partial output kept); 0 = no deadline. A
    # submit(deadline_ms=) overrides per request.
    "serve_deadline_ms": (int, 0, "default serving request deadline"),
    # deadline-aware admission control (serving.py): when a request
    # carries a deadline and the engine's measured per-token latency x
    # its estimated queue position says even the FIRST token cannot land
    # before it, submit() refuses the request up front (outcome
    # 'rejected_early', DeadlineUnmeetable raised) instead of queueing
    # doomed work
    "serve_admission_control": (bool, True,
                                "refuse unmeetable-deadline requests at "
                                "submit time"),
    # EngineSupervisor wedge detection: a busy engine whose decode-loop
    # heartbeat is older than this is declared wedged, torn down and
    # rebuilt; declaring a wedge also emits a monitor stall record for
    # site "serve.decode"
    # (per-dispatch stall_guard deadlines stay on the global
    # stall_timeout_ms flag)
    "serve_wedge_timeout_ms": (int, 30_000,
                               "supervised-engine wedge-detection "
                               "deadline"),
    # lifetime restart budget for one EngineSupervisor: past it the
    # supervisor gives up, finishes every pending handle with outcome
    # 'error' and closes (a permanently failing engine must not restart
    # forever)
    "serve_max_restarts": (int, 3, "EngineSupervisor restart budget"),
    # serving brownout: once the request queue has held at least
    # queue_factor x serve_queue_depth entries for window consecutive
    # scheduler ticks, new admissions have max_new_tokens capped at
    # brownout_max_new_tokens — the engine sheds tokens per request
    # instead of letting queue latency collapse; 0 factor = brownout off
    "serve_brownout_queue_factor": (float, 0.0,
                                    "queue-saturation fraction that "
                                    "engages brownout (0 = off)"),
    "serve_brownout_window": (int, 16,
                              "consecutive saturated ticks before "
                              "brownout engages"),
    "serve_brownout_max_new_tokens": (int, 16,
                                      "max_new_tokens cap applied to "
                                      "admissions during brownout"),
    # request-scoped SLO plane (serving_trace.py): terminal requests are
    # measured against these targets and the pt_slo_* counters burn on
    # every miss — a censored request (terminal before its first token)
    # counts AGAINST the TTFT target, so overload cannot improve the
    # apparent SLO. 0 = no target (the status counters stay empty; the
    # deadline burn rows tick regardless — a request's own deadline IS
    # its SLO).
    "serve_slo_ttft_ms": (float, 0.0,
                          "time-to-first-token SLO target (0 = none)"),
    "serve_slo_token_ms": (float, 0.0,
                           "per-token decode-latency SLO target "
                           "(0 = none)"),
    # bounded recently-terminated request ring served on the /requests
    # monitor route (per-phase latency breakdowns + deadline attribution
    # per terminal request)
    "serve_recent_requests": (int, 256,
                              "recently-terminated request ring "
                              "capacity on /requests"),
    # serving fleet (fleet_serving.py): the router's autoscaler. Off by
    # default — a ServingFleet holds the replica count it was built
    # with; on, the pump scales up when the aggregate queue occupancy
    # across serving replicas has been >= scale_up_queue_factor of
    # aggregate queue capacity for autoscale_window consecutive pump
    # ticks (up to max_replicas), and drains-then-retires one replica
    # after scale_down_idle_ticks consecutive fully-idle ticks (down to
    # min_replicas). A spin-up's XLA compiles are reads from jax's
    # persistent cache where one is placed (jax_cache.py).
    "serve_fleet_autoscale": (bool, False,
                              "ServingFleet queue-pressure autoscaling"),
    "serve_fleet_min_replicas": (int, 1,
                                 "autoscale floor on fleet replicas"),
    "serve_fleet_max_replicas": (int, 8,
                                 "autoscale ceiling on fleet replicas"),
    "serve_fleet_scale_up_queue_factor": (
        float, 0.75, "aggregate queue-occupancy fraction that counts a "
                     "pump tick as saturated"),
    "serve_fleet_autoscale_window": (int, 8,
                                     "consecutive saturated pump ticks "
                                     "before a replica spins up"),
    "serve_fleet_scale_down_idle_ticks": (
        int, 64, "consecutive idle pump ticks before one replica is "
                 "drained and retired"),
    # rolling-rollout / retire drain budget: a draining replica gets
    # this long to finish its in-flight set before the router harvests
    # the leftovers and re-homes them on survivors
    "serve_fleet_handoff_timeout_ms": (int, 30_000,
                                       "fleet drain-handoff budget per "
                                       "replica"),
    # unified retry policy (retry.py) used by fleet connect/kv/heartbeat:
    # first backoff sleep; subsequent sleeps take decorrelated jitter in
    # [base, 3*prev] capped at retry_max_delay_ms
    "retry_base_delay_ms": (int, 100, "retry backoff base delay"),
    "retry_max_delay_ms": (int, 5_000, "retry backoff delay cap"),
    # attempts cap per retried call; 0 = bounded only by the call's
    # deadline budget (rpc_deadline_ms or the caller's timeout)
    "retry_max_attempts": (int, 0, "retry attempt cap (0 = deadline-only)"),
}

_values: Dict[str, Any] = {}

# name -> [callbacks]; notified on every set_flags change to that flag
# (and once on registration) so modules can cache hot flag values instead
# of doing a dict lookup per call — monitor.py's enabled() fast path.
_watchers: Dict[str, list] = {}


def _parse(ty, raw: str):
    if ty is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return ty(raw)


def _bootstrap():
    for name, (ty, default, _doc) in _DEFS.items():
        raw = os.environ.get(f"PT_FLAGS_{name}")
        if raw is None:
            raw = os.environ.get(f"FLAGS_{name}")
        _values[name] = _parse(ty, raw) if raw is not None else default


def get_flag(name: str):
    if name not in _DEFS:
        raise KeyError(f"unknown flag '{name}'; known: {sorted(_DEFS)}")
    return _values[name]


def get_flags(names=None) -> Dict[str, Any]:
    if names is None:
        return dict(_values)
    return {n: get_flag(n) for n in names}


def set_flags(flags: Dict[str, Any]):
    for name, v in flags.items():
        if name not in _DEFS:
            raise KeyError(f"unknown flag '{name}'; known: {sorted(_DEFS)}")
        ty = _DEFS[name][0]
        _values[name] = _parse(ty, v) if isinstance(v, str) else ty(v)
        for cb in _watchers.get(name, ()):
            cb(_values[name])


def watch_flag(name: str, callback):
    """Call ``callback(value)`` now and on every subsequent change to
    ``name`` via set_flags — the cached-hot-flag pattern (monitor.py)."""
    if name not in _DEFS:
        raise KeyError(f"unknown flag '{name}'; known: {sorted(_DEFS)}")
    _watchers.setdefault(name, []).append(callback)
    callback(_values[name])


def describe_flags() -> list:
    """Self-documenting flag table: one dict per registered flag with
    ``name``/``type``/``default``/``doc``/``value`` (current), sorted by
    name — so flag docs are reachable without reading this source."""
    return [
        {
            "name": name,
            "type": ty.__name__,
            "default": default,
            "doc": doc,
            "value": _values[name],
        }
        for name, (ty, default, doc) in sorted(_DEFS.items())
    ]


_bootstrap()
