"""Executor and Scope.

API parity with the reference's ``fluid.Executor`` (reference:
python/paddle/fluid/executor.py:550) but execution is whole-block XLA
compilation (see core/lowering.py) instead of injecting feed/fetch ops and
interpreting. The compiled-function cache keyed on
(program version, feed signature, fetch list) replaces the reference's
prepared-context cache (reference: executor.py:704).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time

from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import analysis as _analysis
from paddle_tpu import faults as _faults
from paddle_tpu import monitor as _monitor
from paddle_tpu import numerics as _numerics
from paddle_tpu.core import fingerprint as _fingerprint
from paddle_tpu.core import lowering
from paddle_tpu.framework import (
    CPUPlace,
    TPUPlace,
    Variable,
    default_main_program,
    resolve_place,
)

# Telemetry instruments (no-ops while the 'telemetry' flag is off — one
# boolean check per call, zero allocations; see monitor.py).
_M_CACHE_HITS = _monitor.counter(
    "pt_executor_cache_hits_total", "compiled-step cache hits")
_M_CACHE_MISSES = _monitor.counter(
    "pt_executor_cache_misses_total",
    "compiled-step cache misses (fresh compiles)")
_M_CACHE_EVICTIONS = _monitor.counter(
    "pt_executor_cache_evictions_total",
    "compiled-step cache entries evicted at capacity")
_M_DONATED_DROPS = _monitor.counter(
    "pt_executor_donated_drops_total",
    "donated state buffers dropped after a failed step")
_M_STEPS = _monitor.counter(
    "pt_executor_steps_total",
    "executor steps run (run_steps windows count each inner step)")
_M_FEED_BYTES = _monitor.counter(
    "pt_executor_feed_bytes_total",
    "bytes across feed arrays per step (an upper bound on host->device "
    "transfer: device-resident or staging-cached feeds count too)")
_M_FETCH_BYTES = _monitor.counter(
    "pt_executor_fetch_bytes_total", "bytes across fetch arrays per step")
_M_FIRST_CALLS = _monitor.counter(
    "pt_executor_first_calls_total",
    "first calls of a compiled entry (the call that traces, lowers and "
    "compiles it) by kind (step | window) and by why the executor had no "
    "entry (new_program, program_version, amp, strategy, feed_signature, "
    "fetch_list, scope, evicted)")
_M_NAN_FAILS = _monitor.counter(
    "pt_executor_nan_check_failures_total",
    "check_nan_inf scans that found non-finite values")

# chaos hook (faults.py): armed plans can delay the step body (the fleet
# straggler drill — the sleep lands in the dispatch phase) or raise a
# synthetic RESOURCE_EXHAUSTED (the OOM-forensics drill)
_F_STEP = _faults.site("executor.step")
# deferred-fetch materialization (LazyFetches.wait): a raised
# RESOURCE_EXHAUSTED here drills the async-dispatch error path — the
# device failure that surfaces only when the fetch lands
_F_FETCH = _faults.site("executor.fetch")


# Why a call found no compiled entry: the first part of its identity that
# differs from the last entry built for the same program, in this order
# (Executor._miss_cause; before them new_program, after them evicted).
# A window's feed signature holds its length, its rotation and its
# nan-tracking flavour too: everything the call passes beside the program.
_MISS_CAUSES = ("program_version", "amp", "strategy", "feed_signature",
                "fetch_list", "scope")
# identities remembered per program: a recompile storm must not grow it
_BUILT_CAPACITY = 64
# what a call whose entry was cached opens in place of the first-call span
_NOT_FIRST = contextlib.nullcontext()


@contextlib.contextmanager
def _first_call_span(program, kind, cause, step):
    with _monitor.span("executor.first_call", step=step, program=program,
                       kind=kind, cause=cause), _monitor.compiling(program):
        yield


def _first_call_fn(fn, *args):
    """``fn(*args)`` for the call that traces and lowers ``fn``, on a
    data-stack chunk of its own. CPython keeps a thread's frames in
    16 KiB chunks and unmaps a chunk when the frame at its base returns,
    so a call that does not fit the chunk in use costs an mmap and a
    munmap every time it is made. jax's trace and lowering make millions
    of calls 1,500 to 2,000 slots under ``fn``, which is where the
    harness's and the executor's own frames put the first chunk's end:
    40 slots more of executor frame took ``tbase-train-dp4``'s lowering
    from 17.3 to 39.7 s (PERF.md section 6, PR 43). A frame of 8,000
    slots never fits a chunk in use, and the 128 KiB chunk made for it
    has 8,000 slots left under it, three times what a first call was
    seen to reach."""
    return fn(*args)


_first_call_fn.__code__ = _first_call_fn.__code__.replace(co_stacksize=8000)


def _stage_feeds(feed_vals):
    """Host->device staging for the sampled phase path: ``device_put``
    every non-resident feed so the feed phase measures the real
    host->device transfer. An all-``jax.Array`` feed dict (a
    DeviceLoader-prefetched batch) returns the SAME dict with zero
    ``device_put`` calls — the staging-skip contract the prefetch
    pipeline relies on (and tests spy on)."""
    for v in feed_vals.values():
        if not isinstance(v, jax.Array):
            break
    else:
        return feed_vals
    return {k: v if isinstance(v, jax.Array) else jax.device_put(v)
            for k, v in feed_vals.items()}


class LazyFetches:
    """Deferred fetch results (``Executor.run``/``run_steps`` with
    ``async_fetch=True``): list-like, one element per ``fetch_list``
    entry, already converted to numpy by the time an element is read.

    Construction issues every device->host copy without blocking
    (``copy_to_host_async`` — the two-pass idiom proven in
    parallel/checkpoint.py's async snapshot); the numpy conversion
    happens on first element access (or an explicit ``wait()``), so
    step N's fetch materializes under step N+1's host dispatch. A
    deferred device error surfacing at materialization runs the same
    donated-buffer hygiene + OOM forensics as the synchronous commit
    sites, exactly once, then re-raises."""

    __slots__ = ("_arrays", "_values", "_on_error", "_t0")

    def __init__(self, arrays, on_error=None):
        self._arrays = list(arrays)
        self._values = None
        self._on_error = on_error
        for a in self._arrays:
            if isinstance(a, jax.Array):  # host numpy: nothing to start
                a.copy_to_host_async()
        self._t0 = time.perf_counter() if _monitor.enabled() else 0.0

    @property
    def ready(self) -> bool:
        """Whether the fetches have already materialized to numpy."""
        return self._values is not None

    def wait(self) -> list:
        """Materialize every fetch to numpy (idempotent)."""
        if self._values is None:
            try:
                _F_FETCH.hit()
                self._values = [np.asarray(a) for a in self._arrays]
            except Exception as e:
                cb, self._on_error = self._on_error, None
                if cb is not None:
                    cb(e)
                raise
            self._arrays = None  # release the device buffers
            self._on_error = None
            if self._t0:
                _monitor.fetch_overlap(time.perf_counter() - self._t0)
        return self._values

    def __len__(self):
        vals = self._values
        return len(vals if vals is not None else self._arrays)

    def __getitem__(self, i):
        return self.wait()[i]

    def __iter__(self):
        return iter(self.wait())

    def __repr__(self):
        state = "ready" if self.ready else "pending"
        return f"LazyFetches({len(self)} fetches, {state})"


def _sum_nbytes(vals) -> int:
    total = 0
    for v in vals:
        n = getattr(v, "nbytes", None)
        if n is not None:
            total += int(n)
    return total


def _strategy_id(strategy) -> Optional[str]:
    """Compact SPMD strategy label for step logs: mesh axes x sizes."""
    if strategy is None:
        return None
    mesh = getattr(strategy, "mesh", None)
    if mesh is None:
        return "strategy"
    return ",".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)


class Scope:
    """name -> device array container (reference: framework/scope.h:45).

    Values live as committed JAX arrays (device-resident between steps); numpy
    values are accepted and converted lazily.
    """

    _uid_counter = 0

    def __init__(self):
        self._vars: Dict[str, Any] = {}
        Scope._uid_counter += 1
        self._uid = Scope._uid_counter

    def set(self, name: str, value):
        self._vars[name] = value

    def find_var(self, name: str):
        return self._vars.get(name)

    def var_names(self) -> List[str]:
        return list(self._vars)

    def has(self, name: str) -> bool:
        return name in self._vars

    def drop(self, name: str):
        self._vars.pop(name, None)

    def clear(self):
        self._vars.clear()


_global_scope = Scope()


class _ScopeTLS(threading.local):
    def __init__(self):
        self.stack: List[Scope] = []


_scope_tls = _ScopeTLS()


def global_scope() -> Scope:
    stack = _scope_tls.stack
    return stack[-1] if stack else _global_scope


@contextlib.contextmanager
def scope_guard(scope: Scope):
    """Swap the ambient scope (reference: fluid.executor.scope_guard).

    A guard entered on a worker thread is THREAD-LOCAL: concurrent
    engines (serving-fleet replicas each driving their own supervisor
    loop thread) must not resolve each other's scopes through a shared
    global — a torn swap hands one engine another engine's decode
    state, or a stateless scope mid-step. The main thread keeps the
    legacy process-global swap so unguarded worker threads still
    inherit the main thread's guarded scope."""
    if threading.current_thread() is threading.main_thread():
        global _global_scope
        old, _global_scope = _global_scope, scope
        try:
            yield
        finally:
            _global_scope = old
    else:
        _scope_tls.stack.append(scope)
        try:
            yield
        finally:
            _scope_tls.stack.pop()


@dataclasses.dataclass(slots=True)
class _Call:
    """What a prepare decided (``Executor._run``: a step; ``_run_steps``:
    a window), as ``Executor._dispatch`` runs it."""

    program: Any
    scope: "Scope"
    fn: Any                     # the entry's jitted function
    lowered: Any
    feeds: Dict[str, Any]       # as fn takes them: a step's feed dict, or
    #                             a window's, stacked along a leading axis
    fetch_names: List[str]      # the caller's (the numerics bundle apart)
    nplan: Any                  # the numerics plan whose bundle rides last
    start: int                  # the call's (first) step index
    outcome: str                # _cache_entry's: "hit" | "miss"
    evictions: int
    compile_ms: Optional[float]
    fp: Any                     # the fingerprint, the compile report's key
    first: Any                  # the context fn runs in (_first_call)
    return_numpy: bool
    async_fetch: bool
    tele: bool                  # telemetry as the call found it
    t_run0: float               # the call's start (0.0 with telemetry off)
    ph: bool                    # the phase plane is on
    sampled: bool               # and samples this call: it alone blocks
    compiled: Any = None        # the CompiledProgram a step came as
    steps: Optional[int] = None  # a window's length; None: a step
    t_f0: float = 0.0           # the feed phase's marks, where the
    t_f1: float = 0.0           # prepare staged the feeds (a window)

    @property
    def kind(self) -> str:
        """The label of the call's record, compile report and spans."""
        return "step" if self.steps is None else "window"


def _prng_impl():
    """Program-level PRNG implementation. On TPU, threefry random-bit
    generation is slow enough to dominate dropout (an ablation before the
    chip era: 21.5ms of a 63ms transformer step), so the hardware 'rbg'
    generator is the default there; CPU keeps threefry so test streams
    stay stable. Override with the 'prng_impl' flag."""
    from paddle_tpu import flags as _flags

    choice = _flags.get_flag("prng_impl")
    if choice != "auto":
        return choice
    return "rbg" if jax.default_backend() == "tpu" else None


class Executor:
    """Runs programs on jax's default backend. ``place`` names the
    platform the caller expects (framework.resolve_place): ``None`` takes
    whatever the default device is and records it in ``place`` /
    ``device``; an explicit place the process cannot honor raises."""

    # staged run_steps feed windows kept device-resident across calls;
    # small on purpose: each entry pins a whole stacked feed window on
    # device, so the cap is an HBM contract, not a perf knob
    STAGED_WINDOW_CAPACITY = 4

    def __init__(self, place: Optional[Union[CPUPlace, TPUPlace]] = None):
        self.place, self.device = resolve_place(place)
        self._cache: Dict[tuple, Any] = {}
        self._step = 0
        self._base_keys: Dict[tuple, Any] = {}
        # (kind, program uid) -> the identities built for it, oldest
        # first (_miss_cause reads it on a miss, nothing on a hit)
        self._built: Dict[tuple, Dict[tuple, None]] = {}
        # keyed LRU of run_steps feed stagings: id-tuple of the host
        # arrays -> {"arrs": pinned host refs (id identity stays valid),
        # "stacked": device window, "owner": compiled-cache key}.
        # Replaces the old single-slot cache so alternating feed
        # rotations (stage window B while window A executes) stop
        # thrashing the slot. Evicting a compiled entry drops the staged
        # windows it owns (stale staging would pin device-resident feed
        # windows after the entry is gone).
        self._staged: "collections.OrderedDict[tuple, dict]" = (
            collections.OrderedDict())

    # --- public API ---

    def run(
        self,
        program=None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        async_fetch: bool = False,
    ):
        # Host spans (monitor.span; with telemetry on they reach a
        # jax.profiler trace on the device's clock): executor.run around
        # the whole call, carrying the step index its children share,
        # and children that tile it: executor.prepare (feed
        # normalisation, signature, fingerprint, cache entry),
        # executor.state (gather, commit_state, shard_inputs),
        # executor.run_step (the jitted call alone), executor.commit.
        # None waits for the device: they time the host while the
        # device runs ahead. The call that built its entry (a cache
        # miss) opens executor.first_call inside executor.run_step, with
        # the program, the kind and the cause of the miss: the trace,
        # the lowering and XLA (or the read from jax's cache) happen
        # there, and monitor's jax listeners charge them to the program.
        with _monitor.span("executor.run", step=self._step):
            return self._run(program, feed, fetch_list, scope,
                             return_numpy, use_program_cache, async_fetch)

    def _run(self, program, feed, fetch_list, scope, return_numpy,
             use_program_cache, async_fetch):
        """What a step is: run()'s prepare. _dispatch runs the call."""
        from paddle_tpu.compiler import CompiledProgram

        tele = _monitor.enabled()
        # wall_ms covers the WHOLE call, feed conversion/staging included
        t_run0 = time.perf_counter() if tele else 0.0
        with _monitor.span("executor.prepare"):
            compiled = None
            if isinstance(program, CompiledProgram):
                compiled = program
                program = compiled.program
            if program is None:
                program = default_main_program()
            scope = scope or global_scope()
            feed = feed or {}
            fetch_list = list(fetch_list or [])
            fetch_names = [
                f.name if isinstance(f, Variable) else str(f)
                for f in fetch_list
            ]

            feed_items = sorted(feed.items())
            feed_names = [k for k, _ in feed_items]
            feed_vals = {}
            for k, v in feed_items:
                arr = np.asarray(v) if not isinstance(v, jax.Array) else v
                feed_vals[k] = arr

            # Device-side numerics (numerics.py): an instrumented program's
            # stats bundle rides the SAME compiled step as one extra fetch,
            # decoded after the run on sampled steps. Resolved before the
            # cache key — plan attachment bumps the program version.
            nplan = _numerics.plan_for(program) if _numerics.active() else None
            run_fetch_names = fetch_names if nplan is None else (
                fetch_names + [nplan.bundle_var])

            sig = tuple(
                (k, tuple(np.shape(v)), str(jnp.result_type(v)))
                for k, v in feed_vals.items()
            )
            # Canonical fingerprint (core.fingerprint.program_fingerprint):
            # content-keyed, shared with the lint-once cache and the compile
            # report cache_key. The memo keyed by this cheap identity tuple
            # keeps the hot path at one dict read (program._amp is
            # identity-relevant: flipping it does NOT bump the version).
            ident = (
                program._uid,
                program.version,
                getattr(program, "_amp", False),
                compiled._uid if compiled is not None else 0,
                sig,
                tuple(run_fetch_names),
            )
            fp = _fingerprint.fingerprint_for(
                ident, program, compiled=compiled, feed_sig=sig,
                fetch_names=run_fetch_names)
            key = (fp, scope._uid)

            def build():
                return self._compile(
                    program, compiled, feed_names, run_fetch_names, scope
                )

            if _analysis.lint_active():
                # static verifier BEFORE the first compile of this signature
                # (static_lint flag: warn logs findings, error raises; the
                # off path is the one boolean check above, zero allocations).
                # Gated on the verifier's OWN fingerprint cache, not this
                # executor's compile cache: a static_lint mode flip must
                # re-lint signatures another gate would consider warm.
                _analysis.lint_before_compile(
                    program, feed_names, run_fetch_names,
                    strategy=compiled._strategy if compiled is not None
                    else None,
                    site="executor.run")
            if (tele and _monitor.memory_budget_bytes() > 0
                    and (not use_program_cache or key not in self._cache)):
                # pre-flight BEFORE paying for the compile: a program whose
                # static estimate already exceeds the device budget warns now
                _monitor.check_memory_budget(
                    program, {k: np.shape(v) for k, v in feed_vals.items()})
            if use_program_cache:
                entry, outcome, evictions, compile_ms = self._cache_entry(
                    key, build, program)
            else:
                entry, compile_ms = self._timed_build(build, program)
                outcome, evictions = "miss", 0
            start = self._step
            first = _NOT_FIRST if outcome != "miss" else self._first_call(
                "step", program, start,
                (program.version, getattr(program, "_amp", False),
                 compiled._uid if compiled is not None else 0, sig,
                 tuple(run_fetch_names), scope._uid))
            ph = tele and _monitor.phases_active()
            call = _Call(
                program=program, compiled=compiled, scope=scope, fn=entry[0],
                lowered=entry[1], feeds=feed_vals, fetch_names=fetch_names,
                nplan=nplan, start=start, outcome=outcome,
                evictions=evictions, compile_ms=compile_ms, fp=fp,
                first=first, return_numpy=return_numpy,
                async_fetch=async_fetch, tele=tele, t_run0=t_run0, ph=ph,
                sampled=ph and _monitor.phases_sampled(start))
        return self._dispatch(call)

    def run_steps(
        self,
        program=None,
        feed_list: Optional[Sequence[Dict[str, Any]]] = None,
        steps: int = 1,
        fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        async_fetch: bool = False,
    ):
        """Run ``steps`` training iterations as ONE compiled XLA program,
        rotating over ``feed_list`` (a list of same-signature feed dicts;
        step i consumes feed ``i % len(feed_list)``).

        The whole-loop analog of the reference's ``RunFromDataset`` hot
        loop (reference: framework/executor.cc:120-147): no per-step
        Python dispatch, PRNG streams bit-identical to ``steps``
        successive ``run`` calls (the per-step fold_in index keeps
        advancing ``self._step``). Returns the LAST step's fetches.
        """
        # run()'s span tree, with executor.run_window as the root (its
        # ``step`` is the window's first)
        with _monitor.span("executor.run_window", step=self._step):
            return self._run_steps(program, feed_list, steps, fetch_list,
                                   scope, return_numpy, async_fetch)

    def _run_steps(self, program, feed_list, steps, fetch_list, scope,
                   return_numpy, async_fetch):
        """What a window is: run_steps()'s prepare. _dispatch runs the
        call."""
        from paddle_tpu.compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            raise TypeError(
                "run_steps does not support CompiledProgram (sharded "
                "inputs/SPMD context are per-step concerns); use run()")
        if not feed_list:
            raise ValueError("run_steps needs a non-empty feed_list")
        tele = _monitor.enabled()
        # started before feed stacking: device_put of the whole window is
        # often the dominant host cost, and wall_ms must show it
        t_run0 = time.perf_counter() if tele else 0.0
        with _monitor.span("executor.prepare"):
            if program is None:
                program = default_main_program()
            scope = scope or global_scope()
            fetch_list = list(fetch_list or [])
            fetch_names = [
                f.name if isinstance(f, Variable) else str(f)
                for f in fetch_list
            ]
            feed_names = sorted(feed_list[0])
            steps = int(steps)
            start = self._step
            from paddle_tpu import flags as _flags_mod

            # Per-step in-graph finiteness tracking (core/lowering.py): the
            # compiled window carries the index of the first bad step, so a
            # failure names the step, not just the window. Part of the cache
            # key — flipping the flag compiles the other variant.
            nan_track = bool(_flags_mod.get_flag("check_nan_inf"))
            nplan = _numerics.plan_for(program) if _numerics.active() else None
            run_fetch_names = fetch_names if nplan is None else (
                fetch_names + [nplan.bundle_var])
            # Stacking device_puts every feed; cache by array IDENTITY so a
            # repeated feed_list (the bench window pattern) stages once. The
            # cache only engages when every feed is IMMUTABLE — a jax.Array,
            # or an OWNING numpy array (base is None) with writeable=False —
            # because identity of a mutable buffer says nothing about its
            # contents: the standard preallocated-loader pattern refills the
            # same buffer in place, and a stale identity hit would silently
            # reuse old device data. A frozen VIEW does not qualify: its
            # contents still change through a writeable base. Mutable numpy
            # feeds are re-staged every call (same contract as run()); pass
            # jax.Arrays or owning frozen copies to get one-time staging.
            # The cache is a small keyed LRU (STAGED_WINDOW_CAPACITY), so
            # alternating rotations stay staged — the next rotation's
            # device_put overlaps the current window's device work instead
            # of thrashing a single slot.
            # Phase marks (see _dispatch): the stacking below IS the
            # window's feed phase — device_put of the whole window dominates
            # host cost, and the breakdown must show it.
            ph = tele and _monitor.phases_active()
            sampled = ph and _monitor.phases_sampled(start, steps)
            t_f0 = t_f1 = 0.0
            if sampled:
                t_f0 = time.perf_counter()
            arrs = [fb[k] for fb in feed_list for k in feed_names]
            cacheable = all(
                isinstance(a, jax.Array)
                or (isinstance(a, np.ndarray) and a.base is None
                    and not a.flags.writeable)
                for a in arrs
            )
            stacked = None
            staged_key = tuple(map(id, arrs)) if cacheable else None
            if staged_key is not None:
                entry = self._staged.get(staged_key)
                # the pinned refs keep the id()s valid; the `is` sweep makes
                # the hit exact even so
                if entry is not None and len(entry["arrs"]) == len(arrs) \
                        and all(a is b for a, b in zip(entry["arrs"], arrs)):
                    stacked = entry["stacked"]
                    self._staged.move_to_end(staged_key)
            if stacked is None:
                stacked = {
                    k: jnp.stack([jnp.asarray(fb[k]) for fb in feed_list])
                    for k in feed_names
                }
                if staged_key is not None:
                    # host array refs pinned inside the entry — id() reuse
                    # after GC could otherwise alias a fresh array to a
                    # stale key. An uncacheable call leaves existing entries
                    # alone: each can only hit on its own pinned arrs.
                    self._staged[staged_key] = {
                        "arrs": arrs, "stacked": stacked, "owner": None}
                    while len(self._staged) > self.STAGED_WINDOW_CAPACITY:
                        self._staged.popitem(last=False)
            if sampled:
                jax.block_until_ready(list(stacked.values()))
                t_f1 = time.perf_counter()
            sig = tuple(
                (k, tuple(v.shape), str(v.dtype)) for k, v in sorted(
                    stacked.items())
            )
            # Canonical fingerprint (see _run); the window variant folds in
            # the feed-rotation length and the nan-track flavor. ``steps``
            # rides the KEY, not the fingerprint content hash: it is a static
            # argument of the jit, so each value is a compile of its own and
            # its entry reports its own ``miss`` and ``compile_ms``.
            ident = (
                "multi", program._uid, program.version,
                getattr(program, "_amp", False), len(feed_list), sig,
                tuple(run_fetch_names), nan_track,
            )
            fp = _fingerprint.fingerprint_for(
                ident, program, feed_sig=sig, fetch_names=run_fetch_names,
                extra=("multi", len(feed_list), bool(nan_track)))
            key = (fp, scope._uid, steps)
            if staged_key is not None and staged_key in self._staged:
                # eviction coupling: remember which compiled entry owns the
                # staged window (see _cache_entry)
                self._staged[staged_key]["owner"] = key

            def build():
                lowered = lowering.lower_block(program, 0, feed_names,
                                               run_fetch_names)
                return (lowering.jit_lowered_multi(lowered, len(feed_list),
                                                   track_nonfinite=nan_track),
                        lowered)

            if _analysis.lint_active():
                # static verifier before the window's first compile (the
                # whole-window donation/dataflow semantics are the same
                # single-step block repeated). Gated on the verifier's own
                # fingerprint cache — see _run.
                _analysis.lint_before_compile(
                    program, feed_names, run_fetch_names,
                    site="executor.run_steps")
            if (tele and _monitor.memory_budget_bytes() > 0
                    and key not in self._cache):
                # per-step feed shapes: drop the stacked window axis
                _monitor.check_memory_budget(
                    program,
                    {k: tuple(v.shape[1:]) for k, v in stacked.items()})
            entry, outcome, evictions, compile_ms = self._cache_entry(
                key, build, program)
            first = _NOT_FIRST if outcome != "miss" else self._first_call(
                "window", program, start,
                (program.version, getattr(program, "_amp", False), 0,
                 (sig, len(feed_list), steps, nan_track),
                 tuple(run_fetch_names), scope._uid))
            call = _Call(
                program=program, scope=scope, fn=entry[0], lowered=entry[1],
                feeds=stacked, fetch_names=fetch_names, nplan=nplan,
                start=start, steps=steps, outcome=outcome,
                evictions=evictions, compile_ms=compile_ms, fp=fp,
                first=first, return_numpy=return_numpy,
                async_fetch=async_fetch, tele=tele, t_run0=t_run0, ph=ph,
                sampled=sampled, t_f0=t_f0, t_f1=t_f1)
        return self._dispatch(call)

    # --- the one body of run() and run_steps() ---

    def _dispatch(self, call):
        """Run a prepared call, a step or a window, from the
        ``executor.state`` span to its step-log record."""
        # Ops needing explicit collectives (ring attention, sharded tables)
        # read the SPMD context at trace time, which happens inside the
        # first jitted call.
        from paddle_tpu.core import interp as _interp

        program, compiled, scope = call.program, call.compiled, call.scope
        fn, lowered, feeds, nplan = (call.fn, call.lowered, call.feeds,
                                     call.nplan)
        start, steps = call.start, call.steps
        n = 1 if steps is None else steps
        tele, t_run0 = call.tele, call.t_run0
        # Phase attribution timestamps (perf_counter; 0.0 = not reached,
        # so a call that failed before commit logs a record without
        # phases — truncated phase durations would skew the verdict
        # window). Phases: feed = host->device staging, dispatch =
        # Python + tracing overhead (both segments around the staged
        # feed), device = delta to block_until_ready, fetch =
        # device->host + decode in _commit. Gated separately from
        # `tele`: the device phase costs a per-call sync, and the
        # step_phases / step_phases_every_n flags let metrics-only (or
        # merely steady-state) telemetry keep async dispatch — only a
        # SAMPLED call pays the honest-device-timing block_until_ready.
        ph, sampled = call.ph, call.sampled
        t_f0, t_f1 = call.t_f0, call.t_f1
        t_c1 = t_b1 = t_x0 = t_x1 = 0.0
        with _monitor.span("executor.state"):
            state = self._gather_state(scope, lowered)
            if compiled is not None and call.outcome != "hit":
                state = compiled.commit_state(scope, state)
            # typed base key (rbg on TPU), created ONCE per (seed, impl): the
            # per-step fold_in happens INSIDE the compiled call (the step
            # index rides along as a scalar arg) instead of costing two extra
            # host-side jit dispatches per step.
            base_key = self._base_key_for(program)
            self._step += n
            if steps is None:
                # a step's feed phase (a window's is its prepare's stacking)
                if sampled:
                    t_f0 = time.perf_counter()
                if compiled is not None:
                    state, feeds = compiled.shard_inputs(state, feeds)
                if sampled:
                    if compiled is None:
                        # stage feeds explicitly so the feed phase measures
                        # the real host->device transfer instead of hiding it
                        # inside the jitted call's dispatch (the transfer
                        # happens either way; committed default-device arrays
                        # are what jit would produce; an already-device-
                        # resident feed dict skips staging entirely — see
                        # _stage_feeds). The compiled path keeps shard_inputs
                        # as its staging step — an extra unsharded device_put
                        # would fight the jit's in_shardings.
                        feeds = _stage_feeds(feeds)
                    jax.block_until_ready(list(feeds.values()))
                    t_f1 = time.perf_counter()
        # what fn takes after the state and the feeds; a window's length is
        # a static argument of its jit
        tail = (base_key, np.uint32(start)) + (
            () if steps is None else (steps,))
        strategy = compiled._strategy if compiled is not None else None
        rec = None
        if tele:
            strat_label = _strategy_id(strategy)
            _M_STEPS.inc(n)
            feed_bytes = _sum_nbytes(feeds.values())
            _M_FEED_BYTES.inc(feed_bytes)
            if call.outcome == "miss" and _monitor.compile_reports_active():
                # fresh compile: produce the cost/memory report BEFORE
                # the call executes (lowering only reads avals; after
                # the call the donated state buffers are deleted). The
                # SPMD context scope matters: collective ops read it at
                # trace time.
                with _interp.spmd_ctx_scope(strategy):
                    _monitor.record_compile_report(
                        lowering.build_compile_report(
                            fn, lowered, (state, feeds, *tail),
                            program=program, kind=call.kind,
                            compile_ms=call.compile_ms,
                            strategy=strat_label, cache_key=call.fp))
            if _monitor.step_records_active():
                rec = {"kind": call.kind, "step": start}
                if steps is not None:
                    rec["steps"] = steps
                rec.update(
                    compile_ms=call.compile_ms, cache=call.outcome,
                    evictions=call.evictions, feed_bytes=feed_bytes,
                    fetch_bytes=0, nan_check=None, strategy=strat_label)
                if ph:
                    # phase plane on: mark whether THIS call paid the
                    # honest sync (sampled=False walls are host-only —
                    # /trace and the fleet digest medians filter on it)
                    rec["sampled"] = sampled
        try:
            with _interp.spmd_ctx_scope(strategy), \
                    _monitor.span("executor.run_step"), call.first:
                try:
                    _F_STEP.hit()
                    if call.outcome == "miss":
                        fetches, new_state, *bad = _first_call_fn(
                            fn, state, feeds, *tail)
                    else:
                        fetches, new_state, *bad = fn(state, feeds, *tail)
                except Exception as e:
                    self._failed(call, e)
                    raise
            # under check_nan_inf a window tracks per-step finiteness
            # IN-GRAPH (track_nonfinite) and returns the first bad step's
            # index: the compiled loop stays one dispatch, yet a failure
            # names the exact step inside it
            first_bad = bad[0] if bad else None
            if sampled:
                t_c1 = time.perf_counter()
                # device phase: drain the async dispatch queue. A
                # deferred device error surfaces here instead of inside
                # _commit — same donated-buffer hygiene as a failed call.
                try:
                    jax.block_until_ready((fetches, new_state, first_bad))
                except Exception as e:
                    self._failed(call, e)
                    raise
                t_b1 = time.perf_counter()
            bundle = None
            if nplan is not None:
                bundle, fetches = fetches[-1], fetches[:-1]
            try:
                if sampled:
                    t_x0 = time.perf_counter()
                try:
                    with _monitor.span("executor.commit"):
                        out = self._commit(
                            scope, call.fetch_names, fetches, new_state,
                            call.return_numpy, rec, nan_first_bad=first_bad,
                            window=None if steps is None else (start, steps),
                            async_fetch=call.async_fetch,
                            error_cb=self._fetch_error_cb(
                                scope, lowered, program)
                            if call.async_fetch else None)
                        if sampled:  # only a COMMITTED call is attributed
                            t_x1 = time.perf_counter()
                        # the call's (donated) input state dies inside
                        # the span (and after the fetch phase's mark, which
                        # times _commit alone as it always has): releasing
                        # its buffers is host time of the call, not an
                        # unnamed tail of it
                        state = None
                except Exception as e:
                    # with phases off/unsampled there is no pre-commit
                    # block_until_ready: an async-dispatched device
                    # failure surfaces HERE, in the commit transfer —
                    # same donated-buffer hygiene + OOM hook as the
                    # dispatch/device sites above
                    self._failed(call, e)
                    raise
                return out
            finally:
                # decoded even when check_nan_inf raises — the provenance
                # record is most valuable exactly then. A window's bundle
                # holds its LAST step's stats; nan_step (when the in-graph
                # tracker fired) names its first bad step.
                if bundle is not None and _numerics.should_sample(start, n):
                    summary = _numerics.decode(
                        program, nplan, bundle, start + n - 1,
                        kind=call.kind,
                        nan_step=rec.get("nan_step") if rec else None)
                    if rec is not None:
                        rec["numerics"] = summary
        finally:
            # logged even when the call raises (NaN scan, device/runtime
            # error): the crashed call's record is the one an operator
            # needs for postmortem, and must be the last line of the log
            if tele:
                # watermarks read AFTER the call (success or failure):
                # the post-step high-water is the number an OOM
                # post-mortem wants; self-gating on the sampling period
                _monitor.sample_device_memory(start, n)
            if rec is not None:
                rec["t0"] = t_run0
                rec["wall_ms"] = (time.perf_counter() - t_run0) * 1e3
                if t_x1 > 0.0:  # phases only for calls that completed (a
                    # window's are whole-window totals, one verdict entry)
                    self._attribute_phases(
                        rec, start, t_run0, t_f0, t_f1, t_c1, t_b1,
                        t_x0, t_x1, steps=n,
                        scored=(call.outcome == "hit"))
                elif ph:
                    # unsampled (or failed) call: its input waits must
                    # not pile into the next sampled call's verdict
                    _monitor.discard_input_wait()
                _monitor.log_step(rec)

    def _failed(self, call, e):
        """A call's device failure, wherever it surfaced (the dispatch,
        the sampled drain, the commit's transfer): the donated state
        buffers it consumed are dropped and an OOM leaves its forensics.
        The site re-raises. (A deferred fetch's twin, _fetch_error_cb,
        outlives the call and so holds no record of it: no feeds.)"""
        self._drop_donated(call.scope, call.lowered)
        _monitor.maybe_record_oom(e, program=call.program, phase="run")

    # --- shared plumbing for run()/run_steps() ---

    def _cache_entry(self, key, build, program=None):
        """LRU lookup-or-build with the capacity eviction policy.

        Returns ``(entry, outcome, evictions, compile_ms)`` where
        ``outcome`` is ``"hit"`` (in-memory) or ``"miss"`` (built now:
        traced and lowered on first call, its XLA compile read from
        jax's persistent cache where one is placed and warm). The
        outcome rides the return value (not instance state) so the
        step-log assembly can never read a stale previous call's
        outcome."""
        entry = self._cache.get(key)
        if entry is not None:
            self._cache.pop(key)
            self._cache[key] = entry  # refresh so eviction drops coldest
            _M_CACHE_HITS.inc()
            return entry, "hit", 0, None
        _M_CACHE_MISSES.inc()
        entry, compile_ms = self._timed_build(build, program)
        self._cache[key] = entry
        from paddle_tpu import flags as _flags_mod

        cap = _flags_mod.get_flag("executor_cache_capacity")
        evicted = 0
        while cap > 0 and len(self._cache) > cap:
            victim = next(iter(self._cache))
            self._cache.pop(victim)
            # staged feed windows must not outlive their owning compiled
            # entry (see _staged)
            for sk in [k for k, e in self._staged.items()
                       if e["owner"] == victim]:
                self._staged.pop(sk)
            evicted += 1
        if evicted:
            _M_CACHE_EVICTIONS.inc(evicted)
        return entry, "miss", evicted, compile_ms

    def _miss_cause(self, kind, uid, what):
        """Why this executor holds no entry for the call: ``what`` (a
        tuple aligned with _MISS_CAUSES) against the identities built
        before for the same program. ``new_program``: none was;
        ``evicted``: this very one was (the LRU dropped it, close() or
        release_scope() did, or the caller passed
        ``use_program_cache=False``); else the first part that differs
        from the last one built. Runs on a miss only."""
        seen = self._built.setdefault((kind, uid), {})
        if not seen:
            cause = "new_program"
        elif what in seen:
            cause = "evicted"
        else:
            last = next(reversed(seen))
            cause = next(c for c, a, b in zip(_MISS_CAUSES, last, what)
                         if a != b)
        seen.pop(what, None)
        seen[what] = None   # the last one built is the last key
        while len(seen) > _BUILT_CAPACITY:
            del seen[next(iter(seen))]
        return cause

    def _first_call(self, kind, program, step, what):
        """The context the call that built its entry runs ``fn`` in: the
        ``executor.first_call`` span with the program's id (the compile
        reports' ``program<uid>``), the kind and the cause of the miss,
        and ``monitor.compiling``, by which jax's compile events of the
        call are charged to the program."""
        cause = self._miss_cause(kind, program._uid, what)
        if not _monitor.enabled():
            return _NOT_FIRST
        _M_FIRST_CALLS.inc(labels={"kind": kind, "cause": cause})
        return _first_call_span(f"program{program._uid}", kind, cause, step)

    def _timed_build(self, build, program=None):
        """The executor's OWN build under the ``executor.compile`` span:
        block analysis (``lowering.lower_block``) and the ``jax.jit``
        wrap, milliseconds. NOT the trace of the op list into a jaxpr,
        the lowering to StableHLO or XLA's compile (or the read from
        jax's persistent cache): those happen inside the entry's first
        call, under ``executor.first_call``. Returns ``(entry,
        compile_ms)`` (perf_counter interval) for the step log."""
        with _monitor.span("executor.compile"):
            t0 = time.perf_counter()
            try:
                entry = build()
            except Exception as e:
                # compile-time RESOURCE_EXHAUSTED: the forensics hook's
                # other half (run-time OOMs are caught at the call sites)
                _monitor.maybe_record_oom(e, program=program,
                                          phase="compile")
                raise
            t1 = time.perf_counter()
            # compiles get their own timeline track: a recompile storm
            # reads as a dense compile row, not as mystery-long steps
            _monitor.trace_event("executor.compile", "compile", t0, t1)
            return entry, (t1 - t0) * 1e3

    def _attribute_phases(self, rec, step_idx, t_run0, t_f0, t_f1, t_c1,
                          t_b1, t_x0, t_x1, steps=1, scored=True):
        """Fold a completed step's perf_counter marks into the phase
        breakdown: ``rec['phases']`` (ms), ``rec['bound']`` (the rolling
        window's boundedness verdict), the ``pt_step_phase_seconds``
        histograms, and — on trace-sampled steps — one timeline event
        per phase segment (dispatch is two segments: host work before
        feed staging and the jitted call itself). ``scored=False``
        (fresh compile / disk load): phases are recorded but the step
        stays out of the verdict window — compile time in the dispatch
        segment would otherwise pollute the boundedness verdict."""
        feed_s = t_f1 - t_f0
        disp_s = (t_f0 - t_run0) + (t_c1 - t_f1)
        dev_s = t_b1 - t_c1
        fetch_s = t_x1 - t_x0
        rec["phases"] = {"feed": feed_s * 1e3, "dispatch": disp_s * 1e3,
                         "device": dev_s * 1e3, "fetch": fetch_s * 1e3}
        verdict = _monitor.record_step_phases(feed_s, disp_s, dev_s,
                                              fetch_s, scored=scored)
        if verdict is not None:
            rec["bound"] = verdict
        if _monitor.trace_step_sampled(step_idx, steps):
            step = {"step": step_idx}
            _monitor.trace_event("dispatch", "phase", t_run0, t_f0,
                                 args=step)
            _monitor.trace_event("feed", "phase", t_f0, t_f1, args=step)
            _monitor.trace_event("dispatch", "phase", t_f1, t_c1,
                                 args=step)
            _monitor.trace_event("device", "phase", t_c1, t_b1, args=step)
            _monitor.trace_event("fetch", "phase", t_x0, t_x1, args=step)

    def _gather_state(self, scope, lowered):
        state = {}
        for n in lowered.state_in_names:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"variable '{n}' used by the program is not initialized "
                    f"in the scope — run the startup program first"
                )
            state[n] = v
        return state

    def _base_key_for(self, program):
        seed = program.random_seed if program.random_seed is not None else 0
        impl = _prng_impl()
        base_key = self._base_keys.get((seed, impl))
        if base_key is None:
            base_key = jax.random.key(seed, impl=impl)
            self._base_keys[(seed, impl)] = base_key
        return base_key

    def _drop_donated(self, scope, lowered):
        """After a failed jitted call: donated state buffers that were
        consumed are deleted; drop them so later use fails loudly."""
        for n in lowered.state_in_names:
            v = scope.find_var(n)
            if isinstance(v, jax.Array) and v.is_deleted():
                scope.drop(n)
                _M_DONATED_DROPS.inc()

    def _fetch_error_cb(self, scope, lowered, program):
        """Deferred-fetch failure hygiene (LazyFetches): the same
        donated-buffer drop + OOM forensics the synchronous commit
        sites run, delayed to materialization time."""
        def on_error(e):
            self._drop_donated(scope, lowered)
            _monitor.maybe_record_oom(e, program=program, phase="fetch")
        return on_error

    def _commit(self, scope, fetch_names, fetches, new_state,
                return_numpy, rec=None, nan_first_bad=None, window=None,
                async_fetch=False, error_cb=None):
        from paddle_tpu import flags as _flags

        if _flags.get_flag("benchmark"):
            # honest timing: wait for device work (reference:
            # FLAGS_benchmark forced Wait, operator.cc:946)
            jax.block_until_ready((fetches, new_state))
        # Commit new state BEFORE any post-step check can raise: the old
        # buffers were donated and already deleted.
        for n, v in new_state.items():
            scope.set(n, v)
        if rec is not None:
            rec["fetch_bytes"] = _sum_nbytes(fetches)
            _M_FETCH_BYTES.inc(rec["fetch_bytes"])
        elif _monitor.enabled():
            _M_FETCH_BYTES.inc(_sum_nbytes(fetches))
        if _flags.get_flag("check_nan_inf"):
            if nan_first_bad is not None and window is not None:
                # compiled window: the in-graph tracker names the FIRST
                # failing step (jit_lowered_multi track_nonfinite)
                start, steps = window
                idx = int(np.asarray(nan_first_bad))
                if idx < steps:
                    _M_NAN_FAILS.inc()
                    if rec is not None:
                        rec["nan_check"] = "fail"
                        rec["nan_step"] = start + idx
                    raise FloatingPointError(
                        f"check_nan_inf: step {start + idx} (index {idx} "
                        f"of this {steps}-step compiled window) produced "
                        f"non-finite values (set flag 'check_nan_inf' to "
                        f"False to disable)")
                if rec is not None:
                    rec["nan_check"] = "ok"
            else:
                try:
                    self._check_nan_inf(fetch_names, fetches, new_state)
                except FloatingPointError:
                    _M_NAN_FAILS.inc()
                    if rec is not None:
                        rec["nan_check"] = "fail"
                    raise
                if rec is not None:
                    rec["nan_check"] = "ok"
        if return_numpy:
            if async_fetch:
                # overlapped fetch: the device->host copies are issued
                # now (copy_to_host_async) but materialize lazily — the
                # caller reads them after dispatching the next step
                return LazyFetches(fetches, on_error=error_cb)
            fetches = [np.asarray(x) for x in fetches]
        return fetches

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Drive a Dataset (InMemory/Queue, dataset_api.py) through the
        compiled train step (reference: executor.py:846
        ``train_from_dataset``).

        The reference spins thread-per-core device workers consuming a
        C++ data-feed channel; here the host side is a DeviceLoader
        prefetching ``thread``-deep onto the device while the step's XLA
        program runs — the whole-program-compilation analog of the
        Downpour/Hogwild entry point. Returns the number of steps run.
        """
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        from paddle_tpu.reader.pipeline import DeviceLoader

        fetch_list = list(fetch_list or [])
        names = [f.name if isinstance(f, Variable) else str(f)
                 for f in fetch_list]
        info = list(fetch_info or names)
        # thread=0 means "use the dataset's configured thread num"
        # (reference train_from_dataset convention)
        depth = int(thread or 0) or int(
            getattr(dataset, "_thread_num", 0) or 0)
        loader = DeviceLoader(
            dataset.batch_reader(),
            feed_names=list(getattr(dataset, "_use_var_names", []) or []),
            depth=max(2, depth),
        )
        steps = 0
        for feed in loader:
            fetches = self.run(program, feed=feed, fetch_list=fetch_list,
                               scope=scope)
            steps += 1
            if debug and fetch_list and steps % print_period == 0:
                msg = ", ".join(
                    f"{k}={np.asarray(v).ravel()[:4]}"
                    for k, v in zip(info, fetches))
                print(f"[train_from_dataset] step {steps}: {msg}")
        return steps

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Inference twin of ``train_from_dataset`` (reference:
        executor.py ``infer_from_dataset``): identical drive loop — the
        program simply contains no optimizer ops."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    def close(self):
        self._cache.clear()
        # staging follows its owning entries out (see _cache_entry)
        self._staged.clear()

    def release_scope(self, scope) -> int:
        """Drop every compiled entry (and the staged feed windows it
        owns) keyed to ``scope`` — the per-tenant half of close() for
        executors shared by several predictors/serving engines: one
        replica's retirement must not cold-start its neighbors. Returns
        the number of entries released."""
        uid = scope._uid
        victims = [k for k in self._cache if len(k) > 1 and k[1] == uid]
        for k in victims:
            self._cache.pop(k, None)
            for sk in [s for s, e in self._staged.items()
                       if e["owner"] == k]:
                self._staged.pop(sk, None)
        return len(victims)

    @staticmethod
    def _check_nan_inf(fetch_names, fetches, new_state):
        """Per-step NaN/Inf scan of fetches + updated state
        (reference: FLAGS_check_nan_inf scan, operator.cc:950)."""
        bad = []
        for name, v in list(zip(fetch_names, fetches)) + list(
            new_state.items()
        ):
            try:
                if jnp.issubdtype(jnp.result_type(v), jnp.floating) and not bool(
                    jnp.isfinite(v).all()
                ):
                    bad.append(name)
            except TypeError:
                continue
        if bad:
            raise FloatingPointError(
                f"check_nan_inf: non-finite values in {bad} after this "
                f"step (set flag 'check_nan_inf' to False to disable)"
            )

    # --- internals ---

    def _compile(self, program, compiled, feed_names, fetch_names, scope):
        lowered = lowering.lower_block(program, 0, feed_names, fetch_names)
        return self._jit_for(lowered, compiled), lowered

    @staticmethod
    def _jit_for(lowered, compiled):
        """jax.jit wrapper in the executor call convention."""
        in_shardings = out_shardings = None
        if compiled is not None:
            in_shardings, out_shardings = compiled.shardings(lowered)
            if in_shardings is not None:
                # align with fn(state, feeds, key, step)
                repl = in_shardings[2]
                in_shardings = (*in_shardings, repl)
        return lowering.jit_lowered(
            lowered, in_shardings=in_shardings, out_shardings=out_shardings,
            fold_step=True,
        )
