"""Persistent (level-2) compile cache: AOT executables resolved from disk
before tracing.

BENCH r01 measured 98.9 s compile+first-step against 1.6 s for 20
steady-state steps — cold start is ~60x the per-step cost, and it is
paid again on every trainer auto-resume, every elastic-resize re-exec
generation, and every serving-process restart. This module removes that
cost for a repeated program: the executor's in-memory compiled-entry
cache stays level 1, and a ``compile_cache_dir`` adds a level 2 that
serializes the compiled XLA executable itself
(``jax.experimental.serialize_executable``), so a FRESH PROCESS resolves
the entry from disk and reaches step 1 without tracing or compiling.

Key composition — an entry is addressed by a sha256 digest over:

  ==========================  ==============================================
  component                   why it must match
  ==========================  ==============================================
  program fingerprint         ``program_fingerprint()``: canonical content
                              digest of blocks/vars/ops/attrs + amp flag +
                              feed signature + fetch list + SPMD strategy /
                              mesh plan (the single fingerprint also used
                              for the executor L1 key, the static
                              verifier's lint-once cache, and the compile
                              report ``cache_key``)
  state signature             (name, shape, dtype) of every state-in array
                              gathered from the scope — state avals are
                              baked into the executable
  PRNG key aval               the key dtype encodes the ``prng_impl``
  window shape                run_steps: (n_feeds, steps) — ``steps`` is a
                              static argument baked into the executable
  environment token           jax/jaxlib versions, backend, cache format
                              version (process-independent)
  owning-shard topology       local executables: (sorted addressable
                              device ids, kind) — the ids, NOT a count:
                              the serialized executable bakes an XLA
                              device assignment, and two ranks of a
                              distributed world share a count but not
                              ids. Excludes process/world counts, so a
                              resize never cold-starts a process whose
                              device identity is unchanged; SPMD
                              executables: (process index, process
                              count, global device count, kind) — one
                              entry per program shard
  ==========================  ==============================================

Entries are written atomically (stage + fsync + rename — the checkpoint
commit idiom), so a crash mid-write leaves a ``.tmp`` straggler, never a
torn published entry. Loads validate the stored format/env/digest header
AND the deserialized executable's input avals against the expected
arguments; any mismatch, read error or deserialization failure degrades
to a fresh compile — metered, warned, never an abort.

jax's own persistent compilation cache is a separate tier this module
never touches: ``paddle_tpu.jax_cache`` places it.

Cache files are pickles and therefore as trusted as the directory they
live in — point ``compile_cache_dir`` only at directories you own, same
as checkpoints.

Disabled-path contract (same as monitor.py/faults.py): while
``compile_cache_dir`` is unset, the executor hot path costs one cached
module-boolean read here and allocates nothing in this file.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.experimental import serialize_executable as _se

from paddle_tpu import faults as _faults
from paddle_tpu import flags as _flags
from paddle_tpu import monitor as _monitor

# Bump on any incompatible change to the on-disk payload layout; a
# version mismatch is a silent miss, never an error.
# v2: stored executables are compiled WITHOUT input donation — a
# deserialized donating executable corrupts buffer ownership from its
# second call on (jax 0.4.x flaky use-after-free, first surfaced by the
# serving plane's multi-call decode entries); v1 entries must miss.
FORMAT_VERSION = 2

_M_HITS = _monitor.counter(
    "pt_compile_cache_hits_total",
    "persistent compile-cache hits: executables deserialized from disk, "
    "skipping trace + XLA compile entirely")
_M_MISSES = _monitor.counter(
    "pt_compile_cache_misses_total",
    "persistent compile-cache misses (no disk entry, or a format/env/"
    "topology mismatch): a fresh compile follows and repopulates")
_M_ERRORS = _monitor.counter(
    "pt_compile_cache_errors_total",
    "persistent compile-cache failures degraded to a fresh compile, by "
    "stage (spec/load/store)")
_M_LOAD_SECONDS = _monitor.histogram(
    "pt_compile_cache_load_seconds",
    "disk read + executable deserialization time per persistent "
    "compile-cache hit")
_M_EVICTIONS = _monitor.counter(
    "pt_compile_cache_evictions_total",
    "persistent compile-cache entries removed by the size-capped "
    "LRU-by-mtime disk sweep (compile_cache_max_bytes)")

# Chaos sites (faults.py): load tears the published file BEFORE the read
# (corruption-regression drills), store tears the staged file before the
# atomic rename (torn-write drills).
_F_LOAD = _faults.site("ccache.load")
_F_STORE = _faults.site("ccache.store")


# --------------------------------------------------------------------------
# flag plumbing (cached-hot-flag pattern, monitor.py)
# --------------------------------------------------------------------------

_dir = ""


def _sync_dir(v):
    global _dir
    _dir = str(v or "")


_max_bytes = 0


def _sync_max_bytes(v):
    global _max_bytes
    _max_bytes = int(v)


_flags.watch_flag("compile_cache_dir", _sync_dir)
_flags.watch_flag("compile_cache_max_bytes", _sync_max_bytes)


def active() -> bool:
    """One cached-boolean read — the executor's zero-allocation gate."""
    return bool(_dir)


def cache_dir() -> str:
    return _dir


# --------------------------------------------------------------------------
# canonical fingerprint — THE compile-signature identity shared by the
# executor cache key, the static verifier's lint-once cache, and the
# compile-report cache_key (three subsystems that used to hand-roll
# overlapping signatures that could drift)
# --------------------------------------------------------------------------

def strategy_token(strategy) -> tuple:
    """Content fingerprint of a DistributedStrategy. id() would alias a
    fresh strategy to a GC-reused address (the _latest_stacked hazard);
    content keying also lets two equal strategies share cache entries."""
    if strategy is None:
        return ()
    mesh = getattr(strategy, "mesh", None)
    return (
        tuple(sorted((a, int(mesh.shape[a])) for a in mesh.axis_names))
        if mesh is not None else None,
        getattr(strategy, "data_axis", None),
        getattr(strategy, "slice_axis", None),
        getattr(strategy, "context_axis", None),
        getattr(strategy, "table_axis", None),
        getattr(strategy, "expert_axis", None),
        getattr(strategy, "pipe_axis", None),
        getattr(strategy, "pipe_micro", None),
        bool(getattr(strategy, "strict", False)),
        tuple((r.pattern, str(r.spec))
              for r in getattr(strategy, "rules", ())),
    )


def mesh_token(mesh) -> tuple:
    """Mesh descriptor: axis names/sizes + device platform + count.
    Device IDENTITY is deliberately dropped (the checkpoint manifest-v2
    convention) — a same-shaped mesh on other devices is the same plan."""
    if mesh is None:
        return ()
    try:
        devs = np.asarray(mesh.devices)
        plat = getattr(devs.flat[0], "platform", "?")
        return (tuple((a, int(mesh.shape[a])) for a in mesh.axis_names),
                str(plat), int(devs.size))
    except Exception:
        return ("mesh?",)


def compiled_token(compiled) -> tuple:
    """Content token of a CompiledProgram execution plan (replaces the
    ``compiled._uid`` identity that used to sit in executor cache keys:
    two CompiledPrograms wrapping the same program with the same plan now
    share one compiled entry)."""
    if compiled is None:
        return ()
    return (bool(getattr(compiled, "_data_parallel", False)),
            mesh_token(getattr(compiled, "mesh", None)),
            strategy_token(getattr(compiled, "_strategy", None)))


def program_fingerprint(program, feed_sig=(), fetch_names=(),
                        strategy=None, compiled=None, extra=()) -> str:
    """Canonical compile-signature fingerprint: a sha256 hex digest over
    the program CONTENT (``Program.content_digest()`` — blocks, vars,
    ops, attrs; stable across processes), the amp flag, the feed
    signature, the fetch list, and the SPMD strategy / CompiledProgram
    plan content. Two identically-built programs in two different
    processes produce the SAME fingerprint — the property the persistent
    compile cache rests on.

    Returns a ``local-`` prefixed identity digest when the program
    content cannot be canonicalized (exotic attrs); such fingerprints
    still key in-process caches correctly but are never used for disk
    resolution."""
    try:
        content = program.content_digest()
    except Exception:
        content = None
    parts = (
        content,
        bool(getattr(program, "_amp", False)),
        tuple(feed_sig),
        tuple(fetch_names),
        strategy_token(strategy),
        compiled_token(compiled),
        tuple(extra),
    )
    digest = hashlib.sha256(repr(parts).encode()).hexdigest()[:40]
    if content is None:
        return f"local-{program._uid}v{program.version}-{digest[:24]}"
    return digest


# (identity tuple) -> fingerprint memo so the executor's per-call key
# assembly costs one dict read steady-state (content digests are cached
# per program version; this bounds even the tuple-hash + sha256 of the
# signature parts to one computation per distinct signature).
_FP_MEMO: "OrderedDict[tuple, str]" = OrderedDict()
_FP_CAP = 512


def fingerprint_for(ident: tuple, program, compiled=None, strategy=None,
                    feed_sig=(), fetch_names=(), extra=()) -> str:
    """Memoized ``program_fingerprint`` keyed by the caller's cheap
    identity tuple (uids/versions/signatures). The memo makes the
    fingerprint safe on the executor hot path: a warm signature is one
    dict lookup."""
    fp = _FP_MEMO.get(ident)
    if fp is not None:
        return fp
    if strategy is None:
        strategy = getattr(compiled, "_strategy", None)
    fp = program_fingerprint(
        program, feed_sig=feed_sig, fetch_names=fetch_names,
        strategy=strategy, compiled=compiled, extra=extra)
    _FP_MEMO[ident] = fp
    while len(_FP_MEMO) > _FP_CAP:
        _FP_MEMO.popitem(last=False)
    return fp


def env_token() -> tuple:
    """The process-independent half of what an executable bakes in: a
    mismatch on any component means the disk entry is not ours to load.
    The device/process half lives in ``topology_token`` (keyed by the
    OWNING shard, not the global world — the property that lets a
    joining host of a resized world warm-start from a smaller
    generation's entries)."""
    import jaxlib

    return (FORMAT_VERSION, jax.__version__, jaxlib.__version__,
            jax.default_backend())


def topology_token(state_vals=(), mesh=None, extra_devices=()) -> tuple:
    """Owning-shard topology token — the multi-host half of the entry
    key (ISSUE 14: replaces the blanket ``process_count() > 1``
    decline).

    An executable whose referenced devices (state array shardings, the
    strategy mesh) are all ADDRESSABLE by this process is **local**:
    its token is ``("local", sorted addressable device ids, kind)``.
    The serialized executable bakes an XLA device assignment, so it is
    loadable exactly where its device ids are addressable — the ids ARE
    the owning-shard identity (two ranks of a distributed world have
    distinct local ids and therefore distinct entries; the same rank
    across generations, or any single-process world, shares). The token
    deliberately excludes the process count and the global device
    count, so a world RESIZE does not cold-start processes whose device
    identity is unchanged — what lets a generation-N+1 member
    warm-start from generation N's store.

    An executable that spans non-addressable devices is a per-process
    shard of an SPMD program: its token is ``("spmd", process index,
    process count, global device count, kind)`` — the owning shard's
    identity, so rank 3's serialized executable can never resolve as
    rank 5's, and a replacement host joining at index 3 resolves
    exactly its predecessor shard's entry."""
    devs = set(extra_devices)
    for v in state_vals:
        if isinstance(v, jax.Array):
            try:
                devs |= set(v.sharding.device_set)
            except Exception:
                pass
    if mesh is not None:
        try:
            devs |= set(np.asarray(mesh.devices).flat)
        except Exception:
            pass
    try:
        local = set(jax.local_devices())
        kind = str(getattr(next(iter(local)), "device_kind", "?"))
    except Exception:
        local, kind = set(), "?"
    if devs - local:
        try:
            n_global = len(jax.devices())
        except Exception:
            n_global = 0
        return ("spmd", int(jax.process_index()),
                int(jax.process_count()), n_global, kind)
    ids = tuple(sorted(int(getattr(d, "id", -1)) for d in local))
    return ("local", ids, kind)


def _aval(v) -> tuple:
    dt = getattr(v, "dtype", None)
    if dt is None:
        dt = np.asarray(v).dtype
    try:
        # the executable bakes jax's CANONICAL aval: with x64 disabled an
        # int64 host feed lowers as int32, so the expectation must match
        # args_info on that form (extended dtypes, e.g. PRNG keys, pass
        # through canonicalize unchanged)
        dt = jax.dtypes.canonicalize_dtype(dt)
    except Exception:
        pass
    return (tuple(np.shape(v)), str(dt))


# --------------------------------------------------------------------------
# disk entries
# --------------------------------------------------------------------------

class Spec:
    """Everything needed to resolve one disk entry: the digest path, the
    example arguments to AOT-lower against on a miss (and validate avals
    against on a hit), and the lowered-block recipe the executor entry
    carries alongside the callable."""

    __slots__ = ("path", "digest", "lower_args", "static_steps",
                 "program", "feed_names", "fetch_names", "strategy")

    def __init__(self, path, digest, lower_args, static_steps,
                 program, feed_names, fetch_names, strategy=None):
        self.path = path
        self.digest = digest
        self.lower_args = lower_args
        self.static_steps = static_steps
        self.program = program
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.strategy = strategy

    def make_lowered(self):
        """Rebuild the LoweredBlock for a disk-resolved entry. This is
        block ANALYSIS only (state lists, op histogram) — no jax tracing
        happens until a function is actually jitted, which a disk hit
        never does."""
        from paddle_tpu.core import lowering

        return lowering.lower_block(self.program, 0, self.feed_names,
                                    self.fetch_names)


def executor_spec(program, *, feed_vals, fetch_names, scope, base_key,
                  fingerprint, compiled=None, window_steps=None,
                  n_feeds=None, nan_track=False) -> Optional[Spec]:
    """Build the disk-resolution spec for one executor entry, or None
    when the tier is off or this entry cannot be safely serialized
    (multi-host run, non-portable fingerprint, uninitialized state).
    Called only on a level-1 miss, so its cost is irrelevant next to the
    compile it replaces."""
    if not _dir:
        return None
    if fingerprint.startswith("local-"):
        return None  # content not canonical -> not portable across procs
    try:
        from paddle_tpu.core.lowering import analyze_state

        feed_names = sorted(feed_vals)
        state_in, _ = analyze_state(program.blocks[0], feed_names)
        state = {}
        for n in state_in:
            v = scope.find_var(n)
            if v is None:
                return None  # the run itself will raise the real error
            state[n] = v
        state_sig = tuple((n, _aval(v)) for n, v in state.items())
        # the owning-shard topology token rides the digest: local
        # executables share entries across ranks/world sizes, SPMD
        # executables are keyed per process shard (ISSUE 14 — what used
        # to be a blanket multi-host decline)
        topo = topology_token(
            list(state.values()) + list(feed_vals.values()),
            getattr(compiled, "mesh", None))
        digest = hashlib.sha256(repr((
            fingerprint, state_sig, _aval(base_key),
            None if window_steps is None else (int(n_feeds or 0),
                                               int(window_steps)),
            bool(nan_track), env_token(), topo,
        )).encode()).hexdigest()
        if window_steps is None:
            lower_args: tuple = (state, dict(feed_vals), base_key,
                                 np.uint32(0))
        else:
            lower_args = (state, dict(feed_vals), base_key, np.uint32(0),
                          int(window_steps))
        return Spec(
            path=os.path.join(_dir, f"pcc-{digest[:40]}.bin"),
            digest=digest,
            lower_args=lower_args,
            static_steps=None if window_steps is None else int(window_steps),
            program=program,
            feed_names=tuple(feed_names),
            fetch_names=tuple(fetch_names),
            strategy=getattr(compiled, "_strategy", None),
        )
    except Exception as e:
        _M_ERRORS.inc(labels={"stage": "spec"})
        warnings.warn(f"compile-cache spec degraded to fresh compile "
                      f"({type(e).__name__}: {e})", RuntimeWarning)
        return None


def _canon_host_array(v):
    """Match jax.jit's input canonicalization for a host array. The
    eager jit casts non-canonical host inputs (int64 -> int32 with x64
    off) during device_put; a ``jax.stages.Compiled`` does NOT — it was
    compiled for the canonical aval, and handing it the raw 64-bit
    buffer reinterprets the bytes (garbage values, and observed heap
    corruption on jax 0.4.37). Training-state entries never hit this
    (all-f32 params); the serving programs' int64/bool decode state is
    what first tripped it."""
    if isinstance(v, np.ndarray):
        want = jax.dtypes.canonicalize_dtype(v.dtype)
        if want != v.dtype:
            return v.astype(want)
    return v


def _wrap(comp, static_steps: Optional[int]):
    """Wrap an AOT ``jax.stages.Compiled`` in the executor's call
    convention. run_steps entries bake ``steps`` as a static argument, so
    the wrapper drops the trailing count the eager jit would re-dispatch
    on (the executor keys entries by ``steps``, making a mismatch
    impossible). Host inputs are canonicalized exactly as the eager jit
    would (see _canon_host_array)."""
    _canon = jax.tree_util.tree_map
    if static_steps is None:
        def fn(state, feeds, base_key, step):
            return comp(*_canon(_canon_host_array,
                                (state, feeds, base_key, step)))
    else:
        def fn(state, feeds, base_key, start, n_steps):
            return comp(*_canon(_canon_host_array,
                                (state, feeds, base_key, start)))
    # build_compile_report() reuses this executable for cost/memory
    # analysis instead of AOT-compiling a twin
    fn._pt_compiled = comp
    return fn


def _nonstatic_args(spec: Spec) -> tuple:
    if spec.static_steps is None:
        return spec.lower_args
    return spec.lower_args[:-1]


def _validate_args_info(loaded, spec: Spec):
    """The stored digest already encodes every aval, but a hash is not a
    proof: compare the deserialized executable's input avals against the
    arguments this call will pass. Raises on any drift."""
    got = jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), loaded.args_info)
    exp = jax.tree_util.tree_map(_aval, (_nonstatic_args(spec), {}))
    if got != exp:
        raise ValueError(
            f"cached executable avals {got!r} != expected {exp!r}")


def load(spec: Spec):
    """Resolve ``spec`` from disk. Returns ``(entry_fn, load_ms)`` on a
    hit, None on a miss; counts hits/misses/errors and load seconds.
    Corruption, header mismatch or deserialization failure degrades to a
    miss with a metered error — never raises."""
    t0 = time.perf_counter()
    try:
        if not os.path.exists(spec.path):
            _M_MISSES.inc()
            return None
        _F_LOAD.hit(path=spec.path)
        with open(spec.path, "rb") as f:
            payload = pickle.load(f)
        if (payload.get("format") != FORMAT_VERSION
                or payload.get("env") != env_token()
                or payload.get("digest") != spec.digest):
            # another format/jax/topology wrote this name: silent miss
            _M_MISSES.inc()
            return None
        loaded = _se.deserialize_and_load(
            payload["payload"], payload["in_tree"], payload["out_tree"])
        _validate_args_info(loaded, spec)
        fn = _wrap(loaded, spec.static_steps)
        dt = time.perf_counter() - t0
        _M_HITS.inc()
        _M_LOAD_SECONDS.observe(dt)
        try:
            # LRU touch: the size-capped GC sweep evicts by mtime, so a
            # hit must refresh it or hot entries age like cold ones
            os.utime(spec.path)
        except OSError:
            pass
        return fn, dt * 1e3
    except Exception as e:
        _M_ERRORS.inc(labels={"stage": "load"})
        warnings.warn(
            f"compile-cache entry {os.path.basename(spec.path)} unusable "
            f"({type(e).__name__}: {e}); recompiling", RuntimeWarning)
        return None


def store(spec: Spec, comp) -> bool:
    """Serialize ``comp`` and publish it atomically (stage + fsync +
    rename — the checkpoint commit idiom: a crash leaves a ``.tmp``
    straggler, never a torn published entry). Best-effort: failure
    counts an error and the in-memory entry proceeds unaffected."""
    tmp = None
    try:
        ser, in_tree, out_tree = _se.serialize(comp)
        payload = {
            "format": FORMAT_VERSION,
            "env": env_token(),
            "digest": spec.digest,
            "payload": ser,
            "in_tree": in_tree,
            "out_tree": out_tree,
            "meta": {
                "ts": time.time(),
                "program_uid": int(spec.program._uid),
                "static_steps": spec.static_steps,
                "n_bytes": len(ser),
            },
        }
        os.makedirs(_dir, exist_ok=True)
        # pid alone is not unique: concurrent serving replicas (fleet
        # supervisor loop threads) store the same digest from one
        # process, and a shared tmp name turns the second rename into
        # a FileNotFoundError store failure
        tmp = spec.path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
            f.flush()
            os.fsync(f.fileno())
        _F_STORE.hit(path=tmp)
        os.replace(tmp, spec.path)
        gc()  # keep the disk tier inside compile_cache_max_bytes
        return True
    except Exception as e:
        _M_ERRORS.inc(labels={"stage": "store"})
        warnings.warn(f"compile-cache store skipped "
                      f"({type(e).__name__}: {e})", RuntimeWarning)
        if tmp is not None:
            try:
                os.remove(tmp)
            except OSError:
                pass
        return False


def aot_build(spec: Spec, jitfn):
    """Fresh-compile path with the disk tier on: AOT-compile ``jitfn``
    against the spec's example arguments (ONE trace + ONE XLA compile —
    the eager jit is never invoked), persist the executable, and return
    the wrapped entry callable. Returns None when AOT compilation itself
    fails; the caller keeps the eager jit and nothing is stored.

    ``jitfn`` must be the DONATION-FREE twin (executor._jit_for
    donate_state=False): a donating executable round-tripped through
    serialize/deserialize mishandles buffer ownership from its second
    call on (jax 0.4.x — flaky use-after-free observed as garbage KV
    caches and glibc heap aborts in the serving decode loop). The cost
    is one extra in-flight copy of the state in disk-tier processes;
    the value contract is what the tier exists for."""
    try:
        from paddle_tpu.core import interp as _interp

        # trace under the strategy's SPMD context, exactly like the
        # eager jit's first call (executor.run) and
        # build_compile_report: collective ops (DGC exchange, MoE
        # all_to_all) read it at TRACE time — without it they silently
        # lower their non-collective fallback, and the wrong executable
        # would be both executed and persisted
        with _interp.spmd_ctx_scope(spec.strategy):
            comp = jitfn.lower(*spec.lower_args).compile()
    except Exception as e:
        _M_ERRORS.inc(labels={"stage": "store"})
        warnings.warn(f"compile-cache AOT build degraded to eager jit "
                      f"({type(e).__name__}: {e})", RuntimeWarning)
        return None
    store(spec, comp)  # best-effort; an unstorable executable still runs
    return _wrap(comp, spec.static_steps)


# stage-file stragglers older than this are crash leftovers (the
# publishing process fsync+renames within seconds); the GC sweep
# reclaims them alongside over-budget entries
_TMP_REAP_AGE_S = 3600.0


def gc(max_bytes: Optional[int] = None) -> int:
    """Size-capped LRU-by-mtime sweep of the persistent cache dir
    (closes the 'unbounded today' remainder of the disk tier): evict
    published ``pcc-*.bin`` entries oldest-mtime-first until the total
    fits ``max_bytes`` (default: the ``compile_cache_max_bytes`` flag;
    0 = unbounded, no sweep), always keeping the newest entry even when
    it alone exceeds the cap (evicting everything would defeat the
    cache). Loads refresh mtime, so eviction order is least-recently-
    USED. Also reaps ``.tmp.*`` stage stragglers older than an hour
    (crashed publishers). Returns entries evicted, metered by
    ``pt_compile_cache_evictions_total``; any listing/unlink error
    degrades silently — GC must never fail a store."""
    cap = _max_bytes if max_bytes is None else int(max_bytes)
    if not _dir or cap <= 0:
        return 0
    evicted = 0
    try:
        entries = []
        now = time.time()
        with os.scandir(_dir) as it:
            for de in it:
                if not de.is_file():
                    continue
                if ".tmp." in de.name:
                    try:
                        st = de.stat()
                        if now - st.st_mtime > _TMP_REAP_AGE_S:
                            os.remove(de.path)
                    except OSError:
                        pass
                    continue
                if de.name.startswith("pcc-") and de.name.endswith(".bin"):
                    try:
                        st = de.stat()
                    except OSError:
                        continue
                    entries.append((st.st_mtime, st.st_size, de.path))
        total = sum(size for _, size, _ in entries)
        entries.sort()  # oldest mtime first = coldest first
        while total > cap and len(entries) > 1:
            mtime, size, path = entries.pop(0)
            try:
                os.remove(path)
            except FileNotFoundError:
                # a concurrent GC reclaimed it — not evicted by us, but
                # the space IS gone: without the subtraction this
                # process over-evicts still-hot entries past the cap
                total -= size
                continue
            except OSError:
                continue
            total -= size
            evicted += 1
        if evicted:
            _M_EVICTIONS.inc(evicted)
    except OSError:
        pass
    return evicted


def stats() -> Dict[str, Any]:
    """Operator-facing snapshot (debugging, tests)."""
    return {
        "dir": _dir,
        "hits": _M_HITS.value(),
        "misses": _M_MISSES.value(),
        "evictions": _M_EVICTIONS.value(),
        "errors": {stage: _M_ERRORS.value(labels={"stage": stage})
                   for stage in ("spec", "load", "store")},
    }
