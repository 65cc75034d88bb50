"""Sharded, optionally-async, crash-consistent, topology-independent
checkpointing.

The TPU-native replacement for the reference's distributed checkpointing,
where parameters sliced across pservers are saved per-server and re-merged
on load (reference: io.py:282 ``_save_distributed_persistables``, slice
concat io.py:315-360; trainer serial-numbered checkpoint dirs
contrib/trainer.py:100,580). Here the unit is a sharded ``jax.Array``:

- each PROCESS writes only its addressable shards (one ``.npz`` per
  process) plus a manifest fragment of {name -> GLOBAL shape, dtype,
  sharding descriptor, shard index ranges, per-array crc32}, so
  multi-host saves never gather the model onto one host;
- restore reassembles the global value from whatever shard files are
  present — a PARTIAL subset is accepted whenever the surviving shards
  still cover every element (replica coverage), and a subset that does
  not raises a structured ``IOError`` naming the absent shard files —
  and can re-shard the result straight onto the restoring program's
  ``in_shardings`` (``reshard`` / the ``shardings=`` parameter), so a
  checkpoint saved on a 2x4 mesh restores bit-exact onto 1x8, onto a
  shrunk 4-process world, or onto a single host. The manifest carries
  everything needed (format v2: global shape + dtype + sharding spec per
  array); nothing about restore depends on the saving topology;
- ``async_save=True`` issues every device->host copy up front
  (``copy_to_host_async``, overlapping the transfers with each other),
  materializes the host snapshot in the caller's thread — timed into
  ``pt_ckpt_snapshot_seconds`` — and runs checksum + serialize + commit
  on a background thread, overlapping them with the next training steps
  (the orbax async-checkpoint pattern). Snapshotting in the caller is
  what makes the overlap SAFE: the next step may donate the parameter
  buffers, so device arrays must not be read after return.

Crash-consistent commit protocol (the orbax commit-marker pattern)::

    write  checkpoint_<N>.tmp/shards_<pid>.npz      (fsync)
    write  checkpoint_<N>.tmp/manifest.json.<pid>   (fsync)
    -- multi-host: every writer p>0 kv-acks; process 0 collects the
       acks (retry.py-backed fleet KV, deadline-budgeted) BEFORE the
       marker, and kv-publishes after the pointer flip --
    write  checkpoint_<N>.tmp/COMMIT                (fsync, process 0)
    rename checkpoint_<N>.tmp -> checkpoint_<N>     (atomic publish)
    write  latest.tmp; rename -> latest             (atomic pointer)

A crash at ANY point leaves either a ``.tmp`` staging dir (ignored by
``available_steps``/``latest_step``) or a fully committed serial: resume
can never observe a half-written checkpoint. ``validate_checkpoint``
additionally proves integrity (COMMIT marker, replica-coverage of every
array by the shards present, crc32 match), and ``latest_step`` skips
invalid serials — counting them into ``pt_ckpt_invalid_skipped_total`` —
falling back to the newest valid one. Multi-host commits ride the
``FleetCommitCoordinator`` barrier above (auto-engaged when the fleet is
initialized), closing the late-writer race the single-host protocol
could not see.
"""

from __future__ import annotations

import atexit
import json
import os
import shutil
import threading
import time as _time
import warnings
import zlib
from typing import Dict, List, Optional

import jax
import numpy as np

from paddle_tpu import faults as _faults
from paddle_tpu import monitor as _monitor
from paddle_tpu import retry as _retry
from paddle_tpu.parallel import mesh as _mesh

_MANIFEST = "manifest.json"
_LATEST = "latest"
_COMMIT = "COMMIT"
_STAGING_SUFFIX = ".tmp"
# manifest/COMMIT format: v2 adds the per-array sharding descriptor and
# the partial-subset restore contract (v1 checkpoints load unchanged)
_FORMAT = 2

_M_COMMIT_S = _monitor.histogram(
    "pt_ckpt_commit_seconds",
    "checkpoint commit-protocol duration (multi-host ack collection + "
    "COMMIT marker -> published latest pointer)")
_M_SNAPSHOT_S = _monitor.histogram(
    "pt_ckpt_snapshot_seconds",
    "device->host checkpoint snapshot duration (all copies issued "
    "asynchronously up front, then materialized)")
_M_INVALID_SKIPS = _monitor.counter(
    "pt_ckpt_invalid_skipped_total",
    "uncommitted/corrupt checkpoint serials skipped while resolving the "
    "newest valid one")
_M_ASYNC_ERRS = _monitor.counter(
    "pt_ckpt_async_errors_total",
    "background checkpoint-save failures surfaced outside wait()")
_M_PARTIAL = _monitor.counter(
    "pt_ckpt_partial_restores_total",
    "arrays reassembled from a partial shard-file subset whose surviving "
    "shards still covered every element")
_M_SLOT_REKEYS = _monitor.counter(
    "pt_ckpt_slot_rekeys_total",
    "optimizer slot-state entries re-keyed onto a differently-built "
    "restoring program's slot names via the manifest's (param, kind) "
    "descriptors (reshard_optimizer_state)")

_F_WRITE = _faults.site("ckpt.write_shards")
_F_COMMIT = _faults.site("ckpt.commit")
_F_READ = _faults.site("ckpt.read")


def _fsync_dir(path: str):
    """Durably record a rename/create in its parent directory."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass  # some filesystems refuse dir fsync; rename is still atomic
    finally:
        os.close(fd)


def _fsync_file(path: str):
    """Flush an already-written file's data to disk (read-only open —
    shared by the inference-export publish path)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _checksum(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


def _shard_slices(arr) -> List[dict]:
    """Addressable shards of a jax.Array as JSON-able index metadata."""
    out = []
    for sh in arr.addressable_shards:
        idx = []
        for sl, dim in zip(sh.index, arr.shape):
            start = 0 if sl.start is None else int(sl.start)
            stop = dim if sl.stop is None else int(sl.stop)
            idx.append([start, stop])
        out.append({"index": idx, "replica_id": int(sh.replica_id)})
    return out


def _fkey_file(fkey: str) -> str:
    """Shard file that holds a manifest shard key (``name::pid::i``)."""
    try:
        pid = fkey.rsplit("::", 2)[1]
        return f"shards_{pid}.npz"
    except IndexError:
        return "shards_0.npz"


def _copy_async(arr):
    """Start a device->host transfer without blocking; materializing the
    same array later finds the bytes already (or soon) resident."""
    if isinstance(arr, jax.Array):  # host numpy: nothing to start
        arr.copy_to_host_async()


# ---------------------------------------------------------------------------
# multi-host commit coordination (the barrier the v1 docstring admitted
# it was missing)
# ---------------------------------------------------------------------------

# one logical save = one coordination round; the counter gives repeated
# saves of the SAME serial fresh KV keys (the same SPMD call-sequence
# discipline fleet.barrier_or_dead uses for its epoch numbers)
_COORD_SEQ_LOCK = threading.Lock()
_coord_seq = 0


def _next_coord_seq() -> int:
    global _coord_seq
    with _COORD_SEQ_LOCK:
        _coord_seq += 1
        return _coord_seq


class FleetCommitCoordinator:
    """COMMIT/publish coordination over the fleet KV store: writers with
    rank > 0 ack once their shard + manifest files are durable, process 0
    collects every ack BEFORE writing the COMMIT marker, and publishes a
    KV key after the pointer flip so non-zero writers return only once
    the serial is observable. All KV traffic rides fleet.put/get, i.e.
    the unified retry.py backoff + deadline policies; a dead writer
    surfaces as a TimeoutError on process 0 (save fails, staging dir
    stays staged, resume falls back to the previous valid serial).
    """

    def __init__(self, fleet=None, timeout_ms: Optional[int] = None):
        if fleet is None:
            from paddle_tpu.incubate.fleet import fleet as _fleet

            fleet = _fleet
        self._fleet = fleet
        self.rank = fleet.worker_index()
        self.world = fleet.worker_num()
        if timeout_ms is None:
            from paddle_tpu import flags as _flags

            timeout_ms = _flags.get_flag("rpc_deadline_ms")
        self._timeout_ms = int(timeout_ms)

    def _key(self, kind: str, seq: int, step: int, rank=None) -> str:
        tail = "" if rank is None else f"/{rank}"
        return f"ckpt/{kind}/{seq}:{step}{tail}"

    def ack_write(self, seq: int, step: int):
        self._fleet.put(self._key("ack", seq, step, self.rank), b"1")

    def wait_writers(self, seq: int, step: int):
        """Process 0: block until EVERY non-zero writer acked, under one
        shared deadline budget across the sequential gets."""
        dl = _retry.Deadline(self._timeout_ms / 1000.0)
        for r in range(1, self.world):
            self._fleet.get(self._key("ack", seq, step, r),
                            timeout_ms=max(1, dl.remaining_ms()))

    def publish(self, seq: int, step: int):
        self._fleet.put(self._key("pub", seq, step), b"1")

    def wait_published(self, seq: int, step: int):
        self._fleet.get(self._key("pub", seq, step),
                        timeout_ms=self._timeout_ms)


def _resolve_coordinator(coordinator):
    """``"auto"`` -> a FleetCommitCoordinator when the fleet is up with
    >1 workers, else None (single-host protocol); explicit
    None/coordinator objects pass through."""
    if coordinator != "auto":
        return coordinator
    try:
        from paddle_tpu.incubate.fleet import fleet as _fleet

        if _fleet._initialized and _fleet.worker_num() > 1:
            return FleetCommitCoordinator(_fleet)
    except Exception:  # pragma: no cover - fleet plane absent/broken
        pass
    return None


# ---------------------------------------------------------------------------
# async handles: a failed background save must never vanish
# ---------------------------------------------------------------------------

_HANDLES_LOCK = threading.Lock()
_async_handles: List["_AsyncHandle"] = []


class _AsyncHandle:
    __slots__ = ("_thread", "error", "step", "_surfaced")

    def __init__(self, step: int):
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        self.step = step
        self._surfaced = False

    def done(self) -> bool:
        # ident is None until the thread starts — and is_alive() is
        # False then too, so without the ident check a reap racing the
        # handle's registration would drop it (and its eventual error)
        t = self._thread
        return t is not None and t.ident is not None and not t.is_alive()

    def wait(self):
        """Join the background write; raises its error. Idempotent —
        safe to call any number of times (each call re-raises a stored
        error rather than losing it)."""
        t = self._thread
        if t is not None:
            t.join()
        self._surfaced = True
        if self.error is not None:
            raise self.error


def _reap_async(final: bool = False):
    """Surface errors of finished handles nobody ``wait()``ed (called at
    every save and at exit, so a failed background save is loud at most
    one save later). ``final`` joins still-running writers first."""
    with _HANDLES_LOCK:
        handles = list(_async_handles)
    for h in handles:
        if final and h._thread is not None:
            h._thread.join(timeout=30.0)
        if not h.done():
            continue
        with _HANDLES_LOCK:
            if h in _async_handles:
                _async_handles.remove(h)
        if h.error is not None and not h._surfaced:
            h._surfaced = True
            _M_ASYNC_ERRS.inc()
            warnings.warn(
                f"async checkpoint save (step {h.step}) failed and was "
                f"never wait()ed: {h.error!r}", RuntimeWarning)


atexit.register(_reap_async, final=True)


# ---------------------------------------------------------------------------
# save
# ---------------------------------------------------------------------------


def save_checkpoint(
    dirname: str,
    state: Dict[str, object],
    step: int = 0,
    async_save: bool = False,
    coordinator="auto",
    process_index: Optional[int] = None,
    slots: Optional[Dict[str, dict]] = None,
):
    """Write ``state`` (name -> array) to ``dirname/checkpoint_<step>``
    via the staging-dir commit protocol (module docstring).

    Sharded arrays: this process writes its addressable, replica-0 shards
    and records the GLOBAL shape/dtype/sharding in its manifest fragment.
    Host numpy / replicated values: only process 0 writes. Multi-host,
    the COMMIT/publish is coordinated through ``coordinator`` ("auto" =
    a FleetCommitCoordinator when the fleet is initialized; pass None to
    force the uncoordinated single-host protocol). ``process_index``
    overrides the shard-file naming rank (defaults to
    ``jax.process_index()``; the commit-barrier tests simulate a world
    with it). Returns an ``_AsyncHandle`` when ``async_save`` (call
    ``.wait()`` before relying on the files), else None — with
    ``async_save`` only the device->host snapshot happens here; checksum,
    serialization and the commit run on a background thread.

    ``slots`` ({var name -> {"param": ..., "slot": ...}}, e.g. an
    ``Optimizer.slot_descriptor()``) records the optimizer slot-state
    descriptor on each covered manifest entry, so a restore into a
    DIFFERENTLY-BUILT program (per-stage pipeline layouts, drifted
    unique-name counters) can re-key the state through
    ``reshard_optimizer_state`` instead of silently dropping it.
    """
    _reap_async()
    ckpt_dir = os.path.join(dirname, f"checkpoint_{step}")
    stage_dir = ckpt_dir + _STAGING_SUFFIX
    coord = _resolve_coordinator(coordinator)
    if process_index is not None:
        pid = int(process_index)
    elif coord is not None:
        # the writer identity that names shard files / manifest
        # fragments: the FLEET rank when a commit coordinator is
        # engaged. Identical to jax.process_index() in a jax.distributed
        # fleet, but in a coordination-only fleet (PT_COORD_ONLY) every
        # rank's jax process index is 0 — four writers would clobber one
        # shards_0.npz mid-commit
        pid = coord.rank
    else:
        pid = jax.process_index()
    rank = coord.rank if coord is not None else pid
    seq = _next_coord_seq() if coord is not None else 0

    # Pass 1: issue EVERY device->host copy before materializing any —
    # the transfers overlap each other instead of round-tripping one by
    # one (the orbax async-snapshot shape).
    manifest: Dict[str, dict] = {}
    snap: List[tuple] = []  # (file key, array ref) pending materialize
    for name, v in state.items():
        key = name.replace("/", "__")
        if isinstance(v, jax.Array) and len(v.sharding.device_set) > 1:
            entry = {
                "shape": list(v.shape),
                "dtype": str(v.dtype),
                "sharded": True,
                "shards": {},
                "checksums": {},
                "sharding": _mesh.sharding_descriptor(v.sharding),
            }
            slices = _shard_slices(v)
            for i, sh in enumerate(v.addressable_shards):
                if sh.replica_id != 0:
                    continue  # one copy of each logical shard is enough
                fkey = f"{key}::{pid}::{i}"
                _copy_async(sh.data)
                snap.append((fkey, sh.data))
                entry["shards"][fkey] = slices[i]["index"]
            if slots and name in slots:
                entry["slot"] = dict(slots[name])
            manifest[name] = entry
        elif rank == 0:
            if isinstance(v, jax.Array):
                _copy_async(v)
            snap.append((key, v))
            manifest[name] = {
                "sharded": False,
                "file_key": key,
                "sharding": _mesh.sharding_descriptor(
                    getattr(v, "sharding", None)),
            }
            if slots and name in slots:
                manifest[name]["slot"] = dict(slots[name])

    # Pass 2: materialize the host snapshot IN THE CALLER'S THREAD — the
    # next training step may donate these buffers, so device arrays must
    # never be read after save_checkpoint returns.
    t_snap = _time.perf_counter()
    payload: Dict[str, np.ndarray] = {}
    for k, ref in snap:
        host = np.asarray(ref)
        # On the CPU backend np.asarray of a jax.Array is a ZERO-COPY
        # view of the device buffer; an async snapshot must own its
        # bytes or the next training step mutates the payload under
        # the background writer (reused/donated buffers -> checksums
        # recorded over different values than the ones serialized).
        if async_save and not host.flags.owndata:
            host = np.array(host, copy=True)
        payload[k] = host
    _M_SNAPSHOT_S.observe(_time.perf_counter() - t_snap)
    for name, entry in manifest.items():
        if not entry["sharded"]:
            entry["shape"] = list(payload[entry["file_key"]].shape)
            entry["dtype"] = str(payload[entry["file_key"]].dtype)

    def _write():
        # checksums are serialize-side work: under async_save they run
        # here, off-thread, over the already-host-resident snapshot
        for entry in manifest.values():
            if entry["sharded"]:
                entry["checksums"] = {
                    k: _checksum(payload[k]) for k in entry["shards"]}
            else:
                entry["checksum"] = _checksum(payload[entry["file_key"]])
        # uncoordinated multi-host legacy fallback: a non-zero process
        # arriving after process 0 already committed lands its files
        # inside the published dir. With a coordinator this cannot
        # happen — process 0 renames only after every ack.
        target = stage_dir
        if coord is None and rank != 0 and os.path.isdir(ckpt_dir):
            target = ckpt_dir
        os.makedirs(target, exist_ok=True)
        shard_path = os.path.join(target, f"shards_{pid}.npz")
        with open(shard_path, "wb") as f:
            np.savez(f, **payload)
            f.flush()
            os.fsync(f.fileno())
        # chaos hook: raise here = crash after the (possibly partial)
        # shard write, before commit; truncate = torn shard file
        _F_WRITE.hit(path=shard_path)
        # every process writes its manifest fragment; fragments merge on
        # load (shard keys are globally unique)
        with open(os.path.join(target, f"{_MANIFEST}.{pid}"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if coord is not None and rank != 0:
            # files durable -> ack; return only once process 0 made the
            # serial observable (so callers may prune/validate after)
            coord.ack_write(seq, step)
            coord.wait_published(seq, step)
            return
        if rank == 0:
            t0 = _time.perf_counter()
            if coord is not None:
                # the commit barrier: EVERY writer's files are durable
                # before the marker that declares the dir complete
                coord.wait_writers(seq, step)
            _F_COMMIT.hit()
            with open(os.path.join(target, _COMMIT), "w") as f:
                json.dump({"step": step, "format": _FORMAT}, f)
                f.flush()
                os.fsync(f.fileno())
            if target is stage_dir:
                old_dir = ckpt_dir + ".old" + _STAGING_SUFFIX
                # Re-save of the same serial: park the committed old
                # copy aside instead of rmtree-before-replace — a crash
                # in this window must not lose the only copy
                # (_recover_displaced renames it back on discovery).
                # Retried once because a concurrent reader's recovery
                # can recreate ckpt_dir between the two renames; the
                # new save must win, not fail with ENOTEMPTY.
                for attempt in range(2):
                    if os.path.isdir(ckpt_dir):
                        shutil.rmtree(old_dir, ignore_errors=True)
                        os.rename(ckpt_dir, old_dir)
                    try:
                        os.replace(stage_dir, ckpt_dir)
                        break
                    except OSError:
                        if attempt:
                            raise
                shutil.rmtree(old_dir, ignore_errors=True)
            _fsync_dir(dirname)
            latest_tmp = os.path.join(dirname, _LATEST + _STAGING_SUFFIX)
            with open(latest_tmp, "w") as f:
                f.write(str(step))
                f.flush()
                os.fsync(f.fileno())
            os.replace(latest_tmp, os.path.join(dirname, _LATEST))
            _fsync_dir(dirname)
            _M_COMMIT_S.observe(_time.perf_counter() - t0)
            if coord is not None:
                coord.publish(seq, step)
            _sweep_stale_staging(dirname, step)

    if async_save:
        handle = _AsyncHandle(step)

        def _run():
            try:
                _write()
            except BaseException as e:  # surfaced by wait() / next reap
                handle.error = e

        handle._thread = threading.Thread(target=_run, daemon=True)
        with _HANDLES_LOCK:
            _async_handles.append(handle)
        handle._thread.start()
        return handle
    _write()
    return None


# ---------------------------------------------------------------------------
# discovery + validation
# ---------------------------------------------------------------------------


def _sweep_stale_staging(dirname: str, committed_step: int):
    """Garbage-collect `.tmp` staging dirs left by CRASHED saves of
    older serials (a crashed save of THIS serial was replaced above)
    and `.old.tmp` parked copies whose serial exists again. Staging
    dirs of in-flight async saves are left alone."""
    import re

    with _HANDLES_LOCK:
        live = {h.step for h in _async_handles if not h.done()}
    try:
        entries = os.listdir(dirname)
    except OSError:
        return
    for d in entries:
        m = re.match(r"checkpoint_(\d+)\.tmp$", d)
        if m and int(m.group(1)) < committed_step \
                and int(m.group(1)) not in live:
            shutil.rmtree(os.path.join(dirname, d), ignore_errors=True)
            continue
        m = re.match(r"checkpoint_(\d+)\.old\.tmp$", d)
        if m and os.path.isdir(
                os.path.join(dirname, f"checkpoint_{m.group(1)}")):
            shutil.rmtree(os.path.join(dirname, d), ignore_errors=True)


def _recover_displaced(dirname: str):
    """Crash recovery for the re-save window: a serial parked at
    ``checkpoint_<n>.old.tmp`` whose main dir is missing was displaced
    by a save that died before publishing — rename the committed copy
    back so discovery sees it again."""
    import re

    try:
        entries = os.listdir(dirname)
    except OSError:
        return
    for d in entries:
        m = re.match(r"checkpoint_(\d+)\.old\.tmp$", d)
        if m:
            main = os.path.join(dirname, f"checkpoint_{m.group(1)}")
            if not os.path.isdir(main):
                try:
                    os.rename(os.path.join(dirname, d), main)
                except OSError:
                    pass


def _pointer_step(dirname: str) -> Optional[int]:
    try:
        with open(os.path.join(dirname, _LATEST)) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


def available_steps(dirname: str) -> List[int]:
    import re

    out = []
    try:
        for d in os.listdir(dirname):
            m = re.match(r"checkpoint_(\d+)$", d)  # excludes .tmp staging
            if m:
                out.append(int(m.group(1)))
    except OSError:
        pass
    return sorted(out)


def validate_checkpoint(dirname: str, step: int,
                        verify_checksums: bool = True) -> bool:
    """True iff ``checkpoint_<step>`` is committed and internally
    consistent: COMMIT marker present and parseable, manifest fragments
    parse, the shards present in the shard files COVER every element of
    every array (a missing shard file is tolerated exactly when replica
    coverage still reassembles the value — the same partial-subset rule
    ``load_checkpoint`` applies), and (by default) every present array's
    crc32 matches its manifest record.

    Legacy tolerance: dirs written BEFORE the commit protocol carry no
    COMMIT marker — they are accepted when structurally complete (the
    new protocol never leaves a markerless final-named dir, so a
    missing marker can only mean pre-plane format; a markerless dir
    torn by an old-style crash still fails the structural checks)."""
    ckpt_dir = os.path.join(dirname, f"checkpoint_{step}")
    try:
        marker = os.path.join(ckpt_dir, _COMMIT)
        if os.path.exists(marker):
            with open(marker) as f:
                json.load(f)
        elif not os.path.isdir(ckpt_dir):
            return False
        manifest, payload = _read_raw(ckpt_dir,
                                      load_payload=verify_checksums)
        if not manifest:
            return False
        for name, entry in manifest.items():
            if entry.get("sharded"):
                present = [k for k in entry["shards"] if k in payload]
                sums = entry.get("checksums", {})
                if set(present) != set(entry["shards"]) and \
                        not _covers(entry, present):
                    return False
            else:
                present = ([entry["file_key"]]
                           if entry["file_key"] in payload else [])
                if not present:
                    return False
                sums = {entry["file_key"]: entry.get("checksum")}
            for k in present:
                want = sums.get(k) if verify_checksums else None
                if want is not None and _checksum(payload[k]) != want:
                    return False
        return True
    except Exception:  # noqa: BLE001 — any torn-file failure = invalid
        return False


def _covers(entry: dict, present: List[str]) -> bool:
    """Do the PRESENT shards of a manifest entry cover every element?"""
    seen = np.zeros(entry["shape"], dtype=bool)
    for fkey in present:
        seen[tuple(slice(a, b) for a, b in entry["shards"][fkey])] = True
    return bool(seen.all())


def latest_step(dirname: str,
                verify_checksums: bool = True) -> Optional[int]:
    """Newest VALID committed serial, scanning the serial dirs on disk
    newest-first — NOT the ``latest`` pointer, which can be one step
    stale (a crash between the dir rename and the pointer update leaves
    a fully committed serial the pointer doesn't name yet; the pointer
    file remains as a cheap human-readable hint). Serials that fail
    validation count into ``pt_ckpt_invalid_skipped_total`` (one count
    per skip EVENT, not per distinct serial) and are skipped.

    COST: the default full verification reads every candidate's arrays
    to prove their crc32s — the honest "is this resumable" answer. Pass
    ``verify_checksums=False`` for a cheap structural probe (npz name
    indexes only), or use ``load_latest`` when the values are needed
    anyway (single read)."""
    _recover_displaced(dirname)
    for s in reversed(available_steps(dirname)):
        if validate_checkpoint(dirname, s, verify_checksums):
            return s
        _M_INVALID_SKIPS.inc()
    return None


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------


def load_latest(dirname: str, shardings: Optional[dict] = None):
    """``(step, {name -> array})`` of the newest loadable serial, or
    None. Single-pass: each candidate (newest first) is loaded
    directly — ``_load_one`` verifies shard coverage and crc32 in the
    same read, so resume never reads a multi-GB checkpoint twice.
    Markerless pre-plane dirs load like any other (the structural
    checks reject torn ones; see validate_checkpoint). Unloadable
    serials count into ``pt_ckpt_invalid_skipped_total``.
    ``shardings`` re-shards the result on load (see ``reshard``)."""
    _recover_displaced(dirname)
    for s in reversed(available_steps(dirname)):
        try:
            values = _load_one(dirname, s)
        except Exception:  # noqa: BLE001 — torn/corrupt: try the next
            _M_INVALID_SKIPS.inc()
            continue
        if shardings:
            values = reshard(values, shardings)
        return s, values
    return None


def load_checkpoint(dirname: str, step: Optional[int] = None,
                    shardings: Optional[dict] = None) -> Dict[str, object]:
    """Reassemble {name -> full array} from the shard files of
    ``checkpoint_<step>`` (default: the newest VALID serial —
    uncommitted or corrupt newer ones are skipped, so a crash mid-save
    falls back to the previous committed checkpoint). The result is
    independent of the topology that SAVED it: any per-process shard
    layout reassembles, including a partial file subset when replica
    coverage is complete. ``shardings`` ({name -> jax.sharding.Sharding})
    re-shards named arrays onto the restoring program's layout in the
    same call (``reshard``); everything else stays host numpy, which the
    executor's ``in_shardings`` place at the next run."""
    if step is not None:
        values = _load_one(dirname, step)
        return reshard(values, shardings) if shardings else values
    loaded = load_latest(dirname, shardings=shardings)
    if loaded is None:
        if _pointer_step(dirname) is None and not available_steps(dirname):
            raise FileNotFoundError(f"no checkpoint in {dirname}")
        raise IOError(
            f"no valid committed checkpoint in {dirname} "
            f"(serials on disk: {available_steps(dirname)})")
    return loaded[1]


def reshard(values: Dict[str, object], shardings: dict) -> Dict[str, object]:
    """Place restored host arrays onto target shardings — the
    reshard-on-load half of mesh portability. ``shardings`` maps names
    to ``jax.sharding.Sharding``s (e.g. a DistributedStrategy's
    ``sharding_for`` outputs, i.e. the restoring program's
    ``in_shardings``); names it does not cover stay host numpy. Each
    covered array is built shard-by-shard from the reassembled host
    value (``make_array_from_callback``), so every device gets exactly
    its slice — no whole-array broadcast — and the bytes are bit-exact
    regardless of the mesh the checkpoint was saved on."""
    out: Dict[str, object] = {}
    for n, v in values.items():
        sh = shardings.get(n)
        if sh is None:
            out[n] = v
            continue
        host = np.asarray(v)
        out[n] = jax.make_array_from_callback(
            host.shape, sh, lambda idx, _h=host: _h[idx])
    return out


def manifest_slots(dirname: str, step: int) -> Dict[str, dict]:
    """{var name -> {"param": ..., "slot": ...}} recorded by
    ``save_checkpoint(slots=)`` for ``checkpoint_<step>``, merged across
    every process's manifest fragment. Manifest-only read (no array
    data) — the resume path calls this right after ``load_latest`` to
    decide whether slot re-keying applies. Empty for checkpoints saved
    without descriptors (pre-ISSUE-14 or slot-less saves)."""
    ckpt_dir = os.path.join(dirname, f"checkpoint_{step}")
    out: Dict[str, dict] = {}
    for fn in sorted(os.listdir(ckpt_dir)):
        if fn.startswith(_MANIFEST):
            path = os.path.join(ckpt_dir, fn)
            _F_READ.hit(path=path)
            with open(path) as f:
                frag = json.load(f)
            for name, entry in frag.items():
                if "slot" in entry:
                    out.setdefault(name, entry["slot"])
    return out


def reshard_optimizer_state(
    values: Dict[str, object],
    saved_slots: Dict[str, dict],
    target_slots: Dict[str, dict],
    shardings: Optional[dict] = None,
    strategy=None,
) -> Dict[str, object]:
    """Re-KEY saved optimizer slot state onto the restoring program's
    slot variables, and optionally re-PLACE it onto that program's
    shardings — the slot-state half of mesh portability (ISSUE 14).

    Parameters restore by NAME (users pin them via ParamAttr), but slot
    var names come from unique-name counters and drift whenever the
    restoring program is built differently — per-stage pipeline
    programs whose stage op sets differ across world sizes, a rebuild
    in a warm process, a reordered build. Restoring those by name
    silently re-initializes the moments to zero. This function joins
    ``saved_slots`` (the manifest's descriptors, ``manifest_slots``)
    against ``target_slots`` (the restoring optimizer's
    ``slot_descriptor()``) on the stable (param, kind) identity:

    - a matched slot moves to the restoring name (metered into
      ``pt_ckpt_slot_rekeys_total`` when the name actually changed) and
      is placed through ``shardings``/``strategy`` exactly like
      ``reshard``/``restore_scope`` place parameters;
    - a saved slot with no target is DROPPED (its parameter is not part
      of the restoring program — the per-stage case);
    - non-slot entries pass through untouched.

    Returns a new dict; ``values`` is not mutated."""
    saved_slots = saved_slots or {}
    target_slots = target_slots or {}
    by_key = {}
    for name, d in saved_slots.items():
        by_key[(d.get("param"), d.get("slot"))] = name
    out = {n: v for n, v in values.items() if n not in saved_slots}
    sh = dict(shardings or {})
    rekeyed = 0
    for tname, d in target_slots.items():
        sname = by_key.get((d.get("param"), d.get("slot")))
        if sname is None or sname not in values:
            continue  # nothing saved for this slot: leave initialized
        v = values[sname]
        if tname not in sh and strategy is not None:
            sh[tname] = strategy.sharding_for(tname)
        target = sh.get(tname)
        if target is not None:
            v = reshard({tname: v}, {tname: target})[tname]
        out[tname] = v
        if tname != sname:
            rekeyed += 1
    if rekeyed:
        _M_SLOT_REKEYS.inc(rekeyed)
    return out


def _read_raw(ckpt_dir: str, load_payload: bool = True):
    """(merged manifest, {file key -> array}) straight off disk. With
    ``load_payload=False`` the payload maps every key present in the
    npz indexes to None (header read only — no array data), which is
    what structural validation needs. Both the manifest parses and the
    shard reads pass through the ``ckpt.read`` fault site, so chaos
    plans can tear the RESTORE path (raise/delay/truncate per file)."""
    manifest: Dict[str, dict] = {}
    for fn in sorted(os.listdir(ckpt_dir)):
        if fn.startswith(_MANIFEST):
            path = os.path.join(ckpt_dir, fn)
            _F_READ.hit(path=path)
            with open(path) as f:
                frag = json.load(f)
            for name, entry in frag.items():
                if name in manifest and entry.get("sharded"):
                    manifest[name]["shards"].update(entry["shards"])
                    manifest[name].setdefault("checksums", {}).update(
                        entry.get("checksums", {}))
                else:
                    manifest.setdefault(name, entry)

    payload: Dict[str, Optional[np.ndarray]] = {}
    for fn in sorted(os.listdir(ckpt_dir)):
        if fn.startswith("shards_") and fn.endswith(".npz"):
            path = os.path.join(ckpt_dir, fn)
            _F_READ.hit(path=path)
            with np.load(path) as z:
                if load_payload:
                    for k in z.files:
                        payload[k] = z[k]
                else:
                    payload.update(dict.fromkeys(z.files))
    return manifest, payload


def _load_one(dirname: str, step: int) -> Dict[str, np.ndarray]:
    ckpt_dir = os.path.join(dirname, f"checkpoint_{step}")
    manifest, payload = _read_raw(ckpt_dir)
    if not manifest:
        # an empty/foreign dir must not load as (step, {}) — resume
        # would pick it over an older REAL checkpoint and then die on
        # the missing-parameters check instead of falling back
        raise IOError(f"checkpoint_{step}: no manifest fragments")

    out: Dict[str, np.ndarray] = {}
    for name, entry in manifest.items():
        if not entry["sharded"]:
            k = entry["file_key"]
            if k not in payload:
                raise IOError(
                    f"checkpoint_{step}: variable '{name}' is missing "
                    f"(shard file '{_fkey_file(k)}' absent, no replica "
                    f"coverage — reassembly impossible)")
            want = entry.get("checksum")
            if want is not None and _checksum(payload[k]) != want:
                raise IOError(
                    f"checkpoint_{step}: checksum mismatch for '{name}' "
                    f"— corrupt shard file")
            out[name] = payload[k]
            continue
        full = np.zeros(entry["shape"], dtype=np.dtype(entry["dtype"]))
        seen = np.zeros(entry["shape"], dtype=bool)
        sums = entry.get("checksums", {})
        absent = [k for k in entry["shards"] if k not in payload]
        for fkey, index in entry["shards"].items():
            if fkey in absent:
                continue
            want = sums.get(fkey)
            if want is not None and _checksum(payload[fkey]) != want:
                raise IOError(
                    f"checkpoint_{step}: checksum mismatch for shard "
                    f"'{fkey}' of '{name}' — corrupt shard file")
            sl = tuple(slice(a, b) for a, b in index)
            full[sl] = payload[fkey]
            seen[sl] = True
        if not seen.all():
            files = sorted({_fkey_file(k) for k in absent})
            raise IOError(
                f"checkpoint_{step}: variable '{name}' is missing shards "
                f"({int((~seen).sum())} of {seen.size} elements uncovered; "
                f"absent shards: {sorted(absent)[:4]} from files {files}) "
                f"— replica coverage does NOT permit reassembly; restore "
                f"the missing processes' shard files"
            )
        if absent:
            # every element still covered by surviving shards: a partial
            # file subset (e.g. a shrunk world lost pure-replica hosts)
            _M_PARTIAL.inc()
        out[name] = full
    return out


def save_scope(dirname: str, scope=None, step: int = 0,
               async_save: bool = False, names=None,
               slots: Optional[Dict[str, dict]] = None):
    """Checkpoint a Scope's state (default: every var in the scope).
    ``slots`` records optimizer slot descriptors in the manifest (see
    ``save_checkpoint``)."""
    from paddle_tpu.executor import global_scope

    scope = scope or global_scope()
    names = list(names) if names is not None else scope.var_names()
    state = {n: scope.find_var(n) for n in names}
    return save_checkpoint(dirname, state, step=step,
                           async_save=async_save, slots=slots)


def restore_scope(dirname: str, scope=None, step: Optional[int] = None,
                  strict: bool = True, shardings: Optional[dict] = None,
                  strategy=None):
    """Load a checkpoint back into a Scope. With ``strict``, every
    restored name simply overwrites/creates the scope entry; missing
    checkpoints raise (a partial restore would silently train from
    re-initialized values — same failure mode io.load_vars guards).
    ``shardings`` ({name -> Sharding}) or ``strategy`` (a
    DistributedStrategy: every restored name goes through its
    ``sharding_for``) re-shards values onto the RESTORING program's
    layout during the load — the saved topology is irrelevant."""
    from paddle_tpu.executor import global_scope

    scope = scope or global_scope()
    values = load_checkpoint(dirname, step=step)
    if strict and not values:
        raise IOError(f"empty checkpoint in {dirname}")
    if strategy is not None:
        sh = {n: strategy.sharding_for(n) for n in values}
        sh.update(shardings or {})
        shardings = sh
    if shardings:
        values = reshard(values, shardings)
    for n, v in values.items():
        scope.set(n, v)
    return list(values)
