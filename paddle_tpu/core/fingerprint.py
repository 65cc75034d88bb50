"""The content fingerprint of a compile signature.

ONE identity shared by the executor's compiled-entry cache key, the
static verifier's lint-once cache (``analysis.py``) and the compile
report's ``cache_key`` — three subsystems that used to hand-roll
overlapping signatures that could drift. It is a digest of program
CONTENT, so two identically-built programs produce the same value in
any process.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict

import numpy as np


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
    processes produce the SAME fingerprint.

    Returns a ``local-`` prefixed identity digest when the program
    content cannot be canonicalized (exotic attrs); such fingerprints
    still key in-process caches correctly but mean nothing to another
    process."""
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
