"""Block -> XLA lowering.

This replaces the reference's op-by-op interpreters (the single-device
``Executor::Run`` hot loop, reference: framework/executor.cc:149, and the
SSA-graph dataflow executors, reference:
framework/details/threaded_ssa_graph_executor.cc:140). On TPU the right
execution model is *whole-program compilation*: a block is traced once into a
single JAX function over a functional environment (name -> array), jitted by
XLA, and run with donated parameter buffers. Scheduling, fusion, memory reuse
(reference: framework/ir/memory_optimize_pass/*) and stream assignment are
all delegated to XLA.

The in-repo precedent in the reference for this design is its nGraph
subgraph engine (reference: operators/ngraph/ngraph_engine.cc), generalized
here to the whole program.
"""

from __future__ import annotations

import copy
import dataclasses
import math
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.framework import Block, Program

# Ops handled by the lowering itself rather than a registered kernel.
_STRUCTURAL_OPS = ("feed", "fetch")

# AMP policy + the op-list interpreter live in core/interp.py (shared with
# control-flow ops, which execute sub-blocks inside lax closures). Re-exported
# here for compatibility.
from paddle_tpu.core.interp import (  # noqa: E402,F401
    AMP_FLOW_OP_TYPES,
    AMP_KEEP_F32_SLOTS,
    AMP_OP_TYPES,
    exec_ops,
    resolve_op_def,
    set_amp_active,
)


@dataclasses.dataclass
class LoweredBlock:
    """A compiled block: ``fn(state, feeds, key) -> (fetches, new_state)``.

    ``state_in_names``: persistable vars read before being written — fetched
    from the Scope (and donated to XLA). ``state_out_names``: every
    state-in var (donation means its buffer must be returned even if
    unchanged) plus every persistable var the block writes.
    """

    fn: Callable
    state_in_names: Tuple[str, ...]
    state_out_names: Tuple[str, ...]
    feed_names: Tuple[str, ...]
    fetch_names: Tuple[str, ...]
    needs_rng: bool
    # op type -> count over the lowered block: the op-lowering histogram
    # carried into compile reports (and the estimate fallback when XLA
    # cost analysis is unavailable)
    op_histogram: Optional[Dict[str, int]] = None


def analyze_state(
    block: Block, feed_names: Sequence[str]
) -> Tuple[List[str], List[str]]:
    """(state_in, state_out) persistable-var lists for the block.

    The functional analog of the reference's Scope residency
    (reference: framework/scope.h:45).
    """
    feed = set(feed_names)
    written: set = set()
    state_in: List[str] = []
    seen_in: set = set()
    written_persistable: List[str] = []

    def is_persistable(name: str) -> bool:
        v = block._find_var_recursive(name)
        return v is not None and v.persistable

    for op in block.ops:
        for name in op.input_arg_names:
            if not name or name in feed or name in written or name in seen_in:
                continue
            if is_persistable(name):
                state_in.append(name)
                seen_in.add(name)
        for name in op.output_arg_names:
            if name and name not in written:
                written.add(name)
                if is_persistable(name):
                    written_persistable.append(name)
    state_out = list(state_in)
    out_seen = set(state_in)
    for name in written_persistable:
        if name not in out_seen:
            state_out.append(name)
            out_seen.add(name)
    return state_in, state_out


def _with_read_grads(ops, fetch_names):
    """``ops`` where an experts' grad op that took its matrices' Adam
    (optimizer.AdamOptimizer._fold_into_experts_grad) writes a matrix's
    gradient after all if something reads it: a fetch, or an op that was
    appended behind ``minimize``. Nothing does in a program that only
    trains, and the ops are the block's own."""
    read = set(fetch_names).union(*(op.input_arg_names for op in ops))
    out = []
    for op in ops:
        kept = {slot: g for slot, g in zip(op.attrs.get("adam_slots", ()),
                                           op.attrs.get("adam_grads", ()))
                if g in read}
        if kept:
            op = copy.copy(op)
            op.attrs = {**op.attrs, "adam_keep_grads": list(kept)}
            op.outputs = {**op.outputs, **{
                "GRAD::" + slot: [g] for slot, g in kept.items()}}
        out.append(op)
    return out


def lower_block(
    program: Program,
    block_idx: int,
    feed_names: Sequence[str],
    fetch_names: Sequence[str],
    amp: bool = False,
) -> LoweredBlock:
    block = program.blocks[block_idx]
    amp = amp or getattr(program, "_amp", False)
    state_in, state_out = analyze_state(block, feed_names)
    state_in, state_out = tuple(state_in), tuple(state_out)
    feed_names = tuple(feed_names)
    fetch_names = tuple(fetch_names)

    # Resolve all kernels up front so unknown ops fail at compile time.
    op_defs = [resolve_op_def(op.type) for op in block.ops]
    needs_rng = any(d.needs_rng for d in op_defs)

    ops = _with_read_grads(block.ops, fetch_names)

    def run_block(state: Dict[str, Any], feeds: Dict[str, Any], key):
        env: Dict[str, Any] = {}
        env.update(state)
        env.update(feeds)
        # the memory ledger of this trace (monitor.memory_ledgers): with
        # telemetry off there is none and exec_ops gathers nothing
        ledger = ValueLedger(state, feeds) if _monitor.enabled() else None
        tok = set_amp_active(amp)
        try:
            exec_ops(ops, env, key=key, amp=amp, op_defs=op_defs,
                     ledger=ledger)
        finally:
            from paddle_tpu.core.interp import _AMP_ACTIVE

            _AMP_ACTIVE.reset(tok)
        fetches = [env[n] for n in fetch_names]
        new_state = {n: env[n] for n in state_out}
        if ledger is not None:
            _monitor.record_memory_ledger(lambda: build_memory_ledger(
                ledger, ops, program, amp, fetch_names + state_out))
        return fetches, new_state

    op_histogram: Dict[str, int] = {}
    for op in ops:
        op_histogram[op.type] = op_histogram.get(op.type, 0) + 1

    return LoweredBlock(
        fn=run_block,
        state_in_names=state_in,
        state_out_names=state_out,
        feed_names=feed_names,
        fetch_names=fetch_names,
        needs_rng=needs_rng,
        op_histogram=op_histogram,
    )


def jit_lowered(
    lowered: LoweredBlock,
    in_shardings=None,
    out_shardings=None,
    fold_step: bool = False,
):
    """Wrap the traced block in jax.jit with parameter-buffer donation.

    ``fold_step``: the returned fn has signature
    ``fn(state, feeds, base_key, step)`` and derives the per-step key with
    ``fold_in`` INSIDE the compiled computation — host-side key derivation
    costs two extra device dispatches per step.

    Entry layouts stay at jax defaults deliberately: AUTO state layouts
    were measured <1% on ResNet-50 in round 4 (relayout copies are
    async-prefetched off the critical path) and executables with custom
    entry layouts deserialized broken from the persistent XLA compilation
    cache."""
    kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    if not fold_step:
        return jax.jit(lowered.fn, **kwargs)

    def step_fn(state, feeds, base_key, step):
        return lowered.fn(state, feeds, jax.random.fold_in(base_key, step))

    return jax.jit(step_fn, **kwargs)


def jit_lowered_multi(lowered: LoweredBlock, n_feeds: int,
                      track_nonfinite: bool = False):
    """Compile ``n_steps`` training steps as ONE XLA program.

    The returned fn has signature
    ``fn(state, feeds_stacked, base_key, start_step, n_steps)`` where
    ``feeds_stacked`` carries each feed with a leading [n_feeds] axis;
    step ``i`` consumes feed ``i % n_feeds`` and folds ``start_step + i``
    into the PRNG key, so the random stream is bit-identical to
    ``n_steps`` successive single-step calls. One host dispatch per
    window instead of one per step — the whole-loop-compiled analog of
    the reference's ``Executor::RunFromDataset`` hot loop
    (reference: framework/executor.cc:120-147, device_worker.h:94
    ``TrainFiles`` — thread-resident step loops without per-step Python).

    ``track_nonfinite``: carry an in-loop finiteness scan of each step's
    float fetches + updated state; the returned fn then yields
    ``(fetches, new_state, first_bad)`` where ``first_bad`` is the LOCAL
    index of the first step that produced a non-finite value (``n_steps``
    when the whole window was clean). This is how ``check_nan_inf``
    names the exact failing step inside a compiled window without
    breaking it into per-step host dispatches.
    """
    sin = lowered.state_in_names
    sout = lowered.state_out_names
    extra_names = tuple(n for n in sout if n not in sin)

    def one(state, feeds_stacked, base_key, step_idx, feed_idx):
        # step_idx (GLOBAL, uint32) feeds the PRNG fold to match the
        # single-step path's fold_in(base_key, np.uint32(step)) stream;
        # feed_idx (LOCAL loop index) drives the rotation so "step i
        # consumes feed i % n_feeds" holds regardless of executor
        # history
        feeds = {
            k: jax.lax.dynamic_index_in_dim(
                v, jax.numpy.remainder(feed_idx, n_feeds), 0,
                keepdims=False
            )
            for k, v in feeds_stacked.items()
        }
        return lowered.fn(
            state, feeds, jax.random.fold_in(base_key, step_idx)
        )

    def _all_finite(vals):
        import jax.numpy as jnp

        flags = [
            jnp.all(jnp.isfinite(v)) for v in vals
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating)
        ]
        if not flags:
            return jnp.bool_(True)
        return jnp.all(jnp.stack(flags))

    def multi_fn(state, feeds_stacked, base_key, start_step, n_steps):
        import jax.numpy as jnp

        shapes = jax.eval_shape(
            lambda s, f, k: one(s, f, k, start_step, 0),
            state, feeds_stacked, base_key,
        )
        fetch0 = [jnp.zeros(x.shape, x.dtype) for x in shapes[0]]
        extra0 = {
            n: jnp.zeros(shapes[1][n].shape, shapes[1][n].dtype)
            for n in extra_names
        }
        # sentinel = n_steps (static here): "no step went non-finite"
        bad0 = jnp.int32(n_steps)

        def body(i, carry):
            st, _extra, _f, bad = carry
            idx = start_step + i.astype(jax.numpy.uint32)
            fetches, new_state = one(st, feeds_stacked, base_key, idx, i)
            if track_nonfinite:
                ok = _all_finite(list(fetches) + list(new_state.values()))
                bad = jnp.where((bad == n_steps) & ~ok,
                                i.astype(jnp.int32), bad)
            st2 = {n: new_state.get(n, st[n]) for n in sin}
            ex2 = {n: new_state[n] for n in extra_names}
            return (st2, ex2, fetches, bad)

        st, ex, fetches, bad = jax.lax.fori_loop(
            0, n_steps, body, (state, extra0, fetch0, bad0)
        )
        if track_nonfinite:
            return fetches, {**st, **ex}, bad
        return fetches, {**st, **ex}

    return jax.jit(multi_fn, static_argnums=(4,), donate_argnums=(0,))


# ---------------------------------------------------------------------------
# the memory ledger (monitor.py memory ledgers): what a step's state
# weighs and what its forward pass keeps for its backward pass
# ---------------------------------------------------------------------------

# the saved rows a ledger keeps (its totals run over all of them)
MEMORY_LEDGER_ROWS = 32
# a layer's index in a name scope, folded so that the layers' rows are
# one row with a count (perf/tools/scope_table.py's spelling: blk#)
_LAYER_INDEX = re.compile(r"\b(blk|enc|dec)\d+\b")
# input slots through which a persistable is read as OPTIMIZER state
# even by a bwd op: an experts' grad op that took its matrices' Adam
# (optimizer.AdamOptimizer._fold_into_experts_grad) reads the moments
# there, and they are no parameters for that
_OPTIMIZER_SLOTS = frozenset(
    {"Moment1", "Moment2", "Beta1Pow", "Beta2Pow", "LearningRate"})


def tile_padded_bytes(shape, dtype) -> int:
    """Bytes of an array of ``shape`` and ``dtype`` in the chip's
    default tiled layout, from the shape alone: the minor dimension up
    to whole lanes (128), the second-minor up to whole sublanes of a
    32-bit word (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit elements),
    rank 1 up to 1024 elements; a scalar is its element. What XLA lays
    out otherwise (a dimension of 1 it moves outward, a short 1-D
    array's smaller tile, a reshape's view that shares its source's
    buffer) PERF.md section 3 lists: the rule says what the Program's
    shapes would cost as they stand."""
    itemsize = jnp.dtype(dtype).itemsize
    dims = [int(d) for d in shape]
    if not dims:
        return itemsize
    if len(dims) == 1:
        dims[0] = -(-dims[0] // 1024) * 1024
    else:
        sublanes = 8 * max(1, 4 // itemsize)
        dims[-1] = -(-dims[-1] // 128) * 128
        dims[-2] = -(-dims[-2] // sublanes) * sublanes
    return math.prod(dims) * itemsize


class _Value:
    """One value of a lowered block: a state array, a feed, or what one
    op wrote under one name (a name written again is a value again)."""

    __slots__ = ("name", "kind", "idx", "role", "scope", "op", "slot",
                 "shape", "dtype", "bytes", "padded", "last_idx",
                 "last_role", "model_read")

    def __init__(self, name, kind, idx, role, scope, op, slot, v):
        self.name, self.kind, self.idx = name, kind, idx
        self.role, self.scope, self.op, self.slot = role, scope, op, slot
        self.shape = tuple(int(d) for d in v.shape)
        dtype = jnp.dtype(v.dtype)
        self.dtype = dtype.name
        self.bytes = math.prod(self.shape) * dtype.itemsize
        self.padded = tile_padded_bytes(self.shape, dtype)
        self.last_idx, self.last_role = None, None
        self.model_read = False


class ValueLedger:
    """What ONE trace of a block gathers for its memory ledger:
    ``run_block`` opens it with the state and the feeds as their avals
    came, ``interp.exec_ops`` tells it every op (``note``), and
    ``build_memory_ledger`` reduces it when the trace ends. Shapes and
    dtypes are the traced values' own: under AMP a bf16 stream counts 2
    bytes whatever its variable declares."""

    def __init__(self, state, feeds):
        self.values: List[_Value] = []
        self.current: Dict[str, _Value] = {}
        for kind, group in (("state", state), ("feed", feeds)):
            for name, v in group.items():
                self._add(name, kind, -1, kind, "", kind, name, v)

    def _add(self, name, kind, idx, role, scope, op, slot, v):
        try:
            val = _Value(name, kind, idx, role, scope, op, slot, v)
        except (AttributeError, TypeError):
            return   # no array (a list of them, a typed key): not counted
        self.values.append(val)
        self.current[name] = val

    def note(self, idx, op, outs):
        """Op ``idx`` of the block ran: it read its inputs' values as
        they stood and wrote ``outs``."""
        role, current = op.role, self.current
        for slot, names in op.inputs.items():
            for n in names:
                val = current.get(n)
                if val is not None:
                    val.last_idx, val.last_role = idx, role
                    if role != "opt" and slot not in _OPTIMIZER_SLOTS:
                        val.model_read = True
        for slot, names in op.outputs.items():
            vals = outs.get(slot, [])
            for i, n in enumerate(names):
                if not n or i >= len(vals) or vals[i] is None:
                    continue
                held = current.get(n)
                if held is not None and held.kind == "state":
                    continue   # a state array updated in place: state
                self._add(n, "value", idx, role, op.namescope, op.type,
                          slot, vals[i])


def _ledger_row(scope: str, val: _Value) -> Dict[str, Any]:
    return {"scope": scope, "op": val.op, "slot": val.slot,
            "shape": list(val.shape), "dtype": val.dtype, "count": 1,
            "bytes": val.bytes, "padded_bytes": val.padded}


def build_memory_ledger(ledger: ValueLedger, ops, program, amp: bool,
                        kept: Sequence[str]) -> Dict[str, Any]:
    """The memory ledger of one lowering (schema:
    monitor.MEMORY_LEDGER_FIELDS) from what its trace gathered. ``kept``
    are the names alive to the end: the fetches and the state handed
    back. It counts the PROGRAM's variables: what an op's compute makes
    inside itself (AMP's bf16 casts of the weights) and whatever XLA
    decides afterwards (fusion, rematerialisation, its temporaries) it
    cannot see."""
    values, n_ops = ledger.values, len(ops)
    state = [v for v in values if v.kind == "state"]
    feeds = [v for v in values if v.kind == "feed"]
    end = n_ops - 1
    for name in kept:
        val = ledger.current.get(name)
        if val is not None and val.kind == "value":
            val.last_idx = end   # (its last reader's role stays)

    # what the forward pass keeps: written under fwd, or fed, and last
    # read by a bwd or opt op
    rows: Dict[tuple, Dict[str, Any]] = {}
    saved_bytes = saved_padded = n_saved = 0
    for v in values:
        if v.kind == "state" or v.role not in ("fwd", "feed") \
                or v.last_role not in ("bwd", "opt"):
            continue
        n_saved += 1
        saved_bytes += v.bytes
        saved_padded += v.padded
        scope = _LAYER_INDEX.sub(r"\1#", v.scope)
        key = (scope, v.op, v.slot, v.shape, v.dtype)
        row = rows.get(key)
        if row is None:
            rows[key] = _ledger_row(scope, v)
        else:
            row["count"] += 1
            row["bytes"] += v.bytes
            row["padded_bytes"] += v.padded
    top = sorted(rows.values(), key=lambda r: (
        -r["padded_bytes"], r["scope"], r["op"], r["slot"]))

    # the walk: a value is alive from its write to its last read (to
    # the end where kept), the state and the feeds throughout; what
    # nothing reads (an op's output for its grad op's sake that the
    # grad op makes again) is dead code to XLA and takes no room
    base = sum(v.padded for v in state) + sum(v.padded for v in feeds)
    delta = [0] * (n_ops + 1)
    spans = []
    for v in values:
        if v.kind != "value" or v.last_idx is None:
            continue
        until = max(v.idx, v.last_idx)
        spans.append((v, until))
        delta[v.idx] += v.padded
        delta[until + 1] -= v.padded
    peak, at, alive_now = base, -1, 0
    for i in range(n_ops):
        alive_now += delta[i]
        if base + alive_now > peak:
            peak, at = base + alive_now, i
    alive = sorted((v for v, until in spans if v.idx <= at <= until),
                   key=lambda v: (-v.padded, v.idx, v.name))[:5]
    op_at = ops[at] if at >= 0 else None
    return {
        "v": _monitor.MEMORY_LEDGER_SCHEMA_VERSION,
        "ts": time.time(),
        "program": f"program{program._uid}",
        "program_uid": int(program._uid),
        "n_ops": n_ops,
        "amp": bool(amp),
        "has_backward": any(op.role == "bwd" for op in ops),
        "state": {
            "param": sum(v.bytes for v in state if v.model_read),
            "optimizer": sum(v.bytes for v in state if not v.model_read),
            "padded_bytes": sum(v.padded for v in state),
            "arrays": len(state)},
        "feed": {"bytes": sum(v.bytes for v in feeds),
                 "padded_bytes": sum(v.padded for v in feeds),
                 "arrays": len(feeds)},
        "saved": {"bytes": saved_bytes, "padded_bytes": saved_padded,
                  "values": n_saved, "rows": top[:MEMORY_LEDGER_ROWS]},
        "walk_peak": {
            "bytes": peak, "index": at,
            "role": op_at.role if op_at is not None else "",
            "scope": op_at.namescope if op_at is not None else "",
            "op": op_at.type if op_at is not None else "",
            "alive": [dict(_ledger_row(v.scope, v), name=v.name)
                      for v in alive]},
    }


# ---------------------------------------------------------------------------
# compile-cost analysis (monitor.py compile reports)
# ---------------------------------------------------------------------------

def _as_int(v) -> Optional[int]:
    try:
        return int(v)
    except (TypeError, ValueError):
        return None


def build_compile_report(
    jitfn,
    lowered: LoweredBlock,
    args: tuple,
    *,
    program,
    kind: str = "step",
    compile_ms: Optional[float] = None,
    strategy: Optional[str] = None,
    cache_key=None,
) -> Dict[str, Any]:
    """Cost/memory report for a freshly compiled executor entry
    (schema: monitor.COMPILE_REPORT_FIELDS).

    AOT-lowers ``jitfn`` against ``args`` (lowering never executes, so
    donated buffers survive — call this BEFORE the step runs) and pulls
    XLA's ``cost_analysis()`` / ``memory_analysis()`` off the compiled
    executable. Both APIs drift across jax versions and backends, so
    every extraction is guarded: when nothing can be extracted the
    report degrades to ``source: "estimate"`` with null cost fields and
    the op-lowering histogram as the only cost signal. Never raises.

    The AOT compile is an extra compile — jax does not reliably share
    the backend cache between ``lower().compile()`` and the eager jit
    path (measured on jax 0.4.37) — which is why compile reports are
    opt-in per monitor.compile_reports_active()."""
    import hashlib
    import time as _time

    key_digest = hashlib.sha1(
        repr(cache_key).encode()).hexdigest()[:16]
    hist = dict(lowered.op_histogram or {})
    report: Dict[str, Any] = {
        "v": _monitor.COMPILE_REPORT_SCHEMA_VERSION,
        "ts": _time.time(),
        "program": f"program{program._uid}",
        "program_uid": int(program._uid),
        "cache_key": key_digest,
        "kind": kind,
        "backend": jax.default_backend(),
        "source": "estimate",
        "compile_ms": compile_ms,
        "analysis_ms": None,
        "flops": None,
        "bytes_accessed": None,
        "peak_bytes": None,
        "argument_bytes": None,
        "output_bytes": None,
        "temp_bytes": None,
        "alias_bytes": None,
        "generated_code_bytes": None,
        "n_ops": sum(hist.values()),
        "op_histogram": hist,
        "strategy": strategy,
    }
    try:
        t0 = _time.perf_counter()
        compiled = jitfn.lower(*args).compile()
        report["analysis_ms"] = (_time.perf_counter() - t0) * 1e3
    except Exception:
        return report

    got_any = False
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if ca.get("flops") is not None:
                report["flops"] = float(ca["flops"])
                got_any = True
            if ca.get("bytes accessed") is not None:
                report["bytes_accessed"] = float(ca["bytes accessed"])
                got_any = True
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        arg = _as_int(getattr(ma, "argument_size_in_bytes", None))
        out = _as_int(getattr(ma, "output_size_in_bytes", None))
        tmp = _as_int(getattr(ma, "temp_size_in_bytes", None))
        ali = _as_int(getattr(ma, "alias_size_in_bytes", None))
        gen = _as_int(getattr(ma, "generated_code_size_in_bytes", None))
        report["argument_bytes"] = arg
        report["output_bytes"] = out
        report["temp_bytes"] = tmp
        report["alias_bytes"] = ali
        report["generated_code_bytes"] = gen
        if None not in (arg, out, tmp):
            report["peak_bytes"] = arg + out + tmp - (ali or 0)
            got_any = True
    except Exception:
        pass
    if got_any:
        report["source"] = "xla"
    else:
        # the AOT compile worked but exposed no numbers (some backends
        # return empty analyses): keep analysis_ms, mark the cost fields
        # as estimates
        report["analysis_ms"] = None
    return report
