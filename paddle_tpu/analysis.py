"""Pre-compile static program verifier.

BENCH r01 measured a cold compile+first-step at 98.9 s — every bug that
survives to runtime costs two orders of magnitude more than one caught
before tracing. The reference framework bakes static checking into graph
construction (per-op ``InferShape`` on every ``Block.append_op``,
transpiler-time graph rewrites); TVM-style compiler stacks run
whole-program verification passes before codegen. This module is that
layer for the Program IR: a multi-pass verifier over
``Program``/``Block``/``Operator`` that rejects or warns on broken
programs in milliseconds, before the executor ever traces them.

Three entry points:

1. ``lint(program) -> List[Finding]`` — standalone whole-program run.
2. ``passes.apply_pass("lint", program)`` — the registered pass form.
3. Automatically in ``Executor.run``/``run_steps`` before the first
   compile of any (program, feeds, fetches) signature, gated by the
   ``static_lint`` flag (``off|warn|error``, default ``warn``). With the
   flag ``off`` the executor hot path costs one boolean read and
   allocates nothing here (same contract as monitor.py/numerics.py).

Checks — each its own pluggable pass over a shared def-use index
(``Program.def_use_index()``, cached per program version):

- **dataflow** — read-before-write / uninitialized non-persistable
  reads, fetch targets nothing produces, dead ops whose outputs never
  reach a fetch target or persistable state (the same backward
  reachability walk ``io._prune_for_inference`` uses to drop them),
  write-never-read persistables.
- **shapes** — re-runs ``Block._infer_shapes``-style abstract inference
  whole-program (through the shared ``framework.infer_op_outputs``) and
  flags ops whose declared output shapes/dtypes disagree with inferred
  ones; audits implicit f32 -> f16/bf16 downcasts outside an
  ``amp.decorate`` scope; reports inference-coverage gaps (ops with no
  registered kernel / missing metadata) as debug findings.
- **donation** — static twins of the executor's ``_drop_donated``
  runtime hygiene: a donated state input whose pre- and post-update
  values are both read in one step (the buffer behind the first read is
  gone), donated state aliased to multiple writers, feeds aliasing
  donated state.
- **sharding** — with a ``DistributedStrategy``: ops mixing arrays whose
  axis specs cannot unify without an unplanned reshard, flagged with the
  inferred resharding cost; strict-strategy rule misses.
- **collectives** — the static deadlock detector behind the stall
  watchdog: collectives under data-dependent control flow (``cond`` /
  ``while`` sub-blocks) whose per-rank emission may diverge, and — via
  ``check_collective_order([prog_rank0, prog_rank1, ...])`` — cross-rank
  comparison of per-rank collective emission order + participant sets.

Findings are metered (``pt_lint_findings_total{check=,severity=}``),
kept per program for ``debugger.pprint_program`` annotations and the
monitor server's ``/lint`` route, and pretty-printed by
``lint_report(program)``.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

import numpy as np

from paddle_tpu import flags as _flags
from paddle_tpu import monitor as _monitor
from paddle_tpu.core import fingerprint as _fingerprint
from paddle_tpu.framework import (
    _BATCH_SENTINEL,
    Block,
    Operator,
    Program,
    infer_op_outputs,
)

_log = logging.getLogger("paddle_tpu")

SEVERITIES = ("debug", "info", "warning", "error")
_SEV_RANK = {s: i for i, s in enumerate(SEVERITIES)}

_M_FINDINGS = _monitor.counter(
    "pt_lint_findings_total",
    "static-verifier findings, by check family and severity")
_M_RUNS = _monitor.counter(
    "pt_lint_runs_total",
    "whole-program static-verifier runs (executor pre-compile runs are "
    "cached per program fingerprint)")


class LintError(RuntimeError):
    """Raised under ``static_lint=error`` when a program has
    error-severity findings. ``.findings`` carries them."""

    def __init__(self, findings: List["Finding"]):
        self.findings = list(findings)
        head = "; ".join(str(f) for f in self.findings[:3])
        more = len(self.findings) - 3
        if more > 0:
            head += f"; ... {more} more"
        super().__init__(
            f"static lint found {len(self.findings)} error(s): {head} "
            f"(set flag static_lint='warn' to log instead of raise)")


@dataclasses.dataclass
class Finding:
    """One verifier finding: check family, severity, site, fix hint."""

    check: str                      # e.g. 'dataflow.uninitialized_read'
    severity: str                   # debug | info | warning | error
    message: str
    block_idx: int = 0
    op_idx: Optional[int] = None
    op_type: Optional[str] = None
    var: Optional[str] = None
    hint: Optional[str] = None
    cost_bytes: Optional[int] = None  # sharding: est. reshard traffic

    @property
    def site(self) -> str:
        parts = [f"block {self.block_idx}"]
        if self.op_idx is not None:
            parts.append(f"op [{self.op_idx}]"
                         + (f" {self.op_type}" if self.op_type else ""))
        if self.var is not None:
            parts.append(f"var '{self.var}'")
        return " ".join(parts)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["site"] = self.site
        return d

    def __str__(self):
        s = f"[{self.severity}] {self.check} @ {self.site}: {self.message}"
        if self.cost_bytes is not None:
            s += f" (~{self.cost_bytes:,} B resharded)"
        if self.hint:
            s += f" — fix: {self.hint}"
        return s


# ---------------------------------------------------------------------------
# def-use index (the shared substrate every check walks)
# ---------------------------------------------------------------------------


def _op_attr_refs(block: Block, op: Operator):
    """(sub_blocks, attr-referenced var names) for one op.

    Control-flow ops reference env vars through attrs (``carry_names``,
    ``cond_name``, ``x_names``...) rather than input slots; treating
    those strings as reads keeps the dataflow checks conservative —
    an op whose attrs name a live var is never reported dead and its
    referenced vars are never reported unread."""
    subs: List[Block] = []
    refs: List[str] = []

    def add_sub(b):
        if not any(s is b for s in subs):  # cond may reuse one block
            subs.append(b)

    for val in op.attrs.values():
        if isinstance(val, Block):
            add_sub(val)
        elif isinstance(val, str):
            if block._find_var_recursive(val) is not None:
                refs.append(val)
        elif isinstance(val, (list, tuple)):
            for x in val:
                if isinstance(x, Block):
                    add_sub(x)
                elif isinstance(x, str) and \
                        block._find_var_recursive(x) is not None:
                    refs.append(x)
    return subs, refs


class DefUseIndex:
    """Writers/readers maps over one block's ops, program-order indexed.

    ``writers[name]`` / ``readers[name]`` list op indices in program
    order; ``first_write``/``first_read`` are the head elements.
    ``attr_reads[i]`` are var names op ``i`` references through attrs
    (control-flow carries); ``sub_blocks[i]`` its nested blocks."""

    def __init__(self, block: Block):
        self.block = block
        self.writers: Dict[str, List[int]] = {}
        self.readers: Dict[str, List[int]] = {}
        self.first_write: Dict[str, int] = {}
        self.first_read: Dict[str, int] = {}
        self.attr_reads: Dict[int, List[str]] = {}
        self.sub_blocks: Dict[int, List[Block]] = {}
        for idx, op in enumerate(block.ops):
            for n in op.input_arg_names:
                if not n:
                    continue
                self.readers.setdefault(n, []).append(idx)
                self.first_read.setdefault(n, idx)
            subs, refs = _op_attr_refs(block, op)
            if subs:
                self.sub_blocks[idx] = subs
            if refs:
                self.attr_reads[idx] = refs
                for n in refs:
                    self.readers.setdefault(n, []).append(idx)
                    self.first_read.setdefault(n, idx)
            for n in op.output_arg_names:
                if not n:
                    continue
                self.writers.setdefault(n, []).append(idx)
                self.first_write.setdefault(n, idx)

    def is_persistable(self, name: str) -> bool:
        v = self.block._find_var_recursive(name)
        return bool(v is not None and getattr(v, "persistable", False))


def build_def_use(program: Program) -> Dict[int, DefUseIndex]:
    """{block idx -> DefUseIndex}; call through
    ``Program.def_use_index()`` to get the version-keyed cached copy."""
    return {b.idx: DefUseIndex(b) for b in program.blocks}


@dataclasses.dataclass
class LintContext:
    """Everything one check pass needs, resolved once per lint run."""

    program: Program
    index: Dict[int, DefUseIndex]
    feed_names: Optional[frozenset]     # None = unknown (standalone run)
    fetch_names: Optional[Sequence[str]]
    strategy: Any                       # parallel.DistributedStrategy


# ---------------------------------------------------------------------------
# check registry (pluggable passes)
# ---------------------------------------------------------------------------

_CHECK_REGISTRY: "collections.OrderedDict[str, Callable]" = \
    collections.OrderedDict()


def register_check(name: str):
    """Decorator registering ``fn(ctx: LintContext) -> Iterable[Finding]``
    as a verifier pass (same shape as passes.register_pass)."""

    def deco(fn):
        if name in _CHECK_REGISTRY:
            raise ValueError(f"lint check '{name}' registered twice")
        _CHECK_REGISTRY[name] = fn
        return fn

    return deco


def registered_checks() -> List[str]:
    return list(_CHECK_REGISTRY)


# ---------------------------------------------------------------------------
# check: dataflow
# ---------------------------------------------------------------------------


@register_check("dataflow")
def _check_dataflow(ctx: LintContext) -> List[Finding]:
    block = ctx.program.global_block()
    idx = ctx.index[block.idx]
    feeds = ctx.feed_names
    out: List[Finding] = []

    for i, op in enumerate(block.ops):
        for n in op.input_arg_names:
            if not n or idx.is_persistable(n):
                continue  # scope state: initialized by startup program
            fw = idx.first_write.get(n)
            if fw is not None and fw < i:
                continue
            if feeds is not None:
                if n in feeds:
                    continue
            else:
                v = block._find_var_recursive(n)
                if fw is None and v is not None and v.shape is not None \
                        and v.dtype is not None:
                    continue  # declared input (layers.data feed candidate)
            if fw is None:
                out.append(Finding(
                    "dataflow.uninitialized_read", "error",
                    f"'{n}' is read but never written and is not a feed",
                    op_idx=i, op_type=op.type, var=n,
                    hint="feed it, write it in the startup program, or "
                         "mark it persistable"))
            else:
                out.append(Finding(
                    "dataflow.read_before_write", "error",
                    f"'{n}' is read before its first writer (op [{fw}])",
                    op_idx=i, op_type=op.type, var=n,
                    hint="reorder the ops or feed the initial value"))

    # fetch targets nothing can produce (the lowering env is
    # state-in ∪ feeds ∪ op outputs — see core/lowering.py run_block)
    fetch = list(ctx.fetch_names or ())
    produced = set(idx.writers)
    for n in fetch:
        if n in produced or (feeds is not None and n in feeds):
            continue
        if idx.is_persistable(n) and n in idx.readers:
            continue  # rides into the env as donated state
        if feeds is None:
            v = block._find_var_recursive(n)
            if v is not None and not v.persistable \
                    and v.shape is not None and v.dtype is not None \
                    and n not in idx.writers:
                continue  # declared input: same feed-candidate
                # heuristic the uninitialized-read check applies
        out.append(Finding(
            "dataflow.unreachable_fetch", "error",
            f"fetch target '{n}' is neither produced by an op, fed, nor "
            f"persistable state the program reads",
            var=n,
            hint="fetch a produced var, or add the producing op"))

    # dead ops: backward reachability from fetch targets — the walk
    # _inference_prune uses to drop them, with persistable writes and
    # control-flow ops kept as roots (state updates are step outputs)
    if fetch:
        needed = set(fetch)
        live = [False] * len(block.ops)
        for i in range(len(block.ops) - 1, -1, -1):
            op = block.ops[i]
            outs = op.output_arg_names
            rooted = (
                i in idx.sub_blocks
                or any(idx.is_persistable(n) for n in outs)
                or any(n in needed for n in outs)
            )
            if rooted:
                live[i] = True
                needed.update(n for n in op.input_arg_names if n)
                needed.update(idx.attr_reads.get(i, ()))
        for i, op in enumerate(block.ops):
            if not live[i]:
                out.append(Finding(
                    "dataflow.dead_op", "info",
                    f"outputs {op.output_arg_names} never reach a fetch "
                    f"target or persistable state",
                    op_idx=i, op_type=op.type,
                    hint="drop the op or fetch its output "
                         "(inference_prune would remove it)"))

    # write-never-read persistables (dead state updates)
    fetch_set = set(fetch)
    for n, ws in idx.writers.items():
        if not idx.is_persistable(n):
            continue
        if n in idx.readers or n in fetch_set:
            continue
        out.append(Finding(
            "dataflow.write_never_read", "info",
            f"persistable '{n}' is written but never read or fetched",
            op_idx=ws[0], op_type=block.ops[ws[0]].type, var=n,
            hint="dead state update — drop it or fetch the value"))
    return out


# ---------------------------------------------------------------------------
# check: shapes / dtypes
# ---------------------------------------------------------------------------

# (op type, attr key, input signature) -> (outs-by-slot sig, gap); the
# memo makes whole-program re-inference cheap on repeated-layer programs
# (a transformer re-infers each distinct layer shape once)
_EVAL_CACHE: Dict[tuple, tuple] = {}
_EVAL_CACHE_CAP = 4096

_FLOAT_NARROW = {"float16", "bfloat16"}


def _eval_key(block: Block, op: Operator):
    try:
        attrs = []
        for k, v in op.compute_attrs().items():
            if isinstance(v, Block) or (
                    isinstance(v, (list, tuple))
                    and any(isinstance(x, Block) for x in v)):
                return None  # sub-block semantics: never memo
            attrs.append((k, repr(v)))
    except Exception:
        return None
    sig = []
    for slot, names in sorted(op.inputs.items()):
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None or v.dtype is None:
                return None
            sig.append((slot, tuple(v.shape), v.dtype))
    return (op.type, tuple(sorted(attrs)), tuple(sig))


def _infer_cached(block: Block, op: Operator):
    key = _eval_key(block, op)
    if key is None:
        return infer_op_outputs(block, op)
    hit = _EVAL_CACHE.get(key)
    if hit is None:
        outs, gap = infer_op_outputs(block, op)
        sig = None
        if outs is not None:
            try:
                sig = {
                    slot: [None if r is None
                           else (tuple(r.shape), np.dtype(r.dtype).name)
                           for r in rs]
                    for slot, rs in outs.items()
                }
            except Exception as e:  # malformed kernel result structure
                sig, gap = None, f"eval_failed:{type(e).__name__}: {e}"
        if len(_EVAL_CACHE) >= _EVAL_CACHE_CAP:
            _EVAL_CACHE.clear()
        _EVAL_CACHE[key] = hit = (sig, gap)
    sig, gap = hit
    if sig is None:
        return None, gap
    # rehydrate the memoized signature into ShapeDtypeStruct-likes
    outs = {
        slot: [None if r is None else _Sds(r[0], r[1]) for r in rs]
        for slot, rs in sig.items()
    }
    return outs, None


class _Sds:
    """Tiny (shape, dtype) record mirroring jax.ShapeDtypeStruct for the
    memoized path (no jax import needed to rehydrate)."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype


@register_check("shapes")
def _check_shapes(ctx: LintContext) -> List[Finding]:
    block = ctx.program.global_block()
    amp = bool(getattr(ctx.program, "_amp", False))
    out: List[Finding] = []
    for i, op in enumerate(block.ops):
        outs, gap = _infer_cached(block, op)
        if outs is None:
            # coverage honesty: the build-time _infer_shapes silently
            # fell through here before; now it is one debug finding
            out.append(Finding(
                "shapes.no_inference", "debug",
                f"shape inference unavailable ({gap})",
                op_idx=i, op_type=op.type,
                hint="register a kernel / declare input metadata so the "
                     "verifier can cover this op"))
            continue
        try:
            for slot, names in op.outputs.items():
                results = outs.get(slot, [])
                for n, r in zip(names, results):
                    if r is None:
                        continue
                    v = block._find_var_recursive(n)
                    if v is None or v.shape is None or v.dtype is None:
                        continue
                    inferred = tuple(
                        -1 if d == _BATCH_SENTINEL else int(d)
                        for d in r.shape)
                    if tuple(v.shape) != inferred:
                        out.append(Finding(
                            "shapes.shape_mismatch", "warning",
                            f"declared shape {list(v.shape)} disagrees "
                            f"with inferred {list(inferred)}",
                            op_idx=i, op_type=op.type, var=n,
                            hint="the program desc was edited or a pass "
                                 "rewrote the op without re-inferring; "
                                 "fix the producer or re-run shape "
                                 "inference"))
                    idt = np.dtype(r.dtype).name
                    if v.dtype != idt:
                        out.append(Finding(
                            "shapes.dtype_mismatch", "warning",
                            f"declared dtype {v.dtype} disagrees with "
                            f"inferred {idt}",
                            op_idx=i, op_type=op.type, var=n,
                            hint="align the declared dtype with the "
                                 "kernel or insert an explicit cast"))
        except Exception as e:
            # a kernel returning a malformed result structure is a
            # coverage gap for THIS op, never an abort of the whole run
            out.append(Finding(
                "shapes.no_inference", "debug",
                f"shape inference unavailable (malformed kernel "
                f"result: {type(e).__name__}: {e})",
                op_idx=i, op_type=op.type,
                hint="fix the kernel's output structure (slot -> list "
                     "of results)"))
            continue

        # implicit-downcast audit: f32 in, f16/bf16 out, outside an
        # amp.decorate scope, from an op that did not explicitly ask
        # for it (cast, or a dtype attr)
        if amp or op.type == "cast" or "dtype" in op.attrs:
            continue
        in_dtypes = set()
        for n in op.input_arg_names:
            v = block._find_var_recursive(n)
            if v is not None and v.dtype is not None:
                in_dtypes.add(v.dtype)
        for n in op.output_arg_names:
            v = block._find_var_recursive(n)
            if v is None or v.dtype is None:
                continue
            if v.dtype in _FLOAT_NARROW and "float32" in in_dtypes:
                out.append(Finding(
                    "shapes.implicit_downcast", "warning",
                    f"f32 input narrowed to {v.dtype} outside an "
                    f"amp.decorate scope",
                    op_idx=i, op_type=op.type, var=n,
                    hint="wrap the build in amp.decorate / apply the "
                         "'amp' pass, or cast explicitly"))
    return out


# ---------------------------------------------------------------------------
# check: donation / aliasing
# ---------------------------------------------------------------------------


@register_check("donation")
def _check_donation(ctx: LintContext) -> List[Finding]:
    block = ctx.program.global_block()
    idx = ctx.index[block.idx]
    feeds = ctx.feed_names or frozenset()
    out: List[Finding] = []

    from paddle_tpu.core.lowering import analyze_state

    state_in, _ = analyze_state(block, feeds)
    for n in state_in:
        ws = idx.writers.get(n, [])
        if len(ws) > 1:
            out.append(Finding(
                "donation.multi_writer", "warning",
                f"donated state '{n}' has {len(ws)} writers "
                f"(ops {ws}); the donated buffer is aliased to multiple "
                f"updates in one step",
                op_idx=ws[1], op_type=block.ops[ws[1]].type, var=n,
                hint="merge the updates into one op or stage the "
                     "intermediate through a non-persistable temp"))
        if not ws:
            continue
        w0 = ws[0]
        before = [i for i in idx.readers.get(n, []) if i < w0]
        after = [i for i in idx.readers.get(n, []) if i > w0]
        if before and after:
            # one step observing two versions of a donated buffer: the
            # buffer behind the pre-update read was donated to the
            # writer — the static twin of _drop_donated's runtime
            # "deleted donated array" failure
            out.append(Finding(
                "donation.read_after_donate", "warning",
                f"donated input '{n}' is read (op [{before[0]}]) before "
                f"and re-read (op [{after[0]}]) after its overwrite "
                f"(op [{w0}]); the re-read observes the updated value, "
                f"not the donated original",
                op_idx=after[0], op_type=block.ops[after[0]].type, var=n,
                hint="move the read before the update, or snapshot the "
                     "pre-update value into a temp and read that"))

    for n in sorted(feeds):
        if idx.is_persistable(n):
            out.append(Finding(
                "donation.feed_aliases_state", "warning",
                f"feed '{n}' aliases persistable state: the executor "
                f"both donates the scope buffer and binds the feed, so "
                f"one of them silently wins",
                var=n,
                hint="rename the feed or drop the persistable flag"))
    return out


# ---------------------------------------------------------------------------
# check: sharding / mesh consistency
# ---------------------------------------------------------------------------

# ops whose single X input's spec flows through unchanged
_UNARY_PRESERVE = frozenset({
    "scale", "relu", "sigmoid", "tanh", "exp", "log", "sqrt", "square",
    "abs", "gelu", "softmax", "log_softmax", "dropout", "cast",
})
_ELEMENTWISE = frozenset({
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow",
})


def _normspec(p, rank: int):
    """PartitionSpec -> per-dim tuple-of-axis-names, padded to rank."""
    entries = list(p) if p is not None else []
    dims = []
    for e in entries:
        if e is None:
            dims.append(())
        elif isinstance(e, (tuple, list)):
            dims.append(tuple(e))
        else:
            dims.append((e,))
    while len(dims) < rank:
        dims.append(())
    return tuple(dims[:rank])


def _var_bytes(v) -> int:
    if v is None or v.shape is None:
        return 0
    n = 1
    for d in v.shape:
        n *= max(int(d), 1)  # -1 batch dim counted as one sample
    try:
        return n * np.dtype(v.dtype or "float32").itemsize
    except TypeError:
        return n * 4


def _reshard_cost(v, axes, mesh) -> int:
    """Estimated all-gather traffic (bytes) to undo sharding ``axes``
    of ``v`` on ``mesh`` — (s-1)/s of the global array crosses links."""
    from paddle_tpu.parallel.mesh import axis_size

    try:
        s = axis_size(mesh, tuple(axes))
    except Exception:
        s = 2
    b = _var_bytes(v)
    return int(b * (s - 1) / s) if s > 1 else b


@register_check("sharding")
def _check_sharding(ctx: LintContext) -> List[Finding]:
    st = ctx.strategy
    if st is None:
        return []
    block = ctx.program.global_block()
    out: List[Finding] = []
    specs: Dict[str, tuple] = {}

    def var_of(n):
        return block._find_var_recursive(n)

    # seed: persistables from the strategy rules, feeds from the batch
    # sharding; everything else propagates (or stays unknown)
    for b in ctx.program.blocks:
        for name, v in b.vars.items():
            if not v.persistable or v.shape is None:
                continue
            try:
                p = st.spec_for(name)
            except ValueError as e:
                out.append(Finding(
                    "sharding.unmatched_rule", "error", str(e), var=name,
                    hint="add a rule (PartitionSpec() for replicated)"))
                continue
            specs[name] = _normspec(p, len(v.shape))
    batch_axes = tuple(
        a for a in (getattr(st, "slice_axis", None),
                    getattr(st, "data_axis", None)) if a)
    for n in (ctx.feed_names or ()):
        v = var_of(n)
        if v is not None and v.shape is not None and len(v.shape) >= 1:
            specs[n] = ((batch_axes,) if batch_axes else ((),)) + \
                ((),) * (len(v.shape) - 1)

    def unify(i, op, pairs):
        """dim-aligned (name_a, dim_a, name_b, dim_b) unification; a
        conflict emits one finding and wins arbitrarily."""
        for (na, da, nb, db) in pairs:
            sa, sb = specs.get(na), specs.get(nb)
            if sa is None or sb is None:
                continue
            if da >= len(sa) or db >= len(sb):
                continue
            a, b = sa[da], sb[db]
            if a and b and a != b:
                va, vb = var_of(na), var_of(nb)
                victim, axes = (
                    (va, a) if _var_bytes(va) <= _var_bytes(vb)
                    else (vb, b))
                out.append(Finding(
                    "sharding.unresolvable_mix", "warning",
                    f"'{na}' dim {da} is sharded over {list(a)} but "
                    f"'{nb}' dim {db} over {list(b)}; GSPMD must "
                    f"reshard one of them",
                    op_idx=i, op_type=op.type, var=na,
                    cost_bytes=_reshard_cost(victim, axes, st.mesh),
                    hint="align the sharding rules of the two operands "
                         "(or accept the reshard and silence with a "
                         "matching rule)"))

    for i, op in enumerate(block.ops):
        t = op.type
        ins = op.input_arg_names
        if t in _UNARY_PRESERVE and ins:
            s = specs.get(ins[0])
            if s is not None:
                for n in op.output_arg_names:
                    v = var_of(n)
                    if v is not None and v.shape is not None \
                            and len(v.shape) == len(s):
                        specs[n] = s
        elif t in _ELEMENTWISE:
            xs = op.inputs.get("X", [])
            ys = op.inputs.get("Y", [])
            if xs and ys:
                x, y = xs[0], ys[0]
                vx, vy = var_of(x), var_of(y)
                if vx is not None and vy is not None and \
                        vx.shape is not None and vy.shape is not None:
                    rx, ry = len(vx.shape), len(vy.shape)
                    axis = int(op.attrs.get("axis", -1))
                    off = rx - ry if axis == -1 else axis
                    sx, sy = specs.get(x), specs.get(y)
                    if 0 <= off <= rx - ry and sx is not None \
                            and sy is not None:
                        unify(i, op, [(x, off + d, y, d)
                                      for d in range(ry)])
                        # joint spec: per-dim union of the two operands.
                        # A mesh axis claimed by DIFFERENT dims of the
                        # union cannot shard both at once — the operands
                        # can only meet through a reshard even though no
                        # single dim conflicts outright.
                        merged = list(sx)
                        for d in range(ry):
                            if not merged[off + d]:
                                merged[off + d] = sy[d]
                        used: Dict[str, int] = {}
                        collide = None
                        for d, axes in enumerate(merged):
                            for a in axes:
                                if a in used and used[a] != d:
                                    collide = (a, used[a], d)
                                used.setdefault(a, d)
                        if collide is not None:
                            a, d0, d1 = collide
                            victim = (vx if _var_bytes(vx)
                                      <= _var_bytes(vy) else vy)
                            out.append(Finding(
                                "sharding.unresolvable_mix", "warning",
                                f"'{x}' and '{y}' jointly claim mesh "
                                f"axis '{a}' for dims {d0} and {d1}; "
                                f"one axis cannot shard both dims, so "
                                f"GSPMD must reshard an operand",
                                op_idx=i, op_type=op.type, var=x,
                                cost_bytes=_reshard_cost(
                                    victim, (a,), st.mesh),
                                hint="align the two operands' sharding "
                                     "rules on one layout"))
                        else:
                            for n in op.output_arg_names:
                                v = var_of(n)
                                if v is not None and v.shape is not None \
                                        and len(v.shape) == rx:
                                    specs[n] = tuple(merged)
        elif t in ("mul", "matmul", "fc"):
            xn = (op.inputs.get("X") or op.inputs.get("Input") or [None])[0]
            yn = (op.inputs.get("Y") or op.inputs.get("W") or [None])[0]
            if xn is None or yn is None:
                continue
            if t == "matmul" and (op.attrs.get("transpose_x")
                                  or op.attrs.get("transpose_y")):
                continue  # transposed contractions: stay conservative
            vx, vy = var_of(xn), var_of(yn)
            if vx is None or vy is None or vx.shape is None \
                    or vy.shape is None or len(vy.shape) != 2:
                continue
            rx = len(vx.shape)
            # contraction: X's trailing dim against Y's dim 0 — both
            # sharded on the same axis is the PLANNED row-parallel
            # matmul (GSPMD inserts the all-reduce); a mismatch is an
            # unplanned reshard
            unify(i, op, [(xn, rx - 1, yn, 0)])
            sx, sy = specs.get(xn), specs.get(yn)
            if sx is not None and sy is not None:
                for n in op.output_arg_names:
                    v = var_of(n)
                    if v is not None and v.shape is not None \
                            and len(v.shape) >= 2:
                        ro = len(v.shape)
                        specs[n] = tuple(
                            sx[d] if d < ro - 1 and d < len(sx) else
                            (sy[1] if d == ro - 1 else ())
                            for d in range(ro))
            if t == "fc":
                bn = (op.inputs.get("Bias") or [None])[0]
                if bn is not None:
                    unify(i, op, [(yn, 1, bn, 0)])
        # every other op type: outputs stay unknown (conservative)
    return out


# ---------------------------------------------------------------------------
# check: collective order
# ---------------------------------------------------------------------------


def _collective_kind(op: Operator, strategy):
    """(kind, axis) when the op lowers to a cross-rank collective under
    ``strategy``, else None. Strategy-aware by design: the same sdpa op
    is a dense kernel without a context axis and a ring collective with
    one."""
    if strategy is None:
        return None
    if op.type == "scaled_dot_product_attention" and \
            getattr(strategy, "context_axis", None):
        return ("ring_attention", strategy.context_axis)
    if op.type == "switch_moe" and getattr(strategy, "expert_axis", None):
        return ("all_to_all", strategy.expert_axis)
    if op.type == "scan" and op.attrs.get("pipelinable", False) and \
            getattr(strategy, "pipe_axis", None):
        return ("gpipe", strategy.pipe_axis)
    if op.type == "lookup_table" and \
            op.attrs.get("is_distributed", False) and \
            getattr(strategy, "table_axis", None):
        return ("sharded_table", strategy.table_axis)
    return None


def collective_signature(program: Program, strategy=None) -> List[Dict]:
    """Ordered list of the collectives this program emits under
    ``strategy``: one dict per collective with kind, op, axis and
    participant count — the per-rank sequence ``check_collective_order``
    compares. Participant sets come from the parallel modules' spec
    extraction (ring_attention/pipeline ``collective_signature``)."""
    sig: List[Dict] = []

    def walk(block: Block):
        for i, op in enumerate(block.ops):
            kind = _collective_kind(op, strategy)
            if kind is not None:
                kname, axis = kind
                entry: Dict[str, Any] = {
                    "kind": kname, "op": op.type, "axis": axis,
                    "block": block.idx, "op_idx": i,
                }
                mesh = getattr(strategy, "mesh", None)
                if mesh is not None:
                    try:
                        from paddle_tpu.parallel.mesh import axis_sizes

                        # per-rank mesh shape rides the signature: two
                        # ranks building different meshes IS a
                        # participant-set divergence
                        entry["mesh"] = axis_sizes(mesh)
                        if kname == "ring_attention":
                            from paddle_tpu.parallel import (
                                ring_attention as _ra,
                            )

                            entry.update(_ra.collective_signature(
                                mesh, axis))
                        elif kname == "gpipe":
                            from paddle_tpu.parallel import (
                                pipeline as _pp,
                            )

                            entry.update(_pp.collective_signature(
                                mesh, axis,
                                getattr(strategy, "pipe_micro", None)))
                        else:
                            from paddle_tpu.parallel.mesh import axis_size

                            entry["participants"] = axis_size(mesh, axis)
                    except Exception:
                        pass
                sig.append(entry)
            for sub in _op_attr_refs(block, op)[0]:
                walk(sub)

    walk(program.global_block())
    return sig


def check_collective_order(programs: Sequence[Program],
                           strategy=None) -> List[Finding]:
    """Cross-rank lint: compare per-rank collective emission order and
    participant sets; any divergence is a static deadlock (rank A waits
    in collective #k while rank B entered a different one — the hang
    the stall watchdog can only report at runtime). ``strategy`` may be
    one shared strategy or a per-rank sequence."""
    strategies = (list(strategy)
                  if isinstance(strategy, (list, tuple))
                  else [strategy] * len(programs))
    if len(strategies) != len(programs):
        raise ValueError(
            f"check_collective_order: {len(programs)} programs but "
            f"{len(strategies)} strategies — pass one shared strategy "
            f"or exactly one per rank")
    sigs = [collective_signature(p, s)
            for p, s in zip(programs, strategies)]
    out: List[Finding] = []
    base = sigs[0] if sigs else []

    def _key(e):
        # everything except the site (block/op_idx): two ranks may
        # interleave non-collective ops differently and still agree;
        # schedule shape (ticks/rotations/mesh) must match exactly —
        # e.g. differing pipe_micro means differing ppermute hop counts
        return tuple(sorted(
            (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
            for k, v in e.items() if k not in ("block", "op_idx")))

    for r, sig in enumerate(sigs[1:], 1):
        if len(sig) != len(base):
            out.append(Finding(
                "collectives.count_divergence", "error",
                f"rank 0 emits {len(base)} collectives but rank {r} "
                f"emits {len(sig)}; the shorter rank deadlocks the "
                f"longer one",
                hint="make every rank trace the identical collective "
                     "sequence (same model config, same strategy axes)"))
            continue
        for k, (a, b) in enumerate(zip(base, sig)):
            if _key(a) != _key(b):
                out.append(Finding(
                    "collectives.order_divergence", "error",
                    f"collective #{k} diverges between rank 0 "
                    f"({_key(a)}) and rank {r} ({_key(b)}); mismatched "
                    f"emission order or participant sets deadlock "
                    f"across ranks",
                    op_idx=a.get("op_idx"), op_type=a.get("op"),
                    hint="align the per-rank programs (same op order, "
                         "same axis specs) before dispatch"))
                break
    return out


@register_check("collectives")
def _check_collectives(ctx: LintContext) -> List[Finding]:
    """Single-program half of the collective-order check: collectives
    under data-dependent control flow (``cond`` branches, ``while``
    trip counts) can fire on some ranks and not others."""
    if ctx.strategy is None:
        return []
    block = ctx.program.global_block()
    idx = ctx.index[block.idx]
    out: List[Finding] = []

    def scan_sub(block_, top_idx, top_type):
        for op in block_.ops:
            kind = _collective_kind(op, ctx.strategy)
            if kind is not None:
                out.append(Finding(
                    "collectives.control_flow", "warning",
                    f"collective '{op.type}' ({kind[0]} over "
                    f"'{kind[1]}') sits inside a data-dependent "
                    f"'{top_type}' body; ranks whose condition "
                    f"diverges deadlock the rest",
                    op_idx=top_idx, op_type=top_type,
                    hint="hoist the collective out of the conditional "
                         "or make the condition provably rank-invariant"))
            for sub in _op_attr_refs(block_, op)[0]:
                scan_sub(sub, top_idx, top_type)

    for i, subs in idx.sub_blocks.items():
        top = block.ops[i]
        if top.type not in ("cond", "while"):
            continue  # bounded_while/scan run every rank in lockstep
        for sub in subs:
            scan_sub(sub, i, top.type)
    return out


# ---------------------------------------------------------------------------
# lint driver + latest-findings store
# ---------------------------------------------------------------------------

# program uid -> latest lint record (bounded; debugger + /lint route)
_LATEST: "collections.OrderedDict[int, Dict]" = collections.OrderedDict()
_LATEST_CAP = 64


def lint(program: Program,
         feeds: Optional[Iterable[str]] = None,
         fetches: Optional[Iterable[str]] = None,
         strategy=None,
         checks: Optional[Sequence[str]] = None,
         min_severity: str = "warning") -> List[Finding]:
    """Run the verifier over ``program`` and return findings at or above
    ``min_severity`` (default 'warning'; pass 'debug' for the full set
    including coverage notes). ``feeds``/``fetches`` sharpen the
    dataflow checks (the executor provides them; standalone runs may
    omit them), ``strategy`` enables the sharding + collective checks,
    ``checks`` selects a subset of ``registered_checks()``."""
    if min_severity not in _SEV_RANK:
        raise ValueError(
            f"min_severity '{min_severity}' not in {SEVERITIES}")
    t0 = time.perf_counter()
    ctx = LintContext(
        program=program,
        index=program.def_use_index(),
        feed_names=(frozenset(feeds) if feeds is not None else None),
        fetch_names=(list(fetches) if fetches is not None else None),
        strategy=strategy,
    )
    findings: List[Finding] = []
    for name in (checks if checks is not None else registered_checks()):
        if name not in _CHECK_REGISTRY:
            raise KeyError(
                f"unknown lint check '{name}'; "
                f"registered: {registered_checks()}")
        findings.extend(_CHECK_REGISTRY[name](ctx))
    findings.sort(key=lambda f: (-_SEV_RANK[f.severity],
                                 f.block_idx,
                                 f.op_idx if f.op_idx is not None else -1))
    ms = (time.perf_counter() - t0) * 1e3
    _M_RUNS.inc()
    for f in findings:
        _M_FINDINGS.inc(labels={"check": f.check.split(".", 1)[0],
                                "severity": f.severity})
    _LATEST[program._uid] = {
        "v": 1,
        "program": f"program{program._uid}",
        "version": program.version,
        "lint_ms": ms,
        "counts": _counts(findings),
        "findings": [f.to_dict() for f in findings],
    }
    while len(_LATEST) > _LATEST_CAP:
        _LATEST.popitem(last=False)
    cut = _SEV_RANK[min_severity]
    return [f for f in findings if _SEV_RANK[f.severity] >= cut]


def _counts(findings: List[Finding]) -> Dict[str, int]:
    c: Dict[str, int] = {}
    for f in findings:
        c[f.severity] = c.get(f.severity, 0) + 1
    return c


def format_counts(counts: Dict[str, int]) -> str:
    """'2 error, 1 warning' (most severe first), or 'clean' — the one
    header formatter lint_report and debugger._lint_lines share."""
    return ", ".join(f"{counts[s]} {s}" for s in reversed(SEVERITIES)
                     if s in counts) or "clean"


def findings_for(program_uid: int) -> Optional[Dict]:
    """The latest lint record for a program uid (debugger annotations,
    /lint route), or None when the program was never linted."""
    return _LATEST.get(program_uid)


def summary() -> Dict[str, Any]:
    """JSON-ready view for the monitor server's ``/lint`` route."""
    return {"mode": _mode, "reports": dict(_LATEST)}


def lint_report(program: Program, findings: Optional[List[Finding]] = None,
                **kw) -> str:
    """Human-readable lint report: severity counts header + one line per
    finding (site, message, fix hint). With ``findings=None`` the
    verifier runs fresh at full verbosity (kwargs forwarded to
    ``lint``); ``debugger.pprint_program`` embeds the stored latest
    record instead of re-running."""
    if findings is None:
        kw.setdefault("min_severity", "debug")
        findings = lint(program, **kw)
    lines = [f"static lint ({len(program.global_block().ops)} ops, "
             f"checks: {','.join(registered_checks())}): "
             f"{format_counts(_counts(findings))}"]
    lines += [f"  {f}" for f in findings]
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# executor / build-site integration (the static_lint flag plane)
# ---------------------------------------------------------------------------

_mode = "warn"


def _sync_mode(_value=None):
    global _mode
    v = str(_flags.get_flag("static_lint")).strip().lower()
    if v not in ("off", "warn", "error"):
        _log.warning(
            "static_lint=%r is not one of off|warn|error; using 'warn'",
            v)
        v = "warn"
    if v != _mode:
        # a mode flip changes dispatch semantics (warn logs, error
        # raises): fingerprints linted under the old mode must re-lint,
        # or warn->error would wave known-broken programs through
        _SEEN.clear()
    _mode = v


_flags.watch_flag("static_lint", _sync_mode)


def lint_mode() -> str:
    return _mode


def lint_active() -> bool:
    """One boolean read — the executor's zero-allocation gate."""
    return _mode != "off"


# Canonical (core.fingerprint.program_fingerprint) signatures already
# linted pre-compile: a recompile of the same signature never re-lints.
# Content-keyed like the executor's compiled-entry cache — two
# identically-built programs share one lint run.
_SEEN: "collections.OrderedDict[str, bool]" = collections.OrderedDict()
_SEEN_CAP = 512


def _dispatch(findings: List[Finding], site: str):
    worst = [f for f in findings if f.severity in ("warning", "error")]
    for f in worst:
        _log.warning("static lint [%s]: %s", site, f)
    if _mode == "error":
        errs = [f for f in findings if f.severity == "error"]
        if errs:
            raise LintError(errs)


def _strategy_token(strategy) -> tuple:
    """Content fingerprint of a DistributedStrategy — THE canonical one
    (core.fingerprint.strategy_token), shared with the executor cache key
    and the compile report so the three subsystems can never drift. id() would alias a fresh strategy to a GC-reused address (the
    same hazard executor._latest_stacked pins references against);
    content keying also lets two equal strategies share one lint run."""
    return _fingerprint.strategy_token(strategy)


def lint_before_compile(program: Program,
                        feed_names: Sequence[str],
                        fetch_names: Sequence[str],
                        strategy=None,
                        site: str = "executor"):
    """Executor hook: verify once per (program, feeds, fetches,
    strategy) fingerprint, right before the first compile of that
    signature. Logs warning/error findings; raises LintError under
    ``static_lint=error``. Callers must gate on ``lint_active()``."""
    key = _fingerprint.fingerprint_for(
        ("lint", program._uid, program.version, tuple(feed_names),
         tuple(fetch_names), _strategy_token(strategy)),
        program, strategy=strategy, feed_sig=tuple(feed_names),
        fetch_names=fetch_names, extra=("lint",))
    if key in _SEEN:
        return
    findings = lint(program, feeds=feed_names, fetches=fetch_names,
                    strategy=strategy, min_severity="debug")
    # dispatch BEFORE caching the fingerprint: under static_lint=error a
    # raising dispatch must re-lint (and re-raise) on the next call, not
    # wave the broken program through to the compiler
    _dispatch(findings, site)
    _SEEN[key] = True
    while len(_SEEN) > _SEEN_CAP:
        _SEEN.popitem(last=False)


def lint_at_build(program: Program, strategy=None,
                  checks: Optional[Sequence[str]] = None,
                  site: str = "build"):
    """Build-site hook (CompiledProgram.with_strategy, contrib.Trainer):
    verify the freshly built program without feed/fetch context. Gated
    on ``lint_active()`` internally — call sites stay one-liners."""
    if not lint_active():
        return
    key = _fingerprint.fingerprint_for(
        ("lint-build", program._uid, program.version, site,
         _strategy_token(strategy)),
        program, strategy=strategy, extra=("lint-build", site))
    if key in _SEEN:
        return
    findings = lint(program, strategy=strategy, checks=checks,
                    min_severity="debug")
    _dispatch(findings, site)  # before caching — see lint_before_compile
    _SEEN[key] = True
    while len(_SEEN) > _SEEN_CAP:
        _SEEN.popitem(last=False)
