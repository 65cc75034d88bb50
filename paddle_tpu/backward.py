"""Graph-time autodiff: append_backward.

Same contract as the reference (reference: python/paddle/fluid/backward.py:394):
walk the op list backwards from the loss, append ``<type>_grad`` ops into the
program, de-duplicate repeated gradients with ``sum`` ops (reference:
backward.py:135 ``_addup_repetitive_outputs_``), prune non-contributing ops
(reference: backward.py:579 ``_find_op_path_``). Unlike the reference there
are no per-op C++ GradOpDescMakers: the grad op descs follow the uniform
convention of core/autodiff.py and their kernels are derived with jax.vjp.

Recomputation (Fluid 1.6's ``checkpoints``; Chen et al. 2016,
arXiv:1604.06174). A builder marks variables (``framework.checkpoint`` /
``layers.checkpoint``; ``append_backward(checkpoints=[...])`` takes the
same list and both feed one path). The ops of the loss's path between two
marks are a SEGMENT. Walking the path in reverse, where a segment begins
its forward ops are appended AGAIN (role ``bwd``, the name scope of the
first run, outputs renamed ``<name>@RECOMPUTE@<segment>``) and its grad
ops read the renamed values, so nothing the segment made in the forward
pass is read by the backward pass: only the marks, the parameters and the
feeds are. What holds the replay to that:

- everything it reads that is not state (the mark, a feed) passes ONE
  ``recompute_barrier`` op (``jax.lax.optimization_barrier``) together
  with the gradients that arrive at the segment's end: XLA can then
  neither merge the replay with the first run nor start it before the
  backward pass has come down to the segment;
- an op with a PRNG key replays its first run's (``forward_op_idx``);
- only the ops whose values a grad op lists among its inputs are
  appended again (a generic grad op lists its forward's outputs too; what
  its kernel does not read of them, XLA drops);
- the ops before the first mark and behind the last are not replayed
  (behind the last mark the backward pass begins at once);
- a Program without marks gets the op list it always got, op for op.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from paddle_tpu import monitor as _monitor
from paddle_tpu.core.autodiff import GRAD_SLOT_PREFIX
from paddle_tpu.core.lowering import resolve_op_def
from paddle_tpu.core.registry import GRAD_OP_SUFFIX
from paddle_tpu.framework import (
    OP_NAMESCOPE_ATTR,
    Block,
    Operator,
    Parameter,
    Variable,
    checkpoint_names,
    grad_var_name,
    op_role_guard,
)

RECOMPUTE_TAG = "@RECOMPUTE@"

_M_RECOMPUTE_OPS = _monitor.counter(
    "pt_backward_recompute_ops_total",
    "forward ops append_backward appended again (role bwd) so that a "
    "segment's grad ops read a replay and not the first run's values, by "
    "program and segment (the segments of the loss's path between two "
    "checkpoints, counted from the first mark)")
_M_CHECKPOINTS = _monitor.counter(
    "pt_backward_checkpoints_total",
    "checkpoint marks append_backward was given, by program and used "
    "(true: the mark is made by an op on the loss's path and bounds a "
    "segment; false: it is not, and is ignored)")


def _is_float_var(block: Block, name: str) -> bool:
    v = block._find_var_recursive(name)
    if v is None or v.dtype is None:
        return True
    return np.issubdtype(np.dtype(v.dtype), np.floating)


def _find_op_path(block: Block, loss: Variable) -> List[int]:
    """Indices of ops contributing to the loss, in forward order."""
    needed: Set[str] = {loss.name}
    marked: List[int] = []
    for idx in range(len(block.ops) - 1, -1, -1):
        op = block.ops[idx]
        if any(n in needed for n in op.output_arg_names):
            marked.append(idx)
            needed.update(n for n in op.input_arg_names if n)
    marked.reverse()
    return marked


def _segments(block: Block, op_path: List[int], marks: List[str]):
    """-> ({op index: segment number} for the ops of ``op_path`` between
    two marks, the marks on the path, the marks off it). A mark is on the
    path where an op of the path writes it; the segments are counted from
    the first mark."""
    last_writer: Dict[str, int] = {}
    for pos, idx in enumerate(op_path):
        for n in block.ops[idx].output_arg_names:
            last_writer[n] = pos
    used = [m for m in marks if m in last_writer]
    unused = [m for m in marks if m not in last_writer]
    cuts = sorted({last_writer[m] for m in used})
    seg_of: Dict[int, int] = {}
    for k, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
        for pos in range(lo + 1, hi + 1):
            seg_of[op_path[pos]] = k
    return seg_of, used, unused


class _Replay:
    """One segment while its grad ops are appended: the names its grad
    ops read in place of the first run's, and where the replay goes."""

    def __init__(self, block, number, idxs):
        self.block, self.number, self.idxs = block, number, idxs
        ops = [block.ops[i] for i in idxs]
        self.made = {n for op in ops for n in op.output_arg_names if n}
        self.read = {n for op in ops for n in op.input_arg_names if n}
        # what the segment makes, and what it reads that is not state
        self.rename: Dict[str, str] = {}
        for n in sorted(self.made | self.read):
            v = block._find_var_recursive(n)
            if n in self.made or not (v is not None and v.persistable):
                self.rename[n] = self.copy(n)

    def begin(self, grads):
        """The segment's grad ops follow: ``grads``, the gradients that
        arrive at its end (each summed by now), go behind the barrier;
        the replay will go where the block ends now."""
        self.grads_in = {g: self.copy(g) for g in grads}
        self.at = len(self.block.ops)

    def copy(self, name) -> str:
        """A variable like ``name`` under the segment's tag."""
        var = self.block._find_var_recursive(name)
        new = f"{name}{RECOMPUTE_TAG}{self.number}"
        self.block.create_var(
            name=new, shape=var.shape if var is not None else None,
            dtype=var.dtype if var is not None else "float32",
            stop_gradient=getattr(var, "stop_gradient", False))
        return new

    def renamed(self, op: Operator, defined=None) -> Operator:
        """``op`` reading and writing the replay's names: what its grad
        op is made from (not appended). ``defined``: the names the
        replay has made so far; an op that reads a value the segment
        writes LATER (a running statistic updated in place) reads the
        first run's."""
        def sub(slots, known):
            return {s: [self.rename.get(n, n)
                        if known is None or n in known else n for n in ns]
                    for s, ns in slots.items()}
        return Operator(self.block, op.type, sub(op.inputs, defined),
                        sub(op.outputs, None), dict(op.attrs))

    def insert(self) -> int:
        """Put the barrier and the replay in front of the segment's grad
        ops (everything appended since ``at``): the ops whose values
        those read, and what these read in turn. -> ops appended again."""
        block = self.block
        back = {v: k for k, v in self.rename.items()}
        needed = {back[n] for op in block.ops[self.at:]
                  for n in op.input_arg_names if n in back}
        keep: List[int] = []
        for i in reversed(self.idxs):
            op = block.ops[i]
            if any(n in needed for n in op.output_arg_names):
                keep.append(i)
                needed.update(n for n in op.input_arg_names if n)
        ops = []
        defined = set(self.rename) - self.made
        for i in reversed(keep):
            op = self.renamed(block.ops[i], defined)
            defined.update(block.ops[i].output_arg_names)
            # the key of its first run, as a grad op replays it
            op.attrs.setdefault("forward_op_idx", i)
            ops.append(op)
        # (with nothing kept, a grad op may still read the barrier's names)
        outside = [n for n in self.rename
                   if n in needed and n not in self.made]
        xs = outside + list(self.grads_in)
        if xs:
            outs = ([self.rename[n] for n in outside]
                    + list(self.grads_in.values()))
            ops.insert(0, Operator(block, "recompute_barrier",
                                   {"X": xs}, {"Out": outs}, {}))
        block.ops[self.at:self.at] = ops
        block.program._bump_version()
        return len(ops) - bool(xs)


def append_backward(
    loss: Variable,
    parameter_list: Optional[Sequence[str]] = None,
    no_grad_set: Optional[Set[str]] = None,
    callbacks=None,
    checkpoints: Optional[Sequence] = None,
) -> List[Tuple[Parameter, Variable]]:
    """``checkpoints``: variables (or names) to mark besides the ones the
    Program carries (``framework.checkpoint``); the module docstring says
    what a mark does."""
    # everything emitted here (the *_grad ops, the gradient sums and
    # fills between them, a segment's replay) is the step's backward phase
    with _monitor.span("backward.append_backward"), \
            op_role_guard(loss.block.program, "bwd"):
        return _append_backward(loss, parameter_list, no_grad_set,
                                checkpoint_names(loss.block.program,
                                                 checkpoints))


def _append_backward(loss, parameter_list, no_grad_set, marks=()):
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())

    op_path = _find_op_path(block, loss)
    seg_of: Dict[int, int] = {}
    prog = f"program{program._uid}"
    if marks:
        seg_of, used, unused = _segments(block, op_path, list(marks))
        for flag, names in (("true", used), ("false", unused)):
            if names:
                _M_CHECKPOINTS.inc(len(names), labels={"program": prog,
                                                       "used": flag})
    # the segment whose grad ops are being appended (None outside one)
    replay: Optional[_Replay] = None
    # a replay's name -> the first run's: a gradient is named after that
    first_run: Dict[str, str] = {}

    def leave_segment():
        nonlocal replay
        if replay is not None:
            with _monitor.span("backward.recompute"):
                n = replay.insert()
            if n:
                _M_RECOMPUTE_OPS.inc(n, labels={
                    "program": prog, "segment": str(replay.number)})
            replay = None

    def enter_segment(number):
        nonlocal replay
        new = _Replay(block, number,
                      [i for i in op_path if seg_of.get(i) == number])
        # a value the segment hands on and does not read itself: every
        # reader's grad op is behind us, its gradient is whole
        new.begin([g for n in sorted(new.made - new.read)
                   if (g := lookup(n))])
        first_run.update((v, k) for k, v in new.rename.items())
        behind_barrier.update(new.grads_in)
        replay = new

    # Track gradient producers: target grad name -> list of written names.
    producers: Dict[str, List[str]] = defaultdict(list)
    finalized: Set[str] = set()

    # a gradient that passed a segment's barrier: read under its new name
    behind_barrier: Dict[str, str] = {}

    def provide(var_name: str) -> str:
        g = grad_var_name(first_run.get(var_name, var_name))
        k = len(producers[g])
        name = g if k == 0 else f"{g}@RENAME@{k}"
        producers[g].append(name)
        return name

    def lookup(var_name: str) -> Optional[str]:
        g = _lookup(first_run.get(var_name, var_name))
        return behind_barrier.get(g, g)

    def _lookup(var_name: str) -> Optional[str]:
        g = grad_var_name(var_name)
        lst = producers.get(g)
        if not lst:
            return None
        if len(lst) > 1 and g not in finalized:
            # A row-sparse marker among the partials cannot be summed with
            # dense partials (its array is never materialized). Catches the
            # ordering the sparse grad maker's own @RENAME check misses —
            # the sparse lookup claiming the clean name first.
            for n in lst:
                v = block._find_var_recursive(n)
                if v is not None and getattr(v, "is_selected_rows", False):
                    raise ValueError(
                        f"parameter '{var_name}' has both a row-sparse "
                        f"gradient (is_sparse=True lookup) and other dense "
                        f"gradient contributions; they cannot be combined. "
                        f"Use is_sparse=False for this table."
                    )
            # Combine partial gradients (reference: backward.py:135).
            block.create_var(name=g, dtype=_var_dtype(var_name))
            block.append_op("sum", inputs={"X": list(lst)}, outputs={"Out": g})
            finalized.add(g)
        return g

    def _var_dtype(name: str):
        v = block._find_var_recursive(name)
        return v.dtype if v is not None else "float32"

    def should_skip(name: str, slot: str, opdef) -> bool:
        name = first_run.get(name, name)
        if not name or name in no_grad:
            return True
        v = block._find_var_recursive(name)
        if v is not None and v.stop_gradient:
            return True
        if opdef.diff_inputs is not None and slot not in opdef.diff_inputs:
            return True
        return not _is_float_var(block, name)

    # Seed: d(loss)/d(loss) = 1.
    loss_grad = grad_var_name(loss.name)
    block.create_var(
        name=loss_grad, shape=loss.shape, dtype=loss.dtype, persistable=False
    )
    block.append_op(
        "fill_any_like",
        inputs={"X": loss},
        outputs={"Out": loss_grad},
        attrs={"value": 1.0},
    )
    producers[loss_grad].append(loss_grad)
    finalized.add(loss_grad)

    for idx in reversed(op_path):
        op = block.ops[idx]
        opdef = resolve_op_def(op.type)
        if seg_of.get(idx) != (replay.number if replay else None):
            leave_segment()
            if idx in seg_of:
                enter_segment(seg_of[idx])
        if replay is not None:
            op = replay.renamed(op)
        if opdef.no_grad:
            if op.type == "while" and any(
                lookup(n)
                for names in op.outputs.values() for n in names if n
            ):
                raise RuntimeError(
                    "Cannot backprop through a data-dependent `while` "
                    "loop: XLA's While is not reverse-differentiable, so "
                    "its gradient would be silently dropped. Either (a) "
                    "give the loop an iteration bound — "
                    "While(cond, max_trip_count=N) lowers to a "
                    "differentiable fixed-trip scan with dead iterations "
                    "masked — or (b) rewrite the recurrence with "
                    "layers.StaticRNN / the scan op, the differentiable "
                    "loop primitives. (The reference trains through "
                    "while_op via WhileGradOp, "
                    "operators/controlflow/while_op.cc:43; "
                    "bounded_while is the TPU-native equivalent.)"
                )
            continue

        out_grads: Dict[str, List[str]] = {}
        any_grad = False
        for slot, names in op.outputs.items():
            gs = []
            for n in names:
                g = lookup(n) if n else None
                gs.append(g or "")
                any_grad = any_grad or bool(g)
            out_grads[slot] = gs
        if not any_grad:
            continue

        if opdef.grad_maker is not None:
            descs = opdef.grad_maker(op, block, out_grads, provide, should_skip)
            if descs is not None:  # None = defer to the generic emitter
                for d in descs:
                    if op.namescope:  # as the generic emitter's attr copy
                        d = {**d, "attrs": {
                            OP_NAMESCOPE_ATTR: op.namescope,
                            **(d.get("attrs") or {})}}
                    block.append_op(**d)
                continue

        g_inputs = dict(op.inputs)
        for slot, names in op.outputs.items():
            g_inputs.setdefault(slot, names)
        for slot, gs in out_grads.items():
            g_inputs[GRAD_SLOT_PREFIX + slot] = gs

        g_outputs: Dict[str, List[str]] = {}
        emitted = False
        for slot, names in op.inputs.items():
            outs = []
            for n in names:
                if should_skip(n, slot, opdef):
                    outs.append("")
                else:
                    gname = provide(n)
                    src = block._find_var_recursive(n)
                    block.create_var(
                        name=gname,
                        shape=src.shape if src is not None else None,
                        dtype=src.dtype if src is not None else "float32",
                    )
                    outs.append(gname)
                    emitted = True
            g_outputs[GRAD_SLOT_PREFIX + slot] = outs
        if not emitted:
            continue

        attrs = dict(op.attrs)
        attrs["fwd_input_slots"] = list(op.inputs.keys())
        attrs["fwd_output_slots"] = list(op.outputs.keys())
        attrs["forward_op_idx"] = idx
        block.append_op(
            op.type + GRAD_OP_SUFFIX,
            inputs=g_inputs,
            outputs=g_outputs,
            attrs=attrs,
        )

    leave_segment()

    # Finalize every gradient with multiple partial producers, whether or not
    # something downstream consumed it (calc_gradient reads them directly).
    suffix_len = len(grad_var_name(""))
    for gname, lst in list(producers.items()):
        if len(lst) > 1 and gname not in finalized:
            lookup(gname[:-suffix_len])

    # Collect (param, grad) pairs.
    if parameter_list is not None:
        params = [
            block.var(p) if isinstance(p, str) else p for p in parameter_list
        ]
    else:
        params = [p for p in program.all_parameters() if p.trainable]

    result = []
    for p in params:
        g = lookup(p.name)
        if g is None:
            continue
        result.append((p, block.var(g)))
        program._param_grad_map[p.name] = g
    return result


def calc_gradient(targets, inputs, target_gradients=None, no_grad_set=None):
    """Gradients of targets w.r.t. arbitrary inputs (reference: backward.py:619)."""
    if isinstance(targets, Variable):
        targets = [targets]
    if isinstance(inputs, Variable):
        inputs = [inputs]
    assert len(targets) == 1, "calc_gradient currently supports one target"
    block = targets[0].block
    append_backward(targets[0], no_grad_set=no_grad_set,
                    parameter_list=[])
    outs = []
    for v in inputs:
        g = grad_var_name(v.name)
        outs.append(block.var(g) if block.has_var(g) else None)
    return outs


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    return calc_gradient(targets, inputs, target_gradients, no_grad_set)
