"""Program-level pass framework.

The TPU-native analog of the reference's IR pass registry
(reference: paddle/fluid/framework/ir/pass.h + ~45 registered passes).
Fusion/layout/memory passes are delegated to XLA by design (SURVEY.md
section 7 phase 4), so the passes that remain are PROGRAM rewrites —
AMP marking, quantization-aware-training insertion, inference folding,
pruning — and this module gives them one registry + pipeline API instead
of ad-hoc entry points:

    from paddle_tpu import passes
    passes.apply_pass("conv_bn_fuse", program, scope=scope)
    pm = passes.PassManager(["quant_aware", "amp"])
    pm.apply(program)

A pass is ``apply(program, scope=None, **kw) -> program`` (mutating in
place and returning the program; the return value allows rewriting
passes that build a new Program, e.g. inference pruning).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

_PASS_REGISTRY: Dict[str, Callable] = {}


def register_pass(name: str):
    """Decorator registering ``fn(program, scope=None, **kw) -> program``
    (reference: REGISTER_PASS, framework/ir/pass.h)."""

    def deco(fn):
        if name in _PASS_REGISTRY:
            raise ValueError(f"pass '{name}' registered twice")
        _PASS_REGISTRY[name] = fn
        return fn

    return deco


def registered_passes() -> List[str]:
    return sorted(_PASS_REGISTRY)


def get_pass(name: str) -> Callable:
    if name not in _PASS_REGISTRY:
        raise KeyError(
            f"unknown pass '{name}'; registered: {registered_passes()}"
        )
    return _PASS_REGISTRY[name]


def apply_pass(name: str, program, scope=None, **kw):
    out = get_pass(name)(program, scope=scope, **kw)
    return program if out is None else out


class PassManager:
    """Ordered pass pipeline (reference: ir/pass.h PassRegistry usage in
    details/build_strategy.cc:52-230)."""

    def __init__(self, names: Sequence[str] = ()):
        self.names = list(names)

    def append(self, name: str) -> "PassManager":
        self.names.append(name)
        return self

    def apply(self, program, scope=None, **kw):
        for n in self.names:
            program = apply_pass(n, program, scope=scope, **kw)
        return program


# --- built-in passes wrapping the existing rewrites ---


@register_pass("conv_bn_fuse")
def _conv_bn_fuse(program, scope=None, **kw):
    """Fold inference-mode batch norms into the preceding conv
    (transpiler.InferenceTranspiler)."""
    from paddle_tpu.transpiler import InferenceTranspiler

    InferenceTranspiler().transpile(program, scope)
    return program


@register_pass("quant_aware")
def _quant_aware(program, scope=None, weight_bits=8, activation_bits=8,
                 **kw):
    """Insert fake-quant STE ops before matmul/conv inputs
    (slim.quantization.QuantizationTransformPass)."""
    from paddle_tpu.slim.quantization import QuantizationTransformPass

    QuantizationTransformPass(
        weight_bits=weight_bits, activation_bits=activation_bits
    ).apply(program)
    return program


@register_pass("amp")
def _amp(program, scope=None, **kw):
    """Mark the program for bf16 AMP lowering (core/lowering.py reads
    ``program._amp`` at trace time)."""
    program._amp = True
    return program


@register_pass("instrument_numerics")
def _instrument_numerics(program, scope=None, vars=None, histogram_bins=0,
                         **kw):
    """Append the in-graph tensor-stats bundle (numerics.py): cheap
    on-device reductions (non-finite count, max-abs, rms, optional
    log2-magnitude histogram) over selected op outputs — activations,
    gradients, parameters — fetched by the executor as ONE auxiliary
    array per sampled step and decoded into pt_tensor_* instruments and
    NaN-provenance records. Apply after the program is fully built
    (minimize/clip/AMP included)."""
    from paddle_tpu import numerics

    numerics.instrument(program, vars=vars, histogram_bins=histogram_bins)
    return program


@register_pass("lint")
def _lint(program, scope=None, feeds=None, fetches=None, strategy=None,
          checks=None, **kw):
    """Static program verifier (analysis.py) in pass form: runs every
    registered check over the shared def-use index, meters + stores the
    findings (debugger.pprint_program / the /lint route show them), and
    logs warning/error findings — raising LintError instead when the
    ``static_lint`` flag is 'error'. The program itself is never
    mutated; the pass returns it unchanged so lint composes anywhere in
    a PassManager pipeline."""
    from paddle_tpu import analysis

    findings = analysis.lint(
        program, feeds=feeds, fetches=fetches, strategy=strategy,
        checks=checks, min_severity="debug")
    analysis._dispatch(findings, site="pass")
    return program


@register_pass("inference_prune")
def _inference_prune(program, scope=None, targets=None, feeds=None, **kw):
    """Prune to the inference subgraph reaching ``targets`` (io.py's
    save_inference_model pruning, exposed as a standalone pass)."""
    if targets is None:
        raise ValueError("inference_prune needs targets=[vars or names]")
    from paddle_tpu import io as _io

    return _io._prune_for_inference(program, feeds or [], targets)


@register_pass("fc_fuse")
def _fc_fuse(program, scope=None, fetch_targets=(), **kw):
    """Collapse mul + elementwise_add pairs into single fc ops
    (reference: framework/ir/fc_fuse_pass.cc). Program-level rewrite on
    the shared matcher (ir_pattern.match_chain): the mul's output must
    feed ONLY the add, the add's Y must be a 1-D bias that is already
    DEFINED at the mul's position (a parameter or an earlier op's
    output — the fc is spliced where the mul was, so a later-produced
    bias would be read before it exists), added on the TRAILING axis,
    and the mul must use the default y_num_col_dims (2-D W). Mostly
    useful for the sub-block interp path and smaller serialized
    programs — XLA fuses the pair anyway in whole-program compilation.
    The mul's intermediate (pre-bias) var is no longer produced after
    fusion, so fusion is skipped when it is persistable or named in
    ``fetch_targets``; fetch the fc output otherwise."""
    from paddle_tpu.framework import Operator
    from paddle_tpu.ir_pattern import BlockGraph, match_chain

    block = program.global_block()
    graph = BlockGraph(block)
    fetch_names = {
        f if isinstance(f, str) else f.name for f in fetch_targets
    }

    plans = []  # (mul idx, add idx, fused Operator)
    for i, j in match_chain(graph, ("mul",), "Out",
                            "elementwise_add", "X"):
        op, nxt = block.ops[i], block.ops[j]
        out = op.outputs["Out"][0]
        if graph.is_persistable(out) or out in fetch_names:
            continue
        y = nxt.inputs.get("Y", [None])[0]
        yv = block._find_var_recursive(y) if y else None
        xnc = int(op.attrs.get("x_num_col_dims", 1))
        add_axis = int(nxt.attrs.get("axis", -1))
        if (yv is not None and yv.shape is not None
                and len(yv.shape) == 1
                # the fused fc runs at the mul's position
                and graph.available_before(y, i)
                # bias must land on the TRAILING (column) axis: the
                # mul output is rank xnc+1
                and add_axis in (-1, xnc)
                # fc mirrors mul only for 2-D W (default y_num_col_dims)
                and int(op.attrs.get("y_num_col_dims", 1)) == 1):
            plans.append((i, j, Operator(
                block, "fc",
                inputs={"Input": list(op.inputs["X"]),
                        "W": list(op.inputs["Y"]),
                        "Bias": [y]},
                outputs={"Out": list(nxt.outputs["Out"])},
                attrs={"in_num_col_dims": xnc},
            )))

    if plans:
        replace = {i: fc for i, _, fc in plans}
        drop = {j for _, j, _ in plans}
        for i, _, _ in plans:
            # the pre-bias intermediate is no longer produced
            block.vars.pop(block.ops[i].outputs["Out"][0], None)
        block.ops[:] = [
            replace.get(idx, op) for idx, op in enumerate(block.ops)
            if idx not in drop
        ]
        program._bump_version()
    return program


# Op types safe to deduplicate / fold: deterministic pure functions of
# their inputs+attrs (no PRNG, no state updates, no side effects, no
# sub-blocks). Conservative by construction — unlisted types are left
# alone. Reference analogs: framework/ir/ (constant folding) and the
# executor-level CSE the reference gets from its SSA graph.
_PURE_OP_TYPES = frozenset({
    "scale", "cast", "reshape", "transpose", "unsqueeze", "squeeze",
    "expand", "slice", "concat", "stack", "split",
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow",
    "relu", "sigmoid", "tanh", "exp", "log", "sqrt", "square", "abs",
    "softmax", "log_softmax",
    "matmul", "mul",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min", "mean",
    "fill_constant", "fill_any_like", "assign_value", "range",
    "less_than", "less_equal", "greater_than", "greater_equal", "equal",
    "not_equal", "logical_and", "logical_or", "logical_not",
    "attn_bias", "one_hot", "lookup_table",
})

# Pure generators with NO inputs: their (type, attrs) alone determines
# the value, so they both seed constant folding and are CSE-able.
_CONST_GENERATORS = frozenset({"fill_constant", "assign_value", "range"})


def _unstable_vars(block):
    """Var names whose value is NOT a pure function of their name within
    the block — reassigned names (multiple writers: assign output=,
    increment in_place, a while op's Out carries) or names read before
    their (only) writer (a feed/outer var later overwritten). Name-keyed
    optimizations (CSE, constant folding) must not treat reads of these
    as referentially transparent: the same name denotes different values
    at different program points."""
    first_write = {}
    writers = {}
    first_read = {}
    for idx, op in enumerate(block.ops):
        for n in op.input_arg_names:
            first_read.setdefault(n, idx)
        for n in op.output_arg_names:
            writers[n] = writers.get(n, 0) + 1
            first_write.setdefault(n, idx)
    unstable = {n for n, c in writers.items() if c > 1}
    for n, w in first_write.items():
        if first_read.get(n, w + 1) < w:
            unstable.add(n)  # read-before-write: the name is reused
    return unstable


def _op_key(op):
    """Hashable identity of a pure op: (type, sorted inputs, sorted
    attrs its kernel sees: two ops that differ only in name scope
    compute the same value). None when any attr resists cheap stable
    serialization."""
    try:
        attrs = tuple(sorted((k, repr(v))
                             for k, v in op.compute_attrs().items()))
    except Exception:
        return None
    ins = tuple(sorted((slot, tuple(ns)) for slot, ns in op.inputs.items()))
    return (op.type, ins, attrs)


@register_pass("cse")
def _cse(program, scope=None, fetch_targets=(), **kw):
    """Common-subexpression elimination over the global block: two pure
    ops with identical (type, inputs, attrs) compute the same value, so
    the later one's outputs alias the earlier one's (consumers are
    renamed; the duplicate op is dropped). Whole-program XLA lowering
    gets this from XLA itself; this pass exists for SERIALIZED programs
    — inference artifacts and the sub-block interp path — where
    duplicate chains (e.g. per-layer rebuilt attention biases) would
    otherwise execute N times. Persistable or fetched outputs are never
    aliased away."""
    block = program.global_block()
    fetch_names = {f if isinstance(f, str) else f.name
                   for f in fetch_targets}
    unstable = _unstable_vars(block)
    seen = {}           # op key -> canonical op index
    rename = {}         # var name -> canonical var name
    drop = set()
    for idx, op in enumerate(block.ops):
        # apply pending renames so chained duplicates collapse
        # transitively in one pass
        if any(n in rename for ns in op.inputs.values() for n in ns):
            op.inputs = {
                slot: [rename.get(n, n) for n in ns]
                for slot, ns in op.inputs.items()
            }
        if op.type not in _PURE_OP_TYPES:
            continue
        # reads or writes of a reassigned name are position-dependent:
        # two textually identical ops can observe different values
        if any(n in unstable
               for ns in list(op.inputs.values()) + list(op.outputs.values())
               for n in ns):
            continue
        key = _op_key(op)
        if key is None:
            continue
        canon = seen.get(key)
        if canon is None:
            seen[key] = idx
            continue
        outs = [n for ns in op.outputs.values() for n in ns]
        if any(graph_is_persistable(block, n) or n in fetch_names
               for n in outs):
            continue
        canon_op = block.ops[canon]
        for slot, ns in op.outputs.items():
            for a, b in zip(ns, canon_op.outputs.get(slot, [])):
                rename[a] = b
        drop.add(idx)
    if drop:
        for idx in drop:
            for ns in block.ops[idx].outputs.values():
                for n in ns:
                    block.vars.pop(n, None)
        block.ops[:] = [op for idx, op in enumerate(block.ops)
                        if idx not in drop]
        program._bump_version()
    return program


def graph_is_persistable(block, name):
    v = block._find_var_recursive(name)
    return bool(v is not None and getattr(v, "persistable", False))


@register_pass("constant_fold")
def _constant_fold(program, scope=None, fetch_targets=(),
                   max_elems=4096, **kw):
    """Fold pure ops whose inputs are all compile-time constants
    (transitively rooted at fill_constant / assign_value / range) into
    ``assign_value`` literals, evaluated through the op kernels
    themselves (one source of truth for semantics; reference analog:
    the constant-folding IR pass). Results larger than ``max_elems``
    stay unfolded — giant literals would bloat the serialized program
    past what the fold saves."""
    import numpy as np

    from paddle_tpu.core import interp as _interp
    from paddle_tpu.framework import Operator

    block = program.global_block()
    fetch_names = {f if isinstance(f, str) else f.name
                   for f in fetch_targets}
    unstable = _unstable_vars(block)
    const_vals = {}     # var name -> np.ndarray
    replace = {}        # op idx -> Operator (assign_value) or None=drop
    for idx, op in enumerate(block.ops):
        if op.type not in _PURE_OP_TYPES:
            continue
        # a reassigned name is not a constant even when its first writer
        # is one (assign output= / increment / while carries rebind it)
        if any(n in unstable
               for ns in list(op.inputs.values()) + list(op.outputs.values())
               for n in ns):
            continue
        ins = [n for ns in op.inputs.values() for n in ns if n]
        if op.type not in _CONST_GENERATORS and (
                not ins or not all(n in const_vals for n in ins)):
            continue
        if op.type in _CONST_GENERATORS and ins:
            if not all(n in const_vals for n in ins):
                continue
        key = _op_key(op)
        if key is None:
            continue
        try:
            env = {n: const_vals[n] for n in ins}
            _interp.exec_ops([op], env, key=None, amp=False)
        except Exception:
            continue
        outs = [n for ns in op.outputs.values() for n in ns]
        vals = {n: np.asarray(env[n]) for n in outs}
        if any(v.size > max_elems for v in vals.values()):
            continue
        const_vals.update(vals)
        if op.type in _CONST_GENERATORS and len(outs) == 1:
            # already a literal; no rewrite needed, but it seeds folds
            continue
        if len(outs) == 1 and outs[0] not in fetch_names \
                and not graph_is_persistable(block, outs[0]):
            v = vals[outs[0]]
            replace[idx] = Operator(
                block, "assign_value", inputs={},
                outputs={"Out": [outs[0]]},
                attrs={"shape": list(v.shape),
                       "dtype": str(v.dtype),
                       "values": v.reshape(-1).tolist()})
    if replace:
        # ops whose outputs became dead literals' inputs are cleaned by
        # a follow-up inference_prune; here only the folds are applied
        block.ops[:] = [replace.get(idx, op)
                        for idx, op in enumerate(block.ops)]
        program._bump_version()
    return program
