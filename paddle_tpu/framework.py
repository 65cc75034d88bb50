"""Program IR: Program / Block / Operator / Variable / Parameter.

TPU-native re-design of the reference's Python graph builder
(reference: python/paddle/fluid/framework.py:366,925,1370,2705,3481).
The programming model is the same define-then-run contract — Python appends
OpDescs into blocks of a serializable Program — but:

- Shape/dtype inference is abstract evaluation of the registered JAX kernel
  (``jax.eval_shape``) instead of per-op C++ InferShape.
- There is no LoD; variable-length data is padded/bucketed host-side and
  carried as dense tensors plus masks (XLA static-shape discipline,
  SURVEY.md section 5).
- Execution happens by lowering a whole block to one XLA computation
  (see core/lowering.py), so the Program is a *staging* IR, not an
  interpreter instruction list.
"""

from __future__ import annotations

import hashlib

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu import monitor as _monitor
from paddle_tpu import unique_name
from paddle_tpu.core.registry import (
    GRAD_OP_SUFFIX,
    GRAD_SUFFIX,
    get_op_def,
    has_op,
)
from paddle_tpu.proto import framework_pb2 as pb

# Sentinel used to stand in for a symbolic (-1) batch dim during abstract
# shape inference. Prime and unlikely to appear as a real static dim.
_BATCH_SENTINEL = 997

# Attrs that NAME an op and never reach its kernel (reference:
# framework/op_proto_maker.h kOpRoleAttrName / kOpNameScopeAttrName):
# the phase of the step the op belongs to ("bwd" and "opt" are recorded,
# a forward op carries no role) and the name scope it was appended in.
# core/interp.exec_ops lowers each op under
# jax.named_scope("<phase>/<scope>/<op.type>"), which is how the device
# trace gets the program's names; Operator.compute_attrs() is what a
# kernel, shape inference and CSE see.
OP_ROLE_ATTR = "op_role"
OP_NAMESCOPE_ATTR = "op_namescope"
OP_META_ATTRS = (OP_ROLE_ATTR, OP_NAMESCOPE_ATTR)
OP_ROLES = ("fwd", "bwd", "opt")


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def convert_np_dtype_to_dtype_(dtype) -> str:
    """Canonicalize any dtype spec to a numpy dtype name string."""
    if isinstance(dtype, str) and dtype in ("bfloat16",):
        return "bfloat16"
    try:
        return np.dtype(dtype).name
    except TypeError:
        # jax dtypes like jnp.bfloat16
        return np.dtype(getattr(dtype, "dtype", dtype)).name


class Variable:
    """A named tensor in a Block (reference: framework.py:366)."""

    def __init__(
        self,
        block: "Block",
        name: str,
        shape: Optional[Sequence[int]] = None,
        dtype: Any = "float32",
        persistable: bool = False,
        stop_gradient: bool = False,
        is_parameter: bool = False,
        trainable: bool = True,
        kind: int = pb.VarDesc.DENSE_TENSOR,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(d) for d in shape) if shape is not None else None
        self.dtype = convert_np_dtype_to_dtype_(dtype) if dtype is not None else None
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_parameter = is_parameter
        self.trainable = trainable
        self.kind = kind
        # set by layers that carry a sequence mask alongside padded data
        self.mask_name: Optional[str] = None

    @property
    def grad_name(self) -> str:
        return grad_var_name(self.name)

    def to_proto(self) -> pb.VarDesc:
        d = pb.VarDesc(name=self.name, kind=self.kind)
        if self.dtype is not None:
            d.dtype = self.dtype
        if self.shape is not None:
            d.shape.extend(self.shape)
        d.persistable = self.persistable
        d.stop_gradient = self.stop_gradient
        d.is_parameter = self.is_parameter
        d.trainable = self.trainable
        return d

    def __repr__(self):
        return (
            f"Var({self.name}, shape={self.shape}, dtype={self.dtype}"
            + (", persistable" if self.persistable else "")
            + (", stop_gradient" if self.stop_gradient else "")
            + ")"
        )

    __str__ = __repr__

    # numpy-style conveniences used by model code
    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def astype(self, dtype):
        from paddle_tpu import layers

        return layers.cast(self, dtype)

    def _binary(self, other, op, reverse=False):
        from paddle_tpu import layers

        if not isinstance(other, Variable):
            other = layers.fill_constant(
                shape=[1], dtype=self.dtype, value=float(other)
            )
        a, b = (other, self) if reverse else (self, other)
        return layers.elementwise_op(op, a, b)

    def __add__(self, o):
        return self._binary(o, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elementwise_sub")

    def __rsub__(self, o):
        return self._binary(o, "elementwise_sub", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "elementwise_div")

    def __rtruediv__(self, o):
        return self._binary(o, "elementwise_div", reverse=True)

    def __neg__(self):
        from paddle_tpu import layers

        return layers.scale(self, scale=-1.0)


class Parameter(Variable):
    """A trainable persistable variable (reference: framework.py:3481)."""

    def __init__(self, block, name, shape, dtype, **kwargs):
        self.initializer = kwargs.pop("initializer", None)
        self.regularizer = kwargs.pop("regularizer", None)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.do_model_average = kwargs.pop("do_model_average", None)
        trainable = kwargs.pop("trainable", True)
        super().__init__(
            block,
            name,
            shape=shape,
            dtype=dtype,
            persistable=True,
            stop_gradient=not trainable,
            is_parameter=True,
            trainable=trainable,
            **kwargs,
        )


class Operator:
    """One op invocation: type + slot-keyed inputs/outputs + attrs
    (reference: framework.py:925)."""

    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: Optional[Dict[str, Any]] = None,
        outputs: Optional[Dict[str, Any]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = _normalize_slots(inputs)
        self.outputs: Dict[str, List[str]] = _normalize_slots(outputs)
        self.attrs: Dict[str, Any] = dict(attrs or {})
        role = block.program._op_role
        if role != "fwd":  # a grad op copies its forward's attrs: override
            self.attrs[OP_ROLE_ATTR] = role
        if _name_scope_ and OP_NAMESCOPE_ATTR not in self.attrs:
            self.attrs[OP_NAMESCOPE_ATTR] = "/".join(_name_scope_)

    @property
    def role(self) -> str:
        """The phase of the step this op belongs to: fwd, bwd or opt."""
        return self.attrs.get(OP_ROLE_ATTR, "fwd")

    @property
    def namescope(self) -> str:
        """The ``name_scope`` path the op was appended in ("" outside
        any); a grad op carries its forward's."""
        return self.attrs.get(OP_NAMESCOPE_ATTR, "")

    def compute_attrs(self) -> Dict[str, Any]:
        """The attrs the op's kernel sees: all but the naming ones."""
        return {k: v for k, v in self.attrs.items()
                if k not in OP_META_ATTRS}

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self) -> List[str]:
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self) -> List[str]:
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def _set_attr(self, name: str, val):
        self.attrs[name] = val

    def to_proto(self) -> pb.OpDesc:
        d = pb.OpDesc(type=self.type)
        for slot, names in self.inputs.items():
            v = d.inputs.add()
            v.parameter = slot
            v.arguments.extend(names)
        for slot, names in self.outputs.items():
            v = d.outputs.add()
            v.parameter = slot
            v.arguments.extend(names)
        for k, val in self.attrs.items():
            a = d.attrs.add()
            a.name = k
            _attr_to_proto(a, val)
        return d

    def __repr__(self):
        ins = ", ".join(f"{s}={n}" for s, n in self.inputs.items())
        outs = ", ".join(f"{s}={n}" for s, n in self.outputs.items())
        return f"{{{outs}}} = {self.type}({ins}) attrs={self.attrs}"


def _normalize_slots(slots) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    for slot, v in (slots or {}).items():
        if v is None:
            continue
        if isinstance(v, (Variable, str)):
            v = [v]
        names = [x.name if isinstance(x, Variable) else str(x) for x in v]
        if names:
            out[slot] = names
    return out


def _attr_to_proto(a: pb.OpDesc.Attr, val):
    if isinstance(val, bool):
        a.type, a.b = pb.BOOLEAN, val
    elif isinstance(val, int):
        a.type, a.l = pb.LONG, val
    elif isinstance(val, float):
        a.type, a.float64 = pb.FLOAT64, val
    elif isinstance(val, str):
        a.type, a.s = pb.STRING, val
    elif isinstance(val, Block):
        a.type, a.block_idx = pb.BLOCK, val.idx
    elif isinstance(val, (list, tuple)):
        if all(isinstance(x, bool) for x in val) and val:
            a.type = pb.BOOLEANS
            a.bools.extend(val)
        elif all(isinstance(x, int) for x in val):
            a.type = pb.LONGS
            a.longs.extend(val)
        elif all(isinstance(x, float) for x in val):
            a.type = pb.FLOATS
            a.floats.extend(float(x) for x in val)
        elif all(isinstance(x, str) for x in val):
            a.type = pb.STRINGS
            a.strings.extend(val)
        elif all(isinstance(x, Block) for x in val):
            a.type = pb.BLOCKS
            a.blocks_idx.extend(b.idx for b in val)
        else:
            raise TypeError(f"unsupported list attr {val!r}")
    else:
        raise TypeError(f"unsupported attr {val!r} ({type(val)})")


def _attr_from_proto(a: pb.OpDesc.Attr, program: "Program"):
    t = a.type
    if t == pb.BOOLEAN:
        return a.b
    if t == pb.LONG:
        return int(a.l)
    if t == pb.INT:
        return int(a.i)
    if t == pb.FLOAT:
        return float(a.f)
    if t == pb.FLOAT64:
        return float(a.float64)
    if t == pb.STRING:
        return a.s
    if t == pb.BLOCK:
        return program.blocks[a.block_idx]
    if t == pb.BOOLEANS:
        return list(a.bools)
    if t == pb.LONGS:
        return [int(x) for x in a.longs]
    if t == pb.INTS:
        return [int(x) for x in a.ints]
    if t == pb.FLOATS:
        return [float(x) for x in a.floats]
    if t == pb.STRINGS:
        return list(a.strings)
    if t == pb.BLOCKS:
        return [program.blocks[i] for i in a.blocks_idx]
    raise TypeError(f"unsupported proto attr type {t}")


def _canonical_attr_bytes(val) -> bytes:
    """Deterministic cross-process rendering of one op attr for
    Program.content_digest. Blocks render as their index (the block
    content itself is digested in block order), arrays as a data digest,
    floats via repr (full precision)."""
    if isinstance(val, Block):
        return f"block:{val.idx}".encode()
    if isinstance(val, np.ndarray):
        return (f"ndarray:{val.shape}:{val.dtype}:"
                f"{hashlib.sha256(np.ascontiguousarray(val).tobytes()).hexdigest()[:16]}"
                ).encode()
    if isinstance(val, (list, tuple)):
        return b"[" + b",".join(_canonical_attr_bytes(x) for x in val) + b"]"
    return repr(val).encode()  # floats via repr: full precision


class Block:
    """An ordered op list + var table (reference: framework.py:1370)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self) -> Optional["Block"]:
        return None if self.parent_idx < 0 else self.program.blocks[self.parent_idx]

    # --- variables ---

    def create_var(self, name: Optional[str] = None, **kwargs) -> Variable:
        if name is None:
            name = unique_name.generate("tmp")
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name, **kwargs)
        self.vars[name] = v
        return v

    def create_parameter(self, name, shape, dtype, **kwargs) -> Parameter:
        p = Parameter(self, name, shape, dtype, **kwargs)
        self.vars[name] = p
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError(f"variable '{name}' not found in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        b: Optional[Block] = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # --- ops ---

    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        self.program._bump_version()
        self._infer_shapes(op)
        return op

    def _prepend_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._bump_version()
        self._infer_shapes(op)
        return op

    def _infer_shapes(self, op: Operator):
        """Abstract-eval the kernel to fill output var shapes/dtypes."""
        outs, gap = infer_op_outputs(self, op)
        if outs is None:
            # Previously a silent no-op: ops with no registered shape
            # function (or missing input metadata) left their outputs
            # shapeless with no signal. Record the gap so the static
            # verifier (analysis.py) can report inference coverage
            # honestly, and log once per (op_type, kind) at debug level.
            if gap is not None:
                _note_infer_gap(op.type, gap)
            return
        try:
            apply_inferred_outputs(self, op, outs)
        except Exception as e:
            # a kernel returning a malformed result structure must stay
            # an advisory gap (real shapes resolve at lowering), not a
            # build abort
            _note_infer_gap(op.type,
                            f"eval_failed:{type(e).__name__}: {e}")

    def to_proto(self) -> pb.BlockDesc:
        d = pb.BlockDesc(idx=self.idx, parent_idx=self.parent_idx)
        for v in self.vars.values():
            d.vars.append(v.to_proto())
        for op in self.ops:
            d.ops.append(op.to_proto())
        return d

    def __repr__(self):
        lines = [f"block {self.idx} (parent {self.parent_idx}):"]
        lines += [f"  {v}" for v in self.vars.values()]
        lines += [f"  {op}" for op in self.ops]
        return "\n".join(lines)


# (op_type, gap kind) pairs where abstract shape inference could not run
# — the coverage ledger behind analysis.py's debug-level findings. Kinds:
# 'no_kernel' (op type has no registered compute), 'missing_input_meta'
# (an input var lacks shape/dtype), 'eval_failed:<Error>' (the abstract
# eval itself raised). Bounded by the op-type vocabulary.
_SHAPE_INFER_GAPS: set = set()


def shape_infer_gaps() -> set:
    """Snapshot of recorded inference-coverage gaps (see above)."""
    return set(_SHAPE_INFER_GAPS)


def _note_infer_gap(op_type: str, gap: str):
    # ledger + once-per-signature dedup key on the 'eval_failed:<Type>'
    # prefix; the logged line keeps the full diagnostic message
    sig = (op_type, gap.split(": ", 1)[0])
    if sig in _SHAPE_INFER_GAPS:
        return
    _SHAPE_INFER_GAPS.add(sig)
    import logging

    log = logging.getLogger("paddle_tpu")
    if gap.startswith("eval_failed"):
        # a raising kernel is build-time breakage worth a warning
        log.warning(
            "shape inference failed for op '%s': %s "
            "(advisory; real shapes resolved at lowering)", op_type, gap)
    else:
        log.debug("shape inference skipped for op '%s': %s", op_type, gap)


def infer_op_outputs(block: "Block", op: Operator):
    """Abstract-eval ``op``'s kernel over the block's declared metadata.

    Returns ``(outs, gap)``: ``outs`` maps output slot -> list of
    ShapeDtypeStructs (``None`` when inference could not run, with
    ``gap`` naming why — see ``_SHAPE_INFER_GAPS`` kinds). Shared by
    ``Block._infer_shapes`` (build-time advisory fill) and the static
    verifier's whole-program shape/dtype re-check (analysis.py), so the
    two can never disagree about an op's inferred metadata."""
    if not has_op(op.type):
        if op.type.endswith(GRAD_OP_SUFFIX) and \
                has_op(op.type[: -len(GRAD_OP_SUFFIX)]):
            # derived at lowering by autodiff from the forward kernel;
            # shapes mirror the differentiated inputs
            return None, "autodiff_grad"
        return None, "no_kernel"
    opdef = get_op_def(op.type)
    try:
        import jax

        ins = {}
        for slot, names in op.inputs.items():
            specs = []
            for n in names:
                v = block._find_var_recursive(n)
                if v is None or v.shape is None or v.dtype is None:
                    return None, "missing_input_meta"
                shape = tuple(
                    _BATCH_SENTINEL if d == -1 else d for d in v.shape
                )
                specs.append(jax.ShapeDtypeStruct(shape, np.dtype(v.dtype)))
            ins[slot] = specs

        kwargs = {}
        if opdef.needs_rng:
            kwargs["rng"] = jax.random.PRNGKey(0)

        outs = jax.eval_shape(
            lambda i: opdef.compute(i, op.compute_attrs(), **kwargs), ins
        )
        return outs, None
    except Exception as e:
        # the message carries the real diagnostic (broadcast shapes,
        # bad attr, ...); _note_infer_gap dedups on the prefix only
        return None, f"eval_failed:{type(e).__name__}: {e}"


def apply_inferred_outputs(block: "Block", op: Operator, outs) -> None:
    """Write ``infer_op_outputs`` results back into the block's var
    metadata (slot -> list of ShapeDtypeStructs, extra/None entries
    skipped). Raises on malformed kernel results — callers decide
    whether that is advisory (``Block._infer_shapes``) or a reportable
    coverage gap (analysis.py)."""
    for slot, names in op.outputs.items():
        results = outs.get(slot, [])
        for n, r in zip(names, results):
            if r is None:
                continue
            v = block._find_var_recursive(n)
            if v is None:
                v = block.create_var(name=n)
            v.shape = tuple(
                -1 if d == _BATCH_SENTINEL else d for d in r.shape
            )
            v.dtype = np.dtype(r.dtype).name


class Program:
    """A list of blocks; block 0 is global (reference: framework.py:2705)."""

    _uid_counter = 0

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0, -1)]
        self.current_block_idx = 0
        # Monotonic global uid: executor cache keys use this instead of
        # id() (id reuse after GC could alias a stale compiled entry).
        Program._uid_counter += 1
        self._uid = Program._uid_counter
        self._version = 0
        self.random_seed: Optional[int] = None
        # bf16 mixed-precision execution flag (see paddle_tpu/amp.py)
        self._amp = False
        # role recorded on ops appended now (OP_ROLE_ATTR; op_role_guard)
        self._op_role = "fwd"
        # populated by append_backward: {param_name: grad_name}
        self._param_grad_map: Dict[str, str] = {}
        # state a training step moves without a gradient (a router's
        # selection bias): op specs a layer leaves here, which
        # Optimizer.apply_gradients appends behind the parameters'
        # updates under role "opt". A program without an optimizer (an
        # eval clone) never runs them.
        self._step_updates: List[Dict[str, Any]] = []
        # the variables a builder marked for recomputation (checkpoint
        # below): names, in the order they were marked.
        # backward.append_backward replays the ops between two of them
        self._checkpoints: List[str] = []
        # version-keyed def-use index cache (analysis.DefUseIndex per
        # block); every _bump_version invalidates it implicitly
        self._def_use_cache: Optional[tuple] = None
        # version-keyed content digest cache (content_digest below)
        self._content_digest_cache: Optional[tuple] = None

    def _bump_version(self):
        self._version += 1

    @property
    def version(self) -> int:
        return self._version

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def all_parameters(self) -> List[Parameter]:
        return [v for b in self.blocks for v in b.all_parameters()]

    def def_use_index(self) -> Dict[int, Any]:
        """{block idx -> analysis.DefUseIndex} for the whole program,
        cached on the program and invalidated by any version bump (op
        append/rewrite). The shared substrate every static-verifier
        check walks (analysis.py) — and available to passes that want a
        prebuilt writer/reader map instead of hand-rolling one."""
        if (self._def_use_cache is None
                or self._def_use_cache[0] != self._version):
            from paddle_tpu import analysis

            self._def_use_cache = (
                self._version, analysis.build_def_use(self))
        return self._def_use_cache[1]

    def content_digest(self) -> str:
        """sha256 hex digest of the program CONTENT — blocks, vars, op
        list with slot-keyed args and canonicalized attrs, random_seed —
        with no process-local identity (uids, ids) mixed in, so two
        identically-built programs in two different processes digest
        identically. Cached per version (any op append/rewrite bumps the
        version and invalidates). The canonical program token of
        ``core.fingerprint.program_fingerprint``."""
        cache = self._content_digest_cache
        if cache is not None and cache[0] == self._version:
            return cache[1]
        h = hashlib.sha256()
        h.update(repr(self.random_seed).encode())
        for b in self.blocks:
            h.update(f"B{b.idx}:{b.parent_idx}".encode())
            for name in sorted(b.vars):
                v = b.vars[name]
                h.update(repr((
                    name, v.shape, str(v.dtype), bool(v.persistable),
                    bool(v.stop_gradient), bool(v.is_parameter),
                    v.kind,
                )).encode())
            for op in b.ops:
                h.update(op.type.encode())
                h.update(repr(sorted(op.inputs.items())).encode())
                h.update(repr(sorted(op.outputs.items())).encode())
                for k in sorted(op.attrs):
                    h.update(k.encode())
                    h.update(_canonical_attr_bytes(op.attrs[k]))
        digest = h.hexdigest()
        self._content_digest_cache = (self._version, digest)
        return digest

    # --- serialization ---

    def to_proto(self) -> pb.ProgramDesc:
        d = pb.ProgramDesc(version=self._version)
        if self.random_seed is not None:
            d.random_seed = self.random_seed
        for b in self.blocks:
            d.blocks.append(b.to_proto())
        return d

    def desc_str(self) -> bytes:
        return self.to_proto().SerializeToString()

    @staticmethod
    def from_proto(d: pb.ProgramDesc) -> "Program":
        p = Program()
        p.blocks = []
        for bd in d.blocks:
            p.blocks.append(Block(p, bd.idx, bd.parent_idx))
        for bd, b in zip(d.blocks, p.blocks):
            for vd in bd.vars:
                shape = tuple(vd.shape) if vd.shape else None
                kw = dict(
                    shape=shape,
                    dtype=vd.dtype or None,
                    persistable=vd.persistable,
                    stop_gradient=vd.stop_gradient,
                    trainable=vd.trainable,
                    kind=vd.kind,
                )
                if vd.is_parameter:
                    b.create_parameter(
                        vd.name,
                        shape,
                        vd.dtype or "float32",
                        trainable=vd.trainable,
                    )
                else:
                    b.create_var(name=vd.name, **kw)
            for od in bd.ops:
                op = Operator(
                    b,
                    od.type,
                    inputs={v.parameter: list(v.arguments) for v in od.inputs},
                    outputs={v.parameter: list(v.arguments) for v in od.outputs},
                    attrs={a.name: _attr_from_proto(a, p) for a in od.attrs},
                )
                b.ops.append(op)
        p._version = d.version
        if d.HasField("random_seed"):
            p.random_seed = d.random_seed
        return p

    @staticmethod
    def parse_from_string(s: bytes) -> "Program":
        d = pb.ProgramDesc()
        d.ParseFromString(s)
        return Program.from_proto(d)

    def clone(self, for_test: bool = False) -> "Program":
        with _monitor.span("program.clone"):
            p = Program.parse_from_string(self.desc_str())
            p._param_grad_map = dict(self._param_grad_map)
            p._amp = self._amp
            if for_test:
                for b in p.blocks:
                    for op in b.ops:
                        if "is_test" in op.attrs:
                            op.attrs["is_test"] = True
                        if op.type == "dropout":
                            op.attrs["is_test"] = True
                        if op.type == "batch_norm":
                            op.attrs["is_test"] = True
            else:   # a training clone keeps what its optimizer will append
                p._step_updates = [dict(u) for u in self._step_updates]
                p._checkpoints = list(self._checkpoints)
            p._bump_version()
            return p

    def __repr__(self):
        return "\n".join(repr(b) for b in self.blocks)

    __str__ = __repr__


# --- default programs & guards (reference: framework.py:3574-3650) ---

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(program: Program) -> Program:
    global _main_program_
    old, _main_program_ = _main_program_, program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program_
    old, _startup_program_ = _startup_program_, program
    return old


class program_guard:
    def __init__(self, main_program: Program, startup_program: Optional[Program] = None):
        self.main = main_program
        self.startup = startup_program

    def __enter__(self):
        self.old_main = switch_main_program(self.main)
        if self.startup is not None:
            self.old_startup = switch_startup_program(self.startup)
        return self

    def __exit__(self, *exc):
        switch_main_program(self.old_main)
        if self.startup is not None:
            switch_startup_program(self.old_startup)
        return False


import contextlib


# the name scopes open now, outermost first (reference: framework.py
# name_scope / _name_scope, a process-wide stack there too)
_name_scope_: List[str] = []


@contextlib.contextmanager
def name_scope(prefix: str):
    """Ops appended inside carry ``prefix`` (scopes nest with "/") as
    their ``op_namescope`` attr, their grad ops inherit it, and the
    lowering names each op's compute ``<phase>/<scope>/<op type>`` in
    the compiled HLO, so a device trace reads in the program's own
    terms (README "Observability"). A scope is a name, never
    arithmetic; keep parameter names out of it (one scope per layer,
    not per tensor)."""
    _name_scope_.append(str(prefix).strip("/"))
    try:
        yield
    finally:
        _name_scope_.pop()


def checkpoint(var: "Variable") -> "Variable":
    """Mark ``var`` as a recomputation checkpoint of its Program (Fluid
    1.6's ``checkpoints``): ``append_backward`` keeps it for the backward
    pass and makes the ops between two marks again there instead of
    keeping what they made (backward.py's module docstring). The mark
    travels on the Program, so ``Optimizer.minimize(loss)`` needs no
    argument. Returns ``var``. A variable of a sub-block (a ``while`` or
    ``StaticRNN`` body) is refused: a body's values live an iteration."""
    checkpoint_names(var.block.program, [var], mark=True)
    return var


def checkpoint_names(program: "Program", more=None, mark=False) -> List[str]:
    """The Program's marks and ``more`` (variables or names) as names,
    each once; ``mark`` records ``more`` on the Program."""
    names = list(program._checkpoints)
    block = program.global_block()
    for v in more or ():
        name = v.name if isinstance(v, Variable) else str(v)
        if name not in block.vars and any(
                name in b.vars for b in program.blocks[1:]):
            raise ValueError(
                f"checkpoint '{name}' lives in a sub-block (a while or "
                f"StaticRNN body): recomputation is by segments of the "
                f"global block; mark the loop's input or output instead")
        if name not in names:
            names.append(name)
            if mark:
                program._checkpoints.append(name)
    return names


@contextlib.contextmanager
def op_role_guard(program: "Program", role: str):
    """Ops appended to ``program`` inside belong to phase ``role`` (the
    reference's Program._optimized_guard / _backward_role_guard):
    backward.append_backward uses "bwd", Optimizer.apply_gradients
    "opt"."""
    assert role in OP_ROLES, role
    old, program._op_role = program._op_role, role
    try:
        yield
    finally:
        program._op_role = old


# Device "places" (reference: platform/place.h:79). Programs run on jax's
# default backend; a place NAMES the platform the caller expects, and
# ``resolve_place`` refuses a place the process cannot honor instead of
# running somewhere else under its label.
class CPUPlace:
    platform = "cpu"

    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    platform = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


def resolve_place(place=None):
    """-> (place, jax device) an Executor runs on. ``None`` takes jax's
    default device and reports what that is; an explicit place whose
    platform is not jax's default backend raises."""
    import jax

    if place is None:
        dev = jax.devices()[0]
        return (TPUPlace(dev.id) if dev.platform == "tpu"
                else CPUPlace()), dev
    backend = jax.default_backend()
    if place.platform != backend:
        raise RuntimeError(
            f"{place!r} requested but jax's default backend is "
            f"'{backend}' (devices: {jax.devices()}); pass no place to run "
            f"on the default device, or select the platform with "
            f"JAX_PLATFORMS")
    if getattr(place, "device_id", 0) != 0:
        raise NotImplementedError(
            f"{place!r}: an Executor drives jax's default device (index "
            f"0); use CompiledProgram to span several devices")
    return place, jax.devices()[0]


# Alias so reference-style `fluid.CUDAPlace(0)` code keeps working on TPU.
CUDAPlace = TPUPlace


def in_dygraph_mode() -> bool:
    from paddle_tpu.dygraph import base as dygraph_base

    return dygraph_base._in_dygraph_mode()
