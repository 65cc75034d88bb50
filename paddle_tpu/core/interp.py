"""Shared op-list interpreter used by block lowering and control-flow ops.

The reference executes sub-blocks of control-flow ops by recursively invoking
its op-by-op Executor on the sub-scope (reference:
operators/controlflow/while_op.cc:43, conditional_block_op.cc:75). Here the
same role is played by tracing the sub-block's registered JAX kernels into the
enclosing XLA computation: ``exec_ops`` runs an ordered op list against a
functional environment (name -> array), and control-flow ops call it inside
``lax.while_loop`` / ``lax.cond`` / ``lax.scan`` closures so the whole nest
compiles to one XLA program.

AMP (bf16 activation-stream) casting is applied here so sub-blocks behave the
same as top-level blocks under mixed precision.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core import autodiff
from paddle_tpu.core.registry import GRAD_OP_SUFFIX, OpDef, get_op_def, has_op

# What this repo's op rules cost to trace. exec_ops runs at TRACE time
# (a cached compiled step never re-enters Python), so the histogram's
# count is the ops lowered, by op type, and its sum the seconds of a
# first call's jaxpr trace spent in the rules and not in jax's machinery
# around them; a count that grows mid-training means recompiles.
_M_OP_TRACE = _monitor.histogram(
    "pt_op_trace_seconds",
    "trace-time seconds of an op's compute (its key derivation and AMP "
    "casts included) while a block is lowered, by op type; a "
    "control-flow op is charged what its sub-block's ops are not")


class _Nested(threading.local):
    """Seconds this thread has spent in exec_ops calls nested in an op's
    compute (a control-flow op's sub-block), so the outer op is charged
    only what its inner ops were not."""

    s = 0.0


_NESTED = _Nested()

# MXU-heavy ops that run in bfloat16 under AMP: every f32 input (master
# weights included) is cast to bf16 and the output STAYS bf16, so the whole
# activation stream between matmuls lives in bf16 — halving HBM traffic,
# which profiling showed was the step-time bound (casting back to f32 after
# each matmul made every matmul bandwidth-limited). The analog of the
# reference's AMP cast insertion (reference:
# contrib/mixed_precision/fp16_utils.py:67), but bf16 needs no loss scaling
# (SURVEY.md section 7 phase 4).
AMP_OP_TYPES = {
    "mul",
    "matmul",
    "conv2d",
    "depthwise_conv2d",
    "conv2d_transpose",
    "scaled_dot_product_attention",
    # the experts' grouped matmuls (rows and the stacked weights to
    # bf16). The rest of the top-k MoE keeps what it must in f32 by
    # itself: moe_router its logits, softmax and top-k, moe_combine its
    # weighted sum; rms_norm, like layer_norm, its statistics.
    "moe_experts",
    # the gated delta rule (ops/linear_attention_ops.py): Q, K, V to
    # bf16, the operands of its matmuls. What stays float32: its G and
    # Beta (AMP_KEEP_F32_SLOTS below: exponentiated and summed over a
    # chunk), and inside the op the normalisation of q and k, the
    # triangular solve and the state. causal_conv1d, gdn_gates and
    # gated_rms_norm are not listed: each computes in float32 and
    # returns its input's dtype (gdn_gates float32) by itself.
    "gated_delta_rule",
    # the vocabulary projection and the loss over the rows whose label
    # counts (ops/nn_ops.py): X and W to bf16 as mul's, the logsumexp and
    # the label's logit in float32 inside the op, the grad op's
    # GRAD::Loss kept float32 (AMP_KEEP_F32_SLOTS below).
    "linear_cross_entropy",
    # the read and the write-back of hyper-connected residual streams
    # (ops/hc_ops.py): the streams, the sublayer's output and their
    # cotangents to bf16; the mixes HPre, HPost, HRes stay float32
    # (AMP_KEEP_F32_SLOTS below) and the sums inside are float32. hc_mix
    # is not listed: it reads the streams as they come and is float32.
    "hc_pre",
    "hc_post",
}

# Precision-following ops: when any input is already bf16, their remaining
# f32 float inputs (params like layer-norm scale, residual branches) are
# cast down so the op does not silently promote the stream back to f32.
# Numerically sensitive reductions inside these kernels (layer-norm
# mean/var) compute in f32 internally regardless of input dtype.
AMP_FLOW_OP_TYPES = {
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "scale",
    "dropout",
    "relu",
    "gelu",
    "tanh",
    "sigmoid",
    "softmax",
    "concat",
    "stack",
}
# (layer_norm is absent: its kernel handles mixed dtypes itself — f32
# internal math, x-dtype output — so no input casting is wanted.)

# Slots that must stay f32 under AMP (saved numerical stats, not streams;
# and the optimizer state an experts' grad op carries when its matrices'
# Adam is taken inside it: ops/moe_ops._ADAM_IN).
AMP_KEEP_F32_SLOTS = frozenset(
    {"Lse", "GRAD::Lse", "G", "Beta", "GRAD::Loss",
     "Param", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow", "LearningRate",
     "HPre", "HPost", "HRes"})

# Whether AMP casting is active for the block currently being traced;
# None while no block is (core/lowering.run_block sets it for the length
# of its trace). Control-flow op computes read this so sub-blocks inherit
# the policy of the block that contains them (a contextvar because op
# computes only receive (ins, attrs)).
_AMP_ACTIVE: contextvars.ContextVar[Optional[bool]] = contextvars.ContextVar(
    "paddle_tpu_amp_active", default=None
)


def amp_active() -> bool:
    return bool(_AMP_ACTIVE.get())


def lowering_active() -> bool:
    """True inside the trace of a program block being lowered — not in
    build-time shape inference, which runs op computes (and through
    control-flow ops, exec_ops) under jax.eval_shape."""
    return _AMP_ACTIVE.get() is not None


def stands_for_dynamic(n) -> bool:
    """Is ``n`` the stand-in of a dynamic (-1) dim in build-time shape
    inference (framework._BATCH_SENTINEL, a prime)? An op that cuts a
    dim in parts takes the stand-in as it is: a part of a dynamic dim is
    dynamic. Never inside a lowering: there every dim is the data's."""
    from paddle_tpu.framework import _BATCH_SENTINEL

    return not lowering_active() and n == _BATCH_SENTINEL


def set_amp_active(flag: bool):
    return _AMP_ACTIVE.set(bool(flag))


# SPMD context for ops that need an explicit shard_map rather than GSPMD
# propagation: collectives (ring attention over a context axis,
# psum-sharded embedding tables, expert-parallel MoE all_to_all dispatch)
# and Pallas kernels, which GSPMD cannot partition at all. Set by the
# Executor while tracing a program compiled for a mesh; kernels read it
# at trace time. An ``SpmdCtx`` or None (single device).
SpmdCtx = collections.namedtuple(
    "SpmdCtx", ["mesh", "context_axis", "table_axis", "data_axis",
                "expert_axis", "pipe_axis", "pipe_micro"]
)

_SPMD_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_spmd_ctx", default=None
)


def spmd_ctx():
    return _SPMD_CTX.get()


def set_spmd_ctx(ctx):
    return _SPMD_CTX.set(ctx)


@contextlib.contextmanager
def spmd_ctx_scope(strategy):
    """Activate a DistributedStrategy's SPMD context (ring attention /
    sharded tables / expert-parallel MoE / mesh-wrapped Pallas kernels)
    for the enclosed trace. The single place that builds the context —
    kernels read fields by name."""
    ctx = None
    if strategy is not None:
        # Multi-slice: the batch axis kernels see is the COMPOSED
        # (slice, data) tuple so shard_map specs and collective axis
        # lists span both — the batch is sharded over their product
        # (strategy.batch_sharding). Single-axis stays a plain string.
        data_axis = strategy.data_axis
        slice_axis = getattr(strategy, "slice_axis", None)
        if slice_axis is not None:
            data_axis = ((slice_axis, data_axis) if data_axis is not None
                         else slice_axis)
        ctx = SpmdCtx(
            mesh=strategy.mesh,
            context_axis=strategy.context_axis,
            table_axis=strategy.table_axis,
            data_axis=data_axis,
            expert_axis=getattr(strategy, "expert_axis", None),
            pipe_axis=getattr(strategy, "pipe_axis", None),
            pipe_micro=getattr(strategy, "pipe_micro", None),
        )
    tok = _SPMD_CTX.set(ctx)
    try:
        yield
    finally:
        _SPMD_CTX.reset(tok)


MeshSplit = collections.namedtuple(
    "MeshSplit", ["mesh", "free", "nested", "axis", "n"])


def mesh_batch_split():
    """How an op that GSPMD cannot partition splits its batch when it
    wraps itself in a shard_map under the program's mesh (the Pallas
    attention calls, dropout's random words): ``free`` are the mesh
    axes still automatic here, ``nested`` says an enclosing shard_map (a
    GPipe stage) already made the others manual, ``axis`` are the free
    data axes (dim 0 splits over them), ``n`` their ranks. None on one
    device and where every axis is manual already."""
    ctx = spmd_ctx()
    if ctx is None:
        return None
    from paddle_tpu.parallel.mesh import axis_size, axis_tuple

    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    free = frozenset(a for a in ctx.mesh.axis_names if a not in manual)
    if not free:
        return None
    axis = tuple(a for a in axis_tuple(ctx.data_axis) if a in free)
    return MeshSplit(ctx.mesh, free, bool(manual), axis,
                     axis_size(ctx.mesh, axis))


def _is_f32(v):
    return v is not None and hasattr(v, "dtype") and v.dtype == jnp.float32


def _is_bf16(v):
    return v is not None and hasattr(v, "dtype") and v.dtype == jnp.bfloat16


def _amp_cast_ins(ins):
    out = {}
    for slot, vals in ins.items():
        if slot in AMP_KEEP_F32_SLOTS:
            out[slot] = list(vals)
            continue
        out[slot] = [
            v.astype(jnp.bfloat16) if _is_f32(v) else v for v in vals
        ]
    return out


def _amp_flow_cast_ins(ins):
    """Cast f32 inputs to bf16 only when the op already consumes bf16."""
    has_bf16 = any(_is_bf16(v) for vals in ins.values() for v in vals)
    if not has_bf16:
        return ins
    return _amp_cast_ins(ins)


def resolve_op_def(op_type: str) -> OpDef:
    """Resolve an op type to its kernel, deriving ``*_grad`` on demand."""
    if has_op(op_type):
        return get_op_def(op_type)
    if op_type.endswith(GRAD_OP_SUFFIX):
        base = op_type[: -len(GRAD_OP_SUFFIX)]
        if has_op(base):
            fwd = get_op_def(base)
            return OpDef(
                type=op_type,
                compute=autodiff.make_grad_compute(fwd),
                needs_rng=fwd.needs_rng,
                no_grad=True,
            )
    return get_op_def(op_type)  # raises with a helpful message


def op_scope_name(op) -> str:
    """``<phase>/<name scope>/<op type>``: the scope an op's compute is
    lowered under (phase is fwd, bwd or opt; the name scope may be
    empty)."""
    scope = op.namescope
    return (f"{op.role}/{scope}/{op.type}" if scope
            else f"{op.role}/{op.type}")


def exec_ops(
    ops,
    env: Dict[str, Any],
    key=None,
    amp: Optional[bool] = None,
    op_defs: Optional[List[OpDef]] = None,
    ledger=None,
):
    """Execute an op list against ``env`` in place; returns ``env``.

    ``key`` is the PRNG key for this execution; per-op keys are derived by
    folding in the op's ``forward_op_idx`` attr (so a grad op replays its
    forward's key) or its position.

    ``ledger``: what core/lowering.run_block hands the OUTERMOST call of
    a block's lowering while telemetry is on (a control-flow op's
    sub-block comes here without one: its values are its op's business,
    as its seconds are): it is told every op's reads and the values it
    writes, as traced (``lowering.ValueLedger.note``).
    """
    if amp is None:
        amp = amp_active()
    if op_defs is None:
        op_defs = [resolve_op_def(op.type) for op in ops]
    # trace time only, and only while a block is lowered (build-time shape
    # inference runs sub-blocks through here too)
    timed = _monitor.enabled() and lowering_active()
    if timed:
        t_block = time.perf_counter()
        nested_before = _NESTED.s
    for idx, (op, opdef) in enumerate(zip(ops, op_defs)):
        if timed:
            t_op = time.perf_counter()
            nested_op = _NESTED.s
        ins = {
            slot: [env[n] if n else None for n in names]
            for slot, names in op.inputs.items()
        }
        base_type = (
            op.type[: -len(GRAD_OP_SUFFIX)]
            if op.type.endswith(GRAD_OP_SUFFIX)
            else op.type
        )
        # the program's names into the HLO's op_name metadata (and from
        # there into the device trace): <phase>/<name scope>/<op type>.
        # Trace time only. The op's key derivation and its AMP casts sit
        # inside, so both are charged to the op that asked for them. A
        # control-flow op's sub-block re-enters here inside this scope
        # and nests under it.
        with jax.named_scope(op_scope_name(op)):
            kwargs = {}
            if opdef.needs_rng:
                fold = op.attrs.get("forward_op_idx", idx)
                kwargs["rng"] = (
                    jax.random.fold_in(key, fold) if key is not None else None
                )
            if amp and base_type in AMP_OP_TYPES:
                ins = _amp_cast_ins(ins)
            elif amp and base_type in AMP_FLOW_OP_TYPES:
                ins = _amp_flow_cast_ins(ins)
            outs = opdef.compute(ins, op.compute_attrs(), **kwargs)
        if timed:
            _M_OP_TRACE.observe(
                time.perf_counter() - t_op - (_NESTED.s - nested_op),
                labels={"op": op.type})
            if ledger is not None:
                ledger.note(idx, op, outs)
        for slot, names in op.outputs.items():
            vals = outs.get(slot, [])
            for i, n in enumerate(names):
                if not n:
                    continue
                v = vals[i] if i < len(vals) else None
                if v is not None:
                    env[n] = v
    if timed:
        # this whole call, once, whatever its own sub-blocks added
        _NESTED.s = nested_before + (time.perf_counter() - t_block)
    return env
