"""Control-flow ops: ``while``, ``cond``, ``scan``.

TPU-native redesign of the reference's control-flow operators
(reference: operators/controlflow/while_op.cc:43,
operators/controlflow/conditional_block_op.cc:75,
operators/recurrent_op.cc:250). The reference interprets a sub-block with a
recursively invoked executor over per-iteration scopes; on TPU the sub-block
is *traced* into the enclosing XLA computation as the closure of a
structural primitive:

- ``while``  -> ``lax.while_loop``  (data-dependent trip count; no gradient,
  matching XLA's non-differentiable While — training loops use ``scan``)
- ``cond``   -> ``lax.cond``        (differentiable via its linearization)
- ``scan``   -> ``lax.scan``        (fixed trip count; differentiable — this
  is the training-time recurrence primitive, replacing RecurrentOp's
  save-everything tape with XLA's scan transpose)

Conventions shared by the three ops: the sub-block reads/writes a functional
env (name -> array). Values crossing the block boundary are *op inputs*
(slots ``X``/``Init``/``Captured``), never Python closure captures, so state
analysis (core/lowering.py:analyze_state) and autodiff see them. Name lists
mapping slot positions to env names ride in attrs.

PRNG: each op folds the incoming key with the iteration counter so stochastic
sub-ops (dropout) draw fresh randomness per step, and the derived grad op
replays the same keys (attrs carry ``forward_op_idx``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import interp
from paddle_tpu.core.registry import register_op


def _scalar_bool(x):
    return jnp.reshape(jnp.asarray(x), ()).astype(jnp.bool_)


def _sub_env(cap_names, cap_vals):
    env = {}
    for n, v in zip(cap_names, cap_vals):
        env[n] = v
    return env


@register_op("while", no_grad=True, needs_rng=True)
def _while(ins, attrs, rng=None):
    """Run ``sub_block`` while the condition var is true.

    attrs: sub_block, carry_names (env names of loop-carried values, first
    updated by each iteration), cond_name (env name of the bool scalar the
    sub-block must refresh each iteration), captured_names.
    inputs: Condition=[cond0], X=carried initial values, Captured=read-only.
    outputs: Out=final carried values (same order as carry_names).
    """
    sub = attrs["sub_block"]
    carry_names = list(attrs["carry_names"])
    cond_name = attrs["cond_name"]
    cap_names = list(attrs.get("captured_names", []))
    cap_vals = list(ins.get("Captured", []))
    amp = interp.amp_active()
    sub_ops = list(sub.ops)

    def cond_fun(carry):
        return _scalar_bool(carry[1])

    def body_fun(carry):
        i, cond_val = carry[0], carry[1]
        env = _sub_env(cap_names, cap_vals)
        env[cond_name] = cond_val
        env.update(zip(carry_names, carry[2:]))
        key = jax.random.fold_in(rng, i) if rng is not None else None
        interp.exec_ops(sub_ops, env, key=key, amp=amp)
        return (i + 1, _scalar_bool(env[cond_name])) + tuple(
            env[n] for n in carry_names
        )

    init = (
        jnp.zeros((), jnp.int32),
        _scalar_bool(ins["Condition"][0]),
    ) + tuple(ins.get("X", []))
    final = lax.while_loop(cond_fun, body_fun, init)
    return {"Out": list(final[2:]), "CondOut": [final[1]], "Steps": [final[0]]}


@register_op("bounded_while", diff_inputs=("X", "Captured"), needs_rng=True)
def _bounded_while(ins, attrs, rng=None):
    """Differentiable While: fixed trip count + liveness mask — the
    trainable lowering of the reference's while_op grad
    (operators/controlflow/while_op.cc:43 WhileGradOp). XLA's While is
    not reverse-differentiable, so ``While(cond, max_trip_count=N)``
    lowers to a ``lax.scan`` over exactly N steps where a dead step
    passes its carries through a select — gradients flow through the
    selects (dead iterations contribute zero) and through the captured
    values (weights read inside the loop). Costs N body evaluations
    regardless of the dynamic trip count; CondOut still True after N
    steps means the loop was TRUNCATED (the bound is a hard contract).

    Same attrs as ``while`` plus ``max_trip_count``; Steps counts the
    live iterations.
    """
    sub = attrs["sub_block"]
    carry_names = list(attrs["carry_names"])
    cond_name = attrs["cond_name"]
    cap_names = list(attrs.get("captured_names", []))
    n_steps = int(attrs["max_trip_count"])
    cap_vals = list(ins.get("Captured", []))
    amp = interp.amp_active()
    sub_ops = list(sub.ops)
    init = tuple(ins.get("X", []))
    init_dtypes = [jnp.result_type(v) for v in init]

    def body(carry, i):
        live, steps = carry[0], carry[1]
        vals = carry[2:]
        env = _sub_env(cap_names, cap_vals)
        env[cond_name] = live
        env.update(zip(carry_names, vals))
        key = jax.random.fold_in(rng, i) if rng is not None else None
        interp.exec_ops(sub_ops, env, key=key, amp=amp)
        new_vals = tuple(
            jnp.where(live, env[n].astype(dt), v)
            for n, v, dt in zip(carry_names, vals, init_dtypes)
        )
        new_live = jnp.logical_and(live, _scalar_bool(env[cond_name]))
        return ((new_live, steps + live.astype(jnp.int32)) + new_vals,
                None)

    carry0 = (_scalar_bool(ins["Condition"][0]),
              jnp.zeros((), jnp.int32)) + init
    final, _ = lax.scan(body, carry0, jnp.arange(n_steps, dtype=jnp.int32))
    return {"Out": list(final[2:]), "CondOut": [final[0]],
            "Steps": [final[1]]}


@register_op("cond", diff_inputs=("Captured",), needs_rng=True)
def _cond(ins, attrs, rng=None):
    """Select between two sub-blocks on a scalar predicate.

    attrs: true_block, false_block, true_out_names, false_out_names,
    captured_names. Both branches read the same Captured values; outputs are
    paired positionally (``Out[i]`` = true_out_names[i] / false_out_names[i]).
    """
    true_block, false_block = attrs["true_block"], attrs["false_block"]
    t_outs = list(attrs["true_out_names"])
    f_outs = list(attrs["false_out_names"])
    cap_names = list(attrs.get("captured_names", []))
    amp = interp.amp_active()
    pred = _scalar_bool(ins["Cond"][0])
    t_key = jax.random.fold_in(rng, 0) if rng is not None else None
    f_key = jax.random.fold_in(rng, 1) if rng is not None else None

    def make_branch(block, out_names, key):
        ops_ = list(block.ops)

        def branch(cap_vals):
            env = _sub_env(cap_names, cap_vals)
            interp.exec_ops(ops_, env, key=key, amp=amp)
            return tuple(env[n] for n in out_names)

        return branch

    outs = lax.cond(
        pred,
        make_branch(true_block, t_outs, t_key),
        make_branch(false_block, f_outs, f_key),
        tuple(ins.get("Captured", [])),
    )
    return {"Out": list(outs)}


@register_op(
    "scan", diff_inputs=("X", "Init", "Captured"), needs_rng=True
)
def _scan(ins, attrs, rng=None):
    """Fixed-length recurrence: run ``sub_block`` over the leading axis.

    attrs: sub_block, x_names (env names of per-step slices of X),
    state_in_names/state_out_names (parallel: carried state env names read /
    written per step), y_names (env names stacked into Y), captured_names,
    reverse, n_steps (required when X is empty).
    inputs: X=[T, ...] scanned tensors (time-major), Init=initial states,
    Captured=read-only values (parameters live here so gradients flow).
    outputs: Y=stacked per-step outputs [T, ...], FinalState=final states.

    Differentiable: the derived ``scan_grad`` op vjps through ``lax.scan``,
    which XLA transposes into the reverse-time accumulation the reference
    hand-writes in RecurrentGradOp (reference: operators/recurrent_op.cc:250).
    """
    sub = attrs["sub_block"]
    x_names = list(attrs.get("x_names", []))
    s_in = list(attrs.get("state_in_names", []))
    s_out = list(attrs.get("state_out_names", []))
    y_names = list(attrs.get("y_names", []))
    cap_names = list(attrs.get("captured_names", []))
    reverse = bool(attrs.get("reverse", False))
    xs = list(ins.get("X", []))
    init = list(ins.get("Init", []))
    cap_vals = list(ins.get("Captured", []))
    amp = interp.amp_active()
    sub_ops = list(sub.ops)

    if xs:
        n_steps = jnp.shape(xs[0])[0]
    else:
        n_steps = int(attrs["n_steps"])

    init_dtypes = [jnp.result_type(v) for v in init]

    # Pipeline parallelism: a scan marked ``pipelinable`` (scan-over-layers
    # model builds — one step per LAYER, carry = the activation stream)
    # runs the GPipe microbatch schedule over the strategy's pipe axis
    # instead of lax.scan: same math, layers spread one-per-rank with the
    # stacked weights sharded P(pipe) (parallel/pipeline.py). Time-scans
    # (RNNs) are never pipelined — they lack the marker.
    if attrs.get("pipelinable", False):
        ctx = interp.spmd_ctx()
        if ctx is not None and ctx.pipe_axis is not None:
            return _scan_as_gpipe(
                ctx, sub_ops, xs, init, cap_vals, cap_names, x_names,
                s_in, s_out, y_names, init_dtypes, reverse, rng, amp,
                list(attrs.get("stream_names", [])))

    def body(carry, step):
        i, xt = step
        env = _sub_env(cap_names, cap_vals)
        env.update(zip(s_in, carry))
        env.update(zip(x_names, xt))
        key = jax.random.fold_in(rng, i) if rng is not None else None
        interp.exec_ops(sub_ops, env, key=key, amp=amp)
        # AMP may narrow a carried activation to bf16 mid-body; scan
        # requires carry-in/carry-out types to match, so restore the
        # initial dtypes at the step boundary.
        new_carry = tuple(
            env[n].astype(dt) for n, dt in zip(s_out, init_dtypes)
        )
        ys = tuple(env[n] for n in y_names)
        return new_carry, ys

    # `unroll`: layers per loop iteration. unroll >= n_steps drops the
    # scan machinery entirely — a static Python loop with STATIC slices
    # of the stacked inputs, so no scan-transpose residual stacking and
    # no dynamic-update-slices in the backward; this is the re-plumbed
    # "unrolled build over stacked weights" path (measured: lax.scan
    # unroll=1 0.216 MFU / full-unroll-inside-scan 0.341 / this path
    # matches build() — round-4 "scan-over-layers" measurement). Intermediate
    # unrolls measured SLOWER than unroll=1 (0.18-0.19) and are kept
    # only for completeness.
    unroll = int(attrs.get("unroll", 1))
    if unroll >= int(n_steps):
        order = range(int(n_steps))
        if reverse:
            order = reversed(order)
        carry = tuple(init)
        ys_steps = []
        for i in order:
            carry, ys_t = body(carry, (jnp.int32(i),
                                       tuple(x[i] for x in xs)))
            ys_steps.append(ys_t)
        if reverse:
            ys_steps.reverse()
        ys = tuple(
            jnp.stack([st[j] for st in ys_steps])
            for j in range(len(y_names))
        )
        return {"Y": list(ys), "FinalState": list(carry)}
    steps = (jnp.arange(n_steps, dtype=jnp.int32), tuple(xs))
    final, ys = lax.scan(body, tuple(init), steps, reverse=reverse,
                         unroll=max(1, unroll))
    return {"Y": list(ys), "FinalState": list(final)}


def _scan_as_gpipe(ctx, sub_ops, xs, init, cap_vals, cap_names, x_names,
                   s_in, s_out, y_names, init_dtypes, reverse, rng, amp,
                   stream_names):
    """Run a pipelinable layer-scan as a GPipe schedule (see _scan)."""
    from paddle_tpu.parallel import pipeline as pp

    n_stages = ctx.mesh.shape[ctx.pipe_axis]
    if len(init) != 1 or y_names:
        raise ValueError(
            "pipeline strategy: a pipelinable scan must carry exactly one "
            "activation stream and emit no per-step outputs "
            f"(got {len(init)} carries, {len(y_names)} outputs)"
        )
    if not xs or int(xs[0].shape[0]) != n_stages:
        raise ValueError(
            f"pipeline strategy: the scan has {0 if not xs else int(xs[0].shape[0])} "
            f"stacked layers but the pipe axis '{ctx.pipe_axis}' has "
            f"{n_stages} ranks; they must match (one layer per rank)"
        )
    if reverse:
        raise ValueError("pipeline strategy: reverse layer-scan unsupported")

    # Captured values the BUILDER declared batch-shaped (attention biases,
    # the encoder output — scan attr ``stream_names``) are microbatched in
    # step with the activation stream; everything else closes over the
    # stage body unchanged. Declared, not inferred: a replicated constant
    # whose leading dim coincidentally equals the batch size must NOT be
    # sliced.
    b = int(init[0].shape[0])
    declared = set(stream_names)
    stream_idx = [i for i, n in enumerate(cap_names) if n in declared]
    for i in stream_idx:
        v = cap_vals[i]
        if not (hasattr(v, "ndim") and v.ndim >= 1 and v.shape[0] == b):
            raise ValueError(
                f"pipeline strategy: declared stream '{cap_names[i]}' "
                f"does not have the carry's batch dim {b} "
                f"(shape {getattr(v, 'shape', None)})"
            )
    stream_names = [cap_names[i] for i in stream_idx]
    const_pairs = [
        (n, v) for i, (n, v) in enumerate(zip(cap_names, cap_vals))
        if i not in stream_idx
    ]

    def stage(params, act, *streams, micro_idx):
        env = {n: v for n, v in const_pairs}
        env.update(zip(stream_names, streams))
        env.update(zip(s_in, (act,)))
        env.update(zip(x_names, params))
        # layer key: the layer index IS the pipe rank (matching the
        # lax.scan path's fold_in(rng, step)); the microbatch index folds
        # in too so microbatches draw INDEPENDENT dropout masks — the
        # full-batch lax.scan mask differs row to row.
        key = None
        if rng is not None:
            key = jax.random.fold_in(
                jax.random.fold_in(rng, lax.axis_index(ctx.pipe_axis)),
                micro_idx)
        interp.exec_ops(sub_ops, env, key=key, amp=amp)
        return env[s_out[0]].astype(init_dtypes[0])

    out = pp.gpipe(
        stage, tuple(xs), init[0], ctx.mesh, pipe_axis=ctx.pipe_axis,
        n_micro=ctx.pipe_micro,
        batch_streams=tuple(cap_vals[i] for i in stream_idx),
        with_micro_idx=True,
        data_axis=ctx.data_axis,
    )
    return {"Y": [], "FinalState": [out]}
