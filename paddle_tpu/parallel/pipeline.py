"""Pipeline parallelism: GPipe-style microbatched stage execution.

Net-new capability vs the reference (SURVEY.md section 2.3 row
"Pipeline/tensor/sequence/context parallelism ... absent in reference").
TPU-native design: stages live on a ``pipe`` mesh axis; every rank holds
ONE stage's parameters (a pytree stacked on a leading stage axis, sharded
``P('pipe')``), and activations hop rank -> rank+1 over ICI with
``lax.ppermute`` while microbatches stream through — the classic GPipe
schedule of ``n_micro + n_stages - 1`` ticks with bubble fraction
``(S-1)/(M+S-1)``. The whole schedule is a ``lax.scan`` inside one
``shard_map``, so XLA overlaps the per-tick compute with the neighbor
exchange and the loop compiles once regardless of microbatch count.

The stage body must be shape-preserving (``fn(params_i, x) -> y`` with
``y.shape == x.shape``) — the transformer's homogeneous layer stack, which
is what pipeline parallelism is for. Gradients flow through ppermute/scan
transposes, so ``jax.grad`` (and the Program-IR autodiff that rides on
it) works through the pipeline unchanged.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu import monitor as _monitor

# gpipe() runs at TRACE time (once per compile) — ticks per trace is the
# schedule length n_micro + n_stages - 1; the bubble fraction falls out
# of ticks vs microbatches.
_M_PIPE_TRACES = _monitor.counter(
    "pt_pipeline_traces_total", "GPipe schedule traces (per compile)")
_M_PIPE_TICKS = _monitor.counter(
    "pt_pipeline_ticks_total", "pipeline schedule ticks traced")
_M_PIPE_MICRO = _monitor.counter(
    "pt_pipeline_microbatches_total", "microbatches traced through gpipe")


def _gpipe_local(params, x_micro, streams, *, fn: Callable, axis: str,
                 n_micro: int, with_micro_idx: bool = False):
    """Per-rank body. params: this rank's stage params (leading stage axis
    already sliced away by shard_map); x_micro: [n_micro, mb, ...]
    microbatched input (replicated; only rank 0 reads it); streams:
    tuple of [n_micro, mb, ...] per-microbatch side inputs every stage
    reads for ITS current microbatch (attention biases etc.)."""
    n_stages = lax.psum(1, axis)
    rank = lax.axis_index(axis)
    total = n_micro + n_stages - 1
    mb_shape = x_micro.shape[1:]

    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        incoming, out_buf = carry
        mb_idx = t - rank                       # microbatch this rank runs
        active = (mb_idx >= 0) & (mb_idx < n_micro)
        # rank 0 feeds from the input stream; others from the wire
        feed = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False
        )
        x_in = jnp.where(rank == 0, feed, incoming)
        mb_clip = jnp.clip(mb_idx, 0, n_micro - 1)
        stream_t = tuple(
            lax.dynamic_index_in_dim(sm, mb_clip, axis=0, keepdims=False)
            for sm in streams
        )
        if with_micro_idx:
            y = fn(params, x_in, *stream_t, micro_idx=mb_clip)
        else:
            y = fn(params, x_in, *stream_t)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage banks its result at the microbatch's slot
        write_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_last = rank == n_stages - 1
        bank = jnp.where(
            active & is_last, y, jnp.zeros_like(y)
        )
        out_buf = lax.dynamic_update_index_in_dim(
            out_buf,
            lax.dynamic_index_in_dim(out_buf, write_idx, 0, keepdims=False)
            + bank,
            write_idx,
            axis=0,
        )
        # activations hop to the next stage (ring; the wraparound value
        # into rank 0 is ignored — rank 0 always reads the feed)
        incoming = lax.ppermute(y, axis, fwd)
        return (incoming, out_buf), None

    zero = jnp.zeros(mb_shape, x_micro.dtype)
    out0 = jnp.zeros_like(x_micro)
    # carries become rank-varying inside the body; align the initial type
    # to every manual axis in play (pipe from the params, plus the data
    # axis when dp x pp compose in one shard_map)
    vary = (set(jax.typeof(jax.tree.leaves(params)[0]).vma)
            | set(jax.typeof(x_micro).vma) | {axis})

    def _pcast_to(v):
        missing = tuple(vary - set(jax.typeof(v).vma))
        return lax.pcast(v, missing, to="varying") if missing else v

    zero, out0 = _pcast_to(zero), _pcast_to(out0)
    (_, out), _ = lax.scan(tick, (zero, out0), jnp.arange(total))
    # only the last rank holds nonzero outputs; psum replicates them
    return lax.psum(out, axis)


def gpipe(
    fn: Callable,
    stage_params,
    x,
    mesh: Mesh,
    pipe_axis: str = "pipe",
    n_micro: Optional[int] = None,
    batch_streams=(),
    with_micro_idx: bool = False,
    data_axis: Optional[str] = None,
):
    """Run ``x`` through ``n_stages`` stages pipelined over ``pipe_axis``.

    - ``fn(params_i, x_mb, *stream_mbs) -> y_mb`` — one stage's
      computation, shape preserving in ``x_mb``.
    - ``stage_params`` — pytree whose leaves have a leading ``n_stages``
      axis (sharded onto the pipe axis; each rank holds one slice).
    - ``x`` — [B, ...] global batch; split into ``n_micro`` microbatches
      (default: one per stage).
    - ``batch_streams`` — [B, ...] side inputs every stage reads for its
      current microbatch (attention masks/biases); microbatched in step
      with ``x``.
    - ``with_micro_idx`` — pass the stage's current microbatch index as a
      ``micro_idx`` kwarg (stochastic stages fold it into their PRNG key
      so microbatches draw independent randomness).
    - ``data_axis`` — compose dp x pp in ONE program: each data-rank
      group pipelines ITS batch shard (the microbatch dim is sharded over
      ``data_axis``; ppermute/psum stay scoped to the pipe axis, so the
      schedules run independently per data shard and the gradient
      all-reduce over data happens outside in GSPMD land).
    Returns [B, ...] outputs (replicated over the pipe axis).
    """
    n_stages = mesh.shape[pipe_axis]
    b = x.shape[0]
    n_micro = n_micro or n_stages
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    if _monitor.enabled():
        _M_PIPE_TRACES.inc()
        _M_PIPE_TICKS.inc(n_micro + n_stages - 1)
        _M_PIPE_MICRO.inc(n_micro)
    if data_axis:
        from paddle_tpu.parallel.mesh import axis_size

        d = axis_size(mesh, data_axis)
        if (b // n_micro) % d != 0:
            raise ValueError(
                f"dp x pp: microbatch size {b // n_micro} "
                f"(batch {b} / n_micro {n_micro}) not divisible by the "
                f"data axis '{data_axis}' ({d} ranks)")
    x_m = x.reshape((n_micro, b // n_micro) + x.shape[1:])
    streams_m = tuple(
        sv.reshape((n_micro, b // n_micro) + sv.shape[1:])
        for sv in batch_streams
    )

    param_specs = jax.tree.map(
        lambda p: P(pipe_axis, *([None] * (p.ndim - 1))), stage_params
    )

    def local(params, x_micro, streams):
        # shard_map slices the stage axis to length 1; drop it
        params = jax.tree.map(lambda p: p[0], params)
        return _gpipe_local(
            params, x_micro, streams, fn=fn, axis=pipe_axis,
            n_micro=n_micro, with_micro_idx=with_micro_idx
        )

    mb_spec = P(None, data_axis) if data_axis else P()
    # tp x pp composition: mesh axes not named here (e.g. a 'model'
    # tensor-parallel axis) stay AUTO — GSPMD partitions the stage body
    # over them from the stacked weights' own shardings (strategy rules
    # like pipeline_tp_rules put P(pipe, None, model) on a stacked
    # column-parallel weight: dim 0 is the manual stage axis this
    # shard_map slices, the model dim rides through as an auto-axis
    # sharding and GSPMD inserts the row-parallel all-reduces inside the
    # per-tick stage computation).
    manual = {pipe_axis}
    if data_axis:
        manual |= (set(data_axis) if isinstance(data_axis, (tuple, list))
                   else {data_axis})
    # a size-1 axis shards nothing: manual costs nothing, and a stage
    # body over a FULLY manual mesh can hold Pallas kernels directly
    manual |= {a for a in mesh.axis_names if mesh.shape[a] == 1}
    # multi-host dispatch can block inside the call (compile-time
    # rendezvous, a stage rank that never arrives): watchdog-guarded so
    # a hung pipeline schedule produces a stall record, not a silent job
    with _monitor.stall_guard("pipeline.dispatch"):
        out = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(param_specs, mb_spec, mb_spec),
            out_specs=mb_spec,
            axis_names=frozenset(manual),
        )(stage_params, x_m, streams_m)
    return out.reshape((b,) + x.shape[1:])


def collective_signature(mesh: Mesh, pipe_axis: str = "pipe",
                         n_micro: Optional[int] = None) -> dict:
    """Static description of the GPipe schedule's collective footprint
    over ``mesh``: every rank on ``pipe_axis`` runs the same
    ``n_micro + n_stages - 1`` ticks, each ending in one ppermute hop
    (plus the final psum). Consumed by the static verifier's
    collective-order check (analysis.collective_signature) — extraction
    only, no tracing."""
    n_stages = int(mesh.shape[pipe_axis])
    m = int(n_micro) if n_micro else n_stages
    return {
        "participants": n_stages,
        "schedule": "gpipe",
        "ticks": m + n_stages - 1,
    }


def sequential_reference(fn, stage_params, x):
    """Same computation without the pipeline (for parity tests)."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    y = x
    for i in range(n_stages):
        params_i = jax.tree.map(lambda p: p[i], stage_params)
        y = fn(params_i, y)
    return y
