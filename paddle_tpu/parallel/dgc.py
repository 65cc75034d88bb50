"""Deep Gradient Compression: top-k sparsified gradient exchange
(reference: paddle/fluid/operators/dgc_op.h, dgc_clip_by_norm_op.h,
framework/details/sparse_all_reduce_op_handle.h:30; the vendored paper
is Lin et al., "Deep Gradient Compression", arXiv:1712.01887).

TPU-first design. The reference pairs a CUDA k-select kernel with an
NCCL allgather of (index, value) pairs; here the whole step is one pure
function built from ``lax.top_k`` + ``lax.all_gather`` + scatter-add, so
it composes with ``shard_map`` over any mesh axis — the data axis (ICI)
or the slice axis (DCN), where sparse exchange actually pays (round-4
note: ICI dense psum is byte-cheap enough that DGC only wins on slow
inter-slice links or at extreme sparsity).

One deliberate divergence: the reference's ``k`` varies at runtime with
the sparsity rampup schedule. A dynamic ``k`` would force a dynamic
output shape on ``top_k`` — hostile to XLA — so the selection runs at
TWO static widths behind a ``lax.cond`` on the (replicated) step
counter: the schedule-max width during rampup, the terminal-sparsity
width once the schedule saturates, with the per-step effective k
applied as a mask inside each. Same trajectory, static shapes, and the
steady-state exchange moves only ~n/1000 entries, not the warmup max.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# The saturation sparsity every schedule converges to past rampup_step
# (the reference's hard-coded 0.999, dgc_op.h:24). period_sparsity's
# saturation value, max_k's tail and dgc_step's steady-state gather
# width k_term all derive from THIS constant — they must agree or the
# steady-state mask silently truncates the exchange.
_TERMINAL_SPARSITY = 0.999


def period_sparsity(sparsity: Sequence[float], step, rampup_step: float):
    """The reference's get_period_sparcity (dgc_op.h:24): index the
    sparsity list by ``step * len / rampup_step`` (note: GLOBAL step,
    the reference quirk), saturating at _TERMINAL_SPARSITY."""
    sp = jnp.asarray(list(sparsity), jnp.float32)
    idx = (step.astype(jnp.float32) * len(sparsity)
           / float(rampup_step)).astype(jnp.int32)
    return jnp.where(idx >= len(sparsity), jnp.float32(_TERMINAL_SPARSITY),
                     sp[jnp.clip(idx, 0, len(sparsity) - 1)])


def max_k(numel: int, sparsity: Sequence[float]) -> int:
    """Static selection width: the largest per-step k the schedule can
    ask for (plus the saturated terminal tail)."""
    ratios = [1.0 - s for s in sparsity] + [1.0 - _TERMINAL_SPARSITY]
    return max(1, int(numel * max(ratios)))


def dgc_step(
    g: jax.Array,
    u: jax.Array,
    v: jax.Array,
    step: jax.Array,
    *,
    momentum: float,
    sparsity: Sequence[float] = (0.999,),
    rampup_begin_step: float = 0.0,
    rampup_step: float = 1.0,
    use_nesterov: bool = False,
    axis: Optional[str] = None,
    combine: str = "sum",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One DGC iteration for one parameter's gradient.

    Per the reference kernel (dgc_op.h:86-129): momentum-correct the
    residual accumulators (``u = m*u + g; v = v + u``, nesterov:
    ``u = m*(u+g); v = v + u + g``), select the top-k of ``|v|``, zero
    ``u``/``v`` at the selected (sent) positions, and exchange ONLY the
    selected (index, value) pairs over ``axis``; the decoded gradient is
    the scatter-add of every worker's selection. Before
    ``rampup_begin_step`` the dense gradient passes through untouched
    (the reference's early return).

    ``g``/``u``/``v`` may be any shape (flattened internally). ``axis``
    names a mesh axis when called under ``shard_map`` with per-worker
    LOCAL gradients and ``combine='sum'`` — the honest multi-worker
    exchange. ``combine='mean'`` divides the decoded sum by the axis
    size, for gradients that are ALREADY globally reduced (the GSPMD
    whole-program path, where every worker holds the same g and the
    exchange is redundant-but-correct).

    Returns ``(decoded_grad, u_new, v_new)`` with ``g``'s shape.
    """
    shape = g.shape
    gf = g.reshape(-1).astype(jnp.float32)
    uf = u.reshape(-1).astype(jnp.float32)
    vf = v.reshape(-1).astype(jnp.float32)
    n = gf.shape[0]
    step = jnp.asarray(step, jnp.float32).reshape(())

    if use_nesterov:
        u2 = momentum * (uf + gf)
        v2 = vf + u2 + gf
    else:
        u2 = momentum * uf + gf
        v2 = vf + u2

    kmax = min(max_k(n, sparsity), n)
    # steady-state width: once the schedule saturates (step >=
    # rampup_step -> sparsity 0.999), k_eff never exceeds the terminal
    # k again, so gathering the full schedule-max width forever would
    # move ~max_ratio*n entries per step in perpetuity (e.g. n/4 with
    # the paper's 0.75-first warmup) instead of n/1000 — negating the
    # byte cut dgc_allreduce_bytes models. +1 absorbs the f32-vs-python
    # rounding of the reference's int cast.
    k_term = min(n, max(1, int(n * (1.0 - _TERMINAL_SPARSITY))) + 1)
    ratio = 1.0 - period_sparsity(sparsity, step, rampup_step)
    k_eff = jnp.maximum(
        (ratio * n).astype(jnp.int32), 1)            # reference int cast

    def _select_exchange(width):
        _, idx = lax.top_k(jnp.abs(v2), width)
        live = jnp.arange(width) < jnp.minimum(k_eff, width)
        sent_vals = jnp.where(live, v2[idx], 0.0)
        sent_idx = jnp.where(live, idx, 0)           # dead slots add 0.0

        # momentum factor masking: sent positions reset locally
        # (scatter-min so a dead slot's index-0 placeholder can't
        # overwrite a live zero)
        keep = jnp.ones((n,), jnp.float32).at[sent_idx].min(
            jnp.where(live, 0.0, 1.0))
        u3 = u2 * keep
        v3 = v2 * keep

        if axis is not None:
            all_vals = lax.all_gather(sent_vals, axis)   # [W, width]
            all_idx = lax.all_gather(sent_idx, axis)
            dec = jnp.zeros((n,), jnp.float32).at[
                all_idx.reshape(-1)].add(all_vals.reshape(-1))
            if combine == "mean":
                dec = dec / all_vals.shape[0]
        else:
            dec = jnp.zeros((n,), jnp.float32).at[sent_idx].add(sent_vals)
        return dec, u3, v3

    if kmax > k_term:
        # two static widths behind a cond: every rank holds the same
        # replicated step, so all ranks take the same branch and the
        # collective is uniform; steady state moves only k_term entries.
        decoded, u3, v3 = lax.cond(
            step >= float(rampup_step),
            lambda: _select_exchange(k_term),
            lambda: _select_exchange(kmax))
    else:
        decoded, u3, v3 = _select_exchange(kmax)

    active = step >= float(rampup_begin_step)
    decoded = jnp.where(active, decoded, gf)
    u_out = jnp.where(active, u3, uf)
    v_out = jnp.where(active, v3, vf)
    return (decoded.reshape(shape).astype(g.dtype),
            u_out.reshape(shape).astype(u.dtype),
            v_out.reshape(shape).astype(v.dtype))


def clip_by_norm_rampup(g, step, *, clip_norm: float,
                        rampup_begin_step: float):
    """The reference's dgc_clip_by_norm (dgc_clip_by_norm_op.h): past
    the rampup begin step, clip the LOCAL gradient to ``clip_norm``
    (callers pass local_grad_clip_norm / num_trainers**2); before it,
    pass through."""
    norm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
    active = jnp.asarray(step, jnp.float32).reshape(()) >= float(
        rampup_begin_step)
    return jnp.where(active, g * scale.astype(g.dtype), g)


def dgc_allreduce_bytes(numel: int, k: int, world: int) -> dict:
    """Comm cost model for the note above: per-device bytes moved
    by a ring dense allreduce vs the DGC allgather of (idx, val) pairs.
    Dense ring: 2 * numel * 4 * (W-1)/W. DGC allgather: (W-1) * k * 8
    received per device (4B value + 4B index per entry)."""
    dense = 2 * numel * 4 * (world - 1) / world
    sparse = (world - 1) * k * 8
    return {"dense_bytes": dense, "sparse_bytes": sparse,
            "payoff": dense / max(sparse, 1)}
