"""Expert-parallel Mixture-of-Experts op.

Net-new capability vs the reference (SURVEY.md section 2.3: "EP, MoE —
absent in reference; in-scope as native capabilities"). This op makes
``parallel/moe.py`` reachable from the Program IR the same way ring
attention is reachable from scaled_dot_product_attention: when the program
runs under a DistributedStrategy declaring an ``expert_axis``, tokens are
dispatched over ICI with ``lax.all_to_all`` (one expert per rank);
otherwise the identical fixed-capacity Switch math runs densely on one
device, so 1-device and n-device runs of the same program are comparable.

Inputs: X [.., d] tokens (any leading shape), GateW [d, E] router,
stacked expert FFN weights W1 [E, d, dff], B1 [E, dff], W2 [E, dff, d],
B2 [E, d]. Outputs: Out (same shape as X), AuxLoss [] (Switch
load-balancing loss; add ``aux_weight * AuxLoss`` to the training loss).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core import interp
from paddle_tpu.core.registry import register_op

_ACTS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


def _x(ins, slot, i=0):
    v = ins.get(slot)
    return v[i] if v else None


@register_op(
    "switch_moe",
    diff_inputs=("X", "GateW", "W1", "B1", "W2", "B2"),
    doc="Switch-style top-1 MoE FFN; expert-parallel all_to_all dispatch "
        "under a strategy expert axis (parallel/moe.py)",
)
def _switch_moe(ins, attrs):
    """Capacity caveat: expert capacity is ``cap_factor * n_local / e``
    where n_local is the PER-RANK token count under a data axis. Global
    capacity matches the dense path (capacity * ranks == cap_factor*n/e),
    but truncation applies per rank — so a 1-device and an n-device run
    of the same program are bit-comparable only while no expert
    overflows its per-rank capacity (skewed routing truncates earlier
    distributed). Raise ``capacity_factor`` if dropped-token parity
    matters (see tests/test_moe_ir.py)."""
    x = _x(ins, "X")
    gate_w = _x(ins, "GateW")
    w1, b1 = _x(ins, "W1"), _x(ins, "B1")
    w2, b2 = _x(ins, "W2"), _x(ins, "B2")
    act = _ACTS[attrs.get("act", "relu")]
    cap_factor = float(attrs.get("capacity_factor", 2.0))
    e = int(gate_w.shape[-1])

    shape = jnp.shape(x)
    d = shape[-1]
    xf = jnp.reshape(x, (-1, d))
    n = int(xf.shape[0])
    # Router math in f32 regardless of the AMP activation stream: argmax
    # ties and softmax fractions are routing decisions, not a bandwidth
    # bound, and bf16 routing can diverge between runs.
    gate_w = gate_w.astype(jnp.float32)

    def ffn(p, t):
        pw1, pb1, pw2, pb2 = p
        h = act(t @ pw1.astype(t.dtype) + pb1.astype(t.dtype))
        return h @ pw2.astype(t.dtype) + pb2.astype(t.dtype)

    params = (w1, b1, w2, b2)

    from paddle_tpu.core.interp import spmd_ctx
    from paddle_tpu.parallel import moe

    ctx = spmd_ctx()
    dist = None
    if ctx is not None and ctx.expert_axis is not None:
        mesh = ctx.mesh
        # A declared expert axis that cannot serve this op is a strategy
        # configuration error, not a fallback case: silently running the
        # dense path would leave the [E, ...] expert weights sharded by
        # moe_rules with no all_to_all — GSPMD would all-gather them every
        # step with no signal (cf. DistributedStrategy strict rationale).
        if mesh.shape[ctx.expert_axis] != e:
            raise ValueError(
                f"switch_moe: strategy expert_axis '{ctx.expert_axis}' has "
                f"mesh size {mesh.shape[ctx.expert_axis]} but the op has "
                f"{e} experts; they must match (one expert per rank)"
            )
        from paddle_tpu.parallel.mesh import axis_size

        data_axis = ctx.data_axis
        n_ranks = axis_size(mesh, data_axis) if data_axis else 1
        if data_axis is not None and n % n_ranks != 0:
            raise ValueError(
                f"switch_moe: {n} tokens do not divide the data axis "
                f"'{data_axis}' ({n_ranks} ranks)"
            )
        dist = (mesh, ctx.expert_axis, data_axis, n_ranks)

    n_loc = n // (dist[3] if dist else 1)
    capacity = max(1, int(cap_factor * n_loc / e))

    if dist is not None:
        mesh, expert_axis, data_axis, _ = dist
        out, aux = moe.moe_ffn(
            xf, gate_w, params, ffn, mesh,
            expert_axis=expert_axis, data_axis=data_axis, capacity=capacity,
        )
    else:
        out, aux = moe.moe_dense(xf, gate_w, params, ffn, capacity)
    return {
        "Out": [jnp.reshape(out, shape).astype(x.dtype)],
        "AuxLoss": [aux.astype(jnp.float32)],
    }


# ---------------------------------------------------------------------------
# Dropless top-k MoE (OLMoE, arXiv:2409.02060): four ops, so that the
# program's name scopes (router / dispatch / experts / combine) reach the
# device trace in both directions. Every chosen (token, expert) pair is
# computed: the pairs are sorted by expert and the experts' matmuls run
# over the ragged groups (parallel/grouped_matmul.py: the program's own
# kernels, or jax.lax.ragged_dot), no capacity, no padding to a worst
# case, nothing dropped. ``layers.topk_moe`` wires them up.
# What it shares with switch_moe above: the float32 router and the
# stacked [E, ...] expert weights; switch_moe is argmax top-1 with a
# fixed capacity per expert, biases and ReLU/GELU experts.
# ---------------------------------------------------------------------------


_M_ROWS = _monitor.counter(
    "pt_moe_rows_total",
    "(token, expert) rows the experts of a top-k MoE layer computed, by "
    "layer and expert: fed by record_expert_rows from a fetched "
    "expert_rows, telemetry on only")


def record_expert_rows(layer: str, rows):
    """Add one step's ``expert_rows`` ([E], as ``layers.topk_moe``
    returns it and a trainer fetched it) of ``layer`` to
    ``pt_moe_rows_total{layer, expert}``. The rows are device values:
    nothing counts them unless a caller fetches them, and nothing is
    counted while telemetry is off."""
    if not _monitor.enabled():
        return
    for e, n in enumerate(rows):
        if n:
            _M_ROWS.inc(int(n), labels={"layer": layer, "expert": str(e)})


_M_ROUTER = _monitor.counter(
    "pt_moe_router_dispatch_total",
    "moe_router calls lowered (trace time, telemetry on), by score "
    "(softmax / sigmoid), bias (1 where a selection bias moves the "
    "choice), k and experts (the router's outputs)")


def _sigmoid_router(logits, rows, bias, attrs):
    """The sigmoid form (DeepSeek-V3, arXiv:2412.19437 2.1.2) of logits
    [n, E]: scores s = sigmoid(logits); the k experts are the largest of
    s + Bias (the bias moves the CHOICE and never the weight), the
    weights the chosen s themselves (renormalised over the k if
    ``norm_topk``) times ``routed_scale``. -> (TopW, TopI, LBLoss), the
    last the sequence-wise balance loss: the mean over the ``rows``
    sequences the n tokens are of sum_e f_e P_e, f_e = E / (k t) * the
    row's count of e, P_e the row's mean of s_e / sum_e' s_e'. One group
    of experts only (``n_group`` 1: no group step)."""
    if (int(attrs.get("n_group", 1)), int(attrs.get("topk_group", 1))) \
            != (1, 1):
        raise NotImplementedError(
            "moe_router: experts chosen by groups first (n_group > 1)")
    k, e = int(attrs["k"]), int(logits.shape[-1])
    s = jax.nn.sigmoid(logits)
    pick = s if bias is None else s + bias.astype(jnp.float32)
    _, top_i = jax.lax.top_k(jax.lax.stop_gradient(pick), k)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if attrs.get("norm_topk", False):
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * float(attrs.get("routed_scale", 1.0))
    chosen = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=1)
    f = jnp.mean(chosen.reshape(rows, -1, e), 1) * (e / k)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(rows, -1, e), 1)
    return top_w, top_i, jnp.mean(jnp.sum(f * p, -1))


@register_op("moe_router", diff_inputs=("X", "W"))
def _moe_router(ins, attrs):
    """X [.., d] tokens (n of them), W [d, E] -> TopW [n, k] f32 (the softmax
    probabilities of the k largest, renormalised only if ``norm_topk``),
    TopI [n, k] int32, LBLoss [] (E * sum_e f_e * P_e: f_e the share of
    tokens that chose e, summed over the k slots; P_e the mean
    probability) and ZLoss [] (mean squared logsumexp of the logits).
    ``score="sigmoid"`` (with the optional input Bias [E] and the attr
    ``routed_scale``): ``_sigmoid_router``'s TopW, TopI and LBLoss, a
    row of X [b, t, d] a sequence.

    Float32 whatever the activation stream: logits at the highest matmul
    precision (on a TPU a default f32 matmul is one bf16 pass), scores,
    bias add and top-k in f32. Which experts a token takes is a
    decision, not a bandwidth bound."""
    w = _x(ins, "W").astype(jnp.float32)
    tokens = _x(ins, "X")
    x = tokens.astype(jnp.float32).reshape(-1, w.shape[0])
    k = int(attrs["k"])
    e = int(w.shape[-1])
    score, bias = attrs.get("score", "softmax"), _x(ins, "Bias")
    if score not in ("softmax", "sigmoid") or (
            score == "softmax" and bias is not None):
        raise ValueError(f"moe_router: score={score!r} with"
                         f"{'' if bias is not None else 'out'} a Bias")
    if _monitor.enabled() and interp.lowering_active():
        _M_ROUTER.inc(labels={
            "score": score, "bias": str(int(bias is not None)),
            "k": str(k), "experts": str(e)})
    logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        top_w, top_i, lb = _sigmoid_router(
            logits, tokens.shape[0] if tokens.ndim == 3 else 1, bias, attrs)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        if attrs.get("norm_topk", False):
            top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
        chosen = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=1)
        lb = e * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(probs, 0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return {"TopW": [top_w], "TopI": [top_i.astype(jnp.int32)],
            "LBLoss": [lb], "ZLoss": [z]}


@register_op("moe_bias_update", no_grad=True)
def _moe_bias_update(ins, attrs):
    """The selection bias's step (auxiliary-loss-free balancing,
    arXiv:2412.19437 2.1.2): Bias [E] f32, TopI [n, k] (the router's
    choices of this step, over all E) -> BiasOut = Bias + ``gamma`` *
    sign(mean_e(count) - count_e): an expert chosen less than the
    average is lifted, one chosen more is lowered. No gradient reaches
    or leaves it; ``layers.topk_moe(select_bias=True)`` has the
    optimizer append it to the training step (role opt)."""
    bias, top_i = _x(ins, "Bias"), _x(ins, "TopI")
    e = int(bias.shape[0])
    count = jnp.sum(jax.nn.one_hot(top_i.reshape(-1), e, dtype=jnp.float32),
                    axis=0)
    step = float(attrs["gamma"]) * jnp.sign(jnp.mean(count) - count)
    return {"BiasOut": [bias + step.astype(bias.dtype)]}


@jax.custom_vjp
def _rows(x, idx, back):
    """``x[idx]`` where ``back`` [n, r] lists, for each row of x, the r
    places of the result that copy it: the cotangent is then a gather
    and a sum over r, not a scatter-add over repeated indices."""
    return jnp.take(x, idx, axis=0)


def _rows_fwd(x, idx, back):
    return jnp.take(x, idx, axis=0), back


def _rows_bwd(back, g):
    return (jnp.sum(jnp.take(g, back, axis=0), axis=1), None, None)


_rows.defvjp(_rows_fwd, _rows_bwd)


def _slot_major(x, slot):
    """The rows of ``x`` [n*k, d] that hold each token's k pairs, as
    [k, n, d] (``slot`` [n, k]: moe_dispatch's): the slot in FRONT. A
    [n, k, d] array whose k is no multiple of 8 (Qwen3-Next's 10) is
    padded to one on the chip, k being the tile's second-minor
    dimension: 60% more bytes in every pass over it and a relayout
    copy that carries no scope (2.7 ms a layer at 8192 x 10 x 2048, my
    chip run, PR 32). In front k is padded by nothing."""
    n, k = slot.shape
    return jnp.take(x, slot.T.reshape(-1), axis=0).reshape(k, n, -1)


@jax.custom_vjp
def _rows_of_pairs(x, idx, slot):
    """``_rows`` for a held share: ``x[idx]`` whose cotangent is the sum
    over each token's k rows, gathered slot-major (``_slot_major``)."""
    return jnp.take(x, idx, axis=0)


def _rows_of_pairs_fwd(x, idx, slot):
    return jnp.take(x, idx, axis=0), slot


def _rows_of_pairs_bwd(slot, g):
    return (jnp.sum(_slot_major(g, slot).astype(jnp.float32), axis=0
                    ).astype(g.dtype), None, None)


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


@register_op("moe_dispatch", diff_inputs=("X",))
def _moe_dispatch(ins, attrs):
    """X [.., d], TopI [n, k] -> Xs [n*k, d]: one row per chosen (token,
    expert) pair, sorted by expert (stable: by token inside an expert);
    Rows [E] int32, the rows each expert got; Order [n*k] int32, the
    flat pair (token * k + slot) at each row of Xs; Slot [n, k] int32,
    its inverse: the row of Xs that holds pair (token, slot).

    ``held_first`` / ``held_count``: this layer holds only experts
    first .. first + count - 1 of the ``num_experts`` the router scored
    (one chip's share under expert parallelism). Rows is then [count],
    the held experts' rows, which sum to less than n*k; the pairs on
    held experts come first in Xs, sorted by expert, and the pairs on
    experts held elsewhere lie behind them (by token): they keep their
    place in the buffer, which has a row for every pair whatever the
    routing, and no expert here reads them."""
    x, top_i = _x(ins, "X"), _x(ins, "TopI")
    x = x.reshape(-1, x.shape[-1])
    n, k = top_i.shape
    e = int(attrs["num_experts"])
    flat = top_i.reshape(-1)
    if "held_count" in attrs:
        e = int(attrs["held_count"])
        local = flat - int(attrs.get("held_first", 0))
        flat = jnp.where(jnp.logical_and(local >= 0, local < e), local, e)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    slot = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    rows = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
    gather = _rows_of_pairs if "held_count" in attrs else _rows
    return {"Xs": [gather(x, order // k, slot)], "Rows": [rows],
            "Order": [order], "Slot": [slot]}


def _live_rows(attrs, m):
    """grouped_matmul's ``live_rows`` for an experts op over m rows:
    nothing for a layer that holds every expert its router scores."""
    if "held_count" not in attrs:
        return {}
    return {"live_rows": -(-m * int(attrs["held_count"])
                           // int(attrs["num_experts"]))}


def _swiglu(gate, up):
    return (jax.nn.silu(gate) * up).astype(gate.dtype)


@register_op("moe_experts", diff_inputs=("Xs", "WGate", "WUp", "WDown"))
def _moe_experts(ins, attrs):
    """SwiGLU experts over ragged groups: Xs [m, d] sorted by expert,
    Rows [E] its group sizes, WGate / WUp [E, d, f], WDown [E, f, d] ->
    Ys [m, d] = (silu(Xs WGate[e]) * (Xs WUp[e])) WDown[e], e the
    row's expert. Under AMP the lowering casts rows and weights to bf16
    (core/interp.AMP_OP_TYPES). Each grouped matmul is
    ``parallel/grouped_matmul.grouped_matmul``: the program's ``moe.*``
    Pallas kernels where ``gmm_tile`` gives the call a tile, else
    ``jax.lax.ragged_dot`` (on the v5e libtpu's ``ragged-dot-none``
    Mosaic call). Also emits the two projections (Gate, Up [m, f]) so
    that the paired grad op below does not run them again: XLA cannot
    CSE custom calls (dead when nothing reads them).

    ``held_count`` of ``num_experts`` (a held share of the experts,
    see moe_dispatch): Rows sum to less than m; the rows behind the last
    group are multiplied by nothing and Ys has zeros there, so they add
    nothing to moe_combine's sum. The grouped matmuls are told the rows
    an even router would put on the held experts (``live_rows``: their
    row tile goes with those, not with the buffer). Such a layer also
    hands over X, the tokens, and Order (moe_dispatch's): the grad op
    gathers Xs again from them and does not keep the buffer, 16 times
    its live rows, from the forward pass (0.32 GB a layer at 81,920
    rows of 2048)."""
    from paddle_tpu.parallel.grouped_matmul import grouped_matmul

    xs, rows = _x(ins, "Xs"), _x(ins, "Rows")
    wg, wu, wd = _x(ins, "WGate"), _x(ins, "WUp"), _x(ins, "WDown")
    kw = _live_rows(attrs, xs.shape[0])
    gate = grouped_matmul(xs, wg.astype(xs.dtype), rows, **kw)
    up = grouped_matmul(xs, wu.astype(xs.dtype), rows, **kw)
    ys = grouped_matmul(_swiglu(gate, up), wd.astype(xs.dtype), rows, **kw)
    return {"Ys": [ys], "Gate": [gate], "Up": [up]}


@register_op("moe_experts_grad", no_grad=True)
def _moe_experts_grad(ins, attrs):
    """The six grouped matmuls of the backward pass from the forward's
    saved Gate and Up: no projection runs twice (the generic vjp-style
    grad op would trace the forward again, and a custom call that is
    traced twice executes twice)."""
    from paddle_tpu.parallel.grouped_matmul import grouped_matmul_grads

    xs, rows = _x(ins, "Xs"), _x(ins, "Rows")
    if _x(ins, "X") is not None:
        # a held share: gathered again (behind a barrier, or XLA merges
        # this gather with moe_dispatch's and keeps that one's result)
        x, order = jax.lax.optimization_barrier(
            (_x(ins, "X"), _x(ins, "Order")))
        x = x.reshape(-1, x.shape[-1])
        xs = jnp.take(x, order // (xs.shape[0] // x.shape[0]),
                      axis=0).astype(xs.dtype)
    wg, wu, wd = _x(ins, "WGate"), _x(ins, "WUp"), _x(ins, "WDown")
    gate = _x(ins, "Gate").astype(xs.dtype)
    up = _x(ins, "Up").astype(xs.dtype)
    g = _x(ins, "GRAD::Ys").astype(xs.dtype)
    kw = _live_rows(attrs, xs.shape[0])
    h, swiglu_vjp = jax.vjp(_swiglu, gate, up)
    dh, dwd = grouped_matmul_grads(h, wd.astype(xs.dtype), rows, g, **kw)
    dgate, dup = swiglu_vjp(dh)
    dx_gate, dwg = grouped_matmul_grads(xs, wg.astype(xs.dtype), rows,
                                        dgate, **kw)
    dx_up, dwu = grouped_matmul_grads(xs, wu.astype(xs.dtype), rows, dup,
                                      **kw)
    return {"GRAD::Xs": [dx_gate + dx_up],
            "GRAD::WGate": [dwg.astype(wg.dtype)],
            "GRAD::WUp": [dwu.astype(wu.dtype)],
            "GRAD::WDown": [dwd.astype(wd.dtype)]}


@register_op("moe_combine", diff_inputs=("Ys", "TopW"))
def _moe_combine(ins, attrs):
    """Ys [n*k, d] expert outputs in dispatch order, TopW [n, k], Order
    and Slot of moe_dispatch -> Out = sum_j TopW[t, j] * Ys[Slot[t, j]],
    in the shape of Like (the tokens as the router got them): summed
    in f32, returned in Ys's dtype."""
    ys, top_w = _x(ins, "Ys"), _x(ins, "TopW")
    order, slot = _x(ins, "Order"), _x(ins, "Slot")
    n, k = slot.shape
    if "held_count" in attrs:   # a held share: slot-major, its own grad op
        out = jnp.einsum("knd,nk->nd",
                         _slot_major(ys, slot).astype(jnp.float32),
                         top_w.astype(jnp.float32))
        return {"Out": [out.astype(ys.dtype).reshape(
            _x(ins, "Like").shape)]}
    picked = _rows(ys, slot.reshape(-1), order[:, None]).reshape(n, k, -1)
    out = jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                     top_w.astype(jnp.float32))
    return {"Out": [out.astype(ys.dtype).reshape(_x(ins, "Like").shape)]}


@register_op("moe_combine_grad", no_grad=True)
def _moe_combine_grad(ins, attrs):
    """A layer that holds every expert its router scores: jax's
    transposes of the forward above, as the generic grad op derives
    them. A held share (``held_count``, see moe_dispatch), whose row
    buffer is num_experts / held_count times its live rows, writes the
    two cotangents out instead: jax's transpose of the weighted sum
    keeps the gathered rows AND their products as float32 [n, k, d]
    (1.6 GB at 81,920 rows of 2048 compiled for a v5e, and the gathered
    rows saved from the forward pass beside them); here GRAD::Ys is one
    gather of the cotangent's rows times the pair's weight, and
    GRAD::TopW one product of Ys's rows, gathered again, with the
    cotangent, accumulated in float32: nothing is kept from the forward
    pass and nothing float32 is [n, k, d]. Where the pairs of a token
    lie side by side they do so slot-major (``_slot_major``)."""
    if "held_count" not in attrs:
        from paddle_tpu.core import autodiff
        from paddle_tpu.core.registry import get_op_def

        return autodiff.make_grad_compute(get_op_def("moe_combine"))(
            ins, attrs)
    # (behind a barrier, or XLA merges the gather of Ys's rows below
    # with the forward op's and keeps that one's result)
    ys, slot = jax.lax.optimization_barrier((_x(ins, "Ys"), _x(ins, "Slot")))
    top_w, order = _x(ins, "TopW"), _x(ins, "Order")
    n, k = slot.shape
    g = _x(ins, "GRAD::Out").reshape(n, -1)
    pair_w = jnp.take(top_w.astype(jnp.float32).reshape(-1), order)
    d_ys = (jnp.take(g, order // k, axis=0).astype(jnp.float32)
            * pair_w[:, None]).astype(ys.dtype)
    d_w = jnp.einsum("knd,nd->nk", _slot_major(ys, slot),
                     g.astype(ys.dtype),
                     preferred_element_type=jnp.float32)
    return {"GRAD::Ys": [d_ys], "GRAD::TopW": [d_w.astype(top_w.dtype)]}
