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

import functools

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core import interp
from paddle_tpu.core.registry import register_op
from paddle_tpu.parallel import grouped_matmul as _gm
from paddle_tpu.parallel import pair_sum as _ps
from paddle_tpu.parallel.grouped_matmul import (live_window, over_live_rows,
                                                put_rows, rows_at)

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
            "k": str(k), "experts": str(e),
            "input": attrs.get("input", "own")})
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
    and a sum over r, not a scatter-add over repeated indices
    (moe_combine's gather of Ys in a layer that holds every expert; the
    tokens' gather has ``_rows_of_pairs``)."""
    return jnp.take(x, idx, axis=0)


def _rows_fwd(x, idx, back):
    return jnp.take(x, idx, axis=0), back


def _rows_bwd(back, g):
    return (jnp.sum(jnp.take(g, back, axis=0), axis=1), None, None)


_rows.defvjp(_rows_fwd, _rows_bwd)


# A held share (``held_count`` of ``num_experts``: one chip of an
# expert-parallel group). Its row buffer has a row for every pair the
# router made, n * k, so that no routing drops a token; the pairs on held
# experts, the LIVE rows, come first, and an even router makes a
# sixteenth of the buffer live. Every pass such a layer makes over the
# buffer is a loop over the windows that hold a live row
# (``over_live_rows``: the trip count is sum(Rows), the step's own, a
# device scalar), as the grouped matmuls visit no tile behind the last
# group. The two token-major ones (a token's sum over its pairs) are one
# Pallas kernel that fetches the groups of 16 rows a token tile's pairs
# lie in and adds them on the MXU (``_add_into_tokens``,
# parallel/pair_sum.py), and without a TPU a gather of all k rows of
# every token and their sum (``_sum_by_token``).
#
# The buffers' contract (PR 63): every buffer, inside an op and handed
# on (Xs, Gate, Up, h, Ys and the cotangents), is FINITE TO THE END OF
# THE ROW TILE THE LAST LIVE ROW LIES IN (zeros behind that row) AND NOT
# DEFINED BEHIND IT, and nothing writes behind: no buffer is born as n *
# k rows of zeros. Every reader keeps to that: the grouped matmuls visit
# no tile behind the last group and mask a straddling tile themselves
# (``gmm`` on the write, ``tgmm`` on the read, where rows of its lhs are
# multiplied by zeros: hence finite to the tile's end), ``pairs.sum.*``
# fetches only groups of rows a live pair lies in, ``_sum_by_token``
# selects, and a row-major pass reads by window under ``keep``. A
# row-major pass starts from memory nothing filled (``_carry``:
# ``grouped_matmul.unfilled``) and the ``jnp.where(keep, v, 0)`` of its
# last trip zeroes the window's tail, which covers the row tile because
# the window is whole row tiles (``_carry`` looks: where it is not, or
# the layer's matmuls run as no kernel, the carry is zeros as it was and
# ``pt_moe_buffer_fills_total`` says so); a grouped matmul's result has
# the one tile the last group ends in zeroed behind it
# (``zero_behind="tile"``), and the three that stay inside an op (dh and
# the two halves of d Xs) not even that (``zero_behind=False``).

_M_PASSES = _monitor.counter(
    "pt_moe_rows_dispatch_total",
    "passes of a top-k MoE layer over its row buffer lowered (trace time, "
    "telemetry on), by op, pass, form (windowed: a loop over the windows "
    "of `window` rows that hold a live row, the trip count the step's "
    "own; kernel: a token-major sum as the pairs.sum.* Pallas kernel, "
    "which fetches the groups of rows a token tile's pairs lie in; "
    "windowed|by_token: that sum where the kernel takes no tile, a gather "
    "of all k rows of every token of which the live ones are added (the "
    "label is older than the form: readers know it); whole: the pass "
    "walks all buffer_rows) and buffer_rows")


_M_FILLS = _monitor.counter(
    "pt_moe_buffer_fills_total",
    "whole-buffer zero fills a held top-k MoE layer lowers (trace time, "
    "telemetry on), by op, buffer (the pass's result: Xs, h, GRAD::Ys, "
    "..), rows and width: a row-major pass whose first carry is zeros "
    "and not memory nothing filled (no TPU, a mesh, matmuls that run as "
    "no kernel, a window that is not whole row tiles)")


def _live_rows(attrs, m):
    """grouped_matmul's ``live_rows`` for an experts op over m rows:
    nothing for a layer that holds every expert its router scores."""
    if "held_count" not in attrs:
        return {}
    return {"live_rows": -(-m * int(attrs["held_count"])
                           // int(attrs["num_experts"]))}


def _window(attrs, m):
    """The rows a trip of a held layer's passes works on
    (``live_window``, from the shape); None for a layer
    that holds every expert: its buffer is all live rows and its passes
    walk it whole."""
    kw = _live_rows(attrs, m)
    return live_window(m, kw["live_rows"]) if kw else None


def _note_passes(op, m, w, *passes, by_token=(), kernel=()):
    """One row of ``pt_moe_rows_dispatch_total`` a pass of ``op`` over
    its m-row buffer, from the branch that lowers them: ``w`` the window
    its loop takes, None where it walks the buffer whole; ``by_token``
    those of the passes that walk it by token and ``kernel`` those the
    ``pairs.sum.*`` kernel does (``_add_into_tokens``). A grad op that
    traces its forward again counts that one's passes again, as the
    router's counter does."""
    if not _monitor.enabled() or not interp.lowering_active():
        return
    for name in passes:
        form = ("kernel" if name in kernel else "whole" if not w else
                "windowed|by_token" if name in by_token else "windowed")
        _M_PASSES.inc(labels={
            "op": op, "pass": name, "form": form,
            "buffer_rows": str(m), "window": str(w or "")})


def rows_dispatch_counts():
    """{"op pass form buffer_rows[ window]": passes lowered so far}: the
    counter above as chip_smoke.py prints it."""
    out = {}
    for row in _monitor.snapshot()[_M_PASSES.name]["values"]:
        lb = row["labels"]
        name = " ".join(lb.get(key, "?") for key in
                        ("op", "pass", "form", "buffer_rows"))
        if lb.get("window"):
            name += f" w{lb['window']}"
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def _carry(attrs, op, w, m, dtype, buffer, width):
    """The first carry of a held layer's row-major pass into ``buffer``
    [m, width]: memory nothing filled (``grouped_matmul.unfilled``)
    where the layer's grouped matmuls are kernels (``row_tile``) and the
    window ``w`` is whole row tiles of theirs, so that the last trip's
    zeros reach the end of the tile the last live row lies in; zeros,
    and a row of ``pt_moe_buffer_fills_total``, anywhere else."""
    tm = _gm.row_tile(m, int(attrs["held_count"]), dtype,
                      **_live_rows(attrs, m))
    if tm is not None and w % tm == 0:
        return _gm.unfilled((m, width), dtype)
    if _monitor.enabled() and interp.lowering_active():
        _M_FILLS.inc(labels={"op": op, "buffer": buffer, "rows": str(m),
                             "width": str(width)})
    return jnp.zeros((m, width), dtype)


def buffer_fill_counts():
    """{"op buffer rows x width": fills lowered so far}: the counter
    above as chip_smoke.py prints it."""
    out = {}
    for row in _monitor.snapshot()[_M_FILLS.name]["values"]:
        lb = row["labels"]
        name = "%s %s %sx%s" % tuple(
            lb.get(key, "?") for key in ("op", "buffer", "rows", "width"))
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def _live_pass(live, w, into, body):
    """A row-major pass over the live rows: ``body(r0) -> rows`` ([w,
    width] each, one for each buffer of ``into``, ``_carry``'s), written
    in place: zeros behind the last live row to its window's end, and
    what ``into`` held in every window no trip reached."""
    def trip(r0, keep, bufs):
        return tuple(put_rows(b, r0, jnp.where(keep, v, 0))
                     for b, v in zip(bufs, body(r0)))

    return over_live_rows(live, w, trip, tuple(into))


def _gather_live(x, order, live, w, into):
    """Xs of the tokens x [n, d], written into ``into``: row r < live
    is the token of pair ``order[r]``."""
    k = order.shape[0] // x.shape[0]
    return _live_pass(
        live, w, [into],
        lambda r0: (jnp.take(x, rows_at(order, r0, w) // k, axis=0),))[0]


def _sum_by_token(rows, slot, live, top_w=None):
    """The token-major sum as XLA ops, float32: all k rows of every
    token gathered (slot-major: [n, k, d] with k no multiple of 8 is
    padded to one on the chip, the slot in front by nothing, PR 32), the
    pairs behind ``live`` masked, times the weight where there is one,
    summed over k. The form of a process without a TPU and of a call
    ``pair_sum.sum_tile`` gives no tile."""
    n, k = slot.shape
    at = slot.T
    picked = jnp.where(
        (at < live)[..., None],
        jnp.take(rows, at.reshape(-1), axis=0).reshape(k, n, -1), 0
    ).astype(jnp.float32)
    if top_w is None:
        return jnp.sum(picked, axis=0)
    return jnp.einsum("knd,nk->nd", picked, top_w.astype(jnp.float32))


_SUM_KERNELS = {"moe_combine": "pairs.sum.combine",
                "moe_dispatch_grad": "pairs.sum.dispatch_grad"}


def _add_into_tokens(note, rows, slot, sizes, w, top_w=None):
    """[n, d] in the rows' dtype: each live row r of ``rows`` [n * k, d]
    (``sizes``: moe_dispatch's Rows, the held experts' groups, which lie
    first and sum to the live rows), times its pair's weight where
    ``top_w`` [n, k] is given, added into its token (``slot`` [n, k]:
    the row of every pair). Each product and the sum are float32
    whatever the rows' dtype, cast once at the end. ``note`` (op, pass)
    names it in ``pt_moe_rows_dispatch_total`` beside ``w``, the window
    of the layer's other passes.

    ONE kernel, ``parallel/pair_sum.pair_sum``, at the tile ``sum_tile``
    gives the call from its shapes, dtype, backend and mesh; where it
    gives none, ``_sum_by_token``."""
    n, k = slot.shape
    tile = _ps.sum_tile(n, k, rows.shape[1], rows.dtype)
    _note_passes(note[0], n * k, w, note[1],
                 **{"kernel" if tile else "by_token": note[1:]})
    if tile is not None:
        return _ps.pair_sum(rows, slot, sizes, tile, top_w,
                            name=_SUM_KERNELS[note[0]])
    return _sum_by_token(rows, slot, jnp.sum(sizes), top_w).astype(
        rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rows_of_pairs(x, order, slot, sizes, into, w):
    """Xs of the tokens x: the token of pair ``order[r]`` at every row r
    inside a group (``w``: the layer's window and ``into`` its loop's
    first carry, both None for a layer that holds every expert), whose
    cotangent is those rows added into their tokens, not a scatter-add
    over repeated indices."""
    if w is None:
        return jnp.take(x, order // slot.shape[1], axis=0)
    return _gather_live(x, order, jnp.sum(sizes), w, into)


def _rows_of_pairs_fwd(x, order, slot, sizes, into, w):
    return _rows_of_pairs(x, order, slot, sizes, into, w), (slot, sizes)


def _rows_of_pairs_bwd(w, res, g):
    return (_add_into_tokens(("moe_dispatch_grad", "d_x"), g, *res, w),
            None, None, None, None)


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
    routing; no expert here reads them, and Xs has zeros to the end of
    the last live row's window and is NOT DEFINED behind it (the
    contract above: nothing fills the buffer)."""
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
    w = _window(attrs, n * k)
    _note_passes("moe_dispatch", n * k, w, "gather_xs")
    into = None if w is None else _carry(
        attrs, "moe_dispatch", w, n * k, x.dtype, "Xs", x.shape[1])
    xs = _rows_of_pairs(x, order, slot, rows, into, w)
    return {"Xs": [xs], "Rows": [rows], "Order": [order], "Slot": [slot]}


@register_op("moe_dispatch_grad", no_grad=True)
def _moe_dispatch_grad(ins, attrs):
    """GRAD::X of moe_dispatch: every row of GRAD::Xs inside a group
    added into its token (``_add_into_tokens``), in X's shape and
    dtype. The generic grad op would trace the forward again for the
    same sum (``_rows_of_pairs``' rule, which a caller that
    differentiates the forward itself still gets) and hand the kernel a
    name of jax's making."""
    x, slot = _x(ins, "X"), _x(ins, "Slot")
    d_x = _add_into_tokens(("moe_dispatch_grad", "d_x"), _x(ins, "GRAD::Xs"),
                           slot, _x(ins, "Rows"), _window(attrs, slot.size))
    return {"GRAD::X": [d_x.astype(x.dtype).reshape(x.shape)]}


def _swiglu(gate, up):
    return (jax.nn.silu(gate) * up).astype(gate.dtype)


def _reglu(gate, up):
    return (jax.nn.relu(gate) * up).astype(gate.dtype)


def _gated_unit(attrs):
    """The experts' gated unit act(gate) * up of attr ``act``: "silu"
    (SwiGLU, the default) or "relu" (ReGLU)."""
    return {"silu": _swiglu, "relu": _reglu}[attrs.get("act", "silu")]


def _relu2(up):
    return jnp.square(jax.nn.relu(up)).astype(up.dtype)


def _plain_unit(attrs):
    """The activation of experts that are not gated units (attr
    ``gated`` false: two matrices an expert, act(x WUp) WDown): "relu2",
    relu(.)^2 (Nemotron-H's), or "relu"."""
    return {"relu2": _relu2,
            "relu": lambda up: jax.nn.relu(up)}[attrs.get("act", "relu2")]


def _glu_live(glu, gate, up, live, w, into):
    """``glu`` on the live rows of Gate and Up [m, f], into ``into``."""
    return _live_pass(
        live, w, [into],
        lambda r0: (glu(rows_at(gate, r0, w), rows_at(up, r0, w)),))[0]


def _act_live(act, up, live, w, into):
    """``act`` on the live rows of Up [m, f], into ``into``."""
    return _live_pass(
        live, w, [into], lambda r0: (act(rows_at(up, r0, w)),))[0]


def _held(attrs, op, m, dtype):
    """(grouped_matmul's keywords, the window, ``carry(buffer, width)``)
    of an experts op over m rows: for a held share the expected live
    rows with results zeroed to their last row tile's end and no
    further, and ``_carry``; ({}, None, None) for a layer that holds
    every expert."""
    kw, w = _live_rows(attrs, m), _window(attrs, m)
    if w is None:
        return kw, w, None
    return (dict(kw, zero_behind="tile"), w,
            functools.partial(_carry, attrs, op, w, m, dtype))


@register_op("moe_experts", diff_inputs=("Xs", "WGate", "WUp", "WDown"))
def _moe_experts(ins, attrs):
    """Gated experts over ragged groups: Xs [m, d] sorted by expert,
    Rows [E] its group sizes, WGate / WUp [E, d, f], WDown [E, f, d] ->
    Ys [m, d] = (act(Xs WGate[e]) * (Xs WUp[e])) WDown[e], e the
    row's expert; attr ``act`` "silu" (SwiGLU, the default) or "relu"
    (ReGLU). Under AMP the lowering casts rows and weights to bf16
    (core/interp.AMP_OP_TYPES). Each grouped matmul is
    ``parallel/grouped_matmul.grouped_matmul``: the program's ``moe.*``
    Pallas kernels where ``gmm_tile`` gives the call a tile, else
    ``jax.lax.ragged_dot`` (on the v5e libtpu's ``ragged-dot-none``
    Mosaic call). Also emits the two projections (Gate, Up [m, f]) so
    that the paired grad op below does not run them again: XLA cannot
    CSE custom calls (dead when nothing reads them).

    Attr ``gated`` false (Nemotron-H's experts): no WGate and no Gate,
    Ys = act(Xs WUp[e]) WDown[e] with ``act`` "relu2" (relu squared) or
    "relu": two grouped matmuls where a gated unit has three.

    ``held_count`` of ``num_experts`` (a held share of the experts,
    see moe_dispatch): Rows sum to less than m; the rows behind the last
    group are multiplied by nothing, and Ys, Gate and Up have zeros to
    the end of the row tile the last group ends in and are not defined
    behind it (``zero_behind="tile"``). The grouped matmuls are told the
    rows an even router would put on the held experts (``live_rows``: their
    row tile goes with those, not with the buffer), and SwiGLU runs over
    the windows that hold a live row. Such a layer also hands over X,
    the tokens, and Order (moe_dispatch's): the grad op gathers Xs again
    from them and does not keep the buffer, 16 times its live rows, from
    the forward pass (0.32 GB a layer at 81,920 rows of 2048)."""
    xs, rows = _x(ins, "Xs"), _x(ins, "Rows")
    wu, wd = _x(ins, "WUp"), _x(ins, "WDown")
    m = xs.shape[0]
    kw, w, carry = _held(attrs, "moe_experts", m, xs.dtype)
    if not attrs.get("gated", True):
        act = _plain_unit(attrs)
        _note_passes("moe_experts", m, w, "act")
        up = _gm.grouped_matmul(xs, wu.astype(xs.dtype), rows, **kw)
        h = (act(up) if w is None else _act_live(
            act, up, jnp.sum(rows), w, carry("h", up.shape[1])))
        ys = _gm.grouped_matmul(h, wd.astype(xs.dtype), rows, **kw)
        return {"Ys": [ys], "Up": [up]}
    wg = _x(ins, "WGate")
    glu = _gated_unit(attrs)
    # (the pass keeps its name whatever the activation: readers know it)
    _note_passes("moe_experts", m, w, "swiglu")
    gate = _gm.grouped_matmul(xs, wg.astype(xs.dtype), rows, **kw)
    up = _gm.grouped_matmul(xs, wu.astype(xs.dtype), rows, **kw)
    h = (glu(gate, up) if w is None else _glu_live(
        glu, gate, up, jnp.sum(rows), w, carry("h", gate.shape[1])))
    ys = _gm.grouped_matmul(h, wd.astype(xs.dtype), rows, **kw)
    return {"Ys": [ys], "Gate": [gate], "Up": [up]}


# An experts' grad op that carries its matrices' Adam
# (optimizer.AdamOptimizer._fold_into_experts_grad): attr ``adam_slots``
# names the matrices, and each slot below holds one entry a matrix in
# that order, under the names the ``adam`` op has them. The gradient of
# such a matrix (attr ``adam_grads`` keeps its name) is no output of the
# op, unless the lowering finds that something reads it after all (a
# fetch, an op appended behind minimize): ``adam_keep_grads`` then names
# the matrix (core/lowering._with_read_grads), its gradient is made and
# written as ever and the step follows it.
_ADAM_IN = ("Param", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow",
            "LearningRate")
_ADAM_OUT = ("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut")


def _adam_steps(ins, attrs):
    """{matrix slot: (``grouped_matmul.AdamStep``, (Beta1PowOut,
    Beta2PowOut))} for the matrices whose update this grad op takes:
    the scalars as the ``adam`` / ``adamw`` op computes them."""
    from paddle_tpu.ops import optimizer_ops as opt

    steps, kept = {}, attrs.get("adam_keep_grads", ())
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    for i, slot in enumerate(attrs.get("adam_slots", ())):
        p, m1, m2, b1p, b2p, lr = (_x(ins, name, i) for name in _ADAM_IN)
        lr = lr.reshape(())
        b1pn, b2pn = b1p * b1, b2p * b2
        decay = None
        if attrs["adam_op"] == "adamw":
            decay = lr.astype(p.dtype) * attrs.get("weight_decay", 0.01)
        steps[slot] = (_gm.AdamStep(
            (p, m1, m2), opt.adam_lr_t(lr, b1pn, b2pn), decay, b1, b2,
            attrs.get("epsilon", 1e-8)), (b1pn, b2pn), slot in kept)
    return steps


def _adam_of(steps, slot):
    """``grouped_matmul_grads``' keyword for the matrix ``slot``."""
    step = steps.get(slot)
    return {"adam": step[0]} if step and not step[2] else {}


def _experts_grad_outs(attrs, steps, dx, **dws):
    """The grad op's results: GRAD::Xs, and for each matrix (WGate=..)
    its gradient in the weight's dtype or, where its Adam step was taken
    with it (``steps``), the ``adam`` op's five results."""
    outs = {"GRAD::Xs": [dx]}
    for slot in attrs.get("adam_slots", ()):
        step, pows, kept = steps[slot]
        state, dtype = dws.pop(slot)
        if kept:    # state: the gradient, which somebody reads
            outs["GRAD::" + slot] = [state.astype(dtype)]
            state = step.after(outs["GRAD::" + slot][0])
        for name, value in zip(_ADAM_OUT, (*state, *pows)):
            outs.setdefault(name, []).append(value)
    for slot, (dw, dtype) in dws.items():
        outs["GRAD::" + slot] = [dw.astype(dtype)]
    return outs


def _plain_experts_grad(ins, attrs):
    """``moe_experts_grad`` for experts that are not gated units: the
    four grouped matmuls of the backward pass from the forward's saved
    Up; a held share gathers Xs again and runs the activation and its
    gradient over the windows that hold a live row."""
    xs, rows = _x(ins, "Xs"), _x(ins, "Rows")
    wu, wd = _x(ins, "WUp"), _x(ins, "WDown")
    m, dtype = xs.shape[0], xs.dtype
    up = _x(ins, "Up").astype(dtype)
    g = _x(ins, "GRAD::Ys").astype(dtype)
    kw, w, carry = _held(attrs, "moe_experts_grad", m, dtype)
    act = _plain_unit(attrs)
    steps = _adam_steps(ins, attrs)
    adam = functools.partial(_adam_of, steps)
    _note_passes("moe_experts_grad", m, w, "gather_xs", "act", "act_grad")
    if w is None:
        h, act_vjp = jax.vjp(act, up)
        dh, dwd = _gm.grouped_matmul_grads(h, wd.astype(dtype), rows, g,
                                           **adam("WDown"))
        dup, = act_vjp(dh)
        dx, dwu = _gm.grouped_matmul_grads(xs, wu.astype(dtype), rows, dup,
                                           **adam("WUp"))
    else:
        live = jnp.sum(rows)
        # gathered again (behind a barrier, or XLA merges this gather
        # with moe_dispatch's and keeps that one's result)
        x, order = jax.lax.optimization_barrier(
            (_x(ins, "X"), _x(ins, "Order")))
        xs = _gather_live(x.reshape(-1, x.shape[-1]).astype(dtype), order,
                          live, w, carry("Xs", xs.shape[1]))
        h = _act_live(act, up, live, w, carry("h", up.shape[1]))
        # dh is read by window alone; dx is handed on: its last row
        # tile zeroed behind the last live row
        dh, dwd = _gm.grouped_matmul_grads(
            h, wd.astype(dtype), rows, g, **dict(kw, zero_behind=False),
            **adam("WDown"))

        def act_grad(r0):
            _, vjp = jax.vjp(act, rows_at(up, r0, w))
            return vjp(rows_at(dh, r0, w))

        dup, = _live_pass(live, w, [carry("dup", up.shape[1])], act_grad)
        dx, dwu = _gm.grouped_matmul_grads(xs, wu.astype(dtype), rows, dup,
                                           **kw, **adam("WUp"))
    return _experts_grad_outs(attrs, steps, dx, WUp=(dwu, wu.dtype),
                              WDown=(dwd, wd.dtype))


@register_op("moe_experts_grad", no_grad=True)
def _moe_experts_grad(ins, attrs):
    """The six grouped matmuls of the backward pass from the forward's
    saved Gate and Up: no projection runs twice (the generic vjp-style
    grad op would trace the forward again, and a custom call that is
    traced twice executes twice). A held share gathers Xs again and runs
    SwiGLU, its gradient and the sum of the rows' two gradients over the
    windows that hold a live row. Experts that are not gated units
    (attr ``gated`` false): ``_plain_experts_grad``, four."""
    if not attrs.get("gated", True):
        return _plain_experts_grad(ins, attrs)
    xs, rows = _x(ins, "Xs"), _x(ins, "Rows")
    wg, wu, wd = _x(ins, "WGate"), _x(ins, "WUp"), _x(ins, "WDown")
    m, dtype = xs.shape[0], xs.dtype
    gate = _x(ins, "Gate").astype(dtype)
    up = _x(ins, "Up").astype(dtype)
    g = _x(ins, "GRAD::Ys").astype(dtype)
    kw, w, carry = _held(attrs, "moe_experts_grad", m, dtype)
    glu = _gated_unit(attrs)
    steps = _adam_steps(ins, attrs)
    adam = functools.partial(_adam_of, steps)
    if w is not None:
        kw["zero_behind"] = False   # dh, dx_gate, dx_up: read by window
    _note_passes("moe_experts_grad", m, w, "gather_xs", "swiglu",
                 "swiglu_grad", "sum_dx")
    if w is None:
        h, swiglu_vjp = jax.vjp(glu, gate, up)
        dh, dwd = _gm.grouped_matmul_grads(h, wd.astype(dtype), rows, g,
                                           **adam("WDown"))
        dgate, dup = swiglu_vjp(dh)
    else:
        live = jnp.sum(rows)
        # gathered again (behind a barrier, or XLA merges this gather
        # with moe_dispatch's and keeps that one's result)
        x, order = jax.lax.optimization_barrier(
            (_x(ins, "X"), _x(ins, "Order")))
        xs = _gather_live(x.reshape(-1, x.shape[-1]).astype(dtype), order,
                          live, w, carry("Xs", xs.shape[1]))
        h = _glu_live(glu, gate, up, live, w, carry("h", gate.shape[1]))
        dh, dwd = _gm.grouped_matmul_grads(h, wd.astype(dtype), rows, g,
                                           **kw, **adam("WDown"))

        def swiglu_grad(r0):
            _, vjp = jax.vjp(glu, rows_at(gate, r0, w),
                             rows_at(up, r0, w))
            return vjp(rows_at(dh, r0, w))

        dgate, dup = _live_pass(
            live, w, [carry(name, gate.shape[1]) for name in ("dgate", "dup")],
            swiglu_grad)
    dx_gate, dwg = _gm.grouped_matmul_grads(xs, wg.astype(dtype), rows,
                                            dgate, **kw, **adam("WGate"))
    dx_up, dwu = _gm.grouped_matmul_grads(xs, wu.astype(dtype), rows, dup,
                                          **kw, **adam("WUp"))
    if w is None:
        dx = dx_gate + dx_up
    else:
        dx, = _live_pass(live, w, [carry("GRAD::Xs", xs.shape[1])],
                         lambda r0: (rows_at(dx_gate, r0, w)
                                     + rows_at(dx_up, r0, w),))
    return _experts_grad_outs(attrs, steps, dx, WGate=(dwg, wg.dtype),
                              WUp=(dwu, wu.dtype), WDown=(dwd, wd.dtype))


@register_op("moe_combine", diff_inputs=("Ys", "TopW"))
def _moe_combine(ins, attrs):
    """Ys [n*k, d] expert outputs in dispatch order, TopW [n, k], Order
    and Slot of moe_dispatch -> Out = sum_j TopW[t, j] * Ys[Slot[t, j]],
    in the shape of Like (the tokens as the router got them): summed
    in f32, returned in Ys's dtype. A held share (which also gets Rows,
    moe_dispatch's) adds each live row, times its pair's weight, into
    its token (``_add_into_tokens``) and reads no row of Ys behind the
    last live one. A layer that holds every expert gathers all k rows
    of every token: its grad op, jax's transposes of these lines,
    multiplies the same gathered rows again and XLA keeps them from here
    (with the ``pairs.sum.combine`` kernel in this place the backward
    gathers them itself: 2.3 ms more for 0.6 less at OLMoE's layer, my
    chip run, PR 41)."""
    ys, top_w = _x(ins, "Ys"), _x(ins, "TopW")
    order, slot = _x(ins, "Order"), _x(ins, "Slot")
    n, k = slot.shape
    w = _window(attrs, n * k)
    if w is not None:   # a held share: its own grad op
        out = _add_into_tokens(("moe_combine", "sum_pairs"), ys, slot,
                               _x(ins, "Rows"), w, top_w)
        return {"Out": [out.reshape(_x(ins, "Like").shape)]}
    _note_passes("moe_combine", n * k, None, "sum_pairs")
    picked = _rows(ys, slot.reshape(-1), order[:, None]).reshape(n, k, -1)
    out = jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                     top_w.astype(jnp.float32))
    return {"Out": [out.astype(ys.dtype).reshape(_x(ins, "Like").shape)]}


@register_op("moe_combine_grad", no_grad=True)
def _moe_combine_grad(ins, attrs):
    """A layer that holds every expert its router scores: jax's
    transposes of the forward above, as the generic grad op derives
    them. A held share (``held_count``, see moe_dispatch) writes the two
    cotangents out, one walk over its live rows for both (jax's
    transpose of a weighted sum by token keeps the gathered rows AND
    their products as float32 [n, k, d]: 1.6 GB at 81,920 rows of 2048
    compiled for a v5e): a window of the cotangent's rows is gathered by
    token once; GRAD::Ys is that times the pair's weight, and GRAD::TopW
    its row-wise product with Ys's window, summed in float32 and put at
    the pair's own place. GRAD::TopW is zero for a pair held elsewhere;
    GRAD::Ys is zeros to the end of the last live row's window and not
    defined behind it (``_carry``: nothing fills it)."""
    m = _x(ins, "Order").shape[0]
    w = _window(attrs, m)
    _note_passes("moe_combine_grad", m, w, "d_ys", "d_w")
    if w is None:
        from paddle_tpu.core import autodiff
        from paddle_tpu.core.registry import get_op_def

        return autodiff.make_grad_compute(get_op_def("moe_combine"))(
            ins, attrs)
    ys, top_w, order = _x(ins, "Ys"), _x(ins, "TopW"), _x(ins, "Order")
    n, k = top_w.shape
    g = _x(ins, "GRAD::Out").reshape(n, -1)
    pair_w = top_w.astype(jnp.float32).reshape(-1)

    def trip(r0, keep, carry):
        d_ys, d_w = carry
        pairs = rows_at(order, r0, w)
        g_rows = jnp.take(g, pairs // k, axis=0).astype(jnp.float32)
        d_ys = put_rows(d_ys, r0, jnp.where(
            keep, g_rows * jnp.take(pair_w, pairs)[:, None], 0.0))
        dots = jnp.sum(rows_at(ys, r0, w).astype(jnp.float32) * g_rows,
                       axis=-1)
        return d_ys, d_w.at[pairs].set(
            jnp.where(keep[:, 0], dots, 0.0), unique_indices=True,
            mode="promise_in_bounds")

    d_ys, d_w = over_live_rows(
        jnp.sum(_x(ins, "Rows")), w, trip,
        (_carry(attrs, "moe_combine_grad", w, m, ys.dtype, "GRAD::Ys",
                ys.shape[1]), jnp.zeros(n * k, jnp.float32)))
    return {"GRAD::Ys": [d_ys],
            "GRAD::TopW": [d_w.reshape(n, k).astype(top_w.dtype)]}
