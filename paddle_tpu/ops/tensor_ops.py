"""Tensor creation & manipulation ops.

Reference kernels: paddle/fluid/operators/{fill_constant_op.cc,
gaussian_random_op.cc, uniform_random_op.cc, reshape_op.cc, transpose_op.cc,
concat_op.cc, split_op.cc, slice_op.cc, stack_op.cc, squeeze_op.cc,
unsqueeze_op.cc, expand_op.cc, gather_op.cc, one_hot_op.cc,
lookup_table_op.cc, top_k_op.cc, arg_max_op.cc, assign_op.cc}.

RNG ops are stateless-keyed (Philox-style jax PRNG folded per-op and
per-step by the lowering), replacing the reference's stateful per-op seeds
(SURVEY.md section 7 hard part 6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import monitor as _monitor
from paddle_tpu.core import autodiff, interp
from paddle_tpu.core.registry import OpDef, register_op


def _x(ins, slot="X", i=0):
    return ins[slot][i]


@register_op("fill_constant", no_grad=True)
def _fill_constant(ins, attrs):
    shape = tuple(attrs.get("shape", []))
    dtype = attrs.get("dtype", "float32")
    value = attrs.get("value", 0.0)
    return {"Out": [jnp.full(shape, value, dtype=dtype)]}


@register_op("fill_zeros_like", no_grad=True)
def _fill_zeros_like(ins, attrs):
    return {"Out": [jnp.zeros_like(_x(ins))]}


@register_op("fill_any_like", no_grad=True)
def _fill_any_like(ins, attrs):
    return {"Out": [jnp.full_like(_x(ins), attrs.get("value", 0.0))]}


@register_op("gaussian_random", no_grad=True, needs_rng=True)
def _gaussian_random(ins, attrs, rng=None):
    shape = tuple(attrs["shape"])
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    dtype = attrs.get("dtype", "float32")
    return {"Out": [mean + std * jax.random.normal(rng, shape, dtype=dtype)]}


@register_op("uniform_random", no_grad=True, needs_rng=True)
def _uniform_random(ins, attrs, rng=None):
    shape = tuple(attrs["shape"])
    lo = attrs.get("min", -1.0)
    hi = attrs.get("max", 1.0)
    dtype = attrs.get("dtype", "float32")
    return {"Out": [jax.random.uniform(rng, shape, dtype=dtype, minval=lo, maxval=hi)]}


@register_op("truncated_gaussian_random", no_grad=True, needs_rng=True)
def _truncated_gaussian_random(ins, attrs, rng=None):
    shape = tuple(attrs["shape"])
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    dtype = attrs.get("dtype", "float32")
    x = jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype=dtype)
    return {"Out": [mean + std * x]}


@register_op("assign")
def _assign(ins, attrs):
    return {"Out": [_x(ins)]}


@register_op("recompute_barrier", no_grad=True)
def _recompute_barrier(ins, attrs):
    """Out[i] = X[i], all behind ONE ``optimization_barrier``: what a
    replayed segment reads (its checkpoint, the feeds) and the gradients
    that arrive at its end (backward.py). XLA merges no op across it and
    starts nothing that reads Out before every X is there."""
    return {"Out": list(jax.lax.optimization_barrier(tuple(ins["X"])))}


@register_op("assign_value", no_grad=True)
def _assign_value(ins, attrs):
    shape = tuple(attrs["shape"])
    dtype = attrs.get("dtype", "float32")
    vals = np.asarray(attrs["values"], dtype=np.float64)
    return {"Out": [jnp.asarray(vals.reshape(shape)).astype(dtype)]}


@register_op("shape", no_grad=True)
def _shape(ins, attrs):
    return {"Out": [jnp.asarray(jnp.shape(_x(ins)), dtype=jnp.int64)]}


@register_op("reshape2")
def _reshape2(ins, attrs):
    x = _x(ins)
    # Reference semantics: 0 copies the input dim, -1 infers (reshape_op.cc).
    shape = [
        jnp.shape(x)[i] if d == 0 else d for i, d in enumerate(attrs["shape"])
    ]
    return {"Out": [jnp.reshape(x, shape)], "XShape": []}


@register_op("transpose2")
def _transpose2(ins, attrs):
    return {"Out": [jnp.transpose(_x(ins), attrs["axis"])], "XShape": []}


@register_op("flatten2")
def _flatten2(ins, attrs):
    import math

    x = _x(ins)
    axis = attrs.get("axis", 1)
    s = jnp.shape(x)
    return {
        "Out": [jnp.reshape(x, (math.prod(s[:axis]) if axis else 1, -1))],
        "XShape": [],
    }


@register_op("concat")
def _concat(ins, attrs):
    return {"Out": [jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("split")
def _split(ins, attrs):
    x = _x(ins)
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if sections:
        idx = np.cumsum(sections[:-1]).tolist()
        parts = jnp.split(x, idx, axis=axis)
    elif interp.stands_for_dynamic(jnp.shape(x)[axis]):
        parts = [x] * num   # (a part of a dynamic dim is dynamic)
    else:
        parts = jnp.split(x, num, axis=axis)
    return {"Out": list(parts)}


@register_op("slice")
def _slice(ins, attrs):
    x = _x(ins)
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * jnp.ndim(x)
    for ax, st, en in zip(axes, starts, ends):
        idx[ax] = slice(st, en)
    return {"Out": [x[tuple(idx)]]}


@register_op("stack")
def _stack(ins, attrs):
    return {"Out": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def _unstack(ins, attrs):
    x = _x(ins)
    axis = attrs.get("axis", 0)
    num = jnp.shape(x)[axis]
    parts = [jnp.squeeze(p, axis=axis) for p in jnp.split(x, num, axis=axis)]
    return {"Y": parts}


@register_op("squeeze2")
def _squeeze2(ins, attrs):
    axes = tuple(attrs.get("axes", []))
    x = _x(ins)
    return {"Out": [jnp.squeeze(x, axis=axes or None)], "XShape": []}


@register_op("unsqueeze2")
def _unsqueeze2(ins, attrs):
    x = _x(ins)
    for ax in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, ax)
    return {"Out": [x], "XShape": []}


@register_op("expand")
def _expand(ins, attrs):
    x = _x(ins)
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register_op("expand_as")
def _expand_as(ins, attrs):
    x, y = _x(ins), _x(ins, "Y")
    return {"Out": [jnp.broadcast_to(x, jnp.shape(y))]}


@register_op("gather", diff_inputs=("X",))
def _gather(ins, attrs):
    x, index = _x(ins), _x(ins, "Index")
    axis = attrs.get("axis", 0)
    return {"Out": [jnp.take(x, index, axis=axis)]}


@register_op("scatter", diff_inputs=("X", "Updates"))
def _scatter(ins, attrs):
    x, ids, updates = _x(ins), _x(ins, "Ids"), _x(ins, "Updates")
    if attrs.get("overwrite", True):
        return {"Out": [x.at[ids].set(updates)]}
    return {"Out": [x.at[ids].add(updates)]}


@register_op("one_hot", no_grad=True)
def _one_hot(ins, attrs):
    x = _x(ins)
    depth = attrs["depth"]
    if jnp.ndim(x) > 1 and jnp.shape(x)[-1] == 1:
        x = jnp.squeeze(x, axis=-1)
    return {"Out": [jax.nn.one_hot(x, depth, dtype=attrs.get("dtype", "float32"))]}


def _lookup_table_grad_maker(op, block, out_grads, provide, should_skip):
    """Emit the row-sparse grad pair when the layer asked for
    ``is_sparse=True`` (the SelectedRows capability, reference:
    lookup_table_op.cc grad -> SelectedRows), the dense table's own grad
    op ``lookup_table_grad`` (W for its shape, Ids, GRAD::Out -> GRAD::W)
    otherwise; a row-sharded lookup (``is_distributed``) defers to the
    generic auto-vjp grad emitter (return None). The sparse pair is two IR
    vars named ``{W}@GRAD@ROWS`` / ``{W}@GRAD@VALUES``; the ``{W}@GRAD``
    variable itself becomes a never-materialized marker carrying
    ``is_selected_rows`` so the optimizer dispatches to its sparse op."""
    from paddle_tpu.core.registry import get_op_def

    sparse = op.attrs.get("is_sparse", False)
    if op.attrs.get("is_distributed", False) and not sparse:
        return None  # generic dense path
    w = op.inputs["W"][0]
    g_out = (out_grads.get("Out") or [""])[0]
    if not g_out:
        return []
    opdef = get_op_def("lookup_table")
    if should_skip(w, "W", opdef):
        return []
    src = block._find_var_recursive(w)
    gname = provide(w)
    if not sparse:
        block.create_var(name=gname, shape=src.shape if src else None,
                         dtype=src.dtype if src else "float32")
        return [dict(
            type="lookup_table_grad",
            inputs={"W": [w], "Ids": list(op.inputs["Ids"]),
                    "GRAD::Out": [g_out]},
            outputs={"GRAD::W": [gname]},
            attrs=dict(op.attrs),
        )]
    if "@RENAME@" in gname:
        raise ValueError(
            f"lookup_table(is_sparse=True): table '{w}' is consumed by "
            f"multiple lookups in the backward path; the row-sparse "
            f"gradient pair cannot be summed. Use is_sparse=False for "
            f"shared tables."
        )
    gv = block.create_var(name=gname, shape=src.shape if src else None,
                          dtype=src.dtype if src else "float32")
    rows_name, values_name = gname + "@ROWS", gname + "@VALUES"
    block.create_var(name=rows_name, dtype="int32")
    block.create_var(name=values_name,
                     dtype=src.dtype if src else "float32")
    gv.is_selected_rows = True
    gv.sparse_rows_name = rows_name
    gv.sparse_values_name = values_name
    attrs = {"vocab_size": int(src.shape[0])}
    # mirror the forward's squeeze behavior exactly (dynamic default when
    # the layer didn't pin it)
    if "squeeze_last" in op.attrs:
        attrs["squeeze_last"] = op.attrs["squeeze_last"]
    if "padding_idx" in op.attrs:
        attrs["padding_idx"] = op.attrs["padding_idx"]
    return [dict(
        type="lookup_table_sparse_grad",
        inputs={"Ids": list(op.inputs["Ids"]), "GRAD::Out": [g_out]},
        outputs={"Rows": [rows_name], "Values": [values_name]},
        attrs=attrs,
    )]


@register_op("lookup_table", diff_inputs=("W",),
             grad_maker=_lookup_table_grad_maker,
             doc="embedding lookup; grad is the dense [vocab, d] sum of the "
                 "cotangent's rows by id (lookup_table_grad: the embed.grad "
                 "kernel over the sorted rows where embed_grad_tile gives "
                 "the call a tile, XLA's scatter-add elsewhere), or a "
                 "row-sparse {rows, values} pair under is_sparse=True "
                 "(the reference's SelectedRows, lookup_table_op.cc)")
def _lookup_table(ins, attrs):
    w, ids = _x(ins, "W"), _x(ins, "Ids")
    ids, padding_idx = _lookup_ids(w, ids, attrs)
    out = None
    if attrs.get("is_distributed", False):
        # Row-sharded table (replaces the reference's pserver-distributed
        # lookup table + RPC prefetch, parameter_prefetch.cc): each shard
        # gathers its local rows, psum over ICI combines. Only active when
        # the program runs under a strategy declaring a table axis.
        from paddle_tpu.core.interp import spmd_ctx

        ctx = spmd_ctx()
        if ctx is not None:
            mesh, table_axis, data_axis = ctx.mesh, ctx.table_axis, ctx.data_axis
            if table_axis is not None and (
                jnp.shape(w)[0] % mesh.shape[table_axis] == 0
            ):
                from paddle_tpu.parallel.embedding import (
                    sharded_embedding_lookup,
                )

                out = sharded_embedding_lookup(
                    w, ids, mesh, shard_axis=table_axis,
                    data_axis=data_axis,
                )
    if out is None:
        out = jnp.take(w, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return {"Out": [out]}


def _lookup_ids(w, ids, attrs):
    """(ids as the lookup reads them, the padding row or None)."""
    # [N, 1] column-ids convention: squeeze unless the layer says the ids
    # are already a padded [b, t] batch (a [b, 1] batch is ambiguous).
    squeeze_last = attrs.get(
        "squeeze_last", jnp.ndim(ids) > 1 and jnp.shape(ids)[-1] == 1
    )
    if squeeze_last:
        ids = jnp.squeeze(ids, axis=-1)
    # Reference semantics: kNoPadding when absent; negative = vocab + idx
    # (lookup_table_op.cc). The layer omits the attr when padding is off.
    padding_idx = attrs.get("padding_idx", None)
    if padding_idx is not None and padding_idx < 0:
        padding_idx = jnp.shape(w)[0] + padding_idx
    return ids, padding_idx


_M_EMBED_GRAD = _monitor.counter(
    "pt_embedding_grad_dispatch_total",
    "dense embedding gradient implementation chosen at trace time, one "
    "row a lowered lookup_table_grad: impl kernel (embed.grad, "
    "parallel/embed_grad.py) or xla (the scatter-add); "
    "parallel/embed_grad.embed_grad_tile's answer for the call")

# (the rule of the XLA form, and of the generic emitter's op: the vjp of
# _lookup_table, a scatter-add into zeros)
_LOOKUP_TABLE_XLA_GRAD = autodiff.make_grad_compute(OpDef(
    type="lookup_table", compute=_lookup_table, diff_inputs=("W",)))


@register_op("lookup_table_grad", no_grad=True)
def _lookup_table_grad(ins, attrs):
    """GRAD::W [vocab, d] of a dense lookup_table, in W's dtype: the sum
    of GRAD::Out's rows by id, float32. Padding rows, negative ids and
    ids outside the table add what the forward's vjp adds (nothing, the
    row vocab + id, nothing). ONE kernel, ``parallel/embed_grad``, at the
    tile ``embed_grad_tile`` gives the call from its shapes, the
    cotangent's dtype, the backend and the mesh; where it gives none,
    and for a row-sharded table (the generic emitter's op), the vjp of
    the forward: XLA's scatter-add."""
    from paddle_tpu.parallel import embed_grad

    w, g = _x(ins, "W"), _x(ins, "GRAD::Out")
    vocab, d = jnp.shape(w)
    tile = None
    if not attrs.get("is_distributed", False) and jnp.ndim(g) >= 2:
        tile = embed_grad.embed_grad_tile(g.size // d, vocab, d, g.dtype)
    # off with telemetry; build-time shape inference is not a lowering
    if _monitor.enabled() and interp.lowering_active():
        _M_EMBED_GRAD.inc(labels={"impl": "kernel" if tile else "xla"})
    if tile is None:
        return _LOOKUP_TABLE_XLA_GRAD(ins, {
            "fwd_input_slots": ["W", "Ids"], "fwd_output_slots": ["Out"],
            **attrs})
    ids, padding_idx = _lookup_ids(w, _x(ins, "Ids"), attrs)
    keys = jnp.where(ids < 0, ids + vocab, ids).reshape(-1)
    if padding_idx is not None:   # the forward's mask, on the ids as fed
        keys = jnp.where(ids.reshape(-1) == padding_idx, -1, keys)
    dw = embed_grad.embed_grad(g.reshape(-1, d), keys, vocab, tile)
    return {"GRAD::W": [dw.astype(w.dtype)]}


@register_op("top_k", no_grad=True)
def _top_k(ins, attrs):
    """``lax.top_k`` over the last axis of the whole tensor. On a TPU
    that is a sort of every row, whatever ``k``: fine for a router's few
    of a hundred, not for thousands of positions a query, where
    ``ops/dsa_ops.choose`` (``layers.dsa_select``) finds the k-th value
    by bisection and never sorts."""
    x = _x(ins)
    k = attrs["k"]
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


@register_op("arg_max", no_grad=True)
def _arg_max(ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jnp.argmax(_x(ins), axis=axis).astype(jnp.int64)]}


@register_op("arg_min", no_grad=True)
def _arg_min(ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jnp.argmin(_x(ins), axis=axis).astype(jnp.int64)]}


@register_op("range", no_grad=True)
def _range(ins, attrs):
    start = attrs.get("start", 0)
    end = attrs["end"]
    step = attrs.get("step", 1)
    dtype = attrs.get("dtype", "int64")
    return {"Out": [jnp.arange(start, end, step, dtype=dtype)]}


@register_op("where", diff_inputs=("X", "Y"))
def _where(ins, attrs):
    cond, x, y = _x(ins, "Condition"), _x(ins), _x(ins, "Y")
    return {"Out": [jnp.where(cond, x, y)]}


@register_op("cumsum")
def _cumsum(ins, attrs):
    x = _x(ins)
    axis = attrs.get("axis", -1)
    if attrs.get("reverse", False):
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", False):
        out = out - x
    if attrs.get("reverse", False):
        out = jnp.flip(out, axis)
    return {"Out": [out]}


@register_op("pad")
def _pad(ins, attrs):
    x = _x(ins)
    paddings = attrs["paddings"]  # [before0, after0, before1, after1, ...]
    value = attrs.get("pad_value", 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(jnp.ndim(x))]
    return {"Out": [jnp.pad(x, cfg, constant_values=value)]}


@register_op("tile")
def _tile(ins, attrs):
    return {"Out": [jnp.tile(_x(ins), attrs["repeat_times"])]}


@register_op("dynamic_update", diff_inputs=("X", "Value"))
def _dynamic_update(ins, attrs):
    """Write Value at dynamic position Index along axis 0 of X.

    Static-shape stand-in for the reference's LoDTensorArray write
    (reference: operators/controlflow/tensor_array_read_write_op.cc):
    the "array" is a preallocated [maxlen, ...] dense tensor.
    """
    import jax.lax as lax

    x = _x(ins)
    idx = jnp.reshape(ins["Index"][0], ()).astype(jnp.int32)
    v = ins["Value"][0]
    v = jnp.expand_dims(v, 0).astype(x.dtype)
    zero = jnp.zeros((), jnp.int32)
    starts = (idx,) + (zero,) * (x.ndim - 1)
    return {"Out": [lax.dynamic_update_slice(x, v, starts)]}


@register_op("dynamic_slice", diff_inputs=("X",))
def _dynamic_slice(ins, attrs):
    """Read the [Index] slice along axis 0 of X (LoDTensorArray read)."""
    import jax.lax as lax

    x = _x(ins)
    idx = jnp.reshape(ins["Index"][0], ()).astype(jnp.int32)
    sizes = (1,) + tuple(x.shape[1:])
    zero = jnp.zeros((), jnp.int32)
    starts = (idx,) + (zero,) * (x.ndim - 1)
    out = lax.dynamic_slice(x, starts, sizes)
    return {"Out": [jnp.squeeze(out, 0)]}


# --- remaining reference tensor/array ops ---


@register_op("reverse", diff_inputs=("X",))
def _reverse(ins, attrs):
    return {"Out": [jnp.flip(_x(ins), axis=tuple(attrs.get("axis", [0])))]}


@register_op("argsort", no_grad=True)
def _argsort(ins, attrs):
    x = _x(ins)
    axis = attrs.get("axis", -1)
    descending = attrs.get("descending", False)
    idx = jnp.argsort(-x if descending else x, axis=axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {"Out": [out], "Indices": [idx.astype(jnp.int64)]}


@register_op("diag", no_grad=True)
def _diag(ins, attrs):
    return {"Out": [jnp.diag(_x(ins, "Diagonal"))]}


@register_op("linspace", no_grad=True)
def _linspace(ins, attrs):
    start = jnp.reshape(_x(ins, "Start"), ())
    stop = jnp.reshape(_x(ins, "Stop"), ())
    num = int(attrs["num"])
    dtype = attrs.get("dtype", "float32")
    return {"Out": [jnp.linspace(start, stop, num, dtype=dtype)]}


@register_op("gather_nd", diff_inputs=("X",))
def _gather_nd(ins, attrs):
    x, index = _x(ins), _x(ins, "Index")
    idx = tuple(jnp.moveaxis(index, -1, 0))
    return {"Out": [x[idx]]}


@register_op("scatter_nd_add", diff_inputs=("X", "Updates"))
def _scatter_nd_add(ins, attrs):
    x = _x(ins)
    index = _x(ins, "Index")
    updates = _x(ins, "Updates")
    idx = tuple(jnp.moveaxis(index, -1, 0))
    return {"Out": [x.at[idx].add(updates)]}


@register_op("pad2d", diff_inputs=("X",))
def _pad2d(ins, attrs):
    """NCHW spatial padding with constant/reflect/edge modes
    (reference: pad2d_op.cc)."""
    x = _x(ins)
    t, b, l, r = attrs.get("paddings", [0, 0, 0, 0])
    mode = {"constant": "constant", "reflect": "reflect",
            "edge": "edge"}[attrs.get("mode", "constant")]
    kw = {}
    if mode == "constant":
        kw["constant_values"] = attrs.get("pad_value", 0.0)
    out = jnp.pad(x, ((0, 0), (0, 0), (t, b), (l, r)), mode=mode, **kw)
    return {"Out": [out]}


@register_op("pad_constant_like", diff_inputs=("Y",))
def _pad_constant_like(ins, attrs):
    """Pad Y up to X's shape with pad_value
    (reference: pad_constant_like_op.cc)."""
    x, y = _x(ins), _x(ins, "Y")
    pads = [(0, int(a) - int(b)) for a, b in zip(jnp.shape(x), jnp.shape(y))]
    return {"Out": [jnp.pad(y, pads,
                            constant_values=attrs.get("pad_value", 0.0))]}


@register_op("crop", diff_inputs=("X",))
def _crop(ins, attrs):
    """Crop a static-offset window (reference: crop_op.cc)."""
    x = _x(ins)
    offsets = attrs.get("offsets", [0] * jnp.ndim(x))
    shape = attrs["shape"]
    sl = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return {"Out": [x[sl]]}


@register_op("shuffle_channel", diff_inputs=("X",))
def _shuffle_channel(ins, attrs):
    """Channel shuffle for group convs (reference: shuffle_channel_op.cc)."""
    x = _x(ins)
    g = int(attrs.get("group", 1))
    n, c, h, w = jnp.shape(x)
    out = jnp.reshape(
        jnp.swapaxes(jnp.reshape(x, (n, g, c // g, h, w)), 1, 2), (n, c, h, w)
    )
    return {"Out": [out]}


@register_op("pixel_shuffle", diff_inputs=("X",))
def _pixel_shuffle(ins, attrs):
    """Depth-to-space upscaling (reference: pixel_shuffle_op.cc)."""
    x = _x(ins)
    r = int(attrs.get("upscale_factor", 1))
    n, c, h, w = jnp.shape(x)
    out = jnp.reshape(x, (n, c // (r * r), r, r, h, w))
    out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
    return {"Out": [jnp.reshape(out, (n, c // (r * r), h * r, w * r))]}


@register_op("space_to_depth", diff_inputs=("X",))
def _space_to_depth(ins, attrs):
    """Inverse of pixel shuffle (reference: space_to_depth_op.cc)."""
    x = _x(ins)
    r = int(attrs.get("blocksize", 1))
    n, c, h, w = jnp.shape(x)
    out = jnp.reshape(x, (n, c, h // r, r, w // r, r))
    out = jnp.transpose(out, (0, 3, 5, 1, 2, 4))
    return {"Out": [jnp.reshape(out, (n, c * r * r, h // r, w // r))]}


@register_op("multiplex", diff_inputs=("X",))
def _multiplex(ins, attrs):
    """Row-wise select among candidate tensors by index
    (reference: multiplex_op.cc)."""
    xs = jnp.stack(ins["X"], axis=0)        # [K, B, ...]
    ids = _x(ins, "Ids")
    if jnp.ndim(ids) > 1:
        ids = jnp.squeeze(ids, -1)
    b = jnp.shape(xs)[1]
    return {"Out": [xs[ids.astype(jnp.int32), jnp.arange(b)]]}


@register_op("sampling_id", no_grad=True, needs_rng=True)
def _sampling_id(ins, attrs, rng=None):
    """Sample a column index per row from probability rows
    (reference: sampling_id_op.cc)."""
    x = _x(ins)
    ids = jax.random.categorical(rng, jnp.log(jnp.maximum(x, 1e-30)), axis=-1)
    return {"Out": [ids.astype(jnp.int64)]}


@register_op("shard_index", no_grad=True)
def _shard_index(ins, attrs):
    """Map global ids to shard-local ids (reference: shard_index_op.cc)."""
    x = _x(ins)
    index_num = attrs["index_num"]
    nshards = attrs["nshards"]
    shard_id = attrs["shard_id"]
    ignore = attrs.get("ignore_value", -1)
    per = (index_num + nshards - 1) // nshards
    in_shard = (x // per) == shard_id
    return {"Out": [jnp.where(in_shard, x % per, ignore)]}


@register_op("iou_similarity", no_grad=True)
def _iou_similarity(ins, attrs):
    """Pairwise IoU of two box sets [N,4] x [M,4] (xmin,ymin,xmax,ymax)
    (reference: operators/detection/iou_similarity_op.cc)."""
    from paddle_tpu.ops.box_util import iou_xyxy

    x = _x(ins)         # [N, 4]
    y = _x(ins, "Y")    # [M, 4]
    return {"Out": [iou_xyxy(x, y)]}


@register_op("box_coder", no_grad=True)
def _box_coder(ins, attrs):
    """Encode/decode boxes against priors (reference:
    operators/detection/box_coder_op.cc). PriorBox [M,4], TargetBox
    encode:[N,4] / decode:[N,M,4]."""
    prior = _x(ins, "PriorBox")
    target = _x(ins, "TargetBox")
    code_type = attrs.get("code_type", "encode_center_size")
    norm = attrs.get("box_normalized", True)
    one = 0.0 if norm else 1.0
    # variances scale the encoded offsets (box_coder_op.h): per-prior
    # tensor input, or a 4-vector attr, or none (all ones)
    pvar = ins.get("PriorBoxVar", [None])
    pvar = pvar[0] if pvar else None
    if pvar is None:
        va = attrs.get("variance", [])
        pvar = jnp.asarray(va if va else [1.0, 1.0, 1.0, 1.0])
        pvar = jnp.broadcast_to(pvar, (jnp.shape(prior)[0], 4))
    pw = prior[:, 2] - prior[:, 0] + one
    ph = prior[:, 3] - prior[:, 1] + one
    px = prior[:, 0] + pw * 0.5
    py = prior[:, 1] + ph * 0.5
    if code_type.startswith("encode"):
        tw = target[:, 2] - target[:, 0] + one
        th = target[:, 3] - target[:, 1] + one
        tx = target[:, 0] + tw * 0.5
        ty = target[:, 1] + th * 0.5
        ox = (tx[:, None] - px[None, :]) / pw[None, :] / pvar[None, :, 0]
        oy = (ty[:, None] - py[None, :]) / ph[None, :] / pvar[None, :, 1]
        ow = jnp.log(tw[:, None] / pw[None, :]) / pvar[None, :, 2]
        oh = jnp.log(th[:, None] / ph[None, :]) / pvar[None, :, 3]
        out = jnp.stack([ox, oy, ow, oh], axis=-1)     # [N, M, 4]
    else:
        tx = target[..., 0] * pvar[None, :, 0] * pw[None, :] + px[None, :]
        ty = target[..., 1] * pvar[None, :, 1] * ph[None, :] + py[None, :]
        tw = jnp.exp(target[..., 2] * pvar[None, :, 2]) * pw[None, :]
        th = jnp.exp(target[..., 3] * pvar[None, :, 3]) * ph[None, :]
        out = jnp.stack(
            [tx - tw * 0.5, ty - th * 0.5,
             tx + tw * 0.5 - one, ty + th * 0.5 - one], axis=-1)
    return {"OutputBox": [out]}


@register_op("flatten")
def _flatten(ins, attrs):
    # same semantics as flatten2 minus the XShape output
    return {"Out": _flatten2(ins, attrs)["Out"]}


@register_op("prior_box", no_grad=True)
def _prior_box(ins, attrs):
    """SSD prior boxes per feature-map cell (reference:
    operators/detection/prior_box_op.cc). Input [N,C,H,W] feature map,
    Image [N,C,Hi,Wi]. Outputs Boxes/Variances [H, W, P, 4]."""
    feat = _x(ins, "Input")
    img = _x(ins, "Image")
    h, w = jnp.shape(feat)[2], jnp.shape(feat)[3]
    ih, iw = jnp.shape(img)[2], jnp.shape(img)[3]
    min_sizes = list(attrs.get("min_sizes", [100.0]))
    max_sizes = list(attrs.get("max_sizes", []))
    ars = [1.0]
    for ar in attrs.get("aspect_ratios", []):
        if not any(abs(ar - a) < 1e-6 for a in ars):
            ars.append(ar)
            if attrs.get("flip", True):
                ars.append(1.0 / ar)
    variances = attrs.get("variances", [0.1, 0.1, 0.2, 0.2])
    step_w = attrs.get("step_w", 0.0) or float(iw) / w
    step_h = attrs.get("step_h", 0.0) or float(ih) / h
    offset = attrs.get("offset", 0.5)

    whs = []
    for i, ms in enumerate(min_sizes):
        for ar in ars:
            whs.append((ms * (ar ** 0.5), ms / (ar ** 0.5)))
        # max_sizes pair index-wise with min_sizes (prior_box_op.h):
        # one extra sqrt(min*max) square prior per min size
        if i < len(max_sizes):
            s = (ms * max_sizes[i]) ** 0.5
            whs.append((s, s))
    p = len(whs)
    cw = jnp.asarray([a for a, _ in whs]) / iw    # [P]
    ch = jnp.asarray([b for _, b in whs]) / ih
    cx = (jnp.arange(w) + offset) * step_w / iw   # [W]
    cy = (jnp.arange(h) + offset) * step_h / ih   # [H]
    cxg = jnp.broadcast_to(cx[None, :, None], (h, w, p))
    cyg = jnp.broadcast_to(cy[:, None, None], (h, w, p))
    boxes = jnp.stack([
        cxg - cw / 2, cyg - ch / 2, cxg + cw / 2, cyg + ch / 2
    ], axis=-1)
    if attrs.get("clip", True):
        boxes = jnp.clip(boxes, 0.0, 1.0)
    var = jnp.broadcast_to(jnp.asarray(variances), (h, w, p, 4))
    return {"Boxes": [boxes], "Variances": [var]}


@register_op("anchor_generator", no_grad=True)
def _anchor_generator(ins, attrs):
    """RPN anchors per cell (reference:
    operators/detection/anchor_generator_op.cc). Outputs
    Anchors/Variances [H, W, A, 4] in input-image pixels."""
    feat = _x(ins, "Input")
    h, w = jnp.shape(feat)[2], jnp.shape(feat)[3]
    sizes = attrs.get("anchor_sizes", [64.0, 128.0, 256.0, 512.0])
    ratios = attrs.get("aspect_ratios", [0.5, 1.0, 2.0])
    variances = attrs.get("variances", [0.1, 0.1, 0.2, 0.2])
    stride = attrs.get("stride", [16.0, 16.0])
    offset = attrs.get("offset", 0.5)
    whs = []
    for r in ratios:
        for s in sizes:
            area = s * s
            aw = (area / r) ** 0.5
            whs.append((aw, aw * r))
    a = len(whs)
    aw = jnp.asarray([x for x, _ in whs])
    ah = jnp.asarray([y for _, y in whs])
    cx = (jnp.arange(w) + offset) * stride[0]
    cy = (jnp.arange(h) + offset) * stride[1]
    cxg = jnp.broadcast_to(cx[None, :, None], (h, w, a))
    cyg = jnp.broadcast_to(cy[:, None, None], (h, w, a))
    anchors = jnp.stack([
        cxg - aw / 2, cyg - ah / 2, cxg + aw / 2, cyg + ah / 2
    ], axis=-1)
    var = jnp.broadcast_to(jnp.asarray(variances), (h, w, a, 4))
    return {"Anchors": [anchors], "Variances": [var]}


# --- v1-named aliases of the *2 ops (reference registers both; the v1
# forms lack the XShape side output) ---


@register_op("reshape", diff_inputs=("X",))
def _reshape_v1(ins, attrs):
    x = _x(ins)
    shape = [int(s) for s in attrs["shape"]]
    out_shape = []
    for i, s in enumerate(shape):
        if s == 0:
            out_shape.append(x.shape[i])
        else:
            out_shape.append(s)
    return {"Out": [jnp.reshape(x, out_shape)]}


@register_op("transpose", diff_inputs=("X",))
def _transpose_v1(ins, attrs):
    return {"Out": [jnp.transpose(_x(ins), attrs["axis"])]}


@register_op("squeeze", diff_inputs=("X",))
def _squeeze_v1(ins, attrs):
    x = _x(ins)
    axes = attrs.get("axes", [])
    if not axes:
        return {"Out": [jnp.squeeze(x)]}
    return {"Out": [jnp.squeeze(x, axis=tuple(axes))]}


@register_op("unsqueeze", diff_inputs=("X",))
def _unsqueeze_v1(ins, attrs):
    x = _x(ins)
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return {"Out": [x]}
