"""Neural-network ops: conv, pool, normalization, dropout, losses, metrics.

Reference kernels: paddle/fluid/operators/{conv_op.cc, pool_op.cc,
batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc, softmax_op.cc,
cross_entropy_op.cc, softmax_with_cross_entropy_op.cc,
metrics/accuracy_op.cc}. Convs map straight onto the MXU through
``lax.conv_general_dilated``; XLA picks TPU-friendly layouts regardless of
the NCHW API convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core import interp
from paddle_tpu.core.autodiff import GRAD_SLOT_PREFIX as GRAD_SLOT
from paddle_tpu.core.registry import register_op
from paddle_tpu.parallel.grouped_matmul import (over_live_rows, put_rows,
                                                rows_at)

# Runs at TRACE time (once per compile, like pt_attention_dispatch_total):
# how each lowered draw of random words was laid over the program's mesh.
_M_RNG_DRAW = _monitor.counter(
    "pt_rng_draw_total",
    "random-word draws lowered, by op, sharded_over (the data axes the "
    "draw was split over, each shard drawing its own rows; empty on one "
    "device) and replicated_over (mesh axes of size > 1 whose every rank "
    "repeats the same draw)")


def rng_draw_counts():
    """{"op[ sharded_over=axes][ replicated_over=axes]": draws lowered
    so far} — pt_rng_draw_total as attention_ops.dispatch_counts() gives
    the dispatch counter."""
    out = {}
    for row in _monitor.snapshot()[_M_RNG_DRAW.name]["values"]:
        lb = row["labels"]
        name = lb.get("op", "?")
        for k in ("sharded_over", "replicated_over"):
            if lb.get(k):
                name += f" {k}={lb[k]}"
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def _x(ins, slot="X", i=0):
    v = ins.get(slot)
    return v[i] if v else None


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


@register_op("conv2d", diff_inputs=("Input", "Filter"))
def _conv2d(ins, attrs):
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    # Emit the conv in NHWC logical order: the API is NCHW (reference
    # conv_op.cc convention) but XLA's TPU conv emitter tiles NHWC-labelled
    # convs measurably better (ResNet-50 train: +3.5% step time with
    # identical physical layouts — the transposes below fold into layout
    # assignment and emit no copies).
    out = jax.lax.conv_general_dilated(
        jnp.transpose(x, (0, 2, 3, 1)),
        jnp.transpose(w, (2, 3, 1, 0)),
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    return {"Output": [jnp.transpose(out, (0, 3, 1, 2))]}


@register_op("depthwise_conv2d", diff_inputs=("Input", "Filter"))
def _depthwise_conv2d(ins, attrs):
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", jnp.shape(x)[1])
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register_op("conv2d_transpose", diff_inputs=("Input", "Filter"))
def _conv2d_transpose(ins, attrs):
    """Gradient-of-conv semantics (reference conv_transpose_op.cc): filter is
    [C_in, C_out/groups, kh, kw]; out H = (H-1)*s - 2p + d*(k-1) + 1.
    Expressed as a fractionally-strided forward conv (lhs_dilation) so XLA
    lowers it onto the MXU like any conv."""
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    sh, sw = _pair(attrs.get("strides", [1, 1]))
    ph, pw = _pair(attrs.get("paddings", [0, 0]))
    dh, dw = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    kh, kw = jnp.shape(w)[2], jnp.shape(w)[3]
    # [C_in, C_out/g, kh, kw] -> flip spatial, swap io -> [C_out, C_in/g, ...]
    if groups > 1:
        ci = jnp.shape(w)[0]
        wg = jnp.reshape(w, (groups, ci // groups) + tuple(jnp.shape(w)[1:]))
        wg = jnp.flip(wg, axis=(-2, -1))
        wg = jnp.swapaxes(wg, 1, 2)  # [g, C_out/g, C_in/g, kh, kw]
        w_eff = jnp.reshape(wg, (-1, ci // groups, kh, kw))
    else:
        w_eff = jnp.swapaxes(jnp.flip(w, axis=(-2, -1)), 0, 1)
    pad_h = dh * (kh - 1) - ph
    pad_w = dw * (kw - 1) - pw
    out = jax.lax.conv_general_dilated(
        x,
        w_eff,
        window_strides=(1, 1),
        padding=[(pad_h, pad_h), (pad_w, pad_w)],
        lhs_dilation=(sh, sw),
        rhs_dilation=(dh, dw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register_op("pool2d")
def _pool2d(ins, attrs):
    x = _x(ins)
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [2, 2]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    if attrs.get("global_pooling", False):
        ksize = (jnp.shape(x)[2], jnp.shape(x)[3])
        strides = ksize
        pads = (0, 0)
    window = (1, 1) + ksize
    wstrides = (1, 1) + strides
    padding = ((0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1]))
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, wstrides, padding)
    else:
        summed = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, window, wstrides, padding
        )
        if attrs.get("exclusive", True) and pads != (0, 0):
            ones = jnp.ones_like(x)
            count = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, window, wstrides, padding
            )
            out = summed / count
        else:
            out = summed / (ksize[0] * ksize[1])
    return {"Out": [out]}


@register_op(
    "batch_norm",
    diff_inputs=("X", "Scale", "Bias"),
    inplace={"MeanOut": "Mean", "VarianceOut": "Variance"},
)
def _batch_norm(ins, attrs):
    x = _x(ins)
    scale, bias = _x(ins, "Scale"), _x(ins, "Bias")
    mean, var = _x(ins, "Mean"), _x(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(jnp.ndim(x)) if i != (1 if layout == "NCHW" else jnp.ndim(x) - 1))
    c_axis = 1 if layout == "NCHW" else jnp.ndim(x) - 1
    shape = [1] * jnp.ndim(x)
    shape[c_axis] = jnp.shape(x)[c_axis]

    # Stats and normalization math in f32; Y comes back in x's dtype, so
    # a bf16 AMP stream stays bf16 — promoting the whole activation to
    # f32 materialized a full-precision copy of the widest tensors
    # (measured ~1.5 ms/step per early ResNet-50 stage at b=128).
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    if is_test:
        use_mean, use_var = mean, var
        new_mean, new_var = mean, var
        saved_mean = mean
        saved_var = var
    else:
        # One-pass stats: E[x] and E[x^2] reduce in the same traversal (a
        # single multi-output reduction XLA fuses into the producing conv's
        # epilogue), where mean-then-var is two passes over a tensor that
        # is usually the widest in the model. Cancellation in E[x^2]-E[x]^2
        # is benign here: stats are f32 and NN activations keep
        # std/|mean| far from the f32 cliff. Measured on ResNet-50 b=128
        # (1x v5e): 0.292 -> 0.321 MFU together with the affine rewrite
        # below.
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=axes) - jnp.square(use_mean), 0.0
        )
        new_mean = momentum * mean + (1 - momentum) * use_mean
        new_var = momentum * var + (1 - momentum) * use_var
        saved_mean = use_mean
        saved_var = use_var

    # Affine form y = k*x + c with per-channel k, c: one fused
    # multiply-add over the wide tensor, and its vjp re-derives x-hat
    # without re-centering passes. The affine itself runs in x's dtype
    # (k, c are [C]-sized and cast once): under bf16 AMP an f32 affine
    # whose output has MULTIPLE consumers (SE blocks: pool AND the gate
    # multiply read the same BN output) makes XLA materialize the f32
    # tensor instead of recompute-fusing it into each consumer —
    # measured 817 us/step per stage-0 SE-ResNeXt block of pure f32
    # copy traffic, ~8 ms/step total (round 5; ResNet-50 was immune
    # because every BN output there has a single consumer chain).
    inv = jax.lax.rsqrt(use_var + eps)
    k = inv if scale is None else inv * scale
    c = -use_mean * k
    if bias is not None:
        c = c + bias
    y = x * k.astype(x.dtype).reshape(shape) + c.astype(x.dtype).reshape(shape)
    return {
        "Y": [y],
        "MeanOut": [jax.lax.stop_gradient(new_mean)],
        "VarianceOut": [jax.lax.stop_gradient(new_var)],
        "SavedMean": [jax.lax.stop_gradient(saved_mean)],
        "SavedVariance": [jax.lax.stop_gradient(saved_var)],
    }


@register_op("layer_norm", diff_inputs=("X", "Scale", "Bias"))
def _layer_norm(ins, attrs):
    x = _x(ins)
    scale, bias = _x(ins, "Scale"), _x(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, jnp.ndim(x)))
    # All internal math in f32 regardless of the activation dtype (bf16
    # under AMP): stats are precision-sensitive, and doing the affine in
    # f32 keeps the scale/bias gradient reductions in f32 through the vjp.
    # Only the final result returns to x's dtype, so the HBM stream stays
    # bf16 and the f32 intermediates live inside the XLA fusion.
    stat_dtype = jnp.promote_types(x.dtype, jnp.float32)  # f32 unless f64
    xf = x.astype(stat_dtype)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = (xf - mean) * inv
    feat_shape = jnp.shape(x)[begin:]
    if scale is not None:
        y = y * jnp.reshape(scale, (1,) * begin + feat_shape).astype(stat_dtype)
    if bias is not None:
        y = y + jnp.reshape(bias, (1,) * begin + feat_shape).astype(stat_dtype)
    y = y.astype(x.dtype)
    return {
        "Y": [y],
        "Mean": [jax.lax.stop_gradient(jnp.reshape(mean, (-1,)))],
        "Variance": [jax.lax.stop_gradient(jnp.reshape(var, (-1,)))],
    }


@register_op("rms_norm", diff_inputs=("X", "Scale"))
def _rms_norm(ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale (Zhang &
    Sennrich 2019): no mean, no bias. As layer_norm above, the mean of
    squares and the gain run in f32 whatever the activation dtype (bf16
    under AMP), so the gain's gradient reduction is f32 too; only Y
    returns to X's dtype. ``zero_centered``: the gain is 1 + Scale
    (Qwen3-Next: the parameter starts at 0 and weight decay pulls the
    gain to 1)."""
    x, scale = _x(ins), _x(ins, "Scale")
    stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(stat_dtype)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + attrs.get("epsilon", 1e-5))
    gain = scale.astype(stat_dtype)
    if attrs.get("zero_centered", False):
        gain = 1.0 + gain
    return {"Y": [(y * gain).astype(x.dtype)]}


def _draw_bits(op, rng, shape):
    """uint16 random words of ``shape`` for op ``op``, each device
    drawing only the rows it holds. XLA's SPMD partitioner cannot split
    a RngBitGenerator: under a mesh a plain ``jax.random.bits`` makes
    every chip generate the words of the GLOBAL batch and keep its
    share. So where the program is lowered for a mesh whose data axes
    are still automatic and dim 0 divides over them, only the draw goes
    into a shard_map over those axes: each shard folds its index along
    them into the key and draws its local ``[b/n, ...]``. Anywhere else
    (one device; no data axis; an indivisible or missing dim 0; inside a
    GPipe stage, where the data axes are manual already) it is the plain
    draw, and pt_rng_draw_total names the axes that repeat it."""
    split = interp.mesh_batch_split()
    sharded = (split is not None and not split.nested and split.n > 1
               and len(shape) >= 1 and shape[0] % split.n == 0)
    axis = split.axis if sharded else ()
    if _monitor.enabled() and interp.lowering_active():
        ctx = interp.spmd_ctx()
        # a key that already varies over a manual axis (a GPipe stage
        # folds its pipe rank in) is not repeated over that axis
        varies = set(axis) | set(getattr(jax.typeof(rng), "vma", ()))
        _M_RNG_DRAW.inc(labels={
            "op": op, "sharded_over": ",".join(axis),
            "replicated_over": ",".join(sorted(
                a for a in (ctx.mesh.axis_names if ctx else ())
                if ctx.mesh.shape[a] > 1 and a not in varies))})
    if not sharded:
        return jax.random.bits(rng, shape, dtype=jnp.uint16)
    from jax.sharding import PartitionSpec as P

    local_shape = (shape[0] // split.n, *shape[1:])

    def local(key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        return jax.random.bits(key, local_shape, dtype=jnp.uint16)

    return jax.shard_map(local, mesh=split.mesh, in_specs=P(),
                         out_specs=P(axis), axis_names=set(axis))(rng)


@register_op("dropout", needs_rng=True)
def _dropout(ins, attrs, rng=None):
    """Out = X with each element kept with probability 1 - p, Mask the
    uint8 keep-mask the backward consumes.

    The random stream's contract. On one device the mask is
    ``jax.random.bits(key, shape(x), uint16) < threshold`` on the op's
    key for this step, nothing else. Under a mesh with data axes the
    mask is keyed by (the op's key for this step, the shard's index
    along the data axes): each shard draws its own rows (_draw_bits), so
    for one seed the mask depends on how many shards split the batch, as
    any non-partitionable generator's does, and two shards never share a
    mask. The keep probability, the uint16 threshold, upscale_in_train,
    the saved Mask and dropout_grad are the same mathematics at the
    same precision on every path. Under dp x tp an activation that is
    also split over the model axis is drawn whole by every model rank
    (the op cannot observe that split)."""
    x = _x(ins)
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            return {"Out": [x], "Mask": []}
        return {"Out": [x * (1.0 - p)], "Mask": []}
    if p <= 0.0:  # keep-everything: the uint16 threshold below would
        return {"Out": [x], "Mask": []}  # overflow at 65536
    # keep-mask from 16-bit random words: RngBitGenerator throughput is
    # random-bits-bound on TPU, so uint16 halves its cost vs the uint32
    # words bernoulli() draws; 1/65536 probability granularity (~2e-5
    # keep-rate bias worst case) is far below dropout's statistical noise.
    bits = _draw_bits("dropout", rng, jnp.shape(x))
    keep = bits < jnp.uint16(min(round((1.0 - p) * 65536.0), 65535))
    if impl == "upscale_in_train":
        y = jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))
    else:
        y = jnp.where(keep, x, jnp.zeros((), x.dtype))
    return {"Out": [y], "Mask": [keep.astype(jnp.uint8)]}


@register_op("dropout_grad", no_grad=True)
def _dropout_grad(ins, attrs):
    """Mask-consuming backward (overrides the auto vjp derivation, which
    would re-run RngBitGenerator to rebuild the keep mask — measured ~40%
    of the transformer bench's dropout cost; the reference likewise feeds
    the saved mask to its grad kernel, dropout_op.cc DropoutGradKernel)."""
    g = _x(ins, "GRAD::Out")
    mask = _x(ins, "Mask")
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        dx = g if impl == "upscale_in_train" else g * (1.0 - p)
    elif p <= 0.0:  # forward was identity (no mask emitted)
        dx = g
    else:
        keep = mask.astype(jnp.bool_)
        gs = g / (1.0 - p) if impl == "upscale_in_train" else g
        dx = jnp.where(keep, gs, jnp.zeros((), g.dtype))
    return {"GRAD::X": [dx]}


@register_op("softmax")
def _softmax(ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.softmax(_x(ins), axis=axis)]}


@register_op("log_softmax")
def _log_softmax(ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.log_softmax(_x(ins), axis=axis)]}


@register_op("cross_entropy", diff_inputs=("X",))
def _cross_entropy(ins, attrs):
    x, label = _x(ins), _x(ins, "Label")
    eps = 1e-8
    ignore_index = attrs.get("ignore_index", -100)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        if jnp.ndim(label) == jnp.ndim(x):
            label = jnp.squeeze(label, axis=-1)
        lbl = label.astype(jnp.int32)
        picked = jnp.take_along_axis(
            x, jnp.maximum(lbl, 0)[..., None], axis=-1
        )
        loss = -jnp.log(picked + eps)
        if ignore_index >= 0:
            keep = (lbl != ignore_index)[..., None]
            loss = loss * keep.astype(loss.dtype)
    return {"Y": [loss]}


_M_LOSS_HEAD = _monitor.counter(
    "pt_loss_head_dispatch_total",
    "softmax_with_cross_entropy calls lowered, one row a lowered call of "
    "the op (pass fwd) or of its grad op (pass bwd): labels hard / soft, "
    "softmax_grad 1 where the program reads Softmax and its gradient "
    "reaches the grad op (the softmax's own vjp term is then paid for); "
    "labels hard_rows: linear_cross_entropy and its grad op, the "
    "projection and the loss over the rows whose label counts")


def loss_head_dispatch_counts():
    """{"hard|soft|hard_rows fwd|bwd 0|1": calls lowered so far}:
    pt_loss_head_dispatch_total as rng_draw_counts() gives the draws."""
    out = {}
    for row in _monitor.snapshot()[_M_LOSS_HEAD.name]["values"]:
        lb = row["labels"]
        name = (f"{lb.get('labels', '?')} {lb.get('pass', '?')} "
                f"{lb.get('softmax_grad', '?')}")
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def _note_loss_head(labels, bwd, softmax_grad=False):
    # off with telemetry; build-time shape inference is not a lowering
    if _monitor.enabled() and interp.lowering_active():
        _M_LOSS_HEAD.inc(labels={
            "labels": labels,
            "pass": "bwd" if bwd else "fwd",
            "softmax_grad": "1" if softmax_grad else "0"})


def _xent_rows(logits):
    """(the logits in >= float32, their rows' logsumexp [..., 1]). The
    reduction over the vocabulary is precision-sensitive, so it runs in
    float32 even where the logits stream is bf16 (AMP); the cast fuses.
    The op and its grad op both come here, so XLA's CSE finds ONE."""
    x = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
    return x, jax.nn.logsumexp(x, axis=-1, keepdims=True)


def _xent_hard_label(logits, label, ignore_index):
    """(hit [..., vocab]: the label's column of each row; keep [..., 1]:
    False on a row whose label is ignore_index, None where nothing is
    ignored). A compare against an iota: the select it feeds sits inside
    the pass that already walks the row, where a gather would need its
    operand written out first."""
    if jnp.ndim(label) == jnp.ndim(logits):
        label = jnp.squeeze(label, axis=-1)
    lbl = label.astype(jnp.int32)
    cols = jax.lax.broadcasted_iota(
        jnp.int32, jnp.shape(logits), jnp.ndim(logits) - 1)
    hit = cols == jnp.maximum(lbl, 0)[..., None]
    keep = (lbl != ignore_index)[..., None] if ignore_index >= 0 else None
    return hit, keep


def _softmax_with_cross_entropy_grad_maker(op, block, out_grads, provide,
                                           should_skip):
    """softmax_with_cross_entropy_grad over Logits, Label, GRAD::Loss
    and, only where the program made one, GRAD::Softmax -> GRAD::Logits.
    Nothing stands in for a gradient the program did not give."""
    from paddle_tpu.core.registry import get_op_def

    logits = op.inputs["Logits"][0]
    grads = {}
    for slot in ("Loss", "Softmax"):
        g = (out_grads.get(slot) or [""])[0]
        if g:
            grads[GRAD_SLOT + slot] = [g]
    if not grads or should_skip(
            logits, "Logits", get_op_def("softmax_with_cross_entropy")):
        return []
    src = block._find_var_recursive(logits)
    gname = provide(logits)
    block.create_var(name=gname, shape=src.shape if src else None,
                     dtype=src.dtype if src else "float32")
    return [dict(
        type="softmax_with_cross_entropy_grad",
        inputs={"Logits": [logits], "Label": list(op.inputs["Label"]),
                **grads},
        outputs={GRAD_SLOT + "Logits": [gname]},
        attrs=dict(op.attrs),
    )]


@register_op("softmax_with_cross_entropy", diff_inputs=("Logits",),
             grad_maker=_softmax_with_cross_entropy_grad_maker,
             doc="Loss [..., 1] = logsumexp(logits) - logits[label] (hard "
                 "labels, 0 on a row whose label is ignore_index) or "
                 "logsumexp * sum(label) - sum(label * logits) (soft "
                 "ones), in float32; Softmax = exp(logits - logsumexp). "
                 "The logits are read, never copied: no log-probability "
                 "tensor exists. Its grad op is its own "
                 "(softmax_with_cross_entropy_grad)")
def _softmax_with_cross_entropy(ins, attrs):
    logits, label = _x(ins, "Logits"), _x(ins, "Label")
    soft_label = attrs.get("soft_label", False)
    _note_loss_head("soft" if soft_label else "hard", bwd=False)
    x, lse = _xent_rows(logits)
    if soft_label:
        loss = (lse * jnp.sum(label, axis=-1, keepdims=True)
                - jnp.sum(label * x, axis=-1, keepdims=True))
    else:
        hit, keep = _xent_hard_label(
            logits, label, attrs.get("ignore_index", -100))
        loss = lse - jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)
        if keep is not None:
            loss = jnp.where(keep, loss, 0.0)
    # dead code unless the program reads it
    return {"Softmax": [jnp.exp(x - lse)], "Loss": [loss]}


@register_op("softmax_with_cross_entropy_grad", no_grad=True)
def _softmax_with_cross_entropy_grad(ins, attrs):
    """GRAD::Logits, in the logits' dtype, computed in float32:
    g * (softmax - onehot) for hard labels (0 on an ignored row),
    g * (softmax * sum(label) - label) for soft ones, g = GRAD::Loss;
    with a GRAD::Softmax (a program that reads Softmax), the softmax's
    own vjp softmax * (gs - sum(gs * softmax)) besides. The row
    statistics are the forward's (_xent_rows): one pass over the logits
    for them, one that writes dlogits."""
    logits, label = _x(ins, "Logits"), _x(ins, "Label")
    g, gs = _x(ins, GRAD_SLOT + "Loss"), _x(ins, GRAD_SLOT + "Softmax")
    soft_label = attrs.get("soft_label", False)
    _note_loss_head("soft" if soft_label else "hard", bwd=True,
                    softmax_grad=gs is not None)
    x, lse = _xent_rows(logits)
    p = jnp.exp(x - lse)
    d = None
    if g is not None:
        g = jnp.reshape(g.astype(x.dtype), jnp.shape(lse))
        if soft_label:
            d = g * (p * jnp.sum(label, axis=-1, keepdims=True) - label)
        else:
            hit, keep = _xent_hard_label(
                logits, label, attrs.get("ignore_index", -100))
            if keep is not None:
                g = jnp.where(keep, g, 0.0)
            d = g * jnp.where(hit, p - 1.0, p)
    if gs is not None:
        gs = gs.astype(x.dtype)
        ds = p * (gs - jnp.sum(gs * p, axis=-1, keepdims=True))
        d = ds if d is None else d + ds
    return {GRAD_SLOT + "Logits": [d.astype(logits.dtype)]}


# ---------------------------------------------------------------------------
# linear_cross_entropy: the projection onto the vocabulary and the loss in
# one op, over the rows whose label counts. A masked-LM head labels a
# seventh of its positions; the rows that count are put first (a stable
# sort of the flags) and walked in chunks of _ROWS_CHUNK, as many as the
# live count asks for, the last a short one where few rows are left of
# few chunks (_over_chunks; a device scalar: the trip count is the data's
# and one compiled loop serves every labelling; grouped_matmul.over_live_rows,
# the expert layers' walk over their live rows). A chunk's logits
# [_ROWS_CHUNK, vocab] are the only logits that ever exist; the grad op
# makes them again. Every move of rows is a gather (XLA's scatter costs
# more a row the more rows it takes: PERF.md section 6, PR 35): the
# chunks gather their rows of X by the sorted order, the results are
# written chunk by chunk into a compact buffer, and a row finds its own
# there by the count of live rows before it.
# ---------------------------------------------------------------------------

# rows a trip of the loop projects. On bert-train (b256 x 128, 4,864 of
# 32,768 rows live, vocabulary 30,522, one v5e; my chip run, PR 55, one
# run each on one seed): 512 rows 178.26 ms a step, 1024 rows 176.89,
# 2048 rows 178.60 (the parent 200.61). At 1024 a trip's matmuls run at
# three quarters to nine tenths of the MXU's peak and hide the float32
# dW carry's trip through HBM; ten trips of 512 do not, and three of
# 2048 project 6,144 rows where five of 1024 project 5,120.
_ROWS_CHUNK = 1024
# Where the rows are few chunks (no more than _SHORT_TRIP of them: a
# trip is a quarter of the walk or more), the last chunk's trip is a
# short one, of a chunk's quarter, where no more live rows than that are
# left: a labelling whose live count lies AT a multiple of the chunk (a
# noise schedule that masks half of 4096 rows: 2048 +- 45) otherwise pays
# a whole trip, 1% of sdar-train-s4096's step, for a few dozen rows in
# every other feed (my chip runs, PR 61, six seeds: spread 0.50% without
# it, 0.17% with it). Not where the rows are many chunks: the branch in
# front of the loop costs bert-train's step (32 chunks, 4864 live rows,
# never a short trip) 0.43% whether it is taken or not.
_SHORT_TRIP = 4


def _rows_that_count(x, label, ignore_index):
    """(x [n, d], label [n] int32, c the chunk, keep [n], live, order
    [a multiple of c >= n]: the live rows' indices first and in their
    own order, place [n]: where a LIVE row sits in that order)."""
    x = jnp.reshape(x, (-1, jnp.shape(x)[-1]))
    label = jnp.reshape(label, (-1,)).astype(jnp.int32)
    n = label.shape[0]
    c = min(_ROWS_CHUNK, n)
    keep = label != ignore_index
    k = keep.astype(jnp.int32)
    order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, -n % c))
    return x, label, c, keep, jnp.sum(k), order, jnp.cumsum(k) - k


def _rows(x, idx):
    """x[idx] for indices this op made: all inside x."""
    return x.at[idx].get(mode="promise_in_bounds")


def _over_chunks(live, n, c, trip_of, zeros):
    """The carry ``zeros()`` after ``trip_of(rows)(r0, alive, carry)``
    over the ``live`` of ``n`` rows: trips of ``c`` rows
    (``over_live_rows``) and, for a walk of few chunks (_SHORT_TRIP),
    where the rows left behind the last whole chunk fit a short trip
    (c / _SHORT_TRIP), that one trip, of a body of its own, in place of
    a whole one. The short trip comes FIRST and makes the loop's initial
    carry (as a second loop behind the first, XLA laid its float32 dW
    carry out another way and copied it between the two every step)."""
    s = c // _SHORT_TRIP
    # (a short trip is whole sublane tiles)
    if n > _SHORT_TRIP * c or c % (8 * _SHORT_TRIP):
        return over_live_rows(live, c, trip_of(c), zeros())
    live = jnp.asarray(live, jnp.int32)
    r0, left = live // c * c, live % c
    short = (left > 0) & (left <= s)
    alive = r0 + jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0) < live
    init = jax.lax.cond(
        short, lambda: trip_of(s)(r0, alive, zeros()), zeros)
    return over_live_rows(jnp.where(short, r0, live), c, trip_of(c), init)


def _chunk(x, w, label, order, r0, c):
    """A chunk of the compacted rows: (their indices, their rows of X,
    those rows' logits, hit [c, vocab]: the label's column of each)."""
    idx = rows_at(order, r0, c)
    xc = _rows(x, idx)
    logits = xc @ w
    hit, _ = _xent_hard_label(logits, _rows(label, idx)[:, None], -1)
    return idx, xc, logits, hit


def _to_own_rows(buf, keep, place, shape):
    """The compact buffer's rows at their own places, zeros on the rows
    that do not count."""
    return jnp.reshape(
        jnp.where(keep[:, None], _rows(buf, place), 0), shape)


# the eager engine (dygraph/tracer.py) differentiates an op's forward
# itself and cannot walk a loop whose trip count is data: the forward's
# vjp IS the grad op's function
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _linear_xent(x, w, label, ignore_index):
    lead = jnp.shape(x)[:-1]
    x, label, c, keep, live, order, place = _rows_that_count(
        x, label, ignore_index)

    def trip_of(rows):
        def trip(r0, alive, lossc):
            _, _, logits, hit = _chunk(x, w, label, order, r0, rows)
            xf, lse = _xent_rows(logits)
            loss = lse - jnp.sum(jnp.where(hit, xf, 0.0), axis=-1,
                                 keepdims=True)
            return put_rows(lossc, r0, jnp.where(alive, loss, 0.0))
        return trip

    lossc = _over_chunks(
        live, label.shape[0], c, trip_of,
        lambda: jnp.zeros((order.shape[0], 1), jnp.float32))
    return _to_own_rows(lossc, keep, place, (*lead, 1))


def _linear_xent_grads(x, w, label, g, ignore_index):
    """(dX in X's dtype and shape, dW in W's): a chunk's logits made
    again, dlogits = g * (softmax - onehot) in float32 and cast to the
    logits' dtype, dX's rows written into the compact buffer, dW summed
    in float32 in the loop's carry."""
    shape = jnp.shape(x)
    x, label, c, keep, live, order, place = _rows_that_count(
        x, label, ignore_index)
    g = jnp.reshape(g, (-1, 1)).astype(jnp.float32)

    def trip_of(rows):
        def trip(r0, alive, carry):
            dxc, dw = carry
            idx, xc, logits, hit = _chunk(x, w, label, order, r0, rows)
            xf, lse = _xent_rows(logits)
            p = jnp.exp(xf - lse)
            d = (jnp.where(alive, _rows(g, idx), 0.0)
                 * jnp.where(hit, p - 1.0, p))
            d = d.astype(logits.dtype)
            dw = dw + jax.lax.dot_general(
                xc, d, (((0,), (0,)), ((), ())),
                preferred_element_type=dw.dtype)
            return put_rows(dxc, r0, d @ w.T), dw
        return trip

    dxc, dw = _over_chunks(
        live, label.shape[0], c, trip_of, lambda: (
            jnp.zeros((order.shape[0], shape[-1]), x.dtype),
            jnp.zeros(jnp.shape(w), jnp.promote_types(w.dtype, jnp.float32))))
    return _to_own_rows(dxc, keep, place, shape), dw.astype(w.dtype)


_linear_xent.defvjp(
    lambda x, w, label, ignore_index: (
        _linear_xent(x, w, label, ignore_index), (x, w, label)),
    lambda ignore_index, res, g: (
        *_linear_xent_grads(*res, g, ignore_index), None))


def _linear_cross_entropy_grad_maker(op, block, out_grads, provide,
                                     should_skip):
    """linear_cross_entropy_grad over X, W, Label, GRAD::Loss ->
    GRAD::X, GRAD::W (a hole where the program wants none)."""
    from paddle_tpu.core.registry import get_op_def

    g = (out_grads.get("Loss") or [""])[0]
    opdef = get_op_def("linear_cross_entropy")
    outs = {}
    for slot in ("X", "W"):
        name = op.inputs[slot][0]
        if not g or should_skip(name, slot, opdef):
            outs[GRAD_SLOT + slot] = [""]
            continue
        src = block._find_var_recursive(name)
        gname = provide(name)
        block.create_var(name=gname, shape=src.shape if src else None,
                         dtype=src.dtype if src else "float32")
        outs[GRAD_SLOT + slot] = [gname]
    if not any(n for names in outs.values() for n in names):
        return []
    return [dict(
        type="linear_cross_entropy_grad",
        inputs={"X": list(op.inputs["X"]), "W": list(op.inputs["W"]),
                "Label": list(op.inputs["Label"]), GRAD_SLOT + "Loss": [g]},
        outputs=outs,
        attrs=dict(op.attrs),
    )]


@register_op("linear_cross_entropy", diff_inputs=("X", "W"),
             grad_maker=_linear_cross_entropy_grad_maker,
             doc="Loss [..., 1] float32 = what mul(X [..., d], W [d, "
                 "vocab]) + softmax_with_cross_entropy give on hard "
                 "labels, 0 on a row whose Label is ignore_index (any "
                 "integer), computed over the rows that count alone, in "
                 "chunks: no [rows, vocab] tensor exists. Its grad op is "
                 "its own (linear_cross_entropy_grad)")
def _linear_cross_entropy(ins, attrs):
    _note_loss_head("hard_rows", bwd=False)
    return {"Loss": [_linear_xent(
        _x(ins), _x(ins, "W"), _x(ins, "Label"),
        int(attrs.get("ignore_index", -100)))]}


@register_op("linear_cross_entropy_grad", no_grad=True)
def _linear_cross_entropy_grad(ins, attrs):
    """GRAD::X and GRAD::W in their operands' dtypes, by the forward's
    own walk over the rows that count (_linear_xent_grads)."""
    _note_loss_head("hard_rows", bwd=True)
    dx, dw = _linear_xent_grads(
        _x(ins), _x(ins, "W"), _x(ins, "Label"), _x(ins, GRAD_SLOT + "Loss"),
        int(attrs.get("ignore_index", -100)))
    return {GRAD_SLOT + "X": [dx], GRAD_SLOT + "W": [dw]}


@register_op("sigmoid_cross_entropy_with_logits", diff_inputs=("X",))
def _sigmoid_ce(ins, attrs):
    x, label = _x(ins), _x(ins, "Label")
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": [loss]}


@register_op("huber_loss", diff_inputs=("X",))
def _huber_loss(ins, attrs):
    x, y = _x(ins), _x(ins, "Y")
    delta = attrs.get("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * r * r, delta * (a - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register_op("square_error_cost", diff_inputs=("X", "Label"))
def _square_error_cost(ins, attrs):
    x, label = _x(ins), _x(ins, "Label")
    return {"Out": [jnp.square(x - label)]}


@register_op("smooth_l1_loss", diff_inputs=("X",))
def _smooth_l1(ins, attrs):
    x, y = _x(ins), _x(ins, "Y")
    w_in = _x(ins, "InsideWeight")
    w_out = _x(ins, "OutsideWeight")
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if w_in is not None:
        d = d * w_in
    a = jnp.abs(d)
    loss = jnp.where(a < 1.0 / s2, 0.5 * d * d * s2, a - 0.5 / s2)
    if w_out is not None:
        loss = loss * w_out
    return {"Out": [jnp.sum(loss, axis=-1, keepdims=True)], "Diff": [d]}


@register_op("accuracy", no_grad=True)
def _accuracy(ins, attrs):
    indices, label = _x(ins, "Indices"), _x(ins, "Label")
    if jnp.ndim(label) > 1:
        label = jnp.squeeze(label, axis=-1)
    correct = jnp.any(indices == label[:, None], axis=-1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = jnp.asarray(jnp.shape(indices)[0], jnp.float32)
    return {
        "Accuracy": [num_correct / total],
        "Correct": [num_correct.astype(jnp.int32)],
        "Total": [total.astype(jnp.int32)],
    }


@register_op("mean_iou", no_grad=True)
def _mean_iou(ins, attrs):
    pred, label = _x(ins, "Predictions"), _x(ins, "Labels")
    n = attrs["num_classes"]
    pred = pred.reshape(-1)
    label = label.reshape(-1)
    cm = jnp.zeros((n, n), jnp.float32).at[label, pred].add(1.0)
    inter = jnp.diag(cm)
    union = jnp.sum(cm, 0) + jnp.sum(cm, 1) - inter
    iou = inter / jnp.maximum(union, 1.0)
    valid = (union > 0).astype(jnp.float32)
    miou = jnp.sum(iou * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    return {"OutMeanIou": [miou], "OutWrong": [], "OutCorrect": []}


@register_op("maxout", diff_inputs=("X",))
def _maxout(ins, attrs):
    x = _x(ins)  # [N, C, H, W]
    g = attrs["groups"]
    n, c, h, w = jnp.shape(x)
    return {"Out": [jnp.max(x.reshape(n, c // g, g, h, w), axis=2)]}


@register_op("label_smooth", diff_inputs=("X",))
def _label_smooth(ins, attrs):
    x = _x(ins)
    eps = attrs.get("epsilon", 0.1)
    k = jnp.shape(x)[-1]
    dist = ins.get("PriorDist")
    if dist and dist[0] is not None:
        return {"Out": [(1 - eps) * x + eps * dist[0]]}
    return {"Out": [(1 - eps) * x + eps / k]}


@register_op("prelu", diff_inputs=("X", "Alpha"))
def _prelu(ins, attrs):
    """out = x > 0 ? x : alpha * x; alpha shared per-op, per-channel, or
    per-element by `mode` (reference: operators/prelu_op.cc)."""
    x, alpha = _x(ins), _x(ins, "Alpha")
    mode = attrs.get("mode", "all")
    if mode == "channel":
        shape = [1] * jnp.ndim(x)
        shape[1] = -1
        alpha = jnp.reshape(alpha, shape)
    elif mode == "element":
        alpha = jnp.reshape(alpha, (1,) + tuple(jnp.shape(x)[1:]))
    else:
        alpha = jnp.reshape(alpha, ())
    return {"Out": [jnp.where(x > 0, x, alpha * x)]}


@register_op("group_norm", diff_inputs=("X", "Scale", "Bias"))
def _group_norm(ins, attrs):
    """Normalize over channel groups of an NCHW tensor
    (reference: operators/group_norm_op.cc)."""
    x = _x(ins)
    scale, bias = _x(ins, "Scale"), _x(ins, "Bias")
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = jnp.shape(x)[0], jnp.shape(x)[1]
    spatial = tuple(jnp.shape(x)[2:])
    stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
    xg = jnp.reshape(x.astype(stat_dtype), (n, g, c // g) + spatial)
    axes = tuple(range(2, jnp.ndim(xg)))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = (xg - mean) * jax.lax.rsqrt(var + eps)
    y = jnp.reshape(y, jnp.shape(x))
    pshape = [1, c] + [1] * len(spatial)
    if scale is not None:
        y = y * jnp.reshape(scale, pshape).astype(stat_dtype)
    if bias is not None:
        y = y + jnp.reshape(bias, pshape).astype(stat_dtype)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [jax.lax.stop_gradient(jnp.reshape(mean, (n, g)))],
        "Variance": [jax.lax.stop_gradient(jnp.reshape(var, (n, g)))],
    }


@register_op(
    "sync_batch_norm",
    diff_inputs=("X", "Scale", "Bias"),
    inplace={"MeanOut": "Mean", "VarianceOut": "Variance"},
)
def _sync_batch_norm(ins, attrs):
    """Cross-device batch norm (reference: operators/sync_batch_norm_op.cu
    — NCCL all-reduce of per-GPU partial sums). TPU-native: the kernel is
    the ordinary batch_norm compute; under GSPMD data parallelism the
    batch axis is sharded, so ``jnp.mean`` over it ALREADY reduces across
    devices (XLA inserts the ICI all-reduce) — global statistics are the
    default, not an extra op."""
    return _batch_norm(ins, attrs)


@register_op("norm", diff_inputs=("X",))
def _norm(ins, attrs):
    """L2-normalize along axis (reference: operators/norm_op.cc)."""
    x = _x(ins)
    axis = attrs.get("axis", 1)
    eps = attrs.get("epsilon", 1e-10)
    n = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / n], "Norm": [n]}


@register_op("affine_channel", diff_inputs=("X", "Scale", "Bias"))
def _affine_channel(ins, attrs):
    """Per-channel scale+shift (reference: affine_channel_op.cc)."""
    x = _x(ins)
    scale, bias = _x(ins, "Scale"), _x(ins, "Bias")
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else jnp.ndim(x) - 1
    shape = [1] * jnp.ndim(x)
    shape[c_axis] = jnp.shape(x)[c_axis]
    return {"Out": [x * jnp.reshape(scale, shape) + jnp.reshape(bias, shape)]}


def _interp_out_size(attrs, h, w):
    """Output size resolution matching the reference's precedence
    (interpolate_op.cc: a positive ``scale`` attr WINS over out_h/out_w)."""
    scale = attrs.get("scale", 0.0)
    if scale and scale > 0:
        return int(h * scale), int(w * scale)
    out_h = int(attrs.get("out_h", 0) or 0)
    out_w = int(attrs.get("out_w", 0) or 0)
    return (out_h if out_h > 0 else int(h),
            out_w if out_w > 0 else int(w))


@register_op("bilinear_interp", diff_inputs=("X",))
def _bilinear_interp(ins, attrs):
    """NCHW bilinear resize (reference: operators/interpolate_op.cc).
    align_corners semantics follow the reference default (True)."""
    x = _x(ins)
    n, c, h, w = jnp.shape(x)
    out_h, out_w = _interp_out_size(attrs, h, w)
    align = attrs.get("align_corners", True)
    # align_corners=False splits further by align_mode (reference
    # interpolate_op.cc): mode 1 (the API default) samples src = i*scale,
    # mode 0 samples half-pixel centers
    mode = int(attrs.get("align_mode", 1))
    if align and out_h > 1:
        ys = jnp.linspace(0.0, h - 1.0, out_h)
    elif mode == 1:
        ys = jnp.arange(out_h) * (h / out_h)
    else:
        ys = (jnp.arange(out_h) + 0.5) * h / out_h - 0.5
    if align and out_w > 1:
        xs = jnp.linspace(0.0, w - 1.0, out_w)
    elif mode == 1:
        xs = jnp.arange(out_w) * (w / out_w)
    else:
        xs = (jnp.arange(out_w) + 0.5) * w / out_w - 0.5
    ys = jnp.clip(ys, 0, h - 1)
    xs = jnp.clip(xs, 0, w - 1)
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[None, None, :, None]
    wx = (xs - x0)[None, None, None, :]
    g = lambda yy, xx: x[:, :, yy, :][:, :, :, xx]
    out = (
        g(y0, x0) * (1 - wy) * (1 - wx)
        + g(y1, x0) * wy * (1 - wx)
        + g(y0, x1) * (1 - wy) * wx
        + g(y1, x1) * wy * wx
    )
    return {"Out": [out.astype(x.dtype)]}


@register_op("nearest_interp", diff_inputs=("X",))
def _nearest_interp(ins, attrs):
    """NCHW nearest-neighbor resize (reference: interpolate_op.cc)."""
    x = _x(ins)
    n, c, h, w = jnp.shape(x)
    out_h, out_w = _interp_out_size(attrs, h, w)
    align = attrs.get("align_corners", True)
    if align and out_h > 1:
        ys = jnp.round(jnp.linspace(0.0, h - 1.0, out_h)).astype(jnp.int32)
    else:
        ys = jnp.floor(jnp.arange(out_h) * h / out_h).astype(jnp.int32)
    if align and out_w > 1:
        xs = jnp.round(jnp.linspace(0.0, w - 1.0, out_w)).astype(jnp.int32)
    else:
        xs = jnp.floor(jnp.arange(out_w) * w / out_w).astype(jnp.int32)
    return {"Out": [x[:, :, ys, :][:, :, :, xs]]}


@register_op("row_conv", diff_inputs=("X", "Filter"))
def _row_conv(ins, attrs):
    """Lookahead row convolution over time (reference: row_conv_op.cc).
    X [B, T, D], Filter [future_len, D]."""
    x = _x(ins)
    f = _x(ins, "Filter")
    k = jnp.shape(f)[0]
    xp = jnp.pad(x, ((0, 0), (0, k - 1), (0, 0)))
    out = sum(xp[:, i : i + jnp.shape(x)[1], :] * f[i][None, None, :]
              for i in range(k))
    return {"Out": [out]}


@register_op("temporal_shift", diff_inputs=("X",))
def _temporal_shift(ins, attrs):
    """Shift a fraction of channels across the segment (time) dim
    (reference: temporal_shift_op.cc). X [N*T, C, H, W]."""
    x = _x(ins)
    seg = int(attrs.get("seg_num", 1))
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = jnp.shape(x)
    n = nt // seg
    x5 = jnp.reshape(x, (n, seg, c, h, w))
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    fwd = jnp.pad(x5[:, 1:, :c1], ((0, 0), (0, 1), (0, 0), (0, 0), (0, 0)))
    bwd = jnp.pad(x5[:, :-1, c1:c2], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    keep = x5[:, :, c2:]
    out = jnp.concatenate([fwd, bwd, keep], axis=2)
    return {"Out": [jnp.reshape(out, (nt, c, h, w))]}


@register_op("grid_sampler", diff_inputs=("X", "Grid"))
def _grid_sampler(ins, attrs):
    """Bilinear sampling at normalized grid locations
    (reference: grid_sampler_op.cc). X [N,C,H,W], Grid [N,Ho,Wo,2] in
    [-1, 1]."""
    x = _x(ins)
    grid = _x(ins, "Grid")
    n, c, h, w = jnp.shape(x)
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0     # [N, Ho, Wo]
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1

    def sample(yy, xx):
        # out-of-bound corners contribute ZERO, matching the reference's
        # zero padding (grid_sampler_op.h) — not border clamping
        inb = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        bidx = jnp.arange(n)[:, None, None]
        vals = x[bidx, :, yy, xx]                  # [N, Ho, Wo, C]
        return vals * inb[..., None].astype(vals.dtype)

    wx = gx - x0
    wy = gy - y0
    out = (
        sample(y0, x0) * ((1 - wy) * (1 - wx))[..., None]
        + sample(y1, x0) * (wy * (1 - wx))[..., None]
        + sample(y0, x1) * ((1 - wy) * wx)[..., None]
        + sample(y1, x1) * (wy * wx)[..., None]
    )
    return {"Output": [jnp.transpose(out, (0, 3, 1, 2)).astype(x.dtype)]}


@register_op("auc", no_grad=True)
def _auc(ins, attrs):
    """Batch-local ROC-AUC via threshold buckets (reference:
    operators/metrics/auc_op.cc; streaming state lives in metrics.Auc)."""
    pred = _x(ins, "Predict")   # [N, 2] or [N, 1] prob of positive
    label = _x(ins, "Label")
    if jnp.ndim(label) > 1:
        label = jnp.squeeze(label, -1)
    p = pred[:, -1]
    buckets = int(attrs.get("num_thresholds", 200))
    idx = jnp.clip((p * buckets).astype(jnp.int32), 0, buckets - 1)
    pos = jnp.zeros((buckets,)).at[idx].add(label.astype(jnp.float32))
    neg = jnp.zeros((buckets,)).at[idx].add(1.0 - label.astype(jnp.float32))
    # integrate from the highest threshold down
    tp = jnp.cumsum(pos[::-1])
    fp = jnp.cumsum(neg[::-1])
    tot_pos = jnp.maximum(tp[-1], 1e-12)
    tot_neg = jnp.maximum(fp[-1], 1e-12)
    tpr = jnp.concatenate([jnp.zeros((1,)), tp / tot_pos])
    fpr = jnp.concatenate([jnp.zeros((1,)), fp / tot_neg])
    auc = jnp.sum((fpr[1:] - fpr[:-1]) * (tpr[1:] + tpr[:-1]) / 2.0)
    return {"AUC": [auc]}


@register_op("bilinear_tensor_product",
             diff_inputs=("X", "Y", "Weight", "Bias"))
def _bilinear_tensor_product(ins, attrs):
    """out[b, k] = x[b] @ W[k] @ y[b] + bias[k]
    (reference: operators/bilinear_tensor_product_op.cc)."""
    x, y = _x(ins), _x(ins, "Y")
    w = _x(ins, "Weight")                        # [K, Dx, Dy]
    bias = _x(ins, "Bias")
    out = jnp.einsum("bi,kij,bj->bk", x, w, y)
    if bias is not None:
        out = out + jnp.reshape(bias, (1, -1))
    return {"Out": [out]}


@register_op("nce", diff_inputs=("Input", "Weight", "Bias"), needs_rng=True)
def _nce(ins, attrs, rng=None):
    """Noise-contrastive estimation loss (reference: operators/nce_op.cc,
    uniform sampler). Avoids the full-vocab softmax: per example, score
    the true class plus ``num_neg_samples`` uniform negatives.

    inputs: Input [B, D], Label [B, 1] int, Weight [C, D], Bias [C] opt.
    outputs: Cost [B, 1].
    """
    x = ins["Input"][0]
    label = ins["Label"][0]
    if jnp.ndim(label) > 1:
        label = jnp.squeeze(label, -1)
    w = ins["Weight"][0]
    bias = _x(ins, "Bias")
    c = jnp.shape(w)[0]
    k = int(attrs.get("num_neg_samples", 10))
    b = jnp.shape(x)[0]

    neg = jax.random.randint(rng, (b, k), 0, c)          # uniform sampler
    ids = jnp.concatenate([label[:, None], neg], axis=1)  # [B, 1+K]
    w_sel = jnp.take(w, ids, axis=0)                      # [B, 1+K, D]
    logits = jnp.einsum("bd,bkd->bk", x, w_sel)
    if bias is not None:
        logits = logits + jnp.take(bias, ids)
    # NCE with uniform noise: q = k / C per class
    log_q = jnp.log(jnp.asarray(k, logits.dtype)) - jnp.log(
        jnp.asarray(c, logits.dtype))
    adj = logits - log_q
    pos = jax.nn.log_sigmoid(adj[:, 0])
    negs = jnp.sum(jax.nn.log_sigmoid(-adj[:, 1:]), axis=1)
    return {"Cost": [(-(pos + negs))[:, None]]}


@register_op("hierarchical_sigmoid", diff_inputs=("X", "W", "Bias"))
def _hierarchical_sigmoid(ins, attrs):
    """Binary-tree sigmoid classifier over log2(C) path nodes (reference:
    hsigmoid_op.cc with the default complete-tree SimpleCode: leaf code =
    label + C, ancestors are the code's bit-prefixes). X [b, d],
    W [C-1, d], Label [b, 1] or [b], Bias [C-1] optional ->
    Out [b, 1] cost, PreOut [b, max_len] (padded with zeros)."""
    x, w = _x(ins), _x(ins, "W")
    label = _x(ins, "Label")
    bias = _x(ins, "Bias")
    num_classes = int(attrs["num_classes"])
    if jnp.ndim(label) > 1:
        label = jnp.reshape(label, (-1,))
    code = label.astype(jnp.int32) + num_classes       # [b], in [C, 2C)
    # exact integer bit length (f32 log2 over-counts near 2^k boundaries
    # from C ~ 2^20, silently corrupting tree paths): count thresholds
    length = jnp.sum(
        (code[:, None] >= jnp.left_shift(
            jnp.int32(1), jnp.arange(31, dtype=jnp.int32))[None, :]
         ).astype(jnp.int32),
        axis=1,
    )
    path_len = length - 1                              # internal nodes
    max_len = int(num_classes).bit_length()
    pres, losses = [], []
    for j in range(max_len):
        # j-th step: ancestor = the (j+1)-bit prefix of the code minus 1
        # (root first), direction = the next bit (reference SimpleCode:
        # calc_index/calc_bit)
        bit_shift = path_len - 1 - j
        active = bit_shift >= 0
        safe = jnp.maximum(bit_shift, 0)
        node = jnp.right_shift(code, safe + 1) - 1     # [b] in [0, C-2]
        node = jnp.clip(node, 0, num_classes - 2)
        bit = jnp.bitwise_and(jnp.right_shift(code, safe), 1).astype(x.dtype)
        pre = jnp.sum(jnp.take(w, node, axis=0) * x, axis=-1)
        if bias is not None:
            pre = pre + jnp.take(jnp.reshape(bias, (-1,)), node)
        # per-node logistic loss: log(1+e^pre) - bit*pre
        lj = jax.nn.softplus(pre) - bit * pre
        mask = active.astype(x.dtype)
        pres.append(pre * mask)
        losses.append(lj * mask)
    out = sum(losses)[:, None]
    pre_out = jnp.stack(pres, axis=1)
    return {"Out": [out], "PreOut": [pre_out]}


@register_op("sample_logits", needs_rng=True,
             diff_inputs=("Logits",))
def _sample_logits(ins, attrs, rng=None):
    """Sampled-softmax helper (reference: sample_logits_op.cc): keep the
    true-label logits plus ``num_samples`` uniformly sampled classes,
    subtracting log(q) so softmax over the slice estimates the full one.
    Logits [b, C], Labels [b, T] -> Samples [b, T+S], Probabilities,
    SampledLogits [b, T+S], SampledLabel [b, T]."""
    logits = _x(ins, "Logits")
    labels = _x(ins, "Labels")
    s = int(attrs["num_samples"])
    remove_hits = bool(attrs.get("remove_accidental_hits", True))
    b, c = logits.shape
    t = labels.shape[1]
    labels = labels.astype(jnp.int32)
    sampled = jax.random.randint(rng, (b, s), 0, c, dtype=jnp.int32)
    samples = jnp.concatenate([labels, sampled], axis=1)   # [b, t+s]
    # uniform proposal: q = s / C per draw (with replacement)
    q = jnp.full((b, t + s), float(s) / c, logits.dtype)
    picked = jnp.take_along_axis(logits, samples, axis=1)
    adjusted = picked - jnp.log(q)
    if remove_hits:
        # a sampled class equal to the true label would double-count it
        hit = samples[:, None, t:] == labels[:, :, None]   # [b, t, s]
        hit_any = jnp.any(hit, axis=1)                     # [b, s]
        neg = jnp.asarray(-1e20, adjusted.dtype)
        adjusted = jnp.concatenate(
            [adjusted[:, :t],
             jnp.where(hit_any, neg, adjusted[:, t:])], axis=1)
    sampled_label = jnp.tile(jnp.arange(t, dtype=jnp.int64)[None], (b, 1))
    return {"Samples": [samples.astype(jnp.int64)],
            "Probabilities": [q],
            "SampledLogits": [adjusted],
            "SampledLabel": [sampled_label]}


@register_op("fc", diff_inputs=("Input", "W", "Bias"))
def _fc_fused(ins, attrs):
    """Fused fully-connected op — the rewrite target of the fc_fuse pass
    (reference: operators/fc_op.cc + framework/ir/fc_fuse_pass.cc:
    mul + elementwise_add collapse into one kernel). Mirrors the mul
    op's flatten semantics, then adds the bias on the output columns."""
    import math as _m

    x, w = ins["Input"][0], ins["W"][0]
    b_in = ins.get("Bias")
    b = b_in[0] if b_in else None
    xnc = int(attrs.get("in_num_col_dims", 1))
    xs, wsh = jnp.shape(x), jnp.shape(w)
    x2 = jnp.reshape(x, (_m.prod(xs[:xnc]), -1))
    out2 = x2 @ w
    if b is not None:
        out2 = out2 + jnp.reshape(b, (1, -1))
    return {"Out": [jnp.reshape(out2, xs[:xnc] + wsh[1:])]}
