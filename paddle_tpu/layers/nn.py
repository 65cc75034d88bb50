"""NN layer functions (reference: python/paddle/fluid/layers/nn.py — 170
layer fns). Each builds vars + appends ops via LayerHelper."""

from __future__ import annotations

from typing import Optional, Sequence, Union

from paddle_tpu import unique_name
from paddle_tpu.framework import Variable
from paddle_tpu.initializer import ConstantInitializer
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.param_attr import ParamAttr

__all__ = [
    "fc", "embedding", "conv2d", "conv2d_transpose", "pool2d", "batch_norm",
    "layer_norm", "dropout", "relu", "sigmoid", "tanh", "sqrt", "exp", "log",
    "abs", "square", "gelu", "leaky_relu", "softplus", "softsign", "elu",
    "relu6", "swish", "hard_swish", "hard_sigmoid", "softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy", "linear_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "huber_loss",
    "smooth_l1", "mean", "mul", "matmul", "elementwise_op", "elementwise_add",
    "elementwise_sub", "elementwise_mul", "elementwise_div", "elementwise_pow",
    "elementwise_max", "elementwise_min", "reduce_sum", "reduce_mean",
    "reduce_max", "reduce_min", "reduce_prod", "scale", "cast", "clip",
    "clip_by_norm", "accuracy", "topk", "one_hot", "lookup_table", "gather",
    "scatter", "label_smooth", "l2_normalize", "dropout", "split", "pad",
    "pow", "stack", "unstack", "squeeze", "unsqueeze", "expand", "expand_as",
    "argmax", "argmin", "equal", "less_than", "greater_than", "logical_and",
    "logical_or", "logical_not", "where", "cumsum", "increment", "reshape",
    "transpose", "concat", "fill_constant_like", "log_softmax",
    "sequence_pool", "sequence_softmax", "sequence_mask", "sequence_reverse",
    "sequence_expand", "im2sequence", "batch_norm", "group_norm", "prelu",
    "flatten", "sums", "elementwise_mod", "elementwise_floordiv", "maxout",
    "mean_iou",
    "linear_chain_crf", "crf_decoding", "warpctc", "edit_distance",
    "bilinear_tensor_product", "nce", "switch_moe", "topk_moe",
    "rms_norm", "rotary_embedding", "scaled_dot_product_attention",
    "causal_conv1d", "short_conv_gate",
    "gdn_gates",
    "gated_delta_rule", "gated_rms_norm", "silu", "selective_scan",
    "mamba2_scan", "diff_attention_combine", "hc_mix", "hc_pre", "hc_post",
    "dsa_select", "dsa_selected_rows", "dsa_index_loss",
    "roi_align", "roi_pool", "lrn", "spp", "affine_grid", "multiclass_nms",
    "yolo_box", "sequence_conv", "add_position_encoding", "conv3d",
    "spectral_norm", "hsigmoid", "sample_logits",
    "chunk_eval", "ctc_greedy_decoder",
    "py_func", "hash", "tree_conv",
]


def _single_op(op_type, x, attrs=None, dtype=None, slot_in="X", slot_out="Out",
               name=None, stop_gradient=False):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(
        dtype=dtype or x.dtype, stop_gradient=stop_gradient
    )
    helper.append_op(
        op_type, inputs={slot_in: x}, outputs={slot_out: out}, attrs=attrs or {}
    )
    return out


# --- dense / conv layers ---


def fc(
    input: Union[Variable, Sequence[Variable]],
    size: int,
    num_flatten_dims: int = 1,
    param_attr=None,
    bias_attr=None,
    act: Optional[str] = None,
    is_test: bool = False,
    name: Optional[str] = None,
):
    """Fully-connected layer (reference: layers/nn.py fc)."""
    helper = LayerHelper("fc", name=name, bias_attr=bias_attr, act=act)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = param_attr if isinstance(param_attr, (list, tuple)) else [
        param_attr
    ] * len(inputs)
    mul_results = []
    for x, pa in zip(inputs, param_attrs):
        import math

        in_features = math.prod(x.shape[num_flatten_dims:])
        w = helper.create_parameter(
            ParamAttr._to_attr(pa), shape=[in_features, size], dtype=x.dtype
        )
        tmp = helper.create_variable_for_type_inference(dtype=x.dtype)
        helper.append_op(
            "mul",
            inputs={"X": x, "Y": w},
            outputs={"Out": tmp},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype=inputs[0].dtype)
        helper.append_op("sum", inputs={"X": mul_results}, outputs={"Out": pre_bias})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(
    input: Variable,
    size: Sequence[int],
    is_sparse: bool = False,
    is_distributed: bool = False,
    padding_idx: Optional[int] = None,
    param_attr=None,
    dtype: str = "float32",
    name: Optional[str] = None,
):
    """Embedding lookup (reference: layers/nn.py embedding). On TPU the
    gradient is the dense [vocab, d] sum of the cotangent's rows by id
    (``lookup_table_grad``) unless ``is_sparse`` asks for the row-sparse
    pair, and ``is_distributed`` sharding is a pjit spec (SURVEY.md
    section 2.3)."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=list(size), dtype=dtype
    )
    out = helper.create_variable_for_type_inference(dtype=dtype)
    # Padded [b, t] ids convention: never squeeze, even when t == 1 (the
    # op's squeeze heuristic exists for the reference's [N, 1] column ids).
    attrs = {"squeeze_last": False}
    if padding_idx is not None:
        attrs["padding_idx"] = int(padding_idx)
    if is_distributed:
        # Marks the table for row-sharded lookup (psum over the strategy's
        # table axis) when run under CompiledProgram.with_strategy.
        attrs["is_distributed"] = True
    if is_sparse:
        # Row-sparse {rows, values} gradient pair instead of a dense
        # [V, D] scatter-add (the reference's SelectedRows); consumed by
        # the *_sparse optimizer ops. See ops/sparse_ops.py.
        attrs["is_sparse"] = True
    helper.append_op(
        "lookup_table",
        inputs={"W": w, "Ids": input},
        outputs={"Out": out},
        attrs=attrs,
    )
    return out


lookup_table = embedding


def conv2d(
    input: Variable,
    num_filters: int,
    filter_size: Union[int, Sequence[int]],
    stride: Union[int, Sequence[int]] = 1,
    padding: Union[int, Sequence[int]] = 0,
    dilation: Union[int, Sequence[int]] = 1,
    groups: int = 1,
    param_attr=None,
    bias_attr=None,
    use_cudnn: bool = True,
    act: Optional[str] = None,
    name: Optional[str] = None,
):
    """2D convolution, NCHW (reference: layers/nn.py conv2d)."""
    helper = LayerHelper("conv2d", name=name, bias_attr=bias_attr, act=act)
    c_in = input.shape[1]
    fs = list(filter_size) if isinstance(filter_size, (list, tuple)) else [filter_size] * 2
    groups = groups or 1
    w_shape = [num_filters, c_in // groups] + fs

    import math

    fan_in = (c_in // groups) * math.prod(fs)
    from paddle_tpu.initializer import NormalInitializer

    default_init = NormalInitializer(0.0, math.sqrt(2.0 / fan_in))
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=w_shape,
        dtype=input.dtype,
        default_initializer=default_init,
    )
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "conv2d" if groups == 1 or c_in != groups else "depthwise_conv2d",
        inputs={"Input": input, "Filter": w},
        outputs={"Output": out},
        attrs={
            "strides": [stride] * 2 if isinstance(stride, int) else list(stride),
            "paddings": [padding] * 2 if isinstance(padding, int) else list(padding),
            "dilations": [dilation] * 2 if isinstance(dilation, int) else list(dilation),
            "groups": groups,
        },
    )
    pre_act = _conv_bias(helper, out)
    return helper.append_activation(pre_act)


def _conv_bias(helper, out):
    bias_attr = helper.kwargs.get("bias_attr")
    if bias_attr is False:
        return out
    num_filters = out.shape[1] if out.shape else 1
    b = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[num_filters], dtype=out.dtype,
        is_bias=True,
    )
    if b is None:
        return out
    res = helper.create_variable_for_type_inference(dtype=out.dtype)
    helper.append_op(
        "elementwise_add",
        inputs={"X": out, "Y": b},
        outputs={"Out": res},
        attrs={"axis": 1},
    )
    return res


def conv2d_transpose(
    input, num_filters, output_size=None, filter_size=None, padding=0,
    stride=1, dilation=1, groups=1, param_attr=None, bias_attr=None,
    use_cudnn=True, act=None, name=None,
):
    helper = LayerHelper("conv2d_transpose", name=name, bias_attr=bias_attr, act=act)
    c_in = input.shape[1]
    fs = list(filter_size) if isinstance(filter_size, (list, tuple)) else [filter_size] * 2
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=[c_in, num_filters // (groups or 1)] + fs,
        dtype=input.dtype,
    )
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "conv2d_transpose",
        inputs={"Input": input, "Filter": w},
        outputs={"Output": out},
        attrs={
            "strides": [stride] * 2 if isinstance(stride, int) else list(stride),
            "paddings": [padding] * 2 if isinstance(padding, int) else list(padding),
            "dilations": [dilation] * 2 if isinstance(dilation, int) else list(dilation),
        },
    )
    pre_act = _conv_bias(helper, out)
    return helper.append_activation(pre_act)


def pool2d(
    input,
    pool_size=2,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    exclusive=True,
    name=None,
):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "pool2d",
        inputs={"X": input},
        outputs={"Out": out},
        attrs={
            "pooling_type": pool_type,
            "ksize": [pool_size] * 2 if isinstance(pool_size, int) else list(pool_size),
            "strides": [pool_stride] * 2 if isinstance(pool_stride, int) else list(pool_stride),
            "paddings": [pool_padding] * 2 if isinstance(pool_padding, int) else list(pool_padding),
            "global_pooling": global_pooling,
            "exclusive": exclusive,
        },
    )
    return out


def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    in_place=False,
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    do_model_average_for_mean_and_var=False,
    use_global_stats=False,
):
    """Batch normalization (reference: layers/nn.py batch_norm)."""
    helper = LayerHelper("batch_norm", name=name, act=act)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = input.dtype

    scale = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[c], dtype=dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[c], dtype=dtype, is_bias=True,
    )
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, initializer=ConstantInitializer(0.0),
                  trainable=False),
        shape=[c], dtype=dtype,
    )
    var = helper.create_parameter(
        ParamAttr(name=moving_variance_name, initializer=ConstantInitializer(1.0),
                  trainable=False),
        shape=[c], dtype=dtype,
    )
    out = helper.create_variable_for_type_inference(dtype=dtype)
    saved_mean = helper.create_variable_for_type_inference(dtype=dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype=dtype, stop_gradient=True)
    helper.append_op(
        "batch_norm",
        inputs={"X": input, "Scale": scale, "Bias": bias, "Mean": mean, "Variance": var},
        outputs={
            "Y": out,
            "MeanOut": mean,
            "VarianceOut": var,
            "SavedMean": saved_mean,
            "SavedVariance": saved_var,
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test or use_global_stats,
            "data_layout": data_layout,
        },
    )
    return helper.append_activation(out)


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("layer_norm", name=name, act=act)
    import math

    feat = math.prod(input.shape[begin_norm_axis:])
    inputs = {"X": input}
    if scale:
        s = helper.create_parameter(
            ParamAttr._to_attr(param_attr), shape=[feat], dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = s
    if shift:
        b = helper.create_parameter(
            ParamAttr._to_attr(bias_attr), shape=[feat], dtype=input.dtype,
            is_bias=True,
        )
        inputs["Bias"] = b
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    m = helper.create_variable_for_type_inference(dtype=input.dtype, stop_gradient=True)
    v = helper.create_variable_for_type_inference(dtype=input.dtype, stop_gradient=True)
    helper.append_op(
        "layer_norm",
        inputs=inputs,
        outputs={"Y": out, "Mean": m, "Variance": v},
        attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def rms_norm(input, epsilon=1e-5, param_attr=None, zero_centered=False,
             name=None):
    """Root-mean-square normalisation over the last axis with a learned
    gain and no bias (Zhang & Sennrich 2019): the pre-norm of the
    decoder-only language models (models/olmoe.py). ``zero_centered``:
    the gain is 1 + the parameter, which then starts at 0
    (models/qwen3_next.py)."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[input.shape[-1]],
        dtype=input.dtype, default_initializer=ConstantInitializer(
            0.0 if zero_centered else 1.0))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    attrs = {"epsilon": float(epsilon)}
    if zero_centered:
        attrs["zero_centered"] = True
    helper.append_op("rms_norm", inputs={"X": input, "Scale": scale},
                     outputs={"Y": out}, attrs=attrs)
    return out


def rotary_embedding(q, k, theta=10000.0, rotary_dim=None, name=None,
                     interleaved=False, layout="bhtd", scaling=None,
                     periods=1, norm_param_attrs=None, norm_epsilon=1e-5,
                     positions=None, mrope_section=None):
    """Rotary positions (rotate-half form) on q and k [b, h, t, dh] (k
    may have fewer heads); position p of the sequence is p, or, with
    ``periods`` = n, p mod t / n: the positions 0 .. t / n - 1 run n
    times over the row (a row of a noised and a clean copy of the same
    tokens: 2; the kernels read one run's tables n times).
    ``rotary_dim``: only the first rotary_dim features of a head turn,
    as a head of that width would, and the others pass.
    ``interleaved``: feature 2i pairs with 2i + 1 (not i with
    i + dh/2). ``layout`` "bthd": q and k come token-major
    [b, t, h, dh], as a projection leaves them (the op transposes as it
    rotates: one pass). Returns the rotated (q, k), head-major
    [b, h, t, dh] whatever the layout.

    ``scaling``: a yarn scaling (Peng et al. 2023, as HF's
    ``rope_type: "yarn"`` computes it), a dict of plain numbers:
    ``factor`` and ``original_max_position_embeddings`` (required),
    ``beta_fast`` (32), ``beta_slow`` (1), ``attention_factor`` (HF's
    default 0.1 ln(factor) + 1). The inverse frequencies of the features
    that turn (``rotary_dim`` of them, or the head) become f_j / factor
    * ramp_j + f_j * (1 - ramp_j) (``parallel/rope.inv_freq``: waves
    that make more than beta_fast rotations over the original length
    keep their frequency, those under beta_slow are interpolated, a
    linear ramp between), and cos and sin are multiplied by the
    attention factor. They become attributes of the op, which stays a
    function of its attributes. Which calls the ``rope.*`` kernels take
    (bf16, a TPU, no mesh): the whole head in rotate-half form at a
    width on the 128 lanes, and the first ``rotary_dim`` features of a
    head exactly 128 wide, each with or without a scaling (they read
    tables); a call that turns part of a wider head or pairs neighbours
    runs as XLA's ops (``parallel/rope.rope_tile`` decides,
    ``pt_rope_dispatch_total{impl, scaling}`` says which).

    ``norm_param_attrs`` (q's, k's): each head of q and of k is first
    RMS-normalised over its dh with a learned gain [dh] (QK-norm per
    head: ``rms_norm``'s arithmetic with ``norm_epsilon``, its
    parameters, created here in that order, 1 at the start), in the same
    op: where the kernels take the call the statistics, the gains and
    their gradients ride in the rotation's pass, elsewhere the op is
    rms_norm's lines in front of the rotation's.

    ``positions`` [n, t] (a fed variable, int or float, shared by the
    batch's rows) with ``mrope_section`` (n counts that sum to the
    rotated frequency pairs): the positions are the FEED's and pair i
    turns by the row its section gives it: multi-axis rotary (Qwen2-VL:
    [temporal, height, width] over sections [16, 24, 24] of a head of
    128); no ``mrope_section``: row 0 turns every pair. The tables are
    then device values, made in ``parallel/rope.cos_sin`` as the
    implicit ones are. Without ``positions`` the op is unchanged."""
    helper = LayerHelper("rotary_embedding", name=name)
    inputs = {"Q": q, "K": k}
    attrs = {"theta": float(theta)}
    if positions is not None:
        inputs["Positions"] = positions
        if mrope_section:
            attrs["mrope_section"] = [int(n) for n in mrope_section]
    if norm_param_attrs is not None:
        for slot, x, attr in zip(("QScale", "KScale"), (q, k),
                                 norm_param_attrs):
            inputs[slot] = helper.create_parameter(
                ParamAttr._to_attr(attr), shape=[x.shape[-1]], dtype=x.dtype,
                default_initializer=ConstantInitializer(1.0))
        attrs["norm_epsilon"] = float(norm_epsilon)
    q_out = helper.create_variable_for_type_inference(dtype=q.dtype)
    k_out = helper.create_variable_for_type_inference(dtype=k.dtype)
    if rotary_dim is not None and rotary_dim != q.shape[-1]:
        attrs["rotary_dim"] = int(rotary_dim)
    if interleaved:
        attrs["interleaved"] = True
    if layout != "bhtd":
        if layout != "bthd":
            raise ValueError(f"rotary_embedding: layout {layout!r} is "
                             "neither 'bhtd' nor 'bthd'")
        attrs["layout"] = layout
    if int(periods) != 1:
        attrs["periods"] = int(periods)
    if scaling:
        import math

        factor = float(scaling["factor"])
        attrs.update(
            yarn_factor=factor,
            yarn_original_length=float(
                scaling["original_max_position_embeddings"]),
            yarn_beta_fast=float(scaling.get("beta_fast") or 32.0),
            yarn_beta_slow=float(scaling.get("beta_slow") or 1.0),
            yarn_attention_factor=float(
                scaling.get("attention_factor")
                or 0.1 * math.log(factor) + 1.0))
    helper.append_op("rotary_embedding", inputs=inputs,
                     outputs={"QOut": q_out, "KOut": k_out}, attrs=attrs)
    return q_out, k_out


def scaled_dot_product_attention(q, k, v, scale, causal=True, window=None,
                                 name=None, block_diffusion=None,
                                 q_pe=None, k_pe=None, selected=None,
                                 live=None, with_lse=False):
    """softmax(scale q k^T) v of head-major q [b, h, t, dk], k
    [b, hk, t, dk] and v [b, hk, t, dv] -> [b, h, t, dv]: ONE op, which
    the flash kernels take on a TPU (``ops/attention_ops.py``). hk may
    divide h (grouped queries: query head i reads key/value head
    i // (h / hk), nothing is copied) and dv may differ from dk.
    ``causal``: the mask rides in the kernel, no bias tensor exists;
    ``window``: a query sees its last ``window`` positions only, itself
    among them. ``block_diffusion=B`` (instead of either: the mask is
    not causal): the row is two halves of t / 2, a noised copy and the
    clean copy of the same positions in blocks of B, under block
    diffusion's training mask: a noised block sees itself, both ways,
    and the clean blocks before it; the clean half is block-causal and
    sees no noised key (``parallel/flash_attention.bd_visible``). Every
    position is real and nothing is dropped: the
    packed decoders' call (``models/decoder.py``'s families).
    ``q_pe`` [b, h, t, r] and ``k_pe`` [b, hp, t, r], hp dividing h
    (both or neither): the queries and keys come in TWO parts, the
    scores are scale * (q k^T + q_pe k_pe^T) with query head i reading
    k_pe's head i // (h / hp): latent attention's rotary features, the
    keys' ONE head shared by all. The kernels read the parts where they
    lie; nobody builds a wide q or copies the shared head (the op does,
    itself, where no kernel takes the call).
    ``selected`` [b, t / 32, t] int32 with ``live`` (``dsa_select``'s
    pair, beside ``causal``): query p reads key s only where its bit of
    the selection is set, every head alike: a learned sparse attention's
    choice, a device value that gets no gradient. The kernels read it in
    blocks beside K and V and walk the causal triangle; a block the live
    table calls empty computes nothing and fetches nothing.
    ``with_lse``: return (out, lse), lse
    [b, h, t, 1] float32 the scores' logsumexp rows (detached; real on
    every path under a selection), as ``dsa_index_loss`` reads them.
    ``models/transformer.py`` appends the op itself, token-major with
    dropout and a padding bias. ``name`` names the layer's temporaries."""
    helper = LayerHelper(name or "scaled_dot_product_attention")
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    # logsumexp rows, consumed by the paired grad op (DCE'd at inference)
    lse = helper.create_variable_for_type_inference(dtype="float32")
    lse.stop_gradient = True
    if block_diffusion and window:
        raise ValueError("scaled_dot_product_attention: block_diffusion "
                         "takes no window")
    attrs = {"scale": float(scale), "dropout_prob": 0.0, "is_test": True,
             "layout": "bhtd",
             "causal": bool(causal) and not block_diffusion}
    if window:
        attrs["window"] = int(window)
    if block_diffusion:
        attrs["block_diffusion"] = int(block_diffusion)
    inputs = {"Q": q, "K": k, "V": v}
    if q_pe is not None or k_pe is not None:
        inputs.update(QPe=q_pe, KPe=k_pe)
    if selected is not None:
        inputs.update(Selected=selected, Live=live)
    helper.append_op("scaled_dot_product_attention", inputs=inputs,
                     outputs={"Out": out, "Lse": lse}, attrs=attrs)
    return (out, lse) if with_lse else out


def dsa_select(q_index, k_index, weights, topk, q_chunk=512, kv_chunk=512,
               name=None):
    """A lightning indexer's choice (DeepSeek Sparse Attention,
    ``ops/dsa_ops.py``): index queries ``q_index`` [b, hI, t, dI], the
    ONE index key head ``k_index`` [b, 1, t, dI] and the per-head
    weights ``weights`` [b, t, hI] -> ``(selected [b, t / 32, t] int32,
    a bit a pair, live [b, t / cq, t / ck] int32, index_lse [b, t]
    float32)``: each query's
    min(p + 1, ``topk``) keys s <= p of largest I[p, s] = c0 sum_j
    w[p, j] relu(qI[p, j] . kI[s]) (c0 = hI^-1/2 dI^-1/2, float32, ties
    to the lower s; ``topk`` None: every s <= p, the dense warm-up
    stage), the table of the blocks of ``q_chunk`` x ``kv_chunk`` that
    hold a selected pair, and the logsumexp of I over a query's
    selection. No gradient passes (a top-k has none):
    ``scaled_dot_product_attention(selected=, live=)`` reads the first
    two, ``dsa_index_loss`` the first and the last,
    ``dsa_selected_rows`` unpacks. The scores are made a tile at a time
    and the top-k is a bisection, not a sort."""
    from paddle_tpu.ops.dsa_ops import index_scale

    helper = LayerHelper("dsa_select", name=name)
    outs = [helper.create_variable_for_type_inference(dtype=d,
                                                      stop_gradient=True)
            for d in ("int32", "int32", "float32")]
    helper.append_op(
        "dsa_select", inputs={"QI": q_index, "KI": k_index, "W": weights},
        outputs={"Selected": outs[0], "Live": outs[1], "IndexLse": outs[2]},
        attrs={"scale": index_scale(q_index.shape[1], q_index.shape[3]),
               "topk": int(topk or 0), "q_chunk": int(q_chunk),
               "kv_chunk": int(kv_chunk)})
    return tuple(outs)


def dsa_selected_rows(selected, live, last=0, name=None):
    """``dsa_select``'s ``selected`` as a mask a pair, [b, t, t] int8
    (1: query p reads key s), or its ``last`` rows [b, last, t]: what a
    check or a test reads; the kernels read the bits."""
    helper = LayerHelper("dsa_selected_rows", name=name)
    out = helper.create_variable_for_type_inference(dtype="int8",
                                                    stop_gradient=True)
    helper.append_op("dsa_selected_rows",
                     inputs={"Selected": selected, "Live": live},
                     outputs={"Out": out}, attrs={"last": int(last)})
    return out


def dsa_index_loss(q_index, k_index, weights, q, k, lse, selected, index_lse,
                   attn_scale, q_chunk=512, kv_chunk=512, name=None):
    """The indexer's own loss, a float32 scalar (``ops/dsa_ops.py``): L_I =
    mean over the batch's positions p of KL(P[p, .] || softmax over the
    selection of I[p, .]), P the main attention's probabilities under
    the selection averaged over its heads, made again a tile at a time
    from its ``q`` [b, h, t, dh], ``k`` [b, hk, t, dh] (as the attention
    read them) and ``lse`` (``scaled_dot_product_attention(with_lse=
    True)``'s) and DETACHED: only ``q_index``, ``k_index`` and
    ``weights`` get a gradient, which the op makes in the same pass
    (through the relu and the per-head weights, by hand)."""
    from paddle_tpu.ops.dsa_ops import index_scale

    helper = LayerHelper("dsa_index_loss", name=name)
    loss = helper.create_variable_for_type_inference(dtype="float32")
    saved = [helper.create_variable_for_type_inference(dtype=x.dtype,
                                                       stop_gradient=True)
             for x in (q_index, k_index)]
    saved.append(helper.create_variable_for_type_inference(
        dtype="float32", stop_gradient=True))
    helper.append_op(
        "dsa_index_loss",
        inputs={"QI": q_index, "KI": k_index, "W": weights, "Q": q, "K": k,
                "Lse": lse, "Selected": selected, "IndexLse": index_lse},
        outputs={"Loss": loss, "DQI": saved[0], "DKI": saved[1],
                 "DW": saved[2]},
        attrs={"scale": index_scale(q_index.shape[1], q_index.shape[3]),
               "attn_scale": float(attn_scale), "q_chunk": int(q_chunk),
               "kv_chunk": int(kv_chunk)})
    return loss


def causal_conv1d(input, taps=4, act="silu", param_attr=None,
                  bias_attr=False, name=None):
    """Depthwise convolution over the sequence of ``input`` [b, t, c]
    that sees no later position, ``taps`` wide, then ``act``: "silu"
    (the short convolution in front of a linear attention or a selective
    scan) or None (no activation: the output is the taps' sum, plus the
    bias where there is one) (ops/linear_attention_ops.py). Parameter
    [c, taps];
    ``bias_attr`` (False: none, as Qwen3-Next's) adds a bias [c] in
    front of ``act``, as Mamba's."""
    helper = LayerHelper("causal_conv1d", name=name)
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[input.shape[-1], int(taps)],
        dtype=input.dtype)
    inputs = {"X": input, "W": w}
    if bias_attr is not False:
        inputs["Bias"] = helper.create_parameter(
            ParamAttr._to_attr(bias_attr), shape=[input.shape[-1]],
            dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("causal_conv1d", inputs=inputs,
                     outputs={"Y": out}, attrs={"act": act or ""})
    return out


def short_conv_gate(input, taps=3, param_attr=None, name=None):
    """LFM2's gated short convolution as one op: ``input`` [b, t, 3c] is
    the fused projection [B | C | u] of the token (thirds in that
    order), the result [b, t, c] is C * conv(B * u), the convolution
    depthwise and causal over ``taps`` positions with no bias and no
    activation (ops/linear_attention_ops.gated_short_conv: one kernel a
    pass on a TPU, the ranges read in place; its backward pass saves
    nothing but ``input``). Parameter [c, taps]."""
    helper = LayerHelper("gated_short_conv", name=name)
    if input.shape[-1] % 3:
        raise ValueError(f"short_conv_gate: {input.shape[-1]} channels are "
                         f"not three equal ranges")
    c = input.shape[-1] // 3
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[c, int(taps)],
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("gated_short_conv", inputs={"X": input, "W": w},
                     outputs={"Y": out})
    return out


def gdn_gates(b, a, a_log_attr=None, dt_bias_attr=None, name=None):
    """(beta, g) of a gated delta rule from two projections of the
    token, ``b`` and ``a`` [b, t, h]: beta = sigmoid(b), g = -exp(A_log)
    * softplus(a + dt_bias), float32 both. Parameters A_log and dt_bias
    [h] (defaults: log of uniform(0, 16), and 1). ``a`` [b, t, h, dk]
    (a decay a key feature, Kimi Delta Attention's): dt_bias [h, dk],
    A_log still [h], g [b, t, h, dk]."""
    from paddle_tpu.initializer import LogUniformInitializer

    helper = LayerHelper("gdn_gates", name=name)
    h = b.shape[-1]
    a_log = helper.create_parameter(
        ParamAttr._to_attr(a_log_attr), shape=[h], dtype="float32",
        default_initializer=LogUniformInitializer(1e-4, 16.0))
    dt_bias = helper.create_parameter(
        ParamAttr._to_attr(dt_bias_attr), shape=list(a.shape[2:]),
        dtype="float32", default_initializer=ConstantInitializer(1.0))
    beta = helper.create_variable_for_type_inference(dtype="float32")
    g = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        "gdn_gates",
        inputs={"B": b, "A": a, "ALog": a_log, "DtBias": dt_bias},
        outputs={"Beta": beta, "G": g})
    return beta, g


def gated_delta_rule(q, k, v, g, beta, chunk=64, impl="chunked",
                     epsilon=1e-6, name=None):
    """Gated delta-rule linear attention (Gated DeltaNet,
    arXiv:2412.06464): q, k [b, t, hk, dk] (normalised to unit length
    inside, q also divided by sqrt(dk)), v [b, t, hv, dv] with hv a
    multiple of hk (key head i serves value heads i * hv / hk ...), g
    [b, t, hv] the log of the state's decay and beta [b, t, hv] the
    write strength -> o [b, t, hv, dv]. Per value head a state S
    [dk, dv] from zero: S = exp(g_t) S; S += k_t (beta_t (v_t - S^T
    k_t))^T; o_t = S^T q_t. g [b, t, hv, dk] is a decay a key FEATURE
    (Kimi Delta Attention, arXiv:2510.26692): S = Diag(exp(g_t)) S; the
    rank of g decides, and the kernels are ``kda.rule.*``
    (``kda_tile``). ``impl``: "chunked" (the chunkwise form,
    ``chunk`` positions a step; a sequence the chunk does not divide is
    padded: as the ``gdn.rule.*`` Pallas kernels where
    ``parallel/gated_delta_rule.gdn_tile`` gives the call a tile (bf16
    operands, heads of 128, chunk 64, a TPU, no mesh), as XLA ops
    elsewhere; the dispatch counter says which) or "recurrent" (a step
    a position: the fallback a caller asks for)."""
    if impl not in ("chunked", "recurrent"):
        raise ValueError(f"gated_delta_rule: impl {impl!r}")
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_variable_for_type_inference(dtype=v.dtype)
    # the state each chunk starts from, kept for the backward pass
    states = helper.create_variable_for_type_inference(
        dtype=q.dtype, stop_gradient=True)
    helper.append_op(
        "gated_delta_rule",
        inputs={"Q": q, "K": k, "V": v, "G": g, "Beta": beta},
        outputs={"Out": out, "States": states},
        attrs={"chunk": int(chunk), "impl": impl,
               "epsilon": float(epsilon)})
    return out


def selective_scan(x, dt, b, c, z=None, state_size=16, chunk=64,
                   impl="chunked", a_log_attr=None, d_attr=None,
                   dt_bias_attr=None, name=None):
    """Mamba-1's selective scan (ops/selective_scan_ops.py): x, dt
    [b, t, e] (dt the pre-activation of the step size), b, c [b, t, n]
    with n = ``state_size``, optional gate z [b, t, e] -> out [b, t, e]:

        delta = softplus(dt + dt_bias);  s_t = exp(delta A) s_{t-1}
        + delta b_t x_t;  y_t = c_t . s_t + D x_t;  out = y * silu(z)

    with A = -exp(A_log). Parameters A_log [e, n], D [e] and dt_bias [e]
    (defaults: log(1 .. n) in every channel, 1 and 0), float32 all.
    ``impl``: "chunked" (a state saved every ``chunk`` positions: the
    ``ssm.scan.*`` Pallas kernels where
    ``parallel/selective_scan.ssm_tile`` gives the call a tile, XLA ops
    elsewhere; the dispatch counter says which) or "recurrent" (one scan
    over all positions: the fallback a caller asks for)."""
    from paddle_tpu.initializer import LogRangeInitializer

    if impl not in ("chunked", "recurrent"):
        raise ValueError(f"selective_scan: impl {impl!r}")
    helper = LayerHelper("selective_scan", name=name)
    e, n = x.shape[-1], int(state_size)
    a_log = helper.create_parameter(
        ParamAttr._to_attr(a_log_attr), shape=[e, n], dtype="float32",
        default_initializer=LogRangeInitializer())
    d = helper.create_parameter(
        ParamAttr._to_attr(d_attr), shape=[e], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    dt_bias = helper.create_parameter(
        ParamAttr._to_attr(dt_bias_attr), shape=[e], dtype="float32",
        default_initializer=ConstantInitializer(0.0))
    a = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op("exp", inputs={"X": a_log}, outputs={"Out": a})
    neg_a = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op("scale", inputs={"X": a}, outputs={"Out": neg_a},
                     attrs={"scale": -1.0})
    inputs = {"X": x, "Dt": dt, "A": neg_a, "B": b, "C": c, "D": d,
              "DtBias": dt_bias}
    if z is not None:
        inputs["Z"] = z
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    # the state each chunk starts from, kept for the backward pass
    states = helper.create_variable_for_type_inference(
        dtype="float32", stop_gradient=True)
    helper.append_op(
        "selective_scan", inputs=inputs,
        outputs={"Out": out, "States": states},
        attrs={"chunk": int(chunk), "impl": impl})
    return out


def mamba2_scan(x, dt, b, c, heads, groups=8, chunk=128, impl="chunked",
                a_log_attr=None, d_attr=None, dt_bias_attr=None, name=None):
    """Mamba-2's state-space scan (ops/mamba2_scan_ops.py): x [b, t,
    heads * p], dt [b, t, heads] (the pre-activation of the step size),
    b, c [b, t, groups * n], which the heads of a group share -> out
    [b, t, heads * p]. Per head a state S [p, n] from zero:

        dt = softplus(dt + dt_bias);  S_t = exp(-exp(A_log) dt_t) S_{t-1}
        + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t

    Parameters A_log, D and dt_bias [heads] (defaults: log(1 .. heads),
    1 and 0), float32 all. ``impl``: "chunked" (the chunkwise matmul
    form, ``chunk`` positions a step and a state saved for each: the
    ``mamba2.chunk.*`` Pallas kernels where
    ``parallel/mamba2_scan.mamba2_tile`` gives the call a tile, XLA ops
    elsewhere; the dispatch counter says which) or "recurrent" (one
    scan over all positions: the form a test asks for)."""
    from paddle_tpu.initializer import LogRangeInitializer

    if impl not in ("chunked", "recurrent"):
        raise ValueError(f"mamba2_scan: impl {impl!r}")
    heads, groups = int(heads), int(groups)
    if (dt.shape[-1] != heads or x.shape[-1] % heads or heads % groups
            or b.shape[-1] % groups or b.shape[-1] != c.shape[-1]):
        raise ValueError(
            f"mamba2_scan: x {x.shape}, dt {dt.shape}, b {b.shape}, c "
            f"{c.shape} with {heads} heads in {groups} groups")
    helper = LayerHelper("mamba2_scan", name=name)
    a_log = helper.create_parameter(
        ParamAttr._to_attr(a_log_attr), shape=[heads], dtype="float32",
        default_initializer=LogRangeInitializer())
    d = helper.create_parameter(
        ParamAttr._to_attr(d_attr), shape=[heads], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    dt_bias = helper.create_parameter(
        ParamAttr._to_attr(dt_bias_attr), shape=[heads], dtype="float32",
        default_initializer=ConstantInitializer(0.0))
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    # the state each chunk starts from, kept for the backward pass
    states = helper.create_variable_for_type_inference(
        dtype="float32", stop_gradient=True)
    helper.append_op(
        "mamba2_scan",
        inputs={"X": x, "Dt": dt, "ALog": a_log, "B": b, "C": c, "D": d,
                "DtBias": dt_bias},
        outputs={"Out": out, "States": states},
        attrs={"groups": groups, "chunk": int(chunk), "impl": impl})
    return out


def diff_attention_combine(o1, o2, lambda_init, head_dim, epsilon=1e-5,
                           lambda_attr=None, param_attr=None, name=None):
    """Differential attention's combination of the two softmax maps'
    outputs o1, o2 [.., dv] (ops/attention_ops.py): rms_norm(o1 - lambda
    o2) * gain * (1 - lambda_init), lambda = exp(lq1 . lk1) - exp(lq2 .
    lk2) + lambda_init. Parameters: four vectors [head_dim]
    (``lambda_attr``: a ParamAttr whose name is their prefix; normal(0,
    0.1)) and the gain [dv] (1)."""
    from paddle_tpu.initializer import NormalInitializer

    helper = LayerHelper("diff_attention_combine", name=name)
    base = ParamAttr._to_attr(lambda_attr)
    prefix = (base.name if base is not None and base.name
              else helper.name + ".lambda")
    vecs = {
        slot: helper.create_parameter(
            ParamAttr(name=f"{prefix}_{slot.lower()}",
                      initializer=NormalInitializer(0.0, 0.1)),
            shape=[int(head_dim)], dtype="float32")
        for slot in ("LQ1", "LK1", "LQ2", "LK2")}
    scale = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[o1.shape[-1]],
        dtype="float32", default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype=o1.dtype)
    helper.append_op(
        "diff_attention_combine",
        inputs={"O1": o1, "O2": o2, "Scale": scale, **vecs},
        outputs={"Out": out},
        attrs={"lambda_init": float(lambda_init), "epsilon": float(epsilon)})
    return out


def gated_rms_norm(input, gate, epsilon=1e-6, param_attr=None, name=None,
                   gate_first=False, group_size=None, gate_act="silu"):
    """rms_norm(input) * gain * silu(gate) over the last axis (a plain
    gain that starts at 1): the norm behind a gated delta rule.
    ``gate_first``: rms_norm(input * silu(gate)) * gain, the gate in
    front of the statistics (Mamba-2's); ``group_size``: the statistics
    over each group of that many features of the last axis, not over all
    of it (the gain stays one a feature); ``gate_act="sigmoid"``:
    sigmoid(gate) where silu(gate) stands (Kimi Delta Attention's)."""
    if gate_act not in ("silu", "sigmoid"):
        raise ValueError(f"gated_rms_norm: gate_act {gate_act!r}")
    helper = LayerHelper("gated_rms_norm", name=name)
    scale = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[input.shape[-1]],
        dtype=input.dtype, default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    attrs = {"epsilon": float(epsilon)}
    # (absent by default: the op's defaults, and the program's text
    # today's)
    if gate_first:
        attrs["gate_first"] = True
    if gate_act != "silu":
        attrs["gate_act"] = gate_act
    if group_size is not None and int(group_size) != input.shape[-1]:
        if input.shape[-1] % int(group_size):
            raise ValueError(f"gated_rms_norm: groups of {group_size} in "
                             f"{input.shape[-1]} features")
        attrs["group_size"] = int(group_size)
    helper.append_op(
        "gated_rms_norm", inputs={"X": input, "Z": gate, "Scale": scale},
        outputs={"Y": out}, attrs=attrs)
    return out


def hc_mix(x, n, epsilon=1e-6, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0),
           phi_attr=None, bias_attr=None, alpha_attr=None, name=None):
    """The mix of one sublayer's manifold-constrained hyper-connection
    (arXiv:2512.24880) from the ``n`` streams ``x`` [b, t, n d] (side by
    side along the features, stream i at i d .. (i + 1) d):
    ``(h_pre [b, n, t], h_post [b, n, t], h_res [b, n, n, t])``, float32
    and token-minor, by ``ops/hc_ops.py``'s equations (one norm statistic
    and a [n d] -> n^2 + 2n projection a token, two sigmoids, ``iters``
    Sinkhorn iterations on exp of the clamped n x n part). Parameters,
    created here in this order: ``Phi`` [n d, n^2 + 2n] (normal(0, 0.02)
    unless the attribute says), ``Bias`` [n^2 + 2n] (the start at which
    the layer is a plain pre-norm residual over the streams' mean:
    logit(1 / n) for H_pre, 0 for H_post = 1, 0 on and -8 off H_res's
    diagonal) and ``Alpha`` [3] (0.01 each: the gates that open the
    token's part of pre, post and res)."""
    import math

    import numpy as np

    from paddle_tpu.initializer import NormalInitializer, NumpyArrayInitializer

    helper = LayerHelper("hc_mix", name=name)
    n = int(n)
    k = n * n + 2 * n
    phi = helper.create_parameter(
        ParamAttr._to_attr(phi_attr), shape=[int(x.shape[-1]), k],
        dtype="float32",
        default_initializer=NormalInitializer(0.0, 0.02))
    start = np.concatenate([
        np.full(n, math.log(1.0 / n / (1.0 - 1.0 / n)) if n > 1 else 30.0),
        np.zeros(n), (-8.0 * (1.0 - np.eye(n))).reshape(-1)])
    bias = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[k], dtype="float32",
        default_initializer=NumpyArrayInitializer(start.astype("float32")))
    alpha = helper.create_parameter(
        ParamAttr._to_attr(alpha_attr), shape=[3], dtype="float32",
        default_initializer=ConstantInitializer(0.01))
    outs = [helper.create_variable_for_type_inference(dtype="float32")
            for _ in range(3)]
    helper.append_op(
        "hc_mix", inputs={"X": x, "Phi": phi, "Bias": bias, "Alpha": alpha},
        outputs={"HPre": outs[0], "HPost": outs[1], "HRes": outs[2]},
        attrs={"n": n, "epsilon": float(epsilon), "iters": int(iters),
               "hc_eps": float(hc_eps), "clamp_min": float(clamp[0]),
               "clamp_max": float(clamp[1])})
    return tuple(outs)


def hc_pre(x, h_pre, name=None):
    """A sublayer's input from the streams x [b, t, n d] (n: h_pre's
    second dim): sum_i h_pre[:, i] x_i, [b, t, d] in x's dtype (float32
    sums, rounded once)."""
    helper = LayerHelper("hc_pre", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("hc_pre", inputs={"X": x, "HPre": h_pre},
                     outputs={"Out": out}, attrs={"n": int(h_pre.shape[1])})
    return out


def hc_post(x, y, h_res, h_post, name=None):
    """The streams behind a sublayer whose output is ``y`` [b, t, d]:
    stream j is sum_i h_res[:, j, i] x_i + h_post[:, j] y, [b, t, n d]
    in x's dtype (float32 sums, rounded once)."""
    helper = LayerHelper("hc_post", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "hc_post", inputs={"X": x, "Y": y, "HRes": h_res, "HPost": h_post},
        outputs={"Out": out}, attrs={"n": int(h_post.shape[1])})
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    """Group normalization (reference: layers/nn.py group_norm,
    operators/group_norm_op.cc)."""
    if data_layout != "NCHW":
        raise ValueError("group_norm supports NCHW layout")
    helper = LayerHelper("group_norm", name=name, act=act)
    c = input.shape[1]
    scale = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[c], dtype=input.dtype,
        is_bias=True,
    )
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    mean = helper.create_variable_for_type_inference(
        dtype=input.dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(
        dtype=input.dtype, stop_gradient=True)
    helper.append_op(
        "group_norm",
        inputs={"X": input, "Scale": scale, "Bias": bias},
        outputs={"Y": out, "Mean": mean, "Variance": var},
        attrs={"groups": groups, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def dropout(
    x,
    dropout_prob,
    is_test=False,
    seed=None,
    name=None,
    dropout_implementation="downgrade_in_infer",
):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype="uint8", stop_gradient=True)
    helper.append_op(
        "dropout",
        inputs={"X": x},
        outputs={"Out": out, "Mask": mask},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


# --- activations ---


def _make_act(name):
    def _act(x, **kwargs):
        attrs = {k: v for k, v in kwargs.items() if k != "name"}
        return _single_op(name, x, attrs=attrs, name=kwargs.get("name"))

    _act.__name__ = name
    return _act


relu = _make_act("relu")
sigmoid = _make_act("sigmoid")
tanh = _make_act("tanh")
sqrt = _make_act("sqrt")
exp = _make_act("exp")
log = _make_act("log")
abs = _make_act("abs")
square = _make_act("square")
softplus = _make_act("softplus")
softsign = _make_act("softsign")
relu6 = _make_act("relu6")
swish = _make_act("swish")
silu = _make_act("silu")
hard_swish = _make_act("hard_swish")
hard_sigmoid = _make_act("hard_sigmoid")
elu = _make_act("elu")


def gelu(x, approximate=False, name=None):
    return _single_op("gelu", x, attrs={"approximate": approximate}, name=name)


def leaky_relu(x, alpha=0.02, name=None):
    return _single_op("leaky_relu", x, attrs={"alpha": alpha}, name=name)


def pow(x, factor=1.0, name=None):
    return _single_op("pow", x, attrs={"factor": factor}, name=name)


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        shape = [1]
    elif mode == "channel":
        shape = [x.shape[1]]
    else:
        shape = [int(__import__("math").prod(x.shape[1:]))]
    alpha = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "prelu",
        inputs={"X": x, "Alpha": alpha},
        outputs={"Out": out},
        attrs={"mode": mode},
    )
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "maxout", inputs={"X": x}, outputs={"Out": out}, attrs={"groups": groups}
    )
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    return _single_op("softmax", input, attrs={"axis": axis}, name=name)


def log_softmax(input, axis=-1, name=None):
    return _single_op("log_softmax", input, attrs={"axis": axis}, name=name)


# --- losses ---


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "cross_entropy",
        inputs={"X": input, "Label": label},
        outputs={"Y": out},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(
    logits, label, soft_label=False, ignore_index=-100,
    numeric_stable_mode=True, return_softmax=False,
):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(dtype=logits.dtype)
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    helper.append_op(
        "softmax_with_cross_entropy",
        inputs={"Logits": logits, "Label": label},
        outputs={"Softmax": softmax_out, "Loss": loss},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def linear_cross_entropy(x, size, label, ignore_index=-100, param_attr=None,
                         name=None):
    """The loss of ``softmax_with_cross_entropy(fc(x, size, bias_attr=
    False, num_flatten_dims=x's rank - 1), label)`` on hard labels, [..., 1]
    float32 and 0 on a row whose label is ``ignore_index`` (any integer),
    as ONE op that projects the rows that count alone, in chunks
    (ops/nn_ops.py linear_cross_entropy): for a head most of whose rows
    carry no label. The [d, size] parameter is fc's (same initialiser,
    same naming)."""
    helper = LayerHelper("linear_cross_entropy", name=name)
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[x.shape[-1], size],
        dtype=x.dtype)
    loss = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        "linear_cross_entropy",
        inputs={"X": x, "W": w, "Label": label},
        outputs={"Loss": loss},
        attrs={"ignore_index": ignore_index},
    )
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "sigmoid_cross_entropy_with_logits",
        inputs={"X": x, "Label": label},
        outputs={"Out": out},
        attrs={"ignore_index": ignore_index},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "square_error_cost",
        inputs={"X": input, "Label": label},
        outputs={"Out": out},
    )
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    res = helper.create_variable_for_type_inference(dtype=input.dtype, stop_gradient=True)
    helper.append_op(
        "huber_loss",
        inputs={"X": input, "Y": label},
        outputs={"Out": out, "Residual": res},
        attrs={"delta": delta},
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    diff = helper.create_variable_for_type_inference(dtype=x.dtype, stop_gradient=True)
    inputs = {"X": x, "Y": y}
    if inside_weight is not None:
        inputs["InsideWeight"] = inside_weight
    if outside_weight is not None:
        inputs["OutsideWeight"] = outside_weight
    helper.append_op(
        "smooth_l1_loss",
        inputs=inputs,
        outputs={"Out": out, "Diff": diff},
        attrs={"sigma": sigma or 1.0},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    inputs = {"X": label}
    if prior_dist is not None:
        inputs["PriorDist"] = prior_dist
    helper.append_op(
        "label_smooth", inputs=inputs, outputs={"Out": out},
        attrs={"epsilon": float(epsilon)},
    )
    return out


# --- math wrappers ---


def mean(x, name=None):
    return _single_op("mean", x, name=name)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "mul",
        inputs={"X": x, "Y": y},
        outputs={"Out": out},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "matmul",
        inputs={"X": x, "Y": y},
        outputs={"Out": out},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": float(alpha)},
    )
    return out


def elementwise_op(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name, act=act)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        op_type, inputs={"X": x, "Y": y}, outputs={"Out": out}, attrs={"axis": axis}
    )
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_div", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_pow", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_min", x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_mod", x, y, axis, act, name)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return elementwise_op("elementwise_floordiv", x, y, axis, act, name)


def _reduce(op_type, input, dim, keep_dim, name):
    attrs = {"keep_dim": keep_dim}
    if dim is None:
        attrs["reduce_all"] = True
    else:
        attrs["dim"] = [dim] if isinstance(dim, int) else list(dim)
    return _single_op(op_type, input, attrs=attrs, name=name)


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name, act=act)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "scale",
        inputs={"X": x},
        outputs={"Out": out},
        attrs={"scale": float(scale), "bias": float(bias),
               "bias_after_scale": bias_after_scale},
    )
    return helper.append_activation(out)


def cast(x, dtype):
    from paddle_tpu.framework import convert_np_dtype_to_dtype_

    dtype = convert_np_dtype_to_dtype_(dtype)
    return _single_op("cast", x, attrs={"out_dtype": dtype}, dtype=dtype)


def clip(x, min, max, name=None):
    return _single_op("clip", x, attrs={"min": float(min), "max": float(max)}, name=name)


def clip_by_norm(x, max_norm, name=None):
    return _single_op("clip_by_norm", x, attrs={"max_norm": float(max_norm)}, name=name)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    sq = square(x)
    ssum = reduce_sum(sq, dim=axis, keep_dim=True)
    norm = sqrt(elementwise_max(ssum, fill_constant_like(ssum, epsilon)))
    return elementwise_div(x, norm)


def fill_constant_like(x, value):
    helper = LayerHelper("fill_any_like")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "fill_any_like", inputs={"X": x}, outputs={"Out": out},
        attrs={"value": float(value)},
    )
    return out


# --- metrics / indexing ---


def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    topk_out, topk_indices = topk(input, k)
    acc = helper.create_variable_for_type_inference(dtype="float32", stop_gradient=True)
    correct = correct or helper.create_variable_for_type_inference(
        dtype="int32", stop_gradient=True)
    total = total or helper.create_variable_for_type_inference(
        dtype="int32", stop_gradient=True)
    helper.append_op(
        "accuracy",
        inputs={"Out": topk_out, "Indices": topk_indices, "Label": label},
        outputs={"Accuracy": acc, "Correct": correct, "Total": total},
    )
    return acc


def topk(input, k, name=None):
    """(the ``k`` largest values along the last axis, their indices):
    ``lax.top_k`` over the whole tensor, which on a TPU SORTS every row
    (a bitonic network over the row's length, whatever k): the price of
    a router's 8 of 128, not of 2048 of 16,384 positions a query. For a
    top-k over POSITIONS that an attention reads as a mask, see
    ``dsa_select`` (a threshold by bisection a tile of queries, no
    sort, no indices)."""
    helper = LayerHelper("top_k", name=name)
    vals = helper.create_variable_for_type_inference(dtype=input.dtype)
    idx = helper.create_variable_for_type_inference(dtype="int64", stop_gradient=True)
    helper.append_op(
        "top_k", inputs={"X": input}, outputs={"Out": vals, "Indices": idx},
        attrs={"k": k},
    )
    return vals, idx


def one_hot(input, depth, dtype="float32"):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference(dtype=dtype, stop_gradient=True)
    helper.append_op(
        "one_hot", inputs={"X": input}, outputs={"Out": out},
        attrs={"depth": depth, "dtype": dtype},
    )
    return out


def argmax(x, axis=0, name=None):
    return _single_op("arg_max", x, attrs={"axis": axis}, dtype="int64",
                      stop_gradient=True, name=name)


def argmin(x, axis=0, name=None):
    return _single_op("arg_min", x, attrs={"axis": axis}, dtype="int64",
                      stop_gradient=True, name=name)


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou")
    out = helper.create_variable_for_type_inference(dtype="float32", stop_gradient=True)
    helper.append_op(
        "mean_iou",
        inputs={"Predictions": input, "Labels": label},
        outputs={"OutMeanIou": out},
        attrs={"num_classes": num_classes},
    )
    return out


def _compare(op_type, x, y, cond=None):
    helper = LayerHelper(op_type)
    out = cond or helper.create_variable_for_type_inference(
        dtype="bool", stop_gradient=True)
    helper.append_op(op_type, inputs={"X": x, "Y": y}, outputs={"Out": out})
    return out


def equal(x, y, cond=None):
    return _compare("equal", x, y, cond)


def less_than(x, y, cond=None, force_cpu=None):
    return _compare("less_than", x, y, cond)


def greater_than(x, y, cond=None):
    return _compare("greater_than", x, y, cond)


def logical_and(x, y, out=None, name=None):
    return _compare("logical_and", x, y, out)


def logical_or(x, y, out=None, name=None):
    return _compare("logical_or", x, y, out)


def logical_not(x, out=None, name=None):
    helper = LayerHelper("logical_not")
    out = out or helper.create_variable_for_type_inference(
        dtype="bool", stop_gradient=True)
    helper.append_op("logical_not", inputs={"X": x}, outputs={"Out": out})
    return out


def where(condition, x, y, name=None):
    helper = LayerHelper("where", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "where", inputs={"Condition": condition, "X": x, "Y": y},
        outputs={"Out": out},
    )
    return out


def cumsum(x, axis=-1, exclusive=False, reverse=False, name=None):
    return _single_op(
        "cumsum", x,
        attrs={"axis": axis, "exclusive": exclusive, "reverse": reverse},
        name=name,
    )


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    out = x if in_place else helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "increment", inputs={"X": x}, outputs={"Out": out}, attrs={"step": float(value)}
    )
    return out


# --- shape manipulation ---


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name, act=act)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        "reshape2", inputs={"X": x}, outputs={"Out": out},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    return _single_op("transpose2", x, attrs={"axis": list(perm)}, name=name)


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "axis": dim}
    else:
        n = len(num_or_sections)
        attrs = {"sections": list(num_or_sections), "axis": dim}
    outs = [helper.create_variable_for_type_inference(dtype=input.dtype)
            for _ in range(n)]
    helper.append_op("split", inputs={"X": input}, outputs={"Out": outs}, attrs=attrs)
    return outs


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", name=name)
    out = helper.create_variable_for_type_inference(dtype=input[0].dtype)
    helper.append_op("concat", inputs={"X": list(input)}, outputs={"Out": out},
                     attrs={"axis": axis})
    return out


def sums(input, out=None):
    helper = LayerHelper("sum")
    out = out or helper.create_variable_for_type_inference(dtype=input[0].dtype)
    helper.append_op("sum", inputs={"X": list(input)}, outputs={"Out": out})
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(dtype=x[0].dtype)
    helper.append_op("stack", inputs={"X": list(x)}, outputs={"Out": out},
                     attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    num = num or x.shape[axis]
    outs = [helper.create_variable_for_type_inference(dtype=x.dtype)
            for _ in range(num)]
    helper.append_op("unstack", inputs={"X": x}, outputs={"Y": outs},
                     attrs={"axis": axis})
    return outs


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("squeeze2", inputs={"X": input}, outputs={"Out": out},
                     attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("unsqueeze2", inputs={"X": input}, outputs={"Out": out},
                     attrs={"axes": list(axes)})
    return out


def expand(x, expand_times, name=None):
    return _single_op("expand", x, attrs={"expand_times": list(expand_times)}, name=name)


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("expand_as", inputs={"X": x, "Y": target_tensor},
                     outputs={"Out": out})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("flatten2", inputs={"X": x}, outputs={"Out": out},
                     attrs={"axis": axis})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    return _single_op("pad", x, attrs={"paddings": list(paddings),
                                       "pad_value": float(pad_value)}, name=name)


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("gather", inputs={"X": input, "Index": index},
                     outputs={"Out": out})
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "scatter", inputs={"X": input, "Ids": index, "Updates": updates},
        outputs={"Out": out}, attrs={"overwrite": overwrite},
    )
    return out


# --- sequence (padded/masked; see ops/sequence_ops.py) ---


def sequence_pool(input, pool_type, length=None):
    helper = LayerHelper("sequence_pool")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"X": input}
    if length is None and getattr(input, "mask_name", None):
        length = input.block.var(input.mask_name)
    if length is not None:
        inputs["Length"] = length
    helper.append_op("sequence_pool", inputs=inputs, outputs={"Out": out},
                     attrs={"pooltype": pool_type.upper()})
    return out


def sequence_softmax(input, length=None, use_cudnn=False, name=None):
    helper = LayerHelper("sequence_softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"X": input}
    if length is not None:
        inputs["Length"] = length
    helper.append_op("sequence_softmax", inputs=inputs, outputs={"Out": out})
    return out


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    helper = LayerHelper("sequence_mask", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype, stop_gradient=True)
    helper.append_op(
        "sequence_mask", inputs={"X": x}, outputs={"Y": out},
        attrs={"maxlen": maxlen if maxlen is not None else -1, "out_dtype": dtype},
    )
    return out


def sequence_reverse(x, length=None, name=None):
    helper = LayerHelper("sequence_reverse", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": x}
    if length is not None:
        inputs["Length"] = length
    helper.append_op("sequence_reverse", inputs=inputs, outputs={"Y": out})
    return out


def sequence_expand(x, y, ref_level=-1, name=None):
    helper = LayerHelper("sequence_expand", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("sequence_expand", inputs={"X": x, "Y": y},
                     outputs={"Out": out})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    fs = [filter_size] * 2 if isinstance(filter_size, int) else list(filter_size)
    st = [stride] * 2 if isinstance(stride, int) else list(stride)
    helper.append_op(
        "im2sequence", inputs={"X": input}, outputs={"Out": out},
        attrs={"kernels": fs, "strides": st},
    )
    return out


def linear_chain_crf(input, label, param_attr=None, length=None):
    """CRF loss layer (reference: layers/nn.py linear_chain_crf).
    ``input`` [b, t, c] emissions, ``label`` [b, t]; creates the [c+2, c]
    transition parameter. Returns the per-sequence NEGATIVE
    log-likelihood [b, 1] (reference kernel semantics: minimize
    ``mean(...)`` directly)."""
    helper = LayerHelper("linear_chain_crf")
    c = input.shape[-1]
    trans = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[c + 2, c], dtype=input.dtype,
    )
    ll = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"Emission": input, "Transition": trans, "Label": label}
    if length is not None:
        inputs["Length"] = length
    helper.append_op(
        "linear_chain_crf", inputs=inputs, outputs={"LogLikelihood": ll}
    )
    return ll


def crf_decoding(input, param_attr=None, label=None, length=None):
    """Viterbi decode with a (shared, by ParamAttr name) transition
    parameter (reference: layers/nn.py crf_decoding). With ``label``, the
    output switches to the reference's per-position correctness mask
    (1 where the Viterbi path agrees with the label) instead of tag ids."""
    helper = LayerHelper("crf_decoding")
    c = input.shape[-1]
    trans = helper.create_parameter(
        ParamAttr._to_attr(param_attr), shape=[c + 2, c], dtype=input.dtype,
    )
    out = helper.create_variable_for_type_inference(
        dtype="int64", stop_gradient=True)
    inputs = {"Emission": input, "Transition": trans}
    if length is not None:
        inputs["Length"] = length
    helper.append_op(
        "crf_decoding", inputs=inputs, outputs={"ViterbiPath": out}
    )
    if label is None:
        return out
    return cast(equal(out, label), "int64")


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    """CTC loss (reference: layers/nn.py warpctc). ``input`` [b, t, c]
    unnormalized logits (batch-major; the reference's time-major LoD
    convention becomes padded + length vectors)."""
    helper = LayerHelper("warpctc")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"Logits": input, "Label": label}
    if input_length is not None:
        inputs["LogitsLength"] = input_length
    if label_length is not None:
        inputs["LabelLength"] = label_length
    helper.append_op(
        "warpctc", inputs=inputs, outputs={"Loss": out},
        attrs={"blank": blank, "norm_by_times": norm_by_times},
    )
    return out


def edit_distance(input, label, normalized=True, input_length=None,
                  label_length=None):
    """Levenshtein distance per row (reference: layers/nn.py
    edit_distance). Returns (distance [b, 1], seq_num [1])."""
    helper = LayerHelper("edit_distance")
    out = helper.create_variable_for_type_inference(
        dtype="float32", stop_gradient=True)
    num = helper.create_variable_for_type_inference(
        dtype="int64", stop_gradient=True)
    inputs = {"Hyps": input, "Refs": label}
    if input_length is not None:
        inputs["HypsLength"] = input_length
    if label_length is not None:
        inputs["RefsLength"] = label_length
    helper.append_op(
        "edit_distance", inputs=inputs,
        outputs={"Out": out, "SequenceNum": num},
        attrs={"normalized": normalized},
    )
    return out, num


def bilinear_tensor_product(x, y, size, act=None, param_attr=None,
                            bias_attr=None, name=None):
    """out_k = x^T W_k y (reference: layers/nn.py bilinear_tensor_product)."""
    helper = LayerHelper("bilinear_tensor_product", name=name, act=act)
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=[size, x.shape[-1], y.shape[-1]], dtype=x.dtype,
    )
    b = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[size], dtype=x.dtype,
        is_bias=True,
    )
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": x, "Y": y, "Weight": w}
    if b is not None:
        inputs["Bias"] = b
    helper.append_op(
        "bilinear_tensor_product", inputs=inputs, outputs={"Out": out}
    )
    return helper.append_activation(out)


def nce(input, label, num_total_classes, num_neg_samples=10,
        param_attr=None, bias_attr=None, name=None):
    """Noise-contrastive estimation (reference: layers/nn.py nce).
    Returns per-example cost [b, 1]; the weight table is [C, D]."""
    helper = LayerHelper("nce", name=name)
    d = input.shape[-1]
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=[num_total_classes, d], dtype=input.dtype,
    )
    b = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[num_total_classes],
        dtype=input.dtype, is_bias=True,
    )
    cost = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"Input": input, "Label": label, "Weight": w}
    if b is not None:
        inputs["Bias"] = b
    helper.append_op(
        "nce", inputs=inputs, outputs={"Cost": cost},
        attrs={"num_neg_samples": num_neg_samples},
    )
    return cost


def switch_moe(input, num_experts, d_ff=None, capacity_factor=2.0,
               act="relu", param_attr=None, name=None):
    """Switch-style top-1 Mixture-of-Experts FFN (net-new vs the
    reference; SURVEY.md section 2.3 "EP, MoE"). Returns
    ``(out, aux_loss)``: out has the input's shape; add a multiple of
    ``aux_loss`` (Switch uses ~0.01) to the training loss for load
    balancing.

    Under ``CompiledProgram.with_strategy`` with a strategy declaring
    ``expert_axis`` (mesh axis of size ``num_experts``), experts shard
    one-per-rank and tokens travel over ICI all_to_all; otherwise the
    identical fixed-capacity math runs on one device. Parameter naming
    matches ``parallel.strategy.moe_rules``: ``{name}_experts.{w1,...}``
    stacked [E, ...] weights, ``{name}_gate.w`` router.
    """
    from paddle_tpu.initializer import NormalInitializer

    helper = LayerHelper("switch_moe", name=name)
    d = input.shape[-1]
    d_ff = d_ff or 4 * d

    def param(suffix, shape, is_bias=False):
        base = ParamAttr._to_attr(param_attr) or ParamAttr()
        # Keep the user's attr fields; only the name is forced (the
        # _experts./_gate. naming is the moe_rules sharding contract).
        attr = ParamAttr(
            name=unique_name.generate(f"{helper.name}{suffix}"),
            initializer=base.initializer,
            learning_rate=base.learning_rate,
            regularizer=base.regularizer,
            trainable=base.trainable,
        )
        init = (ConstantInitializer(0.0) if is_bias
                else NormalInitializer(0.0, 0.02))
        return helper.create_parameter(
            attr, shape=shape, dtype=input.dtype, is_bias=is_bias,
            default_initializer=init,
        )

    gate_w = param("_gate.w", [d, num_experts])
    w1 = param("_experts.w1", [num_experts, d, d_ff])
    b1 = param("_experts.b1", [num_experts, d_ff], is_bias=True)
    w2 = param("_experts.w2", [num_experts, d_ff, d])
    b2 = param("_experts.b2", [num_experts, d], is_bias=True)

    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    aux = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        "switch_moe",
        inputs={"X": input, "GateW": gate_w, "W1": w1, "B1": b1,
                "W2": w2, "B2": b2},
        outputs={"Out": out, "AuxLoss": aux},
        attrs={"capacity_factor": float(capacity_factor), "act": act},
    )
    return out, aux


def topk_moe(input, num_experts, top_k, d_ff, norm_topk_prob=False,
             param_attr=None, name=None, held=None, shared_d_ff=None,
             shared_gate=True, score="softmax", routed_scale=1.0,
             select_bias=False, bias_update_rate=0.001, act="silu",
             router_input=None, gated=True, shared_act="silu",
             shared_gated=True):
    """Dropless top-k Mixture-of-Experts with SwiGLU experts (OLMoE,
    arXiv:2409.02060): ``input`` [.., d] tokens -> ``(out, lb_loss,
    z_loss, expert_rows, top_i)``. out, in input's shape, is the sum
    over the token's ``top_k`` experts of p_e * (silu(x WGate[e]) * (x WUp[e])) WDown[e], p the
    float32 softmax over all ``num_experts`` (renormalised over the k
    only if ``norm_topk_prob``); every chosen pair is computed, no
    capacity. ``lb_loss`` is the load-balancing loss, ``z_loss`` the
    router z-loss (add multiples of both to the training loss),
    ``expert_rows`` [E] int32 the rows each expert got, ``top_i`` [n, k]
    int32 the experts each token chose.

    ``held=(first, count)``: the layer holds only experts first ..
    first + count - 1 of the ``num_experts`` its router scores: one
    chip's share of an expert-parallel layer, with nothing standing in
    for the other chips. The router keeps all its outputs and its
    ``top_k`` a token; ``out`` is the held experts' part of the sum (the
    pairs on experts held elsewhere add nothing; the shares of all
    chips, summed, are the whole layer), the expert parameters are
    [count, ...] and ``expert_rows`` [count]. The row buffer keeps a row
    for every (token, slot) pair, n * top_k of them, so that no routing,
    all pairs on held experts included, drops a token; only the held
    pairs' rows are multiplied.

    ``shared_d_ff``: a shared SwiGLU expert of that width that every
    token takes, behind a sigmoid gate of its own (Qwen3-Next):
    out += sigmoid(x w_s) * (silu(x Wg) * (x Wu)) Wd;
    ``shared_gate=False``: without the gate (DeepSeek-V3), and without
    its parameter.

    ``score="sigmoid"`` (DeepSeek-V3, arXiv:2412.19437 2.1.2): the
    scores are sigmoid(x Wr), a pair's weight its score (renormalised
    over the k if ``norm_topk_prob``) times ``routed_scale``, and
    ``lb_loss`` the sequence-wise balance loss (ops/moe_ops.
    _sigmoid_router). ``select_bias``: a float32 ``{name}_router.bias``
    [E], zero at the start, is added to the scores for the CHOICE of
    the k only. It takes no gradient; the optimizer appends its update
    to the training step (op ``moe_bias_update``, role opt, under this
    layer's ``router`` scope): b_e += ``bias_update_rate`` *
    sign(mean(count) - count_e) from this step's choices. A program
    with no optimizer (an eval clone) never moves it.

    ``act="relu"``: ReGLU experts, (relu(x WGate[e]) * (x WUp[e]))
    WDown[e] (SmallThinker); the shared expert stays SwiGLU.
    ``gated=False``: the experts are not gated units but act(x
    WUp[e]) WDown[e], two matrices an expert and no ``{name}_gate.w``,
    with ``act`` "relu2" (relu squared: Nemotron-H) or "relu".
    ``shared_act`` / ``shared_gated``: the same two choices for the
    shared expert ("silu", "relu" or "relu2"; ``shared_gated=False``: no
    ``_shared_gate.w``; not to be confused with ``shared_gate``, the
    sigmoid mix in front of the shared expert's output).
    ``router_input`` (input's shape): the router scores THAT tensor
    (SmallThinker: the attention's normalised input) while dispatch and
    the experts work on ``input``; the router's gradient flows into it.

    Four ops (ops/moe_ops.py), each under a name scope of its own:
    router, dispatch, experts, combine; the shared expert's ops under a
    fifth, shared. Parameters: ``{name}_router.w`` [d, E],
    ``{name}_gate.w`` / ``{name}_up.w`` [E or count, d, d_ff],
    ``{name}_down.w`` [E or count, d_ff, d]; ``{name}_shared_gate.w`` /
    ``_shared_up.w`` [d, shared_d_ff], ``_shared_down.w`` [shared_d_ff,
    d], ``_shared_mix.w`` [d, 1]."""
    from paddle_tpu.framework import OP_NAMESCOPE_ATTR, name_scope
    from paddle_tpu.initializer import ConstantInitializer, NormalInitializer

    helper = LayerHelper("topk_moe", name=name)
    d = input.shape[-1]
    base = ParamAttr._to_attr(param_attr) or ParamAttr()
    n_held, held_attrs = num_experts, {}
    if held is not None:
        first, n_held = (int(v) for v in held)
        if not (0 <= first and 0 < n_held and first + n_held <= num_experts):
            raise ValueError(f"topk_moe: held={held} of {num_experts}")
        held_attrs = {"num_experts": int(num_experts), "held_first": first,
                      "held_count": n_held}
    init = base.initializer or NormalInitializer(0.0, 0.02)

    def attr(suffix):
        return ParamAttr(
            name=f"{helper.name}{suffix}", initializer=init,
            learning_rate=base.learning_rate, regularizer=base.regularizer,
            trainable=base.trainable)

    def param(suffix, shape):
        return helper.create_parameter(attr(suffix), shape=shape,
                                       dtype=input.dtype)

    def var(dtype, stop_gradient=False):
        return helper.create_variable_for_type_inference(
            dtype=dtype, stop_gradient=stop_gradient)

    if score not in ("softmax", "sigmoid") or (
            score == "softmax" and (select_bias or routed_scale != 1.0)):
        raise ValueError(f"topk_moe: score={score!r} with a selection bias "
                         f"or a routed_scale")
    if act not in (("silu", "relu") if gated else ("relu2", "relu")):
        raise ValueError(f"topk_moe: act={act!r} with gated={gated}")
    if shared_act not in ("silu", "relu", "relu2") or (
            shared_gated and shared_act == "relu2"):
        raise ValueError(f"topk_moe: shared_act={shared_act!r} with "
                         f"shared_gated={shared_gated}")
    # (absent for SwiGLU: the op's default, and the program's text today's)
    act_attrs = {} if act == "silu" else {"act": act}
    if not gated:
        act_attrs["gated"] = False
    with name_scope("router"):
        top_w, top_i = var("float32"), var("int32", True)
        lb, z = var("float32"), var("float32")
        router_in = {"X": input if router_input is None else router_input,
                     "W": param("_router.w", [d, num_experts])}
        router_attrs = {"k": int(top_k), "norm_topk": bool(norm_topk_prob)}
        if router_input is not None:
            router_attrs["input"] = "other"
        if score == "sigmoid":
            router_attrs.update(score=score, routed_scale=float(routed_scale))
        if select_bias:
            bias = helper.create_parameter(
                ParamAttr(name=f"{helper.name}_router.bias",
                          initializer=ConstantInitializer(0.0),
                          trainable=False),
                shape=[num_experts], dtype="float32")
            router_in["Bias"] = bias
        router = helper.append_op(
            "moe_router", inputs=router_in,
            outputs={"TopW": top_w, "TopI": top_i, "LBLoss": lb,
                     "ZLoss": z}, attrs=router_attrs)
        if select_bias:
            # appended by Optimizer.apply_gradients, behind the
            # parameters' updates: nothing of the step reads the new bias
            helper.main_program._step_updates.append(dict(
                type="moe_bias_update",
                inputs={"Bias": bias.name, "TopI": top_i.name},
                outputs={"BiasOut": bias.name},
                attrs={"gamma": float(bias_update_rate),
                       OP_NAMESCOPE_ATTR: router.namescope}))
    with name_scope("dispatch"):
        xs = var(input.dtype)
        rows, order, slot = (var("int32", True) for _ in range(3))
        helper.append_op(
            "moe_dispatch", inputs={"X": input, "TopI": top_i},
            outputs={"Xs": xs, "Rows": rows, "Order": order, "Slot": slot},
            attrs={"num_experts": int(num_experts), **held_attrs})
    with name_scope("experts"):
        ys = var(input.dtype)
        # the projections, kept for the op's backward pass
        kept = {"Up": var(input.dtype, True)}
        weights = {}
        if gated:
            kept = {"Gate": var(input.dtype, True), **kept}
            weights["WGate"] = param("_gate.w", [n_held, d, d_ff])
        regather = {"X": input, "Order": order} if held is not None else {}
        helper.append_op(
            "moe_experts",
            inputs={"Xs": xs, "Rows": rows, **regather, **weights,
                    "WUp": param("_up.w", [n_held, d, d_ff]),
                    "WDown": param("_down.w", [n_held, d_ff, d])},
            outputs={"Ys": ys, **kept},
            attrs={**held_attrs, **act_attrs})
    with name_scope("combine"):
        out = var(input.dtype)
        helper.append_op(
            "moe_combine",
            inputs={"Ys": ys, "TopW": top_w, "Order": order, "Slot": slot,
                    "Like": input,
                    **({"Rows": rows} if held is not None else {})},
            outputs={"Out": out}, attrs=held_attrs)
    if shared_d_ff:
        with name_scope("shared"):
            def linear(x, size, suffix):
                return fc(x, size, num_flatten_dims=len(x.shape) - 1,
                          param_attr=attr(suffix), bias_attr=False)

            unit = {"silu": silu, "relu": relu,
                    "relu2": lambda v: square(relu(v))}[shared_act]
            if shared_gated:
                h = elementwise_mul(unit(linear(input, shared_d_ff,
                                                "_shared_gate.w")),
                                    linear(input, shared_d_ff,
                                           "_shared_up.w"))
            else:
                h = unit(linear(input, shared_d_ff, "_shared_up.w"))
            if shared_gate:
                mix = sigmoid(linear(input, 1, "_shared_mix.w"))
                out = elementwise_add(out, elementwise_mul(
                    linear(h, d, "_shared_down.w"), mix))
            else:
                out = elementwise_add(out, linear(h, d, "_shared_down.w"))
    return out, lb, z, rows, top_i


def _simple_op_layer(op_type, inputs, attrs=None, out_slot="Out",
                     dtype=None, name=None, n_outs=1, out_slots=None):
    helper = LayerHelper(op_type, name=name)
    first = next(iter(inputs.values()))
    base = first[0] if isinstance(first, (list, tuple)) else first
    slots = out_slots or [out_slot]
    outs = {
        s: helper.create_variable_for_type_inference(
            dtype=dtype or base.dtype)
        for s in slots
    }
    helper.append_op(op_type, inputs=inputs, outputs=outs, attrs=attrs or {})
    vals = [outs[s] for s in slots]
    return vals[0] if len(vals) == 1 else tuple(vals)


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, name=None):
    """Bilinear RoI align (reference: layers/nn.py roi_align)."""
    return _simple_op_layer(
        "roi_align", {"X": input, "ROIs": rois},
        {"pooled_height": pooled_height, "pooled_width": pooled_width,
         "spatial_scale": spatial_scale, "sampling_ratio": sampling_ratio},
        name=name)


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0, name=None):
    """Quantized max RoI pooling (reference: layers/nn.py roi_pool)."""
    return _simple_op_layer(
        "roi_pool", {"X": input, "ROIs": rois},
        {"pooled_height": pooled_height, "pooled_width": pooled_width,
         "spatial_scale": spatial_scale}, name=name)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    """Local response normalization (reference: layers/nn.py lrn)."""
    return _simple_op_layer(
        "lrn", {"X": input}, {"n": n, "k": k, "alpha": alpha, "beta": beta},
        name=name)


def spp(input, pyramid_height=3, pool_type="max", name=None):
    """Spatial pyramid pooling (reference: layers/nn.py spp... via spp_op)."""
    return _simple_op_layer(
        "spp", {"X": input},
        {"pyramid_height": pyramid_height, "pooling_type": pool_type},
        name=name)


def affine_grid(theta, out_shape, name=None):
    """2-D affine sampling grid (reference: layers/nn.py affine_grid)."""
    if isinstance(out_shape, (list, tuple)):
        return _simple_op_layer(
            "affine_grid", {"Theta": theta},
            {"output_shape": [int(s) for s in out_shape]},
            out_slot="Output", name=name)
    return _simple_op_layer(
        "affine_grid", {"Theta": theta, "OutputShape": out_shape},
        out_slot="Output", name=name)


def multiclass_nms(bboxes, scores, score_threshold=0.0, nms_top_k=64,
                   keep_top_k=16, nms_threshold=0.3, background_label=0,
                   name=None):
    """Static-shape multiclass NMS: [n, keep_top_k, 6] rows of
    (label, score, box), label -1 padding (reference:
    layers/detection.py multiclass_nms, LoD output redesigned away).
    ``background_label``: class skipped entirely (reference default 0;
    pass -1 to keep every class, e.g. single-class detectors)."""
    return _simple_op_layer(
        "multiclass_nms", {"BBoxes": bboxes, "Scores": scores},
        {"score_threshold": score_threshold, "nms_top_k": nms_top_k,
         "keep_top_k": keep_top_k, "nms_threshold": nms_threshold,
         "background_label": background_label},
        name=name)


def yolo_box(x, img_size, anchors, class_num, conf_thresh=0.01,
             downsample_ratio=32, name=None):
    """YOLOv3 head decode (reference: layers/detection.py yolo_box)."""
    return _simple_op_layer(
        "yolo_box", {"X": x, "ImgSize": img_size},
        {"anchors": list(anchors), "class_num": class_num,
         "conf_thresh": conf_thresh, "downsample_ratio": downsample_ratio},
        out_slots=["Boxes", "Scores"], name=name)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """Spectrally-normalized view of ``weight`` (reference: layers/nn.py
    spectral_norm). Creates persistable U/V power-iteration vectors and
    declares the op's UOut/VOut outputs so the iteration state advances
    across steps (the batch_norm MeanOut/VarianceOut pattern)."""
    from paddle_tpu.initializer import NormalInitializer

    helper = LayerHelper("spectral_norm", name=name)
    shape = weight.shape
    h = int(shape[dim])
    w_elems = 1
    for s_ in shape:
        w_elems *= int(s_)
    w_dim = w_elems // h
    u = helper.create_parameter(
        ParamAttr(name=unique_name.generate(f"{helper.name}.u"),
                  trainable=False),
        shape=[h], dtype=weight.dtype,
        default_initializer=NormalInitializer(0.0, 1.0))
    v = helper.create_parameter(
        ParamAttr(name=unique_name.generate(f"{helper.name}.v"),
                  trainable=False),
        shape=[w_dim], dtype=weight.dtype,
        default_initializer=NormalInitializer(0.0, 1.0))
    out = helper.create_variable_for_type_inference(dtype=weight.dtype)
    helper.append_op(
        "spectral_norm",
        inputs={"Weight": weight, "U": u, "V": v},
        outputs={"Out": out, "UOut": u.name, "VOut": v.name},
        attrs={"dim": dim, "power_iters": power_iters, "eps": eps},
    )
    return out


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=True, param_attr=None, bias_attr=None, act=None,
                  name=None):
    """Context-window sequence convolution (reference: layers/nn.py
    sequence_conv) on padded [b, t, d] batches."""
    helper = LayerHelper("sequence_conv", name=name, bias_attr=bias_attr,
                         act=act)
    d = input.shape[-1]
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=[filter_size * d, num_filters], dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "sequence_conv", inputs={"X": input, "Filter": w},
        outputs={"Out": out},
        attrs={"contextLength": filter_size,
               "contextStart": -(filter_size // 2)})
    out = helper.append_bias_op(out, dim_start=2)
    return helper.append_activation(out)


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    """Sinusoidal position mix-in (reference: layers/nn.py
    add_position_encoding)."""
    return _simple_op_layer(
        "add_position_encoding", {"X": input},
        {"alpha": float(alpha), "beta": float(beta)}, name=name)


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           act=None, name=None):
    """3-D convolution, NCDHW (reference: layers/nn.py conv3d)."""
    helper = LayerHelper("conv3d", name=name, bias_attr=bias_attr, act=act)
    c_in = input.shape[1]

    def triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    fs = triple(filter_size)
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=[num_filters, c_in // groups] + fs, dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        "conv3d", inputs={"Input": input, "Filter": w},
        outputs={"Output": out},
        attrs={"strides": triple(stride), "paddings": triple(padding),
               "dilations": triple(dilation), "groups": groups})
    out = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(out)


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid over a complete binary tree (reference:
    layers/nn.py hsigmoid / hsigmoid_op.cc). Cost [b, 1]."""
    helper = LayerHelper("hsigmoid", name=name)
    d = input.shape[-1]
    w = helper.create_parameter(
        ParamAttr._to_attr(param_attr),
        shape=[num_classes - 1, d], dtype=input.dtype)
    b = helper.create_parameter(
        ParamAttr._to_attr(bias_attr), shape=[num_classes - 1],
        dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    pre = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"X": input, "W": w, "Label": label}
    if b is not None:
        inputs["Bias"] = b
    helper.append_op(
        "hierarchical_sigmoid", inputs=inputs,
        outputs={"Out": out, "PreOut": pre},
        attrs={"num_classes": int(num_classes)})
    return out


def sample_logits(logits, label, num_samples, remove_accidental_hits=True,
                  name=None):
    """Sampled-softmax logits slice (reference: layers/nn.py
    sample_logits). Returns (sampled_logits, sampled_label); feed them to
    softmax_with_cross_entropy."""
    helper = LayerHelper("sample_logits", name=name)
    outs = {
        s: helper.create_variable_for_type_inference(
            dtype="int64" if s in ("Samples", "SampledLabel") else
            logits.dtype,
            stop_gradient=s != "SampledLogits")
        for s in ("Samples", "Probabilities", "SampledLogits",
                  "SampledLabel")
    }
    helper.append_op(
        "sample_logits", inputs={"Logits": logits, "Labels": label},
        outputs=outs,
        attrs={"num_samples": int(num_samples),
               "remove_accidental_hits": bool(remove_accidental_hits)})
    return outs["SampledLogits"], outs["SampledLabel"]


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """Chunk-level P/R/F1 for tagging (reference: layers/nn.py
    chunk_eval). Returns (precision, recall, f1, n_infer, n_label,
    n_correct)."""
    helper = LayerHelper("chunk_eval")
    outs = {}
    for slot, dt in [("Precision", "float32"), ("Recall", "float32"),
                     ("F1-Score", "float32"), ("NumInferChunks", "int64"),
                     ("NumLabelChunks", "int64"),
                     ("NumCorrectChunks", "int64")]:
        outs[slot] = helper.create_variable_for_type_inference(
            dtype=dt, stop_gradient=True)
    inputs = {"Inference": input, "Label": label}
    if seq_length is not None:
        inputs["SeqLength"] = seq_length
    helper.append_op(
        "chunk_eval", inputs=inputs, outputs=outs,
        attrs={"num_chunk_types": num_chunk_types,
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": list(excluded_chunk_types or [])})
    return (outs["Precision"], outs["Recall"], outs["F1-Score"],
            outs["NumInferChunks"], outs["NumLabelChunks"],
            outs["NumCorrectChunks"])


def ctc_greedy_decoder(input, blank, input_length=None, name=None):
    """Greedy CTC decode: per-step argmax then ctc_align merge/blank
    removal (reference: layers/nn.py ctc_greedy_decoder). ``input``
    [B, T, C] probabilities; returns (decoded [B, T] left-compacted with
    -1/0 padding, out_length [B, 1])."""
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    top1 = argmax(input, axis=-1)
    decoded = helper.create_variable_for_type_inference(
        dtype="int64", stop_gradient=True)
    out_len = helper.create_variable_for_type_inference(
        dtype="int32", stop_gradient=True)
    inputs = {"Input": top1}
    if input_length is not None:
        inputs["InputLength"] = input_length
    helper.append_op(
        "ctc_align", inputs=inputs,
        outputs={"Output": decoded, "OutputLength": out_len},
        attrs={"blank": blank, "merge_repeated": True})
    return decoded, out_len


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Register a user Python callable as an operator (reference:
    layers/nn.py:11059 py_func + operators/py_func_op.cc:105). ``func``
    runs on the HOST inside the compiled step via ``jax.pure_callback``;
    ``out`` variables must be pre-created with shapes/dtypes (XLA needs a
    static callback signature — same contract as the reference's "users
    should create out beforehand"). ``backward_func`` receives forward
    inputs, forward outputs, then output gradients (None where absent),
    and returns input gradients (None = no grad)."""
    from paddle_tpu.ops.misc_ops import register_py_func

    helper = LayerHelper("py_func")
    if x is None:
        x = []
    elif isinstance(x, Variable):
        x = [x]
    if out is None:
        out_list = []
    elif isinstance(out, Variable):
        out_list = [out]
    else:
        out_list = list(out)
    for o in out_list:
        if o.shape is None:
            raise ValueError(
                "py_func output shapes must be provided by users manually")
        if any(int(d) < 0 for d in o.shape):
            raise ValueError(
                f"py_func output '{o.name}' has dynamic shape "
                f"{tuple(o.shape)}; the host callback needs a static XLA "
                f"signature — declare concrete dims (including batch)")
    fwd_id = register_py_func(func)
    bwd_id = register_py_func(backward_func) if backward_func else -1
    skip = skip_vars_in_backward_input
    if isinstance(skip, Variable):
        skip = [skip]
    skip_names = [v.name if isinstance(v, Variable) else v for v in skip or []]
    in_out = {v.name for v in list(x) + out_list}
    for n in skip_names:
        if n not in in_out:
            raise ValueError(f"Variable {n} is not found in forward inputs "
                             f"and outputs")
    helper.append_op(
        "py_func",
        inputs={"X": list(x)},
        outputs={"Out": out_list},
        attrs={
            "forward_callable_id": fwd_id,
            "backward_callable_id": bwd_id,
            "out_shapes": [[int(d) for d in o.shape] for o in out_list],
            "out_dtypes": [str(o.dtype) for o in out_list],
            "backward_skip_vars": skip_names,
        },
    )
    return out


def hash(input, hash_size, num_hash=1, name=None):
    """Multi-seed feature hashing into ``[0, hash_size)`` buckets
    (reference: layers/nn.py:10456 + operators/hash_op.cc). ``input``
    [N, d] integer ids; output [N, num_hash, 1].

    Bucket-value compatibility: under ``jax_enable_x64`` the op is
    bit-exact XXH64 and buckets match the reference (so vocabularies,
    pretrained embedding tables, and serving systems built against
    reference hash buckets port numerically). With x64 DISABLED (the
    JAX default) a different mixer is used and bucket values differ
    from the reference — enable x64 before building or porting any
    artifact keyed by hash buckets."""
    helper = LayerHelper("hash", name=name)
    out = helper.create_variable_for_type_inference(
        dtype=input.dtype, stop_gradient=True)
    helper.append_op(
        "hash", inputs={"X": input}, outputs={"Out": out},
        attrs={"num_hash": num_hash, "mod_by": hash_size})
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    """Tree-based convolution over node features (reference:
    layers/nn.py:11351 tree_conv + operators/tree_conv_op.cc).
    ``nodes_vector`` [N, n, f], ``edge_set`` [N, e, 2] directional
    parent->child 1-indexed edges; output [N, n, output_size,
    num_filters]."""
    helper = LayerHelper("tree_conv", name=name, bias_attr=bias_attr,
                         act=act)
    dtype = nodes_vector.dtype
    feature_size = int(nodes_vector.shape[2])
    w = helper.create_parameter(
        attr=param_attr, shape=[feature_size, 3, output_size, num_filters],
        dtype=dtype, is_bias=False)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(
        "tree_conv",
        inputs={"NodesVector": nodes_vector, "EdgeSet": edge_set,
                "Filter": w},
        outputs={"Out": out},
        attrs={"max_depth": max_depth})
    if bias_attr is not False:
        out = helper.append_bias_op(out, dim_start=2)
    return helper.append_activation(out)
