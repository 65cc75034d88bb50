"""Attention support ops: position ids, additive attention bias, and the
fused scaled-dot-product attention kernel (Pallas on TPU, reference JAX
elsewhere).

These replace the reference's LoD-based attention plumbing in
dist_transformer.py (slice/pad helpers) with static-shape mask tensors
(SURVEY.md section 5).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu import monitor as _monitor
from paddle_tpu.core import autodiff, interp
from paddle_tpu.core.registry import OpDef, register_op

NEG_INF = -1e9

# Runs at TRACE time (once per compile, like the ring-attention
# counters): which implementation each lowered sdpa call took.
_M_DISPATCH = _monitor.counter(
    "pt_attention_dispatch_total",
    "attention implementation chosen at trace time, by family "
    "(bthd_small / bthd_kblock / bhtd Pallas kernels, the dense jnp "
    "composition, or ring), pass (fwd/bwd), shape (one device's share "
    "for a kernel family under a mesh; dh the one width of a head, or "
    "\"dk192 dv128\" where queries and keys are wider than values), tile "
    "(heads, query rows and key "
    "rows of one grid step, where the family picks them by the shape: "
    "bhtd; a fwd row's is the forward's own, flash_attention."
    "bhtd_fwd_tile: \"hb2 bq512 bk512\" where two query heads share a "
    "step and, under a group, ONE fetched K / V block; a bwd row's "
    "flash_attention.bhtd_tile's) and replicated_over (mesh axes whose "
    "every rank repeats that same call). A windowed call's shape ends "
    "in w<window> and the row "
    "carries band: skip (the kernels walk the band, no block outside it "
    "is a step), mask (the triangle walked and masked) or dense, and "
    "heads, the call's query heads (a model's window layers may have a "
    "head count of their own). A bwd "
    "row of family bhtd carries form: fused (ONE call, attn.bhtd.bwd) or "
    "split (the pair bwd_dq + bwd_dkv), flash_attention.bhtd_bwd_form's "
    "answer for the call. A row of family bhtd carries edge: the "
    "sub-tiles in which the call walks the blocks its diagonal or its "
    "band's far edge crosses, \"256x256\" on a fused bwd row of blocks "
    "of 512 (flash_attention.bhtd_edge_tile), \"\" where it works on "
    "them whole (every fwd row). A fwd row of family bhtd carries stats: "
    "the layout in which attn.bhtd.fwd writes the call's logsumexp for "
    "the backward, rows ([b, h, 1, tq], four bytes a position, what "
    "attn.bhtd.bwd reads) or column ([b, h, tq, 1], which the chip pads "
    "to a lane tile of 512 bytes a position and XLA copies back into "
    "rows), flash_attention.bhtd_stats_form's answer for the tile. A "
    "block-masked call (the op's block_diffusion attribute: a row of a "
    "noised and a clean copy under block diffusion's training mask) "
    "carries mask: block_diffusion, block: the block's length, and band: "
    "skip (the BHTD kernels walk the mask's live blocks) or dense (the "
    "composition, with its [t, t] scores). A call that was given its "
    "queries and keys in two parts (QPe, KPe: latent attention's rotary "
    "features, the keys' ONE head that all query heads share) carries "
    "parts: own (attn.bhtd.fwd and attn.bhtd.bwd read the parts as "
    "operands of their own: no wide q or k exists) or assembled (the op "
    "concatenated q and k and copied the shared head, and the row is the "
    "wide call's); its shape names the whole head either way. A call "
    "under a SELECTION (the op's Selected input: which keys each query "
    "reads, a device value all heads share) carries sel: operand "
    "(attn.bhtd.fwd and the ONE attn.bhtd.bwd read it in blocks beside K "
    "and V and walk the causal triangle) or dense (the composition under "
    "a [t, t] mask)")


def _windowed(attrs, q, k, bthd, ring):
    """The op's ``window`` attr as the kernels run it: None where the
    call forgets nothing (absent, or as long as the sequence)."""
    from paddle_tpu.parallel import flash_attention as fa

    t_axis = 1 if bthd else 2
    window = fa._band(attrs.get("window") or None,
                      bool(attrs.get("causal", False)),
                      q.shape[t_axis], k.shape[t_axis])
    if window is not None and (bthd or ring is not None):
        raise NotImplementedError(
            "scaled_dot_product_attention: a window needs layout='bhtd' "
            "and no context-parallel ring")
    return window


def _block_masked(attrs, q, k, bthd, ring):
    """The op's ``block_diffusion`` attr (the block's length), None where
    absent; the kernel layer holds it to the row (``fa._halves``)."""
    from paddle_tpu.parallel import flash_attention as fa

    block = attrs.get("block_diffusion") or None
    # (build-time shape inference stands a prime in for a dynamic row:
    # no two halves, and no mask moves a shape)
    if block is None or interp.stands_for_dynamic(q.shape[2]):
        return None
    if bthd or ring is not None:
        raise NotImplementedError(
            "scaled_dot_product_attention: block_diffusion needs "
            "layout='bhtd' and no context-parallel ring")
    return fa._halves(block, bool(attrs.get("causal", False)),
                      attrs.get("window") or None, q.shape[2],
                      k.shape[2])[0]


def _note_dispatch(family, direction, dims, replicated_over=(), window=None,
                   form=None, causal=False, block_diffusion=None, parts=None,
                   sel=None, p_drop=0.0, bias=None, pe_group=None):
    # off with telemetry; build-time shape inference is not a lowering
    if not _monitor.enabled() or not interp.lowering_active():
        return
    b, tq, tk, h, dh = dims[:5]
    # (behind them, where the call is not plain: key/value heads, dv)
    hk, dv = dims[5:7] if len(dims) > 5 else (h, dh)
    tile, edge, stats = "", None, None
    if family == "bhtd":
        # the kernel layer's own answers for the call the op hands it
        # (the op passes no q_block / k_block)
        from paddle_tpu.parallel import flash_attention as fa

        call = dict(dh=dh, group=h // hk, dv=dv,
                    block_diffusion=block_diffusion,
                    itemsize=dims[7] if len(dims) > 7 else 2)
        if direction == "fwd":
            # (the forward's own heads a step; ``p_drop``, ``bias``,
            # ``pe_group``: what else of the call that tile goes by)
            picked = fa.bhtd_fwd_tile(
                h, tq, tk, **call, p_drop=p_drop, bias=bias,
                pe_group=pe_group, selected=sel == "operand")
        else:
            picked = fa.bhtd_tile(h, tq, tk, **call)
        tile = fa.tile_label(picked)
        edge = fa.edge_label(fa.bhtd_edge_tile(picked, causal, form))
        if direction == "fwd":
            stats = fa.bhtd_stats_form(picked, tq)
    # (grouped-query attention names its key/value heads: "h16 kv2";
    # values narrower than queries and keys both widths: "dk192 dv128")
    heads = f"h{h}" if hk == h else f"h{h} kv{hk}"
    width = f"dh{dh}" if dv == dh else f"dk{dh} dv{dv}"
    labels = {
        "family": family, "pass": direction,
        "shape": f"b{b} tq{tq} tk{tk} {heads} {width}", "tile": tile,
        "replicated_over": ",".join(replicated_over)}
    if window is not None:
        labels["shape"] += f" w{window}"
        labels["band"] = "skip" if family == "bhtd" else "dense"
        labels["heads"] = str(h)
    if block_diffusion is not None:
        labels.update(mask="block_diffusion", block=str(block_diffusion),
                      band="skip" if family == "bhtd" else "dense")
    if form is not None:
        labels["form"] = form
    if edge is not None:
        labels["edge"] = edge
    if stats is not None:
        labels["stats"] = stats
    if parts is not None:
        labels["parts"] = parts
    if sel is not None:
        labels["sel"] = sel
    _M_DISPATCH.inc(labels=labels)


def dispatch_counts(tiles=False, forms=False, edges=False, stats=False,
                    masks=False, parts=False, sels=False):
    """{"family pass shape[ replicated_over=axes]": calls lowered so
    far} — the dispatch counter as chip_smoke.py and the multi-chip dry
    run print it. ``tiles``: a row whose family tiles by the shape names
    its tile too, "bhtd fwd <shape> [hb1 bq512 bk512]". ``forms``: a
    backward row of that family says whether it is one call or the
    pair, "bhtd bwd <shape> form=fused". ``edges``: a row whose edge
    blocks are walked in sub-tiles names them, "... edge=256x256".
    ``stats``: a forward row of that family says in which layout the
    call's logsumexp leaves the kernel, "... stats=rows". ``masks``: a
    block-masked row says so, "... mask=block_diffusion block=4
    band=skip". ``parts``: a row of a call given q and k in two parts
    says who read them, "... parts=own" (the kernels) or "...
    parts=assembled" (the op concatenated them). ``sels``: a row of a
    call under a selection says who read it, "... sel=operand" (the
    kernels) or "... sel=dense" (the composition)."""
    out = {}
    for row in _monitor.snapshot()[_M_DISPATCH.name]["values"]:
        lb = row["labels"]
        name = " ".join(lb.get(k, "?") for k in ("family", "pass", "shape"))
        if lb.get("replicated_over"):
            name += f" replicated_over={lb['replicated_over']}"
        if tiles and lb.get("tile"):
            name += f" [{lb['tile']}]"
        if forms and lb.get("form"):
            name += f" form={lb['form']}"
        if edges and lb.get("edge"):
            name += f" edge={lb['edge']}"
        if stats and lb.get("stats"):
            name += f" stats={lb['stats']}"
        if masks and lb.get("mask"):
            name += (f" mask={lb['mask']} block={lb['block']} "
                     f"band={lb['band']}")
        if parts and lb.get("parts"):
            name += f" parts={lb['parts']}"
        if sels and lb.get("sel"):
            name += f" sel={lb['sel']}"
        out[name] = out.get(name, 0) + int(row["value"])
    return out


def _x(ins, slot="X", i=0):
    v = ins.get(slot)
    return v[i] if v else None


@register_op("diff_attention_combine",
             diff_inputs=("O1", "O2", "LQ1", "LK1", "LQ2", "LK2", "Scale"))
def _diff_attention_combine(ins, attrs):
    """Differential attention's combination (Ye et al. 2024,
    arXiv:2410.05258, as HF ``modeling_phi4flash.py`` has it): O1, O2
    [.., dv], the outputs of the pair's two softmax maps over the same
    values; LQ1, LK1, LQ2, LK2 [dh] and Scale [dv] ->

        lambda = exp(LQ1 . LK1) - exp(LQ2 . LK2) + lambda_init
        Out    = rms_norm(O1 - lambda O2) * Scale * (1 - lambda_init)

    lambda, the difference and the norm's statistics in float32, Out in
    O1's dtype."""
    f32 = jnp.float32
    o1, o2 = _x(ins, "O1"), _x(ins, "O2")
    lq1, lk1, lq2, lk2 = (_x(ins, s).astype(f32)
                          for s in ("LQ1", "LK1", "LQ2", "LK2"))
    init = float(attrs["lambda_init"])
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    o = o1.astype(f32) - lam * o2.astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + attrs.get("epsilon", 1e-5))
    o = o * (_x(ins, "Scale").astype(f32) * (1.0 - init))
    return {"Out": [o.astype(o1.dtype)]}


@register_op("position_ids", no_grad=True)
def _position_ids(ins, attrs):
    x = _x(ins)  # [b, t] any int dtype
    b, t = jnp.shape(x)[0], jnp.shape(x)[1]
    return {"Out": [jnp.broadcast_to(jnp.arange(t, dtype=jnp.int64), (b, t))]}


@register_op("attn_bias", no_grad=True)
def _attn_bias(ins, attrs):
    """PadMask [b, t_k] (1=real token) -> additive bias.

    causal=False: [b, 1, 1, t_k] with -1e9 at padding.
    causal=True:  [b, 1, t_k, t_k] padding + upper-triangular future mask.
    """
    mask = _x(ins, "PadMask")
    pad_bias = (1.0 - mask) * NEG_INF  # [b, t]
    if attrs.get("causal", False):
        t = jnp.shape(mask)[1]
        causal = jnp.triu(jnp.full((t, t), NEG_INF, mask.dtype), k=1)
        out = pad_bias[:, None, None, :] + causal[None, None, :, :]
    else:
        out = pad_bias[:, None, None, :]
    return {"Out": [out]}


def _rotate(x, theta, rotary_dim=None, interleaved=False, scaling=None,
            periods=1, positions=None, sections=None):
    """Rotary position embedding of x [b, h, t, dh], rotate-half form
    (Su et al. 2021 as GPT-NeoX and HF lay it out): feature i pairs
    with i + dh/2, position p turns the pair by p * theta^(-2i/dh).
    ``rotary_dim`` < dh: only the FIRST rotary_dim features turn (as a
    head of that width would), the others pass. ``interleaved``: the
    pairs are the neighbours (2i, 2i + 1) instead (the paper's own
    layout, DeepSeek's ``rope_interleave``), the angles the same.
    ``scaling`` (a ``parallel/rope.Yarn``): yarn's frequencies over the
    features that turn and its attention factor on cos and sin
    (``parallel/rope.cos_sin``, which the kernels' tables call too).
    ``periods``: the positions 0 .. t / periods - 1 run that many times
    over the row (index i is position i mod t / periods). ``positions``
    [n, t] with ``sections``: the positions are fed, frequency pair i
    turning by the row ``sections`` gives it (``parallel/rope.cos_sin``)."""
    from paddle_tpu.parallel import rope

    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [_rotate(x[..., :rotary_dim], theta, None, interleaved, scaling,
                     periods, positions, sections),
             x[..., rotary_dim:]], -1)
    t, dh = x.shape[-2], x.shape[-1]
    if interp.stands_for_dynamic(t):
        periods = 1   # (build-time shape inference: no angle moves a shape)
    if t % periods:
        raise ValueError(f"rotary_embedding: a row of {t} positions is "
                         f"not {periods} runs of the same positions")
    if positions is not None and interp.stands_for_dynamic(t):
        positions = None   # (build-time shape inference, as above)
    cos, sin = rope.cos_sin(t // periods, dh, theta, scaling, positions,
                            sections)
    if periods > 1:
        cos, sin = jnp.tile(cos, (periods, 1)), jnp.tile(sin, (periods, 1))
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dh // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
        return out.reshape(x.shape).astype(x.dtype)
    cos = jnp.concatenate([cos, cos], -1)
    sin = jnp.concatenate([sin, sin], -1)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :dh // 2], xf[..., dh // 2:]
    out = xf * cos + jnp.concatenate([-x2, x1], -1) * sin
    return out.astype(x.dtype)


_M_ROPE = _monitor.counter(
    "pt_rope_dispatch_total",
    "rotary embedding implementation chosen at trace time, one row a "
    "lowered call (q and k together): impl kernel (rope.fwd / rope.bwd, "
    "parallel/rope.py) or xla (_rotate, behind XLA's transpose where "
    "layout is bthd), pass (fwd/bwd), layout (bthd: q and k come "
    "token-major; bhtd: head-major), dh and scaling (yarn: the call's "
    "tables hold yarn's frequencies and attention factor; none: plain); "
    "parallel/rope.rope_tile's answer for the call. A call that brings "
    "the heads' gains (QScale, KScale: the per-head RMSNorm of q and k "
    "in the same pass) carries norm: head")


def _rope_attrs(attrs):
    """(theta, rotary_dim or None, interleaved, token-major?) of a
    rotary_embedding op."""
    return (float(attrs.get("theta", 10000.0)),
            int(attrs.get("rotary_dim", 0)) or None,
            bool(attrs.get("interleaved", False)),
            attrs.get("layout", "bhtd") == "bthd")


def _rope_periods(attrs):
    """How many times the positions run over the row (attr ``periods``)."""
    return int(attrs.get("periods", 0)) or 1


def _rope_positions(ins, attrs):
    """(the fed positions [n, t] or None, their ``mrope_section`` or
    None) of a rotary_embedding call: input ``Positions`` and attribute
    ``mrope_section`` (``parallel/rope.cos_sin``)."""
    positions = _x(ins, "Positions")
    if positions is not None and _rope_periods(attrs) != 1:
        raise ValueError("rotary_embedding: fed positions run once "
                         "(periods=1)")
    return positions, tuple(attrs.get("mrope_section") or ()) or None


def _rope_scaling(attrs):
    """The op's yarn scaling (``parallel/rope.Yarn``) from its five
    plain attributes ``yarn_<field>``, which ``layers.rotary_embedding``
    writes together; None where it has none."""
    from paddle_tpu.parallel import rope

    if not attrs.get("yarn_factor"):
        return None
    return rope.Yarn(*(float(attrs[f"yarn_{f}"]) for f in rope.Yarn._fields))


def _rope_gains(ins):
    """(q's, k's) gain [dh] of a rotary_embedding call that norms each
    head in the same pass (``QScale``, ``KScale``: both or neither), or
    None."""
    gains = _x(ins, "QScale"), _x(ins, "KScale")
    if gains == (None, None):
        return None
    if None in gains:
        raise ValueError("rotary_embedding: QScale and KScale come "
                         "together (the per-head norm of q AND of k)")
    return gains


def _rope_tile(q, k, attrs, direction, norm=False):
    """``parallel/rope.rope_tile``'s answer for a rotary_embedding call
    on Q and K (None: the XLA form), noted in
    ``pt_rope_dispatch_total``. ``norm``: the call brings the heads'
    gains."""
    from paddle_tpu.parallel import rope

    _, rd, il, tokens = _rope_attrs(attrs)
    t_axis, h_axis = (1, 2) if tokens else (2, 1)
    tile = None
    if q.dtype == k.dtype and q.shape[-1] == k.shape[-1]:
        tile = rope.rope_tile(
            q.shape[0], q.shape[t_axis], q.shape[h_axis], q.shape[-1], rd,
            il, q.dtype, hk=k.shape[h_axis], periods=_rope_periods(attrs),
            norm=norm)
    # off with telemetry; build-time shape inference is not a lowering
    if _monitor.enabled() and interp.lowering_active():
        _M_ROPE.inc(labels={"impl": "kernel" if tile else "xla",
                            "pass": direction,
                            "layout": "bthd" if tokens else "bhtd",
                            "dh": str(q.shape[-1]),
                            "scaling": "yarn" if attrs.get("yarn_factor")
                            else "none",
                            **({"norm": "head"} if norm else {})})
    return tile


def _rotary_xla(ins, attrs):
    """rotary_embedding as XLA's ops: ``_rotate`` on head-major Q and
    K, behind a transpose where they come token-major, and behind the
    op ``rms_norm``'s own lines over each head where the call brings
    the heads' gains."""
    from paddle_tpu.ops import nn_ops

    theta, rd, il, tokens = _rope_attrs(attrs)
    scaling = _rope_scaling(attrs)
    q, k = _x(ins, "Q"), _x(ins, "K")
    gains = _rope_gains(ins)
    if gains is not None:
        q, k = (nn_ops._rms_norm(
            {"X": [z], "Scale": [g]},
            {"epsilon": attrs["norm_epsilon"]})["Y"][0]
            for z, g in zip((q, k), gains))
    if tokens:
        q, k = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    periods = _rope_periods(attrs)
    at = _rope_positions(ins, attrs)
    return {"QOut": [_rotate(q, theta, rd, il, scaling, periods, *at)],
            "KOut": [_rotate(k, theta, rd, il, scaling, periods, *at)]}


# (the generic grad op's rule for the XLA form: the vjp of _rotary_xla)
_ROPE_DIFF_INPUTS = ("Q", "K", "QScale", "KScale")
_ROTARY_XLA_GRAD = autodiff.make_grad_compute(OpDef(
    type="rotary_embedding", compute=_rotary_xla,
    diff_inputs=_ROPE_DIFF_INPUTS))


@register_op("rotary_embedding", diff_inputs=_ROPE_DIFF_INPUTS)
def _rotary_embedding(ins, attrs):
    """Q, K [b, h, t, dh] (K may have fewer heads) -> the same with
    rotary positions 0..t-1 applied (attr ``theta``, the base;
    ``rotary_dim``, 0 or absent for the whole head: the leading
    features that turn; ``interleaved``: pairs of neighbours, not
    rotate-half; ``yarn_factor``, ``yarn_original_length``,
    ``yarn_beta_fast``, ``yarn_beta_slow``, ``yarn_attention_factor``:
    a yarn scaling of the frequencies of the features that turn and its
    factor on cos and sin, plain numbers, so the op stays a function of
    its attributes; ``periods``, absent for 1: the positions 0 .. t /
    periods - 1 run that many times over the row, index i is position
    i mod t / periods, as a row of a noised and a clean copy of the same
    tokens needs). Optional input ``Positions`` [n, t] (any integer or
    float dtype, shared by the batch's rows) with the attribute
    ``mrope_section`` (n counts that sum to the rotated pairs; absent:
    row 0 turns every pair): the positions are FED, and frequency pair i
    turns by the row its section gives it (multi-axis rotary: temporal |
    height | width); the tables are then device values of the feed, which
    the kernels read as they read any table. The angles and the rotation are f32; the results
    return to the inputs' dtype. ``layout`` "bthd": Q and K come
    token-major [b, t, h, dh], as a projection leaves them; the results
    are head-major [b, h, t, dh] all the same.

    Optional inputs ``QScale`` and ``KScale`` [dh] (float32 under AMP
    too, as rms_norm's ``Scale``; both or neither) with the attribute
    ``norm_epsilon``: the per-head RMSNorm of Q and of K in the same
    pass. The op is then, per head, rotate(rms_norm(x) * gain) with
    ``ops/nn_ops._rms_norm``'s arithmetic (float32 mean of squares over
    dh, rsqrt(ms + eps), the float32 gain, the result in x's dtype) in
    front of the rotation's.

    ONE kernel for Q and K, ``parallel/rope.rope_fwd``, at the tile
    ``rope_tile`` gives the call from its shapes, dtype, backend and
    mesh; where it gives none, ``_rotary_xla``."""
    q, k = _x(ins, "Q"), _x(ins, "K")
    gains = _rope_gains(ins)
    tile = _rope_tile(q, k, attrs, "fwd", norm=gains is not None)
    if tile is None:
        return _rotary_xla(ins, attrs)
    from paddle_tpu.parallel import rope

    theta, rd, _, tokens = _rope_attrs(attrs)
    positions, sections = _rope_positions(ins, attrs)
    q, k = rope.rope_fwd(q, k, theta, tile, tokens=tokens,
                         scaling=_rope_scaling(attrs), rotary_dim=rd,
                         periods=_rope_periods(attrs), gains=gains,
                         eps=attrs.get("norm_epsilon"),
                         positions=positions, sections=sections)
    return {"QOut": [q], "KOut": [k]}


@register_op("rotary_embedding_grad", no_grad=True)
def _rotary_embedding_grad(ins, attrs):
    """GRAD::Q and GRAD::K of rotary_embedding, in Q's and K's layout:
    the rotation's transpose (the rotation by the negated angles) of
    the head-major cotangents, ``parallel/rope.rope_bwd`` where the
    forward took the kernel (a kernel called from a ``custom_vjp`` rule
    would be traced twice and named by jax), else the vjp of
    ``_rotary_xla``, as the generic grad op took it. A call with the
    heads' gains also gives GRAD::QScale and GRAD::KScale, float32 sums;
    the kernel reads Q and K again (the one activation this stretch
    keeps) and makes the norm's statistic from them."""
    q, k = _x(ins, "Q"), _x(ins, "K")
    gains = _rope_gains(ins)
    tile = _rope_tile(q, k, attrs, "bwd", norm=gains is not None)
    if tile is None:
        return _ROTARY_XLA_GRAD(ins, attrs)
    from paddle_tpu.parallel import rope

    theta, rd, _, tokens = _rope_attrs(attrs)

    def cotangent(g, x):   # head-major; zeros where the program gave none
        if g is not None:
            return g.astype(x.dtype)
        return jnp.zeros_like(jnp.swapaxes(x, 1, 2) if tokens else x)

    dq, dk, *dgains = rope.rope_bwd(
        cotangent(_x(ins, "GRAD::QOut"), q),
        cotangent(_x(ins, "GRAD::KOut"), k), theta, tile, tokens=tokens,
        scaling=_rope_scaling(attrs), rotary_dim=rd,
        periods=_rope_periods(attrs), gains=gains,
        eps=attrs.get("norm_epsilon"), x=(q, k),
        **dict(zip(("positions", "sections"), _rope_positions(ins, attrs))))
    grads = {"GRAD::Q": [dq], "GRAD::K": [dk]}
    for slot, d, g in zip(("GRAD::QScale", "GRAD::KScale"), dgains,
                          gains or ()):
        grads[slot] = [d.astype(g.dtype)]
    return grads


def _drops(attrs):
    """Does the call drop attention probabilities (a rate, in training)?"""
    return attrs.get("dropout_prob", 0.0) > 0.0 and not attrs.get(
        "is_test", False)


def _sdpa_config(ins, attrs, rng):
    """Shared fwd/grad config: (scale, p_drop, seed, family, dims).

    The grad op's rng is folded with the SAME forward_op_idx as the
    forward's (core/lowering.py), so the derived dropout seed — and hence
    the in-kernel mask — is identical in both directions. ``family`` is
    the Pallas kernel family the shapes take on this backend, or "dense"
    for the jnp composition (parallel/flash_attention.py); ``dims`` is
    (b, tq, tk, h, dh, key/value heads, dv, q's itemsize), the dispatch
    record's shape (``_note_dispatch`` takes the first five alone too). K and V with fewer heads than Q (grouped-query attention) and
    V narrower than Q and K (dv != dh: latent attention) take the BHTD
    layout only, no dropout, no mesh. Where the kernels read QPe and KPe
    as operands of their own (``_two_parts`` left them in ``ins``), dh
    is the whole head's, Q's and QPe's features together.
    """
    from paddle_tpu.parallel import flash_attention as fa

    q, k, v = _x(ins, "Q"), _x(ins, "K"), _x(ins, "V")
    q_pe = _x(ins, "QPe")
    r = 0 if q_pe is None else q_pe.shape[-1]
    scale = attrs.get("scale", None)
    if scale is None:
        scale = 1.0 / math.sqrt(jnp.shape(q)[-1] + r)
    p_drop = attrs.get("dropout_prob", 0.0)
    training_dropout = _drops(attrs)
    seed = None
    drop = 0.0
    if training_dropout:
        drop = float(p_drop)
        seed = jax.random.randint(rng, (), 0, 2**31 - 1, dtype=jnp.int32)
    if attrs.get("layout", "bhtd") == "bthd":
        b, tq, h, dh = q.shape
        tk = k.shape[1]
        if k.shape[2] != h or v.shape[3] != dh:
            raise ValueError("grouped key/value heads, and values of "
                             "another width than the keys, need "
                             "layout='bhtd'")
        family = fa.bthd_family(tq, tk, h, dh)
        dims = (b, tq, tk, h, dh, h, dh)
    else:
        b, h, tq, dh = q.shape
        dh += r
        tk, hk, dv = k.shape[2], k.shape[1], v.shape[3]
        itemsize = jnp.dtype(q.dtype).itemsize
        family = fa.bhtd_family(
            h, tq, tk, dh=dh, group=h // hk, dv=dv,
            block_diffusion=attrs.get("block_diffusion") or None,
            itemsize=itemsize)
        dims = (b, tq, tk, h, dh, hk, dv, itemsize)
        if (hk != h or dv != dh or attrs.get("block_diffusion")) and (
                training_dropout or interp.spmd_ctx() is not None):
            raise NotImplementedError(
                "scaled_dot_product_attention: grouped key/value heads, "
                "values of another width than the keys, or a block-"
                "diffusion mask, with attention dropout or under a mesh")
    if not attrs.get("use_pallas", True):
        family = "dense"
    return scale, drop, seed, family, dims


def _assemble(q, k, q_pe, k_pe):
    """The wide queries and keys of a call given in two parts, [Q | QPe]
    and [K | KPe]: the fewer of K's and KPe's heads copied up to the
    other's (latent attention's ONE rotary key head, as often as there
    are query heads). What the kernels spare a call whose parts they
    read where they lie (``_two_parts``)."""
    hk, hp = k.shape[1], k_pe.shape[1]
    heads = max(hk, hp)
    if heads % hk or heads % hp:
        raise ValueError(f"scaled_dot_product_attention: K's {hk} heads "
                         f"and KPe's {hp} do not divide one another")
    return (jnp.concatenate([q, q_pe], -1),
            jnp.concatenate([jnp.repeat(k, heads // hk, axis=1),
                             jnp.repeat(k_pe, heads // hp, axis=1)], -1))


def _two_parts(ins, attrs):
    """-> (ins, parts, apart) of an sdpa or sdpa_grad call: ``parts``
    None and ``ins`` as they are for a call without QPe and KPe; "own"
    and ``ins`` as they are where ``attn.bhtd.fwd`` and the ONE
    ``attn.bhtd.bwd`` read the parts as operands of their own
    (``flash_attention.bhtd_parts``: a TPU, kernels on, no mesh, no
    bias, dropout, window or block mask, one head a step); "assembled"
    elsewhere, with Q and K in ``ins`` the wide ones (``_assemble``) and
    no QPe and KPe, so that the rest of the op is the call in one part,
    and ``apart`` (else None): the wide (dq, dk) -> the gradients of Q,
    K, QPe and KPe, ``_assemble``'s transpose. The dispatch counter's
    ``parts`` label says which."""
    from paddle_tpu.parallel import flash_attention as fa

    q_pe, k_pe = _x(ins, "QPe"), _x(ins, "KPe")
    if q_pe is None and k_pe is None:
        return ins, None, None
    if q_pe is None or k_pe is None or attrs.get("layout", "bhtd") != "bhtd":
        raise ValueError("scaled_dot_product_attention: QPe and KPe come "
                         "together, with layout='bhtd'")
    q, k, v = _x(ins, "Q"), _x(ins, "K"), _x(ins, "V")
    (_, h, tq, dh), (_, hk, tk, _) = q.shape, k.shape
    plain = (
        attrs.get("use_pallas", True) and interp.spmd_ctx() is None
        and _x(ins, "Bias") is None and not attrs.get("block_diffusion")
        and not _drops(attrs)
        and fa._band(attrs.get("window") or None,
                     bool(attrs.get("causal", False)), tq, tk) is None)
    if h % hk == 0 and fa.bhtd_parts(
            h, tq, tk, dh=dh, r=q_pe.shape[3], hp=k_pe.shape[1],
            group=h // hk, dv=v.shape[3],
            itemsize=jnp.dtype(q.dtype).itemsize, plain=plain):
        return ins, "own", None
    (wide_q, wide_k), apart = jax.vjp(_assemble, q, k, q_pe, k_pe)
    return {**{slot: x for slot, x in ins.items()
               if slot not in ("QPe", "KPe")},
            "Q": [wide_q], "K": [wide_k]}, "assembled", apart


def _selected(ins, attrs, family, dims):
    """-> (selection, Live, family, sel) of an sdpa or sdpa_grad call
    (None, None and ``sel`` None for a call without), the family the
    call takes under it and the dispatch counter's ``sel`` label:
    "operand" where the BHTD kernels read the op's Selected
    [b, t / 32, t] int32 and its live-block table
    (``flash_attention.bhtd_selected``: causal self-attention, no bias,
    dropout, window, block mask, second part or mesh, the ONE backward
    call, the table's blocks the tile's), else "dense", the composition
    under the selection unpacked to a [b, t, t] mask."""
    from paddle_tpu.parallel import flash_attention as fa

    selected = _x(ins, "Selected")
    if selected is None:
        return None, None, family, None
    if attrs.get("layout", "bhtd") != "bhtd" or not attrs.get("causal"):
        raise ValueError("scaled_dot_product_attention: Selected needs "
                         "layout='bhtd' and causal")
    b, tq, tk, h, dh, hk, dv, itemsize = dims
    plain = (_x(ins, "Bias") is None and _x(ins, "QPe") is None
             and not _drops(attrs) and not attrs.get("block_diffusion")
             and interp.spmd_ctx() is None
             and fa._band(attrs.get("window") or None, True, tq, tk) is None)
    live = _x(ins, "Live")
    if family == "bhtd" and fa.bhtd_selected(
            h, tq, tk, dh=dh, group=h // hk, dv=dv, itemsize=itemsize,
            plain=plain, blocks=live.shape[1:]):
        return selected, live, family, "operand"
    return fa._selected_mask(selected, live), None, "dense", "dense"


def _on_mesh(kernel, arrays, seed, family, direction, dims, window=None,
             form=None, causal=False, block_diffusion=None, parts=None,
             sel=None, **fwd):
    """``kernel(*arrays, seed)`` — a Pallas attention call whose array
    arguments (None allowed) and results all lead with the batch dim —
    under the program's mesh. GSPMD cannot partition a Mosaic kernel
    (jax refuses to lower one in a multi-device jit), so under a mesh
    the call is a shard_map: the batch splits over the data axis, and
    over any other axis every rank repeats the same call — recorded as
    ``replicated_over`` in the dispatch counter, never silent. Each
    shard hands the kernels its first GLOBAL batch row along with the
    seed, so the dropout masks do not depend on how many devices split
    the batch. ``fwd``: what else a BHTD forward's own tile goes by
    (``_note_dispatch``'s ``p_drop``, ``bias``, ``pe_group``)."""
    split = interp.mesh_batch_split()
    if split is None:
        _note_dispatch(family, direction, dims, window=window, form=form,
                       causal=causal, block_diffusion=block_diffusion,
                       parts=parts, sel=sel, **fwd)
        return kernel(*arrays, seed)
    from jax.sharding import PartitionSpec as P

    mesh, free, nested, axis, n = split
    b = arrays[0].shape[0]
    if b % n != 0:
        # (the batch-sharded feeds could not split it either)
        raise ValueError(
            f"attention batch {b} does not split over the {n} ranks of "
            f"mesh axis {axis}: every device would repeat the whole "
            f"batch; feed a batch that is a multiple of {n}")
    _note_dispatch(
        family, direction, (b // n,) + tuple(dims[1:]),
        sorted(a for a in free - set(axis) if mesh.shape[a] > 1), window,
        form, causal, block_diffusion, parts, **fwd)
    batch = P(axis) if axis else P()
    present = [a for a in arrays if a is not None]
    # a [1, ...] bias broadcasts over the batch: it stays replicated
    specs = [batch if a.shape[0] == b else P() for a in present]
    if seed is None:
        seed = jnp.zeros((), jnp.int32)

    def local(seed, *xs):
        it = iter(xs)
        full = [None if a is None else next(it) for a in arrays]
        row0 = jax.lax.axis_index(axis) * (b // n) if axis else 0
        return kernel(*full, jnp.stack(
            [seed.astype(jnp.int32), jnp.asarray(row0, jnp.int32)]))

    # (nested in a manual region, the mesh is the context's)
    return jax.shard_map(local, mesh=None if nested else mesh,
                         in_specs=(P(), *specs), out_specs=batch,
                         axis_names=free)(seed, *present)


def _ring_config_t(q, k, t_axis=2):
    """(mesh, context_axis, data_axis) when sequence-parallel ring
    attention applies, else None. Requires a strategy-declared context
    axis, BOTH sequence lengths divisible by the axis size (cross
    attention has tq != tk). Attention dropout rides along since round
    5: the flash-backed ring body draws an independent in-kernel mask
    stream per rotating block (source-rank-mixed seed), regenerated
    identically in forward and backward. Non-qualifying attention falls
    back to the flash/dense path. ``t_axis`` is the sequence dim: 2 for
    BHTD, 1 for BTHD."""
    ctx = interp.spmd_ctx()
    if ctx is None:
        return None
    mesh, ctx_axis, data_axis = ctx.mesh, ctx.context_axis, ctx.data_axis
    if ctx_axis is None:
        return None
    n = mesh.shape[ctx_axis]
    if (n <= 1 or jnp.shape(q)[t_axis] % n != 0
            or jnp.shape(k)[t_axis] % n != 0):
        return None
    # the batch dim must divide the (possibly composed slice x data)
    # batch-axis ranks; replicate the batch rather than letting
    # shard_map fail with an opaque uneven-sharding trace error
    from paddle_tpu.parallel.mesh import axis_size

    if data_axis is not None and (
        jnp.shape(q)[0] % axis_size(mesh, data_axis) != 0
    ):
        data_axis = None
    return mesh, ctx_axis, data_axis


def _ring_config(q, k):
    return _ring_config_t(q, k, 2)


@register_op("scaled_dot_product_attention",
             diff_inputs=("Q", "K", "V", "QPe", "KPe"), needs_rng=True)
def _sdpa(ins, attrs, rng=None):
    """Fused attention: Q,K,V [b, h, t, dh] + optional additive Bias.
    V (and Out with it) may be narrower or wider than Q and K (latent
    attention: 192-wide queries and keys over 128-wide values; the
    default scale is 1 / sqrt of Q's width). K and V may have fewer
    heads than Q (grouped-query attention: query
    head i reads key/value head i // (h / kv heads)); the BHTD kernels
    pick the head in their index maps and never copy K or V. Attr
    ``window`` (with ``causal``, layout bhtd, no ring): a query sees the
    last ``window`` positions only, itself among them. Attr
    ``block_diffusion`` = B (with neither, layout bhtd, no ring, no
    dropout): the row is a noised and a clean copy of t / 2 positions in
    blocks of B under block diffusion's training mask
    (``flash_attention.bd_visible``).

    Optional inputs ``QPe`` [b, h, t, r] and ``KPe`` [b, hp, t, r], hp
    dividing h (both or neither, layout bhtd): the queries and keys come
    in TWO parts, the scores are scale * (Q K^T + QPe KPe^T) with query
    head i reading KPe's head i // (h / hp), and the default scale is
    1 / sqrt of both widths together; everything behind the scores is
    as it is. Latent attention's call: Q | QPe the 128 features without
    a position and the 64 rotated ones, KPe the ONE rotary key head all
    query heads share. The BHTD kernels read the parts as operands of
    their own where ``flash_attention.bhtd_parts`` takes the call;
    elsewhere the op concatenates them and copies the shared head
    itself and runs the call in one part (``_two_parts``), and the grad
    op slices the wide gradients and sums the copies'. The dispatch
    counter's ``parts`` label says which, so the fallback is never
    silent.

    Optional inputs ``Selected`` [b, t / 32, t] int32 and ``Live``
    [b, t / bq, t / bk] int32 (``dsa_select``'s, with ``causal``, layout
    bhtd): query p reads key s only where its bit of Selected is set,
    every head alike; no gradient reaches either. The BHTD kernels read
    the selection in blocks beside K and V where
    ``flash_attention.bhtd_selected`` takes the call (``_selected``),
    else the dense composition masks by it; the dispatch counter's
    ``sel`` label says which. Lse is then the real logsumexp over the
    selected keys on every path (``dsa_index_loss`` reads it).

    On TPU this routes to the Pallas flash-attention kernel
    (paddle_tpu/parallel/flash_attention.py), including training-time
    attention dropout, which runs inside the kernel from a per-step seed.
    Off-TPU (or in the numeric-grad harness) it uses the jnp composition,
    which XLA fuses. Also emits the logsumexp rows (Lse) so the paired
    grad op below can run the blocked backward kernels WITHOUT re-running
    the forward (XLA cannot CSE custom calls; DCE'd when unused).
    """
    ins, parts, _ = _two_parts(ins, attrs)
    q, k, v = _x(ins, "Q"), _x(ins, "K"), _x(ins, "V")
    bias = _x(ins, "Bias")
    scale, drop, seed, family, dims = _sdpa_config(ins, attrs, rng)
    bthd = attrs.get("layout", "bhtd") == "bthd"
    causal = bool(attrs.get("causal", False))
    from paddle_tpu.parallel import flash_attention as fa

    t_axis = 1 if bthd else 2
    ring = _ring_config_t(q, k, t_axis)
    window = _windowed(attrs, q, k, bthd, ring)
    block = _block_masked(attrs, q, k, bthd, ring)
    selected, live, family, sel = _selected(ins, attrs, family, dims)
    if ring is not None:
        _note_dispatch("ring", "fwd", dims, parts=parts)
        mesh, ctx_axis, data_axis = ring
        from paddle_tpu.parallel import ring_attention as ra

        if bthd:  # ring kernel operates on [b, h, t, dh]
            out = ra.ring_attention(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), mesh, seq_axis=ctx_axis,
                scale=scale, bias=bias, data_axis=data_axis,
                causal=causal, p_drop=float(drop), seed=seed)
            out = jnp.swapaxes(out, 1, 2)
        else:
            out = ra.ring_attention(q, k, v, mesh, seq_axis=ctx_axis,
                                    scale=scale, bias=bias,
                                    data_axis=data_axis, causal=causal,
                                    p_drop=float(drop), seed=seed)
        lse = jnp.zeros(jnp.shape(q)[:3] + (1,), jnp.float32)
    elif family == "dense":
        _note_dispatch("dense", "fwd", dims, window=window,
                       block_diffusion=block, parts=parts, sel=sel)
        sd = seed if drop > 0.0 else None
        lse = jnp.zeros(jnp.shape(q)[:3] + (1,), jnp.float32)
        if bthd:
            out = fa._reference_attention_bthd(
                q, k, v,
                fa._combined_causal_bias(bias, q.shape[1], k.shape[1])
                if causal else bias,
                scale, drop, sd)
        elif selected is not None:
            # (REAL logsumexp rows: dsa_index_loss reads them)
            out, lse = fa._reference_attention_with_lse(
                q, k, v, bias, scale, drop, sd, causal=causal,
                window=window, selected=selected)
        else:
            out = fa._reference_attention(q, k, v, bias, scale, drop, sd,
                                          causal=causal, window=window,
                                          block_diffusion=block)
    elif bthd:
        out, lse = _on_mesh(
            lambda q, k, v, bias, seed: fa.flash_attention_bthd_with_lse(
                q, k, v, bias, seed, scale, float(drop), causal),
            (q, k, v, bias), seed, family, "fwd", dims)
    else:
        # the custom-vjp wrapper makes the op differentiable through
        # jax.vjp too (scan-over-layers grad); the paired grad op below
        # remains the unrolled path's backward
        # (QPe, KPe: None but where the kernels read the parts, "own")
        out, lse = _on_mesh(
            lambda q, k, v, bias, seed: fa.flash_attention_with_lse(
                q, k, v, bias, seed, scale, float(drop), causal=causal,
                window=window, block_diffusion=block,
                q_pe=_x(ins, "QPe"), k_pe=_x(ins, "KPe"),
                selected=selected, live=live),
            (q, k, v, bias), seed, family, "fwd", dims, window,
            block_diffusion=block, parts=parts, sel=sel, p_drop=float(drop),
            bias=None if bias is None else bias.shape,
            pe_group=q.shape[1] // _x(ins, "KPe").shape[1]
            if parts == "own" else None)
    return {"Out": [out.astype(q.dtype)], "Lse": [lse]}


@register_op("scaled_dot_product_attention_grad", no_grad=True,
             needs_rng=True)
def _sdpa_grad(ins, attrs, rng=None):
    """Blocked flash-attention backward consuming the forward's saved
    (Out, Lse) — no forward re-execution (cf. the auto vjp path, which
    would re-run the kernel because custom calls are opaque to CSE).
    A call in two parts (QPe, KPe) also gives GRAD::QPe and GRAD::KPe:
    the ONE call's own where the kernels read the parts, else the wide
    gradients' slices with the copied heads' summed (``_two_parts``)."""
    ins, parts, apart = _two_parts(ins, attrs)
    q, k, v = _x(ins, "Q"), _x(ins, "K"), _x(ins, "V")
    bias = _x(ins, "Bias")
    out, lse = _x(ins, "Out"), _x(ins, "Lse")
    g = _x(ins, "GRAD::Out")
    scale, drop, seed, family, dims = _sdpa_config(ins, attrs, rng)
    bthd = attrs.get("layout", "bhtd") == "bthd"
    causal = bool(attrs.get("causal", False))
    from paddle_tpu.parallel import flash_attention as fa

    t_axis = 1 if bthd else 2
    ring = _ring_config_t(q, k, t_axis)
    window = _windowed(attrs, q, k, bthd, ring)
    block = _block_masked(attrs, q, k, bthd, ring)
    selected, live, family, sel = _selected(ins, attrs, family, dims)
    if ring is not None:
        _note_dispatch("ring", "bwd", dims, parts=parts)
        mesh, ctx_axis, data_axis = ring
        from paddle_tpu.parallel import ring_attention as ra

        def f(q, k, v):
            if bthd:
                o = ra.ring_attention(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), mesh, seq_axis=ctx_axis,
                    scale=scale, bias=bias, data_axis=data_axis,
                    causal=causal, p_drop=float(drop), seed=seed)
                return jnp.swapaxes(o, 1, 2)
            return ra.ring_attention(
                q, k, v, mesh, seq_axis=ctx_axis, scale=scale, bias=bias,
                data_axis=data_axis, causal=causal, p_drop=float(drop),
                seed=seed,
            )

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(g.astype(q.dtype))
    elif family == "dense":
        _note_dispatch("dense", "bwd", dims, window=window,
                       block_diffusion=block, parts=parts, sel=sel)
        sd = seed if drop > 0.0 else None
        if bthd:
            eff_bias = fa._combined_causal_bias(
                bias, q.shape[1], k.shape[1]) if causal else bias

            def f(q, k, v):
                return fa._reference_attention_bthd(
                    q, k, v, eff_bias, scale, drop, sd).astype(q.dtype)
        else:
            def f(q, k, v):
                return fa._reference_attention(
                    q, k, v, bias, scale, drop, sd, causal=causal,
                    window=window, block_diffusion=block,
                    selected=selected).astype(q.dtype)

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(g.astype(q.dtype))
    else:
        bwd = (fa.flash_attention_bthd_bwd if bthd
               else functools.partial(fa.flash_attention_bwd, window=window,
                                      block_diffusion=block))
        # one call or the pair: the kernel layer's own answer, as the
        # entry point reads it (the op passes no q_block / k_block)
        form = None if bthd else fa.bhtd_bwd_form(
            dims[3], dims[1], dims[2], dh=dims[4], group=dims[3] // dims[5],
            dv=dims[6], itemsize=q.dtype.itemsize, p_drop=drop,
            block_diffusion=block)
        pe = {} if parts != "own" else dict(q_pe=_x(ins, "QPe"),
                                            k_pe=_x(ins, "KPe"))
        if selected is not None:
            pe = dict(selected=selected, live=live)
        dq, dk, dv, *d_pe = _on_mesh(
            lambda q, k, v, bias, out, lse, g, seed: bwd(
                q, k, v, bias, seed, out, lse, g, scale=scale,
                p_drop=drop, causal=causal, **pe),
            (q, k, v, bias, out, lse, g.astype(q.dtype)), seed, family,
            "bwd", dims, window, form, causal=causal, block_diffusion=block,
            parts=parts, sel=sel)
    if apart is not None:
        dq, dk, *d_pe = apart((dq, dk))
    grads = {"GRAD::Q": [dq], "GRAD::K": [dk], "GRAD::V": [dv]}
    if parts is not None:
        grads["GRAD::QPe"], grads["GRAD::KPe"] = [d_pe[0]], [d_pe[1]]
    return grads
