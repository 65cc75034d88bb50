"""Optimizer update ops.

Reference kernels: paddle/fluid/operators/optimizers/{sgd_op.cc,
momentum_op.cc, adam_op.cc, adagrad_op.cc, rmsprop_op.cc, lamb_op.cc,
ftrl_op.cc, lars_momentum_op.cc}. Updates are functional: the op outputs the
new parameter/accumulator values under the same variable names; the lowering
rebinds, and XLA's buffer donation makes it in-place in HBM.
"""

from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.registry import register_op


def _g(ins, slot):
    v = ins.get(slot)
    return v[0] if v else None


@register_op("sgd", no_grad=True)
def _sgd(ins, attrs):
    p, g, lr = _g(ins, "Param"), _g(ins, "Grad"), _g(ins, "LearningRate")
    return {"ParamOut": [p - lr.reshape(()).astype(p.dtype) * g.astype(p.dtype)]}


@register_op("momentum", no_grad=True)
def _momentum(ins, attrs):
    p, g, v = _g(ins, "Param"), _g(ins, "Grad"), _g(ins, "Velocity")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    mu = attrs.get("mu", 0.9)
    g = g.astype(p.dtype)
    v_new = mu * v + g
    if attrs.get("use_nesterov", False):
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    return {"ParamOut": [p_new], "VelocityOut": [v_new]}


@register_op("lars_momentum", no_grad=True)
def _lars_momentum(ins, attrs):
    p, g, v = _g(ins, "Param"), _g(ins, "Grad"), _g(ins, "Velocity")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    decay = attrs.get("lars_weight_decay", 0.0005)
    g = g.astype(p.dtype)
    pn = jnp.sqrt(jnp.sum(jnp.square(p)))
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(
        (pn > 0) & (gn > 0), lr * coeff * pn / (gn + decay * pn + 1e-12), lr
    )
    v_new = mu * v + local_lr * (g + decay * p)
    return {"ParamOut": [p - v_new], "VelocityOut": [v_new]}


def adam_moments(g, m1, m2, b1, b2):
    """(Moment1Out, Moment2Out) for the gradient ``g`` in the moments'
    dtype. This and the four functions below are Adam's arithmetic, for
    the ``adam`` / ``adamw`` ops and for the experts' weight-gradient
    kernel, which runs them on its accumulator's tiles
    (parallel/grouped_matmul.tgmm_adam)."""
    return b1 * m1 + (1 - b1) * g, b2 * m2 + (1 - b2) * jnp.square(g)


def adam_lr_t(lr, b1pn, b2pn):
    """The learning rate with this step's bias correction in it."""
    return lr * jnp.sqrt(1 - b2pn.reshape(())) / (1 - b1pn.reshape(()))


def adam_param(p, m1n, m2n, lr_t, eps):
    """ParamOut of ``adam`` from the new moments."""
    upd = lr_t.astype(p.dtype) * (m1n / (jnp.sqrt(m2n) + eps)).astype(p.dtype)
    return p - upd


def adamw_param(p_new, p, lr_decay):
    """ParamOut of ``adamw``: ``adam``'s less the decoupled decay, the
    learning rate times ``weight_decay`` of the weight as it was."""
    return p_new - lr_decay * p


def adam_step(p, g, m1, m2, lr_t, b1, b2, eps, lr_decay=None):
    """(ParamOut, Moment1Out, Moment2Out) of ``adam`` (``adamw`` with
    ``lr_decay``, its learning rate times its decay) from the learning
    rate with the bias correction in it: the three functions above in
    the ops' order, for a caller that holds ``lr_t`` already."""
    m1n, m2n = adam_moments(g, m1, m2, b1, b2)
    new = adam_param(p, m1n, m2n, lr_t, eps)
    if lr_decay is not None:
        new = adamw_param(new, p, lr_decay)
    return new, m1n, m2n


@register_op("adam", no_grad=True)
def _adam(ins, attrs):
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    m1, m2 = _g(ins, "Moment1"), _g(ins, "Moment2")
    b1p, b2p = _g(ins, "Beta1Pow"), _g(ins, "Beta2Pow")
    lr = _g(ins, "LearningRate").reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m1n, m2n = adam_moments(g.astype(m1.dtype), m1, m2, b1, b2)
    b1pn, b2pn = b1p * b1, b2p * b2
    return {
        "ParamOut": [adam_param(p, m1n, m2n, adam_lr_t(lr, b1pn, b2pn), eps)],
        "Moment1Out": [m1n],
        "Moment2Out": [m2n],
        "Beta1PowOut": [b1pn],
        "Beta2PowOut": [b2pn],
    }


@register_op("adamw", no_grad=True)
def _adamw(ins, attrs):
    p = _g(ins, "Param")
    wd = attrs.get("weight_decay", 0.01)
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    outs = _adam(ins, attrs)
    outs["ParamOut"][0] = adamw_param(outs["ParamOut"][0], p, lr * wd)
    return outs


@register_op("adagrad", no_grad=True)
def _adagrad(ins, attrs):
    p, g, m = _g(ins, "Param"), _g(ins, "Grad"), _g(ins, "Moment")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    eps = attrs.get("epsilon", 1e-6)
    g = g.astype(p.dtype)
    m_new = m + jnp.square(g)
    p_new = p - lr * g / (jnp.sqrt(m_new) + eps)
    return {"ParamOut": [p_new], "MomentOut": [m_new]}


@register_op("rmsprop", no_grad=True)
def _rmsprop(ins, attrs):
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    ms, mom = _g(ins, "MeanSquare"), _g(ins, "Moment")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mu = attrs.get("momentum", 0.0)
    g = g.astype(p.dtype)
    ms_new = rho * ms + (1 - rho) * jnp.square(g)
    if attrs.get("centered", False):
        mg = _g(ins, "MeanGrad")
        mg_new = rho * mg + (1 - rho) * g
        denom = ms_new - jnp.square(mg_new) + eps
        mom_new = mu * mom + lr * g / jnp.sqrt(denom)
        return {
            "ParamOut": [p - mom_new],
            "MeanSquareOut": [ms_new],
            "MomentOut": [mom_new],
            "MeanGradOut": [mg_new],
        }
    mom_new = mu * mom + lr * g / jnp.sqrt(ms_new + eps)
    return {
        "ParamOut": [p - mom_new],
        "MeanSquareOut": [ms_new],
        "MomentOut": [mom_new],
    }


@register_op("lamb", no_grad=True)
def _lamb(ins, attrs):
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    m1, m2 = _g(ins, "Moment1"), _g(ins, "Moment2")
    b1p, b2p = _g(ins, "Beta1Pow"), _g(ins, "Beta2Pow")
    lr = _g(ins, "LearningRate").reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    g = g.astype(m1.dtype)
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * jnp.square(g)
    mhat = m1n / (1 - b1p.reshape(()))
    vhat = m2n / (1 - b2p.reshape(()))
    r = mhat / (jnp.sqrt(vhat) + eps) + wd * p.astype(m1.dtype)
    pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
    rn = jnp.sqrt(jnp.sum(jnp.square(r.astype(jnp.float32))))
    trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
    p_new = p - (lr * trust).astype(p.dtype) * r.astype(p.dtype)
    return {
        "ParamOut": [p_new],
        "Moment1Out": [m1n],
        "Moment2Out": [m2n],
        "Beta1PowOut": [b1p * b1],
        "Beta2PowOut": [b2p * b2],
    }


@register_op("ftrl", no_grad=True)
def _ftrl(ins, attrs):
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    sq, lin = _g(ins, "SquaredAccumulator"), _g(ins, "LinearAccumulator")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    g = g.astype(p.dtype)
    sq_new = sq + jnp.square(g)
    sigma = (sq_new**-power - sq**-power) / lr
    lin_new = lin + g - sigma * p
    pre = jnp.clip(lin_new, -l1, l1) - lin_new
    denom = sq_new**-power / lr + 2 * l2
    p_new = pre / denom
    return {
        "ParamOut": [p_new],
        "SquaredAccumOut": [sq_new],
        "LinearAccumOut": [lin_new],
    }


@register_op("decayed_adagrad", no_grad=True)
def _decayed_adagrad(ins, attrs):
    p, g, m = _g(ins, "Param"), _g(ins, "Grad"), _g(ins, "Moment")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g = g.astype(p.dtype)
    m_new = decay * m + (1 - decay) * jnp.square(g)
    return {"ParamOut": [p - lr * g / (jnp.sqrt(m_new) + eps)], "MomentOut": [m_new]}


@register_op("adamax", no_grad=True)
def _adamax(ins, attrs):
    """Adamax: Adam with an infinity-norm second moment (reference:
    operators/optimizers/adamax_op.cc; optimizer.py AdamaxOptimizer)."""
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    m, u = _g(ins, "Moment"), _g(ins, "InfNorm")
    b1p = _g(ins, "Beta1Pow")
    lr = _g(ins, "LearningRate").reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    g = g.astype(m.dtype)
    m_new = b1 * m + (1 - b1) * g
    u_new = jnp.maximum(b2 * u, jnp.abs(g))
    b1pn = b1p * b1
    lr_t = (lr / (1 - b1pn.reshape(()))).astype(p.dtype)
    p_new = p - lr_t * (m_new / (u_new + eps)).astype(p.dtype)
    return {"ParamOut": [p_new], "MomentOut": [m_new],
            "InfNormOut": [u_new], "Beta1PowOut": [b1pn]}


@register_op("adadelta", no_grad=True)
def _adadelta(ins, attrs):
    """Adadelta (reference: operators/optimizers/adadelta_op.cc): the
    classic learning-rate-free update from accumulated squared grads and
    squared updates."""
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    eg2, edx2 = _g(ins, "AvgSquaredGrad"), _g(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g = g.astype(p.dtype)
    eg2_new = rho * eg2 + (1 - rho) * jnp.square(g)
    upd = -jnp.sqrt((edx2 + eps) / (eg2_new + eps)) * g
    edx2_new = rho * edx2 + (1 - rho) * jnp.square(upd)
    return {"ParamOut": [p + upd], "AvgSquaredGradOut": [eg2_new],
            "AvgSquaredUpdateOut": [edx2_new]}


@register_op("proximal_gd", no_grad=True)
def _proximal_gd(ins, attrs):
    """Proximal gradient descent with l1/l2 regularization (reference:
    operators/optimizers/proximal_gd_op.cc)."""
    p, g = _g(ins, "Param"), _g(ins, "Grad")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    prox = p - lr * g.astype(p.dtype)
    if l1 > 0:
        prox = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
    return {"ParamOut": [prox / (1.0 + lr * l2)]}


@register_op("proximal_adagrad", no_grad=True)
def _proximal_adagrad(ins, attrs):
    """Proximal Adagrad (reference:
    operators/optimizers/proximal_adagrad_op.cc)."""
    p, g, m = _g(ins, "Param"), _g(ins, "Grad"), _g(ins, "Moment")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    g = g.astype(p.dtype)
    m_new = m + jnp.square(g)
    denom = jnp.sqrt(m_new)
    # zero-grad elements have zero moment on step one: their update is 0,
    # not lr*0/0 = NaN
    step = jnp.where(denom > 0, lr * g / jnp.maximum(denom, 1e-30), 0.0)
    prox = p - step
    # the reference applies the SCALAR learning rate in the l1 shrink and
    # l2 denominator (proximal_adagrad_op.h), not the adaptive rate
    if l1 > 0:
        prox = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
    return {"ParamOut": [prox / (1.0 + lr * l2)], "MomentOut": [m_new]}


@register_op("dgc_momentum", no_grad=True)
def _dgc_momentum(ins, attrs):
    """Fused DGC + momentum update (reference: operators/dgc_op.h
    compress stage + the momentum op that consumes the sparse-allreduced
    gradient; sparse_all_reduce_op_handle.h:30). One op instead of the
    reference's dgc -> sparse allreduce -> momentum chain: the compress /
    exchange / decode happens in paddle_tpu.parallel.dgc, and the
    decoded gradient immediately feeds the velocity update, all inside
    the same XLA program.

    When a data axis is in SPMD scope the (index, value) exchange runs
    as a real all_gather over that axis inside shard_map with
    combine='mean' — in the GSPMD whole-program path the incoming
    gradient is already globally reduced, so every worker sends the same
    selection and the mean restores the right magnitude. The
    sum-combining local-gradient form is exercised directly through
    parallel.dgc.dgc_step in a manually shard_mapped step."""
    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.core import interp as _interp
    from paddle_tpu.parallel import dgc as _dgc

    p, g = _g(ins, "Param"), _g(ins, "Grad")
    u, v = _g(ins, "U"), _g(ins, "V")
    vel = _g(ins, "Velocity")
    lr = _g(ins, "LearningRate").reshape(()).astype(p.dtype)
    step = _g(ins, "CurrentStep").reshape(())
    mu = float(attrs.get("mu", 0.9))
    use_nesterov = bool(attrs.get("use_nesterov", False))
    sparsity = tuple(attrs.get("sparsity", (0.999,)))
    rampup_begin = float(attrs.get("rampup_begin_step", 0.0))
    rampup = float(attrs.get("rampup_step", 1.0))
    clip_norm = attrs.get("local_grad_clip_norm", None)

    g = g.astype(jnp.float32)
    if clip_norm is not None:
        g = _dgc.clip_by_norm_rampup(
            g, step, clip_norm=float(clip_norm),
            rampup_begin_step=rampup_begin)

    ctx = _interp.spmd_ctx()
    if ctx is not None and ctx.data_axis is not None:
        # composed (slice, dp) tuples gather over the product axis —
        # one exchange spanning DCN x ICI, like the 2-level allreduce
        axis = ctx.data_axis

        def _exchange(g_, u_, v_, step_):
            return _dgc.dgc_step(
                g_, u_, v_, step_, momentum=mu, sparsity=sparsity,
                rampup_begin_step=rampup_begin, rampup_step=rampup,
                use_nesterov=use_nesterov, axis=axis, combine="mean")

        # replicated in/out: the exchange is over the axis name only
        dec, u_new, v_new = jax.shard_map(
            _exchange, mesh=ctx.mesh,
            in_specs=(P(), P(), P(), P()),
            out_specs=(P(), P(), P()),
        )(g, u, v, step)
    else:
        dec, u_new, v_new = _dgc.dgc_step(
            g, u, v, step, momentum=mu, sparsity=sparsity,
            rampup_begin_step=rampup_begin, rampup_step=rampup,
            use_nesterov=use_nesterov, axis=None)

    dec = dec.astype(p.dtype)
    vel_new = mu * vel + dec
    if use_nesterov:
        p_new = p - (dec + mu * vel_new) * lr
    else:
        p_new = p - lr * vel_new
    return {"ParamOut": [p_new], "VelocityOut": [vel_new],
            "UOut": [u_new.astype(u.dtype)], "VOut": [v_new.astype(v.dtype)]}
