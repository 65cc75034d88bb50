"""What the per-family model tests (tests/test_<family>.py) share: host
copies of a scope, the perturbation that makes every parameter matter,
the built Program, and the plain float32 reference (perf/reference/*,
the files the benchmark's ``correct`` is decided by) as ONE jitted
computation each way."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.backward import append_backward
from perf.reference.common import weights_from_scope


def snapshot(scope):
    """Host copies of a scope's weights (a run donates its state)."""
    return {k: np.asarray(v) for k, v in weights_from_scope(scope).items()}


def perturb(scope, seed, rules):
    """Move a scope's variables away from their initial values, so that
    every parameter matters: ``rules`` is the family's list of (the
    names' suffixes, or a predicate of the name; ``new(value, r)``) in
    the order they draw from the one ``RandomState(seed)``; every rule
    that matches a name applies."""
    r = np.random.RandomState(seed)
    for n in scope.var_names():
        v = np.asarray(scope.find_var(n))
        for names, new in rules:
            if names(n) if callable(names) else n.endswith(names):
                scope.set(n, jnp.asarray(new(v, r), jnp.float32))


def moved(by):
    """A rule's ``new``: the value plus ``by`` standard normals."""
    return lambda v, r: v + by * r.randn(*v.shape)


def drawn(scale=1.0):
    """A rule's ``new``: ``scale`` standard normals, the value gone."""
    return lambda v, r: scale * r.randn(*v.shape)


def built(M, cfg, seed, optimizer=None):
    """(main, startup, model, grads) of ``M.build(cfg)``: with
    ``append_backward``'s (parameter, gradient) pairs, or under
    ``optimizer()`` (then ``grads`` is None)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        model = M.build(cfg)
        grads = None
        if optimizer is None:
            grads = append_backward(model["loss"])
        else:
            optimizer().minimize(model["loss"])
    return main, startup, model, grads


def highest(fn):
    """``fn`` of arrays as ONE jitted computation at "highest" matmul
    precision: op by op a reference of a few layers is some 450
    executables, two thirds of a family's gradient test at PR 67."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)
    return run


def reference(ref, w, cfg, feed, *args, **kw):
    """(``ref.forward``'s outputs, ``ref.loss``, its gradient by every
    weight), each through ``highest``."""
    want = highest(lambda w_: ref.forward(
        w_, cfg, feed["input_ids"], *args, **kw))(w)
    loss, grads = highest(jax.value_and_grad(
        lambda w_: ref.loss(w_, cfg, feed)))(w)
    return want, loss, grads


def moe_layer(experts, top_k, d_ff, held, x, weights=None, seed=3, name="m",
              grad=False, **kw):
    """(out, rows[, x@GRAD with ``grad``], {param: value}) of one
    ``layers.topk_moe`` layer as a family builds it (``kw``) on ``x``;
    ``weights``: the uncut layer's, cut to the ``held`` share."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = not grad
        out, _, _, rows, _ = layers.topk_moe(
            xv, experts, top_k, d_ff, name=name, held=held, **kw)
        if grad:
            append_backward(layers.reduce_sum(layers.square(out)))
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    for n, v in (weights or {}).items():
        if n in scope.var_names():
            if held and v.ndim == 3 and v.shape[0] == experts:
                v = v[held[0]:held[0] + held[1]]
            scope.set(n, jnp.asarray(v))
    w = snapshot(scope)
    got = exe.run(main, feed={"x": x}, scope=scope,
                  fetch_list=[out, rows] + ["x@GRAD"] * grad)
    return (*got, w)
