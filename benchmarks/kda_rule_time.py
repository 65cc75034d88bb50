"""Time ``kda.rule.fwd`` / ``kda.rule.bwd`` (the delta rule with a decay
a key feature, parallel/gated_delta_rule.py) alone on the chip at
kimilinear-train-s4096's call (b1 t4096, 32 heads of 128 with keys of
their own, bf16: 256 grid steps of 2 heads x 8 chunks), beside
``gdn.rule.*`` at the same shape with one decay a head and the chunked
XLA form of the same call (ops/linear_attention_ops._feature_parts).

    chiprun -- python benchmarks/kda_rule_time.py [--t 4096 --heads 32]

Each kernel pass is held to the float32 recurrence first, at 512
positions of 2 heads, with gates that take G below -200 inside a chunk
and with mild ones (``err``: the largest difference over the largest
entry of o and of each gradient). Then ms a call: the median of five
stretches of 10 calls dispatched back to back, on the host's clock.
``--tiny`` rehearses it on the CPU through the interpreter. Writes
``chiprun_out/kda_rule_time.json``."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import linear_attention_ops as lin  # noqa: E402
from paddle_tpu.parallel import gated_delta_rule as gdr  # noqa: E402

BF = jnp.bfloat16


def draw(seed, t, h, dt, feature=True):
    r = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(r.randn(1, t, h, 128), BF) for _ in range(3))
    a = r.randn(1, t, h, 128 if feature else 1) * 0.3 + np.log(np.expm1(dt))
    g = -16.0 * jax.nn.softplus(jnp.asarray(a, jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(r.randn(1, t, h), jnp.float32))
    return q, k, v, (g if feature else g[..., 0]), beta


def passes(tile):
    fwd = jax.jit(lambda *a: gdr.gated_delta_rule_fwd(*a, tile))
    bwd = jax.jit(lambda *a: gdr.gated_delta_rule_bwd(*a, tile))
    return fwd, bwd


def xla_passes():
    def fwd(q, k, v, g, beta):
        parts = lin._chunk_parts(
            *lin._chunk_inputs(q, k, v, g, beta, 64, 1e-6), q.dtype)
        o, states = lin._chunk_scan(parts, q.dtype)
        return lin._unchunked(o, q.shape[1], v.dtype), states

    def bwd(q, k, v, g, beta, states, do):
        # the grad op with no tile: one recomputation, a reverse scan and
        # jax's transpose of the parallel part
        tile, lin._kernel_tile = lin._kernel_tile, lambda *a: None
        try:
            return lin._gated_delta_rule_grad(
                {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                 "States": [states], "GRAD::Out": [do]}, {})
        finally:
            lin._kernel_tile = tile

    return jax.jit(fwd), jax.jit(bwd)


def errors(t, dt):
    """Largest differences of the kernels from the float32 recurrence
    over the largest entry: o, dq, dk, dv, dg, dbeta."""
    a = draw(7, t, 2, dt)
    f32 = tuple(x.astype(jnp.float32) for x in a)
    w = jnp.asarray(np.random.RandomState(1).randn(1, t, 2, 128), BF)
    ref = lin.recurrent_gated_delta_rule(*f32)
    grads = jax.grad(lambda *x: jnp.sum(
        lin.recurrent_gated_delta_rule(*x) * w.astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))(*f32)
    fwd, bwd = passes(gdr.kda_tile(t, 2, 2, 128, 128, 64, BF))
    o, states = fwd(*a)
    got = (o,) + tuple(bwd(*a, states, w))
    return {"g_min_in_a_chunk": float(jnp.min(jnp.cumsum(
        a[3][:, :64], 1))), **{n: float(
            jnp.max(jnp.abs(x - y.astype(jnp.float32)))
            / jnp.max(jnp.abs(x))) for n, x, y in zip(
            ("o", "dq", "dk", "dv", "dg", "dbeta"), (ref,) + grads, got)}}


def ms_a_call(f, args, stretches=5, calls=10):
    jax.block_until_ready(f(*args))
    took = []
    for _ in range(stretches):
        t0 = time.perf_counter()
        outs = [f(*args) for _ in range(calls)]
        jax.block_until_ready(outs)
        took.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(took)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--no-xla", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        gdr._INTERPRET = True
        args.t, args.heads = 128, 2
    t, h = args.t, args.heads
    out = {"device": jax.devices()[0].device_kind, "t": t, "heads": h,
           "err_strong": errors(128 if args.tiny else 512, 0.5),
           "err_mild": errors(128 if args.tiny else 512, 0.002)}
    print(json.dumps(out), flush=True)
    forms = {
        "kda.rule": (passes(gdr.kda_tile(t, h, h, 128, 128, 64, BF)),
                     draw(0, t, h, 0.02)),
        "gdn.rule": (passes(gdr.gdn_tile(t, h, h, 128, 128, 64, BF)),
                     draw(0, t, h, 0.02, feature=False))}
    if not args.no_xla:
        forms["kda.chunked_xla"] = (xla_passes(), draw(0, t, h, 0.02))
    do = jnp.asarray(np.random.RandomState(2).randn(1, t, h, 128), BF)
    for name, ((fwd, bwd), a) in forms.items():
        _, states = fwd(*a)
        out[name] = {"fwd_ms": ms_a_call(fwd, a),
                     "bwd_ms": ms_a_call(bwd, a + (states, do))}
        print(name, out[name], flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_rule_time.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
