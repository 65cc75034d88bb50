"""python perf/tools/moe_rows_candidates.py [--out FILE]

On the chip, ALONE (no step around them): the passes of a held expert
layer over its row buffer at the two held cells' shapes, each as the
whole-buffer form PR 34 had and as a loop over the windows of live rows
(ops/moe_ops.over_live_rows) at several windows, and
the candidates for the token-major sums (XLA's scatter-add as the rows
lie, or group by group with the hints a group's order gives). Routing:
k distinct experts a token drawn evenly from all the router scores (a
sixteenth of the pairs live), from twice the held ones (half), or from
the held ones alone (all: the worst case). Prints one JSON object; ms a
call, the median of five stretches of 20 calls dispatched back to back
(host clock around one ``block_until_ready``). Exits 2 without a TPU."""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT

SHAPES = {  # n tokens, k a token, d, experts scored, held
    "qwen3next-train-s8192": (8192, 10, 2048, 512, 32),
    "joyai-train-s4096": (4096, 8, 2048, 256, 16),
}
WINDOWS = (128, 512, 2048)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/moe_rows_candidates.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("moe_rows_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.ops import moe_ops as gm

    def ms(fn, *a):
        # 20 calls dispatched back to back and one wait: the host's
        # part of a call (0.6 ms here) hides behind the device's
        f = jax.jit(fn)
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(20)]
            jax.block_until_ready(outs)
            took.append((time.perf_counter() - t0) * 1e3 / 20)
        return round(statistics.median(took), 4)

    def unwritten(shape, dtype):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            lambda out_ref: None,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
            name="rows.unwritten")()

    report = {"device": jax.devices()[0].device_kind}
    cases = [(cell, share, shape) for cell, shape in SHAPES.items()
             for share in ("even", "half", "all")]
    for cell, share, (n, k, d, e, held) in cases:
        r = np.random.RandomState(7)
        m = n * k
        # k distinct experts a token from a pool: all the router scores
        # (a sixteenth of the pairs live), twice the held ones, the held
        pool = {"even": e, "half": 2 * held, "all": held}[share]
        top_i = np.argsort(r.rand(n, pool), axis=1)[:, :k].astype(np.int32)
        flat = np.where(top_i.reshape(-1) < held, top_i.reshape(-1), held)
        order = jnp.asarray(np.argsort(flat, kind="stable"), jnp.int32)
        slot = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
        sizes = jnp.asarray(np.bincount(flat, minlength=held + 1)[:held],
                            jnp.int32)
        live = jnp.asarray(int((flat < held).sum()), jnp.int32)
        x = jnp.asarray(r.randn(n, d), jnp.bfloat16)
        ys = jnp.where(jnp.arange(m)[:, None] < live,
                       jnp.asarray(r.randn(m, d), jnp.bfloat16), 0)
        top_w = jnp.asarray(r.rand(n, k), jnp.float32)
        out = report[f"{cell} {share}"] = {"rows": m, "live": int(live)}

        out["zeros [m, d] bf16"] = ms(
            lambda v: jnp.zeros((m, d), jnp.bfloat16) + v, x[0, 0])
        out["gather_xs whole"] = ms(
            lambda x_, o: jnp.take(x_, o // k, axis=0), x, order)

        def slot_major(v, s):
            return jnp.take(v, s.T.reshape(-1), axis=0).reshape(k, n, -1)

        out["sum_pairs whole (slot-major gather)"] = ms(
            lambda y, s, tw: jnp.einsum(
                "knd,nk->nd", slot_major(y, s).astype(jnp.float32),
                tw).astype(y.dtype), ys, slot, top_w)
        out["d_w whole (slot-major gather)"] = ms(
            lambda y, s, g: jnp.einsum(
                "knd,nd->nk", slot_major(y, s), g,
                preferred_element_type=jnp.float32), ys, slot, x)

        for w in WINDOWS:
            def gather(x_, o, lv, w=w):
                return gm.over_live_rows(
                    lv, w, lambda r0, keep, buf: gm.put_rows(
                        buf, r0, jnp.where(keep, jnp.take(
                            x_, gm.rows_at(o, r0, w) // k, axis=0), 0)),
                    jnp.zeros((m, d), x_.dtype))

            def add(y, o, tw, lv, w=w, hint=False):
                def trip(r0, keep, acc):
                    pairs = gm.rows_at(o, r0, w)
                    v = gm.rows_at(y, r0, w).astype(jnp.float32) \
                        * jnp.take(tw.reshape(-1), pairs)[:, None]
                    return acc.at[pairs // k].add(
                        jnp.where(keep, v, 0.0), mode="promise_in_bounds",
                        indices_are_sorted=hint)
                return gm.over_live_rows(
                    lv, w, trip, jnp.zeros((n, d), jnp.float32)
                ).astype(y.dtype)

            def add_by_group(y, o, tw, sz, w=w, hint=True):
                # a trip never leaves one expert's group, where the
                # tokens are unique and ascending (a stable sort): the
                # scatter-add may be told so; what the window holds of
                # other groups goes past the end and is dropped
                ends = jnp.cumsum(sz)

                def group(g_, acc):
                    s0, s1 = ends[g_] - sz[g_], ends[g_]

                    def trip(j, acc):
                        r0 = s0 + j * w
                        at = jnp.minimum(r0, m - w)
                        rr = at + jnp.arange(w, dtype=jnp.int32)
                        keep = jnp.logical_and(rr >= r0, rr < s1)
                        pairs = gm.rows_at(o, at, w)
                        v = gm.rows_at(y, at, w).astype(jnp.float32) \
                            * jnp.take(tw.reshape(-1), pairs)[:, None]
                        idx = jnp.where(keep, pairs // k, n + rr)
                        return acc.at[idx].add(
                            v, mode="drop", unique_indices=True,
                            indices_are_sorted=hint)
                    return jax.lax.fori_loop(0, (sz[g_] + w - 1) // w, trip,
                                             acc)
                return jax.lax.fori_loop(
                    0, sz.shape[0], group, jnp.zeros((n, d), jnp.float32)
                ).astype(y.dtype)

            def d_w(y, o, g, lv, w=w):
                def trip(r0, keep, acc):
                    pairs = gm.rows_at(o, r0, w)
                    g_rows = jnp.take(g, pairs // k, axis=0).astype(
                        jnp.float32)
                    dots = jnp.sum(gm.rows_at(y, r0, w).astype(jnp.float32)
                                   * g_rows, axis=-1)
                    return acc.at[pairs].set(
                        jnp.where(keep[:, 0], dots, 0.0),
                        unique_indices=True, mode="promise_in_bounds")
                return gm.over_live_rows(lv, w, trip,
                                         jnp.zeros(m, jnp.float32))

            out[f"gather_xs windowed w{w}"] = ms(gather, x, order, live)
            if w == 512:
                # the windows written into a buffer nobody zeroed: what
                # lies behind them is whatever the memory held
                def gather_unwritten(x_, o, lv, w=w):
                    return gm.over_live_rows(
                        lv, w, lambda r0, keep, buf: gm.put_rows(
                            buf, r0, jnp.where(keep, jnp.take(
                                x_, gm.rows_at(o, r0, w) // k, axis=0), 0)),
                        unwritten((m, d), x_.dtype))

                out[f"gather_xs windowed w{w} into an unwritten buffer"] = \
                    ms(gather_unwritten, x, order, live)
            out[f"sum_pairs scatter-add w{w}"] = ms(add, ys, order, top_w,
                                                    live)
            if w <= 1024:
                want = jnp.einsum(
                    "knd,nk->nd", slot_major(ys, slot).astype(jnp.float32),
                    top_w)
                for fn, a in ((add, (ys, order, top_w, live)),
                              (add_by_group, (ys, order, top_w, sizes))):
                    err = float(jnp.abs(jax.jit(fn)(*a).astype(jnp.float32)
                                        - want).max())
                    assert err <= 0.02 * float(jnp.abs(want).max()), (
                        fn.__name__, w, err)
                out[f"sum_pairs by group, unique + sorted w{w}"] = ms(
                    add_by_group, ys, order, top_w, sizes)
                out[f"sum_pairs by group, unique w{w}"] = ms(
                    functools.partial(add_by_group, w=w, hint=False), ys,
                    order, top_w, sizes)
            out[f"d_w gather + dot + scatter w{w}"] = ms(d_w, ys, order, x,
                                                         live)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
