"""Time the FIRST CARRY of a held expert layer's row-major passes alone
on the chip: the loop over the windows of live rows
(``grouped_matmul.over_live_rows``) started from ``jnp.zeros`` (a fill
of the whole [n * k, width] buffer: what the program did before PR 63)
against started from ``grouped_matmul.unfilled`` (memory nothing
wrote), and a ``lax.cond`` on the live count between the two, for

- ``gather``: ``moe_dispatch``'s Xs (a window of tokens gathered by
  pair), [m, d];
- ``unit``: the experts' unit on a window of Gate and Up (SwiGLU; the
  plain relu^2 of Up for ``nemotron3nano``), [m, f];
- ``d_ys``: ``moe_combine_grad``'s walk (a window of the cotangent
  gathered by token, times the pair's weight into GRAD::Ys [m, d], its
  dot with Ys into GRAD::TopW [m]),

at the seven held cells' shapes ``[n, k, d, f, scored, held]`` with k
distinct experts a token drawn evenly from all the router scores (the
even share live: a sixteenth or an eighth), from twice the held ones
(half) or from the held ones alone (all).

    chiprun -- python benchmarks/moe_fill_candidates.py [--cells a,b] [--shares even,all]
    python benchmarks/moe_fill_candidates.py --lower DIR [--cells a]

ms a call as ``perf/tools/moe_rows_candidates.py`` prints them: the
median of five stretches of 20 calls (fewer where 20 results pass 4 GB)
dispatched back to back, host clock around one ``block_until_ready``.
The unfilled form is held to the filled one's live rows first. One JSON
object, to chiprun_out/moe_fill_candidates.json (and a line a shape and
share as it is done). ``--lower DIR`` needs no chip: it
compiles every form of the first cell asked for a described v5e with
XLA's dump under DIR, one sub-directory a form, for reading the
compiled loop (is the carry copied, is the update in place). How PR 63
chose (PERF.md section 6)."""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "moe_fill_candidates.json")

SHAPES = {  # n tokens, k a token, d, f, experts scored, held
    "sdar-train-s4096": (8192, 8, 2048, 768, 128, 16),
    "qwen3next-train-s8192": (8192, 10, 2048, 512, 512, 32),
    "lfm2moe-train-s8192": (8192, 4, 2048, 1536, 64, 8),
    "smallthinker-train-s16384": (16384, 6, 2560, 768, 64, 8),
    "joyai-train-s4096": (4096, 8, 2048, 768, 256, 16),
    "nemotron3nano-train-s4096": (4096, 6, 2688, 1856, 128, 8),
    "laguna-train-s8192": (8192, 8, 2048, 512, 256, 16),
}
PLAIN = ("nemotron3nano-train-s4096",)


def forms(cell, jnp):
    """{pass: fn(init, *operands)} and the operands' makers: each pass
    as ``ops/moe_ops`` writes it, its first carry ``init(shape, dtype)``
    the caller's."""
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.parallel import grouped_matmul as gm

    n, k, d, f, _, held = SHAPES[cell]
    m = n * k
    w = gm.live_window(m, -(-m * held // SHAPES[cell][4]))
    unit = moe_ops._relu2 if cell in PLAIN else moe_ops._swiglu

    def gather(init, x, order, live):
        return gm.over_live_rows(
            live, w, lambda r0, keep, buf: gm.put_rows(
                buf, r0, jnp.where(keep, jnp.take(
                    x, gm.rows_at(order, r0, w) // k, axis=0), 0)),
            init((m, d), x.dtype))

    def unit_pass(init, gate, up, live):
        def trip(r0, keep, buf):
            args = (gm.rows_at(up, r0, w),)
            if cell not in PLAIN:
                args = (gm.rows_at(gate, r0, w),) + args
            return gm.put_rows(buf, r0, jnp.where(keep, unit(*args), 0))
        return gm.over_live_rows(live, w, trip, init((m, f), up.dtype))

    def d_ys(init, ys, top_w, order, g, live):
        pair_w = top_w.reshape(-1)

        def trip(r0, keep, carry):
            out, d_w = carry
            pairs = gm.rows_at(order, r0, w)
            g_rows = jnp.take(g, pairs // k, axis=0).astype(jnp.float32)
            out = gm.put_rows(out, r0, jnp.where(
                keep, g_rows * jnp.take(pair_w, pairs)[:, None], 0.0))
            dots = jnp.sum(gm.rows_at(ys, r0, w).astype(jnp.float32)
                           * g_rows, axis=-1)
            return out, d_w.at[pairs].set(
                jnp.where(keep[:, 0], dots, 0.0), unique_indices=True,
                mode="promise_in_bounds")
        return gm.over_live_rows(
            live, w, trip, (init((m, d), ys.dtype),
                            jnp.zeros(m, jnp.float32)))[0]

    return w, {"gather": gather, "unit": unit_pass, "d_ys": d_ys}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--shares", default="even,half,all")
    ap.add_argument("--lower", default="")
    ap.add_argument("--cond-at", type=float, default=0.0,
                    help="also time a lax.cond that takes the filled "
                         "loop from this share of the buffer live")
    args = ap.parse_args()
    cells = [c for c in args.cells.split(",") if c]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.parallel import grouped_matmul as gm

    sharding = None
    if args.lower:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        jax.default_backend = lambda: "tpu"
        jax.config.update("jax_enable_x64", False)
        cells = cells[:1]
    elif jax.default_backend() != "tpu":
        print("moe_fill_candidates: no TPU", file=sys.stderr)
        return 2

    def ms(f, *a):
        first = jax.block_until_ready(f(*a))
        # (a stretch's results are all alive at once: under 4 GB)
        calls = max(4, min(20, 2**32 // first.nbytes))
        del first
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(calls)]
            jax.block_until_ready(outs)
            took.append((time.perf_counter() - t0) * 1e3 / calls)
            del outs
        return round(statistics.median(took), 4)

    def lower(name, fn, *a):
        spec = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
                for v in a]
        where = os.path.join(args.lower, name)
        os.makedirs(where, exist_ok=True)
        jax.jit(fn).lower(*spec).compile(compiler_options={
            "xla_dump_to": where, "xla_dump_hlo_as_text": True})
        return where

    report = {}
    if not args.lower:
        report["device"] = jax.devices()[0].device_kind
    for cell in cells:
        n, k, d, f, scored, held = SHAPES[cell]
        m = n * k
        w, passes = forms(cell, jnp)
        for share in args.shares.split(","):
            r = np.random.RandomState(7)
            pool = {"even": scored, "half": 2 * held, "all": held}[share]
            top_i = np.argsort(r.rand(n, pool), axis=1)[:, :k]
            flat = np.where(top_i.reshape(-1) < held,
                            top_i.reshape(-1), held)
            order = jnp.asarray(np.argsort(flat, kind="stable"), jnp.int32)
            live = jnp.asarray(int((flat < held).sum()), jnp.int32)
            x = jnp.asarray(r.randn(n, d), jnp.bfloat16)
            gate = jnp.asarray(r.randn(m, f), jnp.bfloat16)
            up = jnp.asarray(r.randn(m, f), jnp.bfloat16)
            ys = jnp.asarray(r.randn(m, d), jnp.bfloat16)
            top_w = jnp.asarray(r.rand(n, k), jnp.float32)
            operands = {"gather": (x, order, live),
                        "unit": (gate, up, live),
                        "d_ys": (ys, top_w, order, x, live)}
            out = report[f"{cell} {share}"] = {
                "rows": m, "live": int(live), "window": w}
            if not args.lower:
                out["zeros [m, d]"] = ms(jax.jit(
                    lambda v: jnp.zeros((m, d), jnp.bfloat16) + v), x[0, 0])
                out["zeros [m, f]"] = ms(jax.jit(
                    lambda v: jnp.zeros((m, f), jnp.bfloat16) + v), x[0, 0])
                out["unfilled [m, d]"] = ms(jax.jit(
                    lambda: gm.unfilled((m, d), jnp.bfloat16)))
            for name, fn in passes.items():
                a = operands[name]

                def filled(*a, fn=fn):
                    return fn(jnp.zeros, *a)

                def unfilled(*a, fn=fn):
                    return fn(gm.unfilled, *a)

                def cond(*a, fn=fn):
                    return jax.lax.cond(
                        a[-1] >= int(args.cond_at * m),
                        lambda *b: fn(jnp.zeros, *b),
                        lambda *b: fn(gm.unfilled, *b), *a)

                each = {"filled": filled, "unfilled": unfilled}
                if args.cond_at:
                    each["cond"] = cond
                if args.lower:
                    for form, g in each.items():
                        out[f"{name} {form}"] = lower(
                            f"{name}-{share}-{form}", g, *a)
                    continue
                jitted = {form: jax.jit(g) for form, g in each.items()}
                want = np.asarray(jitted["filled"](*a)[:int(live)],
                                  np.float32)
                for form, g in jitted.items():
                    got = np.asarray(g(*a)[:int(live)], np.float32)
                    assert np.array_equal(got, want), (cell, name, form)
                    out[f"{name} {form}"] = ms(g, *a)
            if not args.lower:
                print(f"{cell} {share}", json.dumps(out), flush=True)
                os.makedirs(os.path.dirname(OUT), exist_ok=True)
                with open(OUT, "w") as fh:
                    json.dump(report, fh, indent=1)
    if args.lower:
        print(json.dumps(report, indent=1))
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
