"""Time the gated short convolution's candidates alone on the chip, at
lfm2moe-train-s8192's call (b1 t8192, 2048 channels a range, 3 taps,
bf16).

    chiprun -- python benchmarks/sconv_candidates.py [--tiles 1024,256,32 ...]

Forward and backward of: the XLA form
(ops/linear_attention_ops._gated_conv_xla and its ``jax.vjp``: the
composition in float32 ops), the mixer COMPOSED from what the repo had
before the op (three slices, B * u, the ``gdn.conv.*`` kernels without
activation, C * c, jax's vjp of those), and the ``sconv.gated.*``
kernels (paddle_tpu/parallel/causal_conv.py) at each candidate (rows of
a block, lanes of a block, rows of a pass), each held to the XLA form's
results first; ms a call, the median of five stretches of 20 calls
dispatched back to back (host clock around one ``block_until_ready``:
it reads nothing under 0.2 ms, a dispatch's own time), for the tile
``conv_tile`` answers also ``--chain`` calls on as many distinct
operands inside ONE jitted function (one dispatch, the kernels back to
back on the device: ``chain_*_ms``), and the bytes the mathematics
needs (perf/flops_lfm2moe.sconv_cost: 4
passes of [t, c] forward, 7 backward) over that time. The table goes to
chiprun_out/sconv_candidates.json. How ``conv_tile(gated=True)``'s
answer was checked (PERF.md section 6, PR 48). Needs a TPU.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "sconv_candidates.json")
TILES = ("1024,256,32", "1024,512,32", "512,512,32", "2048,256,32",
         "512,256,32", "1024,128,32", "1024,256,64", "1024,256,16")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", nargs="*", default=list(TILES))
    ap.add_argument("--shape", default="1,8192,2048,3")
    ap.add_argument("--chain", type=int, default=12)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("sconv_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.ops import linear_attention_ops as L
    from paddle_tpu.parallel import causal_conv as cc

    def ms(fn, *a):
        f = jax.jit(fn)
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(20)]
            jax.block_until_ready(outs)
            del outs
            took.append((time.perf_counter() - t0) * 1e3 / 20)
        return round(statistics.median(took), 4)

    def chain_ms(fn, *lists):
        """ms a call of ``fn`` over ``len(lists[0])`` distinct operands
        in one jitted function (a custom call is not CSE'd)."""
        n = len(lists[0])
        f = jax.jit(lambda w, xs, *rest: [
            fn(xs[i], w, *(r[i] for r in rest)) for i in range(n)])
        jax.block_until_ready(f(w, *lists))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(w, *lists))
            took.append((time.perf_counter() - t0) * 1e3 / n)
        return round(statistics.median(took), 4)

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    b, t, c, taps = (int(v) for v in args.shape.split(","))
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(b, t, 3 * c), jnp.bfloat16)
    dy = jnp.asarray(r.randn(b, t, c), jnp.bfloat16)
    w = jnp.asarray(r.uniform(-1, 1, (c, taps)) * taps ** -0.5, jnp.float32)
    plain = cc.conv_tile(t, c, taps, x.dtype)

    def composed(x, w):
        gate_b, gate_c, u = jnp.split(x, 3, axis=-1)
        return gate_c * cc.causal_conv_fwd(gate_b * u, w, plain, "")

    def composed_bwd(x, w, dy):
        """What ``append_backward`` would emit for the composition: the
        multiplies' and the split's vjps around ``gdn.conv.bwd``."""
        gate_b, gate_c, u = jnp.split(x, 3, axis=-1)
        v = gate_b * u
        conv = cc.causal_conv_fwd(v, w, plain, "")
        dv, dw = cc.causal_conv_bwd(v, w, dy * gate_c, plain, "")
        return jnp.concatenate([dv * u, dy * conv, dv * gate_b], -1), dw

    def xla_bwd(x, w, dy):
        return jax.vjp(L._gated_conv_xla, x, w)[1](dy)

    want = jax.jit(L._gated_conv_xla)(x, w)
    want_dx, want_dw = jax.jit(xla_bwd)(x, w, dy)
    fwd_bytes, bwd_bytes = 4 * b * t * c * 2, 7 * b * t * c * 2

    def gbs(row):
        for side, n in (("fwd", fwd_bytes), ("bwd", bwd_bytes)):
            row[f"{side}_gb_s"] = round(n / row[f"{side}_ms"] / 1e6, 1)
        return row

    report = {"device": jax.devices()[0].device_kind,
              "shape": f"b{b} t{t} c{c} taps{taps}",
              "needed_mb": [fwd_bytes / 1e6, bwd_bytes / 1e6],
              "xla": gbs({"fwd_ms": ms(L._gated_conv_xla, x, w),
                          "bwd_ms": ms(xla_bwd, x, w, dy)}),
              "conv_tile": cc.conv_tile(t, c, taps, x.dtype, gated=True),
              "kernels": {}}
    if plain:
        report["composed"] = gbs({
            "y_err": worst(jax.jit(composed)(x, w), want),
            "dx_err": worst(jax.jit(composed_bwd)(x, w, dy)[0], want_dx),
            "fwd_ms": ms(composed, x, w),
            "bwd_ms": ms(composed_bwd, x, w, dy)})
    picked = report["conv_tile"]
    if picked and args.chain:
        xs = [x + 0 for _ in range(args.chain)]
        dys = [dy + 0 for _ in range(args.chain)]
        report["chained"] = gbs({
            "calls": args.chain,
            "fwd_ms": chain_ms(
                lambda x, w: cc.gated_conv_fwd(x, w, tuple(picked)), xs),
            "bwd_ms": chain_ms(
                lambda x, w, dy: cc.gated_conv_bwd(x, w, dy, tuple(picked)),
                xs, dys)})
        del xs, dys
    print(json.dumps(report), flush=True)
    for spec in args.tiles:
        rows, lanes, per_pass = (int(v) for v in spec.split(","))
        cc._PASS_ROWS = per_pass
        tile = (rows, lanes)
        row = {"vmem_mb": round(cc._vmem_bytes(rows, lanes, taps, True)
                                / 2 ** 20, 2)}
        try:
            y = cc.gated_conv_fwd(x, w, tile)
            dx, dw = cc.gated_conv_bwd(x, w, dy, tile)
            row.update(y_err=worst(y, want), dx_err=worst(dx, want_dx),
                       dw_err=worst(dw, want_dw))
            del y, dx, dw
            row["fwd_ms"] = ms(lambda x, w: cc.gated_conv_fwd(x, w, tile),
                               x, w)
            row["bwd_ms"] = ms(
                lambda x, w, dy: cc.gated_conv_bwd(x, w, dy, tile), x, w, dy)
            gbs(row)
        except Exception as e:      # a tile Mosaic refuses: say so, go on
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        report["kernels"][spec] = row
        print(spec, json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
