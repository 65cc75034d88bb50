"""Time the causal convolution's candidates alone on the chip, at
qwen3next-train-s8192's call (b1 t8192 c8192, 4 taps, bf16, silu).

    chiprun -- python benchmarks/conv_candidates.py [--tiles 512,512,64 ...]

Forward and backward of: the XLA form
(ops/linear_attention_ops._conv_xla and its ``jax.vjp``), and the
``gdn.conv.*`` kernels (paddle_tpu/parallel/causal_conv.py) at each
candidate (rows of a block, lanes of a block, rows of a pass), and the
forward with its halo carried in VMEM over a sequential t axis instead
of read by a second BlockSpec, each held to the XLA form's results
first; ms a call, the median of five stretches
of 20 calls dispatched back to back (host clock around one
``block_until_ready``). The table goes to
chiprun_out/conv_candidates.json. How ``conv_tile``'s answer and
``_PASS_ROWS`` were chosen (PERF.md section 6, PR 37). Needs a TPU.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "conv_candidates.json")
TILES = ("512,512,64", "512,512,32", "512,512,16", "1024,512,32",
         "256,512,32", "512,256,64", "512,256,32", "1024,256,64",
         "2048,128,64", "512,128,64", "512,1024,16", "256,1024,32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", nargs="*", default=list(TILES))
    ap.add_argument("--shape", default="1,8192,8192,4")
    ap.add_argument("--act", default="silu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("conv_candidates: no TPU", file=sys.stderr)
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

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    def fwd_carry(x, w, tile, act):
        """The forward with its halo CARRIED: the t axis of the grid
        sequential, the last 16 rows of a block waiting in a VMEM
        scratch for the next, no second BlockSpec (the candidate
        ``gdn.conv.fwd`` was weighed against)."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        rows, lanes = tile
        taps = w.shape[-1]
        x2, wt = cc._operands(x, w, tile)

        def kernel(x_ref, w_ref, y_ref, tail_ref):
            @pl.when(pl.program_id(2) == 0)
            def _():
                tail_ref[...] = jnp.zeros_like(tail_ref)

            cc._fwd_passes(x_ref, tail_ref[...], w_ref, y_ref, taps=taps,
                           act=act, rows=min(rows, cc._PASS_ROWS))
            tail_ref[...] = x_ref[rows - cc._HALO:, :]

        x_spec, _, w_spec = cc._specs(rows, lanes, taps,
                                      lambda i, j, k: (i, j, k))
        return pl.pallas_call(
            kernel, name="gdn.conv.fwd_carry",
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            grid=(x.shape[0], x.shape[2] // lanes, x2.shape[1] // rows),
            in_specs=[x_spec, w_spec], out_specs=x_spec,
            scratch_shapes=[pltpu.VMEM((cc._HALO, lanes), x.dtype)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
        )(x2, wt)[:, :x.shape[1]]

    b, t, c, taps = (int(v) for v in args.shape.split(","))
    act = args.act
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(b, t, c), jnp.bfloat16)
    dy = jnp.asarray(r.randn(b, t, c), jnp.bfloat16)
    w = jnp.asarray(r.randn(c, taps) * 0.5, jnp.float32)

    def xla_bwd(x, w, dy):
        return jax.vjp(lambda x, w: L._conv_xla(x, w, act), x, w)[1](dy)

    want = jax.jit(lambda x, w: L._conv_xla(x, w, act))(x, w)
    want_dx, want_dw = jax.jit(xla_bwd)(x, w, dy)
    report = {"device": jax.devices()[0].device_kind,
              "shape": f"b{b} t{t} c{c} taps{taps} act={act or 'none'}",
              "xla": {"fwd_ms": ms(lambda x, w: L._conv_xla(x, w, act), x, w),
                      "bwd_ms": ms(xla_bwd, x, w, dy)},
              "conv_tile": cc.conv_tile(t, c, taps, x.dtype),
              "kernels": {}}
    print(json.dumps(report), flush=True)
    for spec in args.tiles:
        rows, lanes, per_pass = (int(v) for v in spec.split(","))
        cc._PASS_ROWS = per_pass
        tile = (rows, lanes)
        row = {}
        try:
            y = cc.causal_conv_fwd(x, w, tile, act)
            dx, dw = cc.causal_conv_bwd(x, w, dy, tile, act)
            row = {"y_err": worst(y, want), "dx_err": worst(dx, want_dx),
                   "dw_err": worst(dw, want_dw)}
            del y, dx, dw
            row["fwd_ms"] = ms(
                lambda x, w: cc.causal_conv_fwd(x, w, tile, act), x, w)
            row["bwd_ms"] = ms(
                lambda x, w, dy: cc.causal_conv_bwd(x, w, dy, tile, act),
                x, w, dy)
            row["carry_y_err"] = worst(fwd_carry(x, w, tile, act), want)
            row["fwd_carry_ms"] = ms(
                lambda x, w: fwd_carry(x, w, tile, act), x, w)
        except Exception as e:      # a tile Mosaic refuses: say so, go on
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        report["kernels"][spec] = row
        print(spec, json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
