"""Time the Mamba-2 scan's candidates alone on the chip, at
nemotron3nano-train-s4096's call (b1 t4096, 64 heads of 64 in 8 groups,
state 128, chunk 128, bf16).

    chiprun -- python benchmarks/mamba2_candidates.py [--chunks 8 16 32]

Forward and backward of: the chunked XLA form
(ops/mamba2_scan_ops._chunk_fn under ``lax.scan``, and its reverse scan
of ``jax.vjp``s) and the ``mamba2.chunk.*`` kernels
(paddle_tpu/parallel/mamba2_scan.py: a group's four PAIRS of heads a
grid step, two heads side by side over a lane tile) at each candidate
number of chunks a grid step, each held to the XLA form's results first;
ms a call, the median of five stretches of 20 calls dispatched back to
back (host clock around one ``block_until_ready``). The table goes to
chiprun_out/mamba2_candidates.json. How ``_STEP_CHUNKS`` was chosen
(PERF.md section 6, PR 45). Heads one at a time (64 of a lane tile's 128
lanes) were not built: every elementwise pass of the kernel would run at
half a vector register. Needs a TPU.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "mamba2_candidates.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", nargs="*", type=int, default=[8, 16, 32])
    ap.add_argument("--shape", default="1,4096,64,8",
                    help="batch, positions, heads, groups")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("mamba2_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.ops import mamba2_scan_ops as S
    from paddle_tpu.parallel import mamba2_scan as K

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
        return statistics.median(took)

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    b, t, heads, groups = (int(v) for v in args.shape.split(","))
    bf, f32 = jnp.bfloat16, jnp.float32
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(b, t, heads * K.HEAD_DIM), bf)
    raw = jnp.asarray(r.randn(b, t, heads) - 3.0, bf)
    a_log = jnp.asarray(np.log(np.arange(1, heads + 1)), f32)
    bm, cm = (jnp.asarray(r.randn(b, t, groups * K.STATE) * 0.5, bf)
              for _ in range(2))
    d, bias = jnp.ones((heads,), f32), jnp.zeros((heads,), f32)
    dy = jnp.asarray(r.randn(*x.shape), bf)
    dt, a = S.step_sizes(raw, a_log, bias)
    ins = {"X": [x], "Dt": [raw], "ALog": [a_log], "B": [bm], "C": [cm],
           "D": [d], "DtBias": [bias]}
    attrs = {"groups": groups, "chunk": K.CHUNK}

    tile_fn, K.mamba2_tile = K.mamba2_tile, lambda *a_, **k: None
    try:
        xla_fwd = lambda: S._mamba2_scan(ins, attrs)
        out = jax.jit(xla_fwd)()
        xla_bwd = lambda states: S._mamba2_scan_grad(
            {**ins, "States": [states], "GRAD::Out": [dy]}, attrs)
        want = jax.jit(xla_bwd)(out["States"][0])
        table = {"xla": {"fwd_ms": ms(xla_fwd),
                         "bwd_ms": ms(xla_bwd, out["States"][0])}}
    finally:
        K.mamba2_tile = tile_fn
    print("xla", table["xla"], flush=True)
    pairs = heads // groups // 2
    for chunks in args.chunks:
        tile = (pairs, chunks)
        if chunks % 8 and chunks != -(-t // K.CHUNK):
            continue
        fwd = lambda: K.mamba2_scan_fwd(x, dt, a, bm, cm, d, tile)
        y, states = jax.jit(fwd)()
        bwd = lambda st: K.mamba2_scan_bwd(x, dt, a, bm, cm, d, st, dy, tile)
        got = jax.jit(bwd)(states)
        row = {"fwd_ms": ms(fwd), "bwd_ms": ms(bwd, states),
               "err_y": rel(y, out["Out"][0]),
               "err_dx": rel(got[0], want["GRAD::X"][0]),
               "err_db": rel(got[1], want["GRAD::B"][0]),
               "err_dc": rel(got[2], want["GRAD::C"][0])}
        table[f"kernel chunks{chunks}"] = row
        print(f"chunks {chunks}", row, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
