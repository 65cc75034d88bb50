"""SE-ResNeXt-50 grouped-conv + SE-block microbenchmark (round 5,
VERDICT item 1b/1d).

Isolates the two structures the round-5 analysis blamed for SE-ResNeXt's 0.202
MFU (vs ResNet-50's 0.321 at near-identical analytic FLOPs) and times
each against explicit rooflines on the real chip:

  grouped      production path: lax.conv feature_group_count=32 (what
               ops/nn_ops.py _conv2d emits), fwd and fwd+bwd
  dense        SAME channel counts, groups=1 — 32x the useful FLOPs.
               If XLA internally rewrites grouped->block-diag-dense,
               grouped ~= dense in time; if grouped >> dense the TPU
               conv emitter handles small channels/group WORSE than a
               dense conv, and a Pallas block-diag kernel has headroom.
  patches_dot  im2col patches + dot_general batched over g=32
               ([M, 9*cg] x [9*cg, cg] per group) — the "keep only
               useful FLOPs on the MXU" formulation; measures the
               batched-small-matmul fill penalty directly.
  se_chain     global-pool -> fc(C/16) -> relu -> fc(C) -> sigmoid ->
               broadcast-mul, per stage output shape — the SE gate's
               serialization + traffic cost against its 3-pass HBM
               floor.

Rooflines per shape: HBM floor = (bytes in + bytes out)/819 GB/s;
MXU-fill bound = useful FLOPs / (197e12 * min(K,128)/128 *
min(N,128)/128) for the per-group contraction [M,K=9cg]x[K,cg];
dense-FLOPs bound = physical block-diag FLOPs / 197e12.

Timing methodology: each variant is chained through a lax.fori_loop
(carry = activation, weights scaled for variance preservation) so every
iteration has different inputs. Device time is read from the profiler
trace and divided by the trip count.

Run: python benchmarks/grouped_conv_bench.py
"""

from __future__ import annotations

import glob
import gzip
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HBM_GBS = 819e9
PEAK = 197e12
ITERS = 12

# (tag, N, H, W, C, cg): the four SE-ResNeXt-50 grouped-3x3 stage shapes
# at bench batch 128 (models/se_resnext.py filters_list, cardinality 32).
SHAPES = [
    ("s0", 128, 56, 56, 128, 4),
    ("s1", 128, 28, 28, 256, 8),
    ("s2", 128, 14, 14, 512, 16),
    ("s3", 128, 7, 7, 1024, 32),
]
G = 32


def trace_s(tag, fn, *args):
    """Total device-stream seconds for ONE traced call of fn."""
    o = fn(*args)
    jax.block_until_ready(o)
    d = f"/tmp/perf/gc_{tag}"
    with jax.profiler.trace(d):
        o = fn(*args)
        jax.block_until_ready(o)
    fs = sorted(glob.glob(f"{d}/**/*.trace.json.gz", recursive=True))
    ev = json.load(gzip.open(fs[-1]))["traceEvents"]
    tot = sum(e.get("dur", 0) for e in ev
              if e.get("ph") == "X" and e.get("pid") == 3
              and e.get("tid") == 3)
    return tot * 1e-6


def chain(body):
    @jax.jit
    def run(x):
        return lax.fori_loop(0, ITERS, lambda i, x: body(x), x)
    return run


def conv(x, w, groups):
    return lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def fwd_bwd(f, x, *ws):
    """fwd + dgrad + wgrad, dw kept live via a scalar graft onto dx."""
    y, vjp = jax.vjp(f, x, *ws)
    grads = vjp(y)
    dx = grads[0]
    for dw in grads[1:]:
        dx = dx + jnp.mean(dw).astype(dx.dtype)
    return dx * 0.5


def patches_dot(x, w, cg):
    """[N,H,W,C] -> patches [N,H,W,9,g,cg] -> per-group dot.
    w: [g, 9*cg, cg]."""
    n, h, ww, c = x.shape
    p = lax.conv_general_dilated_patches(
        x, (3, 3), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # patches feature order is [c, kh, kw] flattened -> [C, 9]
    p = p.reshape(n * h * ww, c, 9).reshape(n * h * ww, G, cg, 9)
    p = p.transpose(1, 0, 2, 3).reshape(G, n * h * ww, cg * 9)
    y = lax.dot_general(p, w, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
    y = y.astype(x.dtype).transpose(1, 0, 2).reshape(n, h, ww, c)
    return y


def se_chain(x, w1, b1, w2, b2):
    n, h, ww, c = x.shape
    pool = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    s = jax.nn.relu(pool @ w1 + b1)
    e = jax.nn.sigmoid(s @ w2 + b2)
    return (x * e[:, None, None, :].astype(x.dtype))


def report(tag, t, useful_gflop, bytes_mb, fill_bound_s, note=""):
    tfs = useful_gflop / t / 1e3 if t > 0 else 0
    hbm_floor = bytes_mb * 1e6 / HBM_GBS
    print(f"  {tag:16s}: {t*1e6:9.1f} us  useful {tfs:7.2f} TF/s  "
          f"hbm-floor {hbm_floor*1e6:7.1f} us  "
          f"fill-bound {fill_bound_s*1e6:7.1f} us {note}")


def main():
    r = np.random.RandomState(0)
    total = {"grouped": 0.0, "dense": 0.0, "se": 0.0}
    # block counts per stage in SE-ResNeXt-50
    blocks = {"s0": 3, "s1": 4, "s2": 6, "s3": 3}
    for tag, n, h, w_, c, cg in SHAPES:
        m = n * h * w_
        useful = 2.0 * m * 9 * cg * c / 1e9          # GFLOP
        dense_fl = 2.0 * m * 9 * c * c / 1e9
        io_mb = 2 * (m * c * 2) / 1e6                # x read + y write, bf16
        k, nn_ = 9 * cg, cg
        fill = (min(k, 128) / 128.0) * (min(nn_, 128) / 128.0)
        fill_bound = useful * 1e9 / (PEAK * fill)
        print(f"{tag}: [{n},{h},{w_},{c}] cg={cg}  useful {useful:.1f} "
              f"GFLOP  dense {dense_fl:.1f} GFLOP  io {io_mb:.0f} MB")

        x = jnp.asarray(r.randn(n, h, w_, c) * 0.5, jnp.bfloat16)
        wg = jnp.asarray(r.randn(3, 3, cg, c) / np.sqrt(9 * cg),
                         jnp.bfloat16)
        wd = jnp.asarray(r.randn(3, 3, c, c) / np.sqrt(9 * c),
                         jnp.bfloat16)
        wp = jnp.asarray(r.randn(G, 9 * cg, cg) / np.sqrt(9 * cg),
                         jnp.bfloat16)

        t = trace_s(f"{tag}_grouped", chain(lambda x: conv(x, wg, G)), x)
        report("grouped fwd", t / ITERS, useful, io_mb, fill_bound)
        total["grouped"] += t / ITERS * blocks[tag]

        t = trace_s(f"{tag}_gbwd",
                    chain(lambda x: fwd_bwd(
                        lambda x, w: conv(x, w, G), x, wg)), x)
        report("grouped f+b", t / ITERS, 3 * useful, 3 * io_mb,
               3 * fill_bound)

        t = trace_s(f"{tag}_dense", chain(lambda x: conv(x, wd, 1)), x)
        report("dense fwd", t / ITERS, dense_fl, io_mb,
               dense_fl * 1e9 / PEAK, "(32x FLOPs)")
        total["dense"] += t / ITERS * blocks[tag]

        t = trace_s(f"{tag}_pdot",
                    chain(lambda x: patches_dot(x, wp, cg)), x)
        report("patches_dot", t / ITERS, useful, io_mb, fill_bound)

        # SE chain on the block OUTPUT shape (2*filters channels)
        c2 = 2 * c
        xe = jnp.asarray(r.randn(n, h, w_, c2) * 0.5, jnp.bfloat16)
        w1 = jnp.asarray(r.randn(c2, c2 // 16) * 0.05, jnp.float32)
        b1 = jnp.zeros((c2 // 16,), jnp.float32)
        w2 = jnp.asarray(r.randn(c2 // 16, c2) * 0.05, jnp.float32)
        b2 = jnp.zeros((c2,), jnp.float32)
        se_mb = 3 * (m * c2 * 2) / 1e6   # pool read + mul read + write
        t = trace_s(f"{tag}_se",
                    chain(lambda x: se_chain(x, w1, b1, w2, b2)), xe)
        report("se_chain fwd", t / ITERS, 0.0, se_mb,
               se_mb * 1e6 / HBM_GBS)
        total["se"] += t / ITERS * blocks[tag]

    print("\nper-step fwd totals over 16 blocks:")
    for k, v in total.items():
        print(f"  {k:8s}: {v*1e3:.2f} ms")


if __name__ == "__main__":
    main()
