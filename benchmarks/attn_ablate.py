"""Transformer 0.45-MFU ceiling ablation (round 4, VERDICT item 3).

Measures the flash-attention FORWARD kernel's softmax/VPU cost against
its MXU floor at the transformer-base shape (b=64, h=8, t=256, dh=64),
isolating each claimed contributor:

  matmul-floor   score + pv matmuls only (no softmax) — the MXU floor
                 at dh=64 (50% K/N fill on the two contractions)
  full           production math: row-max, exp, correction, l-sum
  no-rowmax      exp(s) without the running max (unsafe numerically;
                 measures the max+correction VPU cost)
  bf16-exp       softmax arithmetic in bf16 (measures whether the VPU
                 runs 16-bit exp/max faster on this chip)
  dh128          h=4, dh=128, same d_model: fills the MXU contraction
                 (measures the head-shape fill penalty; note
                 transformer-base is DEFINED as h=8/dh=64, so this is a
                 bound probe, not a config change)

Run on the chip: python benchmarks/attn_ablate.py
Results are read from device traces, not wall-clock microtiming.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(np.finfo(np.float32).min)


def make_fwd(variant: str, b, h, t, dh, bq, bk):
    nk = t // bk
    scale = 1.0 / np.sqrt(dh)

    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        kk = pl.program_id(1)

        @pl.when(kk == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if variant == "matmul-floor":
            acc_scr[:] += jax.lax.dot_general(
                s.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
        elif variant == "no-rowmax":
            p = jnp.exp(s)
            l_scr[:] += jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
            acc_scr[:] += jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
        elif variant == "bf16-exp":
            m_prev = m_scr[:, :, :1]
            l_prev = l_scr[:, :, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            sb = (s - m_new).astype(jnp.bfloat16)
            p = jnp.exp(sb)
            corr = jnp.exp((m_prev - m_new).astype(jnp.bfloat16))
            l_new = l_prev * corr.astype(jnp.float32) + jnp.sum(
                p.astype(jnp.float32), axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * corr.astype(jnp.float32) + \
                jax.lax.dot_general(
                    p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        else:  # full
            m_prev = m_scr[:, :, :1]
            l_prev = l_scr[:, :, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(kk == nk - 1)
        def _finish():
            if variant in ("full", "bf16-exp"):
                o_ref[0] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)
            elif variant == "no-rowmax":
                o_ref[0] = (acc_scr[:] /
                            jnp.maximum(l_scr[:, :, :1], 1e-9)).astype(
                                o_ref.dtype)
            else:
                o_ref[0] = acc_scr[:].astype(o_ref.dtype)

    def fwd(q, k, v):
        return pl.pallas_call(
            kernel,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((1, h, bq, dh), lambda i, kk: (i, 0, 0, 0)),
                pl.BlockSpec((1, h, bk, dh), lambda i, kk: (i, 0, kk, 0)),
                pl.BlockSpec((1, h, bk, dh), lambda i, kk: (i, 0, kk, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, bq, dh),
                                   lambda i, kk: (i, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((b, h, t, dh), jnp.bfloat16),
            scratch_shapes=[
                pltpu.VMEM((h, bq, 128), jnp.float32),
                pltpu.VMEM((h, bq, 128), jnp.float32),
                pltpu.VMEM((h, bq, dh), jnp.float32),
            ],
        )(q, k, v)

    return jax.jit(fwd)


def trace_us(tag, fn, *args, iters=20):
    import glob
    import gzip
    import json

    o = fn(*args)
    jax.block_until_ready(o)
    with jax.profiler.trace(f"/tmp/perf/attn_{tag}"):
        for _ in range(iters):
            o = fn(*args)
        jax.block_until_ready(o)
    fs = sorted(glob.glob(f"/tmp/perf/attn_{tag}/**/*.trace.json.gz",
                          recursive=True))
    ev = json.load(gzip.open(fs[-1]))["traceEvents"]
    tot = sum(e.get("dur", 0) for e in ev
              if e.get("ph") == "X" and e.get("pid") == 3
              and e.get("tid") == 3)
    return tot / iters


def main():
    r = np.random.RandomState(0)
    b, t, d = 64, 256, 512
    results = {}
    for name, (h, dh) in [("h8dh64", (8, 64)), ("h4dh128", (4, 128))]:
        q = jnp.asarray(r.randn(b, h, t, dh) * 0.1, jnp.bfloat16)
        k = jnp.asarray(r.randn(b, h, t, dh) * 0.1, jnp.bfloat16)
        v = jnp.asarray(r.randn(b, h, t, dh) * 0.1, jnp.bfloat16)
        variants = (["matmul-floor", "full", "no-rowmax", "bf16-exp"]
                    if h == 8 else ["matmul-floor", "full"])
        for variant in variants:
            fn = make_fwd(variant, b, h, t, dh, 256, 256)
            us = trace_us(f"{name}_{variant}", fn, q, k, v)
            results[f"{name}/{variant}"] = us
            print(f"{name:8s} {variant:14s}: {us:7.1f} us/call")
    # sanity: full vs reference
    return results


if __name__ == "__main__":
    main()
