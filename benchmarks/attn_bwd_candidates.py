"""Time the BHTD attention backward's candidates alone on the chip, at the
calls of the four decoder cells (bf16, causal; smallthinker's both with
its window of 4096 and without).

    chiprun -- python benchmarks/attn_bwd_candidates.py [--calls olmoe ...]

``flash_attention_bwd`` (the delta reduction in front included) as the
split pair (``attn.bhtd.bwd_dq`` + ``attn.bhtd.bwd_dkv``: 7 matmuls and
two walks of the grid a live block) and as ONE call (5 matmuls, one
walk) on each of the two walks that keep nothing gradient-sized in HBM:
q inner (``attn.bhtd.bwd`` as the program lowers it: dq resident in
VMEM for a query head, dk and dv in a block's scratch, or resident too
where a group shares them) and k inner (the candidate it was weighed
against, ``bwd_k_inner`` below: dq in a block's scratch, dk and dv
resident for a key/value head). Each fused form is held to the pair's
gradients first; then ms a call, the median of five stretches of 10
calls dispatched back to back (host clock around one
``block_until_ready``), and us a live (q-block, k-block) pair. The table
goes to chiprun_out/attn_bwd_candidates.json. How ``attn.bhtd.bwd``'s
walk was chosen (PERF.md section 6, PR 39). Needs a TPU.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "attn_bwd_candidates.json")
# call: (b, heads, key/value heads, t, dh, dv, window)
CALLS = {
    "smallthinker_w4096": (1, 28, 4, 16384, 128, 128, 4096),
    "smallthinker_global": (1, 28, 4, 16384, 128, 128, None),
    "joyai": (1, 32, 32, 4096, 192, 128, None),
    "qwen3next": (1, 16, 2, 8192, 256, 256, None),
    "olmoe": (2, 16, 16, 4096, 128, 128, None),
}


def live_blocks(t, bq, bk, window):
    """(q-block, k-block) pairs of one head that hold a visible pair."""
    return sum(
        1 for j in range(t // bq) for kk in range(t // bk)
        if kk * bk <= (j + 1) * bq - 1
        and (window is None or j * bq <= (kk + 1) * bk + window - 2))


def bwd_k_inner(q, k, v, out, lse, g, causal=True, window=None):
    """The fused backward on the dq kernel's walk: grid (batch row,
    key/value head, member of its group, q-block, step over the row's
    k-blocks); dq gathers in a block's scratch, dk and dv [tk, .] stay
    resident for the key/value head and its group. The block's
    arithmetic is the program's (``_bwd_block``)."""
    import functools

    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.parallel import flash_attention as fa

    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    window = fa._band(window, causal, tq, tk)
    tile = fa.bhtd_tile(h, tq, tk, dh=dh, group=group, dv=dv)
    _, bq, bk = tile
    nq, nk = tq // bq, tk // bk
    steps = fa._k_steps(window, nq, nk, bq, bk)
    scale = dh ** -0.5
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
        m, j, r = pl.program_id(2), pl.program_id(3), pl.program_id(4)
        kk = r if window is None else fa._first_k(j, bq, bk, window) + r
        first = (m == 0) & (j == 0) & (r == 0)
        last = (m == group - 1) & (j == nq - 1) & (r == steps - 1)

        def zero(acc, at):
            acc[at, :] = jnp.zeros((acc[at, :].shape), acc.dtype)

        def write(acc, ref, at):
            ref[0, 0, at, :] = acc[at, :].astype(ref.dtype)

        pl.when(r == 0)(lambda: zero(dq_acc, slice(None)))
        for acc in (dk_acc, dv_acc):
            pl.when(first)(functools.partial(
                fa._each_block, acc, bk, functools.partial(zero, acc)))

        def compute(masked=False):
            mask = None
            if masked:
                mask = lambda s_t: fa._causal_mask(
                    s_t[None], j, kk, bq, bk, transposed=True,
                    window=window)[0]
            dq, dk, dv_ = fa._bwd_block(
                q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0], delta_ref[0, 0], None, scale, mask)
            dq_acc[:] += dq
            at = fa._block_rows(dk_acc, kk, bk)
            dk_acc[at, :] += dk
            dv_acc[at, :] += dv_

        fa._when_live(compute, fa._causal_live(j, kk, bq, bk),
                      fa._on_edge(j, kk, bq, bk, window))
        pl.when(r == steps - 1)(lambda: write(dq_acc, dq_ref, slice(None)))
        for acc, ref in ((dk_acc, dk_ref), (dv_acc, dv_ref)):
            pl.when(last)(functools.partial(
                fa._each_block, acc, bk, functools.partial(write, acc, ref)))

    block_of = fa._step_blocks(causal, True, bq, bk, nq, 1, window, steps)

    def at(i, hk, m, j, r, *_):
        return block_of(i, hk * group + m, j, r)

    kernel, specs, args, rows = fa._call_parts(kernel, at, tile, q, k, v,
                                               None)
    whole = [pl.BlockSpec((1, 1, tk, d), lambda i, hk, *_: (i, hk, 0, 0))
             for d in (dh, dv)]
    operands = (fa._seed_arr(None), *args, g, lse.reshape(b, h, 1, tq),
                delta.reshape(b, h, 1, tq))
    resident = 4 * tk * (dh + dv)       # and the outputs, double-buffered
    return pl.pallas_call(
        kernel, name="attn.bhtd.bwd_k_inner",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // group, group, nq, steps),
            in_specs=specs + [rows.o, rows.row, rows.row],
            out_specs=[rows.q, *whole],
            scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32),
                            pltpu.VMEM((tk, dh), jnp.float32),
                            pltpu.VMEM((tk, dv), jnp.float32)]),
        out_shape=[fa._result(operands, x.shape, x.dtype) for x in (q, k, v)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * resident + 24 * 2**20),
    )(*operands)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=list(CALLS))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("attn_bwd_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.parallel import flash_attention as fa

    def ms(f, *a):
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(10)]
            jax.block_until_ready(outs)
            del outs
            took.append((time.perf_counter() - t0) * 1e3 / 10)
        return round(statistics.median(took), 4)

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    table = []
    for name in args.calls:
        b, h, hk, t, dh, dv, window = CALLS[name]
        r = np.random.RandomState(7)

        def rand(*shape, s=1.0):
            return jnp.asarray(r.randn(*shape) * s, jnp.bfloat16)

        q, k = rand(b, h, t, dh, s=0.5), rand(b, hk, t, dh, s=0.5)
        v, g = rand(b, hk, t, dv), rand(b, h, t, dv)
        kw = dict(causal=True, window=window)
        out, lse = jax.jit(
            lambda q, k, v: fa.flash_attention_fwd(q, k, v, **kw))(q, k, v)
        tile = fa.bhtd_tile(h, t, t, dh=dh, group=h // hk, dv=dv)
        blocks = b * h * live_blocks(t, tile[1], tile[2], window)

        def bwd(form):
            def f(q, k, v, out, lse, g):
                if form == "k_inner":
                    return bwd_k_inner(q, k, v, out, lse, g, **kw)
                return fa.flash_attention_bwd(q, k, v, None, None, out, lse,
                                              g, **kw)
            # (bhtd_bwd_form reads the cap while the call is traced: no
            # room for a resident row is the pair)
            cap = fa._BWD_VMEM_CAP_BYTES
            fa._BWD_VMEM_CAP_BYTES = 0 if form == "split" else cap
            try:
                return jax.jit(f).lower(q, k, v, out, lse, g).compile()
            finally:
                fa._BWD_VMEM_CAP_BYTES = cap

        row = {"call": name, "shape": [b, h, hk, t, dh, dv, window],
               "tile": list(tile), "live_blocks": blocks}
        want = None
        for form in ("split", "q_inner", "k_inner"):
            try:
                f = bwd(form)
                got = f(q, k, v, out, lse, g)
                if want is None:
                    want = got
                row[form] = {
                    "ms": ms(f, q, k, v, out, lse, g),
                    "worst_vs_split": [round(worst(a, w), 5)
                                       for a, w in zip(got, want)]}
                row[form]["us_a_live_block"] = round(
                    row[form]["ms"] * 1e3 / blocks, 4)
            except Exception as e:  # a candidate Mosaic refuses is a row
                row[form] = {"error": str(e)[:400]}
            print(name, form, row[form], flush=True)
        table.append(row)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
