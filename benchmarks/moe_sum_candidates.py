"""Time the candidates for an expert layer's two token-major sums alone
on the chip (``moe_combine``'s forward, weighted, and
``moe_dispatch``'s gradient, unweighted: out[t] = sum_j w[t, j] *
rows[Slot[t, j]] over the pairs before ``live``), at the four MoE cells'
shapes ``[n, k, d, scored, held]`` and at live shares of an even router
(held / scored), a half and all.

    chiprun -- python benchmarks/moe_sum_candidates.py [--cells a,b] [--tiles 128,24 ...]

Per shape and share, ms a call (the median of five stretches of 20
calls dispatched back to back, host clock around one
``block_until_ready``) and ns a live row of:

- ``parent``: what ``ops/moe_ops._add_into_tokens`` was before PR 41: a
  ``lax.cond`` on the live count between XLA's scatter-add over the
  windows of live rows (``by_live_row``) and a gather of all k rows of
  every token and their sum (``by_token``, which the program keeps as
  its one XLA form, ``_sum_by_token``); each branch also alone;
- ``take``: a bare ``jnp.take`` of all n * k rows by Slot;
- ``A``: rows by DMA. As the issue wrote it (one row DMA a live pair
  out of the [m, d] bf16 buffer where it lies) Mosaic refuses to
  compile it: the message is recorded under ``A in place``. Timed in
  the one way it takes: XLA packs the buffer into uint32 [m, 1, d / 2]
  (each row its own tile: a relayout of the whole buffer, timed apart
  as ``A relayout``), the kernel starts one DMA a live pair from the
  scalar-prefetched Slot into a [k, tt, 1, d / 2] staging buffer, waits,
  and adds the slabs on the VPU in float32 (no second buffer: the
  DMAs are not hidden under the sum);
- ``B``: segments and a one-hot matmul,
  ``paddle_tpu/parallel/pair_sum.pair_sum`` (the program's kernel) at
  the tile ``sum_tile`` gives the call and at the others asked for.

Every form is held to ``by_token``'s result first. One JSON object, to
chiprun_out/moe_sum_candidates.json. How PR 41 chose (PERF.md section
6). Needs a TPU.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "moe_sum_candidates.json")

SHAPES = {  # n tokens, k a token, d, experts scored, held
    "smallthinker-train-s16384": (16384, 6, 2560, 64, 8),
    "qwen3next-train-s8192": (8192, 10, 2048, 512, 32),
    "joyai-train-s4096": (4096, 8, 2048, 256, 16),
    "olmoe-train-s4096": (8192, 8, 2048, 64, 64),
}
# what the parent's ``_ADD_NS`` held: ns a live row by scatter-add, ns a
# buffer row by token
ADD_NS = {"live_row": 107, "buffer_row": 40}
# Rehearsal hook: the script's own kernels through the interpreter.
_INTERPRET = False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--tiles", nargs="*", default=["256,0", "128,24"],
                    help="tt,cap beside sum_tile's own (cap 0: its cap)")
    ap.add_argument("--skip-a", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if jax.default_backend() != "tpu":
        print("moe_sum_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.parallel import grouped_matmul as gm
    from paddle_tpu.parallel import pair_sum as ps

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

    # -- the parent's forms ------------------------------------------------

    def by_live_row(rows, order, slot, live, w, top_w):
        n, k = slot.shape

        def trip(r0, keep, acc):
            pairs = gm.rows_at(order, r0, w)
            v = gm.rows_at(rows, r0, w).astype(jnp.float32)
            if top_w is not None:
                v = v * jnp.take(top_w.reshape(-1), pairs)[:, None]
            return acc.at[pairs // k].add(jnp.where(keep, v, 0.0),
                                          mode="promise_in_bounds")

        return gm.over_live_rows(
            live, w, trip, jnp.zeros((n, rows.shape[1]), jnp.float32))

    def by_token(rows, order, slot, live, w, top_w):
        return moe_ops._sum_by_token(rows, slot, live, top_w)

    def parent(rows, order, slot, live, w, top_w):
        n, k = slot.shape
        return jax.lax.cond(
            ADD_NS["live_row"] * live <= ADD_NS["buffer_row"] * n * k,
            lambda: by_live_row(rows, order, slot, live, w, top_w),
            lambda: by_token(rows, order, slot, live, w, top_w))

    # -- A: rows by DMA ----------------------------------------------------

    def a_in_place(rows, slot, live):
        """One row DMA a live pair out of the buffer where it lies."""
        n, k = slot.shape
        d, tt = rows.shape[1], min(128, n)

        def kernel(live_ref, slot_ref, rows_ref, out_ref, stage, sem):
            p0 = pl.program_id(0) * (tt * k)

            def one(p, carry):
                @pl.when(slot_ref[p0 + p] < live_ref[0])
                def _():
                    cp = pltpu.make_async_copy(
                        rows_ref.at[pl.ds(slot_ref[p0 + p], 1)],
                        stage.at[p % k, pl.ds(p // k, 1)], sem.at[0])
                    cp.start()
                    cp.wait()
                return carry

            jax.lax.fori_loop(0, tt * k, one, 0)
            out_ref[...] = jnp.sum(stage[...].astype(jnp.float32),
                                   axis=0).astype(out_ref.dtype)

        return pl.pallas_call(
            kernel, name="pairs.sum.a_in_place",
            out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n // tt,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tt, d), lambda i, lv, s: (i, 0)),
                scratch_shapes=[pltpu.VMEM((k, tt, d), rows.dtype),
                                pltpu.SemaphoreType.DMA((1,))]),
            interpret=_INTERPRET,
        )(live.reshape(1), slot.reshape(-1), rows)

    def a_pack(rows):
        m, d = rows.shape
        return jax.lax.bitcast_convert_type(
            rows.reshape(m, d // 2, 2), jnp.uint32).reshape(m, 1, d // 2)

    def a_sum(packed, slot, live, top_w):
        """The same from uint32 [m, 1, d / 2]: every row its own tile."""
        n, k = slot.shape
        h, tt = packed.shape[2], min(128, n)
        weighted = top_w is not None

        def kernel(live_ref, pairs_ref, slot_ref, *refs):
            if weighted:
                w_ref, *refs = refs
            rows_ref, lo_ref, hi_ref, stage, sem = refs
            live_ = live_ref[0]
            p0 = pl.program_id(0) * (tt * k)

            def copy(p, r):
                return pltpu.make_async_copy(
                    rows_ref.at[r], stage.at[p % k, p // k], sem.at[0])

            def start(p, carry):
                @pl.when(pairs_ref[p0 + p] < live_)
                def _():
                    copy(p, pairs_ref[p0 + p]).start()
                return carry

            def wait(p, carry):
                @pl.when(pairs_ref[p0 + p] < live_)
                def _():
                    copy(p, 0).wait()
                return carry

            jax.lax.fori_loop(0, tt * k, start, 0)
            jax.lax.fori_loop(0, tt * k, wait, 0)
            slot_ = slot_ref[...]
            lo = jnp.zeros((tt, h), jnp.float32)
            hi = jnp.zeros((tt, h), jnp.float32)
            for j in range(k):
                u = stage[j].reshape(tt, h)
                keep = slot_[:, j:j + 1] < live_
                a = jax.lax.bitcast_convert_type(u << 16, jnp.float32)
                b = jax.lax.bitcast_convert_type(
                    u & jnp.uint32(0xFFFF0000), jnp.float32)
                if weighted:
                    a, b = a * w_ref[:, j:j + 1], b * w_ref[:, j:j + 1]
                lo = lo + jnp.where(keep, a, 0.0)
                hi = hi + jnp.where(keep, b, 0.0)
            lo_ref[...] = lo
            hi_ref[...] = hi

        block = pl.BlockSpec((tt, k), lambda i, lv, pairs: (i, 0))
        half = pl.BlockSpec((tt, h), lambda i, lv, pairs: (i, 0))
        operands = [slot] + ([top_w] if weighted else [])
        lo, hi = pl.pallas_call(
            kernel, name="pairs.sum.a",
            out_shape=[jax.ShapeDtypeStruct((n, h), jnp.float32)] * 2,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n // tt,),
                in_specs=[block] * len(operands)
                + [pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[half, half],
                scratch_shapes=[pltpu.VMEM((k, tt, 1, h), jnp.uint32),
                                pltpu.SemaphoreType.DMA((1,))]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=64 * 2**20),
            interpret=_INTERPRET,
        )(live.reshape(1), slot.reshape(-1), *operands, packed)
        return jnp.stack([lo, hi], axis=-1).reshape(n, 2 * h).astype(
            jnp.bfloat16)

    # -- the table -----------------------------------------------------------

    report = {"device": jax.devices()[0].device_kind}
    cells = [c for c in args.cells.split(",") if c]
    for cell in cells:
        n, k, d, scored, held = SHAPES[cell]
        m = n * k
        shares = {"even": scored, "half": 2 * held, "all": held}
        if scored == held:
            shares = {"all": held}
        w = gm.live_window(m, -(-m * held // scored))
        for share, pool in shares.items():
            r = np.random.RandomState(7)
            top_i = np.argsort(r.rand(n, pool), axis=1)[:, :k].astype(
                np.int32)
            flat = np.where(top_i.reshape(-1) < held, top_i.reshape(-1),
                            held)
            order = jnp.asarray(np.argsort(flat, kind="stable"), jnp.int32)
            slot = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
            sizes = jnp.asarray(
                np.bincount(flat, minlength=held + 1)[:held], jnp.int32)
            live = jnp.sum(sizes)
            rows = jnp.where(jnp.arange(m)[:, None] < live, jnp.asarray(
                r.randn(m, d), jnp.bfloat16), 0)
            top_w = jnp.asarray(r.rand(n, k), jnp.float32)
            n_live = int(live)
            out = report[f"{cell} {share}"] = {
                "rows": m, "live": n_live, "window": w}
            print(cell, share, out, flush=True)

            def note(name, took):
                out[name] = {"ms": took, "ns_a_live_row": round(
                    took * 1e6 / max(n_live, 1), 1)}
                print(" ", name, out[name], flush=True)

            want = {
                True: jax.jit(by_token, static_argnums=4)(
                    rows, order, slot, live, w, top_w),
                False: jax.jit(by_token, static_argnums=4)(
                    rows, order, slot, live, w, None)}
            scale = {key: float(jnp.abs(v).max()) or 1.0
                     for key, v in want.items()}

            def held_to(name, got, weighted):
                err = float(jnp.abs(got.astype(jnp.float32)
                                    - want[weighted]).max())
                assert err <= 2.0 ** -7 * scale[weighted], (name, err)

            for weighted in (True, False):
                tw = top_w if weighted else None
                tag = "weighted" if weighted else "plain"
                for name, fn in (("parent", parent),
                                 ("by_live_row", by_live_row),
                                 ("by_token", by_token)):
                    f = functools.partial(fn, w=w, top_w=tw)
                    held_to(name, jax.jit(f)(rows, order, slot, live),
                            weighted)
                    note(f"{name} {tag}", ms(
                        lambda *a, f=f: f(*a).astype(rows.dtype), rows,
                        order, slot, live))
            note("take", ms(lambda y, s: jnp.take(y, s.reshape(-1), axis=0),
                            rows, slot))

            own = ps.sum_tile(n, k, d, rows.dtype)
            tiles = [own] + [
                (tt, cap or own[1]) for tt, cap in (
                    map(int, t.split(",")) for t in args.tiles)
                if n % tt == 0]
            for tile in dict.fromkeys(tiles):
                for weighted in (True, False):
                    tw = top_w if weighted else None
                    f = functools.partial(ps.pair_sum, tile=tile)
                    name = (f"B tt{tile[0]} cap{tile[1]} "
                            f"{'weighted' if weighted else 'plain'}"
                            f"{' (sum_tile)' if tile == own else ''}")
                    try:
                        held_to(name, jax.jit(
                            lambda y, s, z, t=tw: f(y, s, z, top_w=t))(
                                rows, slot, sizes), weighted)
                        note(name, ms(
                            lambda y, s, z, t=tw: f(y, s, z, top_w=t),
                            rows, slot, sizes))
                    except AssertionError:
                        raise
                    except Exception as e:   # a tile Mosaic refuses
                        out[name] = {"refused": str(e)[:300]}
                        print(" ", name, out[name], flush=True)

            if args.skip_a:
                continue
            try:
                jax.jit(a_in_place).lower(rows, slot, live).compile()
                out["A in place"] = "compiled"
            except Exception as e:
                text = str(e)
                at = text.find("Mosaic failed")
                out["A in place"] = {"refused": text[max(at, 0):][:260]}
            print("  A in place", out["A in place"], flush=True)
            try:
                packed = jax.jit(a_pack)(rows)
                note("A relayout", ms(a_pack, rows))
                for weighted in (True, False):
                    tw = top_w if weighted else None
                    tag = "weighted" if weighted else "plain"
                    held_to("A", jax.jit(
                        lambda p, s, lv, t=tw: a_sum(p, s, lv, t))(
                            packed, slot, live), weighted)
                    note(f"A kernel {tag}", ms(
                        lambda p, s, lv, t=tw: a_sum(p, s, lv, t), packed,
                        slot, live))
                    note(f"A relayout + kernel {tag}", ms(
                        lambda y, s, lv, t=tw: a_sum(a_pack(y), s, lv, t),
                        rows, slot, live))
                del packed
            except AssertionError:
                raise
            except Exception as e:
                text = str(e)
                at = text.find("Mosaic failed")
                out["A"] = {"refused": text[max(at, 0):][:300]}
                print("  A", out["A"], flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
