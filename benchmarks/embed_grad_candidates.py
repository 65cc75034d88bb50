"""Time the candidates for the dense gradient of an embedding table alone
on the chip, at the calls of the nine cells: n float32 cotangent rows of
width d summed by id into [vocab, d] float32 (ids uniform over the
table, as the cells draw them: duplicates included).

    chiprun -- python benchmarks/embed_grad_candidates.py [--calls phi4flash ...]

X1 today's lowering, the vjp of ``jnp.take`` (XLA: a sort of the ids, a
gather of the rows in that order, a scatter that walks them); X1d the
same with every id distinct, X1b with a bf16 cotangent (what moves its
price a row); X2 XLA's sort + ``jax.ops.segment_sum`` with
``indices_are_sorted``; X3 a one-hot matmul in blocks of 1024 vocabulary
rows, the 0 / 1 matrix bf16 against the cotangent's three bf16 pieces
(exact, 2 * 3 * n * vocab * d FLOPs); K tv x group the kernel
``embed.grad`` (parallel/embed_grad.py) at ``embed_grad_tile``'s answer
and at the other tiles it was weighed against, Kb at a bf16 cotangent;
prep what XLA does in front of the kernel (the sort, the gather, the
list of steps); +sum a second [vocab, d] gradient added behind X1 and
behind K (a tied table's head). Each is held to X1's result first; then
ms a call twice over: [the median of five stretches of 10 calls
dispatched back to back (host clock around one ``block_until_ready``:
under half a millisecond it reads the host's dispatch, not the chip),
the chip's busy time over 5 traced calls (the union of the trace's
``XLA Ops`` events: perf/trace.py)]. ``--widths`` times X1 alone over a
sweep of widths at 4096 rows into 16,384 (its price goes with the
TABLE's rows, and how much a row depends on the width: PERF.md section
6, PR 46). The table goes to chiprun_out/embed_grad_candidates.json.
Needs a TPU; ``--lower`` compiles every candidate for a described v5e
instead.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "embed_grad_candidates.json")
# call: (rows a step, rows of the table, width, tied to the head)
CALLS = {
    "phi4flash": (4096, 25008, 2560, True),
    "smallthinker": (16384, 18992, 2560, False),
    "olmoe": (8192, 50304, 2048, False),
    "qwen3next": (8192, 18992, 2048, False),
    "joyai": (4096, 16160, 2048, False),
    "nemotron3nano": (4096, 16384, 2688, False),
    "tbase": (32768, 10000, 512, False),
    "bert": (32768, 30522, 768, False),
    "tbase-dp4": (8192, 10000, 512, False),   # a chip's rows
}
TILES = ((128, 128), (256, 128), (512, 128), (256, 256), (512, 256))
ONE_HOT_BLOCK = 1024
WIDTHS = (1024, 1536, 2048, 2304, 2432, 2560, 2688, 2816, 3072, 4096, 5120)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=list(CALLS))
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--widths", action="store_true")
    args = ap.parse_args()
    calls = CALLS
    if args.widths:
        calls = {f"d{d}": (4096, 16384, d, False) for d in WIDTHS}
        args.calls = list(calls)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.parallel import embed_grad as eg
    from perf import trace

    sharding = None
    if args.lower:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        print("embed_grad_candidates: no TPU", file=sys.stderr)
        return 2

    def ms(f, *a, calls=10):
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(calls)]
            jax.block_until_ready(outs)
            del outs
            took.append((time.perf_counter() - t0) * 1e3 / calls)
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                jax.block_until_ready([f(*a) for _ in range(5)])
            doc = trace.load(trace.find_xplane(d))
        ops = [ev for line in doc["planes"][0]["lines"]
               if line["name"] == trace.OPS_LINE for ev in line["events"]]
        busy = trace.union_ns([(t, t + dur) for _, t, dur in ops]) / 5e6
        return [round(statistics.median(took), 4), round(busy, 4)]

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    table = []
    for name in args.calls:
        n, vocab, d, tied = calls[name]

        def x1(ids, g):
            zeros = jnp.zeros((vocab, d), jnp.float32)
            take = lambda w: jnp.take(w, ids, axis=0)   # noqa: E731
            return jax.vjp(take, zeros)[1](g.astype(jnp.float32))[0]

        def x2(ids, g):
            order = jnp.argsort(ids, stable=True)
            return jax.ops.segment_sum(
                g[order].astype(jnp.float32), ids[order],
                num_segments=vocab, indices_are_sorted=True)

        def x3(ids, g):
            # (reduce_precision: XLA drops a float32 -> bf16 -> float32
            # round trip, and the two lower pieces with it)
            hi = jax.lax.reduce_precision(g, 8, 7)
            mid = jax.lax.reduce_precision(g - hi, 8, 7)
            pieces = jnp.concatenate([hi, mid, g - hi - mid],
                                     axis=1).astype(jnp.bfloat16)
            blocks = -(-vocab // ONE_HOT_BLOCK)

            def block(i):
                rows = i * ONE_HOT_BLOCK + jnp.arange(ONE_HOT_BLOCK)
                p = (rows[:, None] == ids[None, :]).astype(jnp.bfloat16)
                got = jnp.dot(p, pieces, preferred_element_type=jnp.float32)
                return got[:, 2 * d:] + got[:, d:2 * d] + got[:, :d]

            out = jax.lax.map(block, jnp.arange(blocks))
            return out.reshape(blocks * ONE_HOT_BLOCK, d)[:vocab]

        def kernel(tile, ids, g):
            return eg.embed_grad(g, ids, vocab, tile)

        def prep(tile, ids, g):
            return eg.sorted_rows(g, ids, vocab, tile)

        def summed(f, ids, g, other):
            return f(ids, g) + other.astype(jnp.float32)

        tile = eg.embed_grad_tile(n, vocab, d, jnp.float32, backend="tpu",
                                  on_mesh=False)
        tiles = dict.fromkeys(([tuple(tile)] if tile else []) + list(TILES))
        forms = {"X1": (x1, "f32"), "X1d": (x1, "distinct"),
                 "X1b": (x1, "bf16"), "X2": (x2, "f32"), "X3": (x3, "f32")}
        for t in tiles:
            forms[f"K {t[0]}x{t[1]}"] = (functools.partial(kernel, t), "f32")
        first = next(iter(tiles))
        forms[f"Kb {first[0]}x{first[1]}"] = (
            functools.partial(kernel, first), "bf16")
        forms["prep"] = (functools.partial(prep, first), "f32")
        if args.widths:
            forms = {"X1": forms["X1"], f"K {first[0]}x{first[1]}":
                     forms[f"K {first[0]}x{first[1]}"]}
        if tied:
            forms["X1 +sum"] = (functools.partial(summed, x1), "tied")
            forms[f"K {first[0]}x{first[1]} +sum"] = (
                functools.partial(summed, functools.partial(kernel, first)),
                "tied")

        r = np.random.RandomState(7)
        ids_np = r.randint(0, vocab, (n,)).astype(np.int32)
        distinct_np = (r.permutation(max(vocab, n))[:n] % vocab).astype(
            np.int32)
        if args.lower:
            def arr(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

            ids, distinct = arr((n,), jnp.int32), arr((n,), jnp.int32)
            g = arr((n, d), jnp.float32)
            g16 = arr((n, d), jnp.bfloat16)
            other = arr((vocab, d), jnp.bfloat16)
        else:
            ids, distinct = jnp.asarray(ids_np), jnp.asarray(distinct_np)
            g = jnp.asarray(r.randn(n, d), jnp.float32)
            g16 = g.astype(jnp.bfloat16)
            other = jnp.asarray(r.randn(vocab, d), jnp.bfloat16)
        operands = {"f32": (ids, g), "distinct": (distinct, g),
                    "bf16": (ids, g16), "tied": (ids, g, other)}
        row = {"call": name, "shape": [n, vocab, d],
               "distinct_ids": int(len(np.unique(ids_np))),
               "tile": tile and list(tile)}
        want = {}
        for form, (f, kind) in forms.items():
            a = operands[kind]
            try:
                jitted = jax.jit(f)
                if args.lower:
                    jitted.lower(*a).compile()
                    row[form] = "compiled"
                else:
                    got = jitted(*a)
                    if form != "prep":
                        err = worst(got, want.setdefault(kind, got))
                        assert err < 1e-5, (name, form, err)
                    row[form] = ms(jitted, *a,
                                   calls=3 if form == "X3" else 10)
            except Exception as e:   # a tile Mosaic refuses: say so, go on
                row[form] = f"{type(e).__name__}: {str(e)[:200]}"
            print(name, form, row[form], flush=True)
        table.append(row)
        if not args.lower:
            out = OUT.replace(".json", "_widths.json") if args.widths else OUT
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
