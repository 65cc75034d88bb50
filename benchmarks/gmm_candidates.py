"""Time the grouped-matmul candidates alone on the chip, at a cell's shapes.

    python benchmarks/gmm_candidates.py rows --seeds 3000000001,3000000002
    python benchmarks/gmm_candidates.py time [--tiles 512,2048,1024 ...]

``rows`` fetches ``expert_rows`` of the cell's eval clone on its
correctness sample for each seed (the group sizes a freshly initialised
router really gives) into chiprun_out/gmm_group_sizes.json. ``time``
reads them (else the two routings recorded below) and times, for
[m, k] x [E, k, n] in both of the layer's orientations, forward / rows'
gradient / matrix's gradient of: libtpu's ``ragged_dot``, megablox at
its default tile and at 512^3, and the program's kernels
(paddle_tpu/parallel/grouped_matmul.py) at each candidate tile; the
table goes to chiprun_out/gmm_candidates.json. Two processes, because
the first holds 10 GB of model. How ``gmm_tile``'s answer was chosen
(PERF.md section 6, PR 31). Needs a TPU.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")
SIZES = os.path.join(OUT, "gmm_group_sizes.json")
# olmoe-train-s4096's expert_rows at two seeds (my chip run, PR 31: what
# ``rows`` fetched), for a ``time`` without a ``rows`` before it
ROUTED = {
    "routed_3000000001": [
        1163, 1200, 374, 1453, 1592, 384, 894, 1199, 591, 1010, 439,
        608, 1017, 1423, 1223, 1025, 309, 510, 1064, 791, 586, 489,
        618, 1152, 1011, 268, 671, 1064, 591, 1009, 408, 1327, 566,
        1133, 851, 1650, 1278, 741, 1846, 1263, 442, 185, 1541, 1553,
        1339, 1189, 1538, 1668, 1304, 1363, 1897, 818, 1364, 671, 854,
        376, 1362, 1091, 895, 1550, 1601, 1314, 1666, 1164],
    "routed_3000000002": [
        2642, 1190, 644, 724, 1196, 662, 645, 899, 746, 1410, 822,
        678, 1232, 586, 1005, 485, 934, 1448, 829, 661, 1462, 1446,
        1080, 619, 844, 1649, 1174, 1236, 1065, 1456, 475, 772, 449,
        917, 819, 2030, 408, 1217, 129, 580, 470, 657, 615, 1132,
        1418, 1822, 415, 626, 1129, 867, 785, 411, 943, 1468, 919,
        642, 718, 3048, 1102, 1821, 1620, 1953, 996, 694],
}


def fetch_rows(args):
    from perf import harness, models
    from perf.kinds import train

    cell = harness.load_json("perf", "workloads", f"{args.workload}.json")
    cfg = harness.load_json("perf", "configs", f"{cell['config']}.json")
    harness.require_tpu(cell["chips"])

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import jax_cache

    jax_cache.configure()
    fam = models.family(cfg)
    out = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        _, startup, evalp, _, model = models.build_train(cfg, seed)
        scope, exe = fluid.Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        sample = train.sample_of(fam.feeds(cfg, cell["traffic"], seed)[0])
        rows, = exe.run(evalp, feed=sample,
                        fetch_list=[model["expert_rows"][0]], scope=scope)
        out[f"routed_{seed}"] = [int(r) for r in np.asarray(rows)]
        print(seed, "max/mean", max(out[f"routed_{seed}"]) * len(rows)
              / sum(out[f"routed_{seed}"]), flush=True)
        exe.close()
        del scope, exe
    os.makedirs(OUT, exist_ok=True)
    with open(SIZES, "w") as f:
        json.dump(out, f)


def time_candidates(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import jax.experimental.pallas.ops.tpu.megablox  # noqa: F401

    from paddle_tpu.parallel import grouped_matmul as gm

    assert jax.default_backend() == "tpu", jax.default_backend()
    # (the package exports its function ``gmm`` over the module's name)
    megablox = sys.modules["jax.experimental.pallas.ops.tpu.megablox.gmm"]
    m, e = args.m, args.e
    sizes = json.load(open(SIZES)) if os.path.exists(SIZES) else ROUTED
    sizes = {k: v for k, v in sizes.items() if (sum(v), len(v)) == (m, e)}
    sizes["even"] = [m // e] * e
    first = next(iter(sizes.values()))
    # a routing with empty experts: the first's rows of its smallest
    # eighth of the experts go to its largest
    order = np.argsort(first)
    empt = np.array(first)
    empt[order[-1]] += empt[order[:e // 8]].sum()
    empt[order[:e // 8]] = 0
    sizes["empties"] = [int(x) for x in empt]
    assert all(sum(v) == m for v in sizes.values())
    names = list(sizes)
    main = names[0]
    print("group sizes:", {k: (max(v), min(v)) for k, v in sizes.items()},
          flush=True)

    def bench(fn, *xs):
        try:
            f = jax.jit(fn)
            jax.block_until_ready(f(*xs))
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = f(*xs)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / args.reps * 1e3
        except Exception as ex:  # a tile Mosaic refuses is a row, too
            print("   failed:", repr(ex).splitlines()[0][:200], flush=True)
            return None

    def rag(a, b, gs):
        return jax.lax.ragged_dot(a, b, gs)

    table = []
    r = np.random.RandomState(0)
    for k, n in ((2048, 1024), (1024, 2048)):
        lhs = jnp.asarray(r.randn(m, k), jnp.bfloat16)
        rhs = jnp.asarray(r.randn(e, k, n) * 0.02, jnp.bfloat16)
        g = jnp.asarray(r.randn(m, n), jnp.bfloat16)
        flop_ms = 2 * m * k * n / 197e12 * 1e3

        def row(what, which, fwd, dx, dw):
            gs = jnp.asarray(sizes[which], jnp.int32)
            ms = [bench(fwd, lhs, rhs, gs), bench(dx, g, rhs, lhs, gs),
                  bench(dw, lhs, g, rhs, gs)]
            table.append({"k": k, "n": n, "candidate": what, "sizes": which,
                          "fwd_ms": ms[0], "dx_ms": ms[1], "dw_ms": ms[2],
                          "flop_floor_ms": flop_ms})
            print(f"k{k} n{n} {what:34s} {which:18s} " + " ".join(
                "  ----" if x is None else f"{x:6.3f}" for x in ms),
                flush=True)

        def libtpu(which):
            row("libtpu ragged_dot", which, rag,
                lambda g, b, a, gs: jax.vjp(
                    lambda a: rag(a, b, gs), a)[1](g)[0],
                lambda a, g, b, gs: jax.vjp(
                    lambda b: rag(a, b, gs), b)[1](g)[0])

        def mega(which, tiling):
            row(f"megablox {tiling}", which,
                lambda a, b, gs: megablox.gmm(
                    a, b, gs, jnp.bfloat16, tiling),
                lambda g, b, a, gs: megablox.gmm(
                    g, b, gs, jnp.bfloat16, tiling, transpose_rhs=True),
                lambda a, g, b, gs: megablox.tgmm(
                    a.swapaxes(0, 1), g, gs, jnp.bfloat16, tiling))

        def own(which, tile):
            # (the rows' gradient contracts n and is k wide)
            tm, tk, tn = tile
            row(f"moe.* {tile}", which,
                lambda a, b, gs: gm.gmm(a, b, gs, tile),
                lambda g, b, a, gs: gm.gmm(g, b, gs, (tm, tn, tk),
                                           transpose_rhs=True),
                lambda a, g, b, gs: gm.tgmm(a, g, gs, tile))

        if args.tiles:
            tiles = [tuple(int(x) for x in t.split(","))
                     for t in args.tiles]
            tiles = [t for t in tiles if k % t[1] == 0 and n % t[2] == 0]
        else:
            tiles = [(tm, tk, tn) for tm in (128, 256, 512)
                     for tk in (k, 512) for tn in sorted({n, 1024, 512})
                     if tn <= n]
        libtpu(main)
        mega(main, (128, 128, 128))
        mega(main, (512, 512, 512))
        for tile in tiles:
            own(main, tile)
        picked = gm.gmm_tile(m, k, n, e, jnp.bfloat16)
        print("gmm_tile:", picked, flush=True)
        for which in names[1:]:
            libtpu(which)
            if picked:
                own(which, picked)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "gmm_candidates.json"), "w") as f:
        json.dump({"sizes": sizes, "rows": table}, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("rows", "time"))
    ap.add_argument("--workload", default="olmoe-train-s4096")
    ap.add_argument("--seeds", default="3000000001,3000000002")
    ap.add_argument("--m", type=int, default=65536)
    ap.add_argument("--e", type=int, default=64)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiles", nargs="*",
                    help="tm,tk,tn candidates (default: the whole table)")
    args = ap.parse_args()
    (fetch_rows if args.what == "rows" else time_candidates)(args)


if __name__ == "__main__":
    main()
