"""Time the gated delta rule's triangle inverse alone on the chip, and the
two passes whole, at qwen3next-train-s8192's call (b1 t8192 hk16 hv32,
dk = dv = 128, bf16: 256 grid steps of 2 value heads x 8 chunks, 16
matrices [64, 64] a step).

    chiprun -- python benchmarks/gdn_candidates.py [--parent .parent]

T = (I + A)^-1 of a grid step's 16 strictly lower float32 triangles, 256
grid steps a call as the kernels run them, each candidate a kernel of its
own that keeps the one block in VMEM (no DMA: ``copy`` moves the block
and does nothing, what the harness itself costs):

- ``rank1``: the form until PR 46, 63 rank-1 steps on the VPU, a matrix
  a [64, 128] tile with half its lanes empty;
- ``blocked``: ``gated_delta_rule._invert``: the 16 x 16 diagonal
  blocks by substitution, eight across the lanes (A's columns spread
  over their blocks by ``_columns``' tree of lane rolls), then two
  merges as ``HIGHEST`` products against a block diagonal, every pair's
  first product before any pair's second;
- ``blocked.sub``: its substitution alone (T's diagonal blocks);
- ``blocked.by_pair``: the merges a pair at a time (a pair's second
  product written behind its first: the MXU waits);
- ``blocked.pieces``: the merges' products as the six products of bf16
  pieces by hand, each piece of the right-hand side pushed once;
- ``blocked.rolled``: A's columns one at a time, five lane rolls a vreg;
- ``blocked.mxu``: A's columns by products with a 0 / 1 matrix at
  ``HIGHEST`` (exact);
- ``blocked.alone``: a matrix with zeros beside it in place of a pair
  (what an odd value head pays).

Each is held to numpy's float64 inverse first (``err``: the largest
difference over the largest entry, the 16 matrices).

Then the two passes whole, ``gdn.rule.fwd`` and ``gdn.rule.bwd``, in the
forms of their chunk loops (PERF.md section 6, PR 56), each held to the
float32 recurrence first (``err``: o and the five gradients at 512
positions, one key and two value heads, the largest difference over the
largest entry):

- ``change``: as the module has them: a pre-pass makes each per-chunk
  quantity once for all the chunks of a grid step, [U | W] = T [beta V |
  beta K e^G] is one batched product a head, the sequential loop keeps
  the state chain, and backward T^T [dV' | dW] and dA (one contraction
  of 256) and the rest stand behind it as one straight line;
- ``parent`` (``--parent DIR``, a checkout of the commit before:
  ``git archive``): the parent's loop, everything of a chunk in one
  body in front of the state chain, the gates made twice;
- ``uw.apart``: T (beta V) and T (beta K e^G) as two products;
- ``uw.by_matrix``: [U | W] a matrix at a time, not one batched product;
- ``tt.apart``: T^T dV' and T^T dW as two products;
- ``da.apart``: dA as two products of 128 and an add;
- ``chain.1`` / ``chain.2`` / ``chain.8``: that many chunks of the state
  chain a loop body (the module's ``_UNROLL`` is 4);
- ``NAME`` (``--form NAME=FILE``): any other copy of the module, as a
  builder keeps of a step on the way (gates once and the passes apart,
  the passes staggered into the state loop, ...).

ms a call twice over: [the median of five stretches of 10 calls
dispatched back to back on the host's clock, the chip's busy time over
5 traced calls (the union of the trace's ``XLA Ops`` events:
perf/trace.py)]. The table goes to chiprun_out/gdn_candidates.json.
Needs a TPU; ``--lower`` compiles every candidate for a described v5e
instead, and ``--bundles DIR`` (no chip either) compiles each form's two
passes in a process of its own with libtpu's dumps in DIR and counts the
VLIW bundles of its final schedule: the kernel's straight line a grid
step and each loop's body (DIR/bundles.json).
"""

import argparse
import glob
import importlib.util
import json
import os
import re
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "gdn_candidates.json")
CALL = (1, 8192, 16, 32)      # batch, positions, key heads, value heads
KINDS = ("copy", "rank1", "blocked", "blocked.sub", "blocked.by_pair",
         "blocked.pieces", "blocked.rolled", "blocked.mxu", "blocked.alone")
LOOPS = ("uw.apart", "uw.by_matrix", "tt.apart", "da.apart", "chain.1",
         "chain.2", "chain.8")


def loop_patches(gdr):
    """name -> {name in the module: the form it had or could have}."""
    import jax
    import jax.numpy as jnp

    c, hi, f32 = gdr.CHUNK, gdr._HIGHEST, jnp.float32

    def bdot(a, b, ca, cb):
        return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                                   precision=hi, preferred_element_type=f32)

    def apply_with(product):
        def apply(t_ref, p, *, heads, chunks):
            for r in range(heads):
                ms = gdr._mats(r, chunks)
                uw = product(gdr._t_of(t_ref, r, chunks), p["uw"][ms])
                p["uw"][ms] = uw
                p["wq"][ms, :c] = uw[:, :, 128:].astype(p["wq"].dtype)
        return apply

    def uw_apart(t, x):
        return jnp.concatenate([bdot(t, x[:, :, i:i + 128], 2, 1)
                                for i in (0, 128)], axis=2)

    def uw_by_matrix(t, x):
        return jnp.stack([gdr._dot(t[m], x[m], 1, 0, hi)
                          for m in range(t.shape[0])])

    def through_t_apart(t, dd):
        return jnp.concatenate([bdot(t, dd[:, :, i:i + 128], 1, 1)
                                for i in (0, 128)], axis=2)

    def d_triangle_apart(dr, uw):
        return -(bdot(dr[:, :, :128], uw[:, :, :128], 2, 2)
                 + bdot(dr[:, :, 128:], uw[:, :, 128:], 2, 2))

    return {"uw.apart": {"_apply": apply_with(uw_apart)},
            "uw.by_matrix": {"_apply": apply_with(uw_by_matrix)},
            "tt.apart": {"_through_t": through_t_apart},
            "da.apart": {"_d_triangle": d_triangle_apart},
            "chain.1": {"_UNROLL": 1}, "chain.2": {"_UNROLL": 2},
            "chain.8": {"_UNROLL": 8}}


def load_form(name, path):
    spec = importlib.util.spec_from_file_location(
        "gated_delta_rule_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def form_files(args):
    files = dict(x.split("=", 1) for x in args.form)
    if args.parent:
        files["parent"] = os.path.join(
            args.parent, "paddle_tpu/parallel/gated_delta_rule.py")
    return files


def passes_of(form, files):
    """-> (module, {name: patch}) whose ``gated_delta_rule_fwd`` / ``_bwd``
    are ``form``'s."""
    from paddle_tpu.parallel import gated_delta_rule as gdr

    if form in files:
        return load_form(form, files[form]), {}
    return gdr, loop_patches(gdr).get(form, {})


def loop_bundles(path):
    """libtpu's final schedule of one kernel -> (bundles in all, bundles
    outside any loop of the grid step, [each loop's body])."""
    straight, loops, run = 0, [], 0
    for line in open(path):
        m = re.match(r"^\s*0x[0-9a-f]+\s*(?:[A-Z]{2})?:\s*(>*)\s*\{", line)
        if not m:
            continue
        if len(m.group(1)) >= 2:
            run += 1
            continue
        straight += 1
        if run:
            loops.append(run)
            run = 0
    if run:
        loops.append(run)
    # (a body of a few bundles is a DMA wait or a copy, not a chunk loop)
    return (straight + sum(loops), straight + sum(x for x in loops if x < 16),
            [x for x in loops if x >= 16])


def bundles(args):
    """One process a (form, pass): libtpu aborts behind its dumps."""
    forms = ["change"] + [x for x in args.loops if x != "change"] + list(
        form_files(args))
    table, running = {}, []

    def reap(block):
        for job in list(running):
            proc, form, which, d = job
            if block:
                proc.wait()
            if proc.poll() is None:
                continue
            running.remove(job)
            found = [f for f in glob.glob(os.path.join(
                d, "*gdn.rule.%s*final_bundles.txt" % which))
                if "schedule-analysis" not in f]
            if found:
                total, straight, loops = loop_bundles(found[0])
                table["%s %s" % (form, which)] = {
                    "bundles": total, "straight_line": straight,
                    "loop_bodies": loops}
            else:
                table["%s %s" % (form, which)] = None
            for path in glob.glob(os.path.join(d, "*")):  # ~0.2 GB a kernel
                if not path.endswith(("final_bundles.txt", "stderr.txt")):
                    os.remove(path)
            print(form, which, table["%s %s" % (form, which)], flush=True)

    for form in forms:
        for which in ("fwd", "bwd"):
            while len(running) >= args.jobs:
                reap(False)
                time.sleep(0.5)
            d = os.path.join(args.bundles, "%s_%s" % (form, which))
            os.makedirs(d, exist_ok=True)
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       TPU_LOG_DIR="disabled", ALLOW_MULTIPLE_LIBTPU_LOAD="1",
                       LIBTPU_INIT_ARGS="--xla_jf_dump_to=%s "
                       "--xla_jf_dump_llo_text=true" % d)
            cmd = [sys.executable, os.path.abspath(__file__), "--lower-one",
                   form, which]
            for x in args.form:
                cmd += ["--form", x]
            if args.parent:
                cmd += ["--parent", args.parent]
            running.append((subprocess.Popen(
                cmd, env=env, stdout=subprocess.DEVNULL,
                stderr=open(os.path.join(d, "stderr.txt"), "w")),
                form, which, d))
    while running:
        reap(True)
    with open(os.path.join(args.bundles, "bundles.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


def lower_one(form, which, args):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    mod, patch = passes_of(form, form_files(args))
    for name, fn in patch.items():
        setattr(mod, name, fn)
    b, t, hk, hv = CALL
    bf, f32 = jnp.bfloat16, jnp.float32
    tile = mod.gdn_tile(t, hk, hv, 128, 128, 64, bf, "tpu", False)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    q, v = arr((b, t, hk, 128), bf), arr((b, t, hv, 128), bf)
    g = arr((b, t, hv), f32)
    if which == "fwd":
        jax.jit(lambda *x: mod.gated_delta_rule_fwd(*x, tile)).lower(
            q, q, v, g, g).compile()
    else:
        st = arr((t // 64, b, hv, 128, 128), bf)
        jax.jit(lambda *x: mod.gated_delta_rule_bwd(*x, tile)).lower(
            q, q, v, g, g, st, v).compile()
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kinds", nargs="*", default=list(KINDS))
    ap.add_argument("--parent", default=None)
    ap.add_argument("--form", action="append", default=[],
                    metavar="NAME=FILE")
    ap.add_argument("--loops", nargs="*", default=list(LOOPS))
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--bundles", default=None, metavar="DIR")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--lower-one", nargs=2, default=None)
    args = ap.parse_args()
    if args.lower_one:
        return lower_one(*args.lower_one, args)
    if args.bundles:
        return bundles(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.parallel import gated_delta_rule as gdr
    from perf import trace

    sharding = None
    if args.lower:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        print("gdn_candidates: no TPU", file=sys.stderr)
        return 2

    f32, bf = jnp.float32, jnp.bfloat16
    c, lanes, block = gdr.CHUNK, 128, 16
    b, t, hk, hv = CALL
    heads, chunks = gdr.gdn_tile(t, hk, hv, 128, 128, c, bf, "tpu", False)
    mats = heads * chunks                       # a grid step's matrices
    n = b * hv * (t // c)                       # a call's

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
        busy = trace.union_ns([(s, s + dur) for _, s, dur in ops]) / 5e6
        return [round(statistics.median(took), 4), round(busy, 4)]

    # -- the inversion's candidates ---------------------------------------

    def rank1(a_ref, t_ref):
        ii, jj = gdr._iotas()
        t_ref[...] = jnp.broadcast_to((ii == jj).astype(f32), t_ref.shape)
        for j in range(c - 1):
            r0 = j // 8 * 8
            t_ref[:, r0:, :] = (t_ref[:, r0:, :] - a_ref[:, r0:, j:j + 1]
                                * t_ref[:, j:j + 1, :])

    def columns_rolled(d):      # a column at a time: 5 rolls a vreg
        lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 2)
        cols = []
        for j in range(block):
            col = d if j == 0 else pltpu.roll(d, lanes - j, axis=2)
            for s in (1, 2, 4, 8):
                col = jnp.where(lane % (2 * s) >= s,
                                pltpu.roll(col, s, axis=2), col)
            cols.append(col)
        return cols

    def columns_mxu(d):         # a column at a time: a 0 / 1 product
        flat = d.reshape(-1, lanes)
        lane = jax.lax.broadcasted_iota(jnp.int32, flat.shape, 1)
        ones = (jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 0) // block
                == jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 1)
                // block).astype(f32)
        return [gdr._dot(jnp.where(lane % block == j, flat, 0.0), ones, 1, 0,
                         gdr._HIGHEST).reshape(d.shape) for j in range(block)]

    def pieces(x):      # three float32 pieces of 8 bits each: bf16's
        def top(v):
            bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
            return jax.lax.bitcast_convert_type(
                bits & jnp.uint32(0xFFFF0000), f32)

        hi = top(x)
        mid = top(x - hi)
        return hi, mid, x - hi - mid

    def times_blocks_pieces(x, y, size, below):
        # HIGHEST's six products by hand: each piece of the right-hand
        # side pushed once, the left-hand side's under one another
        xs, ys = pieces(x), pieces(y)
        p = [jax.lax.dot_general(
            jnp.concatenate(xs[:3 - i], axis=1),
            gdr._diagonal(ys[i], size, below), (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.DEFAULT, preferred_element_type=f32)
            for i in range(3)]
        return ((p[0][:, 2 * size:] + p[2] + p[1][:, size:])
                + (p[0][:, size:2 * size] + p[1][:, :size])) + p[0][:, :size]

    def merge_by_pair(a_ref, y, size):      # a pair's two products in turn
        return jnp.concatenate([merge(a_ref.at[i:i + 1], y[i:i + 1], size)
                                for i in range(y.shape[0])], axis=0)

    def sub_only(a_ref, t_ref, x_ref):
        gdr._substitute(a_ref, x_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 2)
        tt = jnp.concatenate(
            [jnp.where(lane // block % 4 == i, x_ref[...], 0.0)
             for i in range(4)], axis=1)
        for half in range(2):
            t_ref[:, half] = tt[:, :, half * c:(half + 1) * c]

    def call_of(kind):
        """A [16, 64, 64] -> T [16, 64, 64] through one candidate."""
        alone = kind == "blocked.alone"
        blocked = kind.startswith("blocked")
        pairs = mats if alone else mats // 2
        a_shape, t_shape, scratch = (mats, c, c), (mats, c, c), []
        if blocked:
            a_shape, t_shape = (pairs, c, 2 * c), (pairs, 2, c, c)
            scratch = [pltpu.VMEM((pairs, block, lanes), f32)]

        def kernel(a_ref, t_ref, *rest):
            if kind == "copy":
                t_ref[...] = a_ref[...]
            elif kind == "rank1":
                rank1(a_ref, t_ref)
            elif kind == "blocked.sub":
                sub_only(a_ref, t_ref, *rest)
            else:
                gdr._invert(a_ref, t_ref, *rest)

        def layout(a):
            if alone:       # (A | 0)
                return jnp.pad(a, ((0, 0), (0, 0), (0, c)))
            if blocked:     # (A_2p | A_2p+1)
                return a.reshape(mats // 2, 2, c, c).transpose(
                    0, 2, 1, 3).reshape(mats // 2, c, 2 * c)
            return a

        def call(a):
            t = pl.pallas_call(
                kernel, name="inv." + kind,
                out_shape=jax.ShapeDtypeStruct(t_shape, f32),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=0, grid=(n // mats,),
                    in_specs=[pl.BlockSpec(a_shape,
                                           lambda i: (0,) * len(a_shape))],
                    out_specs=pl.BlockSpec(t_shape,
                                           lambda i: (0,) * len(t_shape)),
                    scratch_shapes=scratch),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=32 * 2**20))(a)
            return t[:, 0] if alone else t.reshape(-1, c, c)

        return layout, call

    table = {"call": list(CALL), "tile": [heads, chunks], "matrices": n}
    merge = gdr._merge
    patches = {"blocked.rolled": {"_columns": columns_rolled},
               "blocked.mxu": {"_columns": columns_mxu},
               "blocked.pieces": {"_times_blocks": times_blocks_pieces},
               "blocked.by_pair": {"_merge": merge_by_pair}}
    r = np.random.RandomState(0)
    a_np = np.tril(r.randn(mats, c, c) * 0.3, -1).astype(np.float32)
    want = np.linalg.inv(np.eye(c) + a_np.astype(np.float64))
    a = (jax.ShapeDtypeStruct(a_np.shape, f32, sharding=sharding)
         if args.lower else jnp.asarray(a_np))
    for kind in args.kinds:
        patch = patches.get(kind, {})
        kept = {name: getattr(gdr, name) for name in patch}
        for name, form in patch.items():
            setattr(gdr, name, form)
        try:
            layout, call = call_of(kind)
            f = jax.jit(call)
            if args.lower:
                jax.jit(lambda x: call(layout(x))).lower(a).compile()
                table[kind] = "compiled"
            else:
                laid = jax.jit(layout)(a)
                got = np.asarray(f(laid), np.float64)
                err = float(np.abs(got - want).max() / np.abs(want).max())
                whole = kind not in ("copy", "blocked.sub")
                table[kind] = {"ms": ms(f, laid),
                               "err": err if whole else None}
        except Exception as e:   # a form Mosaic refuses: say so, go on
            table[kind] = f"{type(e).__name__}: {str(e)[:300]}"
        finally:
            for name, form in kept.items():
                setattr(gdr, name, form)
        print(kind, table[kind], flush=True)

    # -- the two passes whole ---------------------------------------------

    def arr(shape, dtype, scale=1.0, shift=0.0):
        if args.lower:
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        return jnp.asarray(r.rand(*shape) * scale + shift if dtype == f32
                           else r.randn(*shape), dtype)

    q, k = arr((b, t, hk, 128), bf), arr((b, t, hk, 128), bf)
    v, do = arr((b, t, hv, 128), bf), arr((b, t, hv, 128), bf)
    g, beta = arr((b, t, hv), f32, -0.5), arr((b, t, hv), f32)
    from paddle_tpu.ops import linear_attention_ops as L

    files = form_files(args)
    small = [jnp.asarray(x, dt) for x, dt in (
        (r.randn(1, 512, 1, 128), bf), (r.randn(1, 512, 1, 128), bf),
        (r.randn(1, 512, 2, 128), bf), (-r.rand(1, 512, 2) * 0.5, f32),
        (r.rand(1, 512, 2), f32), (r.randn(1, 512, 2, 128), bf))
    ] if not args.lower else None

    def recurrence_err(mod):
        """o and the five gradients at 512 positions against the
        float32 recurrence and jax's vjp of it."""
        sq, sk, sv, sg, sb, sdo = small
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(L.recurrent_gated_delta_rule, sq.astype(f32),
                               sk.astype(f32), sv.astype(f32), sg, sb)
            want = (out, *vjp(sdo.astype(f32)))
        o, st = jax.jit(lambda *x: mod.gated_delta_rule_fwd(*x, (2, 8)))(
            sq, sk, sv, sg, sb)
        got = (o, *jax.jit(lambda *x: mod.gated_delta_rule_bwd(*x, (2, 8)))(
            sq, sk, sv, sg, sb, st, sdo))
        return max(float(jnp.abs(x.astype(f32) - y).max() / jnp.abs(y).max())
                   for x, y in zip(got, want))

    outs = {}
    for side in ["change"] + list(args.loops) + list(files):
        mod, patch = passes_of(side, files)
        kept = {name: getattr(mod, name) for name in patch}
        for name, form in patch.items():
            setattr(mod, name, form)
        try:
            fwd = jax.jit(lambda *x, m=mod: m.gated_delta_rule_fwd(
                *x, (heads, chunks)))
            bwd = jax.jit(lambda *x, m=mod: m.gated_delta_rule_bwd(
                *x, (heads, chunks)))
            if args.lower:
                st = jax.ShapeDtypeStruct((t // c, b, hv, 128, 128), bf,
                                          sharding=sharding)
                fwd.lower(q, k, v, g, beta).compile()
                bwd.lower(q, k, v, g, beta, st, do).compile()
                table[side] = "compiled"
            else:
                err = recurrence_err(mod)
                o, st = fwd(q, k, v, g, beta)
                outs[side] = (o, *bwd(q, k, v, g, beta, st, do))
                table[side] = {"err": err,
                               "fwd_ms": ms(fwd, q, k, v, g, beta),
                               "bwd_ms": ms(bwd, q, k, v, g, beta, st, do)}
        except Exception as e:   # a form Mosaic refuses: say so, go on
            table[side] = f"{type(e).__name__}: {str(e)[:300]}"
        finally:
            for name, form in kept.items():
                setattr(mod, name, form)
        print(side, table[side], flush=True)
    for side in outs:       # each side's results, o and the gradients
        if "parent" in outs and side != "parent":
            table[side + "_vs_parent"] = [
                float(np.abs(np.asarray(x, np.float32)
                             - np.asarray(y, np.float32)).max()
                      / np.abs(np.asarray(y, np.float32)).max())
                for x, y in zip(outs[side], outs["parent"])]
            print(side + "_vs_parent", table[side + "_vs_parent"],
                  flush=True)
    if not args.lower:
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
