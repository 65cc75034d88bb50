"""Time the BTHD-small attention pair's candidates alone on the chip, at the
calls of the three cells that lower it (bf16, 8 or 12 heads of 64).

    chiprun -- python benchmarks/attn_small_candidates.py [--parent .parent]
    python benchmarks/attn_small_candidates.py --bundles DIR   # no chip

``attn.bthd_small.fwd`` / ``attn.bthd_small.bwd`` written once more here
with each of PR 49's edits as a switch, so that each is timed alone and
all together against the arithmetic the pair had before them (no edit):

- ``fold``: a power-of-two scale multiplies the (cq, h*dh) q block once
  (``fa._fold_scale``) and leaves the h (cq, tk) score blocks; the
  backward's ``* scale`` on ds leaves for dq's (cq, h*dh) result and
  dk's accumulator;
- ``late_norm``: 1 / l multiplies the (cq, dh) product behind P.V, not p;
- ``select``: dropout as ``where(bits < thresh, x, 0)`` on p (and dp),
  1 / p_keep in float32 on the (., dh) results (forward rows, dq, dk, dv);
  ``select_dp`` is the same with 1 / p_keep left as ONE multiply on dp;
- ``delta``: the backward kernel makes delta = sum(do * out) per head from
  the two blocks it holds; without it the entry point makes it in XLA
  (float32, a [b, tq, h] result with h on the lanes), as it did.
  ``delta_m`` sums whole 128-lane blocks under a mask
  (``fa._head_sums``) where ``delta`` slices each head of 64 out first;
- ``rmw``: dk and dv gathered by ONE read-modify-write a scratch, the
  heads side by side, not one a head; ``whole``: the backward at the
  forward's chunk (256 rows: one grid step a batch row);
- ``cols``: the per-row statistics of all heads as ONE (cq, h) array for
  log, reciprocal and the lse block (a (cq, 1) column a head fills one
  lane of 128 of every vreg it takes);
- ``iota`` (causal calls): the future mask as an in-kernel select on row
  and column numbers, no [tq, tk] bias block.

``module`` is the pair as paddle_tpu/parallel/flash_attention.py has it
and, with ``--parent DIR`` (``git archive`` of the commit before),
``parent`` is that checkout's. Each form is first held to ``module``'s
results (forward out; dq, dk, dv: the largest difference over the largest
entry), then timed: [the median of five stretches of 10 calls dispatched
back to back on the host's clock, the chip's busy ms a call over 5 traced
calls (the union of the trace's ``XLA Ops`` events: perf/trace.py)]. The
table goes to chiprun_out/attn_small_candidates.json (PERF.md section 6,
PR 49). ``--bundles DIR`` needs no chip: it compiles every form's kernels
for a described v5e, one process a kernel (libtpu aborts behind its
dumps), and counts the VLIW bundles of libtpu's final schedule, a grid
step of the forward and of the backward.
"""

import argparse
import functools
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
OUT = os.path.join(ROOT, "chiprun_out", "attn_small_candidates.json")
# call: (b, tq, tk, h, dh, bias: "pad" [b,1,1,tk] | "causal" [b,1,tq,tk])
CALLS = {
    "tbase_self": (128, 256, 256, 8, 64, "pad"),
    "tbase_causal": (128, 256, 256, 8, 64, "causal"),
    "tbase_cross": (128, 256, 256, 8, 64, "pad"),
    "dp4_self": (32, 256, 256, 8, 64, "pad"),
    "dp4_causal": (32, 256, 256, 8, 64, "causal"),
    "bert": (256, 128, 128, 12, 64, "pad"),
}
FORMS = {
    "none": (),
    "fold": ("fold",),
    "late_norm": ("late_norm",),
    "select": ("select",),
    "select_dp": ("select_dp",),
    "delta": ("delta",),
    "four": ("fold", "late_norm", "select", "delta"),
    "four_cols": ("fold", "late_norm", "select", "delta", "cols"),
    "four_cols_iota": ("fold", "late_norm", "select", "delta", "cols",
                       "iota"),
    "sdm": ("select", "delta_m"),
    "sdm_rmw": ("select", "delta_m", "rmw"),
    "sdm_whole_rmw": ("select", "delta_m", "rmw", "whole"),
}


def build(fa, edits, p_drop, causal):
    """(fwd(q, k, v, bias, seed), bwd(q, k, v, bias, seed, out, lse, g))
    of the pair with ``edits``; the entry points' plumbing is the
    module's own."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    p_keep = 1.0 - p_drop
    select = "select" in edits or "select_dp" in edits
    iota = "iota" in edits and causal

    def keep(seed_ref, i, j, cq, hi, tk):
        return fa._small_dropout_abs(seed_ref, i, j, cq, hi, tk, p_drop)

    def old_mask(kp):
        return kp.astype(jnp.bfloat16) * jnp.bfloat16(1.0 / p_keep)

    scores = fa._scores_head

    def fold(q2, scale):
        return fa._fold_scale(q2, scale) if "fold" in edits else (q2, scale)

    def future(j, cq, tk):
        if not iota:
            return None
        return (jax.lax.broadcasted_iota(jnp.int32, (cq, tk), 0) + j * cq
                >= jax.lax.broadcasted_iota(jnp.int32, (cq, tk), 1))

    def fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                   *, scale, h, dh, hb):
        i, j = pl.program_id(0), pl.program_id(1)
        q2, s_scale = fold(q_ref[0], scale)
        k2, v2 = k_ref[0], v_ref[0]
        cq, tk = q2.shape[0], k2.shape[0]
        tri = future(j, cq, tk)
        ss = [scores(q2, k2, hi, dh, s_scale, bias_ref, hb, tri)
              for hi in range(h)]
        ms = [jnp.max(s, axis=-1, keepdims=True) for s in ss]
        ps = [jnp.exp(s - m) for s, m in zip(ss, ms)]
        ls = [jnp.sum(p, axis=-1, keepdims=True) for p in ps]
        post = 1.0 / p_keep if select and p_drop > 0.0 else 1.0
        if "cols" in edits:
            m_all, l_all = (jnp.concatenate(x, axis=-1) for x in (ms, ls))
            r_all = jax.lax.reciprocal(fa._times(l_all, 1.0 / post))
            rs = [r_all[:, hi:hi + 1] for hi in range(h)]
            lse_ref[0] = m_all + jnp.log(l_all)
        else:
            rs = [jax.lax.reciprocal(fa._times(l, 1.0 / post)) for l in ls]
            lse_ref[0] = jnp.concatenate(
                [m + jnp.log(l) for m, l in zip(ms, ls)], axis=-1)
        if "late_norm" not in edits:
            ps = [p * r for p, r in zip(ps, rs)]
        if p_drop > 0.0:
            kps = [keep(seed_ref, i, j, cq, hi, tk) for hi in range(h)]
            if select:
                ps = [jnp.where(kp, p, 0.0) for kp, p in zip(kps, ps)]
            else:
                ps = [p * old_mask(kp) for kp, p in zip(kps, ps)]
        outs = [jax.lax.dot_general(
            p.astype(v2.dtype), fa._head(v2, hi, dh),
            (((1,), (0,)), ((), ())), preferred_element_type=f32)
            for hi, p in enumerate(ps)]
        if "late_norm" in edits:
            outs = [o * r for o, r in zip(outs, rs)]
        o_ref[0] = jnp.concatenate(
            [o.astype(o_ref.dtype) for o in outs], axis=-1)

    def bwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, x_ref,
                   lse_ref, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                   scale, nq, h, dh, hb):
        # x_ref: out (cq, h*dh) with ``delta``, else delta (cq, h)
        i, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        q2, k2, v2, do2 = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse2, x2 = lse_ref[0], x_ref[0]
        cq, tk = q2.shape[0], k2.shape[0]
        qs2, s_scale = fold(q2, scale)
        tri = future(j, cq, tk)
        ss = [scores(qs2, k2, hi, dh, s_scale, bias_ref, hb, tri)
              for hi in range(h)]
        ps = [jnp.exp(s - lse2[:, hi:hi + 1]) for hi, s in enumerate(ss)]
        dps = [jax.lax.dot_general(
            fa._head(do2, hi, dh), fa._head(v2, hi, dh),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
            for hi in range(h)]
        if "delta_m" in edits:
            deltas = fa._head_sums(do2.astype(f32) * x2.astype(f32), h, dh)
        elif "delta" in edits:
            deltas = [jnp.sum(fa._head(do2, hi, dh).astype(f32)
                              * fa._head(x2, hi, dh).astype(f32),
                              axis=-1, keepdims=True) for hi in range(h)]
        else:
            deltas = [x2[:, hi:hi + 1] for hi in range(h)]
        post = 1.0                      # what the (., dh) results still lack
        if p_drop > 0.0:
            kps = [keep(seed_ref, i, j, cq, hi, tk) for hi in range(h)]
            if "select_dp" in edits:
                pds = [jnp.where(kp, p, 0.0) for kp, p in zip(kps, ps)]
                dps = [jnp.where(kp, dp, 0.0) * (1.0 / p_keep)
                       for kp, dp in zip(kps, dps)]
            elif "select" in edits:
                pds = [jnp.where(kp, p, 0.0) for kp, p in zip(kps, ps)]
                dps = [jnp.where(kp, dp, 0.0) for kp, dp in zip(kps, dps)]
                deltas = [d * p_keep for d in deltas]
                post = 1.0 / p_keep
            else:
                drops = [old_mask(kp) for kp in kps]
                pds = [p * d for p, d in zip(ps, drops)]
                dps = [dp * d for dp, d in zip(dps, drops)]
        else:
            pds = ps
        dss = [p * (dp - d) for p, dp, d in zip(ps, dps, deltas)]
        dv_post = 1.0 / p_keep if select and p_drop > 0.0 else 1.0
        if "fold" in edits:
            dq_post = dk_post = scale * post
        else:
            dss = [ds * scale for ds in dss]
            dq_post = dk_post = post
        dqs = [jax.lax.dot_general(
            ds.astype(k2.dtype), fa._head(k2, hi, dh),
            (((1,), (0,)), ((), ())), preferred_element_type=f32)
            for hi, ds in enumerate(dss)]
        dq_ref[0] = fa._times(jnp.concatenate(dqs, axis=-1),
                              dq_post).astype(dq_ref.dtype)
        def t_dot(xs, y2):      # x_h^T @ y_h, the heads side by side
            return [jax.lax.dot_general(
                x.astype(y2.dtype), fa._head(y2, hi, dh),
                (((0,), (0,)), ((), ())), preferred_element_type=f32)
                for hi, x in enumerate(xs)]

        if "rmw" in edits:
            dv_scr[...] += jnp.concatenate(t_dot(pds, do2), axis=-1)
            dk_scr[...] += jnp.concatenate(t_dot(dss, q2), axis=-1)
        else:
            for hi, (dv_h, dk_h) in enumerate(zip(t_dot(pds, do2),
                                                  t_dot(dss, q2))):
                dv_scr[:, hi * dh:(hi + 1) * dh] += dv_h
                dk_scr[:, hi * dh:(hi + 1) * dh] += dk_h

        @pl.when(j == nq - 1)
        def _emit():
            dk_ref[0] = fa._times(dk_scr[...], dk_post).astype(dk_ref.dtype)
            dv_ref[0] = fa._times(dv_scr[...], dv_post).astype(dv_ref.dtype)

    def no_bias(kernel, at):
        def wrapped(*refs, **kw):
            return kernel(*refs[:at], None, *refs[at:], **kw)
        return wrapped

    def operands(q, k, v, bias, cq):
        b, tq, h, dh = q.shape
        tk, hdh = k.shape[1], h * dh
        if causal and not iota:
            bias = fa._combined_causal_bias(bias, tq, tk)
        specs = [pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),
                 pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),
                 pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0))]
        args = [q.reshape(b, tq, hdh), k.reshape(b, tk, hdh),
                v.reshape(b, tk, hdh)]
        if bias is not None:
            specs.append(fa._bias_spec_bthd(bias, cq, tk))
            args.append(bias)
        return specs, args, 1 if bias is None else bias.shape[1]

    def fwd(q, k, v, bias, seed):
        b, tq, h, dh = q.shape
        tk, hdh = k.shape[1], h * dh
        cq = fa._pick_cq(tq, tk, h)
        specs, args, hb = operands(q, k, v, bias, cq)
        kernel = fwd_kernel if len(args) == 4 else no_bias(fwd_kernel, 4)
        ops = (fa._seed_arr(seed), *args)
        out2, lse2 = pl.pallas_call(
            functools.partial(kernel, scale=dh ** -0.5, h=h, dh=dh, hb=hb),
            name="attn.bthd_small.fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b, tq // cq), in_specs=specs,
                out_specs=[
                    pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),
                    pl.BlockSpec((1, cq, h), lambda i, j, *_: (i, j, 0))]),
            out_shape=[fa._result(ops, (b, tq, hdh), q.dtype),
                       fa._result(ops, (b, tq, h), f32)])(*ops)
        return out2.reshape(b, tq, h, dh), lse2[..., None]

    def bwd(q, k, v, bias, seed, out, lse, g):
        b, tq, h, dh = q.shape
        tk, hdh = k.shape[1], h * dh
        cq = fa._pick_cq(tq, tk, h)
        if "whole" not in edits:
            cq = min(cq, fa._CQ)
        specs, args, hb = operands(q, k, v, bias, cq)
        kernel = bwd_kernel if len(args) == 4 else no_bias(bwd_kernel, 4)
        rows = pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0))
        stat = pl.BlockSpec((1, cq, h), lambda i, j, *_: (i, j, 0))
        whole = pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0))
        if "delta" in edits or "delta_m" in edits:
            x, x_spec = out.reshape(b, tq, hdh), rows
        else:
            x, x_spec = jnp.sum(g.astype(f32) * out.astype(f32),
                                axis=-1), stat
        ops = (fa._seed_arr(seed), *args, g.reshape(b, tq, hdh), x,
               lse[..., 0])
        dq2, dk2, dv2 = pl.pallas_call(
            functools.partial(kernel, scale=dh ** -0.5, nq=tq // cq, h=h,
                              dh=dh, hb=hb),
            name="attn.bthd_small.bwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b, tq // cq),
                in_specs=specs + [rows, x_spec, stat],
                out_specs=[rows, whole, whole],
                scratch_shapes=[pltpu.VMEM((tk, hdh), f32),
                                pltpu.VMEM((tk, hdh), f32)]),
            out_shape=[fa._result(ops, (b, tq, hdh), q.dtype),
                       fa._result(ops, (b, tk, hdh), k.dtype),
                       fa._result(ops, (b, tk, hdh), v.dtype)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=32 * 2**20),
        )(*ops)
        return (dq2.reshape(b, tq, h, dh), dk2.reshape(b, tk, h, dh),
                dv2.reshape(b, tk, h, dh))

    return fwd, bwd


def module_pair(fa, p_drop, causal):
    def fwd(q, k, v, bias, seed):
        return fa.flash_attention_bthd_fwd(q, k, v, bias, seed, None, p_drop,
                                           causal)

    def bwd(q, k, v, bias, seed, out, lse, g):
        return fa.flash_attention_bthd_bwd(q, k, v, bias, seed, out, lse, g,
                                           None, p_drop, causal)
    return fwd, bwd


def load(root):
    spec = importlib.util.spec_from_file_location(
        "parent_flash_attention",
        os.path.join(root, "paddle_tpu/parallel/flash_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pair_of(form, fa, parent, p_drop, causal):
    if form == "module":
        return module_pair(fa, p_drop, causal)
    if form == "parent":
        return module_pair(parent, p_drop, causal)
    return build(fa, FORMS[form], p_drop, causal)


def shapes(call, make):
    b, tq, tk, h, dh, bias = CALLS[call]
    import jax.numpy as jnp
    q, g = (make((b, tq, h, dh), jnp.bfloat16) for _ in range(2))
    k, v = (make((b, tk, h, dh), jnp.bfloat16) for _ in range(2))
    pad = make((b, 1, 1, tk), jnp.float32)
    return q, k, v, g, pad, bias == "causal"


def bundles(args):
    """One process a (form, call, dropout, pass): libtpu aborts behind
    its dumps. -> table of final-schedule bundle counts."""
    table = {}
    jobs = [(form, call, p, which)
            for form in args.forms for call in args.calls
            for p in args.dropout for which in ("fwd", "bwd")]
    running = []

    def reap(block):
        for job in list(running):
            proc, key, d = job
            if block:
                proc.wait()
            if proc.poll() is None:
                continue
            running.remove(job)
            found = glob.glob(os.path.join(
                d, "*attn.bthd_small.%s*schedule-analysis_final_bundles.txt"
                % key[3]))
            n = None
            if found:
                m = re.search(r"total scheduled bundles:\s+(\d+)",
                              open(found[0]).read())
                n = int(m.group(1)) if m else None
            for path in glob.glob(os.path.join(d, "*")):   # ~0.3 GB a kernel
                if not path.endswith("final_bundles.txt"):
                    os.remove(path)
            table["%s %s p%s %s" % key] = n
            print(*key, n, flush=True)

    for key in jobs:
        while len(running) >= args.jobs:
            reap(False)
            time.sleep(0.5)
        d = os.path.join(args.bundles, "%s_%s_p%s_%s" % key)
        os.makedirs(d, exist_ok=True)
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                   ALLOW_MULTIPLE_LIBTPU_LOAD="1",
                   LIBTPU_INIT_ARGS="--xla_jf_dump_to=%s "
                   "--xla_jf_dump_llo_text=true" % d)
        cmd = [sys.executable, os.path.abspath(__file__), "--lower-one",
               *map(str, key)]
        if args.parent:
            cmd += ["--parent", args.parent]
        running.append((subprocess.Popen(
            cmd, env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(d, "stderr.txt"), "w")), key, d))
    reap(True)
    while running:
        reap(True)
    with open(os.path.join(args.bundles, "bundles.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


def lower_one(form, call, p_drop, which, parent_root):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.parallel import flash_attention as fa

    sh = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    parent = load(parent_root) if parent_root else None
    for mod in (fa, parent):
        if mod is not None:
            mod.kernels_enabled = lambda: True
    p_drop = float(p_drop)

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    q, k, v, g, pad, causal = shapes(call, make)
    fwd, bwd = pair_of(form, fa, parent, p_drop, causal)
    seed = make((), jnp.int32)
    if which == "fwd":
        jax.jit(fwd).lower(q, k, v, pad, seed).compile()
    else:
        lse = make(q.shape[:3] + (1,), jnp.float32)
        jax.jit(bwd).lower(q, k, v, pad, seed, q, lse, g).compile()
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=list(CALLS))
    ap.add_argument("--forms", nargs="*", default=None)
    ap.add_argument("--dropout", nargs="*", type=float, default=[0.1, 0.0])
    ap.add_argument("--parent", default=None)
    ap.add_argument("--bundles", default=None, metavar="DIR")
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--lower-one", nargs=4, default=None)
    args = ap.parse_args()
    if args.lower_one:
        return lower_one(*args.lower_one, args.parent)
    if args.forms is None:
        args.forms = list(FORMS) + ["module"] + (
            ["parent"] if args.parent else [])
    if args.bundles:
        return bundles(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("attn_small_candidates: no TPU", file=sys.stderr)
        return 2
    from paddle_tpu.parallel import flash_attention as fa
    from perf import trace

    parent = load(args.parent) if args.parent else None

    def ms(f, *a):
        jax.block_until_ready(f(*a))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            outs = [f(*a) for _ in range(10)]
            jax.block_until_ready(outs)
            del outs
            took.append((time.perf_counter() - t0) * 1e3 / 10)
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                jax.block_until_ready([f(*a) for _ in range(5)])
            doc = trace.load(trace.find_xplane(d))
        ops = [ev for line in doc["planes"][0]["lines"]
               if line["name"] == trace.OPS_LINE for ev in line["events"]]
        busy = trace.union_ns([(s, s + dur) for _, s, dur in ops]) / 5e6
        return [round(statistics.median(took), 4), round(busy, 4)]

    def worst(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return round(float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)),
                     5)

    table = []
    for call in args.calls:
        r = np.random.RandomState(7)

        def make(shape, dtype):
            if dtype == jnp.float32:        # the pad bias: 9 keys masked
                x = np.zeros(shape, np.float32)
                x[..., -9:] = -1e9
                return jnp.asarray(x)
            return jnp.asarray(r.randn(*shape) * 0.5, dtype)

        q, k, v, g, pad, causal = shapes(call, make)
        seed = jnp.int32(1234)
        for p_drop in args.dropout:
            row = {"call": call, "shape": list(CALLS[call]),
                   "p_drop": p_drop}
            want = None
            for form in ["module"] + [f for f in args.forms
                                      if f != "module"]:
                if "iota" in FORMS.get(form, ()) and not causal:
                    continue
                try:
                    fwd, bwd = (jax.jit(f) for f in pair_of(
                        form, fa, parent, p_drop, causal))
                    out, lse = fwd(q, k, v, pad, seed)
                    got = (out, *bwd(q, k, v, pad, seed, out, lse, g))
                    if want is None:
                        want = got
                    row[form] = {
                        "fwd_ms": ms(fwd, q, k, v, pad, seed),
                        "bwd_ms": ms(bwd, q, k, v, pad, seed, out, lse, g),
                        "worst_vs_module": [worst(a, w)
                                            for a, w in zip(got, want)]}
                except Exception as e:  # a form Mosaic refuses is a row
                    row[form] = {"error": "%s: %s" % (type(e).__name__,
                                                      str(e)[:300])}
                print(call, p_drop, form, row[form], flush=True)
            table.append(row)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
