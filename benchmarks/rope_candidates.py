"""Time the rotary embedding's candidates alone on the chip, from the
projection's token-major q and k to head-major rotated q and k and back
(bf16), at the calls of the four decoder cells.

    chiprun -- python benchmarks/rope_candidates.py [--calls olmoe ...]

X1 today's lowering: split, reshape, transpose [0, 2, 1, 3], then
``ops/attention_ops._rotate``; X2 an XLA form that rotates token-major
(the tables broadcast over the heads) and transposes after; A the kernel
``rope.fwd`` / ``rope.bwd`` head-major in and out behind XLA's transpose;
B the kernel token-major in, head-major out (and back), at
``rope_tile``'s answer and at the other blocks it was weighed against
(rows x heads of q a grid step); Bf is B with the slice of the
projection's result left to XLA to fuse into the call's operand
(``allow_input_fusion``). Forward and backward (the vjp, cotangents
head-major in, token-major out) of each, held to X1's results first;
then ms a call, the median of five stretches of 10 calls dispatched back
to back (host clock around one ``block_until_ready``). JoyAI's
interleaved 64-wide part and Qwen3-Next's 64 of 256 have no kernel
(``rope_tile`` answers None): X1 and X2 alone. The table goes to
chiprun_out/rope_candidates.json (PERF.md section 6, PR 42). Needs a
TPU; ``--lower`` compiles every candidate for a described v5e instead
and prints the bytes XLA's compiled module accesses.

``--norm`` times, in the same process and nothing else, the per-head
QK-norm's two forms at ``sdar-train-s4096``'s call ([1, 8192, 32 + 4
heads of 128], two runs of the positions): N3 ``rms_norm``'s lines on q
and on k as XLA's ops in front of ``rope.fwd`` (backward: ``rope.bwd``,
then the two norms' vjp), and NF the ONE call that brings the gains
(PR 66), NF held to N3's results first. A call alone on an idle chip
reads about 1.7x its time inside a step (PERF.md section 6, PR 49):
compare the forms, never add their ms into a step.
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
OUT = os.path.join(ROOT, "chiprun_out", "rope_candidates.json")
# call: (b, t, q heads, k heads, dh, lanes of the projection's result
# behind k (v, a gate: what q and k are sliced out of), rotary_dim,
# interleaved)
CALLS = {
    "smallthinker": (1, 16384, 28, 4, 128, 512, None, False),
    "olmoe": (2, 4096, 16, 16, 128, 0, None, False),
    "qwen3next": (1, 8192, 16, 2, 256, 0, 64, False),
    "joyai": (1, 4096, 32, 1, 64, 0, None, True),
}
# --norm: (b, t, q heads, k heads, dh, lanes behind k, periods, epsilon)
NORM_CALL = (1, 8192, 32, 4, 128, 512, 2, 1e-6)
# (rows, heads of q) of a grid step B is also timed at; "h" all of q's
# heads, "hk" as many as k has
BLOCKS = ((64, "h"), (128, "h"), (256, "h"), (512, "h"), (1024, "h"),
          (128, "hk"), (256, "hk"), (512, "hk"), (2048, "hk"), (2048, 1))
# rows of a pass of the loop inside a grid step B is also timed at (at
# rope_tile's block)
PASSES = (16, 64)
THETA = 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*", default=list(CALLS))
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--norm", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.attention_ops import _rotate
    from paddle_tpu.parallel import rope

    sharding = None
    if args.lower:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        print("rope_candidates: no TPU", file=sys.stderr)
        return 2

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

    if args.norm:
        return norm_forms(args.lower, sharding, ms, worst)

    table = []
    for name in args.calls:
        b, t, h, hk, dh, rest, rd, il = CALLS[name]
        widths = [h * dh, hk * dh] + ([rest] if rest else [])

        def split(qkv):
            """Token-major q [b, t, h, dh] and k as the builders get
            them: slices of the projection's result, reshaped."""
            q, k = jnp.split(qkv, np.cumsum(widths)[:-1], axis=-1)[:2]
            return q.reshape(b, t, h, dh), k.reshape(b, t, hk, dh)

        def joined(dq, dk):
            """The projection's cotangent from token-major dq and dk."""
            parts = [dq.reshape(b, t, -1), dk.reshape(b, t, -1)]
            if rest:
                parts.append(jnp.zeros((b, t, rest), dq.dtype))
            return jnp.concatenate(parts, -1)

        def heads_first(z):
            return jnp.swapaxes(z, 1, 2)

        def rotate_tokens(x):
            """``_rotate``'s lines on [b, t, h, dh]: the tables
            broadcast over the heads, the first ``rd`` features turn."""
            n = rd or dh
            cos, sin = rope.tables(t, n, THETA)
            xf = x[..., :n].astype(jnp.float32)
            swapped = jnp.concatenate([xf[..., n // 2:], xf[..., :n // 2]],
                                      -1)
            out = xf * cos[:, None, :] + swapped * sin[:, None, :]
            return jnp.concatenate([out.astype(x.dtype), x[..., n:]], -1)

        def x1(qkv):
            return tuple(_rotate(heads_first(z), THETA, rd, il)
                         for z in split(qkv))

        def x2(qkv):
            return tuple(heads_first(rotate_tokens(z)) for z in split(qkv))

        def kernel_a(tile, qkv):
            q, k = (heads_first(z) for z in split(qkv))
            return rope.rope_fwd(q, k, THETA, tile)

        def kernel_b(tile, qkv):
            return rope.rope_fwd(*split(qkv), THETA, tile, tokens=True)

        def kernel_a_bwd(tile, qkv, dq, dk):
            return joined(*(heads_first(z)
                            for z in rope.rope_bwd(dq, dk, THETA, tile)))

        def kernel_b_bwd(tile, qkv, dq, dk):
            return joined(*rope.rope_bwd(dq, dk, THETA, tile, tokens=True))

        def vjp_of(f):
            return lambda qkv, dq, dk: jax.vjp(f, qkv)[1]((dq, dk))[0]

        forms = {"X1": (x1, vjp_of(x1))}
        if not il:
            forms["X2"] = (x2, vjp_of(x2))
        tile = rope.rope_tile(b, t, h, dh, rd, il, jnp.bfloat16, hk=hk,
                              backend="tpu", on_mesh=False)
        if tile is not None:
            forms["A"] = (functools.partial(kernel_a, tile),
                          functools.partial(kernel_a_bwd, tile))
            blocks = [tile] + [
                (rows, {"h": h, "hk": hk}.get(n, n)) for rows, n in BLOCKS]
            for rows, n in dict.fromkeys(blocks):
                if t % rows == 0 and h % n == 0:
                    forms[f"B {rows}x{n}"] = (
                        functools.partial(kernel_b, (rows, n)),
                        functools.partial(kernel_b_bwd, (rows, n)))
            for rows in PASSES:
                forms[f"B {tile[0]}x{tile[1]} pass{rows}"] = forms[
                    f"B {tile[0]}x{tile[1]}"]
            forms["Bf"] = (functools.partial(kernel_b, tile), None)

        r = np.random.RandomState(7)
        shapes = [(b, t, sum(widths)), (b, h, t, dh), (b, hk, t, dh)]
        if args.lower:
            qkv, dq, dk = (jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                                sharding=sharding)
                           for s in shapes)
        else:
            qkv, dq, dk = (jnp.asarray(r.randn(*s), jnp.bfloat16)
                           for s in shapes)
        row = {"call": name, "shape": [b, t, h, hk, dh, rest, rd, il],
               "tile": tile and list(tile)}
        want = {}
        for form, (fwd, bwd) in forms.items():
            got = row[form] = {}
            for which, f, a in (("fwd", fwd, (qkv,)),
                                ("bwd", bwd, (qkv, dq, dk))):
                if f is None:
                    continue
                params, rows = pltpu.CompilerParams, rope._PASS_ROWS
                if " pass" in form:
                    rope._PASS_ROWS = int(form.split(" pass")[1])
                if form == "Bf":   # q and k, not the tables
                    pltpu.CompilerParams = functools.partial(
                        params, allow_input_fusion=[True, True, False, False])
                try:
                    # (a kernel call is one jitted function: a form that
                    # differs by a module's switch alone is traced anew)
                    jax.clear_caches()
                    c = jax.jit(f).lower(*a).compile()
                    if args.lower:
                        got[which] = {"gb_accessed": round(
                            c.cost_analysis()["bytes accessed"] / 1e9, 3)}
                        continue
                    out = c(*a)
                    out = out if which == "fwd" else (out,)
                    want.setdefault(which, out)
                    got[which] = {
                        "ms": ms(c, *a),
                        "worst_vs_X1": [round(worst(x, w), 5) for x, w
                                        in zip(out, want[which])]}
                except Exception as e:  # a form Mosaic refuses is a row
                    got[which] = {"error": str(e)[:400]}
                finally:
                    pltpu.CompilerParams, rope._PASS_ROWS = params, rows
            print(name, form, got, flush=True)
        table.append(row)
    if not args.lower:
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(table, f, indent=1)
    return 0


def norm_forms(lower, sharding, ms, worst):
    """N3 (norm, norm, rope: three ops) against NF (the one call with
    the gains), forward and backward, at ``NORM_CALL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import nn_ops
    from paddle_tpu.parallel import rope

    b, t, h, hk, dh, rest, periods, eps = NORM_CALL
    tile = rope.rope_tile(b, t, h, dh, None, False, jnp.bfloat16, hk=hk,
                          backend="tpu", on_mesh=False, periods=periods,
                          norm=True)
    kw = dict(tokens=True, periods=periods)

    def split(qkv):
        q, k = jnp.split(qkv, [h * dh, (h + hk) * dh], axis=-1)[:2]
        return q.reshape(b, t, h, dh), k.reshape(b, t, hk, dh)

    def joined(dq, dk):
        return jnp.concatenate([dq.reshape(b, t, -1), dk.reshape(b, t, -1),
                                jnp.zeros((b, t, rest), dq.dtype)], -1)

    def norm(x, gain):
        return nn_ops._rms_norm({"X": [x], "Scale": [gain]},
                                {"epsilon": eps})["Y"][0]

    def n3(qkv, sq, sk):
        q, k = split(qkv)
        return rope.rope_fwd(norm(q, sq), norm(k, sk), THETA, tile, **kw)

    def n3_bwd(qkv, sq, sk, dq, dk):
        q, k = split(qkv)
        dqn, dkn = rope.rope_bwd(dq, dk, THETA, tile, **kw)
        (dq, dsq), (dk, dsk) = (jax.vjp(norm, x, g)[1](d) for x, g, d in
                                ((q, sq, dqn), (k, sk, dkn)))
        return joined(dq, dk), dsq, dsk

    def nf(qkv, sq, sk):
        return rope.rope_fwd(*split(qkv), THETA, tile, gains=(sq, sk),
                             eps=eps, **kw)

    def nf_bwd(qkv, sq, sk, dq, dk):
        dq, dk, dsq, dsk = rope.rope_bwd(dq, dk, THETA, tile, gains=(sq, sk),
                                         eps=eps, x=split(qkv), **kw)
        return joined(dq, dk), dsq, dsk

    r = np.random.RandomState(7)
    shapes = [(b, t, (h + hk) * dh + rest), (dh,), (dh,), (b, h, t, dh),
              (b, hk, t, dh)]
    dtypes = [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16,
              jnp.bfloat16]
    if lower:
        ins = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
               for s, d in zip(shapes, dtypes)]
    else:
        ins = [jnp.asarray(r.randn(*s) * (0.2 if len(s) == 1 else 1.0)
                           + (2.0 if len(s) == 1 else 0.0), d)
               for s, d in zip(shapes, dtypes)]
    row, want = {"call": list(NORM_CALL), "tile": list(tile)}, {}
    for form, fwd, bwd in (("N3", n3, n3_bwd), ("NF", nf, nf_bwd)):
        got = row[form] = {}
        for which, f, a in (("fwd", fwd, ins[:3]), ("bwd", bwd, ins)):
            c = jax.jit(f).lower(*a).compile()
            if lower:
                got[which] = {"gb_accessed": round(
                    c.cost_analysis()["bytes accessed"] / 1e9, 3)}
                continue
            out = c(*a)
            want.setdefault(which, out)
            got[which] = {"ms": ms(c, *a),
                          "worst_vs_N3": [round(worst(x, w), 5) for x, w
                                          in zip(out, want[which])]}
        print("sdar", form, got, flush=True)
    if not lower:
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT.replace(".json", "_norm.json"), "w") as f:
            json.dump(row, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
