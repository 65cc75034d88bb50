"""Time the forms a grid step of ``attn.bhtd.fwd`` can take, alone on the
chip, at the decoder cells' calls (bf16), the forward's twin of
benchmarks/attn_bwd_candidates.py.

    chiprun -- python benchmarks/attn_fwd_candidates.py \
        [--parent .parent] [--calls keye_sel ...] [--forms hb1 rows2 ...]

A step of the parent's forward is ONE head's chain (q k^T, row max, exp,
row sum, p v) over one 512 x 512 block, and every query head of a group
fetches the group's K and V block again. The forms, each ONE process's
trace of this tree's ``flash_attention_fwd`` with the step's body or the
tile function swapped while it is traced:

- ``hb1``: one head a step, the parent's form (``--parent DIR``: the
  parent checkout's own module, a ``git archive`` of the parent commit;
  without it this tree at ``_FWD_HEADS`` = 1, which lowers the same
  step). Every other form's ``Out`` and ``Lse`` are held to it BIT FOR
  BIT.
- ``program``: the tree as it stands (``bhtd_fwd_tile``'s tile and
  ``_fwd_kernel``'s step: ``chains2``; ``hb1`` for an odd group).
- ``chains<N>``: N query heads a step written out as N chains one
  behind the other (all of head a, then all of head b); the heads of
  one key head's group read ONE fetched block of K, V (and of a
  selection's words); a key head a query head, N blocks of K and V.
- ``chainsu<N>``: as ``chains<N>``, a selection's words unpacked once a
  step where ``chains<N>`` unpacks them for each head again.
- ``mxu<N>``: the chains in the order q_a k^T, q_b k^T, softmax a, p_a v,
  softmax b, p_b v (the MXU's work of b beside the vector work of a).
- ``rows<N>``: the N heads ONE batched chain, every operation over
  [N, bq, bk] at once; where the heads share K and V their rows go
  through ONE product ([N bq, dh] by the block: ``heads_dot``).
- ``batched<N>``: as ``rows<N>``, the shared K, V block broadcast along
  the heads and every product batched a head.
- ``each2``: two heads a step that fetch a K and a V block EACH through
  a second pair of specs (an odd group: 28 heads on 4), as ``chains2``.
- ``strips<N>``: one head a step, its q block as N row strips in an
  unrolled loop (rows are independent in every operation of the chain).

A form a call does not admit (an odd group under ``rows2``, a block mask
under ``strips``) is "-". Per form: ms a call (the median of five
stretches of 10 calls dispatched back to back), us a live block a head
(``bhtd_pairs``' computed pairs / (bq bk)), seconds to lower and compile.
A call timed alone reads up to 1.7x its ms inside a cell's step: rank
forms by this table, price them by the cell. The table goes to
chiprun_out/attn_fwd_candidates.json and .md. Needs a TPU (``--calls
tiny`` rehearses every form through the interpreter here). PERF.md
section 6, PR 74, has the table this wrote and which form was kept.
"""

import argparse
import contextlib
import functools
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "attn_fwd_candidates")
# call: b, heads, key/value heads, t, dh, dv, then what is not plain
# causal: window, block_diffusion, r (QPe | KPe's width, ONE key head),
# sel (a packed selection of about that many keys a query)
CALLS = {
    "keye_sel": dict(b=1, h=32, hk=4, t=16384, dh=128, sel=2048),
    "smallthinker_global": dict(b=1, h=28, hk=4, t=16384, dh=128),
    "laguna_full": dict(b=1, h=48, hk=8, t=8192, dh=128),
    "laguna_w512": dict(b=1, h=64, hk=8, t=8192, dh=128, window=512),
    "sdar_bd": dict(b=1, h=32, hk=4, t=8192, dh=128, bd=4),
    "joyai_parts": dict(b=1, h=32, hk=32, t=4096, dh=128, r=64),
    "lfm2moe_d64": dict(b=1, h=32, hk=8, t=8192, dh=64),
    "qwen3next_d256": dict(b=1, h=16, hk=2, t=8192, dh=256),
    "olmoe": dict(b=2, h=16, hk=16, t=4096, dh=128),
    # (the interpreter's: every variant at blocks of 128)
    "tiny": dict(b=1, h=4, hk=2, t=512, dh=128, sel=96, block=128),
    "tiny_g3": dict(b=1, h=6, hk=2, t=512, dh=128, window=200, block=128),
    "tiny_bd": dict(b=1, h=4, hk=2, t=512, dh=128, bd=32, block=128),
    "tiny_parts": dict(b=1, h=4, hk=4, t=512, dh=128, r=64, block=128),
}
FORMS = ("hb1", "program", "chains2", "mxu2", "rows2", "batched2", "each2",
         "strips4", "chains4")


def load_parent(path):
    """The parent checkout's flash_attention module, beside this tree's."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "parent_flash_attention",
        os.path.join(path, "paddle_tpu", "parallel", "flash_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_around(fa, step):
    """``fa._fwd_kernel``'s frame (the walk, the statistics' start, the
    live / plain / edge branches, the finish in rows) around another
    body: ``step(c, masked)`` adds one block to the running statistics,
    ``c`` the step's refs and geometry. k_ref and v_ref may be tuples of
    one-head refs, a head of the step each (``each2``)."""
    import types

    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
               m_scr, l_scr, acc_scr, *, scale, nk, ng, p_drop,
               causal=False, window=None, bd=None, pe_refs=None, live=None,
               chain=None):
        assert p_drop == 0.0
        j, r = pl.program_id(2), pl.program_id(3)
        hq, bq = q_ref.shape[1:3]
        bk = (k_ref[0] if isinstance(k_ref, tuple) else k_ref).shape[2]
        if bd is not None:
            kk, bd_live, bd_edge = fa._bd_k_step(j, r, bq, bd)
        else:
            kk = r if window is None else fa._first_k(j, bq, bk, window) + r
        c = types.SimpleNamespace(
            q=q_ref, k=k_ref, v=v_ref, bias=bias_ref, pe=pe_refs, m=m_scr,
            l=l_scr, acc=acc_scr, scale=scale, j=j, kk=kk, hq=hq, bq=bq,
            bk=bk, window=window, bd=bd)

        @pl.when(r == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, fa._NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        def _compute(masked=False):
            step(c, masked)

        if bd is not None:
            fa._when_live(_compute, bd_live, j, kk, bq, bk, None,
                          edge=bd_edge)
        elif causal:
            is_live = fa._causal_live(j, kk, bq, bk)
            if live is not None:
                is_live = jnp.logical_and(
                    is_live, fa._chosen_live(seed_ref, live, j, kk, kk))
            fa._when_live(_compute, is_live, j, kk, bq, bk, window)
        else:
            _compute()

        @pl.when(r == nk - 1)
        def _finish():
            l = l_scr[:, :, :1]
            o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
            lse = m_scr[:] + jnp.log(l_scr[:])
            for i in range(hq):
                lse_ref[0, i] = lse[i].T[:1]

    return kernel


def online_softmax(fa, c, own, s, v, dot):
    """Scores ``s`` of the statistics' rows ``own`` into m, l and acc:
    the chain's second half, ``dot(p, v)`` its product."""
    import jax.numpy as jnp

    m_prev, l_prev = c.m[own], c.l[own]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - fa._lanes(m_new, c.bk))
    corr = jnp.exp(m_prev - m_new)
    c.l[own] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    c.acc[own] = (c.acc[own] * fa._lanes(corr, c.acc.shape[2])
                  + dot(p.astype(v.dtype), v))
    c.m[own] = m_new


def loop_kernel(fa, order, strips=1, unpack_once=False):
    """The step's heads (and a head's row strips) written out one by
    one: ``order`` "chain" (a head's whole chain, then the next head's)
    or "mxu" (every head's scores first). A selection's words are
    unpacked for each head again, or ``unpack_once`` a step."""
    import jax
    import jax.numpy as jnp

    def of(ref, i):     # head i's block of K, V or KPe
        if isinstance(ref, tuple):
            return ref[i][0, 0]
        return ref[0, min(i, ref.shape[1] - 1)]

    def step(c, masked):
        n = c.bq // strips
        nt = (((1,), (1,)), ((), ()))
        whole = None
        if unpack_once and c.bias is not None:
            assert strips == 1
            whole = fa._unpacked(c.bias[0])

        def scores(i, q0):
            rows = slice(q0, q0 + n)
            s = jax.lax.dot_general(
                c.q[0, i, rows], of(c.k, i), nt,
                preferred_element_type=jnp.float32)
            if c.pe is not None:
                s = s + jax.lax.dot_general(
                    c.pe[0][0, i, rows], of(c.pe[1], i), nt,
                    preferred_element_type=jnp.float32)
            s = s * c.scale
            if c.bias is not None:
                s = fa._biased(s, fa._unpacked(c.bias[0], q0, n)
                               if whole is None else whole)
            if masked and c.bd is not None:
                assert strips == 1
                s = fa._bd_mask(s, c.j, c.kk, c.bq, c.bd)
            elif masked:
                s = fa._causal_mask(s[None], c.j, c.kk, c.bq, c.bk,
                                    window=c.window, at=(q0, 0))[0]
            return s

        def rest(i, q0, s):
            online_softmax(
                fa, c, (i, slice(q0, q0 + n)), s, of(c.v, i),
                lambda p, v: jnp.dot(p, v,
                                     preferred_element_type=jnp.float32))

        parts = [(i, a * n) for i in range(c.hq) for a in range(strips)]
        if order == "mxu":
            for part, s in zip(parts, [scores(*part) for part in parts]):
                rest(*part, s)
        else:
            for part in parts:
                rest(*part, scores(*part))

    return kernel_around(fa, step)


def heads_dot(x, y, axis, rows):
    """x [hq, m, c] by y [hk, .., ..] -> [hq, m, n] float32, x's last
    axis against y's ``axis``. hk == hq: a product a head, batched.
    hk == 1 under several heads (their group's K or V, the rotary key
    head): ``rows``, ONE product of all heads' rows against it; else
    the shared head broadcast along the heads and a batched product."""
    import jax
    import jax.numpy as jnp

    hq, m, c = x.shape
    if y.shape[0] != hq and rows:
        out = jax.lax.dot_general(
            x.reshape(hq * m, c), y[0], (((1,), (axis - 1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return out.reshape(hq, m, out.shape[1])
    if y.shape[0] != hq:
        y = jnp.broadcast_to(y, x.shape[:1] + y.shape[1:])
    return jax.lax.dot_general(x, y, (((2,), (axis,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def batched_kernel(fa, rows):
    """The step's heads ONE batched chain: every operation over
    [hq, bq, bk] at once (``heads_dot``)."""
    def step(c, masked):
        s = heads_dot(c.q[0], c.k[0], 2, rows)
        if c.pe is not None:
            s = s + heads_dot(c.pe[0][0], c.pe[1][0], 2, rows)
        s = s * c.scale
        if c.bias is not None:
            s = fa._biased(s, fa._unpacked(c.bias[0]))
        if masked and c.bd is not None:
            s = fa._bd_mask(s, c.j, c.kk, c.bq, c.bd)
        elif masked:
            s = fa._causal_mask(s, c.j, c.kk, c.bq, c.bk, window=c.window)
        online_softmax(fa, c, slice(None), s, c.v[0],
                       lambda p, v: heads_dot(p, v, 1, rows))

    return kernel_around(fa, step)


def each_call_parts(fa, kernel, at, tile, q, k, v, bias, pe=None):
    """``fa._call_parts`` for ``each2``: the step's two query heads read
    a K and a V block EACH (head 2 g + m of the call, its own key head)
    through two pairs of specs."""
    assert bias is None and pe is None
    hq, bq, bk = tile
    group = q.shape[1] // k.shape[1]
    rows = fa._row_specs(at, hq, bq, bk, q.shape[3], 1, v.shape[3])
    specs, args = [rows.q], [q]
    for m in range(hq):
        def at_m(*ids, m=m):
            i, g, j, kk = at(*ids)
            return i, g * hq + m, j, kk
        own = fa._row_specs(at_m, 1, bq, bk, q.shape[3], group, v.shape[3])
        specs += [own.k, own.v]
        args += [k, v]

    def body(seed, q_ref, *refs, **kw):
        kv, rest = refs[:2 * hq], refs[2 * hq:]
        return kernel(seed, q_ref, kv[0::2], kv[1::2], None, *rest, **kw)
    return body, specs, args, rows


@contextlib.contextmanager
def form_setup(form, fa, parent, call):
    """The module to trace ``form`` from, this tree's patched for the
    while; None where the call does not admit the form."""
    if form == "hb1" and parent is not None:
        yield parent
        return
    if form == "program":
        yield fa
        return
    kind, n = re.fullmatch(r"([a-z]+)(\d*)", form).groups()
    n = int(n or 1)
    group = call["h"] // call["hk"]
    heads = 1 if kind in ("hb", "strips") else n
    admitted = (call["h"] % heads == 0
                and (kind == "each" or group == 1 or group % heads == 0)
                and not (kind == "strips" and call.get("bd"))
                and not (kind == "each" and (call.get("sel")
                                             or call.get("r"))))
    if not admitted:
        yield None
        return
    held = {name: getattr(fa, name) for name in (
        "_FWD_HEADS", "_FWD_VMEM_CAP_BYTES", "_fwd_kernel", "_call_parts",
        "bhtd_fwd_tile")}
    fa._FWD_HEADS, fa._FWD_VMEM_CAP_BYTES = heads, 96 * 2**20
    if kind in ("rows", "batched"):
        fa._fwd_kernel = batched_kernel(fa, kind == "rows")
    elif kind in ("chains", "chainsu", "mxu", "each"):
        fa._fwd_kernel = loop_kernel(
            fa, "mxu" if kind == "mxu" else "chain",
            unpack_once=kind == "chainsu")
    elif kind == "strips":
        fa._fwd_kernel = loop_kernel(fa, "chain", strips=n)
    if kind == "each":
        fa._call_parts = functools.partial(each_call_parts, fa)
        tile_of = held["bhtd_fwd_tile"]

        def two_heads(*a, group=1, **kw):    # (as a key head a query head)
            return tile_of(*a, group=1, **kw)
        fa.bhtd_fwd_tile = two_heads
    try:
        yield fa
    finally:
        for name, was in held.items():
            setattr(fa, name, was)


def operands(call, seed=7):
    """-> (args, kw) of ``flash_attention_fwd`` for a call of the table."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel import dsa_score

    b, h, hk, t, dh = (call[x] for x in ("b", "h", "hk", "t", "dh"))
    dv, r = call.get("dv", dh), np.random.RandomState(seed)

    def rand(*shape, s=1.0):
        return jnp.asarray(r.randn(*shape) * s, jnp.bfloat16)

    block = call.get("block")
    kw = dict(causal=not call.get("bd"), window=call.get("window"),
              block_diffusion=call.get("bd"), q_block=block, k_block=block)
    args = dict(q=rand(b, h, t, dh, s=0.5), k=rand(b, hk, t, dh, s=0.5),
                v=rand(b, hk, t, dv))
    if call.get("r"):
        args.update(q_pe=rand(b, h, t, call["r"], s=0.5),
                    k_pe=rand(b, 1, t, call["r"], s=0.5))
    if call.get("sel"):
        # about ``sel`` keys of a query's causal prefix, a bit a pair;
        # a block nobody chose from in the live table
        cq = block or 512
        pos = jnp.arange(t)
        u = jax.random.uniform(jax.random.PRNGKey(seed), (t, t))
        chosen = (u * (pos[:, None] + 1) < call["sel"]) & (
            pos[None, :] <= pos[:, None])
        chosen = chosen.at[2 * cq:3 * cq, :cq].set(False)
        blocks = chosen.reshape(t // cq, cq, t // cq, cq)
        args.update(
            selected=dsa_score.pack_rows(chosen.reshape(
                t // cq, cq, t)).reshape(1, t // 32, t),
            live=blocks.any((1, 3)).astype(jnp.int32)[None])
    return args, kw


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", nargs="*",
                    default=[c for c in CALLS if not c.startswith("tiny")])
    ap.add_argument("--forms", nargs="*", default=list(FORMS))
    ap.add_argument("--parent", default=None)
    args = ap.parse_args()

    import jax
    import numpy as np

    from paddle_tpu.parallel import flash_attention as fa
    tiny = all(c.startswith("tiny") for c in args.calls)
    if not tiny and jax.default_backend() != "tpu":
        print("attn_fwd_candidates: no TPU", file=sys.stderr)
        return 2
    parent = load_parent(args.parent) if args.parent else None
    if tiny:
        # (a short row's heads onto the grid, as a long row's are: one
        # head's K and V blocks of 128 rows fit the cap, two do not)
        for mod in filter(None, (fa, parent)):
            mod._INTERPRET, mod._KV_VMEM_BYTES = True, 12 * 128 * 320
        fa._FWD_PAIR_BLOCK = 128    # (two heads a step at blocks of 128)

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

    table = []
    for name in args.calls:
        call = CALLS[name]
        ops, kw = operands(call)
        names, t = list(ops), call["t"]
        row = {"call": name, "shape": {k: v for k, v in call.items()}}
        want = None
        for form in args.forms:
            with form_setup(form, fa, parent, call) as mod:
                if mod is None:
                    row[form] = cell = {}
                    print(name, form, "-", flush=True)
                    continue
                try:
                    tile_of = getattr(mod, "bhtd_fwd_tile", mod.bhtd_tile)
                    tile = tile_of(
                        call["h"], t, t, kw["q_block"], kw["k_block"],
                        dh=call["dh"] + call.get("r", 0),
                        group=call["h"] // call["hk"],
                        dv=call.get("dv", call["dh"]),
                        block_diffusion=call.get("bd"),
                        **({} if mod is parent else dict(
                            pe_group=call.get("r") and call["h"],
                            selected=bool(call.get("sel")))))
                    computed, _ = mod.bhtd_pairs(
                        t, t, tile, kw["causal"], kw["window"], None,
                        kw["block_diffusion"])
                    blocks = (call["b"] * call["h"] * computed
                              / (tile[1] * tile[2]))
                    cell = {"tile": mod.tile_label(tile)}
                    t0 = time.perf_counter()
                    fwd = jax.jit(lambda *a: mod.flash_attention_fwd(
                        **dict(zip(names, a)), **kw)).lower(
                            *ops.values()).compile()
                    cell["compile_s"] = round(time.perf_counter() - t0, 2)
                    got = fwd(*ops.values())
                    if want is None:
                        want = got
                    cell["same_bits"] = all(
                        np.array_equal(np.asarray(a, np.float32),
                                       np.asarray(w, np.float32))
                        for a, w in zip(got, want))
                    if not tiny:
                        cell["fwd_ms"] = ms(fwd, *ops.values())
                        cell["us_a_block"] = round(
                            cell["fwd_ms"] * 1e3 / blocks, 4)
                except Exception as e:  # a form Mosaic refuses is a row
                    cell = {"error": str(e)[:400]}
            row[form] = cell
            print(name, form, cell, flush=True)
        table.append(row)

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT + ".json", "w") as f:
        json.dump(table, f, indent=1)
    with open(OUT + ".md", "w") as f:
        f.write("| call | " + " | ".join(
            f"{x} ms (us a block)" for x in args.forms) + " |\n")
        f.write("| --- |" + " --- |" * len(args.forms) + "\n")
        for row in table:
            cells = ["%s (%s)%s" % (
                row[x].get("fwd_ms", "-"), row[x].get("us_a_block", "-"),
                "" if row[x].get("same_bits", True) else " BITS DIFFER")
                for x in args.forms]
            f.write(f"| {row['call']} | " + " | ".join(cells) + " |\n")
    return 0 if all(row[x].get("same_bits", True) and "error" not in row[x]
                    for row in table for x in args.forms) else 1


if __name__ == "__main__":
    sys.exit(main())
